"""Attention ops: fused flash attention (Pallas), ring attention (sequence
parallel over a mesh axis), and paged decode attention (the serving
engine's ragged KV-cache path).

No counterpart exists in the reference — it has no attention op at all
(SURVEY.md §2.3: transformers enter only via ONNX import) — but long-context
is first-class here. Layout is (batch, heads, seq, head_dim) throughout.

Three tiers, same math:
  1. `attention_reference`  — jnp, O(S^2) memory; ground truth for tests.
  2. `flash_attention`      — Pallas online-softmax kernel, O(S) memory,
                              custom_vjp with blockwise recompute backward.
  3. `ring_attention`       — flash over sequence shards on a mesh axis;
                              K/V blocks rotate via lax.ppermute so each
                              ICI hop overlaps with the local block matmul
                              (the jax-native form of the RDMA ring pattern
                              in /opt/skills/guides/pallas_guide.md §18).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..observe import record_attention_dispatch

_NEG_INF = -1e30


def _causal_mask(sq, sk, q_off=0, k_off=0, dtype=jnp.float32):
    q_pos = q_off + lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
    k_pos = k_off + lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
    return jnp.where(k_pos > q_pos, _NEG_INF, 0.0).astype(dtype)


# ======================= 1. reference ====================================

def attention_reference(q, k, v, causal=False, scale=None):
    """q,k,v: (B, H, S, D). Returns (B, H, Sq, D)."""
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        s = s + _causal_mask(q.shape[2], k.shape[2], dtype=s.dtype)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


# ======================= 2. flash attention ==============================
# Online-softmax over K blocks; the kernel keeps one (Bq, D) accumulator,
# running row-max m and row-sum l in VMEM scratch. Backward recomputes
# blockwise (no S matrix ever materialized).

# Measured on v5e (fp32, differential timing): at S=4096, 128x128 tiles
# run 30.6 ms vs 4.3 ms at 1024x1024 — per-grid-step overhead dominates
# small tiles, and a (1024,64) tile is still only 256 KB of VMEM. At
# S<=512 inside a full model, 256 beats 512 (~8%) — VMEM pressure against
# the surrounding fused ops. None = pick by sequence length.
DEFAULT_BLOCK_Q = None
DEFAULT_BLOCK_K = None


def _default_block(s):
    import os
    env = os.environ.get("SINGA_FLASH_BLOCK")
    if env:
        return int(env)
    return 1024 if s >= 1024 else 256


def _fit_block(s, target, floor=128):
    """Largest block <= target that tiles s evenly on 8-sublane alignment.
    None when nothing >= `floor` divides s (caller falls back to the XLA
    reference path) — tiles below ~128 are per-grid-step-overhead bound
    and run far slower than the O(S^2) XLA path."""
    b = min(target, s)
    b -= b % 8
    floor = min(floor, s)
    while b >= floor:
        if s % b == 0:
            return b
        b -= 8
    return None


try:  # import here so CPU-only environments still import the module
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    _HAS_PALLAS = True
except ImportError:  # pragma: no cover
    _HAS_PALLAS = False


# TPU Pallas needs the last two block dims (sublane, lane) aligned; scalar
# per-row stats (lse, delta, running m/l) are carried as (rows, _STAT_LANES)
# with the value replicated across lanes — rows on sublanes means reading
# [:, :1] yields the column vector with no relayout.
_STAT_LANES = 8


def _maybe_when(cond, fn):
    """pl.when for traced predicates; plain call for static True."""
    if cond is True:
        fn()
    else:
        pl.when(cond)(fn)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      acc_ref, m_ref, l_ref, *scratch,
                      nk, block_q, block_k, causal, hoist_mask=False):
    """Grid: (batch*heads, q_blocks, k_blocks) — K/V blocks STREAM through
    VMEM one (block_k, D) tile at a time (no whole-row residency, so
    sequence length is bounded by HBM, not VMEM). The online-softmax state
    (acc, m, l) lives in VMEM scratch, which persists across the k grid
    dimension. CONTRACT: the grid must stay FULLY sequential (no
    dimension_semantics 'parallel' on any dim) — hoist_mask initializes
    its scratch at program_id(0) == 0 and every later bh step reads it,
    so a parallelized bh dimension would read uninitialized VMEM."""
    qi = pl.program_id(1)
    kb = pl.program_id(2)

    # hoist_mask (static; only when nq == nk == 1, e.g. S <= 1024 at the
    # default block): the causal mask is identical for every grid step,
    # so it is built ONCE into a persistent VMEM scratch instead of
    # paying iota+compare+select on the full score tile per step
    if hoist_mask:
        mask_ref = scratch[0]          # bf16: -1e30 is representable
        # (8-bit exponent), and halves the persistent VMEM cost

        @pl.when(pl.program_id(0) == 0)
        def _mask_init():
            mask_ref[...] = _causal_mask(block_q, block_k,
                                         dtype=mask_ref.dtype)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: skip K blocks strictly above the diagonal of this Q block.
    # COMPUTE is gated here; the DMA for those blocks is skipped too —
    # _causal_clamp maps their BlockSpec index to the diagonal block, and
    # Pallas TPU elides the copy when the block index doesn't change
    # between grid steps.
    needed = (kb * block_k <= qi * block_q + block_q - 1) if causal else True

    def _update():
        # dots run in the INPUT dtype (bf16 inputs → native MXU rate;
        # upcasting to f32 first would run the matmul at the ~4x-slower
        # fp32 rate) and accumulate f32 via preferred_element_type; the
        # softmax/stats stay in f32. q arrives PRE-SCALED (the wrapper
        # folds the softmax scale into q, where XLA fuses it for free —
        # an in-kernel multiply would cost a VPU pass over the full
        # score tile every grid step).
        q = q_ref[0]                                   # (Bq, D), scaled
        k_blk = k_ref[0]                               # (Bk, D)
        v_blk = v_ref[0]
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        if hoist_mask:
            s = s + mask_ref[...]
        elif causal:
            s = s + _causal_mask(block_q, block_k, q_off=qi * block_q,
                                 k_off=kb * block_k)
        m_prev = m_ref[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[...][:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, (block_q, _STAT_LANES))
        l_ref[...] = jnp.broadcast_to(l_new, (block_q, _STAT_LANES))

    _maybe_when(needed, _update)

    @pl.when(kb == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...][:, :1], 1e-20)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(m_ref[...][:, :1] + jnp.log(l),
                                      (block_q, _STAT_LANES))


def _causal_kv_map(causal, block_q, block_k, nk):
    """K/V BlockSpec index map for grids with kb innermost after the q
    block index. Causal: kb is CLAMPED to this q block's diagonal block,
    so every fully-masked step re-addresses the last needed block and
    Pallas skips the DMA (the copy only fires when the block index
    changes) — masked K/V tiles are neither computed nor streamed."""
    if not causal:
        return lambda i, j, kb: (i, kb, 0)

    def kmap(i, j, kb):
        last = jnp.minimum(((j + 1) * block_q - 1) // block_k, nk - 1)
        return (i, jnp.minimum(kb, last), 0)

    return kmap


def _causal_q_map(causal, block_q, block_k):
    """Q-side BlockSpec index map for the dK/dV grid (bh, kb, j): causal
    clamps j UP to the first unmasked q block for kb, so the leading
    masked steps address the same tile and their DMA is elided."""
    if not causal:
        return lambda i, kb, j: (i, j, 0)

    def qmap(i, kb, j):
        first = (kb * block_k) // block_q
        return (i, jnp.maximum(j, first), 0)

    return qmap


def _flash_fwd_pallas(q, k, v, causal, scale, block_q, block_k, interpret):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    # fold the softmax scale into q here: XLA fuses the multiply into
    # whatever produced q, so the kernel never spends a VPU pass on it
    qf = (q * scale).astype(q.dtype).reshape(bh, sq, d)
    kf = k.reshape(bh, sk, d)
    vf = v.reshape(bh, sk, d)
    nk = sk // block_k
    nq = sq // block_q
    grid = (bh, nq, nk)
    # single-tile causal grids reuse one mask every step; cap the
    # persistent scratch at 2MB so an env-forced giant block can't eat
    # the VMEM budget the streamed tiles need
    hoist = (causal and nq == 1 and nk == 1
             and block_q * block_k * 2 <= 2 * 1024 * 1024)
    kernel = functools.partial(
        _flash_fwd_kernel, nk=nk, block_q=block_q, block_k=block_k,
        causal=causal, hoist_mask=hoist)
    kvmap = _causal_kv_map(causal, block_q, block_k, nk)
    out, lse = pl.pallas_call(
        kernel,
        name="singa_flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), kvmap),
            pl.BlockSpec((1, block_k, d), kvmap),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, block_q, _STAT_LANES),
                         lambda i, j, kb: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, _STAT_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
        ] + ([pltpu.VMEM((block_q, block_k), jnp.bfloat16)]
             if hoist else []),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d), lse[:, :, 0].reshape(b, h, sq)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_acc, *, nk, block_q, block_k, causal,
                         scale):
    """Grid (bh, q_blocks, k_blocks): accumulate dQ over streamed K/V."""
    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    needed = (kb * block_k <= qi * block_q + block_q - 1) if causal else True

    def _update():
        # native-dtype MXU dots (see fwd kernel); ds is rounded to the
        # input dtype for its matmul, standard flash-2 practice. q
        # arrives PRE-SCALED, so s matches the forward's lse directly;
        # the true dL/dq = scale * ds @ k is applied at _finish.
        q = q_ref[0]
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        do = do_ref[0]
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        if causal:
            s = s + _causal_mask(block_q, block_k, q_off=qi * block_q,
                                 k_off=kb * block_k)
        p = jnp.exp(s - lse_ref[0][:, :1])
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1])
        dq_acc[...] += jnp.dot(ds.astype(k_blk.dtype), k_blk,
                               preferred_element_type=jnp.float32)

    _maybe_when(needed, _update)

    @pl.when(kb == nk - 1)
    def _finish():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, nq, block_q,
                          block_k, causal):
    """Grid (bh, k_blocks, q_blocks): accumulate dK/dV over streamed Q."""
    kb = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    needed = (qi * block_q + block_q - 1 >= kb * block_k) if causal else True

    def _update():
        # native-dtype MXU dots; p/ds rounded to the input dtype for
        # their matmuls (flash-2 practice). q arrives PRE-SCALED, so
        # dk = ds.T @ q_scaled IS the true scale * ds.T @ q — no extra
        # multiply anywhere.
        q = q_ref[0]
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        do = do_ref[0]
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        if causal:
            s = s + _causal_mask(block_q, block_k, q_off=qi * block_q,
                                 k_off=kb * block_k)
        p = jnp.exp(s - lse_ref[0][:, :1])                 # (Bq, Bk)
        dv_acc[...] += jnp.dot(p.astype(do.dtype).T, do,
                               preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1])
        dk_acc[...] += jnp.dot(ds.astype(q.dtype).T, q,
                               preferred_element_type=jnp.float32)

    _maybe_when(needed, _update)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                            delta_ref, dq_ref, dk_ref, dv_ref,
                            dq_acc, dk_acc, dv_acc, *, nq, nk, block_q,
                            block_k, causal, scale):
    """Single-pass backward: grid (bh, k_blocks, q_blocks) computes
    s/p/ds ONCE per tile pair and emits all three gradients — the split
    dq/dkv pair recomputes the two largest matmuls (s and dp) and the
    exp, and streams every q/k/v/do tile twice. dQ accumulates in a
    persistent (Sq, D) VMEM scratch (TPU grid iteration is sequential,
    so the scratch survives the whole (nk, nq) sweep of one bh row);
    callers gate this kernel on that scratch fitting VMEM."""
    kb = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when((kb == 0) & (j == 0))
    def _init_dq():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(j == 0)
    def _init_dkv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    needed = (j * block_q + block_q - 1 >= kb * block_k) if causal \
        else True
    # the q-side window this step addresses (mirrors _causal_q_map's
    # clamp) — masked steps re-address the first needed block so their
    # unconditional dq store writes that block's current partial
    if causal:
        eff_j = jnp.maximum(j, (kb * block_k) // block_q)
    else:
        eff_j = j
    rows = pl.dslice(eff_j * block_q, block_q)

    def _update():
        q = q_ref[0]                  # pre-scaled (see fwd kernel)
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        do = do_ref[0]
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        if causal:
            s = s + _causal_mask(block_q, block_k, q_off=j * block_q,
                                 k_off=kb * block_k)
        p = jnp.exp(s - lse_ref[0][:, :1])                 # (Bq, Bk)
        dv_acc[...] += jnp.dot(p.astype(do.dtype).T, do,
                               preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1])
        dk_acc[...] += jnp.dot(ds.astype(q.dtype).T, q,
                               preferred_element_type=jnp.float32)
        dq_acc[rows, :] += jnp.dot(ds.astype(k_blk.dtype), k_blk,
                                   preferred_element_type=jnp.float32)

    _maybe_when(needed, _update)

    # dq: store the addressed window's partial every step — its LAST
    # flush for window j happens at this row's diagonal block (causal;
    # kb = nk-1 otherwise), where the accumulation is complete
    dq_ref[0] = (dq_acc[rows, :] * scale).astype(dq_ref.dtype)

    @pl.when(j == nq - 1)
    def _finish_dkv():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# dq scratch cap for the fused backward: (Sq, D) f32 must fit scoped
# VMEM alongside the streamed tiles (~16 MB total) — 4 MB covers
# S=8192 at D=128; longer sequences fall back to the split kernels.
_FUSED_DQ_BYTES_CAP = 4 * 1024 * 1024


def _flash_bwd_fused(qf, kf, vf, dof, lsef, delta, causal, scale,
                     block_q, block_k, interpret, shapes):
    b, h, sq, sk, d = shapes
    bh = b * h
    nq, nk = sq // block_q, sk // block_k
    kvmap_kq = lambda i, kb, j: (i, kb, 0)
    qmap = _causal_q_map(causal, block_q, block_k)
    stat_spec = pl.BlockSpec((1, block_q, _STAT_LANES), qmap)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_fused_kernel, nq=nq, nk=nk,
                          block_q=block_q, block_k=block_k,
                          causal=causal, scale=scale),
        name="singa_flash_bwd",
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), qmap),
            pl.BlockSpec((1, block_k, d), kvmap_kq),
            pl.BlockSpec((1, block_k, d), kvmap_kq),
            pl.BlockSpec((1, block_q, d), qmap),
            stat_spec,
            stat_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), qmap),
            pl.BlockSpec((1, block_k, d), kvmap_kq),
            pl.BlockSpec((1, block_k, d), kvmap_kq),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), qf.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), kf.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), vf.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((sq, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, delta)
    return dq, dk, dv


def _flash_bwd_stats(o, lse, do):
    """(lsef, delta) lane-broadcast stat tensors for the backward kernels;
    loop-invariant across ring hops, so callers may precompute once."""
    b, h, sq, _ = o.shape
    bh = b * h
    stat = (bh, sq, _STAT_LANES)
    lsef = jnp.broadcast_to(lse.reshape(bh, sq)[:, :, None], stat)
    # delta = rowsum(do * o): cheap elementwise, leave to XLA fusion
    delta = jnp.broadcast_to(
        jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                axis=-1).reshape(bh, sq)[:, :, None], stat)
    return lsef, delta


def _flash_bwd_pallas(q, k, v, o, lse, do, causal, scale, block_q, block_k,
                      interpret, stats=None):
    """Pallas flash backward: dQ and dK/dV kernels with streamed tiles."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    # q pre-scaled, as in the forward (kernels consume scaled q; dq gets
    # its own scale factor at _finish, dk inherits it from q itself)
    qf = (q * scale).astype(q.dtype).reshape(bh, sq, d)
    kf, vf = (a.reshape(bh, -1, d) for a in (k, v))
    dof = do.reshape(bh, sq, d)
    lsef, delta = stats if stats is not None else _flash_bwd_stats(o, lse,
                                                                   do)
    if sq * d * 4 <= _FUSED_DQ_BYTES_CAP:
        dq, dk, dv = _flash_bwd_fused(
            qf, kf, vf, dof, lsef, delta, causal, scale, block_q,
            block_k, interpret, (b, h, sq, sk, d))
        return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
                dv.reshape(b, h, sk, d))
    nq, nk = sq // block_q, sk // block_k
    kvmap = _causal_kv_map(causal, block_q, block_k, nk)
    qmap = _causal_q_map(causal, block_q, block_k)
    stat_spec_q = pl.BlockSpec((1, block_q, _STAT_LANES),
                               lambda i, j, kb: (i, j, 0))
    stat_spec_kq = pl.BlockSpec((1, block_q, _STAT_LANES), qmap)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, nk=nk, block_q=block_q,
                          block_k=block_k, causal=causal, scale=scale),
        name="singa_flash_bwd_dq",
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), kvmap),
            pl.BlockSpec((1, block_k, d), kvmap),
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
            stat_spec_q,
            stat_spec_q,
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, nq=nq, block_q=block_q,
                          block_k=block_k, causal=causal),
        name="singa_flash_bwd_dkv",
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), qmap),
            pl.BlockSpec((1, block_k, d), lambda i, kb, j: (i, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, kb, j: (i, kb, 0)),
            pl.BlockSpec((1, block_q, d), qmap),
            stat_spec_kq,
            stat_spec_kq,
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, kb, j: (i, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, kb, j: (i, kb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, delta)
    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


def _flash_bwd_blockwise(q, k, v, o, lse, do, causal, scale, block_k):
    """Recompute-based backward, scanned over K blocks (O(S) memory)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    qs = q.astype(jnp.float32) * scale
    do_ = do.astype(jnp.float32)
    # delta = rowsum(do * o)  (standard flash-2 backward term)
    delta = jnp.sum(do_ * o.astype(jnp.float32), axis=-1)  # (B,H,Sq)

    nkb = sk // block_k
    kb_idx = jnp.arange(nkb)

    def per_kblock(kb):
        k_blk = lax.dynamic_slice_in_dim(k, kb * block_k, block_k, axis=2)
        v_blk = lax.dynamic_slice_in_dim(v, kb * block_k, block_k, axis=2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qs, k_blk.astype(jnp.float32))
        if causal:
            s = s + _causal_mask(sq, block_k, 0, kb * block_k)[None, None]
        p = jnp.exp(s - lse[..., None])                    # (B,H,Sq,Bk)
        dv = jnp.einsum("bhqk,bhqd->bhkd", p, do_)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do_, v_blk.astype(jnp.float32))
        ds = p * (dp - delta[..., None])
        dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qs) * 1.0
        dq_part = jnp.einsum("bhqk,bhkd->bhqd", ds,
                             k_blk.astype(jnp.float32))
        return dq_part, dk, dv

    def scan_body(dq_acc, kb):
        dq_part, dk, dv = per_kblock(kb)
        return dq_acc + dq_part, (dk, dv)

    dq, (dks, dvs) = lax.scan(scan_body,
                              jnp.zeros(q.shape, jnp.float32), kb_idx)
    dk = jnp.moveaxis(dks, 0, 2).reshape(b, h, sk, d)
    dv = jnp.moveaxis(dvs, 0, 2).reshape(b, h, sk, d)
    return (dq * scale).astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=None):
    """Fused attention; q,k,v (B,H,S,D). Falls back to the reference path
    when shapes don't tile (S % block != 0) or Pallas is unavailable."""
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                        interpret)
    return out


def _resolve(scale, d, interpret):
    scale = scale if scale is not None else d ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return scale, interpret


def _kernel_path(interpret):
    return "interpret" if interpret else "kernel"


def _resolve_blocks(sq, sk, block_q, block_k):
    """(bq, bk, ok): pick tiles that divide the sequence on 8-sublane
    alignment (TPU lowering constraint). None selects the largest evenly-
    tiling block at or below the measured per-sequence-length default
    (so S=384 runs the kernel at 192 instead of falling back); an EXPLICIT
    block that doesn't tile keeps the old contract: ok=False -> reference
    path."""
    if block_q is None:
        bq = _fit_block(sq, _default_block(sq))
    else:
        bq = min(block_q, sq)
        bq = bq if (sq % bq == 0 and bq % 8 == 0) else None
    if block_k is None:
        bk = _fit_block(sk, _default_block(sk))
    else:
        bk = min(block_k, sk)
        bk = bk if (sk % bk == 0 and bk % 8 == 0) else None
    ok = bq is not None and bk is not None
    return (bq or 0), (bk or 0), ok


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    d = q.shape[-1]
    scale, interpret = _resolve(scale, d, interpret)
    sq, sk = q.shape[2], k.shape[2]
    bq, bk, ok = _resolve_blocks(sq, sk, block_q, block_k)
    if not _HAS_PALLAS or not ok:
        record_attention_dispatch("flash_fwd", "reference")
        return attention_reference(q, k, v, causal, scale), None
    record_attention_dispatch("flash_fwd", _kernel_path(interpret))
    out, lse = _flash_fwd_pallas(q, k, v, causal, scale, bq, bk, interpret)
    return out, lse


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                          interpret)
    if lse is None:  # fallback path: vjp of the reference impl
        d = q.shape[-1]
        s, _ = _resolve(scale, d, interpret)
        _, ref_vjp = jax.vjp(
            lambda q_, k_, v_: attention_reference(q_, k_, v_, causal, s),
            q, k, v)
        return out, (None, ref_vjp)
    return out, ((q, k, v, out, lse), None)


def _flash_vjp_bwd(causal, scale, block_q, block_k, interpret, res, g):
    saved, ref_vjp = res
    if saved is None:
        record_attention_dispatch("flash_bwd", "reference")
        return ref_vjp(g)
    q, k, v, out, lse = saved
    d = q.shape[-1]
    s, interp = _resolve(scale, d, interpret)
    sq, sk = q.shape[2], k.shape[2]
    # backward kernels hold ~3x the tiles of forward (q/k/v/do + two
    # accumulators); 1024-blocks overflow the 16MB scoped VMEM, so cap the
    # target at 512 and fit to a dividing block (a capped explicit block
    # may stop tiling evenly — e.g. 768 -> 512 with S=768 — so refit
    # rather than crash the blockwise fallback on a non-divisor)
    bq = _fit_block(sq, min(block_q or _default_block(sq), 512))
    bk = _fit_block(sk, min(block_k or _default_block(sk), 512))
    if _HAS_PALLAS and bq and bk:
        record_attention_dispatch("flash_bwd", _kernel_path(interp))
        return _flash_bwd_pallas(q, k, v, out, lse, g, causal, s, bq, bk,
                                 interp)
    record_attention_dispatch("flash_bwd", "reference")
    return _flash_bwd_blockwise(q, k, v, out, lse, g, causal, s,
                                _fit_block(sk, 512) or sk)


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ======================= 3. ring attention ===============================
#
# Two implementations, same math:
#   _ring_jnp    — einsum per hop (O(S_local^2) scores materialized);
#                  ground truth, and fallback when shards don't tile.
#   _ring_flash  — the Pallas flash kernel per hop + lse merge, with a
#                  second ring for the backward: kernel speed and O(block)
#                  memory on the long-context path itself. Per hop the
#                  K/V shard's origin decides the mask: src < my -> fully
#                  visible, src == my -> the causal diagonal, src > my ->
#                  skipped (zero contribution).
# `ring_attention` dispatches between them.


def _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale, bq, bk, interp):
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    B, H, S, D = q.shape
    f32 = jnp.float32

    def hop(k_cur, v_cur, src):
        def full(_):
            o, l = _flash_fwd_pallas(q, k_cur, v_cur, False, scale, bq, bk,
                                     interp)
            return o.astype(f32), l

        def diag(_):
            o, l = _flash_fwd_pallas(q, k_cur, v_cur, True, scale, bq, bk,
                                     interp)
            return o.astype(f32), l

        def skip(_):
            return (jnp.zeros((B, H, S, D), f32),
                    jnp.full((B, H, S), _NEG_INF, f32))

        if not causal:
            return full(None)
        idx = jnp.where(src < my, 0, jnp.where(src == my, 1, 2))
        return lax.switch(idx, (full, diag, skip), None)

    def step(carry, step_i):
        m, z, num, k_cur, v_cur = carry
        src = (my - step_i) % n
        o_i, lse_i = hop(k_cur, v_cur, src)
        m_new = jnp.maximum(m, lse_i)
        corr = jnp.exp(m - m_new)
        w = jnp.exp(lse_i - m_new)
        z = z * corr + w
        num = num * corr[..., None] + w[..., None] * o_i
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (m_new, z, num, k_nxt, v_nxt), None

    init = (jnp.full((B, H, S), _NEG_INF, f32),
            jnp.zeros((B, H, S), f32),
            jnp.zeros((B, H, S, D), f32), k, v)
    (m, z, num, _, _), _ = lax.scan(step, init, jnp.arange(n))
    z = jnp.maximum(z, 1e-20)
    out = (num / z[..., None]).astype(q.dtype)
    lse = m + jnp.log(z)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring_flash(q, k, v, axis_name, causal, scale, bq, bk, interp):
    out, _ = _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale, bq,
                                  bk, interp)
    return out


def _ring_flash_vjp_fwd(q, k, v, axis_name, causal, scale, bq, bk, interp):
    out, lse = _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale, bq,
                                    bk, interp)
    return out, (q, k, v, out, lse)


def _ring_flash_vjp_bwd(axis_name, causal, scale, bq, bk, interp, res, g):
    q, k, v, out, lse = res
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    f32 = jnp.float32
    # backward tiles capped at 512 for VMEM, same as single-shard flash
    sq, sk = q.shape[2], k.shape[2]
    bqb = _fit_block(sq, min(bq, 512))
    bkb = _fit_block(sk, min(bk, 512))

    stats = _flash_bwd_stats(out, lse, g)  # loop-invariant across hops

    def hop(k_cur, v_cur, src):
        def run(causal_flag):
            def f(_):
                dq, dk, dv = _flash_bwd_pallas(q, k_cur, v_cur, out, lse,
                                               g, causal_flag, scale, bqb,
                                               bkb, interp, stats=stats)
                return dq.astype(f32), dk.astype(f32), dv.astype(f32)
            return f

        def skip(_):
            return (jnp.zeros(q.shape, f32), jnp.zeros(k.shape, f32),
                    jnp.zeros(v.shape, f32))

        if not causal:
            return run(False)(None)
        idx = jnp.where(src < my, 0, jnp.where(src == my, 1, 2))
        return lax.switch(idx, (run(False), run(True), skip), None)

    def step(carry, step_i):
        dq_acc, k_cur, v_cur, dk_cur, dv_cur = carry
        src = (my - step_i) % n
        dq_i, dk_i, dv_i = hop(k_cur, v_cur, src)
        dq_acc = dq_acc + dq_i
        # dk/dv accumulate onto the rotating shard so that after n hops
        # every contribution has ridden the ring home with its shard
        dk_cur = dk_cur + dk_i
        dv_cur = dv_cur + dv_i
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        dk_nxt = lax.ppermute(dk_cur, axis_name, perm)
        dv_nxt = lax.ppermute(dv_cur, axis_name, perm)
        return (dq_acc, k_nxt, v_nxt, dk_nxt, dv_nxt), None

    init = (jnp.zeros(q.shape, f32), k, v,
            jnp.zeros(k.shape, f32), jnp.zeros(v.shape, f32))
    (dq, _, _, dk, dv), _ = lax.scan(step, init, jnp.arange(n))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def ring_attention(q, k, v, axis_name: str, causal=False, scale=None):
    """Sequence-parallel attention INSIDE shard_map: q/k/v hold this
    device's sequence shard (B,H,S_local,D); the axis is the 'sp' mesh
    dimension. K/V shards rotate around the ring with lax.ppermute while
    each device accumulates online-softmax partials — peak memory is one
    shard, total traffic (n-1) shard-hops over ICI, and XLA overlaps each
    hop with the local block's matmuls.

    When the local shard tiles for the Pallas kernel, each hop runs the
    flash kernel (O(block) score memory, kernel speed); otherwise the
    jnp einsum path below is the fallback.
    """
    d = q.shape[-1]
    sq, sk = q.shape[2], k.shape[2]
    resolved_scale = scale if scale is not None else d ** -0.5
    bq = _fit_block(sq, _default_block(sq))
    bk = _fit_block(sk, _default_block(sk))
    # the backward ring has no blockwise fallback, so its capped tiles
    # must fit as well (e.g. S_local=2032: fwd fits 1016 but nothing in
    # [128,512] divides it)
    bwd_ok = _fit_block(sq, min(bq or 0, 512)) and \
        _fit_block(sk, min(bk or 0, 512))
    if _HAS_PALLAS and bq and bk and bwd_ok:
        _, interp = _resolve(resolved_scale, d, None)
        return _ring_flash(q, k, v, axis_name, causal, resolved_scale,
                           bq, bk, interp)
    return _ring_jnp(q, k, v, axis_name, causal, scale)


def _ring_jnp(q, k, v, axis_name: str, causal=False, scale=None):
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    d = q.shape[-1]
    s_local = q.shape[2]
    scale = scale if scale is not None else d ** -0.5
    perm = [(i, (i + 1) % n) for i in range(n)]

    qs = q.astype(jnp.float32) * scale
    m = jnp.full(q.shape[:3] + (1,), _NEG_INF, jnp.float32)
    l = jnp.zeros(q.shape[:3] + (1,), jnp.float32)
    acc = jnp.zeros(qs.shape, jnp.float32)

    def step(carry, step_i):
        m, l, acc, k_cur, v_cur = carry
        src = (my - step_i) % n  # which global shard k_cur came from
        s = jnp.einsum("bhqd,bhkd->bhqk", qs, k_cur.astype(jnp.float32))
        if causal:
            s = s + _causal_mask(s_local, s_local, my * s_local,
                                 src * s_local)[None, None]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_cur.astype(jnp.float32))
        # rotate K/V to the next device (no-op cost on the last step's
        # result; XLA prunes the final unused permute's consumer)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (m_new, l_new, acc_new, k_nxt, v_nxt), None

    (m, l, acc, _, _), _ = lax.scan(step, (m, l, acc, k, v), jnp.arange(n))
    # fully-masked rows (causal, early shards) have l == 0; guard division
    l = jnp.maximum(l, 1e-20)
    return (acc / l).astype(q.dtype)


# ======================= 4. int4 nibble packing ==========================
#
# int4 KV quantization packs TWO 4-bit values per byte along the lane
# (feature) dimension, split-half layout: byte j of a packed row holds
# lane j in its LOW nibble and lane j + L/2 in its HIGH nibble, so the
# unpack is a concat of two sign-extended halves — no strided interleave,
# and a per-token cache-row write stays a contiguous byte-aligned slice
# (packing along the token dim would force read-modify-write of bytes
# shared between positions). Values are symmetric int4 in [-7, 7] with
# the same per-(head, position) scale layout the int8 path uses (scale
# basis max|kv| / 7 instead of / 127) — the scale algebra downstream is
# IDENTICAL, only the byte stream halves again.

def nibble_pack(q):
    """(..., L) int values in [-8, 7] -> (..., L/2) uint8, split-half
    layout (low nibble = lane j, high nibble = lane j + L/2)."""
    L = q.shape[-1]
    assert L % 2 == 0, f"nibble_pack needs an even last dim, got {L}"
    u = q.astype(jnp.uint8)
    lo = u[..., : L // 2] & 0xF
    hi = u[..., L // 2:] & 0xF
    return (hi << 4) | lo


def nibble_unpack(p, dtype=jnp.float32):
    """(..., L/2) uint8 -> (..., L) `dtype`, inverting nibble_pack.
    Arithmetic runs in int32 (sign extension via the 0x8 test) so the
    same expression lowers in Pallas/Mosaic and under plain XLA."""
    x = p.astype(jnp.int32)
    lo = x & 0xF
    hi = (x >> 4) & 0xF
    lo = lo - ((lo & 0x8) << 1)
    hi = hi - ((hi & 0x8) << 1)
    return jnp.concatenate([lo, hi], axis=-1).astype(dtype)


def _kv_dequant(blk, qdtype):
    """Pool/cache block -> matmul operand in the query dtype: int4
    (uint8 packed) unpacks nibbles, int8 casts, float passes through."""
    if blk.dtype == jnp.uint8:
        return nibble_unpack(blk, qdtype)
    if blk.dtype == jnp.int8:
        return blk.astype(qdtype)
    return blk


# ======================= 5. paged decode attention =======================
#
# The serving engine's ragged decode path (singa_tpu.engine): each active
# sequence owns a host-assigned list of fixed-size KV-cache PAGES in a
# shared pool, so a 32-token request stops reserving max-length HBM. The
# attention here is the decode-side flash pattern — one packed query row
# block per sequence, online softmax over its pages — with the page
# table driving WHICH pool rows stream through VMEM (vLLM/PagedAttention
# moved to Pallas scalar prefetch: the BlockSpec index map reads the
# prefetched page table, so only the sequence's own pages are DMA'd).
#
# Two tiers, same math, mirroring flash_attention:
#   paged_attention_reference — gather + masked softmax in jnp; ground
#       truth, and the dispatch default off-TPU (a decode step is tiny;
#       unrolling an interpret-mode grid into every scan step is not).
#   _paged_fwd_pallas — PrefetchScalarGridSpec kernel, grid
#       (seqs, packed-kv-heads, pages): K/V pages stream one at a time,
#       pages at or beyond a sequence's length are neither computed nor
#       DMA'd (the index map clamps to the last needed page, so the
#       block index doesn't change and Pallas elides the copy).
#
# Layout matches the serving cache convention: queries arrive HEAD-PACKED
# block-diagonal (N, Hp, Q, P*D) with Q = P*G rows (serving.py builds
# them via _DecodeCore._pack_q), pools are (n_pages, Hp, page_size, P*D).
# int8 KV is preserved: per-(head, position) scale pools ride along and
# fold into scores/weights exactly as the dense token_step does.

def _paged_factors(sc, groups, rows, q_tokens=1):
    """(T?, P) per-position scales -> (rows, T?) row factors for packed
    block-diagonal queries: row q = c*groups + g reads lane block c.
    With `q_tokens` > 1 (the speculative verify step) the row layout is
    (q_tokens, P, groups) — every token's P*G block reads the same
    per-position factors, so the block is tiled along the row dim.
    Rows beyond q_tokens*P*groups (query padding) get factor 1."""
    f = jnp.repeat(sc.swapaxes(-1, -2), groups, axis=-2)  # (P*G, T)
    if q_tokens > 1:
        f = jnp.concatenate([f] * q_tokens, axis=-2)
    pg = sc.shape[-1] * groups * q_tokens
    if rows > pg:
        pad = jnp.ones(f.shape[:-2] + (rows - pg, f.shape[-1]), f.dtype)
        f = jnp.concatenate([f, pad], axis=-2)
    return f


def _row_limits(lengths, Q, rows_per_token, q_tokens):
    """(N,) final lengths -> (N, Q) per-query-row KV limits. Query rows
    are laid out (q_tokens, P, G): token ti's rows attend positions
    < lengths - (q_tokens - 1 - ti) — the causal ladder of the
    multi-token verify step. q_tokens == 1 is the plain decode case
    (every row sees `lengths` positions). Padding rows (>= q_tokens *
    rows_per_token) inherit the LAST token's limit (outputs
    discarded)."""
    ti = jnp.minimum(jnp.arange(Q) // rows_per_token, q_tokens - 1)
    return lengths[:, None] - (q_tokens - 1 - ti)[None, :]


def paged_attention_reference(q, k_pool, v_pool, page_table, lengths,
                              page_size, scale=1.0, k_scales=None,
                              v_scales=None, groups=1, q_tokens=1):
    """Ground-truth paged decode attention.

    q:          (N, Hp, Q, PD) packed block-diagonal queries
                (Q = q_tokens * P * G; q_tokens > 1 is the speculative
                verify step — token ti's rows attend q_tokens-1-ti
                fewer positions, the causal ladder)
    k_pool/v_pool: (n_pages, Hp, page_size, PD) shared page pools
                (int8 when k_scales/v_scales are given; packed uint8
                (n_pages, Hp, page_size, PD/2) for int4 KV)
    page_table: (N, M) int32 — page ids per sequence, row-major in time
    lengths:    (N,) int32 — valid KV positions per sequence (>= 1),
                counted at the LAST query token under q_tokens > 1
    k_scales/v_scales: (n_pages, Hp, page_size, P) fp32 (quantized KV)

    Returns (N, Hp, Q, PD). The math is the dense token_step's masked
    softmax over the gathered pages — gathers materialize a copy, which
    is why the TPU path streams pages in the kernel instead."""
    N, Hp, Q, PD = q.shape
    M = page_table.shape[1]
    T = M * page_size

    def gather(pool):
        g = pool[page_table]                   # (N, M, Hp, ps, PD/P)
        g = jnp.moveaxis(g, 2, 1)              # (N, Hp, M, ps, ·)
        return g.reshape(N, Hp, T, g.shape[-1])

    kf = _kv_dequant(gather(k_pool), q.dtype)
    vf = _kv_dequant(gather(v_pool), q.dtype)
    s = jnp.einsum("nhqd,nhtd->nhqt", q, kf) * scale
    if k_scales is not None:
        s = s * _paged_factors(gather(k_scales), groups, Q, q_tokens)
    limits = _row_limits(lengths, Q, Q // max(q_tokens, 1), q_tokens)
    valid = (lax.broadcasted_iota(jnp.int32, (1, 1, 1, T), 3)
             < limits[:, None, :, None])
    a = jax.nn.softmax(jnp.where(valid, s, -jnp.inf), axis=-1)
    if v_scales is not None:
        a = a * _paged_factors(gather(v_scales), groups, Q, q_tokens)
    return jnp.einsum("nhqt,nhtd->nhqd", a.astype(q.dtype),
                      vf).astype(q.dtype)


def _paged_fwd_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                      nM, page_size, groups, kvq, q_tokens,
                      rows_per_token):
    """Grid (N, Hp, pages): stream one sequence's pages through VMEM and
    run the online softmax. Pages past the sequence length are gated
    (compute) and their DMA elided (index map re-addresses the last
    needed page). int8 K/V cast in-kernel; int4 K/V arrive as packed
    uint8 (ps, PD/2) blocks and UNPACK in-kernel (nibble_unpack in
    int32 arithmetic) — the HBM stream is the packed bytes, the MXU
    sees the query dtype. With q_tokens > 1 (speculative verify) query
    rows are laid out (q_tokens, P, G) and token ti's rows mask
    positions >= len - (q_tokens-1-ti): the causal ladder. CONTRACT:
    fully sequential grid — the scratch state persists across the page
    dimension."""
    if kvq:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
        ks_ref = vs_ref = None
    n = pl.program_id(0)
    pg = pl.program_id(2)

    @pl.when(pg == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ln = len_ref[n]
    needed = pg * page_size < ln

    def _update():
        # q arrives PRE-SCALED (the wrapper folds the softmax scale in,
        # like flash); quantized K/V dequant in-kernel to the query
        # dtype for native MXU dots, scales fold in exactly as the
        # dense quantized token_step does
        q = q_ref[0, 0]                         # (Qp, PD)
        k_blk = _kv_dequant(k_ref[0, 0], q.dtype)   # (ps, PD)
        v_blk = _kv_dequant(v_ref[0, 0], q.dtype)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        if kvq:
            s = s * _paged_factors(ks_ref[0, 0], groups, s.shape[0],
                                   q_tokens)
        pos = pg * page_size + lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        if q_tokens > 1:
            ti = jnp.minimum(
                lax.broadcasted_iota(jnp.int32, (s.shape[0], 1), 0)
                // rows_per_token, q_tokens - 1)
            s = jnp.where(pos < ln - (q_tokens - 1 - ti), s, _NEG_INF)
        else:
            s = jnp.where(pos < ln, s, _NEG_INF)
        m_prev = m_ref[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[...][:, :1] * corr \
            + jnp.sum(p, axis=-1, keepdims=True)
        if kvq:
            p = p * _paged_factors(vs_ref[0, 0], groups, p.shape[0],
                                   q_tokens)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    pl.when(needed)(_update)

    @pl.when(pg == nM - 1)
    def _finish():
        l = jnp.maximum(l_ref[...][:, :1], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_fwd_pallas(q, k_pool, v_pool, page_table, lengths, page_size,
                      scale, k_scales, v_scales, groups, interpret,
                      q_tokens=1):
    N, Hp, Q, PD = q.shape
    M = page_table.shape[1]
    ps = page_size
    kvq = None
    if k_scales is not None:
        kvq = "int4" if k_pool.dtype == jnp.uint8 else "int8"
    PDk = k_pool.shape[-1]          # PD, or PD/2 for packed int4
    # pad query rows to the 8-sublane alignment; extra rows are zeros
    # (their softmax output is garbage over a zero query — discarded)
    Qp = max(8, Q + (-Q) % 8)
    qf = (q * scale).astype(q.dtype)
    if Qp != Q:
        qf = jnp.concatenate(
            [qf, jnp.zeros((N, Hp, Qp - Q, PD), qf.dtype)], axis=2)
    lengths = jnp.maximum(lengths.astype(jnp.int32), 1)
    pt = page_table.astype(jnp.int32)

    def page_map(n, hp, pg, pt_ref, len_ref):
        # clamp to the last needed page: fully-masked steps re-address
        # it, so their DMA is elided (the block index doesn't change)
        last = jnp.minimum((len_ref[n] - 1) // ps, M - 1)
        return (pt_ref[n, jnp.minimum(pg, last)], hp, 0, 0)

    def q_map(n, hp, pg, pt_ref, len_ref):
        return (n, hp, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, Qp, PD), q_map),
        pl.BlockSpec((1, 1, ps, PDk), page_map),
        pl.BlockSpec((1, 1, ps, PDk), page_map),
    ]
    operands = [qf, k_pool, v_pool]
    if kvq:
        in_specs += [pl.BlockSpec((1, 1, ps, k_scales.shape[-1]),
                                  page_map),
                     pl.BlockSpec((1, 1, ps, v_scales.shape[-1]),
                                  page_map)]
        operands += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N, Hp, M),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, Qp, PD), q_map),
        scratch_shapes=[
            pltpu.VMEM((Qp, PD), jnp.float32),
            pltpu.VMEM((Qp, _STAT_LANES), jnp.float32),
            pltpu.VMEM((Qp, _STAT_LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_fwd_kernel, nM=M, page_size=ps,
                          groups=groups, kvq=kvq, q_tokens=q_tokens,
                          rows_per_token=Q // max(q_tokens, 1)),
        name="singa_paged_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, Hp, Qp, PD), q.dtype),
        interpret=interpret,
    )(pt, lengths, *operands)
    return out[:, :, :Q, :]


def paged_attention(q, k_pool, v_pool, page_table, lengths, page_size,
                    scale=1.0, k_scales=None, v_scales=None, groups=1,
                    use_kernel=None, q_tokens=1):
    """Paged decode attention: dispatch between the Pallas page-streaming
    kernel and the gather-based reference (see paged_attention_reference
    for shapes — int8 and packed-int4 pools dequantize in-kernel;
    q_tokens > 1 runs the speculative verify's causal ladder over
    (q_tokens, P, G)-laid-out query rows). `use_kernel=None` picks the
    kernel only on a real TPU backend — off-TPU the kernel would run in
    interpret mode, unrolling the whole (N, Hp, pages) grid into every
    traced decode step; `use_kernel=True` forces it (interpret off-TPU,
    how the agreement test exercises the kernel path), False forces the
    reference. On a TPU an unaligned shape takes the reference whatever
    `use_kernel` says — always so for int4 KV at PD=128, whose packed
    rows are 64 lanes wide. The path taken is counted per call site
    (observe.record_attention_dispatch)."""
    N, Hp, Q, PD = q.shape
    ps = int(page_size)
    on_tpu = jax.default_backend() == "tpu"
    # lane/sublane alignment gates only the COMPILED path; interpret
    # mode (the off-TPU agreement tests, incl. int4's PD/2-lane packed
    # pools at small test dims) has no tiling constraint
    aligned = (ps % 8 == 0 and PD % 128 == 0
               and k_pool.shape[-1] % 128 == 0)
    if use_kernel is None:
        use_kernel = on_tpu and aligned
    if not use_kernel or not _HAS_PALLAS or (on_tpu and not aligned):
        record_attention_dispatch("paged", "reference")
        return paged_attention_reference(
            q, k_pool, v_pool, page_table, lengths, ps, scale,
            k_scales, v_scales, groups, q_tokens)
    interpret = not on_tpu
    record_attention_dispatch("paged", _kernel_path(interpret))
    return _paged_fwd_pallas(q, k_pool, v_pool, page_table, lengths, ps,
                             scale, k_scales, v_scales, groups, interpret,
                             q_tokens)


# ======================= 6. dense flash-decode ===========================
#
# The dense serving path's decode attention (serving._DecodeCore
# token_step / verify_step): one packed block-diagonal query row block
# per sequence against a CONTIGUOUS (N, Hp, T, PD) head-packed cache,
# masked to each sequence's live length. Same two-tier contract as
# paged_attention — `flash_decode_reference` is the jnp ground truth
# (and the off-TPU dispatch default; a decode step is tiny, an
# interpret-mode grid unrolled into every scan step is not), the Pallas
# kernel streams T blocks through VMEM with the online softmax, masked
# blocks' DMA elided via a scalar-prefetched length clamp. Quantized
# caches (int8, packed-nibble int4) dequantize IN-KERNEL: HBM streams
# the quantized bytes — the whole point of the quantization — and the
# MXU sees the query dtype. q_tokens > 1 runs the speculative verify
# ladder (token ti's rows attend q_tokens-1-ti fewer positions).

def flash_decode_reference(q, K, V, lengths, scale=1.0, k_scales=None,
                           v_scales=None, groups=1, q_tokens=1):
    """Ground-truth dense decode attention.

    q:        (N, Hp, Q, PD) packed block-diagonal queries
              (Q = q_tokens * P * G)
    K/V:      (N, Hp, T, PD) head-packed caches (float or int8), or
              packed uint8 (N, Hp, T, PD/2) for int4 KV
    lengths:  (N,) int32 — live positions per sequence, counted at the
              LAST query token under q_tokens > 1
    k_scales/v_scales: (N, Hp, T, P) fp32 (quantized KV only)

    Returns (N, Hp, Q, PD) — the dense token_step's masked softmax
    with the quantization-scale folding of the int8/int4 cache modes."""
    N, Hp, Q, PD = q.shape
    T = K.shape[2]
    kf = _kv_dequant(K, q.dtype)
    vf = _kv_dequant(V, q.dtype)
    s = jnp.einsum("nhqd,nhtd->nhqt", q, kf) * scale
    if k_scales is not None:
        s = s * _paged_factors(k_scales, groups, Q, q_tokens)
    limits = _row_limits(lengths, Q, Q // max(q_tokens, 1), q_tokens)
    valid = (lax.broadcasted_iota(jnp.int32, (1, 1, 1, T), 3)
             < limits[:, None, :, None])
    a = jax.nn.softmax(jnp.where(valid, s, -jnp.inf), axis=-1)
    if v_scales is not None:
        a = a * _paged_factors(v_scales, groups, Q, q_tokens)
    return jnp.einsum("nhqt,nhtd->nhqd", a.astype(q.dtype),
                      vf).astype(q.dtype)


def _flash_decode_kernel(len_ref, q_ref, k_ref, v_ref, *rest,
                         nT, block_t, groups, kvq, q_tokens,
                         rows_per_token):
    """Grid (N, Hp, t_blocks): stream one sequence's cache blocks
    through VMEM with the online softmax; blocks past the live length
    are gated (compute) and their DMA elided (index map clamps to the
    last needed block). Same contract as the paged kernel: fully
    sequential grid, scratch persists across the t dimension."""
    if kvq:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
        ks_ref = vs_ref = None
    n = pl.program_id(0)
    tb = pl.program_id(2)

    @pl.when(tb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ln = len_ref[n]
    needed = tb * block_t < ln

    def _update():
        q = q_ref[0, 0]                              # (Qp, PD), scaled
        k_blk = _kv_dequant(k_ref[0, 0], q.dtype)    # (bt, PD)
        v_blk = _kv_dequant(v_ref[0, 0], q.dtype)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        if kvq:
            s = s * _paged_factors(ks_ref[0, 0], groups, s.shape[0],
                                   q_tokens)
        pos = tb * block_t + lax.broadcasted_iota(
            jnp.int32, (1, block_t), 1)
        if q_tokens > 1:
            ti = jnp.minimum(
                lax.broadcasted_iota(jnp.int32, (s.shape[0], 1), 0)
                // rows_per_token, q_tokens - 1)
            s = jnp.where(pos < ln - (q_tokens - 1 - ti), s, _NEG_INF)
        else:
            s = jnp.where(pos < ln, s, _NEG_INF)
        m_prev = m_ref[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[...][:, :1] * corr \
            + jnp.sum(p, axis=-1, keepdims=True)
        if kvq:
            p = p * _paged_factors(vs_ref[0, 0], groups, p.shape[0],
                                   q_tokens)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    pl.when(needed)(_update)

    @pl.when(tb == nT - 1)
    def _finish():
        l = jnp.maximum(l_ref[...][:, :1], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _flash_decode_pallas(q, K, V, lengths, scale, k_scales, v_scales,
                         groups, interpret, q_tokens, block_t):
    N, Hp, Q, PD = q.shape
    T = K.shape[2]
    bt = block_t
    nT = T // bt
    kvq = None
    if k_scales is not None:
        kvq = "int4" if K.dtype == jnp.uint8 else "int8"
    PDk = K.shape[-1]
    Qp = max(8, Q + (-Q) % 8)
    qf = (q * scale).astype(q.dtype)
    if Qp != Q:
        qf = jnp.concatenate(
            [qf, jnp.zeros((N, Hp, Qp - Q, PD), qf.dtype)], axis=2)
    lengths = jnp.maximum(lengths.astype(jnp.int32), 1)

    def t_map(n, hp, tb, len_ref):
        # clamp to the last needed block so masked steps' DMA elides
        last = jnp.minimum((len_ref[n] - 1) // bt, nT - 1)
        return (n, hp, jnp.minimum(tb, last), 0)

    def q_map(n, hp, tb, len_ref):
        return (n, hp, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, Qp, PD), q_map),
        pl.BlockSpec((1, 1, bt, PDk), t_map),
        pl.BlockSpec((1, 1, bt, PDk), t_map),
    ]
    operands = [qf, K, V]
    if kvq:
        in_specs += [pl.BlockSpec((1, 1, bt, k_scales.shape[-1]), t_map),
                     pl.BlockSpec((1, 1, bt, v_scales.shape[-1]), t_map)]
        operands += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N, Hp, nT),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, Qp, PD), q_map),
        scratch_shapes=[
            pltpu.VMEM((Qp, PD), jnp.float32),
            pltpu.VMEM((Qp, _STAT_LANES), jnp.float32),
            pltpu.VMEM((Qp, _STAT_LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_flash_decode_kernel, nT=nT, block_t=bt,
                          groups=groups, kvq=kvq, q_tokens=q_tokens,
                          rows_per_token=Q // max(q_tokens, 1)),
        name="singa_flash_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, Hp, Qp, PD), q.dtype),
        interpret=interpret,
    )(lengths, *operands)
    return out[:, :, :Q, :]


def flash_decode(q, K, V, lengths, scale=1.0, k_scales=None,
                 v_scales=None, groups=1, use_kernel=None, q_tokens=1,
                 block_t=None):
    """Dense decode attention: dispatch between the Pallas
    block-streaming kernel and the jnp reference (see
    flash_decode_reference for shapes). `use_kernel=None` picks the
    kernel only on a real TPU backend with tiling alignment;
    `use_kernel=True` forces it (interpret off-TPU — the agreement
    tests), False forces the reference."""
    N, Hp, Q, PD = q.shape
    T = K.shape[2]
    on_tpu = jax.default_backend() == "tpu"
    bt = block_t if block_t is not None else _fit_block(
        T, min(256, T), floor=8)
    aligned = (bt is not None and PD % 128 == 0
               and K.shape[-1] % 128 == 0 and bt % 8 == 0)
    if use_kernel is None:
        use_kernel = on_tpu and aligned
    if not use_kernel or not _HAS_PALLAS or bt is None \
            or (on_tpu and not aligned):
        record_attention_dispatch("flash_decode", "reference")
        return flash_decode_reference(q, K, V, lengths, scale, k_scales,
                                      v_scales, groups, q_tokens)
    record_attention_dispatch("flash_decode", _kernel_path(not on_tpu))
    return _flash_decode_pallas(q, K, V, lengths, scale, k_scales,
                                v_scales, groups, not on_tpu, q_tokens,
                                bt)


def ring_attention_sharded(q, k, v, mesh, axis_name="sp", causal=False):
    """Convenience wrapper: shard (B,H,S,D) arrays over `axis_name` on the
    seq dim and run ring_attention under shard_map."""
    from jax.sharding import PartitionSpec as P
    spec = P(None, None, axis_name, None)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    def run(q_, k_, v_):
        return ring_attention(q_, k_, v_, axis_name, causal)

    return run(q, k, v)
