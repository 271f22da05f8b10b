"""Mixture-of-Experts with expert parallelism over a mesh axis (no
reference counterpart — SINGA has no MoE; EP is first-class here).

Top-k routing with capacity (k=1 is the Switch Transformer, k=2 the
GShard/ST-MoE default): tokens pick k experts by gate probability and the
gates are renormalized over the chosen k; each expert accepts at most
`capacity` tokens per device (overflow tokens pass through that choice with
zero expert output, standard switch behavior — the dropped fraction is
surfaced in `stats`). A router z-loss (ST-MoE: mean squared logsumexp of
the router logits) is also returned so training can keep router logits
small. Under EP, experts are sharded over the 'ep' axis and token blocks
move with TWO lax.all_to_all hops (dispatch + return) — the all-to-all
rides ICI and XLA overlaps it with the expert matmuls.

`dropless_moe` is the other routing: no capacity and nothing dropped. The
(token, choice) pairs are sorted by expert and the experts run as grouped
matrix products over the rows really routed; a device may hold a share of
the experts and computes its part of the sum (no exchange across devices
yet: the capacity path above is the one that runs under `ep_axis`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def topk_gating(x, Wg, capacity: int, k: int = 1):
    """x: (T, D) tokens; Wg: (D, E). Returns (dispatch (T,E,C), combine
    (T,E,C), aux, z_loss, overflow):
      dispatch — one-hot token->(expert, slot) routing for kept choices
      combine  — dispatch weighted by the renormalized gate
      aux      — switch load-balance loss (E * sum frac_tokens*frac_probs,
                 first-choice assignment fractions)
      z_loss   — mean(logsumexp(logits)^2), the ST-MoE router z-loss
      overflow — fraction of (token, choice) routes dropped by capacity
    """
    T = x.shape[0]
    logits = jnp.dot(x, Wg)                               # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    E = probs.shape[-1]
    z = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    z_loss = jnp.mean(z * z)

    topv, topi = lax.top_k(probs, k)                      # (T, k)
    renorm = topv / jnp.sum(topv, axis=-1, keepdims=True)

    fill = jnp.zeros((E,), x.dtype)      # per-expert queue fill so far
    dispatch = jnp.zeros((T, E, capacity), x.dtype)
    combine = jnp.zeros((T, E, capacity), x.dtype)
    kept_total = jnp.zeros((), x.dtype)
    for j in range(k):
        mask = jax.nn.one_hot(topi[:, j], E, dtype=x.dtype)   # (T, E)
        # queue position = tokens already kept by earlier choices (fill)
        # + this choice's own running count
        pos = (jnp.cumsum(mask, axis=0) - 1.0) * mask + fill[None, :] * mask
        keep = mask * (pos < capacity).astype(x.dtype)
        pos_idx = jnp.sum(pos * keep, axis=-1).astype(jnp.int32)  # (T,)
        slot = jax.nn.one_hot(pos_idx, capacity, dtype=x.dtype)   # (T, C)
        d_j = keep[:, :, None] * slot[:, None, :]
        dispatch = dispatch + d_j
        combine = combine + d_j * renorm[:, j][:, None, None]
        fill = fill + jnp.sum(keep, axis=0)
        kept_total = kept_total + jnp.sum(keep)

    # load balance on FIRST-choice assignment (switch-transformer form)
    mask0 = jax.nn.one_hot(topi[:, 0], E, dtype=x.dtype)
    frac_tokens = jnp.mean(mask0, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)
    overflow = 1.0 - kept_total / (T * k)
    return dispatch, combine, aux, z_loss, overflow


def top1_gating(x, Wg, capacity: int):
    """Back-compat switch (k=1) gating: (dispatch, combine, aux)."""
    dispatch, combine, aux, _, _ = topk_gating(x, Wg, capacity, k=1)
    return dispatch, combine, aux


def _expert_ffn(blocks, W1, b1, W2, b2, act):
    """blocks: (E, C, D); per-expert two-layer FFN, batched over E."""
    h = act(jnp.einsum("ecd,edh->ech", blocks, W1) + b1[:, None, :])
    return jnp.einsum("ech,ehd->ecd", h, W2) + b2[:, None, :]


def moe_ffn(x, Wg, W1, b1, W2, b2, capacity_factor=1.25, act=None, k=1):
    """Single-device MoE: x (T, D); W1 (E, D, H); W2 (E, H, D).
    Returns (y, aux, stats) with stats = (z_loss, overflow)."""
    act = act or jax.nn.gelu
    T = x.shape[0]
    E = W1.shape[0]
    capacity = max(1, int(T * k * capacity_factor / E))
    dispatch, combine, aux, z_loss, overflow = topk_gating(
        x, Wg, capacity, k)
    blocks = jnp.einsum("tec,td->ecd", dispatch, x)       # (E, C, D)
    out_blocks = _expert_ffn(blocks, W1, b1, W2, b2, act)
    y = jnp.einsum("tec,ecd->td", combine, out_blocks)
    return y, aux, (z_loss, overflow)


def _a2a(x, axis_name: str, split_axis: int, concat_axis: int):
    """lax.all_to_all with an explicit custom vjp: the transpose of an
    all_to_all is the mirrored all_to_all (it permutes data across
    devices, so its linear adjoint is the inverse permutation). JAX's
    built-in transpose rule mis-lowers when the op is differentiated
    through a lax.scan (the PP x EP pipeline case: expert dispatch
    inside the gpipe slot scan) — the explicit rule sidesteps it and is
    what the math says anyway."""

    @jax.custom_vjp
    def run(v):
        return lax.all_to_all(v, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis)

    def fwd(v):
        return run(v), None

    def bwd(_, dy):
        return (lax.all_to_all(dy, axis_name, split_axis=concat_axis,
                               concat_axis=split_axis),)

    run.defvjp(fwd, bwd)
    return run(x)


def moe_ffn_ep(x, Wg, W1, b1, W2, b2, axis_name: str,
               capacity_factor=1.25, act=None, k=1):
    """Expert-parallel MoE inside shard_map.

    x: (T_local, D) this device's tokens; Wg (D, E_global) replicated;
    W1/b1/W2/b2 hold only the E_local = E_global/n experts this device
    owns. Token blocks for remote experts travel via all_to_all.
    Returns (y, aux, stats); aux/stats are pmean'd over the axis.
    """
    act = act or jax.nn.gelu
    n = lax.axis_size(axis_name)
    T = x.shape[0]
    E = Wg.shape[1]
    e_local = E // n
    capacity = max(1, int(T * k * capacity_factor / E))
    dispatch, combine, aux, z_loss, overflow = topk_gating(
        x, Wg, capacity, k)
    blocks = jnp.einsum("tec,td->ecd", dispatch, x)       # (E, C, D)
    # group by owning device and exchange: (n, E_local, C, D) -> each
    # device receives its expert group from everyone -> (E_local, n, C, D)
    grouped = blocks.reshape(n, e_local, capacity, -1)
    received = _a2a(grouped, axis_name, 0, 1)             # (e_local,n,C,D)
    stacked = received.reshape(e_local, n * capacity, -1)
    out = _expert_ffn(stacked, W1, b1, W2, b2, act)       # (e_local,nC,D)
    out = out.reshape(e_local, n, capacity, -1)
    returned = _a2a(out, axis_name, 1, 0)                 # (n,e_local,C,D)
    out_blocks = returned.reshape(E, capacity, -1)
    y = jnp.einsum("tec,ecd->td", combine, out_blocks)
    aux = lax.pmean(aux, axis_name)
    z_loss = lax.pmean(z_loss, axis_name)
    overflow = lax.pmean(overflow, axis_name)
    return y, aux, (z_loss, overflow)


# ======================= dropless routing ==================================
# Sorted dispatch, in static shapes: the pairs (token, choice) whose expert
# this device holds are sorted to the front of a buffer of
# R = tokens x min(k, held) rows (a token's k choices are k different
# experts, so no more of them can be held), grouped by expert; the rest of
# the buffer is padding that the grouped products do not compute. Both ways
# the rows move by GATHERS: a row of the buffer reads its token, and a
# token reads back its k rows through the inverse permutation; the
# transposes are the same two gathers the other way round (a scatter-add of
# 65,536 rows is what XLA would make of them, and the TPU serialises it).
# The grouped kernel leaves the buffer's rows past the last group as it
# found them (uninitialised memory, NaNs among it: my chip run, PR 32), in
# its outputs and in its input's gradient. Nothing zeroes them: every read
# of a row goes through a SELECT on whether the pair has one (`held`), so
# what lies there never meets a number.


def route_topk(x, Wr, k):
    """(gates (T, k) fp32, renormalised over the k chosen; experts (T, k)
    int32): softmax over ALL experts in fp32, the matmul at full
    precision (a tie in the top-k moves a whole row), then the top k."""
    logits = jnp.dot(x.astype(jnp.float32), Wr.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    topv, topi = lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    return topv / jnp.sum(topv, axis=-1, keepdims=True), topi


def _rows_of_pairs(a, inv, held):
    """a (R, D) -> (T, k, D) fp32: each pair's row, zeros where it has
    none."""
    rows = a[jnp.minimum(inv, a.shape[0] - 1)]
    return jnp.where(held[..., None], rows, 0).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _rows_of_tokens(x, order, inv, held, k):
    """x (T, D) -> the buffer's rows (R, D): row r is the token of pair
    order[r]. `inv` (T, k): the row of each pair; `held` (T, k): whether
    it has one (its expert is held here and the row is in the buffer)."""
    return x[order // k]


def _rows_fwd(x, order, inv, held, k):
    return x[order // k], (inv, held)


def _rows_bwd(k, res, g):
    inv, held = res
    return jnp.sum(_rows_of_pairs(g, inv, held), axis=1).astype(g.dtype), \
        None, None, None


_rows_of_tokens.defvjp(_rows_fwd, _rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _tokens_of_rows(o, gates, order, inv, held, k):
    """y (T, D) fp32 = sum over a token's k pairs of gate x the pair's
    row of o (R, D); `gates` (T, k) is zero where the pair has no row."""
    return _tokens_fwd(o, gates, order, inv, held, k)[0]


def _tokens_fwd(o, gates, order, inv, held, k):
    return jnp.einsum("tk,tkd->td", gates, _rows_of_pairs(o, inv, held)), \
        (o, gates, order, inv, held)


def _tokens_bwd(k, res, dy):
    o, gates, order, inv, held = res
    # row by row over the buffer, one pass: each row's token's cotangent
    # times its gate is the row's, and their product summed is the gate's
    # (read back by pair: numbers, not rows)
    dy_rows = dy[order // k]                               # (R, D) fp32
    do = gates.reshape(-1)[order][:, None] * dy_rows
    dgate_rows = jnp.sum(o.astype(jnp.float32) * dy_rows, axis=1)
    dgates = jnp.where(held, dgate_rows[jnp.minimum(inv, o.shape[0] - 1)],
                       0.0)
    return do.astype(o.dtype), dgates, None, None, None


_tokens_of_rows.defvjp(_tokens_fwd, _tokens_bwd)


def _gmm_tiling(m, k, n):
    """Tiles for the megablox kernel, or None where it cannot tile the
    product: the rows in 512s (256, 128), the contraction and the output
    width in their largest lane-multiple divisors at or under 1024 and
    1152 (my chip run, PR 32: (512, 768, 896) and (512, 896, 1152) for the
    2304 <-> 896 products; 2304 whole overflows VMEM)."""
    fit = lambda s, cap: next((t for t in range(cap - cap % 128, 0, -128)
                               if s % t == 0), None)
    tm = next((t for t in (512, 256, 128) if m % t == 0), None)
    tk, tn = fit(k, 1024), fit(n, 1152)
    return (tm, tk, tn) if tm and tk and tn else None


def grouped_matmul(lhs, rhs, sizes):
    """lhs (R, K) rows grouped by expert, rhs (H, K, N), sizes (H,) int32:
    rows of group e times rhs[e]; rows past sum(sizes) cost nothing and
    come out as whatever (zero from `lax.ragged_dot`, uninitialised memory
    from the kernel: callers select, see above). On a TPU in bfloat16 the
    megablox Pallas kernel (its work follows the rows really routed: 0.67
    ms where `lax.ragged_dot` takes 2.6 over a 65,536-row buffer a quarter
    full, my chip run, PR 32); elsewhere, in fp32 (these tiles overflow
    VMEM at 4 bytes) or where the product does not tile,
    `lax.ragged_dot`."""
    tiling = _gmm_tiling(lhs.shape[0], lhs.shape[1], rhs.shape[2])
    if jax.default_backend() == "tpu" and tiling \
            and lhs.dtype == jnp.bfloat16:
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
        return megablox.gmm(lhs, rhs.astype(lhs.dtype), sizes, lhs.dtype,
                            tiling)
    return lax.ragged_dot(lhs, rhs.astype(lhs.dtype), sizes)


def dropless_moe(x, Wr, Wg, Wu, Wd, k, offset=0):
    """x (T, D); Wr (D, E) the router over ALL experts; Wg, Wu (H, D, F),
    Wd (H, F, D): the H experts offset .. offset + H - 1 this device holds.
    Returns (y (T, D) fp32: sum over a token's chosen AND held experts of
    gate x (silu(x Wg_e) * (x Wu_e)) Wd_e; rows (H,) float32: the rows
    routed to each held expert, off the gradient). The experts compute in
    the dtype of their weights."""
    T, H = x.shape[0], Wg.shape[0]
    R = T * min(k, H)
    with jax.named_scope("router"):
        gates, experts = route_topk(x, Wr, k)
    with jax.named_scope("dispatch"):
        local = experts - offset
        mine = (local >= 0) & (local < H)
        key = jnp.where(mine, local, H).reshape(-1)       # unheld: last
        # (a count by comparison: `bincount` is a scatter-add of T x k
        # ones, which the TPU serialises)
        sizes = jnp.sum(key[:, None] == jnp.arange(H)[None, :], axis=0,
                        dtype=jnp.int32)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inv = jnp.argsort(order).astype(jnp.int32).reshape(T, k)
        order, held = order[:R], mine & (inv < R)
        xs = _rows_of_tokens(x.astype(Wg.dtype), order, inv, held, k)
    with jax.named_scope("experts"):
        h = jax.nn.silu(grouped_matmul(xs, Wg, sizes)) \
            * grouped_matmul(xs, Wu, sizes)
        o = grouped_matmul(h, Wd, sizes)
    with jax.named_scope("combine"):
        y = _tokens_of_rows(o, jnp.where(held, gates, 0.0), order, inv,
                            held, k)
    return y, lax.stop_gradient(sizes.astype(jnp.float32))
