"""Gated delta-rule linear attention (Gated DeltaNet, arXiv:2412.06464,
with the negative-eigenvalue range of arXiv:2411.12537): a token mixer
that carries a fixed-size matrix state a head through the sequence.

    q = l2(silu(taps(x W_q))) dk^-1/2    k = l2(silu(taps(x W_k)))
    v = silu(taps(x W_v))                (heads of dk, dk and dv)
    beta = sigmoid(x W_b), times 2 where negative eigenvalues are allowed
    g = -exp(A_log) softplus(x W_a + dt_bias),  alpha = exp(g)
    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t                                          S (dv x dk)
    y = concat_heads(RMS_dv(o; gain) * silu(x W_g)) W_o

`taps` is ops.shortconv.causal_taps: depthwise and causal, zeros before
the sequence. The recurrence is computed in chunks (`chunked_delta_rule`:
the WY / UT form of arXiv:2406.06484 with the gates of arXiv:2412.06464),
in fp32 whatever the products' dtype. Five device scopes: `proj` (the six
input products), `conv` (taps, SiLU, the L2 norms), `delta` (the gates,
the intra-chunk products, the triangular solve and the pass from chunk to
chunk), `norm_gate` and `out_proj`.

Two implementations of the rule, one result (`chunked_delta_rule` picks by
what the call can observe, `singa_delta_rule_dispatch_total{path}` says
which): a Pallas kernel each way (`singa_delta_fwd`, `singa_delta_bwd`,
tied by a custom vjp) that keeps each head's state in VMEM from chunk to
chunk, where the chunk is a power of two and the head widths tile
(`delta_rule_plan`); elsewhere the XLA form (`_rule_xla`: batched
products, a triangular solve, a scan over the chunks, autodiff's
backward). Both compute in fp32; their products take the same precision
(the solve's at HIGHEST, as XLA's own solve takes them; the others at
DEFAULT, as the XLA form's einsums).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observe import record_delta_rule_dispatch
from .shortconv import causal_taps

L2_EPS = 1e-6      # the reference implementations' l2norm; no config key


def l2_normalize(x, eps=L2_EPS):
    """x / sqrt(sum(x^2) + eps) over the last axis."""
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def chunked_delta_rule(q, k, v, g, beta, chunk=64):
    """q, k (B, S, H, dk), v (B, S, H, dv), g (log alpha) and beta
    (B, S, H) -> o (B, S, H, dv), in the dtype of the inputs (fp32).

    Within a chunk of C tokens, with gamma the running sum of g from its
    start and h (dk x dv) = S^T the state entering it:
      A_ij = beta_i e^(gamma_i - gamma_j) k_i.k_j (j < i)
      (I + A) [U | W] = [beta v | beta e^gamma k]     the triangular solve
      new = U - W h                       the values the chunk writes
      o   = e^gamma q h + (e^(gamma_i - gamma_j) q_i.k_j)_(j <= i) new
      h'  = e^gamma_C h + (e^(gamma_C - gamma_j) k_j)^T new
    The tail of a sequence that is not a multiple of the chunk is padded
    with k = v = q = 0, beta = 0 and g = 0: rows that leave the state as it
    is. The Pallas kernels where `delta_rule_plan` takes the shapes (in
    interpret mode off a TPU), `_rule_xla` elsewhere;
    `singa_delta_rule_dispatch_total{path}` says which."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    n = -(-S // chunk)
    q, k, v, g, beta = (jnp.pad(a, [(0, 0), (0, n * chunk - S)]
                                + [(0, 0)] * (a.ndim - 2))
                        for a in (q, k, v, g, beta))

    def chunks(a):      # (B, n C, H, ...) -> (B, H, n, C, ...)
        return jnp.moveaxis(a.reshape(B, n, chunk, *a.shape[2:]), 3, 1)

    gam, beta = jnp.cumsum(chunks(g), -1), chunks(beta)        # (B, H, n, C)
    tiles = delta_rule_plan(H, chunk, dk, dv)
    if tiles is None:
        record_delta_rule_dispatch("xla")
        o = _rule_xla(chunks(q), chunks(k), chunks(v), gam, beta)
        o = jnp.moveaxis(o, 1, 3).reshape(B, n * chunk, H, dv)
    else:
        interpret = jax.default_backend() != "tpu"
        record_delta_rule_dispatch("interpret" if interpret else "kernel")
        # gamma and beta as two rows a tile: (B H / tile, n, 2, tile C)
        P = tiles.tile
        gb = jnp.stack([gam, beta], -2).reshape(B, H // P, P, n, 2, chunk)
        gb = jnp.moveaxis(gb, 2, 4).reshape(B * H // P, n, 2, P * chunk)
        o = _rule_kernel(q, k, v, gb, tiles, interpret)
    return o[:, :S]


def _rule_xla(q, k, v, gam, beta):
    """The rule over chunked (B, H, n, C, ...) inputs in `jnp` / `lax`: the
    intra-chunk parts batched over the chunks, only h' from chunk to chunk
    (`lax.scan`); autodiff's backward."""
    B, H, _n, chunk, dk = q.shape
    dv = v.shape[-1]
    i = jnp.arange(chunk)

    def decay(mask):    # e^(gamma_i - gamma_j) where `mask`, else 0
        diff = gam[..., :, None] - gam[..., None, :]
        return jnp.where(mask, jnp.exp(jnp.where(mask, diff, 0.0)), 0.0)

    A = beta[..., None] * decay(i[:, None] > i) \
        * jnp.einsum("...id,...jd->...ij", k, k)
    rhs = jnp.concatenate(
        [beta[..., None] * v, (beta * jnp.exp(gam))[..., None] * k], -1)
    uw = lax.linalg.triangular_solve(A, rhs, left_side=True, lower=True,
                                     unit_diagonal=True)
    u, w = uw[..., :dv], uw[..., dv:]
    kd = jnp.exp(gam[..., -1:] - gam)[..., None] * k

    def step(h, xs):
        u_c, w_c, kd_c, last = xs
        new = u_c - jnp.einsum("bhck,bhkv->bhcv", w_c, h)
        return last[..., None, None] * h \
            + jnp.einsum("bhck,bhcv->bhkv", kd_c, new), (h, new)

    h0 = jnp.zeros((B, H, dk, dv), q.dtype)
    _, (hs, new) = lax.scan(step, h0, [jnp.moveaxis(a, 2, 0) for a in (
        u, w, kd, jnp.exp(gam[..., -1]))])
    hs, new = jnp.moveaxis(hs, 0, 2), jnp.moveaxis(new, 0, 2)
    qk = decay(i[:, None] >= i) * jnp.einsum("...id,...jd->...ij", q, k)
    return jnp.einsum("...ck,...kv->...cv", jnp.exp(gam)[..., None] * q, hs) \
        + jnp.einsum("...ij,...jv->...iv", qk, new)


class DeltaTiles(NamedTuple):
    """How the kernels go through a chunk's heads: `heads` an iteration of
    their loop, `tile` at a time as one block of tile x chunk rows (each
    head's chunk a diagonal block of it, the MXU's 128 rows filled)."""
    heads: int
    tile: int


def delta_rule_plan(heads, chunk, dk, dv):
    """DeltaTiles for the kernels over `heads` heads of dk / dv and chunks
    of `chunk` tokens, or None where they do not apply: a chunk not a power
    of two of at least 8 rows, dk or dv not a multiple of 8 (an fp32 tile's
    sublanes), or a chunk's blocks past `_VMEM_BUDGET`. A tile holds
    128 // chunk heads where they divide `heads`, else one; an iteration
    the most tiles, up to `_HEADS` heads, that divide `heads`."""
    if chunk < 8 or chunk & (chunk - 1) or dk % 8 or dv % 8:
        return None
    tile = max(1, _TILE // chunk)
    tile = tile if heads % tile == 0 else 1
    step = max(h for h in range(tile, max(_HEADS, tile) + 1, tile)
               if heads % h == 0)
    lanes = lambda d: -(-d // 128) * 128
    # the backward's (the larger) blocks, double-buffered: q, k, v, do in
    # and dq, dk, dv out, a chunk of every head (H padded to 8 sublanes);
    # the states entering the chunk; the gates and their gradient; the
    # carried gradient of the state; an iteration's (R, R) and (R, d)
    # temporaries, 16 of each. Lane widths count rounded up to 128.
    hp, R = -(-heads // 8) * 8, tile * chunk
    blocks = chunk * hp * (4 * lanes(dk) + 3 * lanes(dv)) \
        + heads * dk * lanes(dv) + 2 * heads // tile * 8 * lanes(R)
    temps = step // tile * 16 * R * max(lanes(R), lanes(dk), lanes(dv))
    need = 4 * (2 * blocks + heads * dk * lanes(dv) + temps)
    return DeltaTiles(step, tile) if need <= _VMEM_BUDGET else None


# ---- the kernels -----------------------------------------------------------
#
# Grid (batch, chunks), the chunks in order: a block holds one chunk of every
# head, q, k, v, o and their gradients read and written in the layer's own
# (B, S, H, d) layout (a head's rows a strided slice), and each head's state
# (dk x dv, fp32) stays in VMEM from chunk to chunk, forward and (in reverse)
# its gradient backward. The forward writes o and the state ENTERING each
# chunk (B H, n, dk, dv), from which the backward rebuilds a chunk's parts;
# gamma and beta come as two rows of a TILE of heads (B H / tile, n, 2,
# tile C). A loop goes through the heads, `heads` an iteration; what does
# not depend on the state (the masks, k k^T, A, its inverse, [U | W], q k^T)
# is computed a tile at a time, the tile's heads stacked as the diagonal
# blocks of one (R, R) = (tile C, tile C) problem and the blocks between
# heads masked to zero, the tiles of an iteration side by side; what does
# (new, o, h') a head at a time.

_VMEM_BUDGET = 48 << 20
_TILE = 128         # the rows of a tile: the MXU's width
_HEADS = 6          # the most heads an iteration works
# the triangular solve's products at XLA's own precision for its solve
# (fp32 HIGHEST); the other products at the precision of the XLA form's
# fp32 einsums
_SOLVE, _PRODUCTS = lax.Precision.HIGHEST, lax.Precision.DEFAULT


def _dot(a, b, ta=False, tb=False, precision=None):
    """a @ b in fp32, a and / or b transposed; at `_PRODUCTS` unless
    `precision` says otherwise."""
    dims = (((0 if ta else 1,), (1 if tb else 0,)), ((), ()))
    return lax.dot_general(a, b, dims, precision=_PRODUCTS
                           if precision is None else precision,
                           preferred_element_type=jnp.float32)


def _rows(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _cols(x):
    return jnp.sum(x, axis=0, keepdims=True)


def _unit_lower_inverses(tiles, chunk):
    """(I + a)^-1 of each tile's strictly lower, block-diagonal a: block
    substitution by doubling. With T_s the inverse of each diagonal block
    of s rows and L_s the part of a below those blocks inside the blocks
    of 2 s rows, T_2s = T_s - T_s L_s T_s (the lower left block of a 2 x 2
    block inverse): every product is of inverses the rule's own solve
    would form, never a power of a. The levels run over every tile in
    turn: their products are independent, and the chip overlaps them."""
    # i and j share a block of 2 s rows and not one of s where s <= i ^ j
    # < 2 s (no integer division: the vector unit has none)
    off = lambda x, s: (x >= s) & (x < 2 * s)
    ts = [jnp.where(x.row == x.col, 1.0, 0.0) - jnp.where(off(x.xor, 1), x.a,
                                                         0.0) for x in tiles]
    s = 2
    while s < chunk:
        ts = [t - _dot(t, _dot(jnp.where(off(x.xor, s), x.a, 0.0), t,
                               precision=_SOLVE), precision=_SOLVE)
              for x, t in zip(tiles, ts)]
        s *= 2
    return ts


class _Tile(NamedTuple):
    """A tile's parts that do not depend on the state (the rule's
    docstring), its heads' chunks stacked: (R, R) masks and products,
    (R, 1) columns, (R, d) rows."""
    row: jax.Array      # (R, R) row and column indices, and their xor
    col: jax.Array
    xor: jax.Array
    lower: jax.Array    # j < i inside a head's block
    gam: jax.Array      # gamma (R, 1)
    beta: jax.Array
    ds: jax.Array       # e^(gamma_i - gamma_j), j < i
    di: jax.Array       # the same, j <= i
    kk: jax.Array       # k k^T
    a: jax.Array
    e: jax.Array        # e^gamma (R, 1)
    bk: jax.Array       # beta e^gamma k
    qk: jax.Array       # q k^T
    p: jax.Array        # (e^(gamma_i - gamma_j) q_i.k_j), j <= i
    kdec: jax.Array     # e^(gamma_C - gamma) (R, 1), gamma_C its head's
    last: jax.Array     # e^gamma_C (R, 1)


def _column(r, row, col):
    """A (1, R) row as an (R, 1) column, exactly (a masked lane sum)."""
    return jnp.sum(jnp.where(row == col, r, 0.0), axis=1, keepdims=True)


def _row(c, row, col):
    """An (R, 1) column as a (1, R) row, exactly."""
    return jnp.sum(jnp.where(row == col, c, 0.0), axis=0, keepdims=True)


def _stack(ref, heads):
    """The chunk of each of `heads` in a (1, C, H, d) block, stacked as one
    (R, d) array."""
    return jnp.concatenate([ref[0, :, j, :] for j in heads], axis=0)


def _tile(q, k, gb, chunk):
    """A tile's state-free parts from its stacked q, k (R, dk) and gb =
    (gamma; beta) as two rows (2, R)."""
    R = q.shape[0]
    row = lax.broadcasted_iota(jnp.int32, (R, R), 0)
    col = lax.broadcasted_iota(jnp.int32, (R, R), 1)
    xor = row ^ col
    head = xor < chunk                  # i and j in one head's chunk
    lower, lower_eq = head & (row > col), head & (row >= col)
    gam_r = gb[0:1]
    gam, beta = _column(gam_r, row, col), _column(gb[1:2], row, col)
    gam_c = jnp.sum(jnp.where(head & ((col & (chunk - 1)) == chunk - 1),
                              gam_r, 0.0), axis=1, keepdims=True)
    decay = jnp.exp(jnp.where(lower_eq, gam - gam_r, 0.0))
    ds, di = jnp.where(lower, decay, 0.0), jnp.where(lower_eq, decay, 0.0)
    kk = _dot(k, k, tb=True)
    e = jnp.exp(gam)
    qk = _dot(q, k, tb=True)
    return _Tile(row, col, xor, lower, gam, beta, ds, di, kk, beta * ds * kk,
                 e, beta * e * k, qk, di * qk, jnp.exp(gam_c - gam),
                 jnp.exp(gam_c))


def _scalar(column, i):
    """Entry i of an (R, 1) column as a scalar (a (1, 1) slice would not
    broadcast to (dk, dv))."""
    rows = lax.broadcasted_iota(jnp.int32, column.shape, 0)
    return jnp.sum(jnp.where(rows == i, column, 0.0))


def _groups(tiles, it):
    """The heads of iteration `it`, a list of each tile's."""
    P, G = tiles.tile, tiles.heads // tiles.tile
    return [[(it * G + g) * P + i for i in range(P)] for g in range(G)]


def _fwd_kernel(q_ref, k_ref, v_ref, gb_ref, o_ref, hs_ref, h_ref, *, tiles):
    @pl.when(pl.program_id(1) == 0)         # a sequence starts from no state
    def _():
        h_ref[...] = jnp.zeros(h_ref.shape, jnp.float32)

    C = q_ref.shape[1]

    def body(it, carry):
        groups = _groups(tiles, it)
        qs, ks, vs = ([_stack(r, heads) for heads in groups]
                      for r in (q_ref, k_ref, v_ref))
        xs = [_tile(q, k, gb_ref[heads[0] // tiles.tile, 0], C)
              for heads, q, k in zip(groups, qs, ks)]
        ts = _unit_lower_inverses(xs, C)
        for heads, x, t, q, k, v in zip(groups, xs, ts, qs, ks, vs):
            u = _dot(t, x.beta * v, precision=_SOLVE)
            w = _dot(t, x.bk, precision=_SOLVE)
            qg, kd, new, oh = x.e * q, x.kdec * k, [], []
            for i, j in enumerate(heads):   # the state: a head at a time
                rs = slice(i * C, (i + 1) * C)
                h = h_ref[j]
                hs_ref[j, 0] = h
                new.append(u[rs] - _dot(w[rs], h))
                oh.append(_dot(qg[rs], h))
                h_ref[j] = _scalar(x.last, i * C) * h \
                    + _dot(kd[rs], new[-1], ta=True)
            pn = _dot(x.p, jnp.concatenate(new, axis=0))
            for i, j in enumerate(heads):
                o_ref[0, :, j, :] = oh[i] + pn[i * C:(i + 1) * C]
        return carry

    lax.fori_loop(0, h_ref.shape[0] // tiles.heads, body, 0)


def _bwd_kernel(q_ref, k_ref, v_ref, gb_ref, hs_ref, do_ref, dq_ref, dk_ref,
                dv_ref, dgb_ref, dh_ref, *, tiles):
    """A chunk's gradients, the chunks last to first; dh_ref holds the
    gradient of the state LEAVING the chunk (none after the last)."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        dh_ref[...] = jnp.zeros(dh_ref.shape, jnp.float32)

    C = q_ref.shape[1]

    def body(it, carry):
        groups = _groups(tiles, it)
        qs, ks = ([_stack(r, heads) for heads in groups]
                  for r in (q_ref, k_ref))
        xs = [_tile(q, k, gb_ref[heads[0] // tiles.tile, 0], C)
              for heads, q, k in zip(groups, qs, ks)]
        ts = _unit_lower_inverses(xs, C)
        for heads, x, t, q, k in zip(groups, xs, ts, qs, ks):
            _bwd_tile(heads, x, t, q, k, _stack(v_ref, heads),
                      _stack(do_ref, heads), hs_ref, dq_ref, dk_ref, dv_ref,
                      dgb_ref, dh_ref)
        return carry

    lax.fori_loop(0, dh_ref.shape[0] // tiles.heads, body, 0)


def _bwd_tile(heads, x, t, q, k, v, do, hs_ref, dq_ref, dk_ref, dv_ref,
              dgb_ref, dh_ref):
    C = q.shape[0] // len(heads)
    beta, e = x.beta, x.e
    u = _dot(t, beta * v, precision=_SOLVE)
    w = _dot(t, x.bk, precision=_SOLVE)
    qg, kd = e * q, x.kdec * k
    # o = qg h + P new,  h' = last h + kd^T new,  new = U - W h: the
    # state's terms a head at a time
    pdo = _dot(x.p, do, ta=True)
    new, d_new, dqg, dkd, dnh, ends = [], [], [], [], [], []
    for i, j in enumerate(heads):
        rs = slice(i * C, (i + 1) * C)
        h, dhn = hs_ref[j, 0], dh_ref[j]
        new.append(u[rs] - _dot(w[rs], h))
        d_new.append(pdo[rs] + _dot(kd[rs], dhn))
        dqg.append(_dot(do[rs], h, tb=True))
        dkd.append(_dot(new[-1], dhn, tb=True))
        dnh.append(_dot(d_new[-1], h, tb=True))
        last = _scalar(x.last, i * C)
        # gamma_C's part through h' = e^gamma_C h + ...
        ends.append(last * jnp.sum(dhn * h) + jnp.sum(
            _rows(dkd[-1] * kd[rs])))
        dh_ref[j] = _dot(qg[rs], do[rs], ta=True) + last * dhn \
            - _dot(w[rs], d_new[-1], ta=True)
    new, d_new, dqg, dkd, dnh = (jnp.concatenate(a, axis=0)
                                 for a in (new, d_new, dqg, dkd, dnh))
    mp = x.di * _dot(do, new, tb=True)             # d(q k^T)
    # [U | W] = T [beta v | bk]:  dT's part through A
    dbv = _dot(t, d_new, ta=True, precision=_SOLVE)
    dbk = -_dot(t, dnh, ta=True, precision=_SOLVE)
    da = jnp.where(x.lower, -(_dot(dbv, u, tb=True) + _dot(dbk, w, tb=True)),
                   0.0)
    ma = da * beta * x.ds                          # d(k k^T)
    dq = _dot(mp, k) + e * dqg
    dk = _dot(ma, k) + _dot(ma, k, ta=True) + _dot(mp, q, ta=True) \
        + beta * e * dbk + x.kdec * dkd
    dv = beta * dbv
    for i, j in enumerate(heads):
        rs = slice(i * C, (i + 1) * C)
        dq_ref[0, :, j, :] = dq[rs]
        dk_ref[0, :, j, :] = dk[rs]
        dv_ref[0, :, j, :] = dv[rs]
    dbeta = _rows(da * x.ds * x.kk) + _rows(dbv * v) + _rows(dbk * e * k)
    ea, ep = da * x.a, mp * x.qk                   # through the decays
    rows = lax.broadcasted_iota(jnp.int32, x.gam.shape, 0)
    dgam = _rows(ea) + _rows(ep) + _rows(dbk * x.bk) + _rows(dqg * qg) \
        - _rows(dkd * kd)
    for i, end in enumerate(ends):                 # gamma_C of head i
        dgam = dgam + jnp.where(rows == (i + 1) * C - 1, end, 0.0)
    g = heads[0] // len(heads)
    dgb_ref[g, 0, 0:1] = _row(dgam, x.row, x.col) - _cols(ea) - _cols(ep)
    dgb_ref[g, 0, 1:2] = _row(dbeta, x.row, x.col)


def _compiler_params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                vmem_limit_bytes=_VMEM_BUDGET + (16 << 20))


class _Blocks(NamedTuple):
    """BlockSpecs of one chunk of every head, the chunks in order or in
    reverse: `tokens(d)` of a (B, n C, H, d) array, `states` of the (B H,
    n, dk, dv) states, `gates` of the (B H / tile, n, 2, tile C) gates."""
    tokens: object
    states: pl.BlockSpec
    gates: pl.BlockSpec


def _blocks(H, n, C, dk, dv, tile, reverse=False):
    at = (lambda b, c: (b, n - 1 - c, 0, 0)) if reverse else \
        (lambda b, c: (b, c, 0, 0))
    return _Blocks(lambda d: pl.BlockSpec((1, C, H, d), at),
                   pl.BlockSpec((H, 1, dk, dv), at),
                   pl.BlockSpec((H // tile, 1, 2, tile * C), at))


def _fwd_pallas(q, k, v, gb, tiles, interpret):
    B, S, H, dk = q.shape
    dv, n = v.shape[-1], gb.shape[1]
    f32 = jnp.float32
    blk = _blocks(H, n, S // n, dk, dv, tiles.tile)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, tiles=tiles),
        name="singa_delta_fwd",
        grid=(B, n),
        in_specs=[blk.tokens(dk), blk.tokens(dk), blk.tokens(dv), blk.gates],
        out_specs=[blk.tokens(dv), blk.states],
        out_shape=[jax.ShapeDtypeStruct((B, S, H, dv), f32),
                   jax.ShapeDtypeStruct((B * H, n, dk, dv), f32)],
        scratch_shapes=[pltpu.VMEM((H, dk, dv), f32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(q, k, v, gb)


def _bwd_pallas(q, k, v, gb, hs, do, tiles, interpret):
    B, S, H, dk = q.shape
    dv, n = v.shape[-1], gb.shape[1]
    blk = _blocks(H, n, S // n, dk, dv, tiles.tile, reverse=True)
    grads = [blk.tokens(dk), blk.tokens(dk), blk.tokens(dv), blk.gates]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, tiles=tiles),
        name="singa_delta_bwd",
        grid=(B, n),
        in_specs=grads + [blk.states, blk.tokens(dv)],
        out_specs=grads,
        out_shape=[jax.ShapeDtypeStruct(a.shape, jnp.float32)
                   for a in (q, k, v, gb)],
        scratch_shapes=[pltpu.VMEM((H, dk, dv), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(q, k, v, gb, hs, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _rule_kernel(q, k, v, gb, tiles, interpret):
    """The rule over fp32 q, k (B, n C, H, dk), v (B, n C, H, dv) and gb =
    (gamma, the running sum of g in each chunk; beta), two rows a tile of
    heads (B H / tile, n, 2, tile C), gone through as `tiles` says:
    o (B, n C, H, dv)."""
    return _fwd_pallas(q, k, v, gb, tiles, interpret)[0]


def _rule_kernel_fwd(q, k, v, gb, tiles, interpret):
    o, hs = _fwd_pallas(q, k, v, gb, tiles, interpret)
    return o, (q, k, v, gb, hs)


def _rule_kernel_bwd(tiles, interpret, res, do):
    return _bwd_pallas(*res, do, tiles, interpret)


_rule_kernel.defvjp(_rule_kernel_fwd, _rule_kernel_bwd)


def gated_delta_net(x, W_q, W_k, W_v, W_a, W_b, W_g, W_o, conv_q, conv_k,
                    conv_v, A_log, dt_bias, o_norm, *, heads, neg_eigval=True,
                    chunk=64, norm_eps=1e-6):
    """x (B, S, E) -> (B, S, E): the layer above. The products in the dtype
    of x and the weights (the gates' two with fp32 sums and results); the
    taps, the norms and the recurrence in fp32."""
    B, S, _ = x.shape
    f32 = jnp.float32
    with jax.named_scope("proj"):
        qkv = [jnp.matmul(x, W) for W in (W_q, W_k, W_v)]
        gate = jnp.matmul(x, W_g)
        a, b = (jnp.matmul(x, W, preferred_element_type=f32)
                for W in (W_a, W_b))
    with jax.named_scope("conv"):
        q, k, v = (jax.nn.silu(causal_taps(z.astype(f32), w.astype(f32)))
                   .reshape(B, S, heads, -1)
                   for z, w in zip(qkv, (conv_q, conv_k, conv_v)))
        q = l2_normalize(q) * q.shape[-1] ** -0.5
        k = l2_normalize(k)
    with jax.named_scope("delta"):
        beta = jax.nn.sigmoid(b) * (2.0 if neg_eigval else 1.0)
        g = -jnp.exp(A_log.astype(f32)) * jax.nn.softplus(
            a + dt_bias.astype(f32))
        o = chunked_delta_rule(q, k, v, g, beta, chunk)
    with jax.named_scope("norm_gate"):
        o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + norm_eps) \
            * o_norm.astype(f32)
        y = (o * jax.nn.silu(gate.astype(f32).reshape(o.shape))) \
            .reshape(B, S, -1).astype(x.dtype)
    with jax.named_scope("out_proj"):
        return jnp.matmul(y, W_o)
