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
yet: the capacity path above is the one that runs under `ep_axis`). Its
sorted buffer has a row for every pair that could be routed here; the
backward's passes over the buffer's rows work on a RUNG of it, the least of
an eighth, a quarter, a half and the whole that holds the rows really
routed, picked on the device a layer and a step. The whole buffer is the
top rung, so a rung cannot drop a row either.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
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
#
# R is the worst case (every pair to a held expert), and an even share of
# the pairs is an eighth or a quarter of it. What XLA makes around the
# grouped products on the BACKWARD pass, row by row over the buffer, would
# cost R rows whatever was routed: the cotangent's rows gathered in fp32,
# the gate's backward, the sum of the two input gradients. So those passes
# work on a RUNG: the least of R/8, R/4, R/2 and R that holds the rows
# really routed, picked on the device from the count the layer has before
# it gathers anything (`rung_of`), each pass under a `lax.switch` whose
# branch does the same arithmetic on the rung's first rows and fills the
# rest of its (R, .) result with zeros. The top rung is the whole buffer,
# so a rung always holds every routed row: nothing is dropped by it and
# there is nothing to set. The switches sit inside the backwards of
# `custom_vjp`s: JAX never differentiates through one (it would keep every
# branch's residuals). The grouped products, the router, the sorts and the
# reads by pair (one a (token, choice) whatever was routed) are outside
# them. So are the forward's two passes over the buffer (my chip run,
# PR 35): the gather into it writes its 268 MB in 0.59 ms as it is (the
# tokens sit in fast memory), which is what gathering an eighth and
# filling the rest costs, and the gate's forward would gain 0.1 ms; and an
# array that a conditional hands out counts TWICE in the memory the
# compiler reserves for the step while it is live, a forward one from
# there to the layer's backward.


def rungs(R):
    """The ladder of buffer lengths for a buffer of R rows: R/8, R/4, R/2
    where they are whole, and R."""
    return tuple(R // d for d in (8, 4, 2, 1) if R % d == 0)


def rung_of(n, R):
    """The least rung of `rungs(R)` that holds `n` rows. One rule for the
    device (`n` traced: the count a layer routed) and the host (`n` a
    number: `models.mellum.record_rows`)."""
    xp = jnp if isinstance(n, jax.Array) else np
    ladder = rungs(R)
    return functools.reduce(lambda rung, b: xp.where(n <= b, b, rung),
                            ladder[-2::-1], ladder[-1])


def _on_rung(rung, R, rows_pass, *args):
    """`rows_pass(B, *args)` for the one B of `rungs(R)` that `rung` is:
    a branch a rung, and only the taken one runs."""
    ladder = rungs(R)
    return lax.switch(sum(rung > b for b in ladder[:-1]),
                      [functools.partial(rows_pass, B) for B in ladder],
                      *args)


def _fill(a, R):
    """`a` (B, ...) -> (R, ...), zeros past its rows."""
    if a.shape[0] == R:
        return a
    return jnp.pad(a, ((0, R - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))


def route_topk(x, Wr, k, score="softmax", bias=None, scale=1.0, eps=0.0):
    """(gates (T, k) fp32, renormalised over the k chosen; experts (T, k)
    int32): the scores of ALL experts in fp32 (`score`: "softmax" over
    them, or "sigmoid" of each), the matmul at full precision (a tie in
    the top-k moves a whole row), then the top k. `bias` (E,): added to
    the scores for the SELECTION alone (the gates are the chosen experts'
    plain scores, and nothing differentiates through it: a buffer that
    balances the load, arXiv:2408.15664). The gates are divided by their
    sum + `eps` and multiplied by `scale`."""
    assert score in ("softmax", "sigmoid"), score
    logits = jnp.dot(x.astype(jnp.float32), Wr.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1) if score == "softmax" \
        else jax.nn.sigmoid(logits)
    if bias is None:
        topv, topi = lax.top_k(scores, k)
    else:
        topi = lax.top_k(scores + lax.stop_gradient(
            bias.astype(jnp.float32)), k)[1]
        # read by comparison: a gather's transpose is a scatter-add of
        # T x k numbers, which the TPU serialises
        chosen = topi[..., None] == jnp.arange(scores.shape[-1])
        topv = jnp.sum(jnp.where(chosen, scores[:, None, :], 0.0), axis=-1)
    total = jnp.sum(topv, axis=-1, keepdims=True)
    gates = topv / (total + eps if eps else total)
    return (gates * scale if scale != 1.0 else gates), topi


def _rows_of_pairs(a, inv, held):
    """a (B, D), B a rung that holds every pair's row -> (T, k, D) fp32:
    each pair's row, zeros where it has none."""
    rows = a[jnp.minimum(inv, a.shape[0] - 1)]
    return jnp.where(held[..., None], rows, 0).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rows_of_tokens(x, order, inv, held, rung, k):
    """x (T, D) -> the buffer's rows (R, D), once for each of the two
    products that read them (so that their two cotangents meet HERE, on the
    rung, and not in a sum over R rows that JAX would write): row r is the
    token of pair order[r]. `inv` (T, k): the row of each pair; `held`
    (T, k): whether it has one (its expert is held here and the row is in
    the buffer); `rung`: the buffer length the backward works on."""
    return _rows_fwd(x, order, inv, held, rung, k)[0]


def _rows_fwd(x, order, inv, held, rung, k):
    xs = x[order // k]
    return (xs, xs), (inv, held, rung)


def _rows_bwd(k, res, gs):
    inv, held, rung = res

    def tokens(B, g_gate, g_up, inv, held):
        g = g_gate[:B] + g_up[:B]
        return jnp.sum(_rows_of_pairs(g, inv, held), axis=1).astype(g.dtype)

    return _on_rung(rung, gs[0].shape[0], tokens, *gs, inv, held), \
        None, None, None, None


_rows_of_tokens.defvjp(_rows_fwd, _rows_bwd)


def _silu_mul(hg, hu):
    return jax.nn.silu(hg) * hu


@jax.custom_vjp
def _gate(hg, hu, rung):
    """silu(hg) * hu of the two (R, F) products; its backward over the
    rung's rows, zeros past them."""
    return _silu_mul(hg, hu)


def _gate_fwd(hg, hu, rung):
    return _silu_mul(hg, hu), (hg, hu, rung)


def _gate_bwd(res, dh):
    hg, hu, rung = res
    R = hg.shape[0]

    def grads(B, hg, hu, dh):
        dhg, dhu = jax.vjp(_silu_mul, hg[:B], hu[:B])[1](dh[:B])
        return _fill(dhg, R), _fill(dhu, R)

    return *_on_rung(rung, R, grads, hg, hu, dh), None


_gate.defvjp(_gate_fwd, _gate_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _tokens_of_rows(o, gates, order, inv, held, rung, k):
    """y (T, D) fp32 = sum over a token's k pairs of gate x the pair's
    row of o (R, D); `gates` (T, k) is zero where the pair has no row."""
    return _tokens_fwd(o, gates, order, inv, held, rung, k)[0]


def _tokens_fwd(o, gates, order, inv, held, rung, k):
    return jnp.einsum("tk,tkd->td", gates, _rows_of_pairs(o, inv, held)), \
        (o, gates, order, inv, held, rung)


def _tokens_bwd(k, res, dy):
    o, gates, order, inv, held, rung = res
    R = o.shape[0]

    def rows(B, o, gates, order, dy):
        # row by row over the rung, one pass: each row's token's cotangent
        # times its gate is the row's, and their product summed is the
        # gate's (read back by pair: numbers, not rows)
        live = order[:B]
        dy_rows = dy[live // k]                            # (B, D) fp32
        do = gates.reshape(-1)[live][:, None] * dy_rows
        dgate_rows = jnp.sum(o[:B].astype(jnp.float32) * dy_rows, axis=1)
        return _fill(do.astype(o.dtype), R), _fill(dgate_rows, R)

    do, dgate_rows = _on_rung(rung, R, rows, o, gates, order, dy)
    dgates = jnp.where(held, dgate_rows[jnp.minimum(inv, R - 1)], 0.0)
    return do, dgates, None, None, None, None


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


def dropless_moe(x, Wr, Wg, Wu, Wd, k, offset=0, bias=None, **route):
    """x (T, D); Wr (D, E) the router over ALL experts; Wg, Wu (H, D, F),
    Wd (H, F, D): the H experts offset .. offset + H - 1 this device holds.
    Returns (y (T, D) fp32: sum over a token's chosen AND held experts of
    gate x (silu(x Wg_e) * (x Wu_e)) Wd_e; rows (H,) float32: the rows
    routed to each held expert, off the gradient). The experts compute in
    the dtype of their weights. `route`: `route_topk`'s `score`, `scale`
    and `eps`. With a selection `bias` (E,) a third result: load (E,)
    float32, the pairs sent to each of ALL the experts, held or not, off
    the gradient (what the bias is moved by)."""
    T, H = x.shape[0], Wg.shape[0]
    R = T * min(k, H)
    with jax.named_scope("router"):
        gates, experts = route_topk(x, Wr, k, bias=bias, **route)
        if bias is not None:
            load = jnp.sum(experts.reshape(-1)[:, None]
                           == jnp.arange(Wr.shape[1])[None, :], axis=0,
                           dtype=jnp.int32)
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
        rung = rung_of(jnp.sum(sizes), R)
        xs_gate, xs_up = _rows_of_tokens(x.astype(Wg.dtype), order, inv,
                                         held, rung, k)
    with jax.named_scope("experts"):
        h = _gate(grouped_matmul(xs_gate, Wg, sizes),
                  grouped_matmul(xs_up, Wu, sizes), rung)
        o = grouped_matmul(h, Wd, sizes)
    with jax.named_scope("combine"):
        y = _tokens_of_rows(o, jnp.where(held, gates, 0.0), order, inv,
                            held, rung, k)
    out = (y, lax.stop_gradient(sizes.astype(jnp.float32)))
    if bias is not None:
        out += (lax.stop_gradient(load.astype(jnp.float32)),)
    return out
