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
import itertools
import operator
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..observe import record_attention_dispatch, record_flash_tiles

_NEG_INF = -1e30


def _causal_mask(sq, sk, q_off=0, k_off=0, dtype=jnp.float32,
                 transposed=False, window=None):
    """Additive mask, (sq, sk), or (sk, sq) for scores held keys x queries.
    With `window` a query sees its last `window` keys, itself among them:
    key positions at or under its own and within window - 1 of it."""
    shape, q_ax = ((sk, sq), 1) if transposed else ((sq, sk), 0)
    q_pos = q_off + lax.broadcasted_iota(jnp.int32, shape, q_ax)
    k_pos = k_off + lax.broadcasted_iota(jnp.int32, shape, 1 - q_ax)
    hidden = k_pos > q_pos
    if window is not None:
        hidden = hidden | (q_pos - k_pos >= window)
    return jnp.where(hidden, _NEG_INF, 0.0).astype(dtype)


# ======================= 1. reference ====================================

def block_diffusion_visible(seq, block):
    """(seq, seq) booleans: whether query i sees key j under the mask of
    block-diffusion training. The sequence is [noised ; clean], two halves
    of S = seq / 2 positions in blocks of `block`; with blk(i) =
    (i mod S) // block a noised query sees the noised keys of its own block
    and the clean keys of the blocks before it, a clean query the clean
    keys of its own block and of those before it, and no noised key."""
    half = seq // 2
    assert seq == 2 * half and half % block == 0, (seq, block)
    pos = jnp.arange(seq)
    noised, blk = pos < half, (pos % half) // block
    qn, kn = noised[:, None], noised[None, :]
    qb, kb = blk[:, None], blk[None, :]
    return jnp.where(qn, jnp.where(kn, kb == qb, kb < qb),
                     _not(kn) & (kb <= qb))


def attention_reference(q, k, v, causal=False, scale=None, window=None,
                        block_diffusion=None):
    """q,k,v: (B, H, S, D). Returns (B, H, Sq, D). `window` (causal only):
    a query sees its last `window` keys. `block_diffusion` (no other
    mask): the block length of `block_diffusion_visible`'s mask."""
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if block_diffusion is not None:
        assert not causal and window is None and q.shape[2] == k.shape[2]
        s = jnp.where(block_diffusion_visible(q.shape[2], block_diffusion),
                      s, jnp.asarray(_NEG_INF, s.dtype))
    if causal:
        s = s + _causal_mask(q.shape[2], k.shape[2], dtype=s.dtype,
                             window=window)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


# ======================= 2. flash attention ==============================
# Online softmax over K blocks, blockwise-recompute backward (no S matrix
# ever materialized). A grid step holds a LARGE (block_q, block_k) tile —
# per-grid-step overhead is real — and works through it in BANDS: the
# forward takes `band` query rows at a time against the key columns the
# band may see, the backward `band` key rows against the query columns
# that may see them. On the tile the causal diagonal crosses, a band's
# columns stop at the diagonal: sub-tiles above it are never multiplied,
# exponentiated or masked, sub-tiles under it take no mask, and only the
# band x band sub-tile on the diagonal adds one. `flash_plan` is the one
# place that picks blocks and band from (sq, sk, d, causal, dtype).
#
# Measured on one TPU v5e (PR 26; `chiprun -- python3 tools/flash_bench.py
# [--shape ... --causal 0|1 --bands ...]`: device time of the Mosaic call,
# median of 30 in a profiler trace; "before" is the parent commit's kernel
# under the same script; tables in PERF.md section 6), bf16, ms a call:
#   (4, 16, 1024, 64) causal, the GPT-2-medium training shape:
#     forward   one 1024 x 1024 tile, mask hoisted (before)  0.371
#               bands of 128 / 256 / 512 / one band   0.182 / 0.159 / 0.163 / 0.217
#     backward  512-blocks, 3 of 4 tiles (before)            0.476
#               bands of 128 / 256 / 512              0.354 / 0.364 / 0.396
#   the same, not causal: forward 0.365 -> 0.208 (256), backward
#     0.622 -> 0.525 (128) / 0.500 (256); D = 128 (8, 16, 1024, 128) causal:
#     forward 0.714 -> 0.320 (256), backward 0.973 -> 0.619 (128) / 0.640.
#   K streamed, (2, 8, 2048, 64) / (1, 8, 4096, 64) causal forward: 0.215 /
#     0.340 before; bands of 128 on the diagonal tile and tiles under it
#     whole 0.218 / 0.339, bands of 256 0.238 / 0.360, every tile in bands
#     0.240 / 0.406: the running (m, l, acc) round trip through VMEM, once
#     a band a step, costs what the skipped sub-tiles save.
# A band of 256 rows keeps each key tile in the MXU for twice the rows of
# one of 128, which is worth more to the forward than the sub-tiles 128
# would skip (36 of 64 against 10 of 16). The backward's matmuls are deeper
# and 128 is 3 % faster a call under a causal mask, but its kernel is twice
# the instructions to trace and lower, once a layer: at 24 layers about a
# second of set-up against 0.24 ms a step, so the backward takes 256 too.
# None = blocks from flash_plan; an explicit block is honoured if it tiles.
DEFAULT_BLOCK_Q = None
DEFAULT_BLOCK_K = None

# What a kernel's name ends in when it works under a sliding window, or
# under the block-diffusion mask: the HLO instruction and its `op_name` tell
# such a call from a causal one.
WINDOW_SUFFIX = "_win"
BLOCKDIFF_SUFFIX = "_bd"

# Largest tile a grid step holds. Bands are lane multiples (the backward
# slices its per-row statistics along lanes); a block that no band divides
# runs as one band.
_BLOCK_TARGET = 1024
# Without bands a backward step holds ~3x the forward's tiles (q/k/v/do and
# four score-sized temporaries): 1024-blocks can overflow the 16 MB of
# scoped VMEM (D = 128, fp32), so such a block is refit at or below 512.
_UNBANDED_BWD_CAP = 512


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


class FlashTiles(NamedTuple):
    """One direction's schedule. `band` 0: a block is worked whole.
    visited / masked / square count sub-tiles a (batch x head) row, in
    units of band x band (block_q x block_k when band is 0): how many the
    kernel computes, how many of those add a mask, and the whole score
    square."""
    block_q: int
    block_k: int
    band: int
    visited: int
    masked: int
    square: int


class FlashPlan(NamedTuple):
    """`fwd` None: no block tiles the call, it takes the reference path
    (`ok` False). `bwd` None: the backward takes the blockwise XLA path.
    `fused`: `held` is every row, so the backward is one kernel (dq
    accumulated in VMEM) and no `_dq` call. `window`: the sliding window
    the schedules were made for (None: none, or one that reaches every
    key); `skipped` (forward, backward): sub-tiles of the blocks at or
    under the diagonal that lie wholly left of the window, which no grid
    step computes or fetches.
    `block_diffusion`: the block length of the block-diffusion mask the
    schedules were made for (None: another mask); `skipped` then counts
    every sub-tile of the score square that no grid step computes.
    `steps` (forward, backward): (grid steps a (batch x head) row, how many
    of them work no tile), summed over the backward's calls. `held`: the
    query rows, the last of a row, whose dq the backward's sweep over k
    blocks accumulates in VMEM beside dk and dv (`_held_rows`; under the
    causal mask the last rows meet the most tiles); a `_dq` call makes dq
    for the rows before them."""
    fwd: FlashTiles | None
    bwd: FlashTiles | None
    fused: bool
    window: int | None = None
    skipped: tuple = (0, 0)
    block_diffusion: int | None = None
    steps: tuple = ((0, 0), (0, 0))
    held: int = 0

    @property
    def ok(self):
        return self.fwd is not None


def _tile_counts(sq, sk, uq, uk, causal):
    """(visited, masked, square) over uq x uk sub-tiles: a sub-tile exists
    when its first column is at or under its last row, and takes a mask
    unless its last column is at or under its first row."""
    rows, cols = sq // uq, sk // uk
    if not causal:
        return rows * cols, 0, rows * cols
    visited = masked = 0
    for i in range(rows):
        seen = min(cols, (i * uq + uq - 1) // uk + 1)
        visited += seen
        masked += seen - min(cols, (i * uq + 1) // uk)
    return visited, masked, rows * cols


def _grid_steps(fwd, bwd, sq, held, count):
    """FlashPlan.steps. `count(t, rows)` at the blocks of schedule t: (the
    grid steps a row of a pass that sweeps the first `rows` query rows by q
    block: forward, dq; of one that sweeps by k block: dkv, the backward's
    sweep; the tiles worked in the first `rows` rows). The backward sweeps
    every tile by k block and has a `_dq` call sweep the rows before the
    `held` ones by q block."""
    by_q, _, worked = count(fwd, sq)
    forward = by_q, by_q - worked
    if bwd is None:
        return forward, (0, 0)
    _, by_k, worked = count(bwd, sq)
    by_q, _, rest = count(bwd, sq - held)
    steps = by_k + by_q
    return forward, (steps, steps - worked - rest)


def _band(bq, bk, bands):
    """The first of `bands` that divides both blocks; 0: none does."""
    return next((t for t in bands if bq % t == 0 and bk % t == 0), 0)


def _tiles(sq, sk, bq, bk, causal, bands):
    """The schedule at blocks (bq, bk), in the first of `bands` that
    divides both."""
    band = _band(bq, bk, bands)
    return FlashTiles(bq, bk, band, *_tile_counts(
        sq, sk, band or bq, band or bk, causal))


# ---- a sliding window under the causal mask ---------------------------------
# A query sees its last `window` keys, itself among them. Of a q block's row
# of (block_q, block_k) tiles only those the band of seen keys touches are
# grid steps at all: the grid's streamed axis is as long as the most tiles a
# block's band touches (`_window_steps`), it counts from the first such tile
# (`_k_range` / `_q_range`), and the index maps clamp to the last, so a tile
# wholly left of the window is neither computed nor fetched. Where blocks
# are square and the window a multiple of them, the tile on the window's
# left edge is the mirror of the one on the diagonal ("edge": bands stop at
# it, one band x band mask); elsewhere a tile the window's edge or the
# diagonal crosses goes whole under one mask built from the step's offsets.

def _k_range(j, bq, bk, nk, window, mx=max, mn=min):
    """(first, last) k block that some row of q block j sees; `mx`, `mn`:
    jnp's where j is traced."""
    return (mx(j * bq - window + 1, 0) // bk,
            mn(((j + 1) * bq - 1) // bk, nk - 1))


def _q_range(kb, bq, bk, nq, window, mx=max, mn=min):
    """(first, last) q block some of whose rows see k block kb."""
    return (mn((kb * bk) // bq, nq - 1),
            mn(((kb + 1) * bk + window - 2) // bq, nq - 1))


def _window_steps(sq, sk, bq, bk, window):
    """(k tiles a q block's sweep holds at most, q tiles a k block's)."""
    nq, nk = sq // bq, sk // bk
    span = lambda rng, n, m: max(
        hi - lo + 1 for lo, hi in (rng(i, bq, bk, m, window)
                                   for i in range(n)))
    return span(_k_range, nq, nk), span(_q_range, nk, nq)


def _not(b):
    return (not b) if isinstance(b, bool) else jnp.logical_not(b)


def _window_kinds(j, kb, bq, bk, nq, nk, window):
    """{kind: whether tile (q block j, k block kb) is worked as that kind}
    under a causal window, for python numbers and for traced ones alike;
    a tile of no kind is not visited. "full": no mask; "diag": the square
    tile on the diagonal, bands stop at it; "edge": the square tile on the
    window's left edge, its mirror; "crossed": one mask over the tile."""
    live = (kb * bk <= j * bq + bq - 1) & ((kb + 1) * bk - 1 > j * bq - window) \
        & (j <= nq - 1) & (kb <= nk - 1)
    square = bq == bk
    if square and window % bq == 0:
        n = window // bq
        return {"diag": live & (kb == j), "edge": live & (j - kb == n),
                "full": live & (kb < j) & (j - kb < n)}
    whole = ((kb + 1) * bk - 1 <= j * bq) & (j * bq + bq - 1 - kb * bk < window)
    if square and bq <= window:
        off = live & (kb != j)
        return {"diag": live & (kb == j), "full": off & whole,
                "crossed": off & _not(whole)}
    return {"full": live & whole, "crossed": live & _not(whole)}


def _window_counts(sq, sk, bq, bk, band, window):
    """(visited, masked, square, skipped) in sub-tiles of band x band
    (block_q x block_k when band is 0), as the kernels work the tiles:
    a "diag" or an "edge" tile of n bands computes n (n + 1) / 2 and masks
    n, a "crossed" one computes and masks all of its own."""
    nq, nk = sq // bq, sk // bk
    n = bq // band if band else 1
    per = n * (bk // band if band else 1)
    visited = masked = skipped = 0
    for j in range(nq):
        for kb in range(min(nk, ((j + 1) * bq - 1) // bk + 1)):
            kinds = _window_kinds(j, kb, bq, bk, nq, nk, window)
            kind = next((k for k, on in kinds.items() if on), None)
            if kind is None:
                skipped += per
            elif kind == "full":
                visited += per
            elif kind == "crossed":
                visited, masked = visited + per, masked + per
            else:
                visited, masked = visited + n * (n + 1) // 2, masked + n
    return visited, masked, (sq // (band or bq)) * (sk // (band or bk)), \
        skipped


# ---- the block-diffusion mask -------------------------------------------------
# The sequence is [noised ; clean], S positions each, in blocks of b
# (`block_diffusion_visible`). In square tiles that tile S and that b
# divides, with nh = S / tile, tile (q block j, k block kb) of the 2 nh x
# 2 nh is one of: "nn" (noised x noised, kb == j: block-diagonal inside);
# "nc" (noised x clean on the quadrant's diagonal, kb == j + nh: blocks
# strictly before the query's); "cc" (clean x clean on the diagonal: blocks
# at or before); "full" (under the diagonal of those two quadrants: no
# mask); or nothing (above a diagonal, off it in noised x noised, all of
# clean x noised): no grid step. The grid of a pass is a TABLE of the tiles
# it works (`_bd_sweeps`), one grid step each and none empty, that the
# index maps and the kernel read from scalar memory: (batch x head, step).
# A noised q block's "nn" tile is no step of its own: it rides in the step
# of the block's "nc" tile, whose bands take the block's own noised keys
# into the same softmax (forward) or work both tiles' key bands against the
# one q block (backward). A q block's sweep is the whole tiles left of its
# diagonal, then its diagonal tile: every row of a sweep's first tile sees
# a key. A k block's sweep (dk, dv) is over a PAIR, clean k block nh + c
# and noised k block c, both resident: the noised q blocks from c on ("nc"
# with "nn" first), then the clean ones. Where b divides the band too, a
# diagonal tile goes in bands that stop at the diagonal under one band x
# band mask ("nn": the diagonal sub-tiles alone); else a band meets the
# whole tile under one mask. A diagonal tile is the last of its q block's
# sweep, so the forward's bands write the output rows themselves (no store
# of the running state that the next band's load would wait behind), and
# its bands are traced a stage apart (`_staggered`; the backward's abreast).
#
# Measured on one TPU v5e (PR 37; `tools/flash_bench.py --shape 1,32,8192,128
# --block-diffusion 4`, the SDAR cell's call: tiles of 1024, nh = 4, bf16;
# ms a call, median of 30 in a profiler trace):
#   forward   grid (bh, 8, 5), 16 of 40 steps a head empty, "nn" a step
#             of its own (before)                                      3.160
#             the table, 20 steps, "nn" riding, bands in turn          2.908
#             + the bands write the output                             2.653
#             + stages abreast 2.505; staggered 2.448 (by two stages 2.656)
#             bands of 256 / 512 in place of 128       2.606 / 2.871 (worse)
#             the columns left of the diagonal as one product a key chunk
#             of 128 (all the rows under it), not one a band   2.806 (worse)
#   backward  dq (bh, 8, 5) + dkv (bh, 8, 8), 16 + 40 steps empty (before)
#                                                               7.905 (both)
#             the tables, 20 + 20 steps, k blocks in pairs   3.242 + 3.472
#             + bands abreast on the diagonal tiles          2.891 + 3.472
#             (staggered 2.987 + 3.472; bands of 128 2.961 + 3.300: half the
#             arithmetic on "nn", twice the instructions to trace and lower)
# A whole tile's dk / dv runs at the MXU's peak (4 x 1024 x 1024 x 128
# multiply-adds in 5.4 us), so only fewer sub-tiles would move that pass; the
# forward's diagonal tiles are at 3.8 us against a whole tile's 3.9 with 40
# of its 64 sub-tiles: still the place furthest from its arithmetic.

class BdStep(NamedTuple):
    """One grid step of a block-diffusion pass: tile (q block `q`, clean k
    block `k`) worked as `kind` ("full", "cc", or "nc": the last with the
    "nn" tile (q, q) riding along); `first` / `last` of its sweep, where
    the accumulators in scratch start and are written out."""
    q: int
    k: int
    kind: str
    first: bool
    last: bool

    def tiles(self):
        """The (q block, k block, kind) tiles the step works."""
        own = (self.q, self.k, self.kind)
        return (own, (self.q, self.q, "nn")) if self.kind == "nc" else (own,)


# what a step's kind reads in the kernels' table, and the table's rows
_BD_STEP_KINDS = ("full", "cc", "nc")
_BD_Q, _BD_K, _BD_KIND, _BD_FIRST, _BD_LAST = range(5)


def _bd_kind(j, kb, nh):
    """The one kind of tile (q block j, k block kb), or None."""
    return next((k for k, on in _bd_kinds(j, kb, nh).items() if on), None)


def _bd_sweeps(nh):
    """(the q blocks' sweeps, the k pairs' sweeps) as lists of BdStep, in
    grid order: every tile of some kind once a list, a sweep's steps
    consecutive. By q block the k blocks ascend, so the diagonal tile comes
    last; by k pair the q blocks ascend, so "nc" comes first."""
    tiles = [(j, kb, _bd_kind(j, kb, nh))
             for j in range(2 * nh) for kb in range(nh, 2 * nh)]
    tiles = [t for t in tiles if t[2] is not None]

    def sweeps(key):
        order = sorted(tiles, key=key)
        own = [key(t)[0] for t in order]
        return [BdStep(j, kb, kind, i == 0 or own[i - 1] != own[i],
                       i == len(own) - 1 or own[i + 1] != own[i])
                for i, (j, kb, kind) in enumerate(order)]

    return sweeps(lambda t: (t[0], t[1])), sweeps(lambda t: (t[1], t[0]))


def _bd_table(sweep):
    """A sweep as the kernels read it: int32 (5, steps), rows _BD_Q .. ., a
    constant of the traced program (numpy: nothing goes to the device while
    the call is traced)."""
    return np.array(
        [[s.q for s in sweep], [s.k for s in sweep],
         [_BD_STEP_KINDS.index(s.kind) for s in sweep],
         [s.first for s in sweep], [s.last for s in sweep]], np.int32)


def _bd_kinds(j, kb, nh):
    """{kind: whether tile (q block j, k block kb) is worked as that kind}
    under the block-diffusion mask, for python numbers and traced ones."""
    qn, kn = j < nh, kb < nh
    inside = (j < 2 * nh) & (kb < 2 * nh)
    return {"nn": qn & (kb == j), "nc": qn & (kb == j + nh),
            "cc": _not(qn) & (kb == j) & inside,
            "full": _not(kn) & inside & (
                (qn & (kb < j + nh)) | (_not(qn) & (kb < j)))}


def _bd_tile_mask(kind, rows, cols, block, q0=0, k0=0, transposed=False):
    """Additive mask of `rows` queries from q0 on against `cols` keys from
    k0 on, both counted from the start of a tile of kind "nn", "nc" or
    "cc" that `block` divides: (rows, cols), or (cols, rows) for scores
    held keys x queries."""
    shape, q_ax = ((cols, rows), 1) if transposed else ((rows, cols), 0)
    pow2 = block & (block - 1) == 0

    def blk(off, axis):
        pos = off + lax.broadcasted_iota(jnp.int32, shape, axis)
        # (the shift: what the TPU's vector unit surely has)
        return lax.shift_right_logical(pos, block.bit_length() - 1) \
            if pow2 else lax.div(pos, jnp.int32(block))
    qb, kb = blk(q0, q_ax), blk(k0, 1 - q_ax)
    seen = {"nn": kb == qb, "nc": kb < qb, "cc": kb <= qb}[kind]
    return jnp.where(seen, 0.0, _NEG_INF).astype(jnp.float32)


def _bd_counts(nh, n, banded):
    """(visited, masked) sub-tiles of the 2 nh x 2 nh tiles of n x n
    sub-tiles each, counted over the tiles as the kernels work them."""
    visited = masked = 0
    for j in range(2 * nh):
        for kb in range(2 * nh):
            kind = _bd_kind(j, kb, nh)
            if kind == "full":
                visited += n * n
            elif kind and not banded:
                visited, masked = visited + n * n, masked + n * n
            elif kind:
                visited += n if kind == "nn" else n * (n + 1) // 2
                masked += n
    return visited, masked


def _bd_plan(seq, d, dtype, block_q, block_k, block):
    """flash_plan's schedules under the block-diffusion mask: square tiles
    that tile a half and that the block length divides, else no plan (the
    reference path); the bands of a streamed K."""
    half = seq // 2
    assert seq == 2 * half and half % block == 0, (seq, block)
    none = FlashPlan(None, None, False)
    if block_q is None and block_k is None:
        tile = next((t for t in range(min(_BLOCK_TARGET, half) // 8 * 8,
                                      min(128, half) - 1, -8)
                     if half % t == 0 and t % block == 0), None)
    else:
        tile = block_q or block_k
        if (block_k or tile) != tile or tile % 8 or half % tile \
                or tile % block:
            tile = None
    if tile is None:
        return none
    nh = half // tile

    def tiles(bands):
        band = _band(tile, tile, bands)
        u = band or tile
        visited, masked = _bd_counts(nh, tile // u, u % block == 0)
        square = (seq // u) ** 2
        return FlashTiles(tile, tile, band, visited, masked, square), \
            square - visited

    fwd, fwd_skipped = tiles((128,))
    bwd, bwd_skipped = tiles((256, 128))
    if not bwd.band and tile > _UNBANDED_BWD_CAP:
        return none
    # the `_bd` calls hold every row's dq, or none
    held = seq if _held_rows(seq, d, dtype, tile) == seq else 0
    by_q, by_k = map(len, _bd_sweeps(nh))   # the same tiles in two orders
    return FlashPlan(
        fwd, bwd, held == seq, None, (fwd_skipped, bwd_skipped), block,
        _grid_steps(fwd, bwd, seq, held, lambda t, rows: (
            (by_q, by_k, by_q) if rows else (0, by_k, 0))), held)


def flash_window(window, causal, sk):
    """The window a call is scheduled for: None where there is none or it
    reaches every key (the plain causal program, to the instruction)."""
    if window is None:
        return None
    assert causal and window >= 1, \
        f"a sliding window needs the causal mask and a width >= 1: {window}"
    return None if window >= sk else int(window)


def flash_plan(sq, sk, d, causal, dtype, block_q=None, block_k=None,
               window=None, block_diffusion=None):
    """The tile schedule of one flash_attention call, forward and backward,
    from what the call can see. Blocks: an explicit block is honoured when
    it tiles its sequence on 8-sublane alignment (else ok=False -> the
    reference path); None takes the largest evenly-tiling block at or
    under 1024 (under a sliding window: at or under the window, so that
    the work follows it), so S <= 1024 is one grid step a (batch x head)
    row and S = 384 or 896 still run the kernel. `block_diffusion`: the
    block length of the block-diffusion mask (`_bd_plan`), which goes with
    no other mask."""
    if block_diffusion is not None:
        assert not causal and window is None and sq == sk, \
            "the block-diffusion mask is the call's only mask, over one " \
            f"doubled sequence: causal={causal} window={window} {sq}x{sk}"
        return _bd_plan(sq, d, dtype, block_q, block_k, int(block_diffusion))
    window = flash_window(window, causal, sk)
    target = _BLOCK_TARGET if window is None \
        else min(_BLOCK_TARGET, max(128, window))

    def pick(s, explicit):
        if explicit is None:
            # under a window first a block that divides it too: then the
            # window's edge falls on tile boundaries ("edge" tiles)
            return window and next(
                (b for b in range(target - target % 8, 127, -8)
                 if s % b == 0 and window % b == 0), None) \
                or _fit_block(s, target)
        b = min(explicit, s)
        return b if s % b == 0 and b % 8 == 0 else None

    bq, bk = pick(sq, block_q), pick(sk, block_k)
    if bq is None or bk is None:
        return FlashPlan(None, None, False)
    if window is not None:
        return _window_plan(sq, sk, d, dtype, bq, bk, window)
    # band heights as measured (comment above): 256, but 128 on the
    # forward's diagonal tile once K streams over the grid
    bwd_bands = (256, 128)
    fwd = _tiles(sq, sk, bq, bk, causal, bwd_bands if bk == sk else (128,))
    bwd = _tiles(sq, sk, bq, bk, causal, bwd_bands)
    if not bwd.band and max(bq, bk) > _UNBANDED_BWD_CAP:
        # a capped block may stop tiling evenly (1016 -> 512 at S=1016), so
        # refit rather than crash the blockwise fallback on a non-divisor
        bq = _fit_block(sq, min(bq, _UNBANDED_BWD_CAP))
        bk = _fit_block(sk, min(bk, _UNBANDED_BWD_CAP))
        bwd = _tiles(sq, sk, bq, bk, causal, bwd_bands) if bq and bk \
            else None
    held = _held_rows(sq, d, dtype, (bwd or fwd).block_q)
    nk = lambda t: sk // t.block_k
    return FlashPlan(fwd, bwd, held == sq, steps=_grid_steps(
        fwd, bwd, sq, held, lambda t, rows: (
            rows // t.block_q * nk(t), sq // t.block_q * nk(t),
            _tile_counts(rows, sk, t.block_q, t.block_k, causal)[0])),
        held=held)


def _held_rows(sq, d, dtype, block_q):
    """The query rows, a multiple of `block_q` and at most `sq`, whose dq the
    backward keeps in VMEM over a sweep by k block: an f32 accumulator and
    the double-buffered output row, within _FUSED_DQ_BYTES_CAP as it counts
    them (D lanes a row; _held_compiler_params gives the call the lanes a
    row of D < 128 takes beyond that)."""
    row = d * (4 + 2 * jnp.dtype(dtype).itemsize)
    return min(sq, _FUSED_DQ_BYTES_CAP // row // block_q * block_q)


def _window_plan(sq, sk, d, dtype, bq, bk, window):
    """flash_plan's schedules under a window: the same bands; a backward
    whose blocks no band divides and that are too large to go whole takes
    the blockwise XLA path."""
    bwd_bands = (256, 128)

    def tiles(bands):
        band = _band(bq, bk, bands)
        *counts, skipped = _window_counts(sq, sk, bq, bk, band, window)
        return FlashTiles(bq, bk, band, *counts), skipped

    # the forward's bands as without a window (on the chip, PR 32, at
    # (1, 32, 8192, 128), W = 1024: 128 2.25 ms, 256 2.68, 512 2.54;
    # blocks of 512 3.00, of 256 6.06)
    fwd, fwd_skipped = tiles(bwd_bands if bk == sk else (128,))
    bwd, bwd_skipped = tiles(bwd_bands)
    if not bwd.band and max(bq, bk) > _UNBANDED_BWD_CAP:
        bwd, bwd_skipped = None, 0
    held = _held_rows(sq, d, dtype, bq)
    nq, nk = sq // bq, sk // bk
    k_steps, q_steps = _window_steps(sq, sk, bq, bk, window)
    worked = [sum(any(_window_kinds(j, kb, bq, bk, nq, nk, window).values())
                  for kb in range(nk)) for j in range(nq)]
    return FlashPlan(fwd, bwd, held == sq, window, (fwd_skipped, bwd_skipped),
                     steps=_grid_steps(fwd, bwd, sq, held, lambda t, rows: (
                         rows // bq * k_steps, nk * q_steps,
                         sum(worked[:rows // bq]))), held=held)


try:  # import here so CPU-only environments still import the module
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    _HAS_PALLAS = True
except ImportError:  # pragma: no cover
    _HAS_PALLAS = False


# TPU Pallas needs the last two block dims (sublane, lane) aligned; the
# forward's per-row stats (lse, running m/l) are carried as (rows,
# _STAT_LANES) with the value replicated across lanes — rows on sublanes
# means reading [:, :1] yields the column vector with no relayout.
_STAT_LANES = 8


def _total(parts):
    """Sum of a non-empty iterable of arrays, in the order given."""
    return functools.reduce(operator.add, parts)


def _dot_nt(a, b):
    """a @ b.T on the MXU as one contraction of the last dimensions, f32
    accumulation."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


# A tile's bands are generators that yield between their stages (a band's
# score products; its statistics and exponentials; its products with V or
# with q / do / k; its write), and the order their stages are traced in is
# the order the compiler keeps (PR 26: statement order moves a kernel's
# time by 3 to 8 %). `_in_turn` is a band after another, every kernel's
# order but for the diagonal tiles of the block-diffusion mask.

def _in_turn(bands):
    for band in bands:
        for _ in band:
            pass


def _abreast(bands):
    """Every band's first stage, then every band's second, and so on."""
    for _ in itertools.zip_longest(*bands):
        pass


def _staggered(bands):
    """A band starts a stage behind the band before it."""
    waiting, running = list(bands), []
    while waiting or running:
        if waiting:
            running.append(waiting.pop(0))
        running = [b for b in running if next(b, running) is not running]


def _visit_by_diagonal(causal, square, single, j, kb, block_q, block_k,
                       visit, window=None, nq=None, nk=None):
    """Run `visit(kind)` for this grid step's (block_q, block_k) tile:
    "full" (no mask), "diag" (square blocks, the tile on the diagonal:
    bands stop at it), "crossed" (blocks that are not square: one mask
    over the tile, built from the step's offsets), or nothing for a tile
    above the diagonal. The DMA for skipped tiles is elided too:
    _causal_kv_map / _causal_q_map re-address the last needed block.
    Under a `window` the kinds are _window_kinds'."""
    if window is not None:
        for kind, on in _window_kinds(j, kb, block_q, block_k, nq, nk,
                                      window).items():
            pl.when(on)(functools.partial(visit, kind))
    elif not causal:
        visit("full")
    elif square and single:
        visit("diag")
    elif square:
        pl.when(kb < j)(lambda: visit("full"))
        pl.when(kb == j)(lambda: visit("diag"))
    else:
        needed = kb * block_k <= j * block_q + block_q - 1
        under = (kb + 1) * block_k - 1 <= j * block_q
        pl.when(needed & under)(lambda: visit("full"))
        pl.when(needed & jnp.logical_not(under))(lambda: visit("crossed"))


def _visit_bd_step(tab_ref, t, visit):
    """Run `visit(kind)` for the kind the table gives step t."""
    for n, kind in enumerate(_BD_STEP_KINDS):
        pl.when(tab_ref[_BD_KIND, t] == n)(functools.partial(visit, kind))


def _flash_fwd_bd_kernel(tab_ref, q_ref, k_ref, v_ref, kn_ref, vn_ref, *rest,
                         **kw):
    """_flash_fwd_kernel on grid (batch*heads, steps of _bd_sweeps' q
    sweeps): `tab_ref` the table in scalar memory, k_ref / v_ref the
    step's clean k block, kn_ref / vn_ref the q block's own noised one."""
    _flash_fwd_kernel(q_ref, k_ref, v_ref, *rest, **kw,
                      sweep=(tab_ref, kn_ref, vn_ref))


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch,
                      nq, nk, block_q, block_k, band, causal, window,
                      steps, bd=None, sweep=None):
    """Grid: (batch*heads, q_blocks, k_blocks) — K/V blocks STREAM through
    VMEM one (block_k, D) tile at a time (no whole-row residency, so
    sequence length is bounded by HBM, not VMEM). Inside a step the query
    rows go `band` at a time: one softmax over every column the band may
    see in this tile (scores of the unmasked columns and of the diagonal
    sub-tile share one row maximum, so a band pays the statistics once,
    not once a sub-tile). With nk > 1 the online-softmax state (acc, m, l)
    lives in VMEM scratch, which persists across the k grid dimension:
    `steps` entries, nk of them, or under a `window` as many as a q
    block's window reaches, counted from the first k block it reaches.
    Under the block-diffusion mask (`bd`: its block length) the grid is
    (batch*heads, steps) and `sweep` holds what tells a step its tile
    (_flash_fwd_bd_kernel): the state starts where the table says a sweep
    does; a band of an "nc" tile takes three pieces into its one softmax
    (the clean columns left of the diagonal, the diagonal sub-tile, and the
    diagonal sub-tile of the q block's noised keys, "nn"); and a diagonal
    tile, the last of its sweep, writes the output rows band by band from
    the state it read, so no step finishes a sweep apart."""
    here = (k_ref, v_ref)
    if bd is not None:
        tab_ref, *noised = sweep
        t = pl.program_id(1)
        j = kb = None
        first = lambda: tab_ref[_BD_FIRST, t] == 1
    else:
        j = pl.program_id(1)
        step = kb = pl.program_id(2)
        if window is not None:
            kb = step + _k_range(j, block_q, block_k, nk, window,
                                 jnp.maximum, jnp.minimum)[0]
        first, last = lambda: step == 0, lambda: step == steps - 1
    square = block_q == block_k
    f32 = jnp.float32
    if steps > 1:
        acc_ref, m_ref, l_ref = scratch

        @pl.when(first())
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

    # one mask serves every sub-tile on the diagonal: square, aligned
    diag_mask = _causal_mask(band or block_q, band or block_q) \
        if causal and square else None
    # the tile on the window's left edge: its mirror, as static
    edge_mask = _causal_mask(band or block_q, band or block_q, q_off=window,
                             window=window) \
        if window is not None and square and window % block_q == 0 else None

    def finish(rows, m, l, acc):
        l = jnp.maximum(l, 1e-20)
        o_ref[0, rows, :] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, rows, :] = jnp.broadcast_to(
            m + jnp.log(l), (acc.shape[0], _STAT_LANES))

    def bd_cols(kind, kv, rows_per, lo, hi, mask):
        """A band's pieces of a diagonal tile of the block-diffusion mask:
        in bands that the block length divides `mask`, band x band, serves
        every band; else the band meets the whole tile under one mask."""
        if mask is None:
            return [(kv, 0, block_k, _bd_tile_mask(kind, rows_per, block_k,
                                                   bd, q0=lo))]
        return ([(kv, 0, lo, None)] if lo and kind != "nn" else []) \
            + [(kv, lo, hi, mask)]

    def visit(kind):
        # with K streamed a tile under the diagonal goes whole: its bands
        # would each reload the key tile into the MXU for no column saved
        rows_per = block_q if kind == "full" and steps > 1 \
            else band or block_q
        bd_diag = bd is not None and kind != "full"
        bd_masks = {k: _bd_tile_mask(k, rows_per, rows_per, bd)
                    for k in ((kind, "nn") if kind == "nc" else (kind,))} \
            if bd_diag and rows_per % bd == 0 else {}
        bands = [work(kind, r, rows_per, bd_masks)
                 for r in range(block_q // rows_per)]
        # a diagonal tile of the block-diffusion mask: a band a stage behind
        # the one before it (measured with the mask's schedule, above)
        (_staggered if bd_diag else _in_turn)(bands)

    def work(kind, r, rows_per, bd_masks):
        # under the block-diffusion mask a diagonal tile is the last of
        # its sweep: its bands write the output themselves
        ends = bd is not None and kind != "full"
        lo, hi = r * rows_per, (r + 1) * rows_per
        rows = slice(lo, hi)
        if kind == "diag":
            cols = ([(here, 0, lo, None)] if lo else []) \
                + [(here, lo, hi, diag_mask)]
        elif kind == "edge":
            cols = [(here, lo, hi, edge_mask)] \
                + ([(here, hi, block_k, None)] if hi < block_k else [])
        elif kind == "full":
            cols = [(here, 0, block_k, None)]
        elif bd is not None:
            cols = bd_cols(kind, here, rows_per, lo, hi, bd_masks.get(kind))
            if kind == "nc":
                cols += bd_cols("nn", noised, rows_per, lo, hi,
                                bd_masks.get("nn"))
        else:
            cols = [(here, 0, block_k, _causal_mask(
                rows_per, block_k, q_off=j * block_q + lo,
                k_off=kb * block_k, window=window))]
        # dots run in the INPUT dtype (bf16 inputs → native MXU rate;
        # upcasting to f32 first would run the matmul at the ~4x-slower
        # fp32 rate) and accumulate f32 via preferred_element_type; the
        # softmax/stats stay in f32. q arrives PRE-SCALED (the wrapper
        # folds the softmax scale into q, where XLA fuses it for free —
        # an in-kernel multiply would cost a VPU pass over the scores).
        q = q_ref[0, rows, :]                      # (band, D), scaled
        ss = []
        for (keys, _), a, b, mask in cols:
            s = _dot_nt(q, keys[0, a:b, :])
            ss.append(s if mask is None else s + mask)
        yield
        m_new = functools.reduce(
            jnp.maximum, (jnp.max(s, axis=-1, keepdims=True) for s in ss))
        if steps > 1:
            m_prev = m_ref[rows, :][:, :1]
            m_new = jnp.maximum(m_prev, m_new)
        ps = [jnp.exp(s - m_new) for s in ss]
        l_new = _total(jnp.sum(p, axis=-1, keepdims=True) for p in ps)
        yield
        pv = _total(
            jnp.dot(p.astype(values.dtype), values[0, a:b, :],
                    preferred_element_type=f32)
            for p, ((_, values), a, b, _) in zip(ps, cols))
        yield
        if steps == 1:      # the band has seen every column it may
            finish(rows, m_new, l_new, pv)
            return
        corr = jnp.exp(m_prev - m_new)
        if ends:
            finish(rows, m_new, l_ref[rows, :][:, :1] * corr + l_new,
                   acc_ref[rows, :] * corr + pv)
            return
        acc_ref[rows, :] = acc_ref[rows, :] * corr + pv
        l_ref[rows, :] = jnp.broadcast_to(
            l_ref[rows, :][:, :1] * corr + l_new,
            (rows_per, _STAT_LANES))
        m_ref[rows, :] = jnp.broadcast_to(m_new, (rows_per, _STAT_LANES))

    if bd is not None:
        _visit_bd_step(tab_ref, t, visit)
    else:
        _visit_by_diagonal(causal, square, nq == 1 and nk == 1, j, kb,
                           block_q, block_k, visit, window, nq, nk)

    if steps > 1 and bd is None:
        @pl.when(last())
        def _finish():
            finish(slice(None), m_ref[...][:, :1], l_ref[...][:, :1],
                   acc_ref[...])


def _causal_kv_map(causal, block_q, block_k, nk, window=None):
    """K/V BlockSpec index map for grids with kb innermost after the q
    block index. Causal: kb is CLAMPED to this q block's diagonal block,
    so every fully-masked step re-addresses the last needed block and
    Pallas skips the DMA (the copy only fires when the block index
    changes) — masked K/V tiles are neither computed nor streamed.
    Under a `window` the innermost index counts from the first k block
    the q block's window reaches."""
    if window is not None:
        def wmap(i, j, step):
            first, last = _k_range(j, block_q, block_k, nk, window,
                                   jnp.maximum, jnp.minimum)
            return (i, jnp.minimum(first + step, last), 0)

        return wmap
    if not causal:
        return lambda i, j, kb: (i, kb, 0)

    def kmap(i, j, kb):
        last = jnp.minimum(((j + 1) * block_q - 1) // block_k, nk - 1)
        return (i, jnp.minimum(kb, last), 0)

    return kmap


def _causal_q_map(causal, block_q, block_k, nq=None, window=None):
    """Q-side BlockSpec index map for the dK/dV grid (bh, kb, j): causal
    clamps j UP to the first unmasked q block for kb, so the leading
    masked steps address the same tile and their DMA is elided. Under a
    `window` the innermost index counts from that block and is clamped to
    the last one whose rows still see kb."""
    if window is not None:
        def wmap(i, kb, step):
            first, last = _q_range(kb, block_q, block_k, nq, window,
                                   jnp.maximum, jnp.minimum)
            return (i, jnp.minimum(first + step, last), 0)

        return wmap
    if not causal:
        return lambda i, kb, j: (i, j, 0)

    def qmap(i, kb, j):
        first = (kb * block_k) // block_q
        return (i, jnp.maximum(j, first), 0)

    return qmap


def _bd_maps(nh):
    """Index maps on grid (batch*heads, step) under a sweep's table: the
    step's q block, its clean k block, and the noised k block of the same
    number as the q block (a clean q block: the last noised one, which the
    sweep before it left in place, so nothing is fetched)."""
    return (lambda i, t, tab: (i, tab[_BD_Q, t], 0),
            lambda i, t, tab: (i, tab[_BD_K, t], 0),
            lambda i, t, tab: (i, jnp.minimum(tab[_BD_Q, t], nh - 1), 0))


def _flash_fwd_pallas(q, k, v, causal, scale, tiles, interpret, window=None,
                      bd=None):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    block_q, block_k, band = tiles[:3]
    # fold the softmax scale into q here: XLA fuses the multiply into
    # whatever produced q, so the kernel never spends a VPU pass on it
    qf = (q * scale).astype(q.dtype).reshape(bh, sq, d)
    kf = k.reshape(bh, sk, d)
    vf = v.reshape(bh, sk, d)
    nk = sk // block_k
    nq = sq // block_q
    steps, name = nk, "singa_flash_fwd"
    if bd is not None:
        sweep = _bd_sweeps(nk // 2)[0]
        steps = len(sweep)
        name += BLOCKDIFF_SUFFIX
    elif window is not None:
        steps = _window_steps(sq, sk, block_q, block_k, window)[0]
        name += WINDOW_SUFFIX
    params = dict(nq=nq, nk=nk, block_q=block_q, block_k=block_k, band=band,
                  causal=causal, window=window, steps=steps, bd=bd)
    out_shape = [
        jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        jax.ShapeDtypeStruct((bh, sq, _STAT_LANES), jnp.float32),
    ]
    scratch = [
        pltpu.VMEM((block_q, d), jnp.float32),
        pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
        pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
    ] if steps > 1 else []
    if bd is not None:
        qmap, kmap, knmap = _bd_maps(nk // 2)
        kv_specs = [pl.BlockSpec((1, block_k, d), m)
                    for m in (kmap, kmap, knmap, knmap)]
        out, lse = pl.pallas_call(
            functools.partial(_flash_fwd_bd_kernel, **params),
            name=name,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(bh, steps),
                in_specs=[pl.BlockSpec((1, block_q, d), qmap)] + kv_specs,
                out_specs=[pl.BlockSpec((1, block_q, d), qmap),
                           pl.BlockSpec((1, block_q, _STAT_LANES), qmap)],
                scratch_shapes=scratch),
            out_shape=out_shape,
            interpret=interpret,
            compiler_params=_bd_compiler_params(),
        )(_bd_table(sweep), qf, kf, vf, kf, vf)
        return out.reshape(b, h, sq, d), lse[:, :, 0].reshape(b, h, sq)
    kernel = functools.partial(_flash_fwd_kernel, **params)
    kvmap = _causal_kv_map(causal, block_q, block_k, nk, window)
    out, lse = pl.pallas_call(
        kernel,
        name=name,
        grid=(bh, nq, steps),
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
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d), lse[:, :, 0].reshape(b, h, sq)


def _flash_bwd_bd_kernel(tab_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         delta_ref, *refs, outs, **kw):
    """_flash_bwd_kernel on grid (batch*heads, steps of one of _bd_sweeps'
    lists): "dq" sweeps by q block and takes the q block's noised k block
    as two more inputs; "dkv" and "all" sweep by k pair, k_ref / v_ref and
    the dk / dv blocks holding (noised, clean) of the pair."""
    noised, refs = (refs[:2], refs[2:]) if outs == "dq" else ((), refs)
    _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                      outs=outs, **kw, sweep=(tab_ref,) + tuple(noised))


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      *refs, nq, nk, block_q, block_k, band, causal, scale,
                      outs, window, steps, bd=None, sweep=None, dq_from=0):
    """The backward of one (block_q, block_k) tile, TRANSPOSED: scores are
    held keys x queries, so p.T and ds.T — what dv = p.T @ do and
    dk = ds.T @ q consume — come out of the matmuls as they are, the
    per-row statistics lie along lanes, and only dq = ds @ k contracts the
    leading dimension (one transpose a sub-tile, not two). Key rows go
    `band` at a time against the query columns that may see them.

    `outs` picks the gradients, the grid and the accumulators:
      "all"  grid (bh, k_blocks, q_blocks): s/p/ds computed ONCE a tile
             pair for all three gradients. dq accumulates in a (Sq, D) VMEM
             scratch over the whole sweep of one bh row (TPU grid iteration
             is sequential, so the scratch survives it) and is written once,
             when the row's last tile is in; callers gate this on the
             scratch fitting VMEM (FlashPlan.held). With `dq_from` > 0 the
             scratch holds the q blocks from dq_from on; the tiles of the
             blocks before compute their dq product too and a select drops
             it (with a `pl.when` around the product and its add the LFM2
             cell's sweep read 38.1 ms a call, with the select 31.6, on one
             TPU v5e); the first of `refs` is then the array of the
             "dq" call that made those blocks, which the output dq aliases
             (its rows are left as that call wrote them).
      "dkv"  the same grid, dk/dv only.
      "dq"   grid (bh, q_blocks, k_blocks), dq only, one q block of scratch.
    "dq" + "dkv" recompute the two largest matmuls and the exp, and
    stream every tile twice: the backward of rows too long for any held.

    The grid's last dimension has `steps` entries: every block of the
    streamed side, or under a `window` as many as it reaches, counted from
    the first block it reaches (_k_range, _q_range). Under the
    block-diffusion mask (`bd`) the grid is (bh, steps) and `sweep` holds
    what tells a step its tile (_flash_bwd_bd_kernel); a step of kind "nc"
    works the "nc" tile's key bands and then the "nn" tile's, against the
    one q block."""
    refs = list(refs)
    if dq_from:
        refs.pop(0)
    want_dq, want_dkv = outs != "dkv", outs != "dq"
    dq_ref = refs.pop(0) if want_dq else None
    dk_ref, dv_ref = (refs.pop(0), refs.pop(0)) if want_dkv else (None, None)
    dq_acc = refs.pop(0) if want_dq else None
    dk_acc, dv_acc = refs if want_dkv else (None, None)
    # where a tile's keys lie: (k ref, v ref, their block's leading index,
    # the accumulators' leading index)
    here = noised = (k_ref, v_ref, (0,), ())
    if bd is not None:
        tab_ref, *kvn = sweep
        t = pl.program_id(1)
        j, kb = tab_ref[_BD_Q, t], None
        first = lambda: tab_ref[_BD_FIRST, t] == 1
        last = lambda: tab_ref[_BD_LAST, t] == 1
        if outs == "dq":
            noised = (*kvn, (0,), ())
            dq_first, dq_last, dq_base = first(), last(), 0
        else:
            here, noised = ((k_ref, v_ref, (0, half), (half,))
                            for half in (1, 0))
            dq_first, dq_last, dq_base = t == 0, t == steps - 1, j * block_q
    elif outs == "dq":
        j, kb = pl.program_id(1), pl.program_id(2)
        step = kb
        if window is not None:
            kb = step + _k_range(j, block_q, block_k, nk, window,
                                 jnp.maximum, jnp.minimum)[0]
        dq_first, dq_last = step == 0, step == steps - 1
        dq_base = 0
    else:
        kb, j = pl.program_id(1), pl.program_id(2)
        step = j
        if window is not None:
            j = step + _q_range(kb, block_q, block_k, nq, window,
                                jnp.maximum, jnp.minimum)[0]
        dq_first = (kb == 0) & (step == 0)
        dq_last = (kb == nk - 1) & (step == steps - 1)
        dq_base = (jnp.maximum(j - dq_from, 0) if dq_from else j) \
            * block_q if nq > 1 else 0
    if bd is None:
        first, last = lambda: step == 0, lambda: step == steps - 1
    rows_per = band or block_k
    square = block_q == block_k
    f32 = jnp.float32

    if want_dq:
        @pl.when(dq_first)
        def _init_dq():
            dq_acc[...] = jnp.zeros_like(dq_acc)

    if want_dkv:
        @pl.when(first())
        def _init_dkv():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

    diag_mask = _causal_mask(rows_per, rows_per, transposed=True) \
        if causal and square else None
    edge_mask = _causal_mask(rows_per, rows_per, q_off=window,
                             transposed=True, window=window) \
        if window is not None and square and window % block_q == 0 else None

    def visit(kind):
        if bd is not None and kind == "nc":
            tile("nc", here)
            tile("nn", noised)
        else:
            tile(kind, here)

    def tile(kind, where):
        bd_mask = _bd_tile_mask(kind, rows_per, rows_per, bd,
                                transposed=True) \
            if bd is not None and kind != "full" and rows_per % bd == 0 \
            else None
        bands = [work(kind, where, c, bd_mask)
                 for c in range(block_k // rows_per)]
        # a diagonal tile of the block-diffusion mask: the bands abreast
        # (dq 2.89 ms a call against 3.24 in turn; dk / dv the same)
        (_abreast if bd is not None and kind != "full" else _in_turn)(bands)

    def work(kind, where, c, bd_mask):
        keys, values, at, acc_at = where
        lo, hi = c * rows_per, (c + 1) * rows_per
        if kind == "diag":
            cols = [(lo, hi, diag_mask)] \
                + ([(hi, block_q, None)] if hi < block_q else [])
        elif kind == "edge":
            cols = ([(0, lo, None)] if lo else []) \
                + [(lo, hi, edge_mask)]
        elif kind == "full":
            cols = [(0, block_q, None)]
        elif bd_mask is not None:
            cols = [(lo, hi, bd_mask)] \
                + ([(hi, block_q, None)]
                   if hi < block_q and kind != "nn" else [])
        elif bd is not None:
            cols = [(0, block_q, _bd_tile_mask(
                kind, block_q, rows_per, bd, k0=lo, transposed=True))]
        else:
            cols = [(0, block_q, _causal_mask(
                block_q, rows_per, q_off=j * block_q,
                k_off=kb * block_k + lo, transposed=True,
                window=window))]
        # native-dtype MXU dots (see fwd kernel); p and ds are rounded
        # to the input dtype for their matmuls, standard flash-2
        # practice. q arrives PRE-SCALED, so s matches the forward's
        # lse directly and dk = ds.T @ q_scaled IS the true
        # scale * ds.T @ q; dq gets its factor when it is written.
        band_rows = (slice(lo, hi), slice(None))
        k_blk = keys[at + band_rows]                   # (band, D)
        v_blk = values[at + band_rows]
        dk = dv = None
        for a, b, mask in cols:
            q = q_ref[0, a:b, :]
            do = do_ref[0, a:b, :]
            s = _dot_nt(k_blk, q)                      # (band, b - a)
            if mask is not None:
                s = s + mask
            yield
            p = jnp.exp(s - lse_ref[0, 0, :, a:b])
            dp = _dot_nt(v_blk, do)
            ds = (p * (dp - delta_ref[0, 0, :, a:b])).astype(q.dtype)
            yield
            if want_dkv:
                # summed as they come: collecting a band's products
                # and adding them after the loop read 3 to 8 % slower
                # on the chip (the compiler follows the order given)
                part = jnp.dot(p.astype(do.dtype), do,
                               preferred_element_type=f32)
                dv = part if dv is None else dv + part
                part = jnp.dot(ds, q, preferred_element_type=f32)
                dk = part if dk is None else dk + part
            if want_dq:
                part = lax.dot_general(ds, k_blk, (((0,), (0,)), ((), ())),
                                       preferred_element_type=f32)
                if dq_from:
                    # the product of a q block that is not held is dropped
                    part = jnp.where(j >= dq_from, part, 0.0)
                dq_acc[pl.ds(dq_base + a, b - a), :] += part
            yield
        if want_dkv:
            dk_acc[acc_at + band_rows] += dk
            dv_acc[acc_at + band_rows] += dv

    if bd is not None:
        _visit_bd_step(tab_ref, t, visit)
    else:
        _visit_by_diagonal(causal, square, nq == 1 and nk == 1, j, kb,
                           block_q, block_k, visit, window, nq, nk)

    if want_dq:
        @pl.when(dq_last)
        def _finish_dq():
            dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)

    if want_dkv:
        @pl.when(last())
        def _finish_dkv():
            dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# VMEM the backward may spend on dq for the rows it holds over its sweep by
# k block (FlashPlan.held): the f32 accumulator and the double-buffered
# output rows beside the streamed tiles, counted at D lanes a row — 6 MB
# covers S=8192 at D=64 in bf16; of longer rows the last that fit are held,
# a `_dq` call makes the rest. Blocks are sized so that a call holding this
# much fits a Mosaic call's default scoped VMEM (_SCOPED_VMEM_BYTES).
_FUSED_DQ_BYTES_CAP = 6 * 1024 * 1024
_SCOPED_VMEM_BYTES = 16 * 1024 * 1024


def _held_compiler_params(held, d, dtype):
    """What the sweep that holds `held` rows' dq is compiled under. In VMEM
    a row takes at least 128 lanes, so where D < 128 the rows held take
    more than _FUSED_DQ_BYTES_CAP counts: the call's scoped VMEM grows by
    what they take beyond it (a D = 64 row twice what the cap counts: at
    32 heads x 16,384 x 64, 12,288 rows held, 22 MiB where a described v5e
    reserves 18.3 MB). None where they fit: the default."""
    held_bytes = held * max(d, 128) * (4 + 2 * jnp.dtype(dtype).itemsize)
    if held_bytes <= _FUSED_DQ_BYTES_CAP:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=_SCOPED_VMEM_BYTES
                                + held_bytes - _FUSED_DQ_BYTES_CAP)


def _bd_compiler_params():
    """What every `_bd` call is compiled under. Blocks and FlashPlan.fused
    are sized so that a call on ONE k block a step fits a Mosaic call's
    default 16 MB of scoped VMEM; a `_bd` step holds a second one (the q
    block's noised K / V beside the clean; a pair's K, V, dk, dv and
    accumulators), which is less than what the call held before: twice the
    default bounds it (fp32 at D = 256, and the fused fp32 backward from
    S = 2048 on, pass 16 MB by 0.5 to 11)."""
    return pltpu.CompilerParams(vmem_limit_bytes=32 * 1024 * 1024)


def _flash_bwd_stats(o, lse, do, block_q):
    """(lse, delta) for the backward kernels as (bh, q_blocks, 1, block_q):
    a q block's statistics lie along lanes, one contiguous row a block.
    Loop-invariant across ring hops, so callers may precompute once."""
    b, h, sq, _ = o.shape
    stat = (b * h, sq // block_q, 1, block_q)
    # delta = rowsum(do * o): cheap elementwise, leave to XLA fusion
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    return lse.reshape(stat), delta.reshape(stat)


def _flash_bwd_pallas(q, k, v, o, lse, do, causal, scale, tiles, held,
                      interpret, stats=None, window=None, bd=None):
    """Pallas flash backward: one sweep by k block that makes dk, dv and
    the dq of the last `held` query rows (FlashPlan.held: every row, the
    fused backward), and a `_dq` call for the rows before them, whose
    array the sweep's dq aliases; none held: the `_dq` + `_dkv` pair."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    block_q, block_k, band = tiles[:3]
    nq, nk = sq // block_q, sk // block_k
    # q pre-scaled, as in the forward (kernels consume scaled q; dq gets
    # its own scale factor as it is written, dk inherits it from q itself)
    qf = (q * scale).astype(q.dtype).reshape(bh, sq, d)
    kf, vf = (a.reshape(bh, sk, d) for a in (k, v))
    dof = do.reshape(bh, sq, d)
    lsef, delta = stats if stats is not None else _flash_bwd_stats(
        o, lse, do, block_q)
    if bd is not None:
        return tuple(g.reshape(b, h, sq, d) for g in _flash_bwd_bd_calls(
            qf, kf, vf, dof, lsef, delta, scale, tiles, held == sq,
            interpret, bd))
    rest = (sq - held) // block_q       # the q blocks the "dq" call makes
    shape_q = jax.ShapeDtypeStruct((bh, sq, d), q.dtype)
    shape_k = jax.ShapeDtypeStruct((bh, sk, d), k.dtype)
    shape_v = jax.ShapeDtypeStruct((bh, sk, d), v.dtype)
    acc_k = pltpu.VMEM((block_k, d), jnp.float32)
    k_steps, q_steps, suffix = nk, nq, ""
    if window is not None:
        k_steps, q_steps = _window_steps(sq, sk, block_q, block_k, window)
        suffix = WINDOW_SUFFIX

    def call(outs, name, grid, qmap, kvmap, out_specs, out_shape, scratch,
             *dq_rest):
        q_spec = pl.BlockSpec((1, block_q, d), qmap)
        kv_spec = pl.BlockSpec((1, block_k, d), kvmap)
        stat_spec = pl.BlockSpec(
            (1, 1, 1, block_q), lambda *g: qmap(*g)[:2] + (0, 0))
        return pl.pallas_call(
            functools.partial(
                _flash_bwd_kernel, nq=nq, nk=nk, block_q=block_q,
                block_k=block_k, band=band, causal=causal, scale=scale,
                outs=outs, window=window, steps=grid[2],
                dq_from=rest if dq_rest else 0),
            name=name + suffix, grid=grid,
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec,
                      stat_spec] + [pl.BlockSpec(memory_space=pl.ANY)]
            * len(dq_rest),
            out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=scratch, interpret=interpret,
            input_output_aliases={6: 0} if dq_rest else {},
            compiler_params=_held_compiler_params(held, d, q.dtype)
            if outs == "all" else None,
        )(qf, kf, vf, dof, lsef, delta, *dq_rest)

    # grid (bh, k blocks, q blocks): q-side tiles stream, clamped to the
    # first block that sees this k block
    qmap = _causal_q_map(causal, block_q, block_k, nq, window)
    kvmap_kq = lambda i, kb, j: (i, kb, 0)
    dkv_specs = [pl.BlockSpec((1, block_k, d), kvmap_kq)] * 2
    dq_rest = ()
    if rest:
        qmap_qk = lambda i, j, kb: (i, j, 0)
        dq_rest = call(
            "dq", "singa_flash_bwd_dq", (bh, rest, k_steps), qmap_qk,
            _causal_kv_map(causal, block_q, block_k, nk, window),
            pl.BlockSpec((1, block_q, d), qmap_qk), shape_q,
            [pltpu.VMEM((block_q, d), jnp.float32)]),
    if not held:
        dk, dv = call(
            "dkv", "singa_flash_bwd_dkv", (bh, nk, q_steps), qmap, kvmap_kq,
            dkv_specs, [shape_k, shape_v], [acc_k, acc_k])
        dq = dq_rest[0]
    else:
        # the held rows' dq, written once a (batch x head) row as the last
        # tile is in: the whole row, or the rows from the first held one on
        dq_spec = pl.BlockSpec(
            (pl.Element(1), pl.Element(held), pl.Element(d)),
            lambda i, kb, j: (i, sq - held, 0)) if rest else \
            pl.BlockSpec((1, sq, d), lambda i, kb, j: (i, 0, 0))
        dq, dk, dv = call(
            "all", "singa_flash_bwd", (bh, nk, q_steps), qmap, kvmap_kq,
            [dq_spec] + dkv_specs, [shape_q, shape_k, shape_v],
            [pltpu.VMEM((held, d), jnp.float32), acc_k, acc_k], *dq_rest)
    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


def _flash_bwd_bd_calls(qf, kf, vf, dof, lsef, delta, scale, tiles, fused,
                        interpret, bd):
    """The backward under the block-diffusion mask on (bh, seq, d) operands:
    (dq, dk, dv), dk and dv as (bh, 2, seq / 2, d). The passes that sweep
    by k block ("dkv", "all") see K, V, dk and dv as (noised, clean) halves
    and hold a PAIR of blocks a sweep, clean k block nh + c over noised c."""
    bh, seq, d = qf.shape
    tile, _, band = tiles[:3]
    nh = seq // tile // 2
    by_q, by_k = _bd_sweeps(nh)
    qmap, kmap, knmap = _bd_maps(nh)
    q_spec = pl.BlockSpec((1, tile, d), qmap)
    stat_spec = pl.BlockSpec(
        (1, 1, 1, tile), lambda i, t, tab: (i, tab[_BD_Q, t], 0, 0))
    pair = (bh, 2, seq // 2, d)
    pair_spec = pl.BlockSpec(
        (1, 2, tile, d), lambda i, t, tab: (i, 0, tab[_BD_K, t] - nh, 0))
    pair_acc = pltpu.VMEM((2, tile, d), jnp.float32)

    def call(outs, name, sweep, keys, out_specs, out_shape, scratch):
        specs, arrays = zip(*keys)
        return pl.pallas_call(
            functools.partial(
                _flash_bwd_bd_kernel, nq=2 * nh, nk=2 * nh, block_q=tile,
                block_k=tile, band=band, causal=False, scale=scale,
                outs=outs, window=None, steps=len(sweep), bd=bd),
            name=name + BLOCKDIFF_SUFFIX,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(bh, len(sweep)),
                in_specs=[q_spec, *specs[:2], q_spec, stat_spec, stat_spec,
                          *specs[2:]],
                out_specs=out_specs, scratch_shapes=scratch),
            out_shape=out_shape, interpret=interpret,
            compiler_params=_bd_compiler_params(),
        )(_bd_table(sweep), qf, *arrays[:2], dof, lsef, delta, *arrays[2:])

    pairs = [(pair_spec, a.reshape(pair)) for a in (kf, vf)]
    shape_q = jax.ShapeDtypeStruct(qf.shape, qf.dtype)
    dkv_shapes = [jax.ShapeDtypeStruct(pair, a.dtype) for a in (kf, vf)]
    if fused:
        return call(
            "all", "singa_flash_bwd", by_k, pairs,
            [pl.BlockSpec((1, seq, d), lambda i, t, tab: (i, 0, 0)),
             pair_spec, pair_spec], [shape_q] + dkv_shapes,
            [pltpu.VMEM((seq, d), jnp.float32), pair_acc, pair_acc])
    dq = call(
        "dq", "singa_flash_bwd_dq", by_q,
        [(pl.BlockSpec((1, tile, d), m), a)
         for m, a in ((kmap, kf), (kmap, vf), (knmap, kf), (knmap, vf))],
        q_spec, shape_q, [pltpu.VMEM((tile, d), jnp.float32)])
    dk, dv = call("dkv", "singa_flash_bwd_dkv", by_k, pairs,
                  [pair_spec, pair_spec], dkv_shapes, [pair_acc, pair_acc])
    return dq, dk, dv


def _flash_bwd_blockwise(q, k, v, o, lse, do, causal, scale, block_k,
                         window=None):
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
            s = s + _causal_mask(sq, block_k, 0, kb * block_k,
                                 window=window)[None, None]
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=None, window=None, block_diffusion=None):
    """Fused attention; q,k,v (B,H,S,D). Falls back to the reference path
    when shapes don't tile (S % block != 0) or Pallas is unavailable.
    `window` (with `causal`): a query sees its last `window` keys, itself
    among them; tiles wholly left of the window are not visited. None, or
    a window that reaches every key, is the causal program itself.
    `block_diffusion` (no other mask): the sequence is [noised ; clean] in
    blocks of that length under `block_diffusion_visible`'s mask; tiles
    outside it are not visited, and the kernels' names end in `_bd`."""
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                        interpret, window, block_diffusion)
    return out


def _resolve(scale, d, interpret):
    scale = scale if scale is not None else d ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return scale, interpret


def _kernel_path(interpret):
    return "interpret" if interpret else "kernel"


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               window=None, block_diffusion=None):
    d = q.shape[-1]
    scale, interpret = _resolve(scale, d, interpret)
    plan = flash_plan(q.shape[2], k.shape[2], d, causal, q.dtype, block_q,
                      block_k, window, block_diffusion)
    if not _HAS_PALLAS or not plan.ok:
        record_attention_dispatch("flash_fwd", "reference")
        return attention_reference(q, k, v, causal, scale, window,
                                   block_diffusion), None
    record_attention_dispatch("flash_fwd", _kernel_path(interpret))
    record_flash_tiles("flash_fwd", *plan.fwd[3:], plan.skipped[0],
                       plan.window, plan.block_diffusion, plan.steps[0])
    return _flash_fwd_pallas(q, k, v, causal, scale, plan.fwd, interpret,
                             plan.window, plan.block_diffusion)


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                   window, block_diffusion):
    out, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                          interpret, window, block_diffusion)
    if lse is None:  # fallback path: vjp of the reference impl
        d = q.shape[-1]
        s, _ = _resolve(scale, d, interpret)
        _, ref_vjp = jax.vjp(
            lambda q_, k_, v_: attention_reference(q_, k_, v_, causal, s,
                                                   window, block_diffusion),
            q, k, v)
        return out, (None, ref_vjp)
    return out, ((q, k, v, out, lse), None)


def _flash_vjp_bwd(causal, scale, block_q, block_k, interpret, window,
                   block_diffusion, res, g):
    saved, ref_vjp = res
    if saved is None:
        record_attention_dispatch("flash_bwd", "reference")
        return ref_vjp(g)
    q, k, v, out, lse = saved
    d = q.shape[-1]
    s, interp = _resolve(scale, d, interpret)
    sk = k.shape[2]
    plan = flash_plan(q.shape[2], sk, d, causal, q.dtype, block_q, block_k,
                      window, block_diffusion)
    if _HAS_PALLAS and plan.bwd:
        record_attention_dispatch("flash_bwd", _kernel_path(interp))
        record_flash_tiles("flash_bwd", *plan.bwd[3:], plan.skipped[1],
                           plan.window, plan.block_diffusion, plan.steps[1],
                           (plan.held, q.shape[2] - plan.held))
        return _flash_bwd_pallas(q, k, v, out, lse, g, causal, s, plan.bwd,
                                 plan.held, interp, window=plan.window,
                                 bd=plan.block_diffusion)
    record_attention_dispatch("flash_bwd", "reference")
    return _flash_bwd_blockwise(q, k, v, out, lse, g, causal, s,
                                _fit_block(sk, 512) or sk, plan.window)


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


def _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale, plan, interp):
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    B, H, S, D = q.shape
    f32 = jnp.float32

    def hop(k_cur, v_cur, src):
        def full(_):
            o, l = _flash_fwd_pallas(q, k_cur, v_cur, False, scale,
                                     plan.fwd, interp)
            return o.astype(f32), l

        def diag(_):
            o, l = _flash_fwd_pallas(q, k_cur, v_cur, True, scale,
                                     plan.fwd, interp)
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash(q, k, v, axis_name, causal, scale, plan, interp):
    out, _ = _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale, plan,
                                  interp)
    return out


def _ring_flash_vjp_fwd(q, k, v, axis_name, causal, scale, plan, interp):
    out, lse = _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale, plan,
                                    interp)
    return out, (q, k, v, out, lse)


def _ring_flash_vjp_bwd(axis_name, causal, scale, plan, interp, res, g):
    q, k, v, out, lse = res
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    f32 = jnp.float32
    # loop-invariant across hops
    stats = _flash_bwd_stats(out, lse, g, plan.bwd.block_q)

    def hop(k_cur, v_cur, src):
        def run(causal_flag):
            def f(_):
                dq, dk, dv = _flash_bwd_pallas(
                    q, k_cur, v_cur, out, lse, g, causal_flag, scale,
                    plan.bwd, plan.held, interp, stats=stats)
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
    resolved_scale = scale if scale is not None else d ** -0.5
    # one plan serves every hop: the blocks do not depend on the mask, and
    # only the diagonal hop is causal. The backward ring has no blockwise
    # fallback, so its tiles must fit as well (e.g. S_local=2032: the
    # forward fits 1016, which takes no band, and nothing in [128,512]
    # divides 2032)
    plan = flash_plan(q.shape[2], k.shape[2], d, causal, q.dtype)
    if _HAS_PALLAS and plan.ok and plan.bwd:
        _, interp = _resolve(resolved_scale, d, None)
        return _ring_flash(q, k, v, axis_name, causal, resolved_scale,
                           plan, interp)
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
