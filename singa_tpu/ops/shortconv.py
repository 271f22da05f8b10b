"""The gated short convolution (the LFM2 family's operator beside
attention):

    (B, C, u) = split3(x W_in)                 W_in (D, 3D), in that order
    z = B * u
    c_t = sum_j w[:, j] * z_{t - (L - 1) + j}  depthwise, causal, z = 0
                                               before the sequence; w (D, L)
    y = (C * c) W_out                          W_out (D, D)

Three device scopes: `in_proj`, `mix` (the elementwise chain between the
products, bound by memory) and `out_proj`. The chain computes in fp32
whatever the products' dtype, and keeps its INPUT for the backward pass:
the (.., 3D) product and the taps, from which z and c are rebuilt, where
the plain vjp would keep four fp32 arrays the size of the stream a layer.

Two implementations of the chain, one result (`gated_taps` picks by what
the call can observe, `singa_shortconv_dispatch_total{path}` says which):
a Pallas kernel each way (`singa_shortconv_fwd`, `singa_shortconv_bwd`)
where the channels tile by 128 lanes and the sequence by 16 rows, which
read the product and write their results once; elsewhere the `jnp` chain
under `jax.checkpoint`, whose fusions write z out in fp32 between them.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observe import record_shortconv_dispatch

# Rows of z a sequence tile hands the next (the taps reach back L - 1 of
# them), and rows of the NEXT tile the backward reads (its dc reaches
# forward L - 1): an fp32 and a bf16 tile's sublanes.
_CARRY, _HALO = 8, 16
# Bytes of the forward's (rows, cols) block and of the backward's (rows, D)
# block of dy (its (.., 3D) blocks are three times that): the least device
# time at the cell's shape, within 48 MB of scoped VMEM in fp32 too
# (tools/shortconv_bench.py, PERF.md section 6, PR 39).
_COLS, _FWD_BLOCK, _BWD_BLOCK = (1024, 512, 256, 128), 1 << 20, 2 << 20


def causal_taps(z, w):
    """z (B, S, D), w (D, L) -> c (B, S, D): c_t = sum_j w[:, j] *
    z_{t-(L-1)+j}, with zeros before each sequence's start."""
    L, S = w.shape[1], z.shape[1]
    zp = jnp.pad(z, ((0, 0), (L - 1, 0), (0, 0)))
    return sum(w[:, j] * zp[:, j:j + S] for j in range(L))


@jax.checkpoint
def _gated_taps_xla(bcu, w):
    b, c, u = (a.astype(jnp.float32) for a in jnp.split(bcu, 3, axis=-1))
    return (c * causal_taps(b * u, w.astype(jnp.float32))).astype(bcu.dtype)


class ConvTiles(NamedTuple):
    """Rows a sequence tile and channels a column block of the kernels:
    the forward's grid is (batch, D / cols, S / rows_fwd), the sequence
    last and in order; the backward's (batch, S / rows_bwd), every column
    block of a tile in its body (it writes dB, dC and du into one
    (.., 3D) block)."""
    rows_fwd: int
    rows_bwd: int
    cols: int


def shortconv_plan(seq, dim, taps, itemsize=2):
    """ConvTiles for a chain over (.., seq, dim) with `taps` taps in a dtype
    of `itemsize` bytes, or None where the kernels do not apply: channels
    not a multiple of 128, a sequence not a multiple of 16, or taps
    reaching back past the carry."""
    if dim % 128 or seq % _HALO or not 1 <= taps <= _CARRY + 1:
        return None
    cols = next(c for c in _COLS if dim % c == 0)

    def rows(block_bytes, width):
        r = _HALO
        while seq % (2 * r) == 0 and 2 * r * width * itemsize <= block_bytes:
            r *= 2
        return r
    return ConvTiles(rows(_FWD_BLOCK, cols), rows(_BWD_BLOCK, dim), cols)


def gated_taps(bcu, w):
    """bcu (B, S, 3D) = [B ; C ; u], w (D, L) -> C * taps(B * u), in bcu's
    dtype. The kernels where `shortconv_plan` gives tiles (in interpret
    mode off a TPU), the `jnp` chain elsewhere."""
    tiles = shortconv_plan(bcu.shape[1], bcu.shape[2] // 3, w.shape[1],
                           bcu.dtype.itemsize)
    if tiles is None:
        record_shortconv_dispatch("xla")
        return _gated_taps_xla(bcu, w)
    interpret = jax.default_backend() != "tpu"
    record_shortconv_dispatch("interpret" if interpret else "kernel")
    return _gated_taps_kernel(bcu, w, tiles, interpret)


# ---- the kernels ----------------------------------------------------------

def _taps_of(ref, start, rows, taps):
    """[ref[start + j : start + j + rows] for j < taps]: a window shifted a
    row at a time (the first reads `taps - 1` rows before `start`)."""
    return [ref[start - (taps - 1) + j:start - (taps - 1) + j + rows]
            for j in range(taps)]


def _fwd_kernel(b_ref, c_ref, u_ref, w_ref, y_ref, zbuf, *, taps):
    rows = y_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)      # a sequence starts from no history
    def _():
        zbuf[:_CARRY] = jnp.zeros((_CARRY, zbuf.shape[1]), jnp.float32)

    f32 = lambda r: r[0].astype(jnp.float32)
    zbuf[_CARRY:] = f32(b_ref) * f32(u_ref)
    w = w_ref[...]
    c = sum(w[j:j + 1] * z for j, z in
            enumerate(_taps_of(zbuf, _CARRY, rows, taps)))
    y_ref[0] = (f32(c_ref) * c).astype(y_ref.dtype)
    zbuf[:_CARRY] = zbuf[rows:]


def _bwd_kernel(g_ref, bcu_ref, gn_ref, bcun_ref, w_ref, d_ref, dw_ref,
                zbuf, dcbuf, *, taps, cols):
    i, s, rows, dim = pl.program_id(0), pl.program_id(1), g_ref.shape[1], \
        g_ref.shape[2]
    # the next tile's first rows: their dc reaches back into this tile
    # (none past the sequence's end)
    last = s == pl.num_programs(1) - 1

    @pl.when((i == 0) & (s == 0))
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, jnp.float32)

    @pl.when(s == 0)
    def _():
        zbuf[:_CARRY] = jnp.zeros((_CARRY, dim), jnp.float32)

    f32 = lambda r, c0, n=rows: r[0, :n, c0:c0 + cols].astype(jnp.float32)
    for c0 in range(0, dim, cols):
        cs = slice(c0, c0 + cols)
        b, c, u = (f32(bcu_ref, c0 + p * dim) for p in range(3))
        g = f32(g_ref, c0)
        zbuf[_CARRY:, cs] = b * u
        w = w_ref[:, cs]
        zs = _taps_of(zbuf.at[:, cs], _CARRY, rows, taps)
        conv = sum(w[j:j + 1] * z for j, z in enumerate(zs))
        dc = g * c
        dcbuf[:rows, cs] = dc
        dcbuf[rows:, cs] = jnp.where(
            last, 0.0, f32(gn_ref, c0, _HALO) * f32(bcun_ref, c0 + dim, _HALO))
        # dz_t = sum_j w_j dc_{t + (L - 1) - j}
        dz = sum(w[j:j + 1] * dcbuf[taps - 1 - j:taps - 1 - j + rows, cs]
                 for j in range(taps))
        dtype = d_ref.dtype
        d_ref[0, :, c0:c0 + cols] = (dz * u).astype(dtype)
        d_ref[0, :, dim + c0:dim + c0 + cols] = (g * conv).astype(dtype)
        d_ref[0, :, 2 * dim + c0:2 * dim + c0 + cols] = (dz * b).astype(dtype)
        dw_ref[:, cs] += jnp.concatenate(
            [jnp.sum(dc * z, axis=0, keepdims=True) for z in zs])
        zbuf[:_CARRY, cs] = zbuf[rows:, cs]


def _compiler_params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=48 * 1024 * 1024)


def _fwd_pallas(bcu, w, tiles, interpret):
    nb, seq, dim3 = bcu.shape
    dim, taps = dim3 // 3, w.shape[1]
    rows, cols = tiles.rows_fwd, tiles.cols
    nd = dim // cols
    part = lambda p: pl.BlockSpec(
        (1, rows, cols), lambda b, c, s: (b, s, c + p * nd))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, taps=taps),
        name="singa_shortconv_fwd",
        grid=(nb, nd, seq // rows),
        in_specs=[part(0), part(1), part(2),
                  pl.BlockSpec((taps, cols), lambda b, c, s: (0, c))],
        out_specs=pl.BlockSpec((1, rows, cols), lambda b, c, s: (b, s, c)),
        out_shape=jax.ShapeDtypeStruct((nb, seq, dim), bcu.dtype),
        scratch_shapes=[pltpu.VMEM((_CARRY + rows, cols), jnp.float32)],
        compiler_params=_compiler_params("parallel", "parallel",
                                         "arbitrary"),
        interpret=interpret,
    )(bcu, bcu, bcu, w.astype(jnp.float32).T)


def _bwd_pallas(bcu, w, g, tiles, interpret):
    nb, seq, dim3 = bcu.shape
    dim, taps = dim3 // 3, w.shape[1]
    rows, cols = tiles.rows_bwd, tiles.cols
    halos = seq // _HALO
    tile = lambda width: pl.BlockSpec((1, rows, width),
                                      lambda b, s: (b, s, 0))
    halo = lambda width: pl.BlockSpec((1, _HALO, width), lambda b, s: (
        b, jnp.minimum((s + 1) * (rows // _HALO), halos - 1), 0))
    whole = pl.BlockSpec((taps, dim), lambda b, s: (0, 0))
    d, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps, cols=cols),
        name="singa_shortconv_bwd",
        grid=(nb, seq // rows),
        in_specs=[tile(dim), tile(dim3), halo(dim), halo(dim3), whole],
        out_specs=[tile(dim3), whole],
        out_shape=[jax.ShapeDtypeStruct(bcu.shape, bcu.dtype),
                   jax.ShapeDtypeStruct((taps, dim), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_CARRY + rows, dim), jnp.float32),
                        pltpu.VMEM((rows + _HALO, dim), jnp.float32)],
        # dw sums over the batch and the sequence: both in order
        compiler_params=_compiler_params("arbitrary", "arbitrary"),
        interpret=interpret,
    )(g, bcu, g, bcu, w.astype(jnp.float32).T)
    return d, dw.T.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _gated_taps_kernel(bcu, w, tiles, interpret):
    return _fwd_pallas(bcu, w, tiles, interpret)


def _gated_taps_kernel_fwd(bcu, w, tiles, interpret):
    # the residuals are the chain's input, as under jax.checkpoint
    return _fwd_pallas(bcu, w, tiles, interpret), (bcu, w)


def _gated_taps_kernel_bwd(tiles, interpret, res, g):
    bcu, w = res
    return _bwd_pallas(bcu, w, g.astype(bcu.dtype), tiles, interpret)


_gated_taps_kernel.defvjp(_gated_taps_kernel_fwd, _gated_taps_kernel_bwd)


def short_conv(x, W_in, w, W_out):
    """x (B, S, D) -> (B, S, D), the products in the dtype of x."""
    with jax.named_scope("in_proj"):
        bcu = jnp.matmul(x, W_in)
    with jax.named_scope("mix"):
        y = gated_taps(bcu, w)
    with jax.named_scope("out_proj"):
        return jnp.matmul(y, W_out)
