"""The short convolution's chain as Pallas kernels (ops/shortconv.py,
`singa_shortconv_fwd` / `singa_shortconv_bwd`, in interpret mode here)
against the `jnp` chain they replace: values and all four gradients (B, C,
u and the taps) for 1, 3 and 4 taps in fp32 and bf16, over three sequence
tiles (the carry of z and the halo of dc crossed) and two column blocks;
a batch of two, no sequence reading its neighbour's end; causality and the
zero history; the fallback where the channels do not tile; the dispatch
counter. tests/test_tpu_compile.py compiles the kernels for a described
v5e at the LFM2 cell's shape.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu import observe
from singa_tpu.ops import shortconv as SC

# three sequence tiles of 16 rows, two column blocks of 128 channels
TILES = SC.ConvTiles(rows_fwd=16, rows_bwd=16, cols=128)


def _args(B=2, S=48, D=256, taps=3, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    bcu = jnp.asarray(rng.standard_normal((B, S, 3 * D)), dtype)
    w = jnp.asarray(rng.uniform(-1, 1, (D, taps)) * taps ** -0.5,
                    jnp.float32)
    dy = jnp.asarray(rng.standard_normal((B, S, D)), dtype)
    return bcu, w, dy


def _kernel(bcu, w):
    return SC._gated_taps_kernel(bcu, w, TILES, True)


def _rel(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-30))


def _value_and_grads(fn, bcu, w, dy):
    y, vjp = jax.vjp(fn, bcu, w)
    return (y, *vjp(dy))


def _dispatch():
    c = observe.get_registry().get("singa_shortconv_dispatch_total")
    return {p: int(c.value(path=p)) if c is not None else 0
            for p in observe.SHORTCONV_PATHS}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("taps", [1, 3, 4])
def test_kernels_match_the_jnp_chain(taps, dtype):
    """y, dB, dC, du (one (.., 3D) gradient) and dw. fp32: the same sums
    in the same precision, the taps' order aside; bf16: the results
    rounded to bf16 from fp32 sums that may differ in their last bits:
    at most one bf16 step of the largest result."""
    bcu, w, dy = _args(taps=taps, dtype=dtype)
    got = _value_and_grads(_kernel, bcu, w, dy)
    want = _value_and_grads(SC._gated_taps_xla, bcu, w, dy)
    tol = 1e-5 if dtype == jnp.float32 else 2 ** -8
    assert [x.dtype for x in got] == [x.dtype for x in want]
    D = w.shape[0]
    parts = lambda g: [g[..., p * D:(p + 1) * D] for p in range(3)]
    for name, a, b in zip(("y", "dB", "dC", "du", "dw"),
                          [got[0], *parts(got[1]), got[2]],
                          [want[0], *parts(want[1]), want[2]]):
        assert _rel(a, b) < tol, (name, _rel(a, b))
        assert bool(jnp.any(a != 0)), name


def test_no_sequence_reads_its_neighbours_end():
    """Each sequence of a batch of two, alone, gives its own row of the
    batch's results and of the gradient; the taps' gradient is the sum of
    the two sequences'."""
    bcu, w, dy = _args(B=2)
    y, d, dw = _value_and_grads(_kernel, bcu, w, dy)
    parts = [_value_and_grads(_kernel, bcu[i:i + 1], w, dy[i:i + 1])
             for i in range(2)]
    for i, (yi, di, _) in enumerate(parts):
        assert np.array_equal(np.asarray(y[i]), np.asarray(yi[0]))
        assert _rel(d[i], di[0]) < 1e-6
    assert _rel(dw, parts[0][2] + parts[1][2]) < 1e-6


@pytest.mark.parametrize("t", [0, 15, 16, 33, 47])
def test_kernel_is_causal(t):
    """A change at position t (at and beside the tiles' edges) leaves every
    output before t as it was and moves position t's; no gradient reaches
    an input after the output it comes from."""
    bcu, w, dy = _args(B=1)
    y = _kernel(bcu, w)
    y2 = _kernel(bcu.at[:, t].add(1.0), w)
    assert np.array_equal(np.asarray(y[:, :t]), np.asarray(y2[:, :t]))
    assert bool(jnp.all(jnp.any(y[:, t] != y2[:, t], axis=-1)))
    _, vjp = jax.vjp(_kernel, bcu, w)
    d, _ = vjp(jnp.zeros_like(dy).at[:, t].set(1.0))
    assert not bool(jnp.any(d[:, t + 1:] != 0))


def test_kernel_starts_from_a_zero_history():
    """Position 0 reads its own z through the last tap alone, position 1
    the last two, in every sequence of the batch."""
    bcu, w, _ = _args(B=2, taps=3)
    b, c, u = jnp.split(bcu, 3, -1)
    z = b * u
    y = _kernel(bcu, w)
    assert _rel(y[:, 0], c[:, 0] * w[:, 2] * z[:, 0]) < 1e-6
    assert _rel(y[:, 1], c[:, 1] * (w[:, 1] * z[:, 0]
                                    + w[:, 2] * z[:, 1])) < 1e-6


def test_plan_takes_the_shapes_that_tile_and_only_those():
    """The cell's shape takes blocks of 1 MiB forward and 2 MiB of dy
    backward, fp32 half the rows; the channels must tile by 128, the
    sequence by 16, the taps reach back no further than the carry."""
    assert SC.shortconv_plan(16384, 2048, 3) == SC.ConvTiles(512, 512, 1024)
    assert SC.shortconv_plan(16384, 2048, 3, 4) == SC.ConvTiles(256, 256,
                                                                1024)
    assert SC.shortconv_plan(48, 128, 3) == SC.ConvTiles(16, 16, 128)
    assert SC.shortconv_plan(16384, 2048, 9) is not None
    for seq, dim, taps in ((48, 16, 3), (40, 128, 3), (48, 192, 3),
                           (48, 128, 10), (48, 128, 0)):
        assert SC.shortconv_plan(seq, dim, taps) is None, (seq, dim, taps)


@pytest.mark.parametrize("dim,path", [(16, "xla"), (128, "interpret")])
def test_dispatch_counts_the_path_taken(dim, path):
    """Where the channels do not tile (the operator tests' D = 16, the
    model tests' 64) the `jnp` chain runs, unchanged; where they do, the kernels, in interpret mode
    off a TPU; the counter says which, once a traced call."""
    bcu, w, _ = _args(B=1, S=48, D=dim)
    before = _dispatch()
    y = jax.jit(SC.gated_taps)(bcu, w)
    after = _dispatch()
    assert {p: after[p] - before[p] for p in after} == {
        p: int(p == path) for p in observe.SHORTCONV_PATHS}
    assert _rel(y, SC._gated_taps_xla(bcu, w)) < 1e-5
