"""A sliding window in the flash kernels (ops/attention.py): forward and the
three gradients against `attention_reference` given the same mask, in
interpret mode on the CPU; the tile schedule `flash_plan` makes for a
window; the gauges; and the arguments that carry a window, a head width and
YaRN's tables from `layer.TransformerBlock` down.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu import autograd, device, layer, observe, tensor
from singa_tpu.ops import attention as att


def _errors(shape, window, block_q=None, block_k=None, groups=1,
            dtype=jnp.float32, fused=True, seed=0):
    """Largest absolute difference of (out, dq, dk, dv) between the kernel
    (interpret mode) and the reference under the same window."""
    rng = np.random.default_rng(seed)
    B, H, S, D = shape
    q, w = (jnp.asarray(rng.standard_normal(shape), dtype) for _ in "qw")
    # grouped-query attention as the layer hands it over: a KV head
    # repeated for the query heads of its group
    k, v = (jnp.repeat(jnp.asarray(
        rng.standard_normal((B, H // groups, S, D)), dtype), groups, 1)
        for _ in "kv")

    def run(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out,) + vjp(w.astype(out.dtype))

    cap = att._FUSED_DQ_BYTES_CAP
    try:
        if not fused:
            att._FUSED_DQ_BYTES_CAP = 0
        got = run(lambda *a: att.flash_attention(
            *a, True, None, block_q, block_k, True, window))
    finally:
        att._FUSED_DQ_BYTES_CAP = cap
    want = run(lambda *a: att.attention_reference(
        *(x.astype(jnp.float32) for x in a), True, None, window))
    return [float(jnp.max(jnp.abs(g.astype(jnp.float32) - r)))
            for g, r in zip(got, want)]


# blocks of 128 over S = 512: a window below the block, equal to it, above
# it, a multiple of it, no multiple of it, one key wide, and blocks that are
# not square; then the blocks the plan picks itself
@pytest.mark.parametrize("fused", [True, False], ids=["fused", "split"])
@pytest.mark.parametrize("shape,window,bq,bk", [
    ((1, 2, 512, 32), 64, 128, 128),
    ((1, 2, 512, 32), 100, 128, 128),
    ((1, 2, 512, 32), 128, 128, 128),
    ((1, 2, 512, 32), 200, 128, 128),
    ((1, 2, 512, 32), 256, 128, 128),
    ((1, 2, 512, 32), 1, 128, 128),
    ((1, 2, 512, 32), 130, 128, 256),
    ((1, 2, 512, 32), 130, 256, 128),
    ((1, 2, 1024, 32), 256, None, None),
    ((1, 2, 1024, 32), 384, None, None),
    ((1, 1, 2048, 32), 1024, None, None),
], ids=lambda p: "x".join(map(str, p)) if isinstance(p, tuple) else str(p))
def test_window_matches_reference(shape, window, bq, bk, fused):
    errs = _errors(shape, window, bq, bk, fused=fused)
    assert max(errs) < 2e-4, dict(zip(("out", "dq", "dk", "dv"), errs))


@pytest.mark.parametrize("groups", [2, 4])
def test_window_with_grouped_query_heads(groups):
    errs = _errors((1, 4, 512, 32), 128, 128, 128, groups=groups)
    assert max(errs) < 2e-4, errs


def test_window_in_bfloat16():
    errs = _errors((1, 2, 512, 64), 256, dtype=jnp.bfloat16)
    assert max(errs) < 6e-2, errs


@pytest.mark.parametrize("window", [512, 600])
def test_a_window_that_reaches_every_key_is_the_causal_program(window):
    """W >= S: the same schedule, the same kernel names, the same jaxpr."""
    q = jnp.ones((1, 2, 512, 32), jnp.float32)
    plain = att.flash_plan(512, 512, 32, True, jnp.float32)
    assert att.flash_plan(512, 512, 32, True, jnp.float32,
                          window=window) == plain
    text = lambda w: str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
        att.flash_attention(q, q, q, True, None, None, None, True, w))))(q))
    assert text(window) == text(None)
    assert att.WINDOW_SUFFIX not in text(window)
    assert "singa_flash_fwd" + att.WINDOW_SUFFIX in text(128)


def test_window_needs_the_causal_mask():
    with pytest.raises(AssertionError):
        att.flash_plan(512, 512, 32, False, jnp.float32, window=128)


@pytest.mark.parametrize("args,window,fwd,bwd,skipped,steps", [
    # the sliding layers of the 8k cell: two tiles a q block (the diagonal
    # and the window's edge, both in bands), 21 of 36 tiles never visited
    ((8192, 8192, 128, "bfloat16"), 1024,
     (1024, 1024, 128, 540, 120, 4096), (1024, 1024, 256, 150, 60, 1024),
     (1344, 336), (2, 2)),
    # a window of two blocks: one whole tile between the diagonal and edge
    ((512, 512, 32, "float32", 128, 128), 256,
     (128, 128, 128, 9, 6, 16), (128, 128, 128, 9, 6, 16), (1, 1), (3, 3)),
    # no multiple of the block: the tiles the window's edge crosses go
    # whole under one mask
    ((512, 512, 32, "float32", 128, 128), 200,
     (128, 128, 128, 9, 9, 16), (128, 128, 128, 9, 9, 16), (1, 1), (3, 3)),
    # the plan picks a block that divides the window where one tiles
    ((1024, 1024, 32, "float32"), 384,
     (128, 128, 128, 26, 13, 64), (128, 128, 128, 26, 13, 64), (10, 10),
     (4, 4)),
], ids=["8k-w1024", "w256-b128", "w200-b128", "w384"])
def test_flash_plan_window_table(args, window, fwd, bwd, skipped, steps):
    sq, sk, d, dtype, *blocks = args
    plan = att.flash_plan(sq, sk, d, True, jnp.dtype(dtype), *blocks,
                          window=window)
    assert plan.ok and plan.window == window
    assert tuple(plan.fwd) == fwd and tuple(plan.bwd) == bwd
    assert plan.skipped == skipped
    assert att._window_steps(sq, sk, *plan.fwd[:2], window) == steps
    causal = att.flash_plan(sq, sk, d, True, jnp.dtype(dtype), *blocks)
    share = lambda t: t.visited / t.square     # the bands may differ
    assert share(plan.fwd) < share(causal.fwd)


def test_window_share_of_the_causal_pairs_at_8k():
    """At S = 8192, W = 1024 a sliding layer keeps 23.4 % of the causal
    pairs; the forward's sub-tiles follow: 540 of the causal 2,080."""
    S, W = 8192, 1024
    pairs = sum(min(i + 1, W) for i in range(S))
    assert abs(pairs / (S * (S + 1) / 2) - 0.234) < 1e-3
    win = att.flash_plan(S, S, 128, True, jnp.bfloat16, window=W)
    full = att.flash_plan(S, S, 128, True, jnp.bfloat16)
    assert (win.fwd.visited, full.fwd.visited) == (540, 2080)
    assert win.fwd.visited / full.fwd.visited < 0.27


def _tiles(site):
    g = observe.get_registry().get("singa_flash_tiles")
    return {k: int(g.value(site=site, kind=k))
            for k in ("visited", "masked", "square", "skipped", "window")}


def test_flash_tiles_gauge_holds_the_window_counts():
    q = jnp.ones((1, 1, 512, 32), jnp.float32)
    run = lambda w: jax.grad(lambda q: jnp.sum(att.flash_attention(
        q, q, q, True, None, 128, 128, True, w)))(q)
    run(128)
    want = {"visited": 7, "masked": 7, "square": 16, "skipped": 3,
            "window": 128}
    assert _tiles("flash_fwd") == want and _tiles("flash_bwd") == want
    run(None)
    want = {"visited": 10, "masked": 4, "square": 16, "skipped": 0,
            "window": 0}
    assert _tiles("flash_fwd") == want and _tiles("flash_bwd") == want


# ---- the arguments that carry it ----------------------------------------------

def _mha(dev, x, **kw):
    m = layer.MultiHeadAttention(4, causal=True, num_kv_heads=2, rope=True,
                                 rope_theta=5e5, **kw)
    tx = tensor.from_numpy(x, device=dev)
    m.initialize(tx)
    return m, tx


YARN = {"factor": 16.0, "original_max_position_embeddings": 64,
        "beta_fast": 32.0, "beta_slow": 1.0,
        "attention_factor": 1.2772588722239782}


@pytest.mark.parametrize("window,scaling", [
    (None, None), (128, None), (None, YARN), (100, YARN)],
    ids=["causal", "window", "yarn", "window+yarn"])
def test_attention_layer_by_arguments(window, scaling):
    """Heads of 32 from a 48-wide stream (not 48 / 4), two KV heads, a
    window and YaRN's tables: the layer against the same thing in jnp."""
    dev = device.get_default_device()
    dev.SetRandSeed(5)
    x = np.random.default_rng(1).standard_normal((2, 256, 48)).astype(
        np.float32)
    m, tx = _mha(dev, x, head_dim=32, window=window, rope_scaling=scaling)
    assert m.Wq.shape == (48, 128) and m.Wk.shape == (48, 64) \
        and m.Wo.shape == (128, 48)
    got = m(tx).data
    heads = lambda W, n: (x @ np.asarray(W.data)).reshape(
        2, 256, n, 32).transpose(0, 2, 1, 3)
    cos, sin = autograd.rope_tables(jnp.arange(256), 32, 5e5, scaling)
    q, k = (autograd.apply_rope(jnp.asarray(a), cos, sin)
            for a in (heads(m.Wq, 4), heads(m.Wk, 2)))
    k, v = (jnp.repeat(a, 2, 1) for a in (k, jnp.asarray(heads(m.Wv, 2))))
    o = att.attention_reference(q, k, v, True, None, window)
    want = o.transpose(0, 2, 1, 3).reshape(2, 256, 128) @ m.Wo.data
    assert float(jnp.max(jnp.abs(got - want))) < 2e-4


def test_default_head_width_is_unchanged():
    dev = device.get_default_device()
    x = np.zeros((1, 8, 64), np.float32)
    m, _ = _mha(dev, x)
    assert m.Wq.shape == (64, 64) and m.Wk.shape == (64, 32) \
        and m.Wo.shape == (64, 64)


def test_ring_attention_refuses_a_window():
    t = tensor.from_numpy(np.zeros((1, 1, 128, 8), np.float32),
                          device=device.get_default_device())
    with pytest.raises(AssertionError):
        autograd.attention(t, t, t, causal=True, seq_axis="sp", window=64)
