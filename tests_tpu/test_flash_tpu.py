"""Compiled (interpret=False) Pallas flash attention on real TPU.

In interpret mode on CPU a TPU-lowering bug is invisible. These tests compile and execute the
forward and backward kernels on the actual chip and check numerics against
the O(S^2) reference math.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from singa_tpu.ops.attention import (attention_reference, flash_attention)


def _assert_close_quantile(actual, desired, tol, max_tol, q=99.99):
    """Element tolerance with a handful of accumulation-order outliers
    allowed: the q-th percentile of |diff| must be < tol, the absolute
    worst element < max_tol (TPU MXU bf16-input rounding produces ~1e-6
    fraction outliers on near-cancelling sums)."""
    diff = np.abs(np.asarray(actual, np.float64) -
                  np.asarray(desired, np.float64))
    assert float(np.percentile(diff, q)) < tol, \
        f"p{q} |diff| = {np.percentile(diff, q):.2e} >= {tol}"
    assert float(diff.max()) < max_tol, \
        f"max |diff| = {diff.max():.2e} >= {max_tol}"


def _rand_qkv(rng, b, h, s, d, dtype=jnp.float32):
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s", [256, 1024])
def test_flash_forward_compiled(causal, s):
    rng = np.random.RandomState(0)
    q, k, v = _rand_qkv(rng, 2, 4, s, 128)
    out = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal, None, 128, 128,
                                        False))(q, k, v)
    ref = attention_reference(q, k, v, causal)
    _assert_close_quantile(out, ref, tol=8e-3, max_tol=5e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_compiled(causal):
    rng = np.random.RandomState(1)
    q, k, v = _rand_qkv(rng, 2, 4, 512, 128)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal, None, 128, 128, False)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        o = attention_reference(q, k, v, causal)
        return jnp.sum(o * jnp.cos(o))

    g_flash = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        _assert_close_quantile(a, b, tol=2e-2, max_tol=1e-1)


def test_flash_train_cell_shape_default_plan():
    """The benchmark's training cell: (4, 16, 1024, 64) bf16 causal at the
    default plan (one grid step a head, in bands of 256 rows; the backward
    holds scores keys x queries), forward and the three gradients against
    the reference on the same bf16 inputs in fp32."""
    rng = np.random.RandomState(4)
    q, k, v = _rand_qkv(rng, 4, 16, 1024, 64, jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal(q.shape), jnp.bfloat16)

    def run(fn, *a):
        out, vjp = jax.vjp(lambda *x: fn(*x, True), *a)
        return (out,) + vjp(w.astype(out.dtype))

    got = jax.jit(lambda *a: run(flash_attention, *a))(q, k, v)
    want = jax.jit(lambda *a: run(attention_reference, *a))(
        *(a.astype(jnp.float32) for a in (q, k, v)))
    _assert_close_quantile(got[0], want[0], tol=8e-3, max_tol=5e-2)
    for a, b in zip(got[1:], want[1:]):
        _assert_close_quantile(a, b, tol=2e-2, max_tol=1e-1)


def test_flash_block_diffusion_cell_shape():
    """The block-diffusion cell's call: (1, 32, 8192, 128) bf16 under the
    mask of blocks of 4 (tiles of 1024, the table's 20 grid steps a pass,
    "nn" riding with "nc", the split backward over k pairs), forward and
    the three gradients against the reference under the dense mask, on the
    first two heads (the reference's scores are 8192 x 8192 a head)."""
    rng = np.random.RandomState(5)
    q, k, v = _rand_qkv(rng, 1, 32, 8192, 128, jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal(q.shape), jnp.bfloat16)
    heads = lambda a: a[:, :2].astype(jnp.float32)

    def run(fn, w, *a):
        out, vjp = jax.vjp(lambda *x: fn(*x, False, None, None, 4), *a)
        return (out,) + vjp(w.astype(out.dtype))

    got = jax.jit(lambda *a: run(
        lambda q, k, v, causal, scale, window, b: flash_attention(
            q, k, v, causal, scale, None, None, False, window, b), *a))(
        w, q, k, v)
    want = jax.jit(lambda *a: run(attention_reference, *a))(
        *(heads(a) for a in (w, q, k, v)))
    _assert_close_quantile(heads(got[0]), want[0], tol=8e-3, max_tol=5e-2)
    for a, b in zip(got[1:], want[1:]):
        _assert_close_quantile(heads(a), b, tol=2e-2, max_tol=1e-1)


def test_flash_long_sequence_compiled():
    """S=16k head: whole-row VMEM residency would blow VMEM (16k*128*4B*2
    = 16 MB just for K/V of one head); streamed blocks must handle it."""
    rng = np.random.RandomState(2)
    q, k, v = _rand_qkv(rng, 1, 2, 16384, 128, jnp.bfloat16)
    out = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, True, None, 128, 128,
                                        False))(q, k, v)
    assert np.isfinite(np.asarray(out, np.float32)).all()


def test_flash_bf16_matches_fp32():
    rng = np.random.RandomState(3)
    q, k, v = _rand_qkv(rng, 1, 2, 512, 128)
    out32 = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, True, None, 128, 128,
                                        False))(q, k, v)
    qb, kb, vb = (a.astype(jnp.bfloat16) for a in (q, k, v))
    outb = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, True, None, 128, 128,
                                        False))(qb, kb, vb)
    np.testing.assert_allclose(np.asarray(outb, np.float32),
                               np.asarray(out32), atol=3e-2, rtol=3e-2)
