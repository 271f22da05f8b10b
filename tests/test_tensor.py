"""Tensor facade: numpy-parity checks (pattern of ref test/python/test_tensor.py)."""

import numpy as np
import pytest

from singa_tpu import tensor


def test_create_and_numpy(dev, rng):
    a = rng.randn(3, 4).astype(np.float32)
    t = tensor.from_numpy(a, dev)
    assert t.shape == (3, 4)
    assert t.dtype == np.float32
    assert np.allclose(t.numpy(), a)
    assert t.size() == 12
    assert t.memsize() == 48


def test_zeros_ones_like(dev):
    t = tensor.ones((2, 3), dev)
    assert np.all(t.numpy() == 1)
    z = tensor.zeros_like(t)
    assert z.shape == (2, 3) and np.all(z.numpy() == 0)


def test_arith_operators(dev, rng):
    a = rng.randn(5).astype(np.float32)
    b = rng.randn(5).astype(np.float32)
    ta, tb = tensor.from_numpy(a, dev), tensor.from_numpy(b, dev)
    assert np.allclose((ta + tb).numpy(), a + b)
    assert np.allclose((ta - tb).numpy(), a - b)
    assert np.allclose((ta * tb).numpy(), a * b)
    assert np.allclose((ta / tb).numpy(), a / b, rtol=1e-5)
    assert np.allclose((ta + 2.0).numpy(), a + 2)
    assert np.allclose((3.0 - ta).numpy(), 3 - a)
    assert np.allclose((-ta).numpy(), -a)


def test_inplace_ops(dev):
    t = tensor.ones((3,), dev)
    t += 2.0
    assert np.allclose(t.numpy(), 3)
    t *= 2.0
    assert np.allclose(t.numpy(), 6)


def test_unary_functions(dev, rng):
    a = np.abs(rng.randn(4, 4)).astype(np.float32) + 0.1
    t = tensor.from_numpy(a, dev)
    assert np.allclose(tensor.exp(t).numpy(), np.exp(a), rtol=1e-5)
    assert np.allclose(tensor.log(t).numpy(), np.log(a), rtol=1e-5)
    assert np.allclose(tensor.sqrt(t).numpy(), np.sqrt(a), rtol=1e-5)
    assert np.allclose(tensor.tanh(t).numpy(), np.tanh(a), rtol=1e-5)
    assert np.allclose(tensor.sigmoid(t).numpy(), 1 / (1 + np.exp(-a)),
                       rtol=1e-5)
    assert np.allclose(tensor.square(t).numpy(), a * a, rtol=1e-5)


def test_matmul_and_gemm(dev, rng):
    a = rng.randn(3, 4).astype(np.float32)
    b = rng.randn(4, 5).astype(np.float32)
    ta, tb = tensor.from_numpy(a, dev), tensor.from_numpy(b, dev)
    assert np.allclose(tensor.mult(ta, tb).numpy(), a @ b, rtol=1e-4)
    assert np.allclose((ta @ tb).numpy(), a @ b, rtol=1e-4)


def test_axpy(dev):
    x = tensor.ones((4,), dev)
    y = tensor.ones((4,), dev)
    tensor.axpy(2.0, x, y)
    assert np.allclose(y.numpy(), 3.0)


def test_reshape_transpose(dev, rng):
    a = rng.randn(2, 6).astype(np.float32)
    t = tensor.from_numpy(a, dev)
    assert t.reshape((3, 4)).shape == (3, 4)
    assert np.allclose(t.transpose().numpy(), a.T)
    assert np.allclose(tensor.transpose(t, (1, 0)).numpy(), a.T)


def test_comparison_masks(dev):
    t = tensor.from_numpy(np.array([-1.0, 0.0, 1.0], np.float32), dev)
    assert np.allclose((t > 0).numpy(), [0, 0, 1])
    assert np.allclose((t <= 0).numpy(), [1, 1, 0])
    assert (t > 0).requires_grad is False


def test_row_col_ops(dev, rng):
    m = rng.randn(3, 4).astype(np.float32)
    r = rng.randn(4).astype(np.float32)
    c = rng.randn(3).astype(np.float32)
    tm = tensor.from_numpy(m, dev)
    assert np.allclose(tensor.add_row(tm, tensor.from_numpy(r, dev)).numpy(),
                       m + r)
    assert np.allclose(
        tensor.mult_column(tm, tensor.from_numpy(c, dev)).numpy(),
        m * c[:, None])
    assert np.allclose(tensor.sum_rows(tm).numpy(), m.sum(0), rtol=1e-5)
    assert np.allclose(tensor.sum_columns(tm).numpy(), m.sum(1), rtol=1e-5)


def test_random_fill(dev):
    t = tensor.Tensor((1000,), dev)
    t.gaussian(1.0, 2.0)
    assert abs(float(t.numpy().mean()) - 1.0) < 0.3
    t.uniform(0, 1)
    x = t.numpy()
    assert x.min() >= 0 and x.max() <= 1
    t.bernoulli(0.3)
    assert set(np.unique(t.numpy())) <= {0.0, 1.0}


def test_concat_repeat(dev, rng):
    a = rng.randn(2, 3).astype(np.float32)
    t = tensor.from_numpy(a, dev)
    cc = tensor.concatenate([t, t], axis=0)
    assert cc.shape == (4, 3)
    rr = tensor.repeat(t, 2, axis=1)
    assert rr.shape == (2, 6)


def test_einsum_tensordot(dev, rng):
    a = rng.randn(2, 3).astype(np.float32)
    b = rng.randn(3, 4).astype(np.float32)
    ta, tb = tensor.from_numpy(a, dev), tensor.from_numpy(b, dev)
    assert np.allclose(tensor.einsum("ij,jk->ik", ta, tb).numpy(), a @ b,
                       rtol=1e-4)
    assert np.allclose(tensor.tensordot(ta, tb, axes=1).numpy(), a @ b,
                       rtol=1e-4)


def test_softmax_ce_fused_pair(dev, rng):
    logits = rng.randn(4, 7).astype(np.float32)
    labels = np.array([1, 0, 6, 3], np.int32)
    ce = tensor.softmax_cross_entropy_fwd(
        tensor.from_numpy(logits, dev).data,
        tensor.from_numpy(labels, dev).data)
    # reference formula
    p = np.exp(logits - logits.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    want = -np.log(p[np.arange(4), labels])
    assert np.allclose(np.asarray(ce), want, rtol=1e-4)


def _ce_case(rng, shape, dtype):
    import jax.numpy as jnp
    z = jnp.asarray(rng.randn(*shape).astype(np.float32) * 3).astype(dtype)
    t = jnp.asarray(rng.randint(0, shape[-1], shape[:-1]).astype(np.int32))
    return z, t


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [(6, 11), (2, 5, 11)],
                         ids=["rank2", "rank3"])
def test_softmax_ce_integer_targets_against_log_softmax(rng, shape, dtype):
    """lse - logits[target] and exp(logits - lse) - onehot against jax's
    own -log_softmax(z)[t] and its gradient, taken in fp32 on the same
    inputs; with the forward's lse handed on and without."""
    import jax
    import jax.numpy as jnp
    z, t = _ce_case(rng, shape, dtype)
    plain = lambda z: -jnp.take_along_axis(
        jax.nn.log_softmax(z.astype(jnp.float32)), t[..., None], -1)[..., 0]
    want = plain(z)
    want_g = jax.grad(lambda z: plain(z).sum())(z.astype(jnp.float32))
    tol = 1e-6 if dtype == "float32" else 4e-2     # bf16: 8 bits of an lse
    lse = tensor.softmax_lse(z)
    assert lse.shape == shape[:-1] and lse.dtype == z.dtype
    for kept in (None, lse):
        ce = tensor.softmax_cross_entropy_fwd(z, t, kept)
        g = tensor.softmax_cross_entropy_bwd(z, t, kept)
        assert ce.shape == shape[:-1] and ce.dtype == z.dtype
        assert g.shape == shape and g.dtype == z.dtype
        assert np.allclose(np.asarray(ce, np.float32), want, rtol=tol,
                           atol=tol)
        assert np.allclose(np.asarray(g, np.float32), want_g, atol=tol)


@pytest.mark.parametrize("soft", [False, True], ids=["one_hot", "soft"])
def test_softmax_ce_dense_targets_are_the_formulas_they_were(rng, soft):
    """A distribution as targets keeps the log-probability form, bit for
    bit: -sum(t * (z - lse)) and softmax(z) - t."""
    import jax
    import jax.numpy as jnp
    z, t = _ce_case(rng, (2, 5, 11), "float32")
    d = jax.nn.softmax(jnp.asarray(rng.randn(2, 5, 11).astype(np.float32))) \
        if soft else jax.nn.one_hot(t, 11, dtype=jnp.float32)
    assert not tensor.targets_are_indices(z, d)
    assert tensor.targets_are_indices(z, t)
    logp = z - jax.scipy.special.logsumexp(z, axis=-1, keepdims=True)
    for kept in (None, tensor.softmax_lse(z)):
        assert np.array_equal(tensor.softmax_cross_entropy_fwd(z, d, kept),
                              -jnp.sum(d * logp, axis=-1))
        assert np.array_equal(tensor.softmax_cross_entropy_bwd(z, d, kept),
                              jax.nn.softmax(z, axis=-1) - d)


def test_astype_l1_l2(dev):
    t = tensor.from_numpy(np.array([3.0, 4.0], np.float32), dev)
    h = t.as_type(tensor.float16)
    assert h.dtype == np.float16
    assert abs(t.l1() - 3.5) < 1e-5
    assert abs(t.l2() - 5.0 / np.sqrt(2)) < 1e-5


def test_clone_copy(dev):
    t = tensor.ones((2, 2), dev)
    c = t.clone()
    c.set_value(5.0)
    assert np.all(t.numpy() == 1) and np.all(c.numpy() == 5)
    t.copy_from(c)
    assert np.all(t.numpy() == 5)
