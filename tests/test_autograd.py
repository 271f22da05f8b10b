"""Autograd ops: forward parity vs numpy + gradient checks vs jax.grad
(pattern of ref test/python/test_operation.py)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from singa_tpu import autograd, tensor


def _param(arr, dev):
    t = tensor.from_numpy(arr, dev)
    t.requires_grad = True
    t.stores_grad = True
    return t


def _grads(loss):
    return {id(p): g.numpy() for p, g in autograd.backward(loss)}


class TestForward:
    """Forward parity on a representative op set."""

    @pytest.mark.parametrize("fn,ref", [
        (autograd.relu, lambda x: np.maximum(x, 0)),
        (autograd.sigmoid, lambda x: 1 / (1 + np.exp(-x))),
        (autograd.tanh, np.tanh),
        (autograd.softplus, lambda x: np.log1p(np.exp(x))),
        (autograd.softsign, lambda x: x / (1 + np.abs(x))),
        (autograd.abs, np.abs),
        (autograd.exp, np.exp),
        (autograd.sin, np.sin),
        (autograd.cos, np.cos),
        (autograd.erf, None),
    ])
    def test_unary(self, dev, rng, fn, ref):
        x = rng.randn(3, 4).astype(np.float32)
        out = fn(tensor.from_numpy(x, dev))
        if ref is not None:
            assert np.allclose(out.numpy(), ref(x), rtol=1e-4, atol=1e-5)

    def test_binary(self, dev, rng):
        a = rng.randn(3, 4).astype(np.float32)
        b = rng.randn(3, 4).astype(np.float32)
        ta, tb = tensor.from_numpy(a, dev), tensor.from_numpy(b, dev)
        assert np.allclose(autograd.add(ta, tb).numpy(), a + b)
        assert np.allclose(autograd.sub(ta, tb).numpy(), a - b)
        assert np.allclose(autograd.mul(ta, tb).numpy(), a * b)
        assert np.allclose(autograd.div(ta, tb).numpy(), a / b, rtol=1e-5)
        assert np.allclose(autograd.min(ta, tb).numpy(), np.minimum(a, b))
        assert np.allclose(autograd.max(ta, tb).numpy(), np.maximum(a, b))

    def test_comparisons_not_differentiable(self, dev, rng, train_mode):
        a = tensor.from_numpy(rng.randn(4).astype(np.float32), dev)
        b = tensor.from_numpy(rng.randn(4).astype(np.float32), dev)
        out = autograd.less(a, b)
        assert out.creator is None  # never recorded on the tape
        assert set(np.unique(out.numpy())) <= {0.0, 1.0}

    def test_shape_ops(self, dev, rng):
        x = rng.randn(2, 3, 4).astype(np.float32)
        t = tensor.from_numpy(x, dev)
        assert autograd.reshape(t, (6, 4)).shape == (6, 4)
        assert autograd.reshape(t, (2, -1)).shape == (2, 12)
        assert autograd.flatten(t).shape == (2, 12)
        assert autograd.transpose(t, (2, 0, 1)).shape == (4, 2, 3)
        assert autograd.squeeze(autograd.unsqueeze(t, [0]), 0).shape == x.shape
        assert autograd.tile(t, (1, 2, 1)).shape == (2, 6, 4)

    def test_slice_split_gather(self, dev, rng):
        x = rng.randn(4, 6).astype(np.float32)
        t = tensor.from_numpy(x, dev)
        s = autograd.slice(t, [1], [3], axes=[0])
        assert np.allclose(s.numpy(), x[1:3])
        parts = autograd.split(t, 1, [2, 4])
        assert parts[0].shape == (4, 2) and parts[1].shape == (4, 4)
        g = autograd.gather(t, 0, [0, 2])
        assert np.allclose(g.numpy(), x[[0, 2]])

    def test_concat(self, dev, rng):
        a = rng.randn(2, 3).astype(np.float32)
        b = rng.randn(2, 3).astype(np.float32)
        out = autograd.cat([tensor.from_numpy(a, dev),
                            tensor.from_numpy(b, dev)], axis=1)
        assert np.allclose(out.numpy(), np.concatenate([a, b], 1))

    def test_reductions(self, dev, rng):
        x = rng.randn(3, 5).astype(np.float32)
        t = tensor.from_numpy(x, dev)
        assert np.allclose(
            autograd.reduce_sum(t, axes=[1], keepdims=False).numpy(),
            x.sum(1), rtol=1e-5)
        assert np.allclose(
            autograd.reduce_mean(t, axes=[0], keepdims=True).numpy(),
            x.mean(0, keepdims=True), rtol=1e-5)

    def test_onehot_cast_where(self, dev):
        idx = tensor.from_numpy(np.array([0, 2], np.int32), dev)
        oh = autograd.onehot(3, idx)
        assert np.allclose(oh.numpy(), [[1, 0, 0], [0, 0, 1]])
        c = autograd.cast(oh, tensor.int32)
        assert c.numpy().dtype == np.int32
        cond = tensor.from_numpy(np.array([True, False]), dev)
        a = tensor.from_numpy(np.array([1.0, 1.0], np.float32), dev)
        b = tensor.from_numpy(np.array([2.0, 2.0], np.float32), dev)
        w = autograd.where(cond, a, b)
        assert np.allclose(w.numpy(), [1.0, 2.0])

    def test_pad_upsample_space_depth(self, dev, rng):
        x = rng.randn(1, 4, 2, 2).astype(np.float32)
        t = tensor.from_numpy(x, dev)
        p = autograd.pad(t, "constant", [0, 0, 1, 1, 0, 0, 1, 1])
        assert p.shape == (1, 4, 4, 4)
        u = autograd.upsample(t, scales=[1, 1, 2, 2])
        assert u.shape == (1, 4, 4, 4)
        d = autograd.space_to_depth(t, 2)
        assert d.shape == (1, 16, 1, 1)
        back = autograd.depth_to_space(d, 2)
        assert np.allclose(back.numpy(), x)


class TestBackward:
    """Gradient checks vs jax.grad through the same math."""

    def test_mlp_chain(self, dev, rng, train_mode):
        x = rng.randn(4, 3).astype(np.float32)
        w = rng.randn(3, 2).astype(np.float32)
        tw = _param(w, dev)
        tx = tensor.from_numpy(x, dev)
        y = autograd.tanh(autograd.matmul(tx, tw))
        loss = autograd.reduce_sum(y, keepdims=False)
        g = _grads(loss)
        ref = jax.grad(lambda wv: jnp.sum(jnp.tanh(x @ wv)))(w)
        assert np.allclose(g[id(tw)], np.asarray(ref), rtol=1e-4, atol=1e-5)

    def test_multi_consumer_accumulation(self, dev, train_mode):
        a = _param(np.array([2.0, 3.0], np.float32), dev)
        s = autograd.mul(a, a)
        u = autograd.add(s, a)
        out = autograd.reduce_sum(u, keepdims=False)
        g = _grads(out)
        assert np.allclose(g[id(a)], 2 * a.numpy() + 1)

    def test_softmax_cross_entropy_grad(self, dev, rng, train_mode):
        logits = _param(rng.randn(4, 5).astype(np.float32), dev)
        labels = tensor.from_numpy(np.array([0, 2, 1, 4], np.int32), dev)
        loss = autograd.softmax_cross_entropy(logits, labels)
        g = _grads(loss)
        ref = jax.grad(lambda z: jnp.mean(
            -jax.nn.log_softmax(z)[jnp.arange(4), labels.data]))(logits.data)
        assert np.allclose(g[id(logits)], np.asarray(ref), atol=1e-5)

    def test_softmax_cross_entropy_grad_3d(self, dev, rng, train_mode):
        """Sequence-model logits (B, T, C): grad scale must match the mean
        over ALL tokens, not just the batch dim."""
        B, T, C = 2, 5, 7
        logits = _param(rng.randn(B, T, C).astype(np.float32), dev)
        labels = tensor.from_numpy(
            rng.randint(0, C, (B, T)).astype(np.int32), dev)
        loss = autograd.softmax_cross_entropy(logits, labels)
        g = _grads(loss)
        ref = jax.grad(lambda z: jnp.mean(-jnp.take_along_axis(
            jax.nn.log_softmax(z), labels.data[..., None], axis=-1)))(
                logits.data)
        assert np.allclose(g[id(logits)], np.asarray(ref), atol=1e-5)

    @pytest.mark.parametrize("region", [False, True],
                             ids=["tape", "region"])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("shape", [(6, 11), (2, 5, 11)],
                             ids=["rank2", "rank3"])
    def test_softmax_cross_entropy_integer_targets(
            self, dev, rng, train_mode, shape, dtype, region):
        """Loss and gradient against jax.grad of a plain fp32
        -log_softmax(z)[t], on the ordinary tape and replayed in a
        `Region`; the gradient in the logits' dtype; the gauge says where
        the backward's log-sum-exp came from."""
        from singa_tpu import observe
        z = jnp.asarray(rng.randn(*shape).astype(np.float32) * 3).astype(dtype)
        logits = tensor.Tensor(data=z, device=dev, requires_grad=True,
                               stores_grad=True)
        labels = tensor.from_numpy(
            rng.randint(0, shape[-1], shape[:-1]).astype(np.int32), dev)
        plain = lambda z: jnp.mean(-jnp.take_along_axis(
            jax.nn.log_softmax(z.astype(jnp.float32)),
            labels.data[..., None], axis=-1))
        if region:
            loss = autograd.region(
                lambda x: autograd.softmax_cross_entropy(x, labels), logits)
        else:
            loss = autograd.softmax_cross_entropy(logits, labels)
        g = autograd.gradients(loss)[logits].data
        want, want_g = plain(z), jax.grad(plain)(z.astype(jnp.float32))
        assert loss.data.dtype == jnp.float32 and g.dtype == z.dtype
        assert abs(float(loss.data) - float(want)) <= 1e-6 * float(want)
        # bf16: the fp32 gradient rounded once, entries of at most 1 / rows
        tol = 1e-7 if dtype == "float32" else 2 ** -8 / want_g.shape[0]
        assert np.allclose(np.asarray(g, np.float32), want_g, atol=tol)
        took = observe.get_registry().get("singa_cross_entropy")
        pairs = {(t, l): took.value(targets=t, lse=l)
                 for t in observe.CE_TARGETS for l in observe.CE_LSE}
        mine = ("integer", "rebuilt" if region else "kept")
        assert pairs == {k: float(k == mine) for k in pairs}

    def test_softmax_cross_entropy_region_equals_the_tape(
            self, dev, rng, train_mode):
        """The looped model's use: the same operator replayed in a region
        gives the ordinary tape's loss and gradient bit for bit."""
        z = rng.randn(2, 5, 11).astype(np.float32)
        labels = tensor.from_numpy(
            rng.randint(0, 11, (2, 5)).astype(np.int32), dev)
        got = []
        for region in (False, True):
            x = _param(z, dev)
            f = lambda x: autograd.softmax_cross_entropy(x, labels)
            loss = autograd.region(f, x) if region else f(x)
            got.append((loss.numpy(), _grads(loss)[id(x)]))
        assert np.array_equal(got[0][0], got[1][0])
        assert np.array_equal(got[0][1], got[1][1])

    def test_softmax_cross_entropy_dense_targets_gauge(
            self, dev, rng, train_mode):
        from singa_tpu import observe
        logits = _param(rng.randn(4, 3).astype(np.float32), dev)
        onehot = tensor.from_numpy(np.eye(3, dtype=np.float32)[[0, 2, 1, 1]],
                                   dev)
        g = _grads(autograd.softmax_cross_entropy(logits, onehot))
        ref = jax.grad(lambda z: jnp.mean(-jnp.sum(
            onehot.data * jax.nn.log_softmax(z), -1)))(logits.data)
        assert np.allclose(g[id(logits)], np.asarray(ref), atol=1e-6)
        took = observe.get_registry().get("singa_cross_entropy")
        assert took.value(targets="dense", lse="rebuilt") == 1
        assert took.value(targets="integer", lse="kept") == 0

    def test_param_grad_survives_none_edge(self, dev, rng, train_mode):
        """A param consumed by both a None-grad slot (CE targets) and a real
        consumer must still yield its accumulated grad."""
        p = _param(rng.rand(4, 3).astype(np.float32), dev)
        logits = _param(rng.randn(4, 3).astype(np.float32), dev)
        # p feeds CE as (soft) targets AND an MSE term
        loss1 = autograd.softmax_cross_entropy(logits, p)   # None grad for p
        loss2 = autograd.mse_loss(p, tensor.from_numpy(
            np.zeros((4, 3), np.float32), dev))
        loss = autograd.add(loss1, loss2)
        g = _grads(loss)
        assert id(p) in g, "param grad dropped when a None edge completed it"
        assert np.allclose(g[id(p)], p.numpy() / 4, atol=1e-5)

    def test_conv2d_grad(self, dev, rng, train_mode):
        from singa_tpu.layer import _ConvGeometry
        x = rng.randn(2, 3, 8, 8).astype(np.float32)
        w = rng.randn(4, 3, 3, 3).astype(np.float32)
        b = np.zeros(4, np.float32)
        tw, tb = _param(w, dev), _param(b, dev)
        tx = tensor.from_numpy(x, dev)
        h = _ConvGeometry((1, 1), (1, 1), 1)
        y = autograd.conv2d(h, tx, tw, tb)
        assert y.shape == (2, 4, 8, 8)
        loss = autograd.reduce_sum(autograd.mul(y, y), keepdims=False)
        g = _grads(loss)

        def ref_loss(wv, bv):
            yv = jax.lax.conv_general_dilated(
                jnp.asarray(x), wv, (1, 1), [(1, 1), (1, 1)],
                dimension_numbers=("NCHW", "OIHW", "NCHW")) \
                + bv[None, :, None, None]
            return jnp.sum(yv * yv)
        rw, rb = jax.grad(ref_loss, argnums=(0, 1))(tw.data, tb.data)
        assert np.allclose(g[id(tw)], np.asarray(rw), rtol=1e-3, atol=1e-3)
        assert np.allclose(g[id(tb)], np.asarray(rb), rtol=1e-3, atol=1e-3)

    def test_pooling_grad(self, dev, rng, train_mode):
        x = _param(rng.randn(1, 2, 4, 4).astype(np.float32), dev)
        y = autograd.pooling_2d(x, (2, 2), (2, 2), is_max=True)
        assert y.shape == (1, 2, 2, 2)
        loss = autograd.reduce_sum(y, keepdims=False)
        g = _grads(loss)
        # max pool grad: one 1 per window
        assert g[id(x)].sum() == 8.0

    def test_batchnorm_train_grad(self, dev, rng, train_mode):
        x = rng.randn(4, 3, 2, 2).astype(np.float32)
        gamma = _param(np.ones(3, np.float32), dev)
        beta = _param(np.zeros(3, np.float32), dev)
        rm = tensor.from_numpy(np.zeros(3, np.float32), dev)
        rv = tensor.from_numpy(np.ones(3, np.float32), dev)
        tx = tensor.from_numpy(x, dev)
        y, nm, nv = autograd.batchnorm_2d(tx, gamma, beta, rm, rv, 0.9, 1e-5,
                                          train=True)
        # normalized output: ~zero mean, unit var per channel
        yn = y.numpy()
        assert np.allclose(yn.mean(axis=(0, 2, 3)), 0, atol=1e-4)
        assert np.allclose(yn.var(axis=(0, 2, 3)), 1, atol=1e-2)
        # running stats moved toward batch stats
        assert np.allclose(np.asarray(nm),
                           0.1 * x.mean(axis=(0, 2, 3)), atol=1e-5)
        loss = autograd.reduce_sum(autograd.mul(y, y), keepdims=False)
        g = _grads(loss)
        assert g[id(gamma)].shape == (3,)

    def test_embedding_grad(self, dev, rng, train_mode):
        table = _param(rng.randn(10, 4).astype(np.float32), dev)
        idx = tensor.from_numpy(np.array([1, 1, 3], np.int32), dev)
        y = autograd.embedding(idx, table)
        assert y.shape == (3, 4)
        loss = autograd.reduce_sum(y, keepdims=False)
        g = _grads(loss)
        gt = g[id(table)]
        assert gt[1].sum() == 8.0  # row 1 used twice
        assert gt[3].sum() == 4.0
        assert gt[0].sum() == 0.0

    def test_gemm_grad(self, dev, rng, train_mode):
        A = rng.randn(3, 4).astype(np.float32)
        W = _param(rng.randn(5, 4).astype(np.float32), dev)  # transB
        C = _param(np.zeros((1, 5), np.float32), dev)
        tA = tensor.from_numpy(A, dev)
        y = autograd.gemm(tA, W, C, alpha=1.0, beta=1.0, transB=1)
        assert y.shape == (3, 5)
        loss = autograd.reduce_sum(y, keepdims=False)
        g = _grads(loss)
        assert np.allclose(g[id(W)], np.tile(A.sum(0), (5, 1)), rtol=1e-4)

    def test_dropout_train_eval(self, dev, rng, train_mode):
        x = tensor.from_numpy(np.ones((1000,), np.float32), dev)
        y = autograd.dropout(x, 0.5)
        kept = float((y.numpy() != 0).mean())
        assert 0.4 < kept < 0.6
        # kept values are scaled by 1/keep
        assert np.allclose(y.numpy()[y.numpy() != 0], 2.0)
        autograd.training = False
        y2 = autograd.dropout(x, 0.5)
        assert np.allclose(y2.numpy(), 1.0)
        autograd.training = True

    def test_lstm_scan_grad(self, dev, rng, train_mode):
        from singa_tpu.ops.rnn import lstm_scan, init_lstm_params
        x = tensor.from_numpy(rng.randn(5, 2, 3).astype(np.float32), dev)
        Wx, Wh, b = init_lstm_params(3, 4, dev, np.float32)
        for t in (Wx, Wh, b):
            t.stores_grad = True
        h0 = tensor.zeros((2, 4), dev)
        c0 = tensor.zeros((2, 4), dev)
        ys, hy, cy = lstm_scan(x, h0, c0, Wx, Wh, b)
        assert ys.shape == (5, 2, 4) and hy.shape == (2, 4)
        loss = autograd.reduce_sum(ys, keepdims=False)
        g = _grads(loss)
        assert g[id(Wx)].shape == (3, 16)
        assert np.isfinite(g[id(Wx)]).all()

    def test_backward_is_generator(self, dev, rng, train_mode):
        """Incremental yield: late-layer grads arrive before early ones."""
        w1 = _param(rng.randn(3, 3).astype(np.float32), dev)
        w2 = _param(rng.randn(3, 3).astype(np.float32), dev)
        x = tensor.from_numpy(rng.randn(2, 3).astype(np.float32), dev)
        h = autograd.matmul(x, w1)
        y = autograd.matmul(h, w2)
        loss = autograd.reduce_sum(y, keepdims=False)
        order = [id(p) for p, _ in autograd.backward(loss)]
        assert order == [id(w2), id(w1)]  # last layer's grad first
