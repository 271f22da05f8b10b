"""Mixed-precision policy tests: bf16 compute + fp32
master weights via Model.compile(amp=...)."""

import numpy as np
import pytest

from singa_tpu import autograd, layer, model, opt, tensor


class Net(model.Model):
    def __init__(self):
        super().__init__()
        self.conv = layer.Conv2d(8, 3, padding=1)
        self.bn = layer.BatchNorm2d(8)
        self.pool = layer.MaxPool2d(2, 2)
        self.flat = layer.Flatten()
        self.fc = layer.Linear(10)
        self.loss_fn = layer.SoftMaxCrossEntropy()

    def forward(self, x):
        return self.fc(self.flat(self.pool(self.bn(self.conv(x)))))

    def train_one_batch(self, x, y):
        out = self.forward(x)
        loss = self.loss_fn(out, y)
        self.optimizer(loss)
        return out, loss


def _data(dev, n=16):
    rng = np.random.RandomState(0)
    x = tensor.from_numpy(rng.rand(n, 3, 16, 16).astype(np.float32),
                          device=dev)
    y = tensor.from_numpy(rng.randint(0, 10, n).astype(np.int32),
                          device=dev)
    return x, y


@pytest.mark.parametrize("use_graph", [True, False])
def test_amp_trains_fp32_masters(dev, use_graph):
    x, y = _data(dev)
    m = Net()
    m.set_optimizer(opt.SGD(lr=0.05))
    m.compile([x], is_train=True, use_graph=use_graph, amp="bfloat16")
    losses = [float(m(x, y)[1].numpy()) for _ in range(20)]
    assert losses[-1] < losses[0] * 0.5, losses
    for name, p in m.get_params().items():
        assert str(p.data.dtype) == "float32", (name, p.data.dtype)
    m.eval()
    out = m(x)
    assert out.shape == (16, 10)


def test_amp_matches_fp32_early_steps(dev):
    """First steps of amp training track the fp32 run (policy is a
    precision change, not a different computation)."""
    def run(amp):
        import jax
        dev.rng_state = jax.random.PRNGKey(7)  # identical init both runs
        x, y = _data(dev)
        m = Net()
        m.set_optimizer(opt.SGD(lr=0.01))
        m.compile([x], is_train=True, use_graph=True, amp=amp)
        return [float(m(x, y)[1].numpy()) for _ in range(5)]

    f32 = run(None)
    bf16 = run("bfloat16")
    np.testing.assert_allclose(bf16, f32, rtol=0.05)


def test_amp_compute_cast_gradient(dev, train_mode):
    """ComputeCast is differentiable: master fp32 weight gets an fp32
    grad through a bf16 matmul."""
    rng = np.random.RandomState(0)
    W = tensor.from_numpy(rng.rand(4, 3).astype(np.float32), device=dev)
    W.requires_grad = True
    W.stores_grad = True
    x = tensor.from_numpy(rng.rand(2, 4).astype(np.float32), device=dev)
    prev = autograd.compute_dtype
    autograd.compute_dtype = "bfloat16"
    try:
        xc, Wc = autograd.compute_cast(x, W)
        assert str(xc.data.dtype) == "bfloat16"
        y = autograd.matmul(xc, Wc)
        loss = autograd.reduce_sum(y, None)
        grads = autograd.gradients(loss)
    finally:
        autograd.compute_dtype = prev
    (gW,) = [g for p, g in grads.items() if p is W]
    assert str(gW.data.dtype) == "float32"
    np.testing.assert_allclose(
        np.asarray(gW.numpy()),
        np.broadcast_to(x.numpy().sum(0)[:, None], (4, 3)), rtol=2e-2)


def test_amp_with_distopt_mesh(dev):
    from singa_tpu import parallel
    mesh = parallel.data_parallel_mesh(4)
    x, y = _data(dev)
    m = Net()
    m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.05), mesh=mesh))
    m.compile([x], is_train=True, use_graph=True, amp="bfloat16")
    losses = [float(m(x, y)[1].numpy()) for _ in range(10)]
    assert losses[-1] < losses[0] * 0.8, losses
