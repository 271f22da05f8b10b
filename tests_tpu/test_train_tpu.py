"""Real-chip training tests: the headline bench path (conv + amp) compiled
and numerically sane on actual TPU hardware, not just the CPU mesh."""

import numpy as np
import pytest

from singa_tpu import layer, model, models, opt, tensor


class SmallConv(model.Model):
    def __init__(self):
        super().__init__()
        self.conv = layer.Conv2d(16, 3, padding=1)
        self.bn = layer.BatchNorm2d(16)
        self.pool = layer.MaxPool2d(2, 2)
        self.flat = layer.Flatten()
        self.fc = layer.Linear(10)
        self.sce = layer.SoftMaxCrossEntropy()

    def forward(self, x):
        return self.fc(self.flat(self.pool(self.bn(self.conv(x)))))

    def train_one_batch(self, x, y):
        out = self.forward(x)
        loss = self.sce(out, y)
        self.optimizer(loss)
        return out, loss


def _data(n=32):
    rng = np.random.RandomState(0)
    return (rng.rand(n, 3, 32, 32).astype(np.float32),
            rng.randint(0, 10, n).astype(np.int32))


@pytest.mark.parametrize("amp", [None, "bfloat16"])
def test_conv_training_on_tpu(dev, amp):
    # pin the device RNG stream: earlier tests in the session consume it,
    # and an unlucky init draw diverges at this lr
    dev.SetRandSeed(0)
    x_np, y_np = _data()
    x = tensor.from_numpy(x_np, device=dev)
    y = tensor.from_numpy(y_np, device=dev)
    m = SmallConv()
    m.set_optimizer(opt.SGD(lr=0.05, momentum=0.9))
    m.compile([x], is_train=True, use_graph=True, amp=amp)
    losses = [float(m(x, y)[1].numpy()) for _ in range(15)]
    assert losses[-1] < losses[0] * 0.6, losses
    assert np.isfinite(losses).all()
    for name, p in m.get_params().items():
        assert str(p.data.dtype) == "float32", (name, amp)
    m.eval()
    out = m(x)
    assert out.shape == (32, 10)


def test_resnet18_amp_step_on_tpu(dev):
    """One amp train step of the bench model family on the real chip."""
    rng = np.random.RandomState(0)
    x = tensor.from_numpy(rng.rand(8, 3, 64, 64).astype(np.float32), device=dev)
    y = tensor.from_numpy(rng.randint(0, 10, 8).astype(np.int32), device=dev)
    m = models.create_model("resnet18", num_channels=3, num_classes=10)
    m.set_optimizer(opt.SGD(lr=0.05, momentum=0.9))
    m.compile([x], is_train=True, use_graph=True, amp="bfloat16")
    losses = [float(m(x, y)[1].numpy()) for _ in range(3)]
    assert np.isfinite(losses).all(), losses


def test_gpt_flash_train_step_on_tpu(dev):
    """GPT + compiled Pallas flash attention: train step on the chip."""
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 512, (2, 256)).astype(np.int32)
    tgt = np.roll(ids, -1, 1).astype(np.int32)
    m = models.create_model("gpt", vocab_size=512, max_seq=256, dim=128,
                            num_heads=4, num_layers=2)
    m.set_optimizer(opt.SGD(lr=0.01))
    tx = tensor.from_numpy(ids, device=dev)
    ty = tensor.from_numpy(tgt, device=dev)
    m.compile([tx], is_train=True, use_graph=True)
    losses = [float(m(tx, ty)[1].numpy()) for _ in range(4)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses
