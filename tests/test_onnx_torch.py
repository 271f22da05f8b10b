"""Real-world ONNX interop: import models exported by torch (an independent
producer) and match its outputs.

An earlier review asked for a real .onnx file imported end-to-end; the
sandbox has no model zoo on disk (zero egress), so we generate genuine
third-party files at test time with torch's TorchScript ONNX exporter.
The exporter's last step needs the `onnx` pip package only to inline
onnxscript functions — a no-op for plain models — so we stub it out.
"""

import io

import numpy as np
import pytest

torch = pytest.importorskip("torch")

from singa_tpu import autograd, sonnx, tensor  # noqa: E402


def _export(m, args, path, opset=13):
    from singa_tpu.sonnx.interop import export_torch_module
    try:
        export_torch_module(m, args, str(path), opset=opset)
    except ImportError:
        pytest.skip("torch internal exporter layout unknown")


def _import_run(path, x_np, dev, n_out=1):
    model = sonnx.load_model(str(path))
    rep = sonnx.prepare(model, dev)
    prev = autograd.training
    autograd.training = False
    try:
        outs = rep.run([tensor.from_numpy(x_np, device=dev)])
    finally:
        autograd.training = prev
    return [np.asarray(o.numpy()) for o in outs[:n_out]]


def test_torch_cnn_import_parity(dev, tmp_path):
    torch.manual_seed(0)
    m = torch.nn.Sequential(
        torch.nn.Conv2d(3, 8, 3, stride=2, padding=1),
        torch.nn.BatchNorm2d(8),
        torch.nn.ReLU(),
        torch.nn.MaxPool2d(2),
        torch.nn.Conv2d(8, 16, 3, padding=1, groups=2),
        torch.nn.ReLU(),
        torch.nn.AdaptiveAvgPool2d(1),
        torch.nn.Flatten(),
        torch.nn.Linear(16, 10),
    )
    x = torch.randn(2, 3, 32, 32)
    p = tmp_path / "cnn.onnx"
    _export(m, x, p)
    with torch.no_grad():
        ref = m(x).numpy()
    (y,) = _import_run(p, x.numpy(), dev)
    np.testing.assert_allclose(y, ref, rtol=1e-3, atol=1e-4)


def test_torch_deconv_instancenorm_import_parity(dev, tmp_path):
    torch.manual_seed(1)

    class G(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.up = torch.nn.ConvTranspose2d(4, 8, 4, stride=2, padding=1)
            self.inorm = torch.nn.InstanceNorm2d(8, affine=True)
            self.act = torch.nn.Hardswish()
            self.out = torch.nn.Conv2d(8, 3, 3, padding=1)

        def forward(self, x):
            return torch.tanh(self.out(self.act(self.inorm(self.up(x)))))

    m = G()
    x = torch.randn(2, 4, 8, 8)
    p = tmp_path / "gen.onnx"
    _export(m, x, p)
    with torch.no_grad():
        ref = m(x).numpy()
    (y,) = _import_run(p, x.numpy(), dev)
    np.testing.assert_allclose(y, ref, rtol=1e-3, atol=1e-4)


def test_torch_transformer_block_import_parity(dev, tmp_path):
    torch.manual_seed(2)

    class Block(torch.nn.Module):
        def __init__(self, d=16, h=4):
            super().__init__()
            self.ln1 = torch.nn.LayerNorm(d)
            self.qkv = torch.nn.Linear(d, 3 * d)
            self.proj = torch.nn.Linear(d, d)
            self.ln2 = torch.nn.LayerNorm(d)
            self.ff1 = torch.nn.Linear(d, 4 * d)
            self.ff2 = torch.nn.Linear(4 * d, d)
            self.h = h
            self.d = d

        def forward(self, x):
            B, S, D = x.shape
            q, k, v = self.qkv(self.ln1(x)).chunk(3, -1)

            def split(t):
                return t.reshape(B, S, self.h, D // self.h).transpose(1, 2)

            q, k, v = split(q), split(k), split(v)
            a = torch.softmax(q @ k.transpose(-1, -2)
                              / (D // self.h) ** 0.5, -1)
            o = (a @ v).transpose(1, 2).reshape(B, S, D)
            x = x + self.proj(o)
            return x + self.ff2(torch.nn.functional.gelu(self.ff1(
                self.ln2(x))))

    m = Block()
    x = torch.randn(2, 6, 16)
    p = tmp_path / "block.onnx"
    _export(m, x, p, opset=14)
    with torch.no_grad():
        ref = m(x).numpy()
    (y,) = _import_run(p, x.numpy(), dev)
    np.testing.assert_allclose(y, ref, rtol=1e-3, atol=1e-4)


def test_torch_lstm_import_parity(dev, tmp_path):
    torch.manual_seed(3)

    class M(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.lstm = torch.nn.LSTM(6, 8)
            self.head = torch.nn.Linear(8, 4)

        def forward(self, x):
            y, _ = self.lstm(x)
            return self.head(y[-1])

    m = M()
    x = torch.randn(5, 2, 6)
    p = tmp_path / "lstm.onnx"
    _export(m, x, p)
    with torch.no_grad():
        ref = m(x).numpy()
    (y,) = _import_run(p, x.numpy(), dev)
    np.testing.assert_allclose(y, ref, rtol=1e-3, atol=1e-4)


def test_torch_imported_model_retrains(dev, tmp_path):
    """Imported third-party graph is trainable: its initializers are tape
    params and loss decreases under SGD (ref examples/onnx/training)."""
    torch.manual_seed(4)
    m = torch.nn.Sequential(torch.nn.Linear(8, 16), torch.nn.ReLU(),
                            torch.nn.Linear(16, 3))
    x = torch.randn(16, 8)
    p = tmp_path / "mlp.onnx"
    _export(m, x, p)

    from singa_tpu import opt
    model = sonnx.load_model(str(p))
    rep = sonnx.prepare(model, dev)
    sgd = opt.SGD(lr=0.5)
    y_np = np.random.RandomState(0).randint(0, 3, 16).astype(np.int32)
    prev = autograd.training
    autograd.training = True
    losses = []
    try:
        for _ in range(15):
            out = rep.run([tensor.from_numpy(x.numpy(), device=dev)])[0]
            loss = autograd.softmax_cross_entropy(
                out, tensor.from_numpy(y_np, device=dev))
            for pr, g in autograd.backward(loss):
                sgd.apply(pr, g)
            losses.append(float(loss.numpy()))
            sgd.step()
    finally:
        autograd.training = prev
    assert losses[-1] < losses[0] * 0.8, losses
