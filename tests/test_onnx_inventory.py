"""ONNX export inventory: every Operator class is either
exportable (with a round-trip parity test for the families the reference
exports — RNNs, ConvTranspose/superres, Pad/UpSample) or DELIBERATELY
unexportable with a documented reason (frontend.UNEXPORTABLE). An op in
neither set fails the inventory — a new operator forces a conscious
export decision, not a silent NotImplementedError at a user's export.

Reference analog: the SingaFrontend rename table + special handlers
(reference python/singa/sonnx.py:86-966).
"""

import numpy as np
import pytest

from singa_tpu import autograd, layer, model, tensor
from singa_tpu import sonnx
from singa_tpu.sonnx.frontend import EXPORTABLE, UNEXPORTABLE
from singa_tpu.device import get_default_device


def _all_operator_classes():
    """Every Operator subclass the package defines (autograd + ops +
    layer + parallel + models), by walking the class tree after
    importing the modules that register them."""
    import singa_tpu.layer          # noqa: F401
    import singa_tpu.ops.rnn        # noqa: F401
    import singa_tpu.ops.attention  # noqa: F401
    import singa_tpu.models.transformer  # noqa: F401

    seen = {}

    def walk(cls):
        for sub in cls.__subclasses__():
            seen.setdefault(sub.__name__, sub)
            walk(sub)

    walk(autograd.Operator)
    return seen


def test_every_operator_is_classified():
    classes = _all_operator_classes()
    missing = sorted(n for n in classes
                     if n not in EXPORTABLE and n not in UNEXPORTABLE)
    assert not missing, (
        f"operators with no export decision: {missing} — add each to "
        "frontend.EXPORTABLE (with an _emit branch) or "
        "frontend.UNEXPORTABLE (with a reason)")
    # and the registries do not drift: no stale names on either side
    stale = sorted((set(EXPORTABLE) | set(UNEXPORTABLE)) - set(classes))
    assert not stale, f"registry names with no Operator class: {stale}"
    assert not set(EXPORTABLE) & set(UNEXPORTABLE)


@pytest.fixture
def dev():
    return get_default_device()


class _Wrap(model.Model):
    """Model wrapper around a thunk of autograd ops for export tests."""

    def __init__(self, fn):
        super().__init__()
        self.fn = fn

    def forward(self, *xs):
        return self.fn(*xs)

    def train_one_batch(self, *a):
        raise NotImplementedError


def _roundtrip(m, xs_np, dev, tmp_path, rtol=1e-5, atol=1e-5):
    txs = [tensor.Tensor(data=x, device=dev) for x in xs_np]
    m.compile(txs, is_train=False, use_graph=False)
    m.eval()
    ref = m.forward(*txs)
    ref = ref.numpy() if isinstance(ref, tensor.Tensor) else ref[0].numpy()
    sonnx.export(m, txs, str(tmp_path / "m.onnx"))
    rep = sonnx.prepare(sonnx.load_model(str(tmp_path / "m.onnx")), dev)
    prev = autograd.training
    autograd.training = False
    try:
        out = rep.run([tensor.Tensor(data=x, device=dev)
                       for x in xs_np])[0]
    finally:
        autograd.training = prev
    np.testing.assert_allclose(ref, out.numpy(), rtol=rtol, atol=atol)


def test_pad_upsample_space_ops_roundtrip(dev, tmp_path):
    """Pad (constant + reflect) -> UpSample(Resize) -> DepthToSpace ->
    SpaceToDepth chain round-trips through our own backend."""
    def fn(x):
        y = autograd.Pad("constant", [0, 0, 1, 1, 0, 0, 1, 1], 0.5)(x)
        y = autograd.Pad("reflect", [0, 0, 1, 1, 0, 0, 1, 1])(y)
        y = autograd.UpSample([1, 1, 2, 2])(y)
        y = autograd.SpaceToDepth(2)(y)
        y = autograd.DepthToSpace(2, "DCR")(y)
        return y

    x = np.random.RandomState(0).randn(2, 4, 5, 5).astype(np.float32)
    _roundtrip(_Wrap(fn), [x], dev, tmp_path)


def test_conv_transpose_superres_roundtrip(dev, tmp_path):
    """The superres upscaling pattern: conv -> ConvTranspose (stride 2,
    output_padding 1) — the family the reference exports via its
    ConvTranspose special handler."""
    rng = np.random.RandomState(1)
    W = tensor.Tensor(data=rng.randn(4, 3, 3, 3).astype(np.float32) * 0.2,
                      device=dev)
    b = tensor.Tensor(data=rng.randn(3).astype(np.float32) * 0.1,
                      device=dev)

    def fn(x):
        return autograd.conv_transpose2d(
            x, W, b, stride=(2, 2), padding=(1, 1), output_padding=(1, 1))

    x = rng.randn(2, 4, 7, 7).astype(np.float32)
    _roundtrip(_Wrap(fn), [x], dev, tmp_path, rtol=1e-4, atol=1e-4)


def test_fused_lstm_roundtrip(dev, tmp_path):
    """CudnnRNN's fused _LSTMScan exports as a real ONNX LSTM node (gate
    order converted ifgo -> iofc) and re-imports through op_LSTM."""
    m = _Wrap(None)
    rnn = layer.CudnnRNN(hidden_size=6)
    m.rnn = rnn
    m.register_layers(rnn)
    m.fn = lambda x: rnn(x)
    x = np.random.RandomState(2).randn(5, 3, 4).astype(np.float32)
    _roundtrip(m, [x], dev, tmp_path, rtol=1e-5, atol=1e-5)


def test_fused_gru_roundtrip(dev, tmp_path):
    """_GRUScan -> ONNX GRU (gate order r|u|n -> z|r|h,
    linear_before_reset preserved)."""
    from singa_tpu.ops import rnn as rnn_ops
    rng = np.random.RandomState(3)
    H, I = 5, 4
    Wx = tensor.Tensor(data=rng.randn(I, 3 * H).astype(np.float32) * 0.3,
                       device=dev)
    Wh = tensor.Tensor(data=rng.randn(H, 3 * H).astype(np.float32) * 0.3,
                       device=dev)
    b = tensor.Tensor(data=rng.randn(3 * H).astype(np.float32) * 0.1,
                      device=dev)
    rb = tensor.Tensor(data=rng.randn(3 * H).astype(np.float32) * 0.1,
                       device=dev)
    h0 = tensor.Tensor(data=np.zeros((3, H), np.float32), device=dev)

    def fn(x):
        ys, hy = rnn_ops.gru_scan(x, h0, Wx, Wh, b, rb)
        return ys

    x = rng.randn(6, 3, I).astype(np.float32)
    _roundtrip(_Wrap(fn), [x], dev, tmp_path, rtol=1e-5, atol=1e-5)


def test_flip_einsum_globalmaxpool_roundtrip(dev, tmp_path):
    def fn(x):
        y = autograd.Flip(0)(x)
        y = autograd.Einsum("nchw->nhwc")(y)
        y = autograd.Einsum("nhwc->nchw")(y)
        return autograd.GlobalMaxPool()(y)

    x = np.random.RandomState(4).randn(2, 3, 4, 4).astype(np.float32)
    _roundtrip(_Wrap(fn), [x], dev, tmp_path)


def test_unexportable_raises_with_reason(dev):
    """A deliberately-unexportable op fails loudly AND cites its reason."""
    from singa_tpu.sonnx import frontend
    x = tensor.Tensor(data=np.full((2, 2), 0.25, np.float32), device=dev)
    t = tensor.Tensor(data=np.full((2, 2), 0.25, np.float32), device=dev)
    prev = autograd.training
    autograd.training = True
    try:
        y = autograd.CrossEntropy()(x, t)
    finally:
        autograd.training = prev
    with pytest.raises(NotImplementedError, match="deliberately"):
        frontend.to_onnx_model([x], [y])


def test_rope_gpt_export_roundtrip(dev, tmp_path):
    """A RoPE GPT exports (rotation decomposed to baked cos/sin +
    rotate-half Slice/Neg/Concat) and re-imports with numeric parity."""
    from singa_tpu import models
    m = models.create_model("gpt", vocab_size=31, max_seq=16, dim=32,
                            num_heads=2, num_layers=1,
                            pos_encoding="rope")
    x = np.random.RandomState(5).randint(0, 31, (2, 8)).astype(np.int32)
    txs = [tensor.Tensor(data=x, device=dev)]
    m.compile(txs, is_train=False, use_graph=False)
    m.eval()
    ref = m.forward(*txs).numpy()
    sonnx.export(m, txs, str(tmp_path / "rope.onnx"))
    rep = sonnx.prepare(sonnx.load_model(str(tmp_path / "rope.onnx")), dev)
    prev = autograd.training
    autograd.training = False
    try:
        out = rep.run([tensor.Tensor(data=x, device=dev)])[0]
    finally:
        autograd.training = prev
    np.testing.assert_allclose(ref, out.numpy(), rtol=1e-4, atol=1e-4)
