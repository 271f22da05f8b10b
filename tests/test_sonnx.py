"""sonnx tests: protobuf codec roundtrip, export->import numeric parity,
SONNXModel retraining (ref test/python/test_onnx.py strategy)."""

import os

import numpy as np
import pytest

from singa_tpu import autograd, layer, models, opt, tensor
from singa_tpu import sonnx
from singa_tpu.sonnx import onnx_pb as pb


def test_codec_roundtrip():
    w = np.random.RandomState(0).randn(4, 3).astype(np.float32)
    node = pb.make_node("Gemm", ["x", "w"], ["y"], alpha=1.0, transB=1,
                        pads=[1, 1], mode="constant")
    graph = pb.GraphProto(
        name="g", node=[node],
        initializer=[pb.numpy_to_tensor(w, "w")],
        input=[pb.make_value_info("x", pb.TensorProto.FLOAT, (2, 3))],
        output=[pb.make_value_info("y", pb.TensorProto.FLOAT, (2, 4))])
    m = pb.ModelProto(ir_version=8, producer_name="t", graph=graph,
                      opset_import=[pb.OperatorSetIdProto(domain="",
                                                          version=13)])
    m2 = pb.ModelProto.FromString(m.SerializeToString())
    assert m2.ir_version == 8
    assert m2.graph.node[0].op_type == "Gemm"
    attrs = m2.graph.node[0].attrs()
    assert attrs["alpha"] == 1.0 and attrs["transB"] == 1
    assert attrs["pads"] == [1, 1] and attrs["mode"] == "constant"
    np.testing.assert_array_equal(
        pb.tensor_to_numpy(m2.graph.initializer[0]), w)
    vi = m2.graph.input[0]
    assert vi.name == "x"
    assert [d.dim_value for d in vi.type.tensor_type.shape.dim] == [2, 3]


def test_codec_negative_and_dtypes():
    t = pb.numpy_to_tensor(np.array([-5, 7], np.int64), "i")
    t2 = pb.TensorProto.FromString(t.SerializeToString())
    np.testing.assert_array_equal(pb.tensor_to_numpy(t2),
                                  np.array([-5, 7], np.int64))
    a = pb.make_attribute("axis", -1)
    a2 = pb.AttributeProto.FromString(a.SerializeToString())
    assert a2.value() == -1


def _trace_and_roundtrip(m, x_np, dev, tmp_path):
    tx = tensor.Tensor(data=x_np, device=dev)
    m.compile([tx], is_train=False, use_graph=False)
    # reference output in eval mode
    m.eval()
    ref = m.forward(tx).numpy()
    proto = sonnx.export(m, [tx], str(tmp_path / "m.onnx"))
    loaded = sonnx.load_model(str(tmp_path / "m.onnx"))
    assert len(loaded.graph.node) == len(proto.graph.node)
    rep = sonnx.prepare(loaded, dev)
    prev = autograd.training
    autograd.training = False
    try:
        out = rep.run([tensor.Tensor(data=x_np, device=dev)])[0]
    finally:
        autograd.training = prev
    return ref, out.numpy()


def test_mlp_export_import_parity(dev, tmp_path):
    x = np.random.RandomState(0).randn(4, 10).astype(np.float32)
    m = models.create_model("mlp", data_size=10, num_classes=3)
    ref, got = _trace_and_roundtrip(m, x, dev, tmp_path)
    np.testing.assert_allclose(ref, got, rtol=1e-5, atol=1e-5)


def test_cnn_export_import_parity(dev, tmp_path):
    x = np.random.RandomState(0).randn(2, 1, 28, 28).astype(np.float32)
    m = models.create_model("cnn")
    ref, got = _trace_and_roundtrip(m, x, dev, tmp_path)
    np.testing.assert_allclose(ref, got, rtol=1e-4, atol=1e-4)


def test_sonnx_model_retrains(dev, tmp_path, train_mode):
    x_np = np.random.RandomState(0).randn(16, 10).astype(np.float32)
    y_np = (x_np.sum(1) > 0).astype(np.int32)
    m = models.create_model("mlp", data_size=10, num_classes=2)
    tx = tensor.Tensor(data=x_np, device=dev)
    m.compile([tx], is_train=False, use_graph=False)
    sonnx.export(m, [tx], str(tmp_path / "mlp.onnx"))

    loaded = sonnx.load_model(str(tmp_path / "mlp.onnx"))

    class Retrain(sonnx.SONNXModel):
        def __init__(self, proto):
            super().__init__(proto, dev)
            self.sce = layer.SoftMaxCrossEntropy()

        def train_one_batch(self, x, y):
            out = self.forward(x)
            loss = self.sce(out, y)
            self.optimizer(loss)
            return out, loss

    rm = Retrain(loaded)
    rm.set_optimizer(opt.SGD(lr=0.1))
    ty = tensor.from_numpy(y_np, device=dev)
    rm.compile([tx], is_train=True, use_graph=True)
    losses = []
    for _ in range(6):
        _, loss = rm(tx, ty)
        losses.append(float(loss.numpy()))
    assert losses[-1] < losses[0]


def test_gpt_export_import_parity(dev, tmp_path):
    """Transformer-scale export: the native GPT — token
    embedding, positional slice, pre-LN blocks with fused flash attention
    (decomposed to MatMul/Softmax on export), tanh-GELU MLP, final LN,
    untied head — exports through sonnx.frontend and re-imports through
    sonnx.backend with logit parity."""
    rng = np.random.RandomState(0)
    V, B, S = 50, 2, 16
    ids = rng.randint(0, V, (B, S)).astype(np.int32)
    m = models.create_model("gpt", vocab_size=V, max_seq=S, dim=32,
                            num_heads=4, num_layers=2)
    tx = tensor.from_numpy(ids, device=dev)
    m.compile([tx], is_train=False, use_graph=False)
    m.eval()
    ref = m.forward(tx).numpy()

    proto = sonnx.export(m, [tx], str(tmp_path / "gpt.onnx"))
    ops = {n.op_type for n in proto.graph.node}
    # the fused kernel must decompose into portable math, not a custom op
    assert {"MatMul", "Softmax", "Tanh",
            "LayerNormalization", "Gather"} <= ops, ops
    # token ids stay a real graph INPUT (int32), not a baked constant
    assert len(proto.graph.input) == 1

    loaded = sonnx.load_model(str(tmp_path / "gpt.onnx"))
    rep = sonnx.prepare(loaded, dev)
    prev = autograd.training
    autograd.training = False
    try:
        out = rep.run([tensor.from_numpy(ids, device=dev)])[0]
    finally:
        autograd.training = prev
    np.testing.assert_allclose(ref, out.numpy(), rtol=2e-4, atol=2e-4)


def test_export_bytes_parse_with_protoc(dev, tmp_path):
    """Cross-tool wire-format validation: decode the
    emitted .onnx bytes with Google's protoc against a transcription of
    the public onnx.proto schema — a parser sharing zero code with our
    hand-rolled codec (sonnx/onnx_pb.py). No onnx/onnxruntime wheel exists
    in this sandbox, so protoc IS the independent consumer."""
    import shutil
    import subprocess
    protoc = shutil.which("protoc")
    if protoc is None:
        pytest.skip("protoc not installed")

    rng = np.random.RandomState(0)
    ids = rng.randint(0, 50, (2, 16)).astype(np.int32)
    m = models.create_model("gpt", vocab_size=50, max_seq=16, dim=32,
                            num_heads=4, num_layers=2)
    tx = tensor.from_numpy(ids, device=dev)
    m.compile([tx], is_train=False, use_graph=False)
    proto = sonnx.export(m, [tx], str(tmp_path / "gpt.onnx"))

    here = os.path.dirname(os.path.abspath(__file__))
    with open(tmp_path / "gpt.onnx", "rb") as f:
        r = subprocess.run(
            [protoc, f"--proto_path={here}", "--decode=onnx.ModelProto",
             "onnx_min.proto"],
            stdin=f, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, f"protoc rejected our bytes: {r.stderr}"
    text = r.stdout
    # structural agreement with what we think we wrote
    assert text.count("op_type:") == len(proto.graph.node)
    assert f'producer_name: "singa_tpu"' in text
    assert "ir_version: 8" in text
    assert text.count("initializer {") == len(proto.graph.initializer)
    for n in proto.graph.node[:5]:
        assert f'op_type: "{n.op_type}"' in text
    # protoc found no unknown fields for any message (decode_raw-style
    # leftovers appear as bare numbers; a clean decode has none at top)
    assert "LayerNormalization" in text


def test_backend_raises_on_unknown_op(dev):
    node = pb.make_node("TotallyFakeOp", ["x"], ["y"])
    graph = pb.GraphProto(
        name="g", node=[node],
        input=[pb.make_value_info("x", pb.TensorProto.FLOAT, (1,))],
        output=[pb.make_value_info("y", pb.TensorProto.FLOAT, (1,))])
    m = pb.ModelProto(ir_version=8, graph=graph)
    rep = sonnx.prepare(m, dev)
    with pytest.raises(NotImplementedError):
        rep.run([tensor.from_numpy(np.zeros(1, np.float32), device=dev)])


def test_backend_handcrafted_graph(dev):
    """Run a hand-built graph: y = relu(x @ W + b)."""
    rng = np.random.RandomState(0)
    W = rng.randn(3, 4).astype(np.float32)
    b = rng.randn(4).astype(np.float32)
    nodes = [pb.make_node("MatMul", ["x", "W"], ["xw"]),
             pb.make_node("Add", ["xw", "b"], ["z"]),
             pb.make_node("Relu", ["z"], ["y"])]
    graph = pb.GraphProto(
        name="g", node=nodes,
        initializer=[pb.numpy_to_tensor(W, "W"), pb.numpy_to_tensor(b, "b")],
        input=[pb.make_value_info("x", pb.TensorProto.FLOAT, (2, 3))],
        output=[pb.make_value_info("y", pb.TensorProto.FLOAT, (2, 4))])
    m = pb.ModelProto(ir_version=8, graph=graph)
    rep = sonnx.prepare(m, dev)
    x = rng.randn(2, 3).astype(np.float32)
    out = rep.run([tensor.from_numpy(x, device=dev)])[0]
    np.testing.assert_allclose(out.numpy(), np.maximum(x @ W + b, 0),
                               rtol=1e-5, atol=1e-6)


def test_sonnx_model_last_layers(dev):
    """Truncated-backbone hook: last_layers=-1 returns the penultimate
    node's output (ref sonnx.py:2212 retraining pattern)."""
    import numpy as np
    from singa_tpu.sonnx import onnx_pb as pb

    w1 = np.random.RandomState(0).randn(4, 8).astype(np.float32)
    w2 = np.random.RandomState(1).randn(8, 3).astype(np.float32)
    nodes = [pb.make_node("MatMul", ["x", "w1"], ["h"]),
             pb.make_node("Relu", ["h"], ["hr"]),
             pb.make_node("MatMul", ["hr", "w2"], ["y"])]
    graph = pb.GraphProto(
        name="g", node=nodes,
        initializer=[pb.numpy_to_tensor(w1, "w1"),
                     pb.numpy_to_tensor(w2, "w2")],
        input=[pb.make_value_info("x", pb.TensorProto.FLOAT, (2, 4))],
        output=[pb.make_value_info("y", pb.TensorProto.FLOAT, (2, 3))])
    m = pb.ModelProto(ir_version=8, producer_name="t", graph=graph,
                      opset_import=[pb.OperatorSetIdProto(domain="",
                                                          version=13)])
    x = np.random.RandomState(2).randn(2, 4).astype(np.float32)
    sm = sonnx.SONNXModel(m, device=dev)
    full = sm.forward(tensor.from_numpy(x, device=dev))
    trunc = sm.forward(tensor.from_numpy(x, device=dev), last_layers=-1)
    np.testing.assert_allclose(np.asarray(full.numpy()),
                               np.maximum(x @ w1, 0) @ w2, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(trunc.numpy()),
                               np.maximum(x @ w1, 0), rtol=1e-5)
