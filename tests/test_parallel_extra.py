"""TP + pipeline parallelism tests on the 8-device CPU mesh."""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding

from singa_tpu.parallel import (
    make_mesh, tp_mlp, shard_columns, shard_rows, gpipe, last_stage_value,
)


def test_tp_mlp_matches_dense():
    mesh = make_mesh({"tp": 4})
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 16)).astype(np.float32)
    W1 = rng.standard_normal((16, 32)).astype(np.float32)
    b1 = rng.standard_normal(32).astype(np.float32)
    W2 = rng.standard_normal((32, 16)).astype(np.float32)
    b2 = rng.standard_normal(16).astype(np.float32)

    ref = jax.nn.gelu(x @ W1 + b1) @ W2 + b2

    run = jax.shard_map(
        functools.partial(tp_mlp, axis_name="tp"),
        mesh=mesh,
        in_specs=(P(), P(None, "tp"), P("tp"), P("tp", None), P()),
        out_specs=P(), check_vma=False)
    W1s = jax.device_put(jnp.asarray(W1), shard_columns(mesh, "tp"))
    W2s = jax.device_put(jnp.asarray(W2), shard_rows(mesh, "tp"))
    b1s = jax.device_put(jnp.asarray(b1), NamedSharding(mesh, P("tp")))
    out = run(jnp.asarray(x), W1s, b1s, W2s, jnp.asarray(b2))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_tp_through_model_api_matches_serial():
    """Linear(tp_axis=...) + DistOpt on a {data:2, tp:4} mesh must train to
    the same losses/params as a serial single-device model (TP
    as a framework feature, not a library function)."""
    from singa_tpu import layer, model, opt, tensor
    from singa_tpu.device import get_default_device

    class TPMLP(model.Model):
        def __init__(self, tp_axis=None):
            super().__init__()
            self.fc1 = layer.Linear(32, tp_axis=tp_axis, tp_mode="column")
            self.relu = layer.ReLU()
            self.fc2 = layer.Linear(4, tp_axis=tp_axis, tp_mode="row")
            self.loss_fn = layer.SoftMaxCrossEntropy()

        def forward(self, x):
            return self.fc2(self.relu(self.fc1(x)))

        def train_one_batch(self, x, y):
            out = self.forward(x)
            loss = self.loss_fn(out, y)
            self._optimizer(loss)
            return out, loss

    dev = get_default_device()
    rng = np.random.RandomState(3)
    X = rng.randn(16, 10).astype(np.float32)
    Y = rng.randint(0, 4, 16).astype(np.int32)
    tx, ty = tensor.from_numpy(X, dev), tensor.from_numpy(Y, dev)

    m_ser = TPMLP()
    m_ser.set_optimizer(opt.SGD(lr=0.1, momentum=0.9))
    m_ser.compile([tx], is_train=True, use_graph=True)
    w0 = {k: v.numpy().copy() for k, v in m_ser.get_params().items()}

    mesh = make_mesh({"data": 2, "tp": 4})
    m_tp = TPMLP(tp_axis="tp")
    m_tp.set_optimizer(opt.DistOpt(opt.SGD(lr=0.1, momentum=0.9),
                                   axis="data", mesh=mesh))
    m_tp.compile([tx], is_train=True, use_graph=True)
    m_tp.set_params(w0)

    for _ in range(5):
        _, l_ser = m_ser(tx, ty)
        _, l_tp = m_tp(tx, ty)
    assert abs(float(l_ser.numpy()) - float(l_tp.numpy())) < 1e-4, \
        (float(l_ser.numpy()), float(l_tp.numpy()))
    for k in m_ser.get_params():
        np.testing.assert_allclose(m_ser.get_params()[k].numpy(),
                                   m_tp.get_params()[k].numpy(),
                                   atol=1e-4, err_msg=k)


def test_tp_gpt_through_model_api():
    """GPT(tp_axis=...) trains through Model on a {data,tp} mesh; loss
    matches the serial model (head-parallel MHA + column/row MLP)."""
    from singa_tpu import models, opt, tensor
    from singa_tpu.device import get_default_device

    dev = get_default_device()
    rng = np.random.RandomState(4)
    V, B, S = 50, 4, 16
    ids = rng.randint(0, V, (B, S)).astype(np.int32)
    tgt = np.roll(ids, -1, axis=1).astype(np.int32)
    tx = tensor.from_numpy(ids, dev)
    ty = tensor.from_numpy(tgt, dev)

    def build(tp_axis=None, dist=False):
        m = models.create_model("gpt", vocab_size=V, max_seq=S, dim=32,
                                num_heads=4, num_layers=2, tp_axis=tp_axis)
        if dist:
            mesh = make_mesh({"data": 2, "tp": 4})
            m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.05), axis="data",
                                        mesh=mesh))
        else:
            m.set_optimizer(opt.SGD(lr=0.05))
        m.compile([tx], is_train=True, use_graph=True)
        return m

    m_ser = build()
    w0 = {k: v.numpy().copy() for k, v in m_ser.get_params().items()}
    m_tp = build(tp_axis="tp", dist=True)
    m_tp.set_params(w0)

    for _ in range(3):
        _, l_ser = m_ser(tx, ty)
        _, l_tp = m_tp(tx, ty)
    assert abs(float(l_ser.numpy()) - float(l_tp.numpy())) < 2e-3, \
        (float(l_ser.numpy()), float(l_tp.numpy()))


def test_tp_gpt_vocab_parallel():
    """GPT(vocab_tp=True): the (V, E) embedding is row-sharded over tp and
    the head is tied to it (Megatron vocab parallelism).
    Vocab 50 is NOT divisible by tp=4 — internal padding to a multiple of 8
    (->56) must be invisible: losses match the same model run serially, and
    the per-device embedding shard is V_pad/tp rows (param bytes drop)."""
    from singa_tpu import models, opt, tensor
    from singa_tpu.device import get_default_device

    dev = get_default_device()
    rng = np.random.RandomState(7)
    V, B, S = 50, 4, 16
    ids = rng.randint(0, V, (B, S)).astype(np.int32)
    tgt = np.roll(ids, -1, axis=1).astype(np.int32)
    tx = tensor.from_numpy(ids, dev)
    ty = tensor.from_numpy(tgt, dev)

    def build(dist=False):
        m = models.create_model(
            "gpt", vocab_size=V, max_seq=S, dim=32, num_heads=4,
            num_layers=2, tp_axis="tp", vocab_tp=True,
            vocab_pad_multiple=8)
        if dist:
            mesh = make_mesh({"data": 2, "tp": 4})
            m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.05), axis="data",
                                        mesh=mesh))
        else:
            m.set_optimizer(opt.SGD(lr=0.05))
        m.compile([tx], is_train=True, use_graph=True)
        return m

    m_ser = build()
    assert m_ser.head is None, "vocab_tp must tie the head"
    assert m_ser.padded_vocab == 56
    w0 = {k: v.numpy().copy() for k, v in m_ser.get_params().items()}
    assert not any("head" in k for k in w0), w0.keys()
    m_tp = build(dist=True)
    m_tp.set_params(w0)

    for _ in range(3):
        out_ser, l_ser = m_ser(tx, ty)
        out_tp, l_tp = m_tp(tx, ty)
    assert abs(float(l_ser.numpy()) - float(l_tp.numpy())) < 2e-3, \
        (float(l_ser.numpy()), float(l_tp.numpy()))
    # caller-facing logits are gathered + sliced back to the true vocab
    assert out_ser.shape[-1] == V and out_tp.shape[-1] == V
    np.testing.assert_allclose(out_ser.numpy()[:B], out_tp.numpy()[:B],
                               atol=5e-3)

    # the whole point: per-device embedding bytes dropped 4x (tp=4)
    emb = m_tp.get_params()["tok_embed.W"] \
        if "tok_embed.W" in m_tp.get_params() else None
    if emb is None:  # param naming may be flat; find the (56, 32) table
        emb = next(v for v in m_tp.get_params().values()
                   if tuple(v.shape) == (56, 32))
    shard = emb.data.addressable_shards[0].data
    assert shard.shape[0] == 56 // 4, shard.shape

    # trained embedding stays consistent with the serial run
    e_ser = next(v for v in m_ser.get_params().values()
                 if tuple(v.shape) == (56, 32))
    np.testing.assert_allclose(e_ser.numpy(), emb.numpy(), atol=2e-3)


def test_vocab_tp_requires_tp_axis():
    """vocab_tp without tp_axis must raise, not silently build a
    different (untied, unpadded) parameter set."""
    import pytest
    from singa_tpu import models
    with pytest.raises(ValueError, match="tp_axis"):
        models.create_model("gpt", vocab_size=50, vocab_tp=True)


def test_tp_gpt_vocab_parallel_predictions_only():
    """vocab_tp_return_logits=False: the train step never materializes
    (B,S,V) logits — it returns per-token argmax predictions (B,S) int32
    computed from the shards, and they match the gathered-logits argmax."""
    from singa_tpu import models, opt, tensor
    from singa_tpu.device import get_default_device

    dev = get_default_device()
    rng = np.random.RandomState(9)
    V, B, S = 48, 4, 8
    ids = rng.randint(0, V, (B, S)).astype(np.int32)
    tgt = np.roll(ids, -1, axis=1).astype(np.int32)
    tx = tensor.from_numpy(ids, dev)
    ty = tensor.from_numpy(tgt, dev)

    def build(return_logits):
        m = models.create_model(
            "gpt", vocab_size=V, max_seq=S, dim=32, num_heads=4,
            num_layers=1, tp_axis="tp", vocab_tp=True,
            vocab_pad_multiple=8,
            vocab_tp_return_logits=return_logits)
        mesh = make_mesh({"data": 2, "tp": 4})
        m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.0), axis="data",
                                    mesh=mesh))
        m.compile([tx], is_train=True, use_graph=True)
        return m

    m_full = build(True)
    w0 = {k: v.numpy().copy() for k, v in m_full.get_params().items()}
    m_pred = build(False)
    m_pred.set_params(w0)

    logits, l1 = m_full(tx, ty)
    preds, l2 = m_pred(tx, ty)
    assert abs(float(l1.numpy()) - float(l2.numpy())) < 1e-5
    assert preds.shape == (B, S) and preds.numpy().dtype == np.int32
    np.testing.assert_array_equal(preds.numpy(),
                                  np.argmax(logits.numpy(), axis=-1))


def test_pp_gpt_through_model_api():
    """PipelinedGPT on a {data:1, pp:4} mesh via Model.compile(
    pipeline_axis=, n_micro=) matches the same model run serially."""
    from singa_tpu import models, opt, tensor
    from singa_tpu.device import get_default_device

    dev = get_default_device()
    rng = np.random.RandomState(5)
    V, B, S = 40, 8, 8
    ids = rng.randint(0, V, (B, S)).astype(np.int32)
    tgt = np.roll(ids, -1, axis=1).astype(np.int32)
    tx = tensor.from_numpy(ids, dev)
    ty = tensor.from_numpy(tgt, dev)

    def build(pp=False):
        m = models.create_model("gpt_pipe", vocab_size=V, max_seq=S,
                                dim=16, num_heads=2, num_layers=4)
        if pp:
            mesh = make_mesh({"data": 1, "pp": 4})
            m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.05), axis="data",
                                        mesh=mesh))
            m.compile([tx], is_train=True, use_graph=True,
                      pipeline_axis="pp", n_micro=4)
        else:
            m.set_optimizer(opt.SGD(lr=0.05))
            m.compile([tx], is_train=True, use_graph=True)
        return m

    m_ser = build()
    w0 = {k: v.numpy().copy() for k, v in m_ser.get_params().items()}
    m_pp = build(pp=True)
    m_pp.set_params(w0)

    for _ in range(3):
        _, l_ser = m_ser(tx, ty)
        _, l_pp = m_pp(tx, ty)
    assert abs(float(l_ser.numpy()) - float(l_pp.numpy())) < 2e-3, \
        (float(l_ser.numpy()), float(l_pp.numpy()))
    # stage-sharded stacks updated correctly on every stage
    for k in ("Wq", "W1"):
        np.testing.assert_allclose(m_ser.get_params()[k].numpy(),
                                   m_pp.get_params()[k].numpy(),
                                   atol=2e-3, err_msg=k)


def test_pp_gpt_1f1b_matches_serial():
    """pipeline_schedule="1f1b": the fused fwd+bwd interleaved schedule
    (loss inside the pipeline, remat per stage, in-flight activations
    bounded by ~2*stages) trains to the same losses/params as the serial
    model — and therefore as GPipe."""
    from singa_tpu import models, opt, tensor
    from singa_tpu.device import get_default_device

    dev = get_default_device()
    rng = np.random.RandomState(11)
    V, B, S = 40, 8, 8
    ids = rng.randint(0, V, (B, S)).astype(np.int32)
    tgt = np.roll(ids, -1, axis=1).astype(np.int32)
    tx = tensor.from_numpy(ids, dev)
    ty = tensor.from_numpy(tgt, dev)

    def build(pp=False):
        m = models.create_model("gpt_pipe", vocab_size=V, max_seq=S,
                                dim=16, num_heads=2, num_layers=4)
        if pp:
            mesh = make_mesh({"data": 1, "pp": 4})
            m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.05), axis="data",
                                        mesh=mesh))
            m.compile([tx], is_train=True, use_graph=True,
                      pipeline_axis="pp", n_micro=4,
                      pipeline_schedule="1f1b")
        else:
            m.set_optimizer(opt.SGD(lr=0.05))
            m.compile([tx], is_train=True, use_graph=True)
        return m

    m_ser = build()
    w0 = {k: v.numpy().copy() for k, v in m_ser.get_params().items()}
    m_pp = build(pp=True)
    m_pp.set_params(w0)

    for _ in range(3):
        _, l_ser = m_ser(tx, ty)
        _, l_pp = m_pp(tx, ty)
    assert abs(float(l_ser.numpy()) - float(l_pp.numpy())) < 2e-3, \
        (float(l_ser.numpy()), float(l_pp.numpy()))
    for k in ("Wq", "W1", "ln_f.gamma", "tok_embed.W"):
        np.testing.assert_allclose(m_ser.get_params()[k].numpy(),
                                   m_pp.get_params()[k].numpy(),
                                   atol=2e-3, err_msg=k)


def test_pp_non_uniform_stages():
    """num_layers % stages != 0: 5 layers over 4 stages —
    stacks padded to 8 rows, masked to identity past row 5; numerics match
    the serial model for BOTH schedules."""
    from singa_tpu import models, opt, tensor
    from singa_tpu.device import get_default_device

    dev = get_default_device()
    rng = np.random.RandomState(13)
    V, B, S, L = 40, 8, 8, 5
    ids = rng.randint(0, V, (B, S)).astype(np.int32)
    tgt = np.roll(ids, -1, axis=1).astype(np.int32)
    tx = tensor.from_numpy(ids, dev)
    ty = tensor.from_numpy(tgt, dev)

    def build(schedule=None):
        m = models.create_model("gpt_pipe", vocab_size=V, max_seq=S,
                                dim=16, num_heads=2, num_layers=L)
        if schedule:
            mesh = make_mesh({"data": 1, "pp": 4})
            m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.05), axis="data",
                                        mesh=mesh))
            m.compile([tx], is_train=True, use_graph=True,
                      pipeline_axis="pp", n_micro=2,
                      pipeline_schedule=schedule)
        else:
            m.set_optimizer(opt.SGD(lr=0.05))
            m.compile([tx], is_train=True, use_graph=True)
        return m

    m_ser = build()
    assert m_ser.get_params()["Wq"].shape[0] == L  # no padding serially
    w0 = {k: v.numpy().copy() for k, v in m_ser.get_params().items()}

    for schedule in ("gpipe", "1f1b"):
        m_pp = build(schedule)
        assert m_pp.get_params()["Wq"].shape[0] == 8, \
            m_pp.get_params()["Wq"].shape  # padded to 4*ceil(5/4)
        m_pp.set_params(w0)  # (5,...) loads into (8,...) real rows
        losses = []
        for _ in range(3):
            _, l_ser = m_ser(tx, ty)
            _, l_pp = m_pp(tx, ty)
            losses = [float(l_ser.numpy()), float(l_pp.numpy())]
        assert abs(losses[0] - losses[1]) < 2e-3, (schedule, losses)
        # trained real rows match; padding rows untouched (zero weights)
        wq_pp = m_pp.get_params()["Wq"].numpy()
        np.testing.assert_allclose(m_ser.get_params()["Wq"].numpy(),
                                   wq_pp[:L], atol=2e-3,
                                   err_msg=schedule)
        assert np.all(wq_pp[L:] == 0.0), schedule
        # reset the serial model for the second schedule pass
        m_ser.set_params(w0)


def test_pp_interleaved_matches_serial():
    """interleave=2 (virtual chunks, Megatron interleaved stages): each
    of 4 devices holds 2 round-robin chunks; the looped-ring schedule
    (parallel/pipeline.py gpipe_interleaved) must train identically to
    the serial model — including the stack-row permutation on load and
    a non-uniform layer count (L=6 over 4 stages x 2 chunks -> pc=1,
    2 padding chunks)."""
    from singa_tpu import models, opt, tensor
    from singa_tpu.device import get_default_device
    from singa_tpu.parallel.pipeline import (pipeline_bubble_fraction,
                                             schedule_table)

    dev = get_default_device()
    rng = np.random.RandomState(17)
    V, B, S = 40, 8, 8
    ids = rng.randint(0, V, (B, S)).astype(np.int32)
    tgt = np.roll(ids, -1, axis=1).astype(np.int32)
    tx = tensor.from_numpy(ids, dev)
    ty = tensor.from_numpy(tgt, dev)

    for L in (8, 6):
        def build(pp=False):
            m = models.create_model(
                "gpt_pipe", vocab_size=V, max_seq=S, dim=16, num_heads=2,
                num_layers=L, interleave=2 if pp else 1)
            if pp:
                mesh = make_mesh({"data": 1, "pp": 4})
                m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.05), axis="data",
                                            mesh=mesh))
                m.compile([tx], is_train=True, use_graph=True,
                          pipeline_axis="pp", n_micro=4)
            else:
                m.set_optimizer(opt.SGD(lr=0.05))
                m.compile([tx], is_train=True, use_graph=True)
            return m

        m_ser = build()
        w0 = {k: v.numpy().copy() for k, v in m_ser.get_params().items()}
        m_pp = build(pp=True)
        # interleaved stacks are (V, n*pc, ...) = (2, 4, ...): the shape
        # itself disambiguates canonical inputs from round-trips
        assert tuple(m_pp.get_params()["Wq"].shape)[:2] == (2, 4)
        m_pp.set_params(w0)  # canonical (L, ...) reshapes into place

        for _ in range(3):
            _, l_ser = m_ser(tx, ty)
            _, l_pp = m_pp(tx, ty)
        assert abs(float(l_ser.numpy()) - float(l_pp.numpy())) < 2e-3, \
            (L, float(l_ser.numpy()), float(l_pp.numpy()))
        # trained rows match in canonical order (a reshape, not a gather)
        wq_pp = m_pp.canonical_stacks()["Wq"][:L]
        # and a same-config round trip is exact (no double permutation)
        m2 = build(pp=True)
        m2.set_params(m_pp.get_params())
        np.testing.assert_array_equal(
            m2.get_params()["Wq"].numpy(),
            m_pp.get_params()["Wq"].numpy())
        np.testing.assert_allclose(m_ser.get_params()["Wq"].numpy(),
                                   wq_pp, atol=2e-3, err_msg=str(L))

    # the schedule accounting: interleaving beats gpipe, 1f1b loses
    # bubble but bounds memory (the dryrun prints this table)
    b_g = pipeline_bubble_fraction(8, 32, "gpipe")
    b_i = pipeline_bubble_fraction(8, 32, "interleaved", 2)
    b_1 = pipeline_bubble_fraction(8, 32, "1f1b")
    assert b_i < b_g < b_1, (b_i, b_g, b_1)
    rows = schedule_table(8, 32, 2)
    assert [r[0] for r in rows] == ["gpipe", "1f1b", "interleaved x2"]
    assert rows[1][2] > 1.0  # 1f1b's remat compute overhead is stated


def test_pp_interleaved_rejects_1f1b():
    from singa_tpu import models, opt, tensor
    from singa_tpu.device import get_default_device
    dev = get_default_device()
    ids = np.zeros((8, 8), np.int32)
    tx = tensor.from_numpy(ids, dev)
    ty = tensor.from_numpy(ids, dev)
    m = models.create_model("gpt_pipe", vocab_size=40, max_seq=8, dim=16,
                            num_heads=2, num_layers=8, interleave=2)
    mesh = make_mesh({"data": 1, "pp": 4})
    m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.05), axis="data", mesh=mesh))
    with pytest.raises(ValueError, match="interleave"):
        m.compile([tx], is_train=True, use_graph=True, pipeline_axis="pp",
                  n_micro=4, pipeline_schedule="1f1b")


def test_pp_ep_moe_gpt_matches_serial():
    """PP x EP: PipelinedGPT(moe_experts=4, ep_axis="ep")
    on a {data:1, pp:2, ep:2} mesh — MoE FFN inside the pipeline stage
    scan, expert dispatch via all_to_all over ep within each slot. In
    the no-drop regime (capacity_factor=num_experts) with router-loss
    weights zeroed, losses must match the same model run serially (whose
    fallback is exactly the non-pipelined dense-dispatch MoE); a second
    model with default ST-MoE loss weights must train finitely."""
    from singa_tpu import models, opt, tensor
    from singa_tpu.device import get_default_device

    dev = get_default_device()
    rng = np.random.RandomState(23)
    V, B, S, L = 40, 8, 8, 4
    ids = rng.randint(0, V, (B, S)).astype(np.int32)
    tgt = np.roll(ids, -1, axis=1).astype(np.int32)
    tx = tensor.from_numpy(ids, dev)
    ty = tensor.from_numpy(tgt, dev)

    def build(pp=False, aux_w=0.0, z_w=0.0):
        m = models.create_model(
            "gpt_pipe", vocab_size=V, max_seq=S, dim=16, num_heads=2,
            num_layers=L, moe_experts=4, moe_k=2,
            moe_capacity_factor=4.0, ep_axis="ep" if pp else None,
            moe_aux_weight=aux_w, moe_z_weight=z_w)
        if pp:
            mesh = make_mesh({"data": 1, "pp": 2, "ep": 2})
            m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.05),
                                        axis=("data", "ep"), mesh=mesh))
            m.compile([tx], is_train=True, use_graph=True,
                      pipeline_axis="pp", n_micro=2)
        else:
            m.set_optimizer(opt.SGD(lr=0.05))
            m.compile([tx], is_train=True, use_graph=True)
        return m

    m_ser = build()
    assert "moeW1" in m_ser.get_params() and \
        "W1" not in m_ser.get_params()
    w0 = {k: v.numpy().copy() for k, v in m_ser.get_params().items()}
    m_pp = build(pp=True)
    m_pp.set_params(w0)

    for _ in range(3):
        _, l_ser = m_ser(tx, ty)
        _, l_pp = m_pp(tx, ty)
    assert abs(float(l_ser.numpy()) - float(l_pp.numpy())) < 2e-3, \
        (float(l_ser.numpy()), float(l_pp.numpy()))
    # expert stacks trained consistently (reduced over data AND ep)
    np.testing.assert_allclose(m_ser.get_params()["moeW1"].numpy(),
                               m_pp.get_params()["moeW1"].numpy(),
                               atol=2e-3)

    # default router-loss weights: finite training through the aux path
    m_aux = build(pp=True, aux_w=0.01, z_w=1e-3)
    m_aux.set_params(w0)
    losses = []
    for _ in range(3):
        _, l_aux = m_aux(tx, ty)
        losses.append(float(l_aux.numpy()))
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses  # it actually trains


def test_pp_moe_rejects_unsupported_combos():
    from singa_tpu import models
    with pytest.raises(ValueError, match="tp_axis"):
        models.create_model("gpt_pipe", vocab_size=40, moe_experts=4,
                            tp_axis="tp")
    with pytest.raises(ValueError, match="interleave"):
        models.create_model("gpt_pipe", vocab_size=40, moe_experts=4,
                            interleave=2)


def test_pp_tp_3d_gpt():
    """PP x TP composition on a {data:2, pp:2, tp:2} mesh (Megatron 3D
    minus sequence dims): block weights shard over tp inside pipeline
    stages (custom-vjp f/g), and vocab_tp=True row-shards the tied
    embedding/head table over tp. Both schedules match the serial model."""
    from singa_tpu import models, opt, tensor
    from singa_tpu.device import get_default_device

    dev = get_default_device()
    rng = np.random.RandomState(17)
    V, B, S, L = 50, 8, 8, 2
    ids = rng.randint(0, V, (B, S)).astype(np.int32)
    tgt = np.roll(ids, -1, axis=1).astype(np.int32)
    tx = tensor.from_numpy(ids, dev)
    ty = tensor.from_numpy(tgt, dev)

    def build(schedule=None):
        m = models.create_model(
            "gpt_pipe", vocab_size=V, max_seq=S, dim=16, num_heads=2,
            num_layers=L, tp_axis="tp", vocab_tp=True,
            vocab_pad_multiple=8)
        if schedule:
            mesh = make_mesh({"data": 2, "pp": 2, "tp": 2})
            m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.05), axis="data",
                                        mesh=mesh))
            m.compile([tx], is_train=True, use_graph=True,
                      pipeline_axis="pp", n_micro=2,
                      pipeline_schedule=schedule)
        else:
            m.set_optimizer(opt.SGD(lr=0.05))
            m.compile([tx], is_train=True, use_graph=True)
        return m

    m_ser = build()
    assert m_ser.head is None and m_ser.padded_vocab == 56
    w0 = {k: v.numpy().copy() for k, v in m_ser.get_params().items()}

    for schedule in ("gpipe", "1f1b"):
        m_3d = build(schedule)
        m_3d.set_params(w0)
        losses = None
        for _ in range(3):
            _, l_ser = m_ser(tx, ty)
            _, l_3d = m_3d(tx, ty)
            losses = (float(l_ser.numpy()), float(l_3d.numpy()))
        assert abs(losses[0] - losses[1]) < 3e-3, (schedule, losses)
        # block weights actually sharded over tp: Wq (Lp, E, E) carries
        # E/2 local columns; the vocab table carries V_pad/2 local rows
        wq = m_3d.get_params()["Wq"]
        assert wq.data.addressable_shards[0].data.shape[-1] == 16 // 2
        emb = next(v for v in m_3d.get_params().values()
                   if tuple(v.shape) == (56, 16))
        assert emb.data.addressable_shards[0].data.shape[0] == 56 // 2
        # trained stacks match serial
        np.testing.assert_allclose(m_ser.get_params()["Wq"].numpy(),
                                   wq.numpy(), atol=3e-3,
                                   err_msg=schedule)
        m_ser.set_params(w0)  # reset for the next schedule

    # misuse guard
    import pytest
    with pytest.raises(ValueError, match="tp_axis"):
        models.create_model("gpt_pipe", vocab_size=V, vocab_tp=True)


def test_pp_vocab_tp_without_tp_axis_in_mesh():
    """PipelinedGPT(vocab_tp=True, tp_axis=...) trained on a mesh WITHOUT
    the tp axis (pp-only): the tied head falls back to the full padded
    table with masked padding columns — 1F1B's in-schedule loss included —
    and matches the serial model."""
    from singa_tpu import models, opt, tensor
    from singa_tpu.device import get_default_device

    dev = get_default_device()
    rng = np.random.RandomState(23)
    V, B, S, L = 50, 8, 8, 2
    ids = rng.randint(0, V, (B, S)).astype(np.int32)
    tgt = np.roll(ids, -1, axis=1).astype(np.int32)
    tx = tensor.from_numpy(ids, dev)
    ty = tensor.from_numpy(tgt, dev)

    def build(pp=False):
        m = models.create_model(
            "gpt_pipe", vocab_size=V, max_seq=S, dim=16, num_heads=2,
            num_layers=L, tp_axis="tp", vocab_tp=True,
            vocab_pad_multiple=8)
        if pp:
            mesh = make_mesh({"data": 2, "pp": 4})  # NO tp axis
            m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.05), axis="data",
                                        mesh=mesh))
            m.compile([tx], is_train=True, use_graph=True,
                      pipeline_axis="pp", n_micro=2,
                      pipeline_schedule="1f1b")
        else:
            m.set_optimizer(opt.SGD(lr=0.05))
            m.compile([tx], is_train=True, use_graph=True)
        return m

    m_ser = build()
    w0 = {k: v.numpy().copy() for k, v in m_ser.get_params().items()}
    m_pp = build(pp=True)
    m_pp.set_params(w0)
    for _ in range(3):
        _, l_ser = m_ser(tx, ty)
        _, l_pp = m_pp(tx, ty)
    assert abs(float(l_ser.numpy()) - float(l_pp.numpy())) < 3e-3, \
        (float(l_ser.numpy()), float(l_pp.numpy()))


def _stage_apply(params, x):
    W, b = params
    return jnp.tanh(x @ W + b)


def test_gpipe_matches_serial():
    n_stages, n_micro, mb, d = 4, 8, 4, 16
    mesh = make_mesh({"pp": n_stages})
    rng = np.random.default_rng(1)
    Ws = rng.standard_normal((n_stages, d, d)).astype(np.float32) * 0.3
    bs = rng.standard_normal((n_stages, d)).astype(np.float32) * 0.1
    x = rng.standard_normal((n_micro, mb, d)).astype(np.float32)

    # serial reference
    ref = x.reshape(n_micro * mb, d)
    for i in range(n_stages):
        ref = np.tanh(ref @ Ws[i] + bs[i])
    ref = ref.reshape(n_micro, mb, d)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P("pp"), P("pp"), P()), out_specs=P(), check_vma=False)
    def run(W, b, xm):
        outs = gpipe(_stage_apply, (W[0], b[0]), xm, "pp")
        return last_stage_value(outs, "pp")

    out = run(jnp.asarray(Ws), jnp.asarray(bs), jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)


def test_gpipe_differentiable():
    """jax.grad flows through the pipeline scan + ppermute."""
    n_stages, n_micro, mb, d = 4, 4, 2, 8
    mesh = make_mesh({"pp": n_stages})
    rng = np.random.default_rng(2)
    Ws = rng.standard_normal((n_stages, d, d)).astype(np.float32) * 0.3
    bs = np.zeros((n_stages, d), np.float32)
    x = rng.standard_normal((n_micro, mb, d)).astype(np.float32)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P("pp"), P("pp"), P()), out_specs=P(), check_vma=False)
    def loss_pp(W, b, xm):
        outs = gpipe(_stage_apply, (W[0], b[0]), xm, "pp")
        return jnp.sum(last_stage_value(outs, "pp") ** 2)

    def loss_serial(W, b, xm):
        h = xm.reshape(-1, d)
        for i in range(n_stages):
            h = jnp.tanh(h @ W[i] + b[i])
        return jnp.sum(h ** 2)

    gW_pp = jax.grad(loss_pp)(jnp.asarray(Ws), jnp.asarray(bs),
                              jnp.asarray(x))
    gW_ser = jax.grad(loss_serial)(jnp.asarray(Ws), jnp.asarray(bs),
                                   jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(gW_pp), np.asarray(gW_ser),
                               rtol=2e-3, atol=2e-3)


def test_tp_gqa_gpt_matches_serial():
    """GQA composes with tensor parallelism: kv heads shard over tp like
    query heads (kv_heads % tp == 0 enforced); numerics match serial."""
    from singa_tpu import models, opt, tensor
    from singa_tpu.device import get_default_device

    dev = get_default_device()
    rng = np.random.RandomState(31)
    V, B, S = 50, 4, 16
    ids = rng.randint(0, V, (B, S)).astype(np.int32)
    tgt = np.roll(ids, -1, axis=1).astype(np.int32)
    tx = tensor.from_numpy(ids, dev)
    ty = tensor.from_numpy(tgt, dev)

    def build(tp_axis=None, dist=False):
        m = models.create_model("gpt", vocab_size=V, max_seq=S, dim=32,
                                num_heads=8, num_kv_heads=4,
                                num_layers=2, tp_axis=tp_axis)
        if dist:
            mesh = make_mesh({"data": 2, "tp": 4})
            m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.05), axis="data",
                                        mesh=mesh))
        else:
            m.set_optimizer(opt.SGD(lr=0.05))
        m.compile([tx], is_train=True, use_graph=True)
        return m

    m_ser = build()
    assert tuple(m_ser.blocks[0].attn.Wk.shape) == (32, 16)  # Hkv*D
    w0 = {k: v.numpy().copy() for k, v in m_ser.get_params().items()}
    m_tp = build(tp_axis="tp", dist=True)
    m_tp.set_params(w0)

    for _ in range(3):
        _, l_ser = m_ser(tx, ty)
        _, l_tp = m_tp(tx, ty)
    assert abs(float(l_ser.numpy()) - float(l_tp.numpy())) < 2e-3, \
        (float(l_ser.numpy()), float(l_tp.numpy()))


def test_pp_gqa_gpt_matches_serial():
    """GQA composes with pipeline parallelism (both schedules): Wk/Wv
    stacks are (L, E, Hkv*D) and the functional block repeats kv heads
    before flash."""
    from singa_tpu import models, opt, tensor
    from singa_tpu.device import get_default_device

    dev = get_default_device()
    rng = np.random.RandomState(37)
    V, B, S = 40, 8, 8
    ids = rng.randint(0, V, (B, S)).astype(np.int32)
    tgt = np.roll(ids, -1, axis=1).astype(np.int32)
    tx = tensor.from_numpy(ids, dev)
    ty = tensor.from_numpy(tgt, dev)

    def build(schedule=None):
        m = models.create_model("gpt_pipe", vocab_size=V, max_seq=S,
                                dim=16, num_heads=4, num_kv_heads=2,
                                num_layers=4)
        if schedule:
            mesh = make_mesh({"data": 1, "pp": 4})
            m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.05), axis="data",
                                        mesh=mesh))
            m.compile([tx], is_train=True, use_graph=True,
                      pipeline_axis="pp", n_micro=4,
                      pipeline_schedule=schedule)
        else:
            m.set_optimizer(opt.SGD(lr=0.05))
            m.compile([tx], is_train=True, use_graph=True)
        return m

    m_ser = build()
    assert tuple(m_ser.get_params()["Wk"].shape) == (4, 16, 8)  # Hkv*D
    w0 = {k: v.numpy().copy() for k, v in m_ser.get_params().items()}
    for schedule in ("gpipe", "1f1b"):
        m_pp = build(schedule)
        m_pp.set_params(w0)
        for _ in range(3):
            _, l_ser = m_ser(tx, ty)
            _, l_pp = m_pp(tx, ty)
        assert abs(float(l_ser.numpy()) - float(l_pp.numpy())) < 2e-3, \
            (schedule, float(l_ser.numpy()), float(l_pp.numpy()))
        m_ser.set_params(w0)


def test_pp_rope_gpt_matches_serial_and_transfers():
    """pos_encoding="rope" on PipelinedGPT (ADVICE r4): the stage fns
    rotate q/k per block with the global position tables, NO learned
    position table exists, and the trained stacks transfer to a serial
    rope GPT (same loss trajectory) — the exact property the silently-
    ignored flag used to break."""
    from singa_tpu import models, opt, tensor
    from singa_tpu.device import get_default_device

    dev = get_default_device()
    rng = np.random.RandomState(41)
    V, B, S, L = 40, 8, 8, 4
    ids = rng.randint(0, V, (B, S)).astype(np.int32)
    tgt = np.roll(ids, -1, axis=1).astype(np.int32)
    tx = tensor.from_numpy(ids, dev)
    ty = tensor.from_numpy(tgt, dev)

    def build(schedule=None):
        m = models.create_model("gpt_pipe", vocab_size=V, max_seq=S,
                                dim=16, num_heads=2, num_layers=L,
                                pos_encoding="rope")
        if schedule:
            mesh = make_mesh({"data": 1, "pp": 4})
            m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.05), axis="data",
                                        mesh=mesh))
            m.compile([tx], is_train=True, use_graph=True,
                      pipeline_axis="pp", n_micro=4,
                      pipeline_schedule=schedule)
        else:
            m.set_optimizer(opt.SGD(lr=0.05))
            m.compile([tx], is_train=True, use_graph=True)
        return m

    m_ser = build()
    # rope: no learned position table at all
    assert "pos_embed" not in m_ser.get_params()
    w0 = {k: v.numpy().copy() for k, v in m_ser.get_params().items()}
    for schedule in ("gpipe", "1f1b"):
        m_pp = build(schedule)
        assert "pos_embed" not in m_pp.get_params()
        m_pp.set_params(w0)
        for _ in range(3):
            _, l_ser = m_ser(tx, ty)
            _, l_pp = m_pp(tx, ty)
        assert abs(float(l_ser.numpy()) - float(l_pp.numpy())) < 2e-3, \
            (schedule, float(l_ser.numpy()), float(l_pp.numpy()))
        m_ser.set_params(w0)

    # rope result differs from a learned-position model (the old bug made
    # them identical): same seed/weights, different positional mechanism
    m_learned = models.create_model("gpt_pipe", vocab_size=V, max_seq=S,
                                    dim=16, num_heads=2, num_layers=L)
    m_learned.set_optimizer(opt.SGD(lr=0.05))
    m_learned.compile([tx], is_train=True, use_graph=True)
    m_learned.set_params({k: v for k, v in w0.items()})
    _, l_rope = m_ser(tx, ty)
    _, l_learn = m_learned(tx, ty)
    assert abs(float(l_rope.numpy()) - float(l_learn.numpy())) > 1e-5

    # weight TRANSFER: the pipelined rope stacks load into a serial rope
    # GPT (per-block params) and reproduce the same loss trajectory
    gpt = models.create_model("gpt", vocab_size=V, max_seq=S, dim=16,
                              num_heads=2, num_layers=L,
                              pos_encoding="rope")
    gpt.set_optimizer(opt.SGD(lr=0.05))
    gpt.compile([tx], is_train=True, use_graph=True)
    m_ser.set_params(w0)
    stacks = {k: np.asarray(v) for k, v in w0.items()}
    for i, blk in enumerate(gpt.blocks):
        blk.ln1.gamma.copy_from_numpy(stacks["g1"][i])
        blk.ln1.beta.copy_from_numpy(stacks["b1"][i])
        blk.ln2.gamma.copy_from_numpy(stacks["g2"][i])
        blk.ln2.beta.copy_from_numpy(stacks["b2"][i])
        blk.attn.Wq.copy_from_numpy(stacks["Wq"][i])
        blk.attn.Wk.copy_from_numpy(stacks["Wk"][i])
        blk.attn.Wv.copy_from_numpy(stacks["Wv"][i])
        blk.attn.Wo.copy_from_numpy(stacks["Wo"][i])
        blk.fc1.W.copy_from_numpy(stacks["W1"][i])
        blk.fc1.b.copy_from_numpy(stacks["bb1"][i])
        blk.fc2.W.copy_from_numpy(stacks["W2"][i])
        blk.fc2.b.copy_from_numpy(stacks["bb2"][i])
    gpt.tok_embed.W.copy_from_numpy(stacks["tok_embed.W"])
    gpt.ln_f.gamma.copy_from_numpy(stacks["ln_f.gamma"])
    gpt.ln_f.beta.copy_from_numpy(stacks["ln_f.beta"])
    gpt.head.W.copy_from_numpy(stacks["head.W"])
    for _ in range(2):
        _, l_pipe = m_ser(tx, ty)
        _, l_gpt = gpt(tx, ty)
    assert abs(float(l_pipe.numpy()) - float(l_gpt.numpy())) < 2e-3, \
        (float(l_pipe.numpy()), float(l_gpt.numpy()))
