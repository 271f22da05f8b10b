"""MoE tests: expert-parallel all_to_all path matches the dense path;
layer trains; routing respects capacity."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from singa_tpu.parallel import make_mesh
from singa_tpu.parallel.moe import (moe_ffn, moe_ffn_ep, top1_gating,
                                    topk_gating)


def _weights(rng, D=16, H=32, E=4):
    Wg = rng.standard_normal((D, E)).astype(np.float32)
    W1 = rng.standard_normal((E, D, H)).astype(np.float32) * 0.2
    b1 = np.zeros((E, H), np.float32)
    W2 = rng.standard_normal((E, H, D)).astype(np.float32) * 0.2
    b2 = np.zeros((E, D), np.float32)
    return Wg, W1, b1, W2, b2


def test_top1_gating_capacity():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32))
    Wg = jnp.asarray(rng.standard_normal((8, 2)).astype(np.float32))
    dispatch, combine, aux = top1_gating(x, Wg, capacity=3)
    # each expert holds at most 3 tokens, each token at most one slot
    assert float(jnp.max(jnp.sum(dispatch, axis=(0, 2)))) <= 3.0
    assert float(jnp.max(jnp.sum(dispatch, axis=(1, 2)))) <= 1.0
    assert np.isfinite(float(aux))


def test_top2_gating():
    """Top-2 routing: each token occupies at most 2 slots,
    gates renormalize over the chosen pair, capacity still binds, and the
    z-loss / overflow stats are surfaced."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32))
    Wg = jnp.asarray(rng.standard_normal((8, 4)).astype(np.float32))
    dispatch, combine, aux, z, ovf = topk_gating(x, Wg, capacity=16, k=2)
    # every token kept twice at generous capacity; combine sums to 1
    per_tok = np.asarray(jnp.sum(dispatch, axis=(1, 2)))
    np.testing.assert_allclose(per_tok, 2.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(jnp.sum(combine, axis=(1, 2))),
                               1.0, atol=1e-5)
    assert float(ovf) == 0.0
    assert np.isfinite(float(z)) and float(z) > 0
    # tight capacity drops routes and reports them
    d2, c2, _, _, ovf2 = topk_gating(x, Wg, capacity=2, k=2)
    assert float(jnp.max(jnp.sum(d2, axis=(0, 2)))) <= 2.0
    assert 0.0 < float(ovf2) < 1.0


def test_ep_matches_dense_top2():
    """4-way EP top-2 == dense top-2 at generous capacity."""
    n = 4
    mesh = make_mesh({"ep": n})
    rng = np.random.default_rng(3)
    D, H, E, T = 16, 32, 4, 32
    Wg, W1, b1, W2, b2 = _weights(rng, D, H, E)
    x = rng.standard_normal((T, D)).astype(np.float32)

    ref, _, _ = moe_ffn(jnp.asarray(x), jnp.asarray(Wg), jnp.asarray(W1),
                        jnp.asarray(b1), jnp.asarray(W2), jnp.asarray(b2),
                        capacity_factor=float(E), k=2)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P("ep"), P(), P("ep"), P("ep"), P("ep"), P("ep")),
        out_specs=P("ep"), check_vma=False)
    def run(x, Wg, W1, b1, W2, b2):
        y, _, _ = moe_ffn_ep(x, Wg, W1, b1, W2, b2, "ep",
                             capacity_factor=float(E), k=2)
        return y

    out = run(jnp.asarray(x), jnp.asarray(Wg), jnp.asarray(W1),
              jnp.asarray(b1), jnp.asarray(W2), jnp.asarray(b2))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_moe_gpt_model_api():
    """MoE-GPT through Model/DistOpt on a {data, ep} mesh (EP
    training through the framework, not the functional path). DistOpt
    reduces over BOTH axes (tuple axis) so replicated params stay in sync
    and grad-scaled expert slices recover the dense-equivalent update;
    losses match the same model run serially (generous capacity)."""
    from singa_tpu import models, opt, tensor
    from singa_tpu.device import get_default_device

    dev = get_default_device()
    rng = np.random.RandomState(21)
    V, B, S, E = 40, 8, 8, 4
    ids = rng.randint(0, V, (B, S)).astype(np.int32)
    tgt = np.roll(ids, -1, axis=1).astype(np.int32)
    tx = tensor.from_numpy(ids, dev)
    ty = tensor.from_numpy(tgt, dev)

    def build(dist=False):
        # router-loss weights zeroed for EXACT serial/EP parity: the aux
        # loss is nonlinear in the token distribution, so mean-of-per-
        # device aux != global aux (its gradient path is covered by
        # test_moe_aux_loss_grads_reach_gate)
        m = models.create_model(
            "gpt", vocab_size=V, max_seq=S, dim=16, num_heads=2,
            num_layers=2, moe_experts=E, moe_k=2, ep_axis="ep",
            moe_capacity_factor=float(E), moe_aux_weight=0.0,
            moe_z_weight=0.0)
        if dist:
            mesh = make_mesh({"data": 2, "ep": 4})
            m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.05),
                                        axis=("data", "ep"), mesh=mesh))
        else:
            m.set_optimizer(opt.SGD(lr=0.05))
        m.compile([tx], is_train=True, use_graph=True)
        return m

    m_ser = build()
    w0 = {k: v.numpy().copy() for k, v in m_ser.get_params().items()}
    m_ep = build(dist=True)
    m_ep.set_params(w0)

    for _ in range(3):
        _, l_ser = m_ser(tx, ty)
        _, l_ep = m_ep(tx, ty)
    assert abs(float(l_ser.numpy()) - float(l_ep.numpy())) < 3e-3, \
        (float(l_ser.numpy()), float(l_ep.numpy()))
    # expert weights trained identically (grad-scale x pmean correct)
    k1 = next(k for k in w0 if k.endswith("moe.W1"))
    np.testing.assert_allclose(m_ser.get_params()[k1].numpy(),
                               m_ep.get_params()[k1].numpy(), atol=3e-3)
    assert not np.allclose(m_ser.get_params()[k1].numpy(), w0[k1]), \
        "experts did not train"


def test_moe_ep_requires_tuple_reduction():
    """DistOpt(axis="data") on a {data, ep} mesh with an EP MoE must
    hard-raise at compile: a data-only reduction silently diverges the
    replicated expert tables across ep ranks."""
    import pytest
    from singa_tpu import models, opt, tensor
    from singa_tpu.device import get_default_device

    dev = get_default_device()
    rng = np.random.RandomState(5)
    ids = rng.randint(0, 40, (8, 8)).astype(np.int32)
    tx = tensor.from_numpy(ids, dev)
    m = models.create_model("gpt", vocab_size=40, max_seq=8, dim=16,
                            num_heads=2, num_layers=1, moe_experts=4,
                            ep_axis="ep")
    mesh = make_mesh({"data": 2, "ep": 4})
    m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.05), axis="data", mesh=mesh))
    with pytest.raises(ValueError, match="diverge"):
        m.compile([tx], is_train=True, use_graph=True)
        ty = tensor.from_numpy(np.roll(ids, -1, 1).astype(np.int32), dev)
        m(tx, ty)


def test_ep_matches_dense():
    """4-way EP with tokens sharded == dense single-device on same data."""
    n = 4
    mesh = make_mesh({"ep": n})
    rng = np.random.default_rng(1)
    D, H, E, T = 16, 32, 4, 32
    Wg, W1, b1, W2, b2 = _weights(rng, D, H, E)
    x = rng.standard_normal((T, D)).astype(np.float32)

    # dense reference with generous capacity (nothing dropped)
    ref, _, _ = moe_ffn(jnp.asarray(x), jnp.asarray(Wg), jnp.asarray(W1),
                        jnp.asarray(b1), jnp.asarray(W2), jnp.asarray(b2),
                        capacity_factor=float(E))

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P("ep"), P(), P("ep"), P("ep"), P("ep"), P("ep")),
        out_specs=P("ep"), check_vma=False)
    def run(x, Wg, W1, b1, W2, b2):
        y, aux, _ = moe_ffn_ep(x, Wg, W1, b1, W2, b2, "ep",
                               capacity_factor=float(E))
        return y

    out = run(jnp.asarray(x), jnp.asarray(Wg), jnp.asarray(W1),
              jnp.asarray(b1), jnp.asarray(W2), jnp.asarray(b2))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_moe_layer_trains(dev, train_mode):
    from singa_tpu import autograd, layer, opt, tensor
    rng = np.random.RandomState(0)
    x_np = rng.randn(32, 16).astype(np.float32)
    y_np = rng.randn(32, 16).astype(np.float32)

    moe = layer.MoE(num_experts=4, hidden=32)
    sgd = opt.SGD(lr=0.05)
    tx = tensor.Tensor(data=x_np, device=dev)
    ty = tensor.from_numpy(y_np, device=dev)

    aux_w = tensor.from_numpy(np.float32(0.01), device=dev)
    losses = []
    for _ in range(6):
        out = moe(tx)
        loss = autograd.add(autograd.mse_loss(out, ty),
                            autograd.mul(moe.aux_loss, aux_w))
        sgd(loss)
        losses.append(float(loss.numpy()))
    assert losses[-1] < losses[0]
    assert moe.aux_loss is not None


def test_moe_aux_loss_grads_reach_gate(dev, train_mode):
    """The load-balancing term must produce nonzero gate-weight grads
    (regression: it used to be stop_gradient'd to death)."""
    from singa_tpu import autograd, layer, tensor
    rng = np.random.RandomState(1)
    moe = layer.MoE(num_experts=4, hidden=8)
    tx = tensor.Tensor(data=rng.randn(32, 8).astype(np.float32), device=dev)
    moe(tx)  # init
    out = moe(tx)
    grads = autograd.gradients(moe.aux_loss)
    gWg = grads.get(moe.Wg)
    assert gWg is not None and float(np.abs(gWg.numpy()).max()) > 0


def test_moe_gpt_ep_x_tp():
    """EP x TP composition: attention/LN run Megatron
    tensor-parallel over `tp` while the MoE FFN dispatches experts over
    `ep` (expert compute replicates across tp ranks — the MoE has no tp
    sharding, so each tp rank runs the same dispatch; correct because
    grads coincide across tp). Losses and trained experts match the
    serial model."""
    from singa_tpu import models, opt, tensor
    from singa_tpu.device import get_default_device

    dev = get_default_device()
    rng = np.random.RandomState(23)
    V, B, S, E = 40, 8, 8, 4
    ids = rng.randint(0, V, (B, S)).astype(np.int32)
    tgt = np.roll(ids, -1, axis=1).astype(np.int32)
    tx = tensor.from_numpy(ids, dev)
    ty = tensor.from_numpy(tgt, dev)

    def build(dist=False):
        m = models.create_model(
            "gpt", vocab_size=V, max_seq=S, dim=16, num_heads=2,
            num_layers=2, moe_experts=E, moe_k=2, ep_axis="ep",
            tp_axis="tp" if dist else None,
            moe_capacity_factor=float(E), moe_aux_weight=0.0,
            moe_z_weight=0.0)
        if dist:
            mesh = make_mesh({"data": 2, "tp": 2, "ep": 2})
            m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.05),
                                        axis=("data", "ep"), mesh=mesh))
        else:
            m.set_optimizer(opt.SGD(lr=0.05))
        m.compile([tx], is_train=True, use_graph=True)
        return m

    m_ser = build()
    w0 = {k: v.numpy().copy() for k, v in m_ser.get_params().items()}
    m_mix = build(dist=True)
    m_mix.set_params(w0)

    for _ in range(3):
        _, l_ser = m_ser(tx, ty)
        _, l_mix = m_mix(tx, ty)
    assert abs(float(l_ser.numpy()) - float(l_mix.numpy())) < 3e-3, \
        (float(l_ser.numpy()), float(l_mix.numpy()))
    k1 = next(k for k in w0 if k.endswith("moe.W1"))
    np.testing.assert_allclose(m_ser.get_params()[k1].numpy(),
                               m_mix.get_params()[k1].numpy(), atol=3e-3)
