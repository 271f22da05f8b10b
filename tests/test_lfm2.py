"""The sparse model of gated short convolutions and attention layers
(models/lfm2.py) against its plain reference (benchmark/reference_lfm2.py),
and what it is built from: `layer.ShortConv` against the reference's
operator (values, every gradient, causality, the zero history), the sigmoid
route under a selection bias (a state, never in the gates), the shares of
the experts adding up to the uncut layer, the scopes and the gauges.

Small size on the CPU: four layers (conv + dense, attention + experts, two
conv + experts), hidden 64, 4 query and 2 KV heads of 16, 3 taps, a dense
feed-forward of 160, 8 experts of width 48 routed top-4 of which this
device holds 4 from the third on, vocabulary 96, 2 x 256 tokens (the flash
kernels run in interpret mode).
"""

import functools
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu import device, layer, models, observe, opt, tensor
from singa_tpu.models import lfm2
from singa_tpu.ops import shortconv
from singa_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "benchmark_" + name, os.path.join(ROOT, "benchmark", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference_lfm2")

CFG = dict(vocab_size=96, dim=64, num_heads=4, num_kv_heads=2, head_dim=16,
           layer_types=["conv", "full_attention", "conv", "conv"],
           conv_taps=3, num_dense_layers=1, dense_ffn_dim=160, ffn_dim=48,
           num_experts=8, experts_per_token=4, experts_held=4,
           expert_offset=2, routed_scaling_factor=1.0, use_expert_bias=True,
           bias_update_rate=1e-3, rope_theta=1e6, norm_eps=1e-5, sample=16)
B, S, L, E = 2, 256, 4, 8
NO_FLAGS = {f: jnp.bool_(False) for f in ref._FLAGS}


class _Keep(opt.SGD):
    """An optimizer that changes nothing and keeps every gradient."""

    def __init__(self):
        super().__init__(lr=0.0)
        self.grads = {}

    def apply(self, param, grad):
        self.grads[id(param)] = grad.data


def _batch(seed=0):
    ids = np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], (B, S + 1)).astype(np.int32)
    return ids[:, :-1].copy(), ids[:, 1:].copy()


def _bias0():
    """A bias as a checkpoint taken mid-training would hold: the first
    step has to exercise it, and a fresh model's is zero."""
    b = np.zeros((L, E), np.float32)
    b[1:] = np.random.default_rng(5).normal(0, 0.05, (L - 1, E))
    return b


def _build(amp=None, recompute=False, graph=False, optimizer=None, **over):
    dev = device.get_default_device()
    dev.SetRandSeed(3)
    m = models.create_model("lfm2", **dict(CFG, recompute=recompute, **over))
    m.set_optimizer(optimizer or _Keep())
    x, _ = _batch()
    m.compile([tensor.from_numpy(x[:1, :128], device=dev)], is_train=True,
              use_graph=graph, amp=amp)
    if m.use_bias:
        m.set_states({f"TransformerBlock_{i}.moe.b": _bias0()[i]
                      for i in m.sparse_layers()})
    return m, dev


def _params(m):
    return {k: jnp.asarray(tensor.to_numpy(v))
            for k, v in m.get_params().items()}


def _rel(a, b):
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b)))
                 / (np.max(np.abs(np.asarray(b))) + 1e-30))


@functools.lru_cache(maxsize=None)
def _stepped(amp, recompute):
    """(initial parameters, loss, sample, rows, load, {name: gradient},
    the bias after the step) of one eager training step on batch 0."""
    m, dev = _build(amp, recompute)
    p0 = _params(m)
    x, y = _batch()
    out = m(tensor.from_numpy(x, device=dev), tensor.from_numpy(y, device=dev))
    names = {id(p): k for k, p in m.get_params().items()}
    grads = {names[i]: np.asarray(g) for i, g in m.optimizer.grads.items()}
    return (p0, *(np.asarray(o.data) for o in out), grads, m.router_bias())


@functools.lru_cache(maxsize=None)
def _reference():
    x, y = _batch()
    p0 = _stepped(None, False)[0]
    at = np.linspace(0, B * S - 1, CFG["sample"]).astype(np.int32)
    return ref.loss_parts(p0, _bias0(), x, y, CFG, rows=at,
                          token_block=128), \
        ref.grad(p0, jnp.asarray(_bias0()), x, y, CFG)


# bf16 against the fp32 reference at this size: the loss reads up to 2e-3
# off, a gradient up to a third of its largest entry (an expert's, when one
# of its few hundred rows went elsewhere); a (token, choice) pair whose 4th
# and 5th scores tie within bf16's rounding of the stream moves from one
# expert to another, and an expert whose load lies that near the mean has
# its bias moved the other way. fp32: rounding order only.
TOL = {None: dict(loss=2e-6, logits=5e-5, grad=5e-4, pairs=0, bias=0),
       "bfloat16": dict(loss=5e-3, logits=1e-1, grad=4e-1, pairs=24, bias=2)}


@pytest.mark.parametrize("recompute", [False, True, 2])
@pytest.mark.parametrize("amp", [None, "bfloat16"])
def test_step_matches_reference(amp, recompute):
    """Loss, the sampled logits, the rows routed to each held expert and
    the pairs sent to each of ALL experts of each layer, the gradient of
    EVERY parameter (taps and routers included) and the bias after the
    step."""
    p0, loss, sample, rows, load, grads, bias = _stepped(amp, recompute)
    want, g_ref = _reference()
    tol = TOL[amp]
    assert abs(loss - want["loss"]) / want["loss"] <= tol["loss"]
    assert _rel(sample, want["sample"]) <= tol["logits"]
    assert rows.shape == (L, 4) and load.shape == (L, E)
    assert np.array_equal(rows, load[:, 2:6])       # the held experts' part
    assert not load[0].any() and (load[1:].sum(1) == B * S * 4).all()
    assert np.abs(load - want["load"]).sum() / 2 <= tol["pairs"]
    assert set(grads) == set(g_ref) == set(p0)
    worst = {k: _rel(grads[k], g_ref[k]) for k in grads}
    assert max(worst.values()) <= tol["grad"], worst
    # the bias moved by the rate, towards the idle experts, and only it
    moved = np.abs(bias - _bias0())
    assert not moved[0].any() and np.allclose(
        moved[1:][load[1:] != load[1:].mean(1, keepdims=True)], 1e-3)
    assert (np.abs(bias - want["bias"]) > 5e-4).sum() <= tol["bias"]


@pytest.mark.parametrize("amp", [None, "bfloat16"])
def test_forward_matches_reference(amp):
    m, dev = _build(amp)
    x, _ = _batch()
    m.eval()
    z = m(tensor.from_numpy(x, device=dev))
    want = ref.logits(_params(m), ref.hidden(_params(m), _bias0(), x, CFG)[0])
    assert z.shape == (B, S, CFG["vocab_size"])
    rms = float(jnp.sqrt(jnp.mean((z.data - want) ** 2)) / jnp.std(want))
    assert rms <= (1e-5 if amp is None else 8e-2), rms
    # eval moves no bias
    assert np.array_equal(m.router_bias(), _bias0())


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_wrong_models_read_far_from_the_right_one(wrong):
    """Each deliberately wrong reference moves the logits by far more than
    bf16 does: what the cell's limits rest on."""
    x, _ = _batch()
    p0 = _stepped(None, False)[0]
    z = ref.logits(p0, ref.hidden(p0, _bias0(), x, CFG)[0])
    zw = ref.logits(p0, ref.hidden(p0, _bias0(), x, CFG, wrong, expert=1)[0])
    err = float(jnp.sqrt(jnp.mean((zw - z) ** 2)) / jnp.std(z))
    assert err > 0.04, err


def test_reference_grads_by_layer_equal_its_whole_gradient():
    x, y = _batch()
    p0 = _stepped(None, False)[0]
    whole = _reference()[1]
    parts = ref.grads(p0, jnp.asarray(_bias0()), x, y, CFG, token_block=128)
    assert max(_rel(parts[k], whole[k]) for k in whole) <= 1e-5


def test_recompute_equals_the_ordinary_tape():
    """A rebuilt block gives the values of a kept one bit for bit and its
    gradients to the rounding of a sum taken in another order."""
    plain, again = (_stepped(None, rc) for rc in (False, True))
    for a, b in zip(plain[1:5], again[1:5]):
        assert np.array_equal(a, b)
    assert np.array_equal(plain[6], again[6])
    assert max(_rel(again[5][k], plain[5][k]) for k in plain[5]) <= 1e-5


@functools.lru_cache(maxsize=None)
def _graph_run(amp, recompute):
    """(what two graph-mode Adam steps hand back, the parameters and the
    bias after the first, the optimizer's state, the step's lowered
    text)."""
    m, dev = _build(amp, recompute, graph=True, optimizer=opt.Adam(lr=1e-3))
    x, y = _batch()
    tx, ty = (tensor.from_numpy(a, device=dev) for a in (x, y))
    outs = [np.asarray(o.data) for o in m(tx, ty)]
    params = {k: tensor.to_numpy(v) for k, v in m.get_params().items()}
    bias = m.router_bias()
    outs += [np.asarray(o.data) for o in m(tx, ty)]
    return outs, params, bias, (m.router_bias(),
                                len(m.optimizer.state_arrays())), \
        m.lower_step().as_text(debug_info=True)


@pytest.mark.parametrize("recompute", [False, 3])
def test_graph_step_with_amp_matches_reference(recompute):
    """Through `Model.compile(use_graph=True, amp="bfloat16")` with Adam:
    the first step's loss, logits and load, the parameters after it against
    the reference's gradient put through Adam's first step, the bias after
    it (the graph step carries the state), and a second step whose loss is
    lower and whose bias moved again."""
    outs, after, bias, (bias2, n_opt), _ = _graph_run("bfloat16", recompute)
    want, g_ref = _reference()
    tol = TOL["bfloat16"]
    assert abs(outs[0] - want["loss"]) / want["loss"] <= tol["loss"]
    assert _rel(outs[1], want["sample"]) <= tol["logits"]
    assert np.abs(outs[3] - want["load"]).sum() / 2 <= tol["pairs"]
    assert outs[4] < outs[0]
    assert (np.abs(bias - want["bias"]) > 5e-4).sum() <= tol["bias"]
    step2 = np.abs(bias2 - bias)[1:]
    assert step2.max() <= 1.001e-3 and (step2 > 9e-4).mean() > 0.9
    p0 = _stepped(None, False)[0]
    check = _load("update_check")
    err = check.Expected(p0, g_ref, 1e-3, 0.0).error_of_step(
        {k: jnp.asarray(v) for k, v in after.items()})
    assert err["leaves_compared"] == len(p0) and err["worst_leaf"] < 0.7, err
    # Adam keeps two moments a PARAMETER and its step count: none for a bias
    assert n_opt == 2 * len(p0) + 1


def test_scopes_name_the_convolution_s_parts_and_the_bias_update():
    text = _graph_run("bfloat16", 3)[4]
    names = set(re.findall(r'"jit\(step\)/([^"]*)"', text))
    has = lambda part: any(part in n for n in names)
    for part in ("in_proj", "mix", "out_proj"):
        assert has(f"TransformerBlock_0/conv/{part}/"), part
        # the second forward is traced for its vjp, the backward is that
        assert has(f"recompute/TransformerBlock_2/conv/jvp({part})/"), part
        assert has(f"bwd/TransformerBlock_3/conv/transpose(jvp({part}))"), \
            part
    assert has("TransformerBlock_1/moe/router/") and has("router_bias/")
    assert has("TransformerBlock_1/attn/") and not has(
        "TransformerBlock_0/attn/") and not has("TransformerBlock_1/conv/")
    assert has("TransformerBlock_0/fc_gate/") and not has(
        "TransformerBlock_0/moe/")
    assert has("head/") and has("sce/")


def test_plan_and_load_gauges():
    m, dev = _build("bfloat16", 3)
    x, y = _batch()
    _, _, rows, load = m(tensor.from_numpy(x, device=dev),
                         tensor.from_numpy(y, device=dev))
    reg = observe.get_registry()
    g = reg.get("singa_moe_plan")
    plan = {k: int(g.value(kind=k)) for k in (
        "experts", "held", "k", "rows_worst", "recomputed_blocks",
        "dense_layers", "sigmoid", "bias")}
    assert plan == {"experts": 8, "held": 4, "k": 4, "rows_worst": B * S * 4,
                    "recomputed_blocks": 3, "dense_layers": 1, "sigmoid": 1,
                    "bias": 1}
    g = reg.get("singa_conv_plan")
    assert {k: int(g.value(kind=k)) for k in (
        "layers", "channels", "taps", "recomputed_blocks")} == {
            "layers": 3, "channels": 64, "taps": 3, "recomputed_blocks": 2}
    bias = m.router_bias()
    lfm2.record_rows(rows.data, load.data, bias, dense_layers=1)
    r, ld = np.asarray(rows.data), np.asarray(load.data)
    gr, gl, gb = (reg.get(n) for n in (
        "singa_moe_rows", "singa_moe_load", "singa_moe_bias"))
    for l in range(1, L):
        assert gr.value(layer=str(l), kind="routed") == r[l].sum()
        assert gl.value(layer=str(l), kind="max") == ld[l].max()
        assert gl.value(layer=str(l), kind="mean") == B * S * 4 / E
        assert gl.value(layer=str(l), kind="min") == ld[l].min()
        assert gb.value(layer=str(l), kind="max") == pytest.approx(
            bias[l].max())
        assert gb.value(layer=str(l), kind="min") == pytest.approx(
            bias[l].min())


# ---- the convolution operator on its own ----------------------------------------

def _conv_args(T=32, D=16, taps=3, seed=0, batch=2):
    rng = np.random.default_rng(seed)
    mk = lambda s, *shape: jnp.asarray(rng.standard_normal(shape) * s,
                                       jnp.float32)
    return (mk(1, batch, T, D), mk(.3, D, 3 * D), mk(.5, D, taps),
            mk(.3, D, D))


def _conv_ref(x, W_in, w, W_out):
    """The reference's operator a sequence, on the program's arguments."""
    p = {"conv.W_in": W_in, "conv.w": w, "conv.W_out": W_out}
    return jnp.stack([ref._conv(seq, p, NO_FLAGS) for seq in x])


@pytest.mark.parametrize("taps", [3, 4, 1])
def test_short_conv_matches_reference(taps):
    """Values and the gradient of the input and of all three parameters."""
    args = _conv_args(taps=taps)
    with jax.default_matmul_precision("highest"):
        assert _rel(shortconv.short_conv(*args), _conv_ref(*args)) < 1e-5
        grad = lambda fn: jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), (0, 1, 2, 3))(*args)
        got, want = grad(shortconv.short_conv), grad(_conv_ref)
    assert max(_rel(a, b) for a, b in zip(got, want)) < 2e-5
    assert all(bool(jnp.any(g != 0)) for g in got)


@pytest.mark.parametrize("t", [0, 1, 7, 31])
def test_short_conv_is_causal(t):
    """A change at position t leaves every output before t as it was, in
    every sequence, and moves position t's."""
    x, W_in, w, W_out = _conv_args()
    y = shortconv.short_conv(x, W_in, w, W_out)
    y2 = shortconv.short_conv(x.at[:, t].add(1.0), W_in, w, W_out)
    assert np.array_equal(np.asarray(y[:, :t]), np.asarray(y2[:, :t]))
    assert bool(jnp.all(jnp.any(y[:, t] != y2[:, t], axis=-1)))


def test_short_conv_starts_from_a_zero_history():
    """Position 0 reads its own z through the last tap alone, position 1
    the last two: nothing stands before a sequence, and no sequence of the
    batch reads its neighbour's end."""
    x, W_in, w, W_out = _conv_args()
    b, c, u = jnp.split(x @ W_in, 3, -1)
    z = b * u
    want0 = (c[:, 0] * w[:, 2] * z[:, 0]) @ W_out
    want1 = (c[:, 1] * (w[:, 1] * z[:, 0] + w[:, 2] * z[:, 1])) @ W_out
    y = shortconv.short_conv(x, W_in, w, W_out)
    assert _rel(y[:, 0], want0) < 1e-5 and _rel(y[:, 1], want1) < 1e-5
    alone = shortconv.short_conv(x[1:], W_in, w, W_out)
    assert _rel(alone[0], y[1]) < 1e-5


def test_short_conv_layer_holds_three_parameters_and_takes_amp():
    dev = device.get_default_device()
    dev.SetRandSeed(1)
    blk = layer.TransformerBlock(4, mixer="conv", conv_taps=3, norm="rms",
                                 ffn="swiglu", ffn_dim=96, ffn_bias=False)
    x = tensor.from_numpy(np.random.default_rng(0).standard_normal(
        (2, 32, 64)).astype(np.float32), device=dev)
    y = blk(x)
    assert y.shape == (2, 32, 64)
    shapes = {k: v.shape for k, v in blk.get_params().items()}
    assert {k: v for k, v in shapes.items() if k.startswith("conv.")} == {
        "conv.W_in": (64, 192), "conv.W_out": (64, 64), "conv.w": (64, 3)}
    assert not any(k.startswith("attn.") for k in shapes)
    assert float(jnp.abs(blk.conv.w.data).max()) <= 3 ** -0.5
    with pytest.raises(AssertionError):
        layer.TransformerBlock(4, mixer="ssm")


def test_onnx_export_refuses_the_short_convolution_by_name():
    from singa_tpu.sonnx import frontend
    assert "_ShortConvOp" in frontend.UNEXPORTABLE
    assert "ShortConv" in frontend.UNEXPORTABLE["_ShortConvOp"]
    assert "_ShortConvOp" not in frontend.EXPORTABLE


# ---- the route ------------------------------------------------------------------

def _experts(T=64, D=32, F=48, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda s, *shape: jnp.asarray(rng.standard_normal(shape) * s,
                                       jnp.float32)
    return (mk(1, T, D), mk(1, D, E), mk(.2, E, D, F), mk(.2, E, D, F),
            mk(.2, E, F, D))


def _route_as_before(x, Wr, k):
    """`route_topk` as it stood before it took a score and a bias."""
    logits = jnp.dot(x.astype(jnp.float32), Wr.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    topv, topi = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    return topv / jnp.sum(topv, axis=-1, keepdims=True), topi


def test_softmax_default_routes_as_before_bit_for_bit():
    """Gates and indices, and the traced program itself: the two sparse
    cells that share the function lower to the text they lowered to."""
    x, Wr = _experts()[:2]
    got, want = moe.route_topk(x, Wr, 4), _route_as_before(x, Wr, 4)
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    trace = lambda fn: str(jax.make_jaxpr(lambda x, Wr: fn(x, Wr, 4))(x, Wr))
    assert trace(moe.route_topk) == trace(_route_as_before)
    # and without a bias the layer counts no load: two results, as before
    assert len(moe.dropless_moe(*_experts(), 4)) == 2


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_bias_moves_the_selection_and_never_the_gates(score):
    x, Wr = _experts()[:2]
    s = (jax.nn.sigmoid if score == "sigmoid" else
         functools.partial(jax.nn.softmax, axis=-1))(jnp.dot(
             x, Wr, precision=jax.lax.Precision.HIGHEST))
    kw = dict(score=score, scale=2.5, eps=1e-6)
    plain_g, plain_i = moe.route_topk(x, Wr, 4, **kw)
    assert np.array_equal(np.asarray(plain_i),
                          np.asarray(jax.lax.top_k(s, 4)[1]))
    # a bias that lifts expert 7 over every score and sinks expert 0
    bias = jnp.zeros(E).at[7].set(2.0).at[0].set(-2.0)
    g, i = moe.route_topk(x, Wr, 4, bias=bias, **kw)
    assert bool(jnp.all(jnp.any(i == 7, -1))) and not bool(jnp.any(i == 0))
    assert not np.array_equal(np.asarray(i), np.asarray(plain_i))
    # the gates are the chosen experts' PLAIN scores over their sum, scaled
    chosen = jnp.take_along_axis(s, i, -1)
    want = chosen / (chosen.sum(-1, keepdims=True) + 1e-6) * 2.5
    assert _rel(g, want) < 1e-6
    assert _rel(g.sum(-1), jnp.full(64, 2.5)) < 2e-4   # less the 1e-6
    # a bias of zeros selects what no bias selects, at the same gates
    g0, i0 = moe.route_topk(x, Wr, 4, bias=jnp.zeros(E), **kw)
    assert np.array_equal(np.asarray(i0), np.asarray(plain_i))
    assert _rel(g0, plain_g) < 1e-6


def test_bias_takes_no_gradient_and_the_load_counts_every_expert():
    x, Wr, Wg, Wu, Wd = _experts()
    bias = jnp.asarray(np.random.default_rng(1).normal(0, 0.1, E),
                       jnp.float32)
    kw = dict(score="sigmoid", eps=1e-6)

    def value(bias, x, Wr):
        return jnp.sum(jnp.sin(moe.dropless_moe(
            x, Wr, Wg[2:6], Wu[2:6], Wd[2:6], 4, 2, bias, **kw)[0]))

    db, dx, dWr = jax.grad(value, (0, 1, 2))(bias, x, Wr)
    assert not bool(jnp.any(db)) and bool(jnp.any(dx)) and bool(jnp.any(dWr))
    y, rows, load = moe.dropless_moe(x, Wr, Wg[2:6], Wu[2:6], Wd[2:6], 4, 2,
                                     bias, **kw)
    experts = moe.route_topk(x, Wr, 4, bias=bias, **kw)[1]
    assert [int(n) for n in load] == [int(jnp.sum(experts == e))
                                      for e in range(E)]
    assert int(load.sum()) == 64 * 4
    assert np.array_equal(np.asarray(rows), np.asarray(load[2:6]))


def test_the_four_shares_add_up_to_the_uncut_reference_s_layer():
    """Four shares of 2 of 8 experts under the sigmoid route and a bias,
    summed, equal the reference's UNCUT expert layer (what the four chips
    of an expert-parallel group would exchange and add); every share
    counts the same load, which is the whole layer's."""
    x, Wr, Wg, Wu, Wd = _experts()
    bias = jnp.asarray(np.random.default_rng(2).normal(0, 0.1, E),
                       jnp.float32)
    kw = dict(score="sigmoid", scale=1.0, eps=ref.GATE_EPS)
    whole_cfg = {"experts_per_token": 4, "expert_offset": 0}
    p = {"moe.Wr": Wr, "moe.Wg": Wg, "moe.Wu": Wu, "moe.Wd": Wd}
    with jax.default_matmul_precision("highest"):
        want, want_rows, want_load = ref._moe(x, p, bias, whole_cfg,
                                              NO_FLAGS, jnp.int32(-1))
        parts = [moe.dropless_moe(x, Wr, Wg[o:o + 2], Wu[o:o + 2],
                                  Wd[o:o + 2], 4, o, bias, **kw)
                 for o in range(0, E, 2)]
    assert _rel(sum(p[0] for p in parts), want) < 1e-5
    assert np.array_equal(np.concatenate([p[1] for p in parts]),
                          np.asarray(want_rows, np.float32))
    for part in parts:
        assert np.array_equal(np.asarray(part[2]),
                              np.asarray(want_load, np.float32))
    assert int(want_load.sum()) == 64 * 4           # every pair, once


def test_the_bias_is_a_state_saved_and_restored_and_in_no_optimizer(
        tmp_path):
    m, dev = _build(graph=True, optimizer=opt.Adam(lr=1e-3))
    names = [f"TransformerBlock_{i}.moe.b" for i in (1, 2, 3)]
    states, params = m.get_states(), m.get_params()
    assert all(n in states and n not in params for n in names)
    assert "TransformerBlock_0.moe.b" not in states
    assert not states[names[0]].requires_grad
    x, y = _batch()
    m(tensor.from_numpy(x, device=dev), tensor.from_numpy(y, device=dev))
    # Adam set up a state for the parameters alone
    assert len(m.optimizer.state_arrays()) == 2 * len(params) + 1
    bias = m.router_bias()
    assert np.abs(bias - _bias0())[1:].max() == pytest.approx(1e-3, rel=1e-4)
    path = str(tmp_path / "ckpt.zip")
    m.save_states(path)
    m.set_states({n: np.zeros(E, np.float32) for n in names})
    assert not m.router_bias().any()
    m.load_states(path)
    assert np.array_equal(m.router_bias(), bias)


def test_a_model_without_the_bias_registers_none():
    m, dev = _build(use_expert_bias=False)
    assert not any(k.endswith(".moe.b") for k in m.get_states())
    x, y = _batch()
    out = m(tensor.from_numpy(x, device=dev), tensor.from_numpy(y, device=dev))
    assert not np.asarray(out[3].data).any()        # no load is counted
    assert not m.router_bias().any()
