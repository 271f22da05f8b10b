"""The looped language model (models/looplm.py) against its plain reference
(benchmark/reference_looplm.py), the tape regions it is built from
(autograd.Region against the ordinary tape), the once-a-step weight cast,
and the block arguments it shares with GPT.

Small size on the CPU: 3 blocks x T = 3, hidden 64, 4 heads of 16,
feed-forward 176, vocabulary 512, 2 x 100 tokens (no flash block divides
100: attention takes its jnp path here, the Pallas path in the one test
that counts kernel calls).
"""

import functools
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu import autograd, device, layer, models, observe, opt, tensor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "benchmark_" + name, os.path.join(ROOT, "benchmark", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference_looplm")

CFG = dict(vocab_size=512, dim=64, num_heads=4, num_layers=3, ffn_dim=176,
           ut_steps=3, rope_theta=1e6, norm_eps=1e-6, beta=0.1, sample=16)
B, S = 2, 100


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


def _build(amp=None, recompute=False, seed=3, **over):
    dev = device.get_default_device()
    dev.SetRandSeed(seed)
    m = models.create_model("looplm", **dict(CFG, recompute=recompute,
                                             **over))
    m.set_optimizer(_Keep())
    x, _ = _batch()
    m.compile([tensor.from_numpy(x[:1, :10], device=dev)], is_train=True,
              use_graph=False, amp=amp)
    # a gate that does something: the initial one is near a half everywhere
    w = np.random.default_rng(5).normal(0, 0.5, CFG["dim"]).astype(np.float32)
    m.set_params({"exit_gate.w": w, "exit_gate.b": np.float32(-0.3)})
    return m, dev


def _step(m, dev, x, y):
    """(loss, ce (T,), p (T,), sample (rows, V), {name: gradient}) of one
    eager training step."""
    out = m(tensor.from_numpy(x, device=dev), tensor.from_numpy(y, device=dev))
    names = {id(p): k for k, p in m.get_params().items()}
    grads = {names[i]: np.asarray(g) for i, g in m.optimizer.grads.items()}
    return [np.asarray(o.data) for o in out] + [grads]


def _params(m):
    return {k: jnp.asarray(tensor.to_numpy(v))
            for k, v in m.get_params().items()}


@functools.lru_cache(maxsize=None)
def _stepped(amp, recompute):
    """(initial parameters, _step's result) of one eager step on batch 0."""
    m, dev = _build(amp, recompute)
    return _params(m), _step(m, dev, *_batch())


def _rel(a, b):
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b)))
                 / (np.max(np.abs(np.asarray(b))) + 1e-30))


# bf16 against the fp32 reference at this size: the loss and its parts read
# 2e-4 to 3e-3 off, a gradient up to 4 % of its largest entry (bf16 keeps 8
# bits; 9 block applications and a softmax over 512 sit between a weight
# and the loss). fp32: rounding order only.
TOL = {None: dict(loss=2e-6, parts=1e-5, logits=2e-5, grad=2e-4),
       "bfloat16": dict(loss=5e-3, parts=1e-2, logits=5e-2, grad=1e-1)}


@pytest.mark.parametrize("recompute", [False, True])
@pytest.mark.parametrize("amp", [None, "bfloat16"])
def test_step_matches_reference(amp, recompute):
    """Loss, per-pass cross-entropies, exit distribution, the sampled
    logits and the gradient of EVERY parameter (shared block weights, N_f,
    gate, head, embedding)."""
    x, y = _batch()
    p0, (loss, ce, pm, sample, grads) = _stepped(amp, recompute)
    rows = np.linspace(0, B * S - 1, CFG["sample"]).astype(np.int32)
    want = ref.loss_parts(p0, x, y, CFG, rows=rows, token_block=100)
    tol = TOL[amp]
    assert abs(loss - want["loss"]) / want["loss"] <= tol["loss"]
    assert _rel(ce, want["ce"]) <= tol["parts"]
    assert _rel(pm, want["p"]) <= tol["parts"]
    assert _rel(sample, want["sample"]) <= tol["logits"]
    g_ref = ref.grad(p0, x, y, CFG)
    assert set(grads) == set(g_ref) == set(p0)
    # the gate's bias is one number, a sum over the positions of terms of
    # either sign: held to the gate weight's scale, not to its own
    g_ref["exit_gate.b"] = g_ref["exit_gate.b"] \
        + np.abs(g_ref["exit_gate.w"]).max() * np.array([0, 1])
    grads = dict(grads, **{"exit_gate.b": grads["exit_gate.b"]
                           + np.abs(g_ref["exit_gate.w"]).max()
                           * np.array([0, 1])})
    worst = {k: _rel(grads[k], g_ref[k]) for k in grads}
    assert max(worst.values()) <= tol["grad"], worst


@pytest.mark.parametrize("amp", [None, "bfloat16"])
def test_forward_matches_reference(amp):
    """Every pass's logits and the exit distribution at every position."""
    m, dev = _build(amp)
    x, _ = _batch()
    m.eval()
    z, p = m(tensor.from_numpy(x, device=dev))
    z_ref, _, p_ref = ref.forward(_params(m), x, CFG)
    assert z.shape == (CFG["ut_steps"], B, S, CFG["vocab_size"])
    for t in range(CFG["ut_steps"]):
        assert _rel(z.data[t], z_ref[t]) <= TOL[amp]["logits"], t
    assert _rel(p.data, p_ref) <= TOL[amp]["logits"]


@functools.lru_cache(maxsize=None)
def _graph_run(amp, recompute):
    """(what two graph-mode Adam steps hand back, the parameters after
    them, the step's lowered text)."""
    m, dev = _build(amp, recompute)
    m.set_optimizer(opt.Adam(lr=1e-3))
    x, y = _batch()
    m.compile([tensor.from_numpy(x[:1, :10], device=dev)], is_train=True,
              use_graph=True, amp=amp)
    tx, ty = (tensor.from_numpy(a, device=dev) for a in (x, y))
    outs = [np.asarray(o.data) for _ in range(2) for o in m(tx, ty)]
    params = {k: tensor.to_numpy(v) for k, v in m.get_params().items()}
    return outs, params, m.lower_step().as_text(debug_info=True)


# A region hands a tensor's cotangent back in one piece where the ordinary
# tape adds its consumers' parts one by one, so h_t's three parts (head and
# loss, gate, the next pass) are summed in another order: equal to rounding,
# not bit for bit. fp32 reads 9e-7 of a gradient's largest entry at worst,
# bf16 1.1e-4 (a changed last bit of a bf16 cotangent, carried down); the
# second graph step's loss 0 and 7e-5.
ORDER = {None: 1e-5, "bfloat16": 1e-3}


@pytest.mark.parametrize("graph", [False, True])
@pytest.mark.parametrize("amp", [None, "bfloat16"])
def test_recompute_equals_the_ordinary_tape(amp, graph):
    """The regions rebuilt in the backward pass against the same model on
    the ordinary tape: the first step's loss and outputs bit for bit (the
    same forward), gradients to the rounding of a sum taken in another
    order; in graph mode, the second step's loss after an Adam update."""
    if graph:
        plain, again = (_graph_run(amp, rc)[0] for rc in (False, True))
        for a, b in zip(plain[:4], again[:4]):
            assert np.array_equal(a, b)
        # Adam's first step is lr * sign(g) nearly: an entry whose sign the
        # order decides moves the other way, the loss after it hardly
        assert abs(plain[4] - again[4]) / plain[4] <= 10 * ORDER[amp]
        return
    plain, again = (_stepped(amp, rc)[1] for rc in (False, True))
    for a, b in zip(plain[:-1], again[:-1]):
        assert np.array_equal(a, b)
    assert set(plain[-1]) == set(again[-1])
    worst = {k: _rel(again[-1][k], plain[-1][k]) for k in plain[-1]}
    assert max(worst.values()) <= ORDER[amp], worst


def test_shared_parameter_gets_one_contribution_a_pass():
    """Untie the reference's weights pass by pass: copy t's gradient is what
    pass t adds, none is zero, and their sum is the program's gradient of
    the shared parameter."""
    x, y = _batch()
    p0, (*_, grads) = _stepped(None, False)
    T = CFG["ut_steps"]
    parts = jax.grad(ref.loss, argnums=4)(p0, x, y, CFG, [p0] * T)
    for k in ("TransformerBlock_0.attn.Wq", "TransformerBlock_2.fc2.W",
              "TransformerBlock_1.ln2_post.gamma", "ln_f.gamma"):
        each = [np.asarray(parts[t][k]) for t in range(T)]
        assert all(np.abs(e).max() > 0 for e in each), k
        assert _rel(grads[k], sum(each)) <= 2e-4, k
        assert min(_rel(grads[k], sum(each) - e) for e in each) > 1e-2, k


def test_exit_distribution_sums_to_one_and_last_takes_the_rest():
    lam = jax.random.uniform(jax.random.PRNGKey(0), (4, 50))
    p = models.looplm.exit_distribution(lam)
    assert np.allclose(p.sum(0), 1.0, atol=1e-6)
    assert np.allclose(p[-1], np.prod(1.0 - np.asarray(lam[:-1]), 0),
                       atol=1e-6)
    assert np.allclose(p[0], lam[0]) and np.allclose(
        p[1], lam[1] * (1 - lam[0]), atol=1e-7)
    assert np.allclose(np.stack(ref.exit_distribution(list(lam))), p,
                       atol=1e-7)
    assert np.array_equal(models.looplm.exit_distribution(lam[:1]),
                          np.ones((1, 50)))


def test_one_pass_is_a_plain_stack():
    """T = 1, the gate's weight zeroed: the exit distribution is (1), its
    entropy 0, and the loss is the plain stack's cross-entropy."""
    m, dev = _build(ut_steps=1)
    m.set_params({"exit_gate.w": np.zeros(CFG["dim"], np.float32)})
    x, y = _batch()
    loss, ce, pm, _, grads = _step(m, dev, x, y)
    one = dict(CFG, ut_steps=1)
    z = ref.forward(_params(m), x, one)[0][0]
    plain = float(jnp.mean(ref._ce(z, jnp.asarray(y))))
    assert abs(loss - plain) / plain <= 2e-6
    assert abs(ce[0] - plain) / plain <= 2e-6 and pm[0] == 1.0
    assert "exit_gate.w" not in grads or not grads["exit_gate.w"].any()


@pytest.mark.parametrize("recompute", [False, True])
def test_loop_plan_gauge(recompute):
    m, dev = _build("bfloat16", recompute, num_layers=6, ut_steps=4)
    x, y = _batch()
    _step(m, dev, x, y)
    g = observe.get_registry().get("singa_loop_plan")
    plan = {k: int(g.value(kind=k)) for k in (
        "passes", "blocks", "applications", "recomputed_regions",
        "weight_casts")}
    assert plan == {"passes": 4, "blocks": 6, "applications": 24,
                    "recomputed_regions": 28 if recompute else 0,
                    "weight_casts": 1}
    # a pass's cross-entropy reads the log-sum-exp its forward made: the
    # first forward's on the ordinary tape, the second's in a region
    ce = observe.get_registry().get("singa_cross_entropy")
    assert ce.value(targets="integer",
                    lse="rebuilt" if recompute else "kept") == 1
    assert ce.value(targets="integer",
                    lse="kept" if recompute else "rebuilt") == 0


# ---- the step program: scopes, casts, kernels --------------------------------

def _step_text(amp, recompute):
    return _graph_run(amp, recompute)[2]


def test_scopes_name_each_pass_and_the_recomputed_work():
    text = _step_text("bfloat16", True)
    names = set(re.findall(r'"jit\(step\)/([^"]*)"', text))
    has = lambda prefix: any(n.startswith(prefix) for n in names)
    for t in (1, 2, 3):
        assert has(f"ut{t}/TransformerBlock_0/attn/")
        assert has(f"ut{t}/ln_f/")
        assert has(f"bwd/ut{t}/TransformerBlock_2/fc2/")
        assert has(f"recompute/ut{t}/TransformerBlock_1/fc_gate/")
    assert not has("ut4/")
    # (of the replayed cross-entropy only the log-sum-exp feeds anything:
    # its hand-written backward reads that and the logits, so jax drops the
    # rest and the second forward is the head's matmul and those two sums)
    for scope in ("head/", "exit_gate/", "loop_loss/", "bwd/head/",
                  "bwd/exit_gate/", "bwd/loop_loss/", "recompute/head/",
                  "recompute/loop_loss/", "opt/", "tok_embed/"):
        assert has(scope), scope
    # nothing of a replay is named like the first forward, or nested
    assert not any("recompute" in n.split("/", 1)[1] for n in names
                   if n.startswith("recompute/"))
    assert not any(n.startswith("bwd/recompute") for n in names)


@pytest.mark.parametrize("recompute", [False, True])
def test_shared_weights_are_cast_once_a_step_and_summed_in_fp32(recompute):
    """One fp32 -> bf16 convert of each shared matrix (3 blocks x 7 and the
    head), whatever T and whether or not regions are rebuilt. Each use's
    gradient is cast back to fp32 before the T = 3 of them are summed: no
    sum of two bf16 weight gradients."""
    text = _step_text("bfloat16", recompute)
    casts = re.findall(
        r"stablehlo\.convert %arg\d+ : \(tensor<(\d+x\d+)xf32>\) -> "
        r"tensor<\d+x\d+xbf16>", text)     # %arg: a parameter of the step
    shapes = {"64x64": 4 * 3, "64x176": 2 * 3, "176x64": 3, "64x512": 1}
    assert {s: casts.count(s) for s in shapes} == shapes
    back = re.findall(
        r"stablehlo\.convert %[\w#]+ : \(tensor<(\d+x\d+)xbf16>\) -> "
        r"tensor<\d+x\d+xf32>", text)
    # (the head's matmul keeps fp32 and converts inside its own transpose)
    del shapes["64x512"]
    assert {s: back.count(s) for s in shapes} \
        == {s: n * 3 for s, n in shapes.items()}
    assert not re.findall(
        r"stablehlo\.add %[\w#]+, %[\w#]+ : "
        r"tensor<(?:64x64|64x176|176x64)xbf16>", text)


def test_recomputed_forward_calls_the_flash_kernel_again():
    """3 blocks x 3 passes: 9 forward kernels, 9 backward, and 9 more
    forwards where the regions are rebuilt."""
    count = lambda text, name: len(re.findall(
        r'pallas_call\[name=' + name + r"\b", text)) or text.count(name)
    for recompute, fwd in ((False, 9), (True, 18)):
        m, dev = _build("bfloat16", recompute)
        before = _dispatch()
        _step(m, dev, *_batch())
        delta = {k: v - before.get(k, 0) for k, v in _dispatch().items()}
        assert delta.get("flash_fwd", 0) == fwd, delta
        assert delta.get("flash_bwd", 0) == 9, delta


def _dispatch():
    c = observe.get_registry().get("singa_attention_dispatch_total")
    if c is None:
        return {}
    return {s: sum(int(c.value(site=s, path=p)) for p in observe.ATTN_PATHS)
            for s in observe.ATTN_SITES}


# ---- the tape's regions on their own ------------------------------------------

def _tiny_region(recompute, dev):
    """y = relu(x W) W2 with W read from outside, x an argument; a second
    output off the tape. As a region, or on the ordinary tape."""
    rng = np.random.default_rng(0)
    mk = lambda *s: tensor.from_numpy(
        rng.normal(size=s).astype(np.float32), device=dev)
    x, W, W2 = mk(4, 8), mk(8, 8), mk(8, 3)
    for t in (x, W, W2):
        t.requires_grad, t.stores_grad = True, True

    class Shape(autograd.Operator):
        never_requires_grad = True

        def forward(self, a):
            return a.sum(0)

    def fn(x):
        h = autograd.relu(autograd.matmul(x, W))
        return autograd.matmul(h, W2), Shape()(h)

    autograd.training = True
    try:
        y, side = autograd.region(fn, x, reads=(W, W2)) if recompute \
            else fn(x)
        loss = autograd.reduce_sum(autograd.mul(y, y), keepdims=False)
        g = autograd.gradients(loss)
    finally:
        autograd.training = False
    return y.data, side.data, [g[t].data for t in (x, W, W2)]


def test_region_gradients_match_jax_and_each_other():
    dev = device.get_default_device()
    kept, again = _tiny_region(False, dev), _tiny_region(True, dev)
    assert np.array_equal(kept[0], again[0])
    assert np.array_equal(kept[1], again[1])
    for a, b in zip(kept[2], again[2]):
        assert np.array_equal(a, b)
    rng = np.random.default_rng(0)
    x, W, W2 = (jnp.asarray(rng.normal(size=s).astype(np.float32))
                for s in ((4, 8), (8, 8), (8, 3)))
    want = jax.grad(lambda x, W, W2: jnp.sum(
        (jax.nn.relu(x @ W) @ W2) ** 2), (0, 1, 2))(x, W, W2)
    for a, b in zip(kept[2], want):
        assert np.allclose(a, b, rtol=1e-5, atol=1e-5)


def test_recomputed_region_keeps_no_residual():
    """Traced, the ordinary tape's backward reads what its forward left;
    a region's reads the region's inputs behind a barrier."""
    dev = device.get_default_device()

    def run(recompute):
        def f(_):
            return _tiny_region(recompute, dev)[2]
        return str(jax.make_jaxpr(f)(0))

    assert "optimization_barrier" in run(True)
    assert "optimization_barrier" not in run(False)


# ---- the block's new arguments leave GPT alone -------------------------------

class _OldBlock(layer.Layer):
    """layer.TransformerBlock as it stood before it took norm / ffn
    arguments (PR 26), dense path."""

    def __init__(self, num_heads, mlp_ratio=4, causal=True, attn_bias=False,
                 **_):
        super().__init__(None)
        self.ln1 = layer.LayerNorm()
        self.attn = layer.MultiHeadAttention(num_heads, causal=causal,
                                             bias=attn_bias)
        self.ln2 = layer.LayerNorm()
        self.mlp_ratio = mlp_ratio

    def initialize(self, x):
        e = x.shape[-1]
        self.fc1 = layer.Linear(e * self.mlp_ratio)
        self.fc2 = layer.Linear(e)

    def forward(self, x):
        x = autograd.add(x, self.attn(self.ln1(x)))
        h = autograd.gelu(self.fc1(self.ln2(x)))
        return autograd.add(x, self.fc2(h))


_OldBlock.__name__ = "TransformerBlock"   # register_layers names by class


def test_gpt_is_unchanged_by_the_blocks_new_arguments(monkeypatch):
    """The same names, and bit-equal logits and loss, from the block as it
    was and as it is."""
    dev = device.get_default_device()
    x, y = _batch()

    def run():
        dev.SetRandSeed(11)
        m = models.create_model("gpt", vocab_size=512, max_seq=S, dim=64,
                                num_heads=4, num_layers=2, attn_bias=True)
        m.set_optimizer(opt.SGD(lr=0.1))
        tx, ty = (tensor.from_numpy(a, device=dev) for a in (x, y))
        m.compile([tx], is_train=True, use_graph=True, amp="bfloat16")
        out = [(np.asarray(lg.data), float(loss.data))
               for lg, loss in (m(tx, ty) for _ in range(2))]
        return list(m.get_params()), out

    new_names, new = run()
    monkeypatch.setattr(layer, "TransformerBlock", _OldBlock)
    old_names, old = run()
    assert new_names == old_names
    for (lg_a, loss_a), (lg_b, loss_b) in zip(new, old):
        assert loss_a == loss_b and np.array_equal(lg_a, lg_b)


@pytest.mark.parametrize("out_dtype", [None, "float32"])
def test_embedding_hands_on_fp32_rows_under_amp_when_asked(out_dtype,
                                                           monkeypatch):
    """`Embedding(out_dtype="float32")`: the looked-up rows stay fp32 under
    `amp` (the looped model's residual stream); the default rounds them to
    the compute dtype, as GPT's does."""
    dev = device.get_default_device()
    emb = layer.Embedding(32, 8, out_dtype=out_dtype)
    ids = tensor.from_numpy(np.arange(6, dtype=np.int32).reshape(2, 3),
                            device=dev)
    monkeypatch.setattr(autograd, "compute_dtype", "bfloat16")
    y = emb(ids)
    assert y.data.dtype == (jnp.float32 if out_dtype else jnp.bfloat16)
    assert np.array_equal(np.asarray(y.data, np.float32),
                          np.asarray(emb.W.data[:6].reshape(2, 3, 8).astype(
                              y.data.dtype), np.float32))


@pytest.mark.parametrize("kind", ["rms", "swiglu"])
def test_new_operators(kind):
    dev = device.get_default_device()
    rng = np.random.default_rng(1)
    a = rng.normal(size=(3, 5, 16)).astype(np.float32)
    b = rng.normal(size=(3, 5, 16)).astype(np.float32)
    ta, tb = (tensor.from_numpy(v, device=dev) for v in (a, b))
    if kind == "rms":
        g = rng.normal(size=16).astype(np.float32)
        got = autograd.rmsnorm(ta, tensor.from_numpy(g, device=dev), 1e-6)
        want = a / np.sqrt((a * a).mean(-1, keepdims=True) + 1e-6) * g
    else:
        got = autograd.swiglu(ta, tb)
        want = a / (1 + np.exp(-a)) * b
    assert np.allclose(np.asarray(got.data), want, rtol=1e-5, atol=1e-6)
