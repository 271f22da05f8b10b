"""The dense model of gated delta-rule linear attention and full attention
layers (models/olmo_hybrid.py) against its plain reference
(benchmark/reference_olmo_hybrid.py), and what it is built from: the
chunked delta rule against the token recurrence (a sequence that is not a
multiple of the chunk, beta in (1, 2), the state left as it is where
alpha = 1 and beta = 0), the layer's causality, the whole-projection QK
norm, OLMo's norms after each branch, the loss over the vocabulary slice
and the gauge. (The other models' steps, lowered as before, are pinned in
tests/test_lowered_programs.py.)

Small size on the CPU: a period of four layers (linear, linear, linear,
full), hidden 64, 4 attention heads of 16, 2 linear heads of 8 / 16, 4
taps, chunks of 4, feed-forward 96, vocabulary 96, 2 x 10 tokens (the flash
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
from singa_tpu.ops import gated_delta

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "benchmark_" + name, os.path.join(ROOT, "benchmark", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference_olmo_hybrid")

LIN, FULL = "linear_attention", "full_attention"
CFG = dict(vocab_size=96, dim=64, num_heads=4, head_dim=16,
           layer_types=[LIN, LIN, LIN, FULL], ffn_dim=96, linear_heads=2,
           linear_key_dim=8, linear_value_dim=16, linear_conv_taps=4,
           linear_neg_eigval=True, chunk=4, norm_eps=1e-6, sample=8)
B, S = 2, 10


class _Keep(opt.SGD):
    """An optimizer that changes nothing and keeps every gradient."""

    def __init__(self):
        super().__init__(lr=0.0)
        self.grads = {}

    def apply(self, param, grad):
        self.grads[id(param)] = grad.data


def _batch(seed=0, vocab=CFG["vocab_size"]):
    ids = np.random.default_rng(seed).integers(
        0, vocab, (B, S + 1)).astype(np.int32)
    return ids[:, :-1].copy(), ids[:, 1:].copy()


def _build(amp=None, recompute=False, graph=False, optimizer=None, **over):
    dev = device.get_default_device()
    dev.SetRandSeed(3)
    m = models.create_model("olmo_hybrid",
                            **dict(CFG, recompute=recompute, **over))
    m.set_optimizer(optimizer or _Keep())
    x, _ = _batch()
    m.compile([tensor.from_numpy(x, device=dev)], is_train=True,
              use_graph=graph, amp=amp)
    return m, dev


def _params(m):
    return {k: jnp.asarray(tensor.to_numpy(v))
            for k, v in m.get_params().items()}


def _rel(a, b):
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b)))
                 / (np.max(np.abs(np.asarray(b))) + 1e-30))


@functools.lru_cache(maxsize=None)
def _stepped(amp, recompute, chunk=CFG["chunk"]):
    """(initial parameters, loss, sample, {name: gradient}) of one eager
    training step on batch 0 (chunks of 8: the delta rule's kernels, in
    interpret mode; of 4: its XLA form)."""
    m, dev = _build(amp, recompute, chunk=chunk)
    p0 = _params(m)
    x, y = _batch()
    out = m(tensor.from_numpy(x, device=dev), tensor.from_numpy(y, device=dev))
    names = {id(p): k for k, p in m.get_params().items()}
    grads = {names[i]: np.asarray(g) for i, g in m.optimizer.grads.items()}
    return (p0, *(np.asarray(o.data) for o in out), grads)


@functools.lru_cache(maxsize=None)
def _reference():
    x, y = _batch()
    p0 = _stepped(None, False)[0]
    at = np.linspace(0, B * S - 1, CFG["sample"]).astype(np.int32)
    return ref.loss_parts(p0, x, y, CFG, rows=at, token_block=8), \
        ref.grad(p0, x, y, CFG)


# fp32: rounding order only; every leaf's gradient to 5e-4 of its largest
# entry. bf16 against the fp32 reference at this size: the loss reads 3e-4
# off, the logits 0.07 of their largest, the whole gradient 0.41 of its
# norm. A leaf alone says little here: rounding the weights to bf16 (0.4 %)
# moves the fp32 reference's OWN gradient of a linear layer's leaf by up to
# 56 % (a head's output norm over 16 numbers of a state that a strong decay
# rebuilds at each token: o ~ (q . k) v, normalised, turns over where q . k
# crosses 0), so the bf16 step is held by the whole gradient.
TOL = {None: dict(loss=2e-6, logits=5e-5, grad=5e-4),
       "bfloat16": dict(loss=5e-3, logits=0.15, grad=0.6)}


def _whole(grads, want):
    """|got - want| over |want|, all leaves as one vector."""
    sq = lambda d: sum(float(np.sum(np.square(np.asarray(d[k], np.float64))))
                       for k in want)
    return np.sqrt(sq({k: np.asarray(grads[k]) - np.asarray(want[k])
                       for k in want}) / sq(want))


@pytest.mark.parametrize("recompute", [False, True, 2])
@pytest.mark.parametrize("amp", [None, "bfloat16"])
def test_step_matches_reference(amp, recompute):
    """Loss, the sampled logits and the gradient of EVERY parameter (the
    taps, A_log, dt_bias and the output norm's gain among them)."""
    p0, loss, sample, grads = _stepped(amp, recompute)
    want, g_ref = _reference()
    tol = TOL[amp]
    assert abs(loss - want["loss"]) / want["loss"] <= tol["loss"]
    assert _rel(sample, want["sample"]) <= tol["logits"]
    assert set(grads) == set(g_ref) == set(p0)
    if amp is None:
        for k in g_ref:
            assert _rel(grads[k], g_ref[k]) <= tol["grad"], k
    else:
        assert _whole(grads, g_ref) <= tol["grad"]


def test_forward_matches_reference():
    m = _build()[0]
    m.eval()
    x, _ = _batch()
    got = np.asarray(m(tensor.from_numpy(x)).data)
    p0 = _params(m)
    want = ref.logits(p0, ref.hidden(p0, x, CFG))
    assert _rel(got, want) < 5e-5


def test_recompute_equals_the_ordinary_tape():
    """A rebuilt block gives the values of a kept one bit for bit and its
    gradients to the rounding of a sum taken in another order."""
    plain, again = (_stepped(None, rc) for rc in (False, True))
    for a, b in zip(plain[1:3], again[1:3]):
        assert np.array_equal(a, b)
    assert max(_rel(again[3][k], plain[3][k]) for k in plain[3]) <= 1e-5


def _rule_paths():
    c = observe.get_registry().get("singa_delta_rule_dispatch_total")
    return {p: int(c.value(path=p)) if c is not None else 0
            for p in observe.DELTA_RULE_PATHS}


def test_the_kernels_step_matches_reference_and_rebuilds_as_the_tape():
    """With chunks of 8 the rule runs in its Pallas kernels (interpret mode
    here; the step's custom vjp): the fp32 step's loss, logits and every
    gradient against the reference as the XLA form's are held, and a
    rebuilt block (its kernels' second forward and backward) against the
    ordinary tape as above."""
    before = _rule_paths()
    plain, again = (_stepped(None, rc, 8) for rc in (False, True))
    moved = {p: n - before[p] for p, n in _rule_paths().items()}
    assert moved["interpret"] > 0 and moved["xla"] == 0, moved
    want, g_ref = _reference()
    tol = TOL[None]
    assert abs(plain[1] - want["loss"]) / want["loss"] <= tol["loss"]
    assert _rel(plain[2], want["sample"]) <= tol["logits"]
    for k in g_ref:
        assert _rel(plain[3][k], g_ref[k]) <= tol["grad"], k
    for a, b in zip(plain[1:3], again[1:3]):
        assert np.array_equal(a, b)
    assert max(_rel(again[3][k], plain[3][k]) for k in plain[3]) <= 1e-5


@functools.lru_cache(maxsize=None)
def _graph_run(recompute, chunk=CFG["chunk"]):
    """(what two graph-mode Adam steps hand back, the parameters after the
    first, the step's lowered text)."""
    m, dev = _build("bfloat16", recompute, graph=True,
                    optimizer=opt.Adam(lr=1e-3), chunk=chunk)
    x, y = _batch()
    tx, ty = (tensor.from_numpy(a, device=dev) for a in (x, y))
    outs = [np.asarray(o.data) for o in m(tx, ty)]
    params = {k: tensor.to_numpy(v) for k, v in m.get_params().items()}
    outs += [np.asarray(o.data) for o in m(tx, ty)]
    return outs, params, m.lower_step().as_text(debug_info=True)


@pytest.mark.parametrize("recompute", [False, 2])
def test_graph_step_with_amp_matches_reference(recompute):
    """Through `Model.compile(use_graph=True, amp="bfloat16")` with Adam:
    the first step's loss and logits, the parameters after it against the
    reference's gradient put through Adam's first step, and a second step
    whose loss is lower."""
    outs, after, _ = _graph_run(recompute)
    want, g_ref = _reference()
    tol = TOL["bfloat16"]
    assert abs(outs[0] - want["loss"]) / want["loss"] <= tol["loss"]
    assert _rel(outs[1], want["sample"]) <= tol["logits"]
    assert outs[2] < outs[0]
    p0 = _stepped(None, False)[0]
    check = _load("update_check")
    err = check.Expected(p0, g_ref, 1e-3, 0.0).error_of_step(
        {k: jnp.asarray(v) for k, v in after.items()})
    # a state left unchanged reads 1 (see TOL for what a leaf alone reads)
    assert err["leaves_compared"] == len(p0) and err["whole"] < 0.7, err


def test_scopes_name_the_linear_attention_s_parts():
    text = _graph_run(2)[2]
    names = set(re.findall(r'"jit\(step\)/([^"]*)"', text))
    has = lambda part: any(part in n for n in names)
    for part in ("proj", "conv", "delta", "norm_gate", "out_proj"):
        # a rebuilt block's first forward runs off the tape; a kept one's
        # is traced for its vjp
        assert has(f"TransformerBlock_0/linear_attn/{part}/"), part
        assert has(f"TransformerBlock_2/linear_attn/jvp({part})/"), part
        # the second forward is traced for its vjp, the backward is that
        assert has(f"recompute/TransformerBlock_1/linear_attn/jvp({part})/"), \
            part
        assert has(f"bwd/TransformerBlock_0/linear_attn/transpose(jvp({part}))"
                   ), part
    assert has("TransformerBlock_3/attn/q_norm/") \
        and has("TransformerBlock_3/ln1_post/") \
        and not has("TransformerBlock_3/ln1/") \
        and not has("TransformerBlock_0/attn/")
    assert has("head/") and has("sce/")
    # with chunks of 8 the rule is its kernels' calls (interpret mode here,
    # named as the compiled calls are): under `delta` in every phase, the
    # backward's under its forward's scope after the custom vjp
    names = set(re.findall(r'"jit\(step\)/([^"]*)"', _graph_run(2, 8)[2]))
    has = lambda *parts: any(all(p in n for p in parts) for n in names)
    assert has("TransformerBlock_0/linear_attn/delta/singa_delta_fwd/")
    assert has("TransformerBlock_2/linear_attn/jvp(delta)/singa_delta_fwd/")
    assert has("recompute/TransformerBlock_1/linear_attn/jvp(delta)/"
               "singa_delta_fwd/")
    for blk in (0, 1, 2):
        assert has(f"bwd/TransformerBlock_{blk}/linear_attn/",
                   "linear_attn/jvp(delta)/singa_delta_bwd/"), blk
    assert not any("singa_delta" in n and "/delta/" not in n
                   and "(delta)/" not in n for n in names)


@pytest.mark.parametrize("recompute,rebuilt", [(False, 0), (2, 2), (True, 3)])
def test_plan_gauge(recompute, rebuilt):
    m, dev = _build(recompute=recompute)
    x, y = _batch()
    m(tensor.from_numpy(x, device=dev), tensor.from_numpy(y, device=dev))
    g = observe.get_registry().get("singa_linear_attn_plan")
    assert {k: int(g.value(kind=k)) for k in observe.LINEAR_ATTN_KINDS} == {
        "layers": 3, "heads": 2, "key_dim": 8, "value_dim": 16, "chunk": 4,
        "chunks": 3, "recomputed_blocks": rebuilt}


def test_loss_is_over_the_vocabulary_slice():
    """The head holds the slice's rows alone and the loss is the mean
    cross-entropy over them: a target past the slice is not a class."""
    m = _build()[0]
    assert m.head.W.shape == (64, 96) and m.tok_embed.W.shape == (96, 64)
    p0, loss = _stepped(None, False)[:2]
    x, y = _batch()
    h = ref.hidden(p0, x, CFG)
    z = np.asarray(ref.logits(p0, h), np.float64)
    lse = np.log(np.exp(z - z.max(-1, keepdims=True)).sum(-1)) \
        + z.max(-1)
    ce = lse - np.take_along_axis(z, y[..., None], -1)[..., 0]
    assert loss == pytest.approx(ce.mean(), rel=2e-6)


# ---- the delta rule on its own --------------------------------------------

def _rule_args(S=10, H=3, dk=8, dv=16, beta=(0.0, 2.0), seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q = gated_delta.l2_normalize(mk(2, S, H, dk)) * dk ** -0.5
    k = gated_delta.l2_normalize(mk(2, S, H, dk))
    g = -jnp.asarray(rng.uniform(0, 1.5, (2, S, H)), jnp.float32)
    b = jnp.asarray(rng.uniform(*beta, (2, S, H)), jnp.float32)
    return q, k, mk(2, S, H, dv), g, b


def _token_loop(q, k, v, g, beta):
    with jax.default_matmul_precision("highest"):
        return jnp.stack([ref.delta_rule(*(a[i] for a in (q, k, v, g, beta)))
                          for i in range(q.shape[0])])


@pytest.mark.parametrize("S,chunk", [(10, 4), (12, 4), (10, 64), (130, 64)])
@pytest.mark.parametrize("beta", [(0.0, 2.0), (1.0, 2.0)])
def test_chunked_rule_matches_the_token_recurrence(S, chunk, beta):
    """Values and every input's gradient, where the sequence is and is not
    a multiple of the chunk and where beta > 1 (negative eigenvalues)."""
    args = _rule_args(S=S, beta=beta)
    with jax.default_matmul_precision("highest"):
        got = gated_delta.chunked_delta_rule(*args, chunk=chunk)
        assert _rel(got, _token_loop(*args)) < 2e-5
        w = jnp.asarray(np.random.default_rng(1).standard_normal(got.shape),
                        jnp.float32)
        f = lambda fn: jax.grad(lambda *a: jnp.sum(fn(*a) * w),
                                argnums=tuple(range(5)))(*args)
        for a, b in zip(f(lambda *a: gated_delta.chunked_delta_rule(
                *a, chunk=chunk)), f(_token_loop)):
            assert _rel(a, b) < 5e-5


def test_a_step_with_alpha_one_and_beta_zero_leaves_the_state():
    """Tokens with g = 0 (alpha = 1) and beta = 0 write nothing: each
    output there reads the state the last real token left, q_t S."""
    q, k, v, g, b = _rule_args(S=12)
    g, b = g.at[:, 5:9].set(0.0), b.at[:, 5:9].set(0.0)
    o = gated_delta.chunked_delta_rule(q, k, v, g, b, chunk=4)
    with jax.default_matmul_precision("highest"):
        # the state after token 4, read by the queries of tokens 5-8
        S4 = _token_loop(q.at[:, 5:9].set(0.0), k, v, g, b)
        want = _token_loop(q, k, v, g, b)
    assert _rel(o, want) < 2e-5
    # rebuild o_t = S_4 q_t from S_4 = the outer products of tokens 0-4
    state = np.zeros((2, 3, 16, 8))
    qn, kn, vn, gn, bn = (np.asarray(a, np.float64) for a in (q, k, v, g, b))
    for t in range(5):
        Sk = np.einsum("bhvk,bhk->bhv", state, kn[:, t])
        state = np.exp(gn[:, t])[..., None, None] * (
            state - bn[:, t, :, None, None] * Sk[..., None]
            * kn[:, t, :, None, :]) \
            + bn[:, t, :, None, None] * vn[:, t, ..., None] \
            * kn[:, t, :, None, :]
    for t in range(5, 9):
        np.testing.assert_allclose(
            np.asarray(o[:, t]), np.einsum("bhvk,bhk->bhv", state, qn[:, t]),
            rtol=2e-5, atol=2e-6)
    assert not np.asarray(S4[:, 5:9]).any()


def _layer_args(x_seed=0, E=32):
    rng = np.random.default_rng(x_seed)
    mk = lambda s, *shape: jnp.asarray(rng.standard_normal(shape) * s,
                                       jnp.float32)
    H, dk, dv = 2, 8, 16
    return (mk(1, 2, 12, E), mk(.2, E, H * dk), mk(.2, E, H * dk),
            mk(.2, E, H * dv), mk(.2, E, H), mk(.2, E, H), mk(.2, E, H * dv),
            mk(.2, H * dv, E), mk(.5, H * dk, 4), mk(.5, H * dk, 4),
            mk(.5, H * dv, 4), jnp.log(jnp.asarray([2.0, 8.0])),
            jnp.asarray([-3.0, -1.0]), 1 + mk(.1, dv))


@pytest.mark.parametrize("t", [0, 3, 4, 11])
def test_layer_is_causal(t):
    """Perturbing token t leaves every output before t unchanged, and
    changes t's."""
    args = _layer_args()
    run = lambda x: gated_delta.gated_delta_net(x, *args[1:], heads=2,
                                                chunk=4)
    x = args[0]
    a, b = run(x), run(x.at[:, t].add(1.0))
    assert np.array_equal(np.asarray(a[:, :t]), np.asarray(b[:, :t]))
    assert not np.allclose(np.asarray(a[:, t]), np.asarray(b[:, t]))


def test_layer_matches_the_reference_operator():
    args = _layer_args()
    names = ("W_q", "W_k", "W_v", "W_a", "W_b", "W_g", "W_o", "conv_q",
             "conv_k", "conv_v", "A_log", "dt_bias", "o_norm")
    p = {"linear_attn." + n: a for n, a in zip(names, args[1:])}
    cfg = dict(linear_heads=2, linear_neg_eigval=True, norm_eps=1e-6)
    flags = {"beta_not_doubled": jnp.bool_(False)}
    with jax.default_matmul_precision("highest"):
        got = gated_delta.gated_delta_net(*args, heads=2, chunk=4)
        want = jnp.stack([ref._linear(seq, p, cfg, flags)
                          for seq in args[0]])
        assert _rel(got, want) < 2e-5
        flags = {"beta_not_doubled": jnp.bool_(True)}
        half = gated_delta.gated_delta_net(*args, heads=2, chunk=4,
                                           neg_eigval=False)
        assert _rel(half, jnp.stack([ref._linear(seq, p, cfg, flags)
                                     for seq in args[0]])) < 2e-5
        assert _rel(half, got) > 1e-2


def test_whole_projection_qk_norm_differs_from_the_per_head_one():
    """The norm over the whole q projection (one mean square a token) and
    the norm a head (one a head) are different maps: the same gains give
    other q, and the whole one's gain is the projection's width."""
    dev = device.get_default_device()
    x = tensor.from_numpy(np.random.default_rng(0).standard_normal(
        (2, 8, 32)).astype(np.float32) * np.linspace(0.2, 3, 32), device=dev)
    outs = {}
    for whole in (False, True):
        dev.SetRandSeed(5)
        att = layer.MultiHeadAttention(
            4, causal=True, qk_norm="projection" if whole else "head",
            qk_norm_eps=1e-6)
        outs[whole] = np.asarray(att(x).data)
        width = att.q_norm.gamma.shape[0]
        assert width == (32 if whole else 8)
    assert not np.allclose(outs[False], outs[True], atol=1e-3)
    # the whole one by hand, through the same weights
    dev.SetRandSeed(5)
    att = layer.MultiHeadAttention(4, causal=True, qk_norm="projection",
                                   qk_norm_eps=1e-6)
    att(x)
    p = {"attn." + k: jnp.asarray(tensor.to_numpy(v))
         for k, v in att.get_params().items()}
    want = jnp.stack([ref._attention(seq, p, dict(num_heads=4, norm_eps=1e-6),
                                     8) for seq in jnp.asarray(x.data)])
    assert _rel(outs[True], want) < 1e-4


def test_norms_sit_after_each_branch_only():
    """OLMo 2's block: x + N1(Op(x)), then h + N2(FFN(h)); no norm before
    a branch, and the sandwich block still has both."""
    olmo = layer.TransformerBlock(
        layer.Attention(4), layer.FeedForward("swiglu", 48, bias=False),
        layer.Norms("rms", pre=False, post=True))
    x = tensor.from_numpy(np.random.default_rng(0).standard_normal(
        (1, 8, 32)).astype(np.float32) * 3)
    y = np.asarray(olmo(x).data)
    assert set(olmo.sublayers()) == {"attn", "ln1_post", "ln2_post",
                                     "fc_gate", "fc1", "fc2"}
    p = {k: np.asarray(tensor.to_numpy(v))
         for k, v in olmo.get_params().items()}
    rms = lambda a, g: a / np.sqrt((a * a).mean(-1, keepdims=True) + 1e-6) * g
    xs = np.asarray(x.data)
    a = np.asarray(olmo.attn(x).data)
    h = xs + rms(a, p["ln1_post.gamma"])
    m = (jax.nn.silu(h @ p["fc_gate.W"]) * (h @ p["fc1.W"])) @ p["fc2.W"]
    np.testing.assert_allclose(y, h + rms(np.asarray(m), p["ln2_post.gamma"]),
                               rtol=1e-4, atol=1e-4)
    sandwich = layer.TransformerBlock(layer.Attention(4),
                                      norms=layer.Norms("rms", post=True))
    sandwich(x)
    assert {"ln1", "ln2", "ln1_post", "ln2_post"} <= set(sandwich.sublayers())

