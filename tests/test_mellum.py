"""The sparse model with two kinds of layer (models/mellum.py) against its
plain reference (benchmark/reference_mellum.py), and what it is built from:
the dropless expert layer against a dense loop over experts, the shares of
the experts adding up to the uncut layer, YaRN's rotary tables, the gauges.

Small size on the CPU: three layers (sliding, sliding, full), hidden 64,
4 query and 2 KV heads of 32 (q is 128 wide from a 64-wide stream), window
128, 8 experts of width 48 routed top-4 of which this device holds 4 from
the third on, vocabulary 96, 2 x 256 tokens (blocks of 128: the window is
one block, the flash kernels run in interpret mode).
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
from singa_tpu.models import mellum
from singa_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "benchmark_" + name, os.path.join(ROOT, "benchmark", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference_mellum")

YARN = {"factor": 16.0, "original_max_position_embeddings": 64,
        "beta_fast": 32.0, "beta_slow": 1.0,
        "attention_factor": 1.2772588722239782}
CFG = dict(vocab_size=96, dim=64, num_heads=4, num_kv_heads=2, head_dim=32,
           layer_types=["sliding_attention", "sliding_attention",
                        "full_attention"], window=128, ffn_dim=48,
           num_experts=8, experts_per_token=4, experts_held=4,
           expert_offset=2, rope_theta=5e5, rope_scaling=YARN,
           norm_eps=1e-6, sample=16)
B, S = 2, 256


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


def _build(amp=None, recompute=False, graph=False, optimizer=None, **over):
    dev = device.get_default_device()
    dev.SetRandSeed(3)
    m = models.create_model("mellum", **dict(CFG, recompute=recompute,
                                             **over))
    m.set_optimizer(optimizer or _Keep())
    x, _ = _batch()
    m.compile([tensor.from_numpy(x[:1, :128], device=dev)], is_train=True,
              use_graph=graph, amp=amp)
    return m, dev


def _params(m):
    return {k: jnp.asarray(tensor.to_numpy(v))
            for k, v in m.get_params().items()}


def _rel(a, b):
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b)))
                 / (np.max(np.abs(np.asarray(b))) + 1e-30))


@functools.lru_cache(maxsize=None)
def _stepped(amp, recompute):
    """(initial parameters, loss, sample, rows, {name: gradient}) of one
    eager training step on batch 0."""
    m, dev = _build(amp, recompute)
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
    return ref.loss_parts(p0, x, y, CFG, rows=at, token_block=128), \
        ref.grad(p0, x, y, CFG)


# bf16 against the fp32 reference at this size: the loss reads up to 2e-3
# off, a gradient up to 6 % of its largest entry; a (token, choice) pair
# whose 4th and 5th probabilities tie within bf16's rounding of the stream
# moves from one expert to another. fp32: rounding order only.
TOL = {None: dict(loss=2e-6, logits=5e-5, grad=5e-4, rows=0),
       "bfloat16": dict(loss=5e-3, logits=6e-2, grad=1.5e-1, rows=8)}


@pytest.mark.parametrize("recompute", [False, True, 2])
@pytest.mark.parametrize("amp", [None, "bfloat16"])
def test_step_matches_reference(amp, recompute):
    """Loss, the sampled logits, the rows routed to each held expert of
    each layer, and the gradient of EVERY parameter (router included)."""
    p0, loss, sample, rows, grads = _stepped(amp, recompute)
    want, g_ref = _reference()
    tol = TOL[amp]
    assert abs(loss - want["loss"]) / want["loss"] <= tol["loss"]
    assert _rel(sample, want["sample"]) <= tol["logits"]
    assert rows.shape == (3, 4) and want["rows"].shape == (3, 4)
    assert np.abs(rows - want["rows"]).max() <= tol["rows"]
    assert set(grads) == set(g_ref) == set(p0)
    worst = {k: _rel(grads[k], g_ref[k]) for k in grads}
    assert max(worst.values()) <= tol["grad"], worst


@pytest.mark.parametrize("amp", [None, "bfloat16"])
def test_forward_matches_reference(amp):
    m, dev = _build(amp)
    x, _ = _batch()
    m.eval()
    z = m(tensor.from_numpy(x, device=dev))
    h, _ = ref.hidden(_params(m), x, CFG)
    assert z.shape == (B, S, CFG["vocab_size"])
    want = ref.logits(_params(m), h)
    if amp is None:
        assert _rel(z.data, want) <= TOL[amp]["logits"]
    # over every position: RMS over the reference's spread, the driver's
    # measure. (Under bf16 a few tokens read 0.2 to 0.5 off on their own:
    # one of their pairs went to another expert, see TOL.)
    rms = float(jnp.sqrt(jnp.mean((z.data - want) ** 2)) / jnp.std(want))
    assert rms <= (1e-5 if amp is None else 8e-2), rms


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_wrong_models_read_far_from_the_right_one(wrong):
    """Each deliberately wrong reference moves the logits by far more than
    bf16 does: what the cell's limits rest on."""
    x, _ = _batch()
    p0 = _stepped(None, False)[0]
    z = ref.logits(p0, ref.hidden(p0, x, CFG)[0])
    zw = ref.logits(p0, ref.hidden(p0, x, CFG, wrong, expert=1)[0])
    err = float(jnp.sqrt(jnp.mean((zw - z) ** 2)) / jnp.std(z))
    assert err > 0.15, err


def test_reference_grads_by_layer_equal_its_whole_gradient():
    x, y = _batch()
    p0 = _stepped(None, False)[0]
    whole, parts = _reference()[1], ref.grads(p0, x, y, CFG, token_block=128)
    assert max(_rel(parts[k], whole[k]) for k in whole) <= 1e-5


@functools.lru_cache(maxsize=None)
def _graph_run(amp, recompute):
    """(what two graph-mode Adam steps hand back, the parameters after the
    first, the step's lowered text)."""
    m, dev = _build(amp, recompute, graph=True, optimizer=opt.Adam(lr=1e-3))
    x, y = _batch()
    tx, ty = (tensor.from_numpy(a, device=dev) for a in (x, y))
    outs = [np.asarray(o.data) for o in m(tx, ty)]
    params = {k: tensor.to_numpy(v) for k, v in m.get_params().items()}
    outs += [np.asarray(o.data) for o in m(tx, ty)]
    return outs, params, m.lower_step().as_text(debug_info=True)


@pytest.mark.parametrize("recompute", [False, True])
def test_graph_step_with_amp_matches_reference(recompute):
    """Through `Model.compile(use_graph=True, amp="bfloat16")` with Adam:
    the first step's loss, logits and rows, the parameters after it against
    the reference's gradient put through Adam's first step, and a second
    step whose loss is lower."""
    outs, after, _ = _graph_run("bfloat16", recompute)
    want, g_ref = _reference()
    tol = TOL["bfloat16"]
    assert abs(outs[0] - want["loss"]) / want["loss"] <= tol["loss"]
    assert _rel(outs[1], want["sample"]) <= tol["logits"]
    assert np.abs(outs[2] - want["rows"]).max() <= tol["rows"]
    assert outs[3] < outs[0]
    p0 = _stepped(None, False)[0]
    check = _load("update_check")
    err = check.Expected(p0, g_ref, 1e-3, 0.0).error_of_step(
        {k: jnp.asarray(v) for k, v in after.items()})
    # Adam's first step is lr x sign(g) nearly: bf16 reads a tenth or two,
    # a state left unchanged 1
    assert err["leaves_compared"] == len(p0) and err["worst_leaf"] < 0.6, err


def test_recompute_equals_the_ordinary_tape():
    plain, again = (_stepped(None, rc) for rc in (False, True))
    for a, b in zip(plain[1:4], again[1:4]):
        assert np.array_equal(a, b)
    assert max(_rel(again[4][k], plain[4][k]) for k in plain[4]) <= 1e-5


def test_scopes_name_the_expert_layer_s_parts_and_the_windowed_kernels():
    text = _graph_run("bfloat16", True)[2]
    names = set(re.findall(r'"jit\(step\)/([^"]*)"', text))
    has = lambda part: any(part in n for n in names)
    for part in ("router", "dispatch", "experts", "combine"):
        assert has(f"TransformerBlock_0/moe/{part}/"), part
        # the second forward is traced for its vjp, the backward is that
        assert has(f"recompute/TransformerBlock_1/moe/jvp({part})/"), part
        assert has(f"bwd/TransformerBlock_2/moe/transpose(jvp({part}))"), \
            part
    assert has("TransformerBlock_0/attn/") and has("head/") and has("sce/")
    # sliding layers call the windowed kernels, the full layer the causal
    calls = re.findall(r"name = \"(singa_flash_\w+)\"", text) \
        or re.findall(r"(singa_flash_\w+)", text)
    assert any(c.endswith("_win") for c in calls)
    assert any(not c.endswith("_win") for c in calls)


def test_moe_plan_and_rows_gauges():
    m, dev = _build("bfloat16", True)
    x, y = _batch()
    _, _, rows = m(tensor.from_numpy(x, device=dev),
                   tensor.from_numpy(y, device=dev))
    g = observe.get_registry().get("singa_moe_plan")
    plan = {k: int(g.value(kind=k)) for k in (
        "experts", "held", "k", "rows_worst", "rung_least",
        "recomputed_blocks")}
    assert plan == {"experts": 8, "held": 4, "k": 4,
                    "rows_worst": B * S * 4, "rung_least": B * S * 4 // 8,
                    "recomputed_blocks": 3}
    mellum.record_rows(rows.data)
    g = observe.get_registry().get("singa_moe_rows")
    r = np.asarray(rows.data)
    for l in range(3):
        assert g.value(layer=str(l), kind="routed") == r[l].sum()
        assert g.value(layer=str(l), kind="held_max") == r[l].max()
        assert g.value(layer=str(l), kind="held_min") == r[l].min()
        # the buffer length the layer's row passes worked on: the least
        # rung of the ladder that holds the rows routed
        assert g.value(layer=str(l), kind="buffer") == min(
            b for b in moe.rungs(B * S * 4) if b >= r[l].sum())
    # every pair whose expert is held is counted, none twice
    assert 0 < r.sum(1).max() <= B * S * 4


# ---- the rotary tables ----------------------------------------------------------

PUBLISHED = {"factor": 16.0, "original_max_position_embeddings": 8192,
             "beta_fast": 32.0, "beta_slow": 1.0,
             "attention_factor": 1.2772588722239782}


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_rope_tables_match_reference(kind):
    """The program's tables (plain; YaRN's blended frequencies with the
    attention factor on cos and sin) against the reference's, at the
    published head width, theta and scaling, over 8,192 positions."""
    cfg = dict(head_dim=128, rope_theta=5e5, rope_scaling=PUBLISHED)
    scaling = PUBLISHED if kind == "full_attention" else None
    f, c = ref.frequencies(cfg, kind)
    if scaling:
        got = autograd.yarn_frequencies(128, 5e5, 16.0, 8192, 32.0, 1.0)
        np.testing.assert_allclose(np.asarray(got), f, rtol=2e-6)
        assert c == PUBLISHED["attention_factor"]
        # the blend: the fastest pairs keep theirs, the slowest take 1/16
        plain = 5e5 ** (-np.arange(64) / 64)
        assert np.allclose(f[:10], plain[:10]) \
            and np.allclose(f[-10:], plain[-10:] / 16)
    cos, sin = autograd.rope_tables(jnp.arange(8192), 128, 5e5, scaling)
    rc, rs = ref.rope_tables(cfg, kind, 8192)
    # fp32 angles of up to 8,191 radians carry 5e-4 of rounding
    assert float(jnp.max(jnp.abs(cos[:, :64] - rc))) < 2e-3 * c
    assert float(jnp.max(jnp.abs(sin[:, :64] - rs))) < 2e-3 * c
    assert jnp.array_equal(cos[:, :64], cos[:, 64:])


# ---- the expert layer on its own ------------------------------------------------

def _dense(x, Wr, Wg, Wu, Wd, k, offset=0):
    """Every expert on every token, the gate zero where it was not chosen."""
    p = jax.nn.softmax(x @ Wr, -1)
    tv, ti = jax.lax.top_k(p, k)
    w = tv / tv.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(Wg.shape[0]):
        we = jnp.sum(jnp.where(ti == e + offset, w, 0.0), -1)
        y = y + we[:, None] * (
            (jax.nn.silu(x @ Wg[e]) * (x @ Wu[e])) @ Wd[e])
    return y


def _experts(T=64, D=32, F=48, E=8, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda s, *shape: jnp.asarray(rng.standard_normal(shape) * s,
                                       jnp.float32)
    return (mk(1, T, D), mk(1, D, E), mk(.2, E, D, F), mk(.2, E, D, F),
            mk(.2, E, F, D))


RUNGS = ("R_8", "R_4", "R_2", "R")


def _wider_by_an_unread_input(Wg, Wu, Wd, sl):
    """The weights of experts `sl` for an input one coordinate wider: they
    do not read it (the router may)."""
    held = Wg[sl].shape[0]
    pad = lambda W: jnp.concatenate([W[sl], jnp.zeros((held, 1, 48))], 1)
    return pad(Wg), pad(Wu), jnp.concatenate(
        [Wd[sl], jnp.zeros((held, 48, 1))], 2)


def _routed(rung, held, offset, k=4):
    """(`_experts()` with one more input coordinate that only the held
    experts' router logits read, R): the coordinate's weight is the first
    of a scan at which the rows routed to the held experts need rung
    `rung` of the buffer's ladder (0: R/8 .. 3: R) and no lower one."""
    x, Wr, Wg, Wu, Wd = _experts()
    T, E = x.shape[0], Wr.shape[1]
    R = T * min(k, held)
    least, most = ((0,) + moe.rungs(R))[rung:rung + 2]
    here = (np.arange(E) >= offset) & (np.arange(E) < offset + held)
    logits = np.asarray(x @ Wr)
    for bias in np.arange(-30.0, 30.0, 0.1):
        chosen = np.argsort(-(logits + bias * here), 1)[:, :k]
        if least < here[chosen].sum() <= most:
            break
    else:
        raise AssertionError((rung, held, offset))
    return (jnp.concatenate([x, jnp.ones((T, 1))], 1),
            jnp.concatenate([Wr, jnp.asarray(bias * here, Wr.dtype)[None]]),
            *_wider_by_an_unread_input(
                Wg, Wu, Wd, slice(offset, offset + held))), R


@pytest.mark.parametrize("n,R,want", [
    (0, 64, 8), (1, 64, 8), (8, 64, 8), (9, 64, 16), (33, 64, 64),
    (64, 64, 64),
    # an R that 8 does not divide: the rungs that are whole
    (3, 12, 3), (4, 12, 6), (7, 12, 12), (5, 7, 7)])
def test_rung_of(n, R, want):
    """The least of R/8, R/4, R/2 and R that holds n rows: one rule for a
    number on the host (`record_rows` hands it a float32) and for a count
    traced on the device."""
    assert int(moe.rung_of(n, R)) == want
    assert int(moe.rung_of(np.float32(n), R)) == want
    assert int(jax.jit(lambda n: moe.rung_of(n, R))(jnp.int32(n))) == want
    assert moe.rungs(R)[-1] == R and want in moe.rungs(R)


@pytest.mark.parametrize("held,offset", [(2, 0), (3, 4)])
@pytest.mark.parametrize("rung", range(4), ids=RUNGS)
def test_row_passes_equal_the_whole_buffer(rung, held, offset, monkeypatch):
    """In every rung of the ladder the layer's value, rows and five
    gradients are BIT-equal to the same call made on the whole buffer
    (`rung_of` patched to the top rung: the program before the ladder)."""
    args, R = _routed(rung, held, offset)

    def step():     # a new function a call: `jax.jit` traces it again
        def run(*a):
            layer = lambda *b: moe.dropless_moe(*b, 4, offset)
            return layer(*a), jax.grad(
                lambda *b: jnp.sum(jnp.sin(layer(*b)[0])),
                (0, 1, 2, 3, 4))(*a)
        return jax.jit(run)

    (y, rows), grads = step()(*args)
    assert int(moe.rung_of(rows.sum(), R)) == moe.rungs(R)[rung]
    assert 0 < int(rows.sum())
    monkeypatch.setattr(moe, "rung_of", lambda n, R: R)
    (y_whole, rows_whole), grads_whole = step()(*args)
    for a, b in zip((y, rows, *grads), (y_whole, rows_whole, *grads_whole)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert all(bool(jnp.any(g != 0)) for g in grads)


@pytest.mark.parametrize("held,offset", [(8, 0), (2, 0), (2, 6), (3, 4)])
def test_expert_layer_matches_a_dense_loop(held, offset):
    """Values, rows and all five gradients, for the whole layer and for a
    share of its experts."""
    x, Wr, Wg, Wu, Wd = _experts()
    sl = slice(offset, offset + held)
    args = (x, Wr, Wg[sl], Wu[sl], Wd[sl])
    with jax.default_matmul_precision("highest"):
        y, rows = moe.dropless_moe(*args, 4, offset)
        assert _rel(y, _dense(*args, 4, offset)) < 1e-5
        chosen = jax.lax.top_k(jax.nn.softmax(x @ Wr, -1), 4)[1]
        assert [int(r) for r in rows] == [
            int(jnp.sum(chosen == e)) for e in range(offset, offset + held)]
        grad = lambda fn: jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), (0, 1, 2, 3, 4))(*args)
        got = grad(lambda *a: moe.dropless_moe(*a, 4, offset)[0])
        want = grad(lambda *a: _dense(*a, 4, offset))
    assert max(_rel(a, b) for a, b in zip(got, want)) < 2e-5


@pytest.mark.parametrize("held,offset", [(8, 0), (1, 5)])
@pytest.mark.parametrize("k", [1, 4])
def test_every_token_sent_to_one_expert_and_none_dropped(k, held, offset):
    """A router that sends every token to expert 5 first: its group is all
    T rows (a capacity would have dropped most), values and gradients as
    the dense loop's. With every pair at a held expert (all 8 held, or
    expert 5 alone) the buffer is full: the top rung of its ladder."""
    x, Wr, Wg, Wu, Wd = _experts()
    Wr = Wr.at[:, 5].set(0.0)
    x = x.at[:, 0].set(0.0)
    Wr = Wr.at[0, :].set(0.0)
    # one more coordinate that only expert 5's logit reads
    x = jnp.concatenate([x, jnp.full((64, 1), 50.0)], 1)
    Wr = jnp.concatenate([Wr, jnp.zeros((1, 8)).at[0, 5].set(1.0)])
    args = (x, Wr, *_wider_by_an_unread_input(
        Wg, Wu, Wd, slice(offset, offset + held)))
    R = 64 * min(k, held)
    with jax.default_matmul_precision("highest"):
        y, rows = moe.dropless_moe(*args, k, offset)
        assert int(rows[5 - offset]) == 64 and int(rows.sum()) == R
        assert int(moe.rung_of(rows.sum(), R)) == R
        assert _rel(y, _dense(*args, k, offset)) < 1e-5
        grad = lambda fn: jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), (0, 2, 3, 4))(*args)
        got = grad(lambda *a: moe.dropless_moe(*a, k, offset)[0])
        want = grad(lambda *a: _dense(*a, k, offset))
    assert max(_rel(a, b) for a, b in zip(got, want)) < 2e-5


def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of 2 of 8 experts, summed, equal the whole layer (what
    the four chips of an expert-parallel group would exchange and add), in
    values and in the gradient of the input and the router."""
    x, Wr, Wg, Wu, Wd = _experts()
    share = lambda x, Wr, o: moe.dropless_moe(
        x, Wr, Wg[o:o + 2], Wu[o:o + 2], Wd[o:o + 2], 4, o)
    with jax.default_matmul_precision("highest"):
        whole, rows = moe.dropless_moe(x, Wr, Wg, Wu, Wd, 4)
        parts = [share(x, Wr, o) for o in range(0, 8, 2)]
        assert _rel(sum(p[0] for p in parts), whole) < 1e-5
        assert np.array_equal(np.concatenate([p[1] for p in parts]), rows)
        assert int(rows.sum()) == 64 * 4          # every pair, once
        g = lambda f: jax.grad(lambda x, Wr: jnp.sum(jnp.sin(f(x, Wr))),
                               (0, 1))(x, Wr)
        got = g(lambda x, Wr: sum(share(x, Wr, o)[0]
                                  for o in range(0, 8, 2)))
        want = g(lambda x, Wr: moe.dropless_moe(x, Wr, Wg, Wu, Wd, 4)[0])
    assert max(_rel(a, b) for a, b in zip(got, want)) < 2e-5


def test_the_model_s_shares_add_up_to_the_uncut_model_s_layer():
    """`layer.DroplessMoE` told which experts it holds: four layers of 2 of
    8 on the same weights sum to the layer that holds all 8."""
    dev = device.get_default_device()
    dev.SetRandSeed(7)
    x = tensor.from_numpy(np.random.default_rng(2).standard_normal(
        (2, 32, 24)).astype(np.float32), device=dev)
    whole = layer.DroplessMoE(8, hidden=16, k=4)
    y = whole(x)
    total = 0
    for o in range(0, 8, 2):
        part = layer.DroplessMoE(8, hidden=16, k=4, held=2, offset=o)
        part(x)             # the first call makes its weights
        part.set_params({"Wr": whole.Wr, **{
            n: np.asarray(getattr(whole, n).data)[o:o + 2]
            for n in ("Wg", "Wu", "Wd")}})
        total = total + part(x).data
        assert np.array_equal(part.rows.data, whole.rows.data[o:o + 2])
    assert _rel(total, y.data) < 1e-5


def test_grouped_matmul_takes_rows_past_the_groups_as_zero():
    rng = np.random.default_rng(0)
    lhs = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((3, 8, 5)), jnp.float32)
    out = moe.grouped_matmul(lhs, rhs, jnp.asarray([4, 0, 7], jnp.int32))
    np.testing.assert_allclose(out[:4], lhs[:4] @ rhs[0], rtol=1e-5)
    np.testing.assert_allclose(out[4:11], lhs[4:11] @ rhs[2], rtol=1e-5)
    assert not np.asarray(out[11:]).any()


@pytest.mark.parametrize("shape,want", [
    ((65536, 2304, 896), (512, 768, 896)),
    ((65536, 896, 2304), (512, 896, 1152)),
    ((256, 64, 48), None)])
def test_gmm_tiling(shape, want):
    assert moe._gmm_tiling(*shape) == want


def test_onnx_export_refuses_both_expert_layers():
    from singa_tpu.sonnx import frontend
    table = next(v for v in vars(frontend).values()
                 if isinstance(v, dict) and "_MoEOp" in v)
    assert "_DroplessMoEOp" in table


@pytest.mark.parametrize("rung", range(4), ids=RUNGS)
def test_rows_past_the_groups_never_meet_a_number(rung, monkeypatch):
    """The TPU's grouped kernel leaves the buffer's rows past the last
    group uninitialised, in its output and in its input's gradient. With
    NaNs planted there, values and every gradient are still the dense
    loop's: each read of a row selects on whether the pair has one. In
    every rung of the buffer's ladder: the row passes work on the rung's
    rows, those between the last group and the rung's end among them."""
    plain = moe.grouped_matmul

    @jax.custom_vjp
    def poisoned(lhs, rhs, sizes):
        return poison(plain(lhs, rhs, sizes), sizes)

    def poison(a, sizes):
        past = jnp.arange(a.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.nan, a)

    def fwd(lhs, rhs, sizes):
        out, vjp = jax.vjp(lambda l, r: plain(l, r, sizes), lhs, rhs)
        return poison(out, sizes), (vjp, sizes)

    def bwd(res, g):
        vjp, sizes = res
        # the kernel's weight gradient selects its rows by group too
        past = jnp.arange(g.shape[0]) >= jnp.sum(sizes)
        dl, dr = vjp(jnp.where(past[:, None], 0, g))
        return poison(dl, sizes), dr, None

    poisoned.defvjp(fwd, bwd)
    monkeypatch.setattr(moe, "grouped_matmul", poisoned)
    args, R = _routed(rung, 3, 2)
    with jax.default_matmul_precision("highest"):
        y, rows = moe.dropless_moe(*args, 4, 2)
        assert int(rows.sum()) < R               # rows are left over
        assert int(moe.rung_of(rows.sum(), R)) == moe.rungs(R)[rung]
        assert _rel(y, _dense(*args, 4, 2)) < 1e-5
        grad = lambda fn: jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), (0, 1, 2, 3, 4))(*args)
        got = grad(lambda *a: moe.dropless_moe(*a, 4, 2)[0])
        want = grad(lambda *a: _dense(*a, 4, 2))
    assert all(bool(jnp.isfinite(g).all()) for g in got)
    assert max(_rel(a, b) for a, b in zip(got, want)) < 2e-5


@pytest.mark.parametrize("graph", [False, True])
def test_weights_set_after_compile_are_the_ones_the_step_trains(graph):
    """What the cell's driver does to start from an embedding of another
    spread (the model takes no argument for it): `set_params` on the
    compiled model before its first step. The step then reads the
    reference's loss, logits and rows on THOSE weights."""
    m, dev = _build(graph=graph, optimizer=opt.SGD(lr=0.0))
    W = tensor.to_numpy(m.get_params()["tok_embed.W"])
    m.set_params({"tok_embed.W": W * (4.0 / W.std())})
    p0 = _params(m)
    assert abs(float(p0["tok_embed.W"].std()) - 4.0) < 1e-3
    x, y = _batch()
    loss, sample, rows = (np.asarray(o.data) for o in m(
        tensor.from_numpy(x, device=dev), tensor.from_numpy(y, device=dev)))
    at = np.linspace(0, B * S - 1, CFG["sample"]).astype(np.int32)
    want = ref.loss_parts(p0, x, y, CFG, rows=at, token_block=128)
    tol = TOL[None]
    assert abs(loss - want["loss"]) / want["loss"] <= tol["loss"]
    assert _rel(sample, want["sample"]) <= tol["logits"]
    assert np.abs(rows - want["rows"]).max() <= tol["rows"]
    # and they are not the weights the model was compiled on
    assert abs(want["loss"] - _reference()[0]["loss"]) > 1e-3
