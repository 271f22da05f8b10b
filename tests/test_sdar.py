"""The block-diffusion model (models/sdar.py) against its plain reference
(benchmark/reference_sdar.py), and what it is built from: the `_bd` flash
kernels against `attention_reference` under the dense mask, their tile
schedule, the weighted cross-entropy, the noising draw, the mask as one
argument's doing, the gauges, and the eight shares of the experts adding up
to the uncut reference's layer on the doubled input.

Small size on the CPU: two layers, hidden 64, 4 query and 2 KV heads of 32
with norms on q and k, 16 experts of width 32 routed top-4 of which this
device holds 4 from the fifth on, vocabulary 96 (its last row is [MASK]),
2 x 128 tokens in blocks of 4, so a doubled sequence of 256 (tiles of 128:
the flash kernels run in interpret mode).
"""

import functools
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu import autograd, data, device, layer, models, observe, opt, \
    tensor
from singa_tpu.models import sdar
from singa_tpu.ops import attention as att
from singa_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "benchmark_" + name, os.path.join(ROOT, "benchmark", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference_sdar")

CFG = dict(vocab_size=96, dim=64, num_heads=4, num_kv_heads=2, head_dim=32,
           num_layers=2, ffn_dim=32, num_experts=16, experts_per_token=4,
           experts_held=4, expert_offset=4, rope_theta=1e6, norm_eps=1e-6,
           block_length=4, sample=16)
B, S = 2, 128


class _Keep(opt.SGD):
    """An optimizer that changes nothing and keeps every gradient."""

    def __init__(self):
        super().__init__(lr=0.0)
        self.grads = {}

    def apply(self, param, grad):
        self.grads[id(param)] = grad.data


def _batch(seed=0):
    """(ids, masked, weight): ids never the [MASK] row."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, CFG["vocab_size"] - 1, (B, S)).astype(np.int32)
    masked, weight, _ = data.block_diffusion_noise(
        rng, (B, S), CFG["block_length"])
    return ids, masked, weight


def _build(amp=None, recompute=False, graph=False, optimizer=None, **over):
    dev = device.get_default_device()
    dev.SetRandSeed(3)
    m = models.create_model("sdar", **dict(CFG, recompute=recompute, **over))
    m.set_optimizer(optimizer or _Keep())
    m.compile([tensor.from_numpy(np.zeros((1, 2 * S), np.int32), device=dev)],
              is_train=True, use_graph=graph, amp=amp)
    # gains away from 1, so that a gain left out or misplaced shows
    rng = np.random.default_rng(5)
    m.set_params({k: (1 + 0.2 * rng.standard_normal(v.shape)).astype(
        np.float32) for k, v in m.get_params().items()
        if k.endswith("gamma")})
    return m, dev


def _params(m):
    return {k: jnp.asarray(tensor.to_numpy(v))
            for k, v in m.get_params().items()}


def _rel(a, b):
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b)))
                 / (np.max(np.abs(np.asarray(b))) + 1e-30))


def _tensors(dev, arrays):
    return [tensor.from_numpy(a, device=dev) for a in arrays]


@functools.lru_cache(maxsize=None)
def _stepped(amp, recompute):
    """(initial parameters, loss, sample, rows, {name: gradient}) of one
    eager training step on batch 0."""
    m, dev = _build(amp, recompute)
    p0 = _params(m)
    out = m(*_tensors(dev, _batch()))
    names = {id(p): k for k, p in m.get_params().items()}
    grads = {names[i]: np.asarray(g) for i, g in m.optimizer.grads.items()}
    return (p0, *(np.asarray(o.data) for o in out), grads)


@functools.lru_cache(maxsize=None)
def _reference():
    x, masked, w = _batch()
    p0 = _stepped(None, False)[0]
    at = sdar.sample_positions(B, S, CFG["sample"])
    return ref.loss_parts(p0, x, masked, w, CFG, rows=at, token_block=64), \
        ref.grad(p0, x, masked, w, CFG)


# bf16 against the fp32 reference at this size (as tests/test_mellum.py's)
TOL = {None: dict(loss=3e-6, logits=5e-5, grad=5e-4, rows=0),
       "bfloat16": dict(loss=5e-3, logits=6e-2, grad=1.5e-1, rows=8)}


@pytest.mark.parametrize("recompute", [False, True, 1])
@pytest.mark.parametrize("amp", [None, "bfloat16"])
def test_step_matches_reference(amp, recompute):
    """Loss, the sampled logits of the noised half, the rows routed to each
    held expert of each layer, and the gradient of EVERY parameter (the
    routers and the gains on q and k among them)."""
    p0, loss, sample, rows, grads = _stepped(amp, recompute)
    want, g_ref = _reference()
    tol = TOL[amp]
    assert abs(loss - want["loss"]) / want["loss"] <= tol["loss"]
    assert _rel(sample, want["sample"]) <= tol["logits"]
    assert rows.shape == (2, 4) and want["rows"].shape == (2, 4)
    assert np.abs(rows - want["rows"]).max() <= tol["rows"]
    assert set(grads) == set(g_ref) == set(p0)
    worst = {k: _rel(grads[k], g_ref[k]) for k in grads}
    assert max(worst.values()) <= tol["grad"], worst


def test_forward_gives_the_noised_half_s_logits():
    m, dev = _build()
    x, masked, _ = _batch()
    m.eval()
    z = m(tensor.from_numpy(np.asarray(ref.doubled(x, masked, CFG)),
                            device=dev))
    assert z.shape == (B, S, CFG["vocab_size"])
    want = ref.logits(_params(m), ref.hidden(_params(m), x, masked, CFG)[0])
    assert _rel(z.data, want) <= TOL[None]["logits"]


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_wrong_models_differ_from_the_right_one(wrong):
    """Each deliberately wrong reference moves the logits (or, the weight
    left out, the loss) by far more than bf16 does: what the cell's limits
    rest on."""
    x, masked, w = _batch()
    p0 = _stepped(None, False)[0]
    if wrong == "weight_off":
        right, off = (ref.loss_parts(p0, x, masked, w, CFG, wrong=k)["loss"]
                      for k in (None, wrong))
        assert abs(off - right) / right > 0.2
        return
    z = ref.logits(p0, ref.hidden(p0, x, masked, CFG)[0])
    zw = ref.logits(p0, ref.hidden(p0, x, masked, CFG, wrong, expert=1)[0])
    err = float(jnp.sqrt(jnp.mean((zw - z) ** 2)) / jnp.std(z))
    assert err > 0.08, err


def test_reference_grads_by_layer_equal_its_whole_gradient():
    x, masked, w = _batch()
    p0 = _stepped(None, False)[0]
    whole = _reference()[1]
    parts = ref.grads(p0, x, masked, w, CFG, token_block=64)
    assert max(_rel(parts[k], whole[k]) for k in whole) <= 1e-5


def test_reference_mask_is_the_three_rules():
    """Counted by hand over a doubled sequence of 2 x 12 in blocks of 4."""
    half, b = 12, 4
    i, j = np.arange(2 * half)[:, None], np.arange(2 * half)[None, :]
    m = np.asarray(ref.visible(i, j, half, b))
    assert not m[half:, :half].any()                   # clean sees no noised
    for q in range(half):
        own = [k for k in range(half) if k // b == q // b]
        assert list(np.nonzero(m[q, :half])[0]) == own
        assert list(np.nonzero(m[q, half:])[0]) == list(range(q // b * b))
        assert list(np.nonzero(m[half + q, half:])[0]) == \
            list(range((q // b + 1) * b))
    assert m.sum() == half * half + half * b           # S^2 + S b
    assert np.array_equal(m, np.asarray(att.block_diffusion_visible(
        2 * half, b)))


@functools.lru_cache(maxsize=None)
def _graph_run(amp, recompute):
    """(what two graph-mode Adam steps hand back, the parameters after the
    first, the step's lowered text)."""
    m, dev = _build(amp, recompute, graph=True, optimizer=opt.Adam(lr=1e-3))
    batch = _tensors(dev, _batch())
    outs = [np.asarray(o.data) for o in m(*batch)]
    params = {k: tensor.to_numpy(v) for k, v in m.get_params().items()}
    outs += [np.asarray(o.data) for o in m(*batch)]
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
    assert err["leaves_compared"] == len(p0) and err["worst_leaf"] < 0.6, err


def test_recompute_equals_the_ordinary_tape():
    plain, again = (_stepped(None, rc) for rc in (False, True))
    for a, b in zip(plain[1:4], again[1:4]):
        assert np.array_equal(a, b)
    assert max(_rel(again[4][k], plain[4][k]) for k in plain[4]) <= 1e-5


def test_scopes_name_the_noising_and_the_block_diffusion_kernels():
    text = _graph_run("bfloat16", True)[2]
    names = set(re.findall(r'"jit\(step\)/([^"]*)"', text))
    has = lambda part: any(part in n for n in names)
    assert has("noise/")
    for part in ("router", "dispatch", "experts", "combine"):
        assert has(f"TransformerBlock_0/moe/{part}/"), part
        assert has(f"recompute/TransformerBlock_1/moe/jvp({part})/"), part
    assert has("TransformerBlock_0/attn/q_norm/") \
        and has("TransformerBlock_0/attn/k_norm/")
    assert has("head/") and has("sce/")
    calls = set(re.findall(r"(singa_flash_\w+)", text))
    assert calls and all(c.endswith(att.BLOCKDIFF_SUFFIX) for c in calls), \
        calls
    assert "singa_flash_fwd_bd" in calls


def test_plan_gauges():
    m, dev = _build("bfloat16", True)
    _, _, rows = m(*_tensors(dev, _batch()))
    reg = observe.get_registry()
    g = reg.get("singa_blockdiff_plan")
    plan = {k: int(g.value(kind=k)) for k in (
        "block", "rows", "loss_rows", "pairs_inside", "pairs_square",
        "recomputed_blocks")}
    assert plan == {"block": 4, "rows": 2 * B * S, "loss_rows": B * S,
                    "pairs_inside": B * (S * S + 4 * S),
                    "pairs_square": B * 4 * S * S, "recomputed_blocks": 2}
    g = reg.get("singa_moe_plan")
    assert int(g.value(kind="rows_worst")) == 2 * B * S * 4
    assert int(g.value(kind="rung_least")) == 2 * B * S * 4 // 8
    assert int(g.value(kind="experts")) == 16 and int(g.value(kind="held")) == 4
    # the buffer length each layer's row passes worked on in that step
    sdar.record_rows(rows.data)
    g = reg.get("singa_moe_rows")
    for l, r in enumerate(np.asarray(rows.data)):
        assert g.value(layer=str(l), kind="buffer") == min(
            b for b in moe.rungs(2 * B * S * 4) if b >= r.sum())
    # the loss took integer targets and read the forward's log-sum-exp
    g = reg.get("singa_cross_entropy")
    assert g.value(targets="integer", lse="kept") == 1
    # the traced flash call: the block-diffusion schedule, nothing of the
    # clean x noised quadrant
    g = reg.get("singa_flash_tiles")
    t = {k: int(g.value(site="flash_fwd", kind=k)) for k in (
        "visited", "masked", "square", "skipped", "block_diffusion",
        "steps", "idle_steps")}
    assert t["block_diffusion"] == 4
    assert t["visited"] + t["skipped"] == t["square"] == 4
    assert t["visited"] == 3
    # a grid step a worked tile, in the forward and in the backward
    assert (t["steps"], t["idle_steps"]) == (2, 0)
    assert [int(g.value(site="flash_bwd", kind=k))
            for k in ("steps", "idle_steps")] == [2, 0]


# ---- the eight shares ----------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_reference_s_layer():
    """Experts 0-1, 2-3, ..., 14-15 of 16 (an eighth each, as the cell's
    0-15, ..., 112-127 of 128): `layer.TransformerBlock` told which experts
    it holds, run on the doubled input under the block mask; the eight
    blocks' expert parts, summed onto the one attention part, equal the
    uncut reference's layer; the rows routed tile the reference's."""
    m, dev = _build()
    p0 = _params(m)
    x, masked, _ = _batch()
    rng = np.random.default_rng(9)
    full = {k.split(".", 1)[1]: v for k, v in p0.items()
            if k.startswith("TransformerBlock_0.")}
    E, d, f = CFG["num_experts"], CFG["dim"], CFG["ffn_dim"]
    for name, shape, fan in (("moe.Wg", (E, d, f), d), ("moe.Wu", (E, d, f), d),
                             ("moe.Wd", (E, f, d), f)):
        full[name] = jnp.asarray(rng.standard_normal(shape).astype(
            np.float32) * (2.0 / fan) ** 0.5)
    cfg_whole = dict(CFG, experts_held=E, expert_offset=0)
    stream = p0["tok_embed.W"][ref.doubled(x, masked, CFG)]
    plan = ref._Plan(cfg_whole, S, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, want_rows = zip(*(plan.layer(seq, full) for seq in stream))
    want, want_rows = jnp.stack(want), sum(want_rows)

    tx = tensor.from_numpy(np.asarray(stream), device=dev)
    total, rows, mid = 0, [], None
    for o in range(0, E, 2):
        blk = layer.TransformerBlock(
            layer.Attention(CFG["num_heads"], causal=False, num_kv_heads=2,
                            head_dim=32, block_diffusion=4, rope=True,
                            rope_theta=1e6, qk_norm="head"),
            layer.Experts(E, 4, dim=f, dropless=True, held=2, offset=o),
            layer.Norms("rms", 1e-6))
        blk(tx)                 # the first call makes its weights
        blk.set_params({k: np.asarray(v[o:o + 2]) if k in (
            "moe.Wg", "moe.Wu", "moe.Wd") else np.asarray(v)
            for k, v in full.items()})
        with jax.default_matmul_precision("highest"):
            if mid is None:     # what every chip computes alike: once
                mid = tx.data + blk.attn(blk.ln1(tx)).data
            total = total + (blk(tx).data - mid)
        rows.append(np.asarray(blk.moe.rows.data))
    assert _rel(mid + total, want) < 2e-5
    assert np.array_equal(np.concatenate(rows), np.asarray(want_rows))
    assert int(np.concatenate(rows).sum()) == 2 * B * S * 4   # every pair


# ---- the kernels -----------------------------------------------------------------

def _errors(shape, block, bq=None, bk=None, dtype=jnp.float32, fused=True,
            seed=0):
    """Largest absolute difference of (out, dq, dk, dv) between the `_bd`
    kernels (interpret mode) and the reference under the dense mask."""
    rng = np.random.default_rng(seed)
    q, k, v, w = (jnp.asarray(rng.standard_normal(shape), dtype)
                  for _ in "qkvw")

    def run(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out,) + vjp(w.astype(out.dtype))

    cap = att._FUSED_DQ_BYTES_CAP
    try:
        if not fused:
            att._FUSED_DQ_BYTES_CAP = 0
        got = run(lambda *a: att.flash_attention(
            *a, False, None, bq, bk, True, None, block))
    finally:
        att._FUSED_DQ_BYTES_CAP = cap
    want = run(lambda *a: att.attention_reference(
        *(x.astype(jnp.float32) for x in a), False, None, None, block))
    return [float(jnp.max(jnp.abs(g.astype(jnp.float32) - r)))
            for g, r in zip(got, want)]


# S one tile (a doubled sequence of two); blocks of 4 in tiles of 128 and
# in the tiles the plan picks; a block length that divides the tile and no
# band (96 in 384: each band under a mask over the whole tile); one as long
# as the band, one as long as the tile; tiles in bands of 128 (forward) and
# 256 (backward)
@pytest.mark.parametrize("fused", [True, False], ids=["fused", "split"])
@pytest.mark.parametrize("shape,block,bq,bk", [
    ((1, 2, 256, 32), 4, None, None),
    ((1, 2, 1024, 32), 4, 128, 128),
    ((1, 2, 1024, 32), 32, 256, None),
    ((1, 2, 768, 32), 96, None, None),
    ((1, 1, 512, 32), 128, None, None),
    ((1, 1, 512, 32), 256, None, None),
    ((1, 1, 2048, 32), 4, None, None),
], ids=lambda p: "x".join(map(str, p)) if isinstance(p, tuple) else str(p))
def test_block_diffusion_kernels_match_reference(shape, block, bq, bk, fused):
    errs = _errors(shape, block, bq, bk, fused=fused)
    assert max(errs) < 2e-4, dict(zip(("out", "dq", "dk", "dv"), errs))
    paths = observe.get_registry().get("singa_attention_dispatch_total")
    assert paths.value(site="flash_fwd", path="interpret") > 0


def test_block_diffusion_kernels_in_bfloat16():
    errs = _errors((1, 2, 512, 64), 4, dtype=jnp.bfloat16)
    assert max(errs) < 6e-2, errs


@pytest.mark.parametrize("shape,block,bq,bk", [
    ((1, 1, 3072, 16), 512, 768, 768),      # the block does not divide the tile
    ((1, 1, 512, 16), 4, 128, 256),         # tiles that are not square
    ((1, 1, 200, 16), 4, None, None),       # nothing tiles a half of 100
])
def test_what_no_tile_fits_takes_the_reference_path(shape, block, bq, bk):
    assert not att.flash_plan(shape[2], shape[2], shape[3], False,
                              jnp.float32, bq, bk, None, block).ok
    if shape[2] <= 512:
        assert max(_errors(shape, block, bq, bk)) < 2e-4


@pytest.mark.parametrize("args,fwd,bwd,skipped,steps", [
    # the cell's call: tiles of 1024, bands of 128 forward and 256 backward.
    # Forward: 4 "nn" tiles of 8 diagonal bands, 4 + 4 diagonal tiles of 36
    # sub-tiles, 6 + 6 whole tiles of 64: 1088 of the square's 4096 (a
    # causal call over 8192 visits 2080). 20 grid steps a pass (the "nn"
    # tiles ride with "nc"), the split backward's two passes 40
    ((8192, 128, "bfloat16", 4), (1024, 1024, 128, 1088, 96, 4096),
     (1024, 1024, 256, 288, 48, 1024), (3008, 736), (20, 40)),
    # S one tile
    ((256, 32, "float32", 4), (128, 128, 128, 3, 3, 4),
     (128, 128, 128, 3, 3, 4), (1, 1), (2, 2)),
    # a block length no band divides: every diagonal tile whole under masks
    ((768, 32, "float32", 96), (384, 384, 128, 27, 27, 36),
     (384, 384, 128, 27, 27, 36), (9, 9), (2, 2)),
    # the plan picks a tile the block length divides (512, not 768)
    ((3072, 32, "float32", 512), (512, 512, 128, 240, 144, 576),
     (512, 512, 256, 60, 36, 144), (336, 84), (12, 12)),
    # S one tile of 1024 (nh = 1): a noised and a clean q block, a step each
    ((2048, 128, "bfloat16", 4), (1024, 1024, 128, 80, 24, 256),
     (1024, 1024, 256, 24, 12, 64), (176, 40), (2, 2)),
    # S two tiles (nh = 2): 2 "nn" x 8, 2 + 2 diagonal tiles x 36, 2 whole
    ((4096, 128, "bfloat16", 4), (1024, 1024, 128, 288, 48, 1024),
     (1024, 1024, 256, 80, 24, 256), (736, 176), (6, 6)),
], ids=["cell", "one-tile", "b96", "b512", "nh1", "nh2"])
def test_flash_plan_block_diffusion_table(args, fwd, bwd, skipped, steps):
    seq, d, dtype, block = args
    plan = att.flash_plan(seq, seq, d, False, jnp.dtype(dtype), None, None,
                          None, block)
    assert plan.block_diffusion == block and plan.window is None
    assert tuple(plan.fwd) == fwd and tuple(plan.bwd) == bwd
    assert plan.skipped == skipped
    for t, s in zip((plan.fwd, plan.bwd), plan.skipped):
        assert t.visited + s == t.square          # visited + skipped = all
    # no tile of the clean x noised quadrant is of any kind, nor one above
    # a diagonal
    nh = seq // 2 // plan.fwd.block_q
    of_a_kind = {(j, kb, att._bd_kind(j, kb, nh))
                 for j in range(2 * nh) for kb in range(2 * nh)
                 if att._bd_kind(j, kb, nh)}
    assert not any(j >= nh > kb for j, kb, _ in of_a_kind)
    assert all(sum(att._bd_kinds(j, kb, nh).values()) <= 1
               for j in range(2 * nh) for kb in range(2 * nh))
    # a pass's grid is the sweeps' steps: every tile of some kind is met
    # exactly once a pass, a q block's (a k pair's) steps are consecutive
    # with `first` and `last` at their ends, no step is empty
    for sweep, own in zip(att._bd_sweeps(nh), (lambda s: s.q, lambda s: s.k)):
        met = [t for s in sweep for t in s.tiles()]
        assert len(met) == len(set(met)) and set(met) == of_a_kind
        assert all(s.tiles() for s in sweep)
        keys = [own(s) for s in sweep]
        runs = [k for i, k in enumerate(keys) if i == 0 or keys[i - 1] != k]
        assert len(runs) == len(set(runs))              # consecutive
        assert [s.first for s in sweep] == [
            i == 0 or keys[i - 1] != k for i, k in enumerate(keys)]
        assert [s.last for s in sweep] == [
            i == len(keys) - 1 or keys[i + 1] != k
            for i, k in enumerate(keys)]
    # the first step of a q block's sweep has met a key for every row: a
    # whole tile, or the block's own diagonal (a noised block's "nc" step
    # brings its "nn" tile), and the diagonal tile is the sweep's last
    by_q, by_k = att._bd_sweeps(nh)
    for s in by_q:
        if s.first:
            assert s.kind == "full" or s.k == (s.q if s.q >= nh
                                               else s.q + nh)
        assert s.last == (s.kind != "full")
    # what the gauge gives: grid steps a row and those that work no tile,
    # summed over the backward's calls
    fused = 1 if plan.fused else 2
    assert plan.steps == ((steps[0], 0), (steps[1], 0))
    assert (len(by_q), len(by_q) * (fused - 1) + len(by_k)) == steps


@pytest.mark.parametrize("mask,fwd,bwd", [
    # the cell's call: 20 steps a pass, the split backward's two passes 40
    ("block_diffusion", (20, 0), (40, 0)),
    # the causal call of the same shape, for scale: 28 of a pass's 64 steps
    # lie above the diagonal; the backward's sweep holds dq for the last
    # 6,144 rows, its `_dq` call takes the first two q blocks (16 steps, 3
    # tiles worked)
    ("causal", (64, 28), (80, 41)),
])
def test_flash_tiles_gauge_counts_grid_steps_and_the_idle_ones(mask, fwd, bwd):
    """singa_flash_tiles{kind=steps|idle_steps} after one traced call at
    (1, 1, 8192, 128) bf16 (traced, not run): the block-diffusion passes
    leave no grid step without a tile."""
    q = jax.ShapeDtypeStruct((1, 1, 8192, 128), jnp.bfloat16)
    causal, block = mask == "causal", 4 if mask == "block_diffusion" else None
    jax.eval_shape(jax.grad(lambda q, k, v: att.flash_attention(
        q, k, v, causal, None, None, None, True, None, block).astype(
            jnp.float32).sum(), (0, 1, 2)), q, q, q)
    g = observe.get_registry().get("singa_flash_tiles")
    got = [tuple(int(g.value(site=site, kind=kind))
                 for kind in ("steps", "idle_steps"))
           for site in ("flash_fwd", "flash_bwd")]
    assert got == [fwd, bwd]


def test_the_mask_is_one_argument_s_doing():
    """A window with the block mask, a window or the block mask with the
    wrong `causal`, and the block mask on the ring are refused, not
    computed."""
    assert autograd.attention_mask(True, 8) == ("window", 8)
    assert autograd.attention_mask(False, None, 4) == ("block_diffusion", 4)
    assert autograd.attention_mask(True) == ("causal", None)
    assert autograd.attention_mask() == (None, None)
    for bad in ((True, 8, 4), (False, 8, 4), (False, 8), (True, None, 4)):
        with pytest.raises(AssertionError):
            autograd.attention_mask(*bad)
        with pytest.raises(AssertionError):
            layer.MultiHeadAttention(2, bad[0], window=bad[1],
                                     block_diffusion=(bad + (None,))[2])
    with pytest.raises(AssertionError):
        layer.TransformerBlock(layer.Attention(2, causal=True, window=8,
                                               block_diffusion=4))
    with pytest.raises(AssertionError):
        att.flash_plan(512, 512, 32, True, jnp.float32, block_diffusion=4)
    q = tensor.from_numpy(np.zeros((1, 1, 8, 4), np.float32))
    with pytest.raises(AssertionError):
        autograd.attention(q, q, q, seq_axis="sp", block_diffusion=4)


def test_both_halves_carry_the_same_positions():
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (1, 2, 16, 8)), jnp.float32)
    once = autograd.Rope(1e6).forward(x[:, :, :8])
    twice = autograd.Rope(1e6, period=8).forward(
        jnp.concatenate([x[:, :, :8], x[:, :, :8]], axis=2))
    assert np.array_equal(twice[:, :, :8], once)
    assert np.array_equal(twice[:, :, 8:], once)


# ---- the weighted loss and the noising draw -------------------------------------------

def test_weighted_integer_cross_entropy_equals_the_dense_form():
    """sum(w CE) / rows, values and the logits' gradient, with class
    indices (nothing of the logits' size formed: the `integer, kept` pair)
    and with the same targets one-hot."""
    rng = np.random.default_rng(0)
    z = rng.standard_normal((2, 8, 12)).astype(np.float32)
    t = rng.integers(0, 12, (2, 8)).astype(np.int32)
    w = (rng.random((2, 8)) < 0.5) / rng.uniform(0.1, 1, (2, 8))
    w = w.astype(np.float32)
    dev = device.get_default_device()
    prev, autograd.training = autograd.training, True
    try:
        def run(targets):
            x = tensor.from_numpy(z, device=dev)
            x.requires_grad = x.stores_grad = True
            loss = autograd.softmax_cross_entropy(
                x, tensor.from_numpy(targets, device=dev),
                tensor.from_numpy(w, device=dev))
            grads = {id(p): g for p, g in autograd.backward(loss)}
            return float(loss.data), np.asarray(grads[id(x)].data)
        got, g_int = run(t)
        g = observe.get_registry().get("singa_cross_entropy")
        assert g.value(targets="integer", lse="kept") == 1
        dense, g_dense = run(np.eye(12, dtype=np.float32)[t])
        assert g.value(targets="dense", lse="rebuilt") == 1
    finally:
        autograd.training = prev
    logp = jax.nn.log_softmax(jnp.asarray(z), -1)
    want = float(jnp.sum(-jnp.take_along_axis(
        logp, jnp.asarray(t)[..., None], -1)[..., 0] * w) / 16)
    assert abs(got - want) < 1e-6 and abs(dense - want) < 1e-6
    assert np.abs(g_int - g_dense).max() < 1e-7
    # a row of weight 0 takes no gradient, and counts in the divisor
    assert np.all(g_int[w == 0] == 0) and np.any(g_int[w > 0] != 0)


def test_sample_positions():
    """Half the first positions, half spread over the rest, none twice."""
    at = sdar.sample_positions(1, 4096, 128)
    assert at.dtype == np.int32 and len(at) == len(set(at.tolist())) == 128
    assert at[:64].tolist() == list(range(64))
    assert at[64] == 64 and at[-1] == 4095 and np.all(np.diff(at) > 0)
    assert sdar.sample_positions(2, 8, 128).tolist() == list(range(16))


def test_noising_draw():
    rng = np.random.default_rng(4)
    masked, weight, rates = data.block_diffusion_noise(rng, (64, 256), 4)
    assert masked.shape == weight.shape == (64, 256)
    assert rates.shape == (64, 64) and masked.dtype == np.int32
    assert rates.min() >= 1e-3 and rates.max() <= 1
    t = np.repeat(rates, 4, axis=1)
    assert np.allclose(weight, masked / t, rtol=1e-6)
    # about half masked (the rate's mean), and E[weight] = 1: the
    # objective's weight makes every position count once on average
    assert abs(masked.mean() - 0.5) < 0.02
    assert abs(weight.mean() - 1.0) < 0.05
    again = data.block_diffusion_noise(np.random.default_rng(4), (64, 256), 4)
    assert np.array_equal(again[0], masked)
    with pytest.raises(AssertionError):
        data.block_diffusion_noise(rng, (1, 10), 4)
