"""Run by hand, not part of tier-1 (like test_benchmark.py beside it):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

The `train_looplm` driver on a hand-built Cell at a tiny size, and the
control that puts the bf16 reference in the program's place; the
references' gradients taken a block at a time against `jax.grad`;
`update_check.py` on numbers worked by hand; the four readers that came
with the driver on a hand-written HLO text and hand-made events;
`flops_looplm.py` against a count by hand; the `train_dp` driver's record
on a four-device CPU mesh (in a child: the device count is fixed before jax
starts).
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

import flops_looplm   # noqa: E402
import run            # noqa: E402

TINY = {"create_model": dict(vocab_size=211, dim=32, num_heads=2,
                             num_layers=2, ffn_dim=88, ut_steps=3,
                             rope_theta=1e6, norm_eps=1e-6, beta=0.1,
                             sample=16)}
LOOP = {
    "driver": "train_looplm",
    "system": {"optimizer": "Adam", "lr": 0.003, "amp": "bfloat16",
               "use_graph": True, "prefetch": 2, "recompute": True},
    "traffic": {"kind": "token_batches", "batch": 1, "seq": 64, "pool": 4,
                "zipf_exponent": 1.1},
    "window": {"fetch_every": 5, "warm_steps": 2, "trace_from_step": 5,
               "trace_steps": 5},
    "check": {"loss_rtol": 0.002, "pass_ce_rtol": 0.002, "exit_atol": 0.002,
              "logit_rms_tol": 0.08, "skip": [3, 1], "skip_early": [1, 0],
              "update_tol": 0.6, "min_custom_calls": 18}}


def test_train_looplm_driver_tiny(tmp_path):
    from singa_tpu import device
    c = run.Cell({"name": "tiny", "config_data": TINY, "traffic_data": LOOP},
                 2 ** 31 + 12345, 0.5, False, device.get_default_device(),
                 out_dir=str(tmp_path))
    rec = run.load_module("drivers", "train_looplm").run(c)
    assert set(rec) >= {"checks", "attempted", "failed", "values",
                        "memory_peak_bytes"}
    json.dumps({"checks": rec["checks"], "metrics": rec["values"],
                "notes": rec["notes"]}, default=float)
    v, notes = rec["values"], rec["notes"]
    for k in ("train_tokens_per_s", "setup_s", "step_ms",
              "model_flops_per_step"):
        assert v[k] > 0, k
    assert v["flash_shape"] == [1, 2, 64, 16]
    assert v["model_flops_per_step"] == \
        64 * flops_looplm.train_flops_per_token(TINY["create_model"], 64)
    # off the chip the kernels take another path: never `correct`
    assert rec["checks"]["kernel_paths"] is False
    others = {k: ok for k, ok in rec["checks"].items() if k != "kernel_paths"}
    assert all(others.values()), (others, notes)
    assert len(notes["pass_ce"]) == len(notes["exit_mean"]) == 3
    assert abs(sum(notes["exit_mean"]) - 1.0) < 1e-5
    assert notes["steps"] % 5 == 0 and rec["failed"] == 0
    assert v["train_tokens_per_s"] == pytest.approx(
        notes["steps"] * 64 / notes["window_s"])
    # 2 blocks x 3 passes: a forward, its recomputation and a backward each
    # in the step, and the eager init pass's forwards
    paths = notes["attention_paths"]
    assert sum(n for k, n in paths.items() if k.startswith("flash_fwd")) == 18
    assert sum(n for k, n in paths.items() if k.startswith("flash_bwd")) == 6


def test_control_puts_the_bf16_reference_through_the_drivers_comparison():
    """The control runs end to end and reads what the driver reads; at this
    size bf16 hardly moves a number, so only the shape is held here."""
    import control_looplm
    from singa_tpu import device
    out = control_looplm.control(
        {"name": "tiny", "config_data": TINY, "traffic_data": LOOP},
        2 ** 31 + 12345, device.get_default_device())
    json.dumps(out, default=float)
    drv = run.load_module("drivers", "train_looplm")
    assert out["reference_in"] == "bfloat16"
    assert out["correct"] == all(out["checks"].values())
    assert set(out["checks"]) == {
        "loss_equals_reference", "pass_losses_equal_reference",
        "exit_distribution_equals_reference", "logits_equal_reference",
        "first_update_equals_reference", "tolerance_tells_a_dropped_pass",
        "tolerance_tells_a_dropped_block",
        "tolerance_tells_a_block_dropped_in_the_first_pass",
        "tolerance_tells_a_gradient_of_one_pass_alone"}
    assert 0 < out["notes"]["first_update_error"]["worst_leaf"] < 1
    assert drv.sample_rows(TINY["create_model"], 64).shape == (16,)


def _tiny_params(name, args, ids, amp=None):
    import jax.numpy as jnp
    from singa_tpu import device, models, opt, tensor
    dev = device.get_default_device()
    dev.SetRandSeed(7)
    m = models.create_model(name, **args)
    m.set_optimizer(opt.Adam(lr=1e-3))
    m.compile([tensor.from_numpy(ids[:1, :16], device=dev)], is_train=True,
              use_graph=True, amp=amp)
    return {k: jnp.asarray(v.data) for k, v in m.get_params().items()}


def _ids(vocab, b, s):
    import numpy as np
    rng = np.random.default_rng(3)
    return (rng.integers(0, vocab, (b, s)).astype("int32"),
            rng.integers(0, vocab, (b, s)).astype("int32"))


def test_reference_gradients_a_block_at_a_time_equal_jax_grad():
    """`reference_looplm.grads` (and the part from the last pass alone)
    against `jax.grad` of the whole graph; `reference_grad.grads` against
    `jax.grad` of reference.py's loss."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import reference
    import reference_grad
    import reference_looplm
    close = lambda a, b: float(jnp.abs(a - b).max()) \
        <= 1e-5 * float(jnp.abs(b).max()) + 1e-9
    cfg = TINY["create_model"]
    ids, tgt = _ids(cfg["vocab_size"], 2, 48)
    p = _tiny_params("looplm", cfg, ids)
    g, last = reference_looplm.grads(p, ids, tgt, cfg, token_block=40)
    want = reference_looplm.grad(p, ids, tgt, cfg)
    assert set(g) == set(want) and all(close(g[k], want[k]) for k in want)
    # pass T's use alone: the gradient of the T-th untied copy
    T = cfg["ut_steps"]
    parts = jax.grad(lambda u: reference_looplm.loss(
        p, ids, tgt, cfg, untied=u))([p] * T)
    assert all(close(last[k], parts[-1][k]) for k in last)
    assert all(k.startswith("TransformerBlock_") for k in last)

    gpt = dict(vocab_size=211, max_seq=64, dim=32, num_heads=2,
               num_layers=2, mlp_ratio=4, attn_bias=True)
    ids, tgt = _ids(211, 3, 40)
    p = _tiny_params("gpt", gpt, ids)

    def loss(p):
        lg = reference.logits(p, ids, 2)
        hit = jnp.take_along_axis(lg, jnp.asarray(tgt)[..., None], -1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(lg, -1) - hit)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(loss)(p)
    g = reference_grad.grads(p, ids, tgt, 2)
    assert set(g) == set(want)
    # a key bias's gradient is zero but for rounding: held to the others'
    top = max(float(jnp.abs(v).max()) for v in want.values())
    assert all(float(jnp.abs(g[k] - want[k]).max()) <= 1e-5 * (
        top if k.endswith("attn.bk") else float(jnp.abs(want[k]).max()))
        + 1e-9 for k in want)
    assert np.isclose(float(loss(p)), reference.loss(p, ids, tgt, 2))


def test_update_check_by_hand():
    import jax.numpy as jnp
    import update_check
    lr = 0.5
    p = {"a": jnp.array([1.0, 2.0, 3.0, 4.0]), "b": jnp.array([10.0, 20.0]),
         "dead": jnp.array([7.0])}
    g = {"a": jnp.array([3.0, -2.0, 1.0, -4.0]), "b": jnp.array([1.0, -1.0]),
         "dead": jnp.array([1e-9])}
    assert update_check.compared(g, 1e-3) == ["a", "b"]
    e = update_check.Expected(p, g, lr, 1e-3)
    step = {k: p[k] - lr * jnp.sign(g[k]) for k in p}   # Adam's first step
    assert e.error_of_step(step)["worst_leaf"] < 1e-6
    # a state left unchanged reads 1; one sign of four wrong reads
    # |2 lr| / (lr * sqrt(4)) = 1 on its leaf, sqrt(4 / 6) on the whole
    same = e.error_of_step(p)
    assert abs(same["whole"] - 1) < 1e-6 and abs(same["worst_leaf"] - 1) < 1e-6
    flipped = dict(step, a=step["a"].at[0].add(2 * lr))
    out = e.error_of_step(flipped)
    assert out["worst_leaf_name"] == "a" and abs(out["worst_leaf"] - 1) < 1e-6
    assert abs(out["whole"] - (4 / 6) ** 0.5) < 1e-6
    assert (out["leaves_compared"], out["leaves"]) == (2, 3)
    wrong = e.error_of_gradient(g, {"b": -g["b"]})
    assert wrong["leaves_compared"] == 1 and abs(wrong["whole"] - 2) < 1e-6


def test_flops_by_hand():
    cfg = dict(dim=2048, ffn_dim=5632, num_layers=6, ut_steps=4,
               vocab_size=49152)
    block = 4 * 2048 * 2048 + 3 * 2048 * 5632            # 51,380,224
    assert flops_looplm.block_matmul_params(cfg) == block == 51380224
    parts = flops_looplm.parts_per_token(cfg, 4096)
    assert parts == {"trunk": 6 * 24 * block,               # 7.40 GFLOP
                     "attention": 24 * 3 * 2 * 4096 * 2048,   # the causal half
                     "heads": 6 * 4 * 49152 * 2048}         # 2.42 GFLOP
    total = flops_looplm.train_flops_per_token(cfg, 4096)
    assert total == sum(parts.values()) == 11022630912
    assert round(100 * parts["heads"] / total) == 22
    # the whole model: 48 layers, the same four heads
    whole = dict(cfg, num_layers=48)
    assert round(100 * flops_looplm.parts_per_token(whole, 4096)["heads"]
                 / flops_looplm.train_flops_per_token(whole, 4096)) == 3
    assert flops_looplm.params_held(cfg) == \
        6 * (block + 4 * 2048) + 2 * 49152 * 2048 + 2048 + 2049 == 509661185


# -- the readers on a hand-written text ----------------------------------------

HLO = '''HloModule jit_step, is_scheduled=true

ENTRY %main.9 (state_arrs_0_.1: f32[8,8]) -> f32[] {
  %state_arrs_0_.1 = f32[8,8]{1,0} parameter(0), metadata={op_name="state_arrs[0]"}
  %fusion.1 = bf16[8,8]{1,0} fusion(%state_arrs_0_.1), kind=kOutput, calls=%f1, metadata={op_name="jit(step)/ut1/TransformerBlock_0/fc1/dot_general"}
  %fusion.2 = bf16[8,8]{1,0} fusion(%fusion.1), kind=kOutput, calls=%f2, metadata={op_name="jit(step)/ut2/ln_f/mul"}
  %flash.1 = bf16[8,8]{1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/ut1/TransformerBlock_0/attn/singa_flash_fwd/pallas_call"}
  %fusion.3 = f32[8,8]{1,0} fusion(%fusion.2), kind=kOutput, calls=%f3, metadata={op_name="jit(step)/head/dot_general"}
  %fusion.4 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%f4, metadata={op_name="jit(step)/exit_gate/jvp()/logistic"}
  %fusion.5 = f32[8]{0} fusion(%fusion.3), kind=kLoop, calls=%f5, metadata={op_name="jit(step)/loop_loss/reduce_max"}
  %fusion.6 = f32[8,8]{1,0} fusion(%fusion.5), kind=kLoop, calls=%f6, metadata={op_name="jit(step)/bwd/loop_loss/exp"}
  %fusion.7 = f32[8,8]{1,0} fusion(%fusion.2), kind=kOutput, calls=%f7, metadata={op_name="jit(step)/recompute/head/jvp()/dot_general"}
  %fusion.8 = bf16[8,8]{1,0} fusion(%fusion.1), kind=kOutput, calls=%f8, metadata={op_name="jit(step)/recompute/ut1/TransformerBlock_0/fc1/jvp()/dot_general"}
  %flash.2 = bf16[8,8]{1,0} custom-call(%fusion.8), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/recompute/ut1/TransformerBlock_0/attn/jvp()/singa_flash_fwd/pallas_call"}
  %flash.3 = bf16[8,8]{1,0} custom-call(%fusion.8), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/bwd/ut1/TransformerBlock_0/attn/transpose(jvp())/singa_flash_bwd/pallas_call"}
  %fusion.9 = f32[8,8]{1,0} fusion(%flash.3), kind=kOutput, calls=%f9, metadata={op_name="jit(step)/bwd/ut1/TransformerBlock_0/fc1/transpose(jvp())/dot_general"}
  %fusion.10 = f32[8,8]{1,0} fusion(%fusion.9), kind=kLoop, calls=%f10, metadata={op_name="jit(step)/opt/sub"}
  %copy.1 = f32[8,8]{0,1} copy(%fusion.10)
  ROOT %add.3 = f32[] add(%fusion.5, %fusion.5), metadata={op_name="jit(step)/loop_loss/add"}
}
'''

# two programs in the traced stretch
TRACE = {"busy_s": 4.0, "window_s": 4.1,
         "self_s": {"fusion.1": 0.4, "fusion.2": 0.1, "flash.1": 0.2,
                    "fusion.3": 0.3, "fusion.4": 0.05, "fusion.5": 0.25,
                    "fusion.6": 0.3, "fusion.7": 0.3, "fusion.8": 0.4,
                    "flash.2": 0.2, "flash.3": 0.5, "fusion.9": 0.6,
                    "fusion.10": 0.2, "copy.1": 0.1, "add.3": 0.1},
         "calls": dict.fromkeys(
             ("fusion.1", "fusion.2", "flash.1", "fusion.3", "fusion.4",
              "fusion.5", "fusion.6", "fusion.7", "fusion.8", "flash.2",
              "flash.3", "fusion.9", "fusion.10", "copy.1", "add.3"), 2)}


@pytest.fixture
def record(tmp_path):
    (tmp_path / "step_0123456789abcdef.hlo.txt").write_text(HLO)
    return {"hlo_dir": str(tmp_path),
            "values": {"model_flops_per_step": 197e12 * 0.5,
                       "device_kind": "TPU v5 lite",
                       "flash_shape": [1, 16, 4096, 128]}}


def reader(name):
    return run.load_module("layer_metrics", name).read


def test_loop_readers_by_hand(record):
    # ut*: fusion.1, .2, .8 (recomputed), .9 (backward); the three Mosaic
    # calls under ut1 are left to attn_kernel_share
    assert reader("loop_trunk_share.train")(record, TRACE) == pytest.approx(
        100 * (0.4 + 0.1 + 0.4 + 0.6) / 4.0)
    # head, gate, loss: fusion.3, .4, .5, .6, .7 (the head again), add.3
    assert reader("exit_loss_share.train")(record, TRACE) == pytest.approx(
        100 * (0.3 + 0.05 + 0.25 + 0.3 + 0.3 + 0.1) / 4.0)
    # recompute/...: fusion.7, fusion.8 and the kernel's second forward
    assert reader("recompute_share.train")(record, TRACE) == pytest.approx(
        100 * (0.3 + 0.4 + 0.2) / 4.0)
    # half the peak's FLOPs a step in 2 s a program: a quarter of the peak
    assert reader("mfu.train")(record, TRACE) == pytest.approx(25.0)
    # the accepted rule counts the recomputed forward as a forward
    import kernels
    back = lambda key, op: "transpose(" in op
    assert kernels.attention_seconds(TRACE, record["hlo_dir"], back) == (
        pytest.approx(0.5), 2)
    assert kernels.attention_seconds(
        TRACE, record["hlo_dir"], lambda k, op: not back(k, op)) == (
        pytest.approx(0.4), 4)


def test_loop_readers_find_nothing_in_another_program(tmp_path, record):
    """No trace, no text, or a text without a pass scope (GPT's step): None,
    and no exception."""
    names = ("loop_trunk_share.train", "exit_loss_share.train",
             "recompute_share.train", "mfu.train")
    for n in names:
        assert reader(n)(record, None) is None
        assert reader(n)(dict(record, hlo_dir=str(tmp_path / "none")),
                         TRACE) is None
    other = tmp_path / "gpt"
    other.mkdir()
    (other / "step_0123456789abcdef.hlo.txt").write_text(
        HLO.replace("ut1/", "").replace("ut2/", ""))
    for n in names[:3]:
        assert reader(n)(dict(record, hlo_dir=str(other)), TRACE) is None
    # a record without the driver's FLOPs (train_gpt2m's): no share
    assert reader("mfu.train")(dict(record, values={
        "device_kind": "TPU v5 lite"}), TRACE) is None


# -- the data-parallel driver on four CPU devices ----------------------------

DP_CHILD = '''
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [{root!r}, {bench!r}]
import run
from singa_tpu import device
tiny = {{"create_model": dict(vocab_size=211, max_seq=64, dim=32, num_heads=2,
                             num_layers=2, mlp_ratio=4, attn_bias=True)}}
mix = {{"driver": "train_dp",
       "system": {{"optimizer": "Adam", "lr": 0.003, "amp": "bfloat16",
                  "use_graph": True, "prefetch": 2, "chips": 4}},
       "traffic": {{"kind": "token_batches", "batch": 8, "seq": 32,
                   "pool": 4, "zipf_exponent": 1.1}},
       "window": {{"fetch_every": 5, "warm_steps": 2, "trace_from_step": 5,
                  "trace_steps": 5}},
       "check": {{"loss_rtol": 0.002, "logit_rms_tol": 0.05,
                 "min_custom_calls": 4, "update_tol": 0.6,
                 "update_floor": 1e-4}}}}
c = run.Cell({{"name": "tiny", "config_data": tiny, "traffic_data": mix}},
             2 ** 31 + 99, 0.5, False, device.get_default_device(),
             out_dir={out!r})
rec = run.load_module("drivers", "train_dp").run(c)
print(json.dumps(rec, default=float))
'''


def test_train_dp_driver_on_four_cpu_devices(tmp_path):
    child = DP_CHILD.format(root=ROOT, bench=BENCH, out=str(tmp_path))
    p = subprocess.run([sys.executable, "-c", child], capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    rec = json.loads(p.stdout.strip().splitlines()[-1])
    assert rec["checks"]["kernel_paths"] is False        # off the chip
    others = {k: ok for k, ok in rec["checks"].items() if k != "kernel_paths"}
    assert all(others.values()), (others, rec["notes"])
    assert rec["checks"]["input_shards_on_every_chip"] is True
    # the update: every leaf but the key biases (their gradient is zero),
    # a tenth off the reference's, and one chip's share of the batch told
    upd, share = (rec["notes"][k] for k in (
        "first_update_error", "update_error_of_one_chip_s_share"))
    assert upd["leaves"] - upd["leaves_compared"] == 2      # one a block
    assert upd["worst_leaf"] < 0.6 < share["worst_leaf"]
    assert rec["notes"]["params_that_differ_between_chips"] == []
    assert rec["notes"]["input_shards"] == {str(i): [2, 32] for i in range(4)}
    v, notes = rec["values"], rec["notes"]
    assert v["flash_shape"] == [2, 2, 32, 16]            # a chip's rows
    assert v["train_tokens_per_s"] == pytest.approx(      # the global batch
        notes["steps"] * 8 * 32 / notes["window_s"])
    for k in ("step_ms", "setup_s", "device_kind"):
        assert v[k], k
