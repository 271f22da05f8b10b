"""Run by hand, not part of tier-1 (like test_benchmark.py beside it):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_mellum_cell.py -q -p no:cacheprovider

The `train_moe_lm` driver on a hand-built Cell at a toy size; each wrong
model of the traffic file's `check.reasons` refused by the limit that names
it; the control that puts the bf16 reference in the program's place;
`flops_mellum.py` against a count by hand and against ISSUE 32's; the new
readers on a hand-written HLO text and hand-made events; the configuration
file against the catalog's keys.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

import flops_mellum   # noqa: E402
import run            # noqa: E402

TINY = {"create_model": dict(
    vocab_size=211, dim=64, num_heads=4, num_kv_heads=2, head_dim=32,
    layer_types=["sliding_attention", "sliding_attention",
                 "sliding_attention", "full_attention"], window=128,
    ffn_dim=48, num_experts=8, experts_per_token=4, experts_held=4,
    expert_offset=0, rope_theta=5e5,
    rope_scaling={"factor": 16.0, "original_max_position_embeddings": 64,
                  "beta_fast": 32.0, "beta_slow": 1.0,
                  "attention_factor": 1.2772588722239782},
    norm_eps=1e-6, sample=32)}
MIX = {
    "driver": "train_moe_lm",
    "system": {"optimizer": "Adam", "lr": 0.003, "amp": "bfloat16",
               "use_graph": True, "prefetch": 2, "recompute": True,
               "weights_seed": 7, "embed_std": 1.0},
    "traffic": {"kind": "token_batches", "batch": 1, "seq": 512, "pool": 4,
                "zipf_exponent": 1.1},
    "window": {"fetch_every": 2, "warm_steps": 2, "trace_from_step": 2,
               "trace_steps": 2},
    "check": {"loss_rtol": 0.004, "logit_rms_tol": 0.08, "rows_moved_tol": 40,
              "update_tol": 0.6, "min_custom_calls": 16}}
SPEC = {"name": "tiny", "config_data": TINY, "traffic_data": MIX}
WRONG = ("window_off", "yarn_off", "gates_not_renormalised",
         "expert_left_out", "layers_swapped")


@pytest.fixture(scope="module")
def record(tmp_path_factory):
    from singa_tpu import device
    c = run.Cell(SPEC, 2 ** 31 + 12345, 3.0, False,
                 device.get_default_device(),
                 out_dir=str(tmp_path_factory.mktemp("out")))
    return run.load_module("drivers", "train_moe_lm").run(c)


@pytest.fixture(scope="module")
def control():
    import control_mellum
    from singa_tpu import device
    return control_mellum.control(SPEC, 2 ** 31 + 12345,
                                  device.get_default_device())


def test_train_moe_lm_driver_tiny(record):
    rec = record
    assert set(rec) >= {"checks", "attempted", "failed", "values",
                        "memory_peak_bytes"}
    json.dumps({"checks": rec["checks"], "metrics": rec["values"],
                "notes": rec["notes"]}, default=float)
    v, notes = rec["values"], rec["notes"]
    for k in ("train_tokens_per_s", "setup_s", "step_ms",
              "model_flops_per_step", "expert_load_imbalance"):
        assert v[k] > 0, k
    import numpy as np
    rows = np.asarray(v["moe_rows"])
    assert rows.shape == (4, 4) and 0 < rows.sum(1).max() <= 512 * 4
    assert v["model_flops_per_step"] == pytest.approx(
        flops_mellum.train_flops_per_step(TINY["create_model"], 1, 512, rows))
    # off the chip the kernels take another path: never `correct`
    assert rec["checks"]["kernel_paths"] is False
    others = {k: ok for k, ok in rec["checks"].items() if k != "kernel_paths"}
    assert all(others.values()), (others, notes)
    # a timed run pays for no wrong model: the control reads them
    assert not any(k.startswith("tolerance_tells_") for k in rec["checks"])
    # the step's own memory by the compiler's count, not the process's peak
    assert rec["memory_peak_bytes"] == v["hbm_peak_gb"] * 1e9 > 0
    a_step = notes["rows_routed_a_step"]
    assert len(a_step["every_step"]) == notes["steps"]
    assert a_step["window_mean"] == pytest.approx(
        np.mean(a_step["every_step"]))
    assert a_step["traced_steps_mean"] == pytest.approx(
        np.mean(a_step["every_step"][2:4]))
    assert notes["steps"] % 2 == 0 and rec["failed"] == 0
    # 4 blocks: a forward, its recomputation and a backward each in the
    # step (three of them under the window), and the eager init's forwards
    paths = notes["attention_paths"]
    assert sum(n for k, n in paths.items() if k.startswith("flash_fwd")) == 12
    assert sum(n for k, n in paths.items() if k.startswith("flash_bwd")) == 4


@pytest.mark.parametrize("wrong", WRONG)
def test_each_wrong_model_is_refused_by_the_limit_that_names_it(
        record, control, wrong):
    """The limit on the logits' RMS error tells every wrong model at this
    size too (the control reads them), and the program passes it."""
    assert control["checks"]["tolerance_tells_" + wrong]
    assert control["notes"]["logit_rms_error_" + wrong] \
        > MIX["check"]["logit_rms_tol"] > record["notes"]["logit_rms_error"]


def test_control_puts_the_bf16_reference_through_the_drivers_comparison(
        control):
    """The control runs end to end and reads what the driver reads."""
    out = control
    json.dumps(out, default=float)
    assert out["reference_in"] == "bfloat16"
    assert out["correct"] == all(out["checks"].values())
    assert set(out["checks"]) == {
        "loss_equals_reference", "logits_equal_reference",
        "rows_routed_equal_reference", "first_update_equals_reference",
        *("tolerance_tells_" + w for w in WRONG)}
    assert 0 < out["notes"]["first_update_error"]["worst_leaf"] < 1.5


def test_flops_by_hand_and_by_the_issue():
    cfg = json.load(open(os.path.join(
        BENCH, "configs", "mellum2-12b-a2.5b.json")))["create_model"]
    assert flops_mellum.attention_params(cfg) == 2304 * (2 * 4096 + 2 * 512)
    assert flops_mellum.expert_params(cfg) == 3 * 2304 * 896
    # ISSUE 32: 595.2M parameters held
    assert round(flops_mellum.params_held(cfg) / 1e6, 1) == 595.2
    assert flops_mellum.pairs(8192) == 8192 * 8193 // 2
    assert flops_mellum.pairs(8192, 1024) == sum(
        min(i + 1, 1024) for i in range(8192))
    assert flops_mellum.pairs(512, 1024) == flops_mellum.pairs(512)
    # ISSUE 32: 1.49 GFLOP a token with each expert at its mean load
    rows = [[8192 * 8 / 64] * 16] * 4
    parts = flops_mellum.parts_per_step(cfg, 1, 8192, rows)
    total = sum(parts.values())
    assert round(total / 8192 / 1e9, 2) == 1.49
    share = {k: round(100 * v / total) for k, v in parts.items()}
    assert share == {"projections": 34, "attention": 23, "experts": 20,
                     "head": 23}
    ops, nbytes = flops_mellum.grouped_product_cost(cfg, 16384)
    assert ops == 2 * 16384 * 2304 * 896
    assert nbytes == 2 * (16384 * 3200 + 16 * 2304 * 896)
    ops, nbytes = flops_mellum.flash_cost(cfg, 1, 8192, 1024, True)
    assert ops == 10 * 32 * flops_mellum.pairs(8192, 1024) * 128
    assert nbytes == 8 * 32 * 8192 * 128 * 2


HLO = '''HloModule jit_step

ENTRY %main.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%f1, metadata={op_name="jit(step)/TransformerBlock_0/moe/router/dot_general"}
  %gmm.1 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/TransformerBlock_0/moe/experts/gmm/pallas_call"}
  %gmm.2 = f32[8]{0} custom-call(%gmm.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/bwd/TransformerBlock_1/moe/transpose(jvp(experts))/tgmm/pallas_call"}
  %fusion.2 = f32[8]{0} fusion(%gmm.2), kind=kLoop, calls=%f2, metadata={op_name="jit(step)/recompute/TransformerBlock_1/moe/jvp(experts)/mul"}
  %win.1 = f32[8]{0} custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/TransformerBlock_0/attn/singa_flash_fwd_win/pallas_call"}
  %win.2 = f32[8]{0} custom-call(%win.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/bwd/TransformerBlock_0/attn/singa_flash_bwd_dq_win/pallas_call"}
  %win.3 = f32[8]{0} custom-call(%win.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/bwd/TransformerBlock_0/attn/singa_flash_bwd_dkv_win/pallas_call"}
  %full.1 = f32[8]{0} custom-call(%win.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/TransformerBlock_3/attn/singa_flash_fwd/pallas_call"}
  ROOT %fusion.3 = f32[8]{0} fusion(%full.1), kind=kLoop, calls=%f3, metadata={op_name="jit(step)/head/dot_general"}
}
'''


def test_readers_on_a_hand_written_step(tmp_path):
    import flops
    hlo = tmp_path / "hlo"
    hlo.mkdir()
    (hlo / "step_abc.hlo.txt").write_text(HLO)
    cfg = json.load(open(os.path.join(
        BENCH, "configs", "mellum2-12b-a2.5b.json")))["create_model"]
    kind = "TPU v5 lite"
    rows = [[1024.0] * 16] * 4
    rec = {"hlo_dir": str(hlo), "values": {
        "model_args": cfg, "batch": [1, 8192], "device_kind": kind,
        "moe_rows": rows, "expert_load_imbalance": 1.7}}
    ms = 1e-3
    self_s = {"fusion.1": 1 * ms, "gmm.1": 2 * ms, "gmm.2": 3 * ms,
              "fusion.2": 1 * ms, "win.1": 1 * ms, "win.2": 2 * ms,
              "win.3": 3 * ms, "full.1": 4 * ms, "fusion.3": 3 * ms}
    trace = {"busy_s": 20 * ms, "self_s": self_s,
             "calls": {n: 1 for n in self_s}}
    read = lambda name: run.load_module("layer_metrics", name).read(rec, trace)
    assert read("moe_share.train") == pytest.approx(100 * 7 / 20)
    assert read("moe_route_share.train") == pytest.approx(100 * 1 / 7)
    assert read("block_recompute_share.train") == pytest.approx(100 * 1 / 20)
    one = flops_mellum.least_seconds(
        flops_mellum.grouped_product_cost(cfg, 16384), kind)
    assert one == pytest.approx(2 * 16384 * 2304 * 896 / 197e12)
    assert read("expert_matmul_roofline.train") == pytest.approx(
        100 * 2 * one / (5 * ms))
    fwd, bwd = (flops_mellum.least_seconds(flops_mellum.flash_cost(
        cfg, 1, 8192, 1024, b), kind) for b in (False, True))
    # the backward pass is counted once, by its dkv call
    assert read("flash_window_roofline.train") == pytest.approx(
        100 * (fwd + bwd) / (6 * ms))
    full = flops_mellum.least_seconds(flops_mellum.flash_cost(
        cfg, 1, 8192, None, False), kind)
    assert read("flash_full_roofline.train") == pytest.approx(
        100 * full / (4 * ms))
    assert read("expert_load_imbalance.train") == 1.7
    # a program with no expert layer (another model's, or the parent's):
    # nothing to read, and no reader raises
    (hlo / "step_abc.hlo.txt").write_text(
        HLO.replace("/moe/", "/mlp/").replace("singa_flash", "other"))
    for mod in (run.load_module("layer_metrics", n) for n in (
            "moe_share.train", "moe_route_share.train",
            "block_recompute_share.train",
            "expert_matmul_roofline.train", "flash_window_roofline.train",
            "flash_full_roofline.train")):
        import kernels
        import scopes
        kernels.mosaic_calls.cache_clear()
        scopes.instructions.cache_clear()
        assert mod.read(rec, trace) is None
    assert flops.peak(kind, "bf16_flops") == 197e12


def test_configuration_file_holds_every_published_key():
    cfg = json.load(open(os.path.join(
        BENCH, "configs", "mellum2-12b-a2.5b.json")))
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    entry = next(d for d in map(json.loads, open(catalog))
                 if d["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert cfg["source"] == entry["source_url"]
    differ = {k for k, v in entry["config"].items() if cfg.get(k) != v}
    assert differ == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert cfg["published"] == {k: entry["config"][k] for k in differ}
    cm = cfg["create_model"]
    assert (cm["dim"], cm["num_heads"], cm["num_kv_heads"], cm["head_dim"],
            cm["ffn_dim"], cm["num_experts"], cm["experts_per_token"],
            cm["window"]) == (2304, 32, 4, 128, 896, 64, 8, 1024)
    assert cm["layer_types"] == entry["config"]["layer_types"][:4]
    assert cm["rope_scaling"]["attention_factor"] == \
        entry["config"]["rope_parameters"]["full_attention"][
            "attention_factor"]
