"""Run by hand, not part of tier-1 (like test_benchmark.py beside it):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_lfm2_cell.py -q -p no:cacheprovider

The `train_lfm2_lm` driver on a hand-built Cell at a toy size; each wrong
model of reference_lfm2.WRONG refused by one of the limits; the control
that puts the bf16 reference in the program's place; `flops_lfm2.py`
against a count by hand and against ISSUE 38's; the new readers on a
hand-written HLO text and hand-made events; the configuration file against
the catalog's keys; BENCHMARK.json's new entries.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

import flops_lfm2     # noqa: E402
import run            # noqa: E402

CELL = "train_lfm2_conv_moe_16k"
TINY = {"create_model": dict(
    vocab_size=211, dim=64, num_heads=4, num_kv_heads=2, head_dim=16,
    layer_types=["conv", "full_attention", "conv", "conv", "conv"],
    conv_taps=3, num_dense_layers=1, dense_ffn_dim=160, ffn_dim=48,
    num_experts=8, experts_per_token=4, experts_held=4, expert_offset=0,
    routed_scaling_factor=1.0, use_expert_bias=True, bias_update_rate=1e-3,
    rope_theta=1e6, norm_eps=1e-5, sample=32)}
MIX = {
    "driver": "train_lfm2_lm",
    "system": {"optimizer": "Adam", "lr": 0.003, "amp": "bfloat16",
               "use_graph": True, "prefetch": 2, "recompute": 3,
               "weights_seed": 7, "embed_std": 1.0, "bias_std": 0.2},
    "traffic": {"kind": "token_batches", "batch": 1, "seq": 512, "pool": 4,
                "zipf_exponent": 1.1},
    "window": {"fetch_every": 2, "warm_steps": 2, "trace_from_step": 2,
               "trace_steps": 2},
    "check": {"loss_rtol": 0.004, "logit_rms_tol": 0.08,
              "logit_outliers": 2, "pairs_moved_tol": 40, "update_tol": 0.6,
              "bias_tol": 0.25,
              "min_custom_calls": 16}}
SPEC = {"name": "tiny", "config_data": TINY, "traffic_data": MIX}


@pytest.fixture(scope="module")
def record(tmp_path_factory):
    from singa_tpu import device
    c = run.Cell(SPEC, 2 ** 31 + 12345, 3.0, False,
                 device.get_default_device(),
                 out_dir=str(tmp_path_factory.mktemp("out")))
    return run.load_module("drivers", "train_lfm2_lm").run(c)


@pytest.fixture(scope="module")
def control():
    import control_lfm2
    from singa_tpu import device
    return control_lfm2.control(SPEC, 2 ** 31 + 12345,
                                device.get_default_device())


def test_train_lfm2_lm_driver_tiny(record):
    rec = record
    assert set(rec) >= {"checks", "attempted", "failed", "values",
                        "memory_peak_bytes"}
    json.dumps({"checks": rec["checks"], "metrics": rec["values"],
                "notes": rec["notes"]}, default=float)
    v, notes = rec["values"], rec["notes"]
    for k in ("train_tokens_per_s", "setup_s", "step_ms",
              "model_flops_per_step", "expert_load_imbalance",
              "router_load_imbalance"):
        assert v[k] > 0, k
    import numpy as np
    rows = np.asarray(v["moe_rows"])
    # a row a block, the dense block's zeros: the readers index by block
    assert rows.shape == (5, 4) and not rows[0].any()
    assert 0 < rows[1:].sum(1).min() and rows.sum(1).max() <= 512 * 4
    assert v["model_flops_per_step"] == pytest.approx(
        flops_lfm2.train_flops_per_step(TINY["create_model"], 1, 512, rows))
    assert 1 <= v["router_load_imbalance"] <= 8
    # off the chip the kernels take another path: never `correct`
    assert rec["checks"]["kernel_paths"] is False
    others = {k: ok for k, ok in rec["checks"].items() if k != "kernel_paths"}
    assert all(others.values()), (others, notes)
    # a timed run pays for no wrong model: the control reads them
    assert not any(k.startswith("tolerance_tells_") for k in rec["checks"])
    assert rec["memory_peak_bytes"] == v["hbm_peak_gb"] * 1e9 > 0
    # every pair goes to one of ALL the experts: 4 layers x 512 x 4
    assert np.asarray(notes["load_first"]).sum() == 4 * 512 * 4
    assert np.asarray(notes["load_reference"]).sum() == 4 * 512 * 4
    # the bias moved: by the rate a step from the drawn one
    ends = np.asarray(notes["bias_ends_a_layer"])
    assert ends.shape == (4, 2) and (ends[:, 0] < 0).all() \
        and (ends[:, 1] > 0).all()
    assert notes["steps"] % 2 == 0 and rec["failed"] == 0
    # one attention block, rebuilt: a forward, its recomputation and a
    # backward in the step, and the eager init's forward
    paths = notes["attention_paths"]
    assert sum(n for k, n in paths.items() if k.startswith("flash_fwd")) == 3
    assert sum(n for k, n in paths.items() if k.startswith("flash_bwd")) == 1


def test_each_wrong_model_is_refused_by_a_limit(record, control):
    """One of the limits tells every wrong model at this size too (the
    control reads them), and the program passes them all."""
    import reference_lfm2
    for wrong in reference_lfm2.WRONG:
        assert control["checks"]["tolerance_tells_" + wrong], (
            wrong, {k: v for k, v in control["notes"].items()
                    if k.endswith(wrong)})
    assert record["notes"]["logit_rms_error"] < MIX["check"]["logit_rms_tol"]
    assert record["notes"]["pairs_moved"] <= MIX["check"]["pairs_moved_tol"]


def test_control_puts_the_bf16_reference_through_the_drivers_comparison(
        control):
    import reference_lfm2
    out = control
    json.dumps(out, default=float)
    assert out["reference_in"] == "bfloat16"
    assert out["correct"] == all(out["checks"].values())
    assert set(out["checks"]) == {
        "loss_equals_reference", "logits_equal_reference",
        "pairs_routed_equal_reference", "rows_are_the_held_experts_load",
        "first_update_equals_reference", "bias_after_step_equals_reference",
        *("tolerance_tells_" + w for w in reference_lfm2.WRONG)}
    assert 0 < out["notes"]["first_update_error"]["worst_leaf"] < 1.5


def _config():
    with open(os.path.join(BENCH, "configs", "lfm2-8b-a1b.json")) as f:
        return json.load(f)


def test_flops_by_hand_and_by_the_issue():
    cfg = _config()["create_model"]
    assert flops_lfm2.conv_params(cfg) == 4 * 2048 * 2048 + 3 * 2048
    assert flops_lfm2.attention_params(cfg) == 2048 * (2 * 2048 + 2 * 512)
    assert flops_lfm2.expert_params(cfg) == 3 * 2048 * 1792
    assert flops_lfm2.dense_ffn_params(cfg) == 3 * 2048 * 7168
    assert flops_lfm2.layers(cfg) == (4, 1, 1, 4)
    # ISSUE 38: 541.3M parameters held (541.37 with the gains counted)
    assert round(flops_lfm2.params_held(cfg) / 1e6, 1) == 541.4
    # ISSUE 38: 22.9 TFLOP a step with each held expert at its even share
    rows = [[0.0] * 8] + [[16384 * 4 / 32] * 8] * 4
    parts = flops_lfm2.parts_per_step(cfg, 1, 16384, rows)
    total = sum(parts.values())
    assert round(total / 1e12, 1) == 22.9
    tera = {k: round(v / 1e12, 1) for k, v in parts.items()}
    assert tera == {"conv_projections": 6.6, "conv_mix": 0.0,
                    "attention_projections": 1.0, "attention": 3.3,
                    "dense_ffn": 4.3, "router": 0.0, "experts": 4.3,
                    "head": 3.3}
    ops, nbytes = flops_lfm2.mix_cost(cfg, 1, 16384, False)
    assert ops == 16384 * 2048 * 8 and nbytes == 4 * 16384 * 2048 * 2
    ops, nbytes = flops_lfm2.mix_cost(cfg, 1, 16384, True)
    assert ops == 2 * 16384 * 2048 * 8 and nbytes == 7 * 16384 * 2048 * 2


HLO = '''HloModule jit_step

ENTRY %main.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%f1, metadata={op_name="jit(step)/TransformerBlock_0/conv/in_proj/dot_general"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%f2, metadata={op_name="jit(step)/TransformerBlock_0/conv/mix/checkpoint/mul"}
  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%f3, metadata={op_name="jit(step)/TransformerBlock_0/conv/out_proj/dot_general"}
  %fusion.4 = f32[8]{0} fusion(%fusion.3), kind=kLoop, calls=%f4, metadata={op_name="jit(step)/recompute/TransformerBlock_2/conv/jvp(mix)/checkpoint/mul"}
  %fusion.5 = f32[8]{0} fusion(%fusion.4), kind=kLoop, calls=%f5, metadata={op_name="jit(step)/bwd/TransformerBlock_2/conv/transpose(jvp(in_proj))/dot_general"}
  %fusion.6 = f32[8]{0} fusion(%fusion.5), kind=kLoop, calls=%f6, metadata={op_name="jit(step)/bwd/TransformerBlock_2/conv/transpose(jvp(mix))/checkpoint/rematted_computation/mul"}
  %fusion.7 = f32[8]{0} fusion(%fusion.6), kind=kLoop, calls=%f7, metadata={op_name="jit(step)/TransformerBlock_2/conv/amp_cast/convert_element_type"}
  %fusion.8 = f32[8]{0} fusion(%fusion.7), kind=kLoop, calls=%f8, metadata={op_name="jit(step)/TransformerBlock_1/moe/router/dot_general"}
  ROOT %fusion.9 = f32[8]{0} fusion(%fusion.8), kind=kLoop, calls=%f9, metadata={op_name="jit(step)/head/dot_general"}
}
'''


def test_readers_on_a_hand_written_step(tmp_path):
    hlo = tmp_path / "hlo"
    hlo.mkdir()
    (hlo / "step_abc.hlo.txt").write_text(HLO)
    rec = {"hlo_dir": str(hlo), "values": {
        "model_args": _config()["create_model"], "batch": [1, 16384],
        "device_kind": "TPU v5 lite", "router_load_imbalance": 1.3}}
    ms = 1e-3
    self_s = {f"fusion.{i}": i * ms for i in range(1, 10)}
    trace = {"busy_s": 60 * ms, "self_s": self_s,
             "calls": {n: 1 for n in self_s}}
    read = lambda name: run.load_module("layer_metrics", name).read(rec, trace)
    # under `conv`: fusions 1-7 = 28 ms; the projections 1 + 3 + 5 = 9
    assert read("shortconv_share.train") == pytest.approx(100 * 28 / 60)
    assert read("shortconv_mix_share.train") == pytest.approx(100 * 19 / 28)
    assert read("router_load_imbalance.train") == 1.3
    # a program with no convolution (another model's, or the parent's) and
    # a record with no such count: nothing to read, and no reader raises
    (hlo / "step_abc.hlo.txt").write_text(HLO.replace("/conv/", "/attn/"))
    import scopes
    scopes.instructions.cache_clear()
    assert read("shortconv_share.train") is None
    assert read("shortconv_mix_share.train") is None
    del rec["values"]["router_load_imbalance"]
    assert read("router_load_imbalance.train") is None


def test_configuration_file_holds_every_published_key():
    cfg = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    entry = next(d for d in map(json.loads, open(catalog))
                 if d["name"] == "LFM2-8B-A1B")
    assert cfg["source"] == entry["source_url"]
    differ = {k for k, v in entry["config"].items() if cfg.get(k) != v}
    assert differ == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"}
    assert cfg["published"] == {k: entry["config"][k] for k in differ}
    cm = cfg["create_model"]
    assert (cm["dim"], cm["num_heads"], cm["num_kv_heads"], cm["head_dim"],
            cm["dense_ffn_dim"], cm["ffn_dim"], cm["num_experts"],
            cm["experts_per_token"], cm["conv_taps"], cm["rope_theta"],
            cm["norm_eps"]) == (2048, 32, 8, 64, 7168, 1792, 32, 4, 3, 1e6,
                                1e-5)
    # layer 0 (the one leading dense layer kept) and the period 2-5
    published = entry["config"]["layer_types"]
    assert cm["layer_types"] == published[:1] + published[2:6]
    for key in ("bias_update", "bias_load", "bias_initial", "qk_norm",
                "gate_eps", "head", "weights"):
        assert key in cfg["assumed"], key


def test_benchmark_json_names_the_cell_where_the_issue_says():
    bench = run.load_json("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-8b-a1b", "zipf_1x16384", 1)
    mix = run.load_json("benchmark", "traffic", "zipf_1x16384.json")
    assert mix["driver"] == "train_lfm2_lm"
    assert mix["traffic"] == {"kind": "token_batches", "batch": 1,
                              "seq": 16384, "pool": 16, "zipf_exponent": 1.1}
    assert set(mix["check"]["reasons"]) >= {
        k for k in mix["check"] if k != "reasons"}
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [CELL])}
    assert listed >= {
        "train_tokens_per_s", "setup_s", "step_ms.train",
        "device_step_ms.train", "device_idle_share.train",
        "hbm_peak_gb.train", "mfu.train", "backbone_dense_share.train",
        "head_loss_share.train", "opt_update_share.train",
        "opt_fused_share.train", "unscoped_share.train", "moe_share.train",
        "moe_route_share.train", "expert_matmul_roofline.train",
        "expert_load_imbalance.train", "block_recompute_share.train",
        "flash_full_roofline.train", "shortconv_share.train",
        "shortconv_mix_share.train", "router_load_imbalance.train",
        "setup_init_s.train", "setup_trace_s.train",
        "setup_compile_s.train", "setup_backend_compiles.train",
        "setup_outside_compile_s.train", "setup_attributed_share.train"}
    for name in ("shortconv_share.train", "shortconv_mix_share.train",
                 "router_load_imbalance.train"):
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] \
            and m["moves"] == "train_tokens_per_s"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
