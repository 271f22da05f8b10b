"""Run by hand, not part of tier-1 (like test_benchmark.py beside it):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_sdar_cell.py -q -p no:cacheprovider

The `train_blockdiff_lm` driver on a hand-built Cell at a toy size; each
wrong model of reference_sdar.WRONG told by a limit; the control that puts
the bf16 reference in the program's place; `flops_sdar.py` against a counted
dense mask and against ISSUE 34's numbers; the two new readers on a
hand-written HLO text and hand-made events; the configuration file against
the catalog's keys; BENCHMARK.json's entries for the cell.
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

import flops_sdar     # noqa: E402
import run            # noqa: E402

TINY = {"create_model": dict(
    vocab_size=211, dim=64, num_heads=4, num_kv_heads=2, head_dim=32,
    num_layers=3, ffn_dim=48, num_experts=16, experts_per_token=4,
    experts_held=4, expert_offset=0, rope_theta=1e6, norm_eps=1e-6,
    block_length=4, sample=32)}
MIX = {
    "driver": "train_blockdiff_lm",
    "system": {"optimizer": "Adam", "lr": 0.003, "amp": "bfloat16",
               "use_graph": True, "prefetch": 2, "recompute": True,
               "weights_seed": 7, "embed_std": 1.0},
    "traffic": {"kind": "token_batches", "batch": 1, "seq": 256, "pool": 4,
                "zipf_exponent": 1.1, "rate_min": 0.001},
    "window": {"fetch_every": 2, "warm_steps": 2, "trace_from_step": 2,
               "trace_steps": 2},
    "check": {"loss_rtol": 0.004, "logit_rms_tol": 0.08, "rows_moved_tol": 40,
              "update_tol": 0.6, "min_custom_calls": 12}}
SPEC = {"name": "tiny", "config_data": TINY, "traffic_data": MIX}
WRONG = ("block_leak", "causal_mask", "positions_run_on", "qk_norm_off",
         "weight_off", "expert_left_out")
SEED = 2 ** 31 + 12345


@pytest.fixture(scope="module")
def record(tmp_path_factory):
    from singa_tpu import device
    c = run.Cell(SPEC, SEED, 3.0, False, device.get_default_device(),
                 out_dir=str(tmp_path_factory.mktemp("out")))
    return run.load_module("drivers", "train_blockdiff_lm").run(c)


@pytest.fixture(scope="module")
def control():
    import control_sdar
    from singa_tpu import device
    return control_sdar.control(SPEC, SEED, device.get_default_device())


def test_train_blockdiff_lm_driver_tiny(record):
    rec = record
    assert set(rec) >= {"checks", "attempted", "failed", "values",
                        "memory_peak_bytes"}
    json.dumps({"checks": rec["checks"], "metrics": rec["values"],
                "notes": rec["notes"]}, default=float)
    v, notes = rec["values"], rec["notes"]
    for k in ("train_tokens_per_s", "setup_s", "step_ms",
              "model_flops_per_step", "expert_load_imbalance"):
        assert v[k] > 0, k
    # data tokens, not the doubled rows
    assert v["batch"] == [1, 256] and notes["rows_a_step"] == 512
    assert v["train_tokens_per_s"] == pytest.approx(
        notes["steps"] * 256 / notes["window_s"])
    rows = np.asarray(v["moe_rows"])
    assert rows.shape == (3, 4) and 0 < rows.sum(1).max() <= 512 * 4
    assert v["model_flops_per_step"] == pytest.approx(
        flops_sdar.train_flops_per_step(TINY["create_model"], 1, 256, rows))
    # off the chip the kernels take another path: never `correct`
    assert rec["checks"]["kernel_paths"] is False
    others = {k: ok for k, ok in rec["checks"].items() if k != "kernel_paths"}
    assert all(others.values()), (others, notes)
    # a timed run pays for no wrong model: the control reads them
    assert not any(k.startswith("tolerance_tells_") for k in rec["checks"])
    assert rec["memory_peak_bytes"] == v["hbm_peak_gb"] * 1e9 > 0
    a_step = notes["rows_routed_a_step"]
    assert len(a_step["every_step"]) == notes["steps"]
    assert notes["steps"] % 2 == 0 and rec["failed"] == 0
    # the same batch met again a pool later reads lower
    assert notes["loss_fall_on_the_same_batches"]
    # 3 blocks: a forward, its recomputation and a backward each in the
    # step, and the eager init's forwards, every one on the `_bd` schedule
    paths = notes["attention_paths"]
    assert sum(n for k, n in paths.items() if k.startswith("flash_fwd")) == 9
    assert sum(n for k, n in paths.items() if k.startswith("flash_bwd")) == 3


def test_the_pool_is_the_seed_s(record):
    from singa_tpu import device
    driver = run.load_module("drivers", "train_blockdiff_lm")
    cell = lambda seed: run.Cell(SPEC, seed, 1.0, False,
                                 device.get_default_device())
    a, b, c = (driver.batches(cell(s)) for s in (SEED, SEED, SEED + 1))
    for (i, m, w), (i2, m2, w2) in zip(a, b):
        assert np.array_equal(i, i2) and np.array_equal(m, m2) \
            and np.array_equal(w, w2)
    assert not np.array_equal(a[0][0], c[0][0])
    assert not np.array_equal(a[0][1], c[0][1])
    ids, masked, weight = a[0]
    assert ids.shape == masked.shape == weight.shape == (1, 256)
    assert ids.max() <= 209          # row 210 is [MASK]: never data
    assert set(np.unique(masked)) <= {0, 1}
    assert np.all((weight > 0) == (masked == 1))


@pytest.mark.parametrize("wrong", WRONG)
def test_each_wrong_model_is_told_by_a_limit(record, control, wrong):
    """The limit on the logits' RMS error tells every wrong model at this
    size too, but the one whose logits are right (the weight left out),
    which the loss's limit tells; and the program passes both."""
    assert control["checks"]["tolerance_tells_" + wrong]
    notes, chk = control["notes"], MIX["check"]
    if wrong == "weight_off":
        assert notes["logit_rms_error_" + wrong] == 0
        assert notes["loss_rel_diff_" + wrong] > chk["loss_rtol"] \
            > record["notes"]["loss_rel_diff"]
    else:
        assert notes["logit_rms_error_" + wrong] > chk["logit_rms_tol"] \
            > record["notes"]["logit_rms_error"]


def test_control_puts_the_bf16_reference_through_the_drivers_comparison(
        control):
    out = control
    json.dumps(out, default=float)
    assert out["reference_in"] == "bfloat16"
    assert out["correct"] == all(out["checks"].values())
    assert set(out["checks"]) == {
        "loss_equals_reference", "logits_equal_reference",
        "rows_routed_equal_reference", "first_update_equals_reference",
        *("tolerance_tells_" + w for w in WRONG)}
    assert 0 < out["notes"]["first_update_error"]["worst_leaf"] < 1.5
    # every parameter but the last block's expert layer and its norm
    upd = out["notes"]["first_update_error"]
    assert upd["leaves_compared"] == upd["leaves"] == 3 * 12 + 3 - 5


def _cfg():
    return json.load(open(os.path.join(
        BENCH, "configs", "sdar-30b-a3b.json")))


def test_pairs_against_a_counted_dense_mask():
    """S^2 + S b, counted over the mask the reference builds."""
    import reference_sdar
    for half, b in ((64, 4), (96, 32), (128, 128), (60, 1)):
        i = np.arange(2 * half)
        m = np.asarray(reference_sdar.visible(
            i[:, None], i[None, :], half, b))
        assert flops_sdar.pairs_inside(half, b) == int(m.sum())
    # half a causal pass over the doubled rows, a quarter of the square
    S = 4096
    inside = flops_sdar.pairs_inside(S, 4)
    assert inside / (2 * S * (2 * S + 1) / 2) == pytest.approx(0.5, abs=1e-3)
    assert inside / (2 * S) ** 2 == pytest.approx(0.25, abs=1e-3)


def test_flops_by_hand_and_by_the_issue():
    cfg = _cfg()["create_model"]
    assert flops_sdar.attention_params(cfg) == 2048 * (2 * 4096 + 2 * 512)
    assert flops_sdar.expert_params(cfg) == 3 * 2048 * 768
    # ISSUE 34: 18.87M attention, 4.719M an expert, 645.6M held
    assert round(flops_sdar.attention_params(cfg) / 1e6, 2) == 18.87
    assert round(flops_sdar.expert_params(cfg) / 1e6, 3) == 4.719
    assert round(flops_sdar.params_held(cfg) / 1e6, 1) == 645.6
    # each expert at its even share: 8192 rows x 8 / 128 = 512 a layer
    rows = [[8192 * 8 / 128] * 16] * 6
    parts = flops_sdar.parts_per_step(cfg, 1, 4096, rows)
    total = sum(parts.values())
    assert parts["projections"] == 6 * 8192 * 6 * (
        flops_sdar.attention_params(cfg) + 2048 * 128)
    assert parts["head"] == 6 * 4096 * 18992 * 2048
    assert parts["experts"] == 6 * 6 * 16 * 512 * 3 * 2048 * 768
    assert parts["attention"] == 12 * 128 * 32 * 6 * (4096 ** 2 + 4096 * 4)
    # ISSUE 34: about 13 TFLOP a step, head and loss about 8 %
    assert round(total / 1e12) == 13
    assert round(100 * parts["head"] / total) in (7, 8)
    ops, nbytes = flops_sdar.flash_cost(cfg, 1, 4096, True)
    assert ops == 10 * 32 * 128 * (4096 ** 2 + 4096 * 4)
    assert nbytes == 8 * 32 * 8192 * 128 * 2


HLO = '''HloModule jit_step

ENTRY %main.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%f1, metadata={op_name="jit(step)/noise/select_n"}
  %bd.1 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/TransformerBlock_0/attn/singa_flash_fwd_bd/pallas_call"}
  %bd.2 = f32[8]{0} custom-call(%bd.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/recompute/TransformerBlock_0/attn/jvp(singa_flash_fwd_bd)/pallas_call"}
  %bd.3 = f32[8]{0} custom-call(%bd.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/bwd/TransformerBlock_0/attn/singa_flash_bwd_dq_bd/pallas_call"}
  %bd.4 = f32[8]{0} custom-call(%bd.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/bwd/TransformerBlock_0/attn/singa_flash_bwd_dkv_bd/pallas_call"}
  %win.1 = f32[8]{0} custom-call(%bd.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/TransformerBlock_1/attn/singa_flash_fwd_win/pallas_call"}
  %full.1 = f32[8]{0} custom-call(%win.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/TransformerBlock_3/attn/singa_flash_fwd/pallas_call"}
  ROOT %fusion.3 = f32[8]{0} fusion(%full.1), kind=kLoop, calls=%f3, metadata={op_name="jit(step)/head/dot_general"}
}
'''


def test_readers_on_a_hand_written_step(tmp_path):
    import kernels
    import scopes
    hlo = tmp_path / "hlo"
    hlo.mkdir()
    (hlo / "step_abc.hlo.txt").write_text(HLO)
    cfg = _cfg()["create_model"]
    kind = "TPU v5 lite"
    rec = {"hlo_dir": str(hlo), "values": {
        "model_args": cfg, "batch": [1, 4096], "device_kind": kind}}
    ms = 1e-3
    self_s = {"fusion.1": 1 * ms, "bd.1": 2 * ms, "bd.2": 2 * ms,
              "bd.3": 3 * ms, "bd.4": 4 * ms, "win.1": 5 * ms,
              "full.1": 5 * ms, "fusion.3": 3 * ms}
    trace = {"busy_s": 25 * ms, "self_s": self_s,
             "calls": {n: 2 for n in self_s}}
    read = lambda name: run.load_module("layer_metrics", name).read(rec, trace)
    # the `_bd` calls alone: not the windowed, not the causal
    assert read("flash_blockdiff_share.train") == pytest.approx(100 * 11 / 25)
    fwd, bwd = (flops_sdar.least_seconds(
        flops_sdar.flash_cost(cfg, 1, 4096, b), kind) for b in (False, True))
    assert fwd == pytest.approx(
        4 * 32 * 128 * (4096 ** 2 + 4096 * 4) / 197e12)
    # two forwards (the recomputed one too) and ONE backward a call counted:
    # the pass split over dq and dkv counts by its dkv call
    assert read("flash_blockdiff_roofline.train") == pytest.approx(
        100 * 2 * (2 * fwd + bwd) / (11 * ms))
    # the accepted flash readers do not take the `_bd` calls for theirs
    import moe_scopes
    assert len(moe_scopes.flash_calls(trace, str(hlo), True)) == 1
    assert len(moe_scopes.flash_calls(trace, str(hlo), False)) == 1
    # a program with no such kernel (another model's, or the parent's):
    # nothing to read, and no reader raises; nor without a trace
    (hlo / "step_abc.hlo.txt").write_text(HLO.replace("_bd", ""))
    kernels.mosaic_calls.cache_clear()
    scopes.instructions.cache_clear()
    for name in ("flash_blockdiff_roofline.train",
                 "flash_blockdiff_share.train"):
        assert read(name) is None
        assert run.load_module("layer_metrics", name).read(rec, None) is None


def test_configuration_file_holds_every_published_key():
    cfg = _cfg()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    entry = next(d for d in map(json.loads, open(catalog))
                 if d["name"] == "SDAR-30B-A3B-Chat")
    assert cfg["source"] == entry["source_url"]
    differ = {k for k, v in entry["config"].items() if cfg.get(k) != v}
    assert differ == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert cfg["published"] == {k: entry["config"][k] for k in differ}
    cm = cfg["create_model"]
    assert (cm["dim"], cm["num_heads"], cm["num_kv_heads"], cm["head_dim"],
            cm["ffn_dim"], cm["num_experts"], cm["experts_per_token"]) == \
        (2048, 32, 4, 128, 768, 128, 8)
    # the floors: a whole period and four layers, 8 routed experts, an
    # eighth of the vocabulary
    assert cm["num_layers"] == cfg["num_hidden_layers"] >= 4
    assert cm["experts_held"] == cfg["num_experts"] >= 8
    assert cm["vocab_size"] == cfg["vocab_size"] \
        >= entry["config"]["vocab_size"] / 8
    for key in ("block_length", "noise_schedule", "qk_norm", "mask_row"):
        assert key in cfg["assumed"]


def test_benchmark_json_names_the_cell():
    bench = run.load_json("BENCHMARK.json")
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == ("train_sdar_blockdiff_4k", "sdar-30b-a3b", "blockdiff_1x4096", 1)
    assert len(cell["why"]) <= 200
    assert bench["configs"][-1]["name"] == "sdar-30b-a3b"
    assert os.path.exists(os.path.join(ROOT, bench["configs"][-1]["file"]))
    listed = {m["name"] for m in bench["per_layer"]
              if cell["name"] in m.get("workloads", [])}
    assert {"flash_blockdiff_roofline.train", "flash_blockdiff_share.train",
            "mfu.train", "step_ms.train", "moe_share.train"} <= listed
    # they count causal pairs: over 100 here
    assert not listed & {"flash_full_roofline.train",
                         "flash_window_roofline.train", "flash_roofline.train"}
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".py")), m["name"]
    mix = run.load_json("benchmark", "traffic", cell["traffic"] + ".json")
    assert mix["driver"] == "train_blockdiff_lm"
    assert set(mix["check"]["reasons"]) >= {
        "control", "loss_rtol", "logit_rms_tol", "rows_moved_tol",
        "update_tol", "min_custom_calls"}
