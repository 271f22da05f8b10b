"""Run by hand, not part of tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

Both drivers at a tiny size on the CPU (where the attention kernels take the
interpret or reference path, so `kernel_paths` must read False and the
record may not count as correct), and the benchmark's own arithmetic.
"""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

import flops          # noqa: E402
import run            # noqa: E402
import trace_reduce   # noqa: E402
import traffic        # noqa: E402

TINY = {"create_model": dict(vocab_size=211, max_seq=64, dim=32, num_heads=2,
                             num_layers=2, mlp_ratio=4, attn_bias=True)}
TRAIN = {
    "driver": "train",
    "system": {"optimizer": "Adam", "lr": 0.003, "amp": "bfloat16",
               "use_graph": True, "prefetch": 2},
    "traffic": {"kind": "token_batches", "batch": 2, "seq": 32, "pool": 4,
                "zipf_exponent": 1.1},
    "window": {"fetch_every": 5, "warm_steps": 2, "trace_from_step": 5,
               "trace_steps": 5},
    "check": {"loss_rtol": 0.002, "logit_rms_tol": 0.05,
              "min_custom_calls": 4}}
SERVE = {
    "driver": "serve_closed",
    "system": {"engine": {"max_slots": 4, "page_size": 8, "max_ctx": 64,
                          "prompt_buckets": [16, 40], "dtype": "bfloat16"}},
    "traffic": {"kind": "requests", "clients": 4, "block": 8, "blocks": 50,
                "pair_seed": 1,
                "prompt": {"median": 16, "sigma": 0.6, "min": 4, "max": 40},
                "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 20}},
    "window": {"ramp_s": 0.5, "trace_from_s": 0.2, "trace_s": 0.5},
    "check": {"sample": 3, "logit_gap_of_range": 2.0 ** -8,
              "min_custom_calls": 2}}


def cell(mix, tmp_path, seconds, trace=False, seed=2 ** 31 + 12345):
    from singa_tpu import device
    # prepare() is main()'s: the tests leave the compile cache alone
    return run.Cell({"name": "tiny", "config_data": TINY,
                     "traffic_data": mix}, seed, seconds, trace,
                    device.get_default_device(), out_dir=str(tmp_path))


def well_formed(rec, names):
    assert set(rec) >= {"checks", "attempted", "failed", "values",
                        "memory_peak_bytes"}
    json.dumps({"checks": rec["checks"], "metrics": rec["values"]})
    for n in names:
        assert isinstance(rec["values"][n], float) and rec["values"][n] > 0, n
    # off the chip the kernels take another path: never `correct`
    assert rec["checks"]["kernel_paths"] is False
    others = {k: v for k, v in rec["checks"].items() if k != "kernel_paths"}
    assert all(others.values()), others


def test_train_driver_tiny(tmp_path):
    c = cell(TRAIN, tmp_path, seconds=0.5)
    rec = run.load_module("drivers", "train").run(c)
    well_formed(rec, ["train_tokens_per_s", "setup_s", "step_ms"])
    notes = rec["notes"]
    assert rec["attempted"] == notes["steps"] and notes["steps"] % 5 == 0
    assert rec["failed"] == 0
    assert len(notes["losses_fetched"]) == notes["steps"] // 5
    assert rec["values"]["train_tokens_per_s"] == pytest.approx(
        notes["steps"] * 2 * 32 / notes["window_s"])
    assert notes["window_s"] >= 0.5


def test_serve_driver_tiny(tmp_path):
    c = cell(SERVE, tmp_path, seconds=2.0)
    rec = run.load_module("drivers", "serve_closed").run(c)
    well_formed(rec, ["serve_tokens_per_s", "ttft_p50_ms", "tpot_p50_ms",
                      "setup_s", "ms_per_decode_step", "slot_occupancy"])
    notes = rec["notes"]
    assert rec["failed"] == 0 and notes["listener_error"] is None
    # closed loop: what was sent in the window is what finished in it, less
    # at most one request in flight a client
    assert 0 <= rec["attempted"] - notes["submitted_and_finished_in_window"] \
        <= SERVE["traffic"]["clients"]
    assert notes["finished_in_window"] \
        >= notes["submitted_and_finished_in_window"] > 0
    assert 0 < rec["values"]["slot_occupancy"] <= 100.0
    assert rec["values"]["serve_tokens_per_s"] == pytest.approx(
        notes["tokens_emitted_in_window"] / notes["window_s"])
    # what the window took from before it about equals what it leaves
    assert abs(notes["tokens_emitted_in_window"]
               - notes["tokens_of_requests_finished_in_window"]) \
        <= SERVE["traffic"]["clients"] * 20
    assert notes["reference_worst_gap_skipping_a_block"] > 2.0 ** -6
    assert notes["reference_sample"] == 3


def test_traffic_is_a_function_of_the_seed_alone():
    p = SERVE["traffic"]
    a, b = traffic.requests(p, 211, 64, 7), traffic.requests(p, 211, 64, 7)
    assert all((x[0] == y[0]).all() and x[1] == y[1] for x, y in zip(a, b))
    c = traffic.requests(p, 211, 64, 2 ** 31 + 8)
    sizes = lambda rs: sorted((len(q), n) for q, n in rs)
    assert sizes(a) == sizes(c)                      # same work, any seed
    assert [len(q) for q, _ in a] != [len(q) for q, _ in c]   # another order
    assert all(len(q) + n <= 64 for q, n in a)
    t = TRAIN["traffic"]
    x, y = traffic.token_batches(t, 211, 3), traffic.token_batches(t, 211, 3)
    assert all((i[0] == j[0]).all() for i, j in zip(x, y))
    assert (x[0][0][:, 1:] == x[0][1][:, :-1]).all()  # targets: next token
    ids = np.concatenate([i[0].ravel() for i in x])
    assert (ids == 0).mean() > (ids == 5).mean() > 0  # Zipf: rank 1 leads


def test_real_traffic_files_fit_the_context():
    for name in os.listdir(os.path.join(BENCH, "traffic")):
        mix = run.load_json("benchmark", "traffic", name)
        if mix["traffic"]["kind"] == "requests":
            ctx = mix["system"]["engine"]["max_ctx"]
            sizes = traffic.request_sizes(mix["traffic"], ctx)
            assert all(a + b <= ctx and a >= 1 and b >= 2 for a, b in sizes)


def test_trace_reduction_by_hand():
    ev = [("a", 0.0, 1.0), ("b", 0.5, 2.0), ("while", 3.0, 6.0),
          ("c", 3.5, 4.0), ("d", 4.0, 5.0)]
    assert trace_reduce.union_seconds(ev) == 5.0
    assert trace_reduce.union_seconds(ev, 0.5, 3.5) == 2.0
    assert trace_reduce.idle_gaps(ev, 0.0, 7.0) == [(2.0, 3.0), (6.0, 7.0)]
    assert trace_reduce.self_times(ev)["while"] == 1.5
    spans = [("step", 2.0, 3.2), ("step/inner", 2.1, 2.9)]
    s = trace_reduce.summarize(ev, spans, 0.0, 7.0)
    assert s["busy_s"] == 5.0 and s["window_s"] == 7.0
    assert s["idle_share"] == pytest.approx(2 / 7)
    assert dict(map(tuple, s["idle_gaps"])) == {"step/inner": 1.0,
                                                "after:step": 1.0}
    assert s["device_ops"][0][1] == 1.5
    assert trace_reduce.name_gap((0.0, 0.1), []) == "none"


def test_flops_against_a_hand_count():
    c = run.load_json("benchmark", "configs", "gpt2-medium.json")
    a = c["create_model"]
    assert (a["num_layers"], a["dim"], a["num_heads"], a["vocab_size"]) == \
        (c["n_layer"], c["n_embd"], c["n_head"], c["vocab_size"])
    # 24 layers x 12 x 1024^2 + 50257 x 1024
    assert flops.gpt2_matmul_params(a) == 301989888 + 51463168
    # + embedding 51463168, positions 1048576, per layer 13 d of biases and
    # norms, final norm 2 d
    assert flops.gpt2_params_held(a) == 353453056 + 51463168 + 1048576 \
        + 24 * 13 * 1024 + 2048
    assert flops.gpt2_train_flops_per_token(a, 1024) == \
        6 * 353453056 + 24 * 6 * 1024 * 1024
    with pytest.raises(KeyError):
        flops.peak("cpu", "bf16_flops")
    assert flops.flash_fwd_cost(1, 1, 4, 2, causal=False) == (128, 64)
    assert flops.roofline_seconds(197e12, 819e9 * 2, "TPU v5 lite") == \
        (2.0, "memory")


def test_run_py_exits_2_off_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", "train_gpt2m", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       env=env, cwd=ROOT, capture_output=True, text=True)
    assert r.returncode == 2 and r.stdout.strip() == ""


def test_benchmark_json_names_and_files():
    b = run.load_json("BENCHMARK.json")
    name, unit = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$"), \
        re.compile(r"[A-Za-z0-9_/%.-]{1,16}$")
    e2e = {m["name"] for m in b["end_to_end"]}
    cells = {w["name"] for w in b["workloads"]}
    for m in b["end_to_end"] + b["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"]), m
        assert set(m.get("workloads", [])) <= cells
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".py")), m["name"]
    for w in b["workloads"]:
        assert name.match(w["name"]) and name.match(w["traffic"])
        mix = run.load_json("benchmark", "traffic", w["traffic"] + ".json")
        assert os.path.exists(os.path.join(
            BENCH, "drivers", mix["driver"] + ".py"))
    for c in b["configs"]:
        assert name.match(c["name"]) and os.path.exists(
            os.path.join(ROOT, c["file"]))


def test_kernels_are_found_by_their_hlo_instruction(tmp_path):
    import kernels
    (tmp_path / "step_0123456789abcdef.hlo.txt").write_text(
        '  %jvp__.3 = (bf16[8]) custom-call(%a), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(step)/jvp()/pallas_call"}\n'
        '  %transpose_jvp___.3 = bf16[8] custom-call(%b), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp())/'
        'pallas_call"}\n'
        '  %custom-call.9 = s32[8] custom-call(%c), custom_call_target='
        '"ConcatBitcast", metadata={op_name="jit(step)/concat"}\n')
    assert kernels.mosaic_calls(str(tmp_path)) == {
        "jvp__.3": ("step", "jit(step)/jvp()/pallas_call"),
        "transpose_jvp___.3": ("step",
                               "jit(step)/transpose(jvp())/pallas_call")}
    trace = {"busy_s": 2.0,
             "self_s": {"jvp__.3": 2e-4, "transpose_jvp___.3": 6e-4,
                        "custom-call.9": 1.0, "fusion.1": 0.9},
             "calls": {"jvp__.3": 2, "transpose_jvp___.3": 2,
                       "custom-call.9": 4, "fusion.1": 4}}
    rec = {"hlo_dir": str(tmp_path),
           "values": {"flash_shape": [4, 16, 1024, 64],
                      "device_kind": "TPU v5 lite"}}
    assert kernels.attention_seconds(trace, str(tmp_path)) == \
        (pytest.approx(8e-4), 4)
    share = run.load_module("layer_metrics", "attn_kernel_share.train")
    assert share.read(rec, trace) == pytest.approx(100 * 8e-4 / 2.0)
    roof = run.load_module("layer_metrics", "flash_roofline.train")
    # forward: 8.59 GFLOP at 197 TFLOP/s = 43.6 us (33.5 MB would take 41);
    # backward 2.5 x the operations, 2 x the bytes: 109 us
    assert roof.read(rec, trace) == pytest.approx(
        100 * (2 * 43.6e-6 + 2 * 109.0e-6) / 8e-4, rel=1e-3)
    assert roof.read(rec, None) is None
