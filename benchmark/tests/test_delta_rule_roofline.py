"""Run by hand, not part of tier-1 (like test_benchmark.py beside it):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_delta_rule_roofline.py -q -p no:cacheprovider

`delta_rule_roofline.train` on a hand-written step text and hand-made
events: the delta-rule kernels' calls against flops_olmo_hybrid.delta_cost's
least time, a backward split over two calls counted as one pass; nothing to
read in a program whose rule XLA implements; the metric's entry in
BENCHMARK.json, found by name.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

import flops              # noqa: E402
import flops_olmo_hybrid  # noqa: E402
import run                # noqa: E402

CELL, NAME = "train_olmo_hybrid_gdn", "delta_rule_roofline.train"

_CALL = ('  %{name} = f32[8]{{0}} custom-call(%p0), '
         'custom_call_target="tpu_custom_call", metadata={{op_name='
         '"jit(step)/{path}/pallas_call"}}\n')
_BLOCK = "TransformerBlock_0/linear_attn"
HLO = ("HloModule jit_step\n\nENTRY %main.1 (p0: f32[8]) -> f32[8] {\n"
       "  %p0 = f32[8]{0} parameter(0)\n"
       + _CALL.format(name="singa_delta_fwd.1",
                      path=f"{_BLOCK}/delta/singa_delta_fwd")
       + _CALL.format(name="singa_delta_fwd.2",
                      path=f"recompute/{_BLOCK}/jvp(delta)/singa_delta_fwd")
       + _CALL.format(name="singa_delta_bwd.1", path=f"bwd/{_BLOCK}/"
                      f"transpose(recompute)/{_BLOCK}/jvp(delta)/"
                      "singa_delta_bwd")
       + _CALL.format(name="singa_flash_fwd.1",
                      path="TransformerBlock_3/attn/singa_flash_fwd")
       + '  ROOT %fusion.1 = f32[8]{0} fusion(%p0), kind=kLoop, '
         'calls=%f1, metadata={op_name="jit(step)/head/dot_general"}\n}\n')


def _config():
    with open(os.path.join(BENCH, "configs", "olmo-hybrid-7b.json")) as f:
        return json.load(f)["create_model"]


def _read(tmp_path, text, cfg, self_s, calls):
    import kernels
    import scopes
    kernels.mosaic_calls.cache_clear()
    scopes.instructions.cache_clear()
    hlo = tmp_path / "hlo"
    hlo.mkdir(exist_ok=True)
    (hlo / "step_abc.hlo.txt").write_text(text)
    rec = {"hlo_dir": str(hlo), "values": {
        "model_args": cfg, "batch": [1, 4096],
        "device_kind": "TPU v5 lite"}}
    trace = {"busy_s": 1.0, "self_s": self_s, "calls": calls}
    return run.load_module("layer_metrics", NAME).read(rec, trace)


def _least(bwd):
    cfg = _config()
    return flops.roofline_seconds(*flops_olmo_hybrid.delta_cost(
        1, 4096, cfg["linear_heads"], cfg["linear_key_dim"],
        cfg["linear_value_dim"], cfg["chunk"], bwd), "TPU v5 lite")


@pytest.mark.parametrize("split", [False, True], ids=["one_call", "split"])
def test_roofline_counts_each_pass_once(tmp_path, split):
    """Forward, second forward and backward calls, five traced steps; a
    backward split over `_dh` and the plain name counts one pass, by the
    plain name, and the time of both."""
    ms = 1e-3
    self_s = {"singa_delta_fwd.1": 20.0 * ms, "singa_delta_fwd.2": 15.0 * ms,
              "singa_delta_bwd.1": 40.0 * ms, "singa_flash_fwd.1": 9.0 * ms,
              "fusion.1": 1.0 * ms}
    calls = dict.fromkeys(self_s, 5)
    text = HLO
    if split:
        text = HLO.replace("  ROOT", _CALL.format(
            name="singa_delta_bwd_dh.1", path=f"bwd/{_BLOCK}/transpose("
            f"recompute)/{_BLOCK}/jvp(delta)/singa_delta_bwd_dh") + "  ROOT")
        self_s["singa_delta_bwd_dh.1"], calls["singa_delta_bwd_dh.1"] = \
            10.0 * ms, 5
    (fwd, bound), (bwd, _) = _least(False), _least(True)
    assert bound == "memory"
    want = 100 * (10 * fwd + 5 * bwd) / ((85 if split else 75) * ms)
    assert _read(tmp_path, text, _config(), self_s, calls) == \
        pytest.approx(want)
    assert 0 < want < 100


def test_nothing_to_read_without_the_kernels(tmp_path):
    """The parent's program (the rule in XLA's fusions), a program that did
    not trace the kernels, or another model's configuration: None, and the
    reader does not raise."""
    ms = 1e-3
    parent = HLO.replace("singa_delta_", "fusion_delta_")
    self_s = {"fusion_delta_fwd.1": ms, "singa_flash_fwd.1": ms}
    assert _read(tmp_path, parent, _config(), self_s, {}) is None
    assert _read(tmp_path, HLO, _config(), {"fusion.1": ms}, {}) is None
    other = {k: v for k, v in _config().items() if k != "linear_heads"}
    self_s = {"singa_delta_fwd.1": ms}
    assert _read(tmp_path, HLO, other, self_s, {}) is None


def test_benchmark_json_lists_the_metric_for_the_cell_alone():
    bench = run.load_json("BENCHMARK.json")
    m = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "device_trace", "layer": "kernels",
                 "moves": "train_tokens_per_s", "workloads": [CELL]}
    assert CELL in {w["name"] for w in bench["workloads"]}
    assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                       NAME + ".py"))
