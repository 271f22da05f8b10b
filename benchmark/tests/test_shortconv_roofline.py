"""Run by hand, not part of tier-1 (like test_benchmark.py beside it):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_shortconv_roofline.py -q -p no:cacheprovider

`shortconv_roofline.train` on a hand-written step text and hand-made
events: the short-convolution kernels' calls against
flops_lfm2.mix_cost's least time; nothing to read in a program whose chain
XLA's fusions implement; the metric's entry in BENCHMARK.json.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

import run            # noqa: E402

CELL, NAME = "train_lfm2_conv_moe_16k", "shortconv_roofline.train"

_CALL = ('  %{name} = bf16[8]{{0}} custom-call(%p0), '
         'custom_call_target="tpu_custom_call", metadata={{op_name='
         '"jit(step)/{path}/pallas_call"}}\n')
HLO = ("HloModule jit_step\n\nENTRY %main.1 (p0: bf16[8]) -> bf16[8] {\n"
       "  %p0 = bf16[8]{0} parameter(0)\n"
       + _CALL.format(name="singa_shortconv_fwd.1",
                      path="TransformerBlock_0/conv/mix/singa_shortconv_fwd")
       + _CALL.format(name="singa_shortconv_fwd.2", path="recompute/"
                      "TransformerBlock_0/conv/jvp(mix)/singa_shortconv_fwd")
       + _CALL.format(name="singa_shortconv_bwd.1", path="bwd/"
                      "TransformerBlock_0/conv/transpose(recompute)/"
                      "TransformerBlock_0/conv/jvp(mix)/singa_shortconv_bwd")
       + _CALL.format(name="singa_flash_fwd.1",
                      path="TransformerBlock_1/attn/singa_flash_fwd")
       + '  ROOT %fusion.1 = bf16[8]{0} fusion(%p0), kind=kLoop, '
         'calls=%f1, metadata={op_name="jit(step)/head/dot_general"}\n}\n')


def _config():
    with open(os.path.join(BENCH, "configs", "lfm2-8b-a1b.json")) as f:
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
        "model_args": cfg, "batch": [1, 16384],
        "device_kind": "TPU v5 lite"}}
    trace = {"busy_s": 1.0, "self_s": self_s, "calls": calls}
    return run.load_module("layer_metrics", NAME).read(rec, trace)


def test_roofline_counts_each_call_as_one_pass(tmp_path):
    ms = 1e-3
    self_s = {"singa_shortconv_fwd.1": 2.0 * ms, "singa_shortconv_fwd.2":
              2.5 * ms, "singa_shortconv_bwd.1": 4.0 * ms,
              "singa_flash_fwd.1": 9.0 * ms, "fusion.1": 1.0 * ms}
    calls = {"singa_shortconv_fwd.1": 5, "singa_shortconv_fwd.2": 5,
             "singa_shortconv_bwd.1": 5, "singa_flash_fwd.1": 5,
             "fusion.1": 5}
    # (1, 16384, 2048) in bf16 at 819 GB/s: the forward reads B, C, u and
    # writes y (4 x 64 MiB), the backward reads dy, B, C, u and writes
    # three gradients (7 x 64 MiB)
    fwd, bwd = 4 * 2 ** 26 / 819e9, 7 * 2 ** 26 / 819e9
    want = 100 * (10 * fwd + 5 * bwd) / (8.5 * ms)
    assert _read(tmp_path, HLO, _config(), self_s, calls) == \
        pytest.approx(want)
    assert 0 < want < 100


def test_nothing_to_read_without_the_kernels(tmp_path):
    """The parent's program (the chain in XLA's fusions), a program that
    did not trace the kernels, or another model's configuration: None, and
    the reader does not raise."""
    ms = 1e-3
    parent = HLO.replace("singa_shortconv_", "fusion_conv_")
    self_s = {"fusion_conv_fwd.1": ms, "singa_flash_fwd.1": ms}
    assert _read(tmp_path, parent, _config(), self_s, {}) is None
    assert _read(tmp_path, HLO, _config(), {"fusion.1": ms}, {}) is None
    other = {k: v for k, v in _config().items() if k != "conv_taps"}
    self_s = {"singa_shortconv_fwd.1": ms}
    assert _read(tmp_path, HLO, other, self_s, {}) is None


def test_benchmark_json_lists_the_metric_for_the_cell_alone():
    bench = run.load_json("BENCHMARK.json")
    m = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "device_trace", "layer": "kernels",
                 "moves": "train_tokens_per_s", "workloads": [CELL]}
    assert bench["per_layer"][-1] is m
    assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                       NAME + ".py"))
