"""introspect: recompile blame, AOT compile/memory telemetry, explain CLI.

Covers the ISSUE-3 acceptance surface: every retrace after the first
compile produces a structured blame record (EventLog + the
`singa_recompile_total{reason=...}` counter, reasons from the documented
enum — never "unknown" here), the compile-phase histogram and the
`singa_xla_*` / `singa_hbm_*` gauges populate after the step compiles,
`Device.cost_analysis` is populated so `PrintTimeProfiling` verbosity 2
prints the GFLOP line, the cached step path stays cold (compile_count 1,
no new per-step EventLog records), and the CLI smoke run.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from singa_tpu import health, introspect, layer, model, observe, opt, tensor
from singa_tpu.observe import EventLog

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _MLP(model.Model):
    def __init__(self):
        super().__init__()
        self.l1 = layer.Linear(16)
        self.relu = layer.ReLU()
        self.l2 = layer.Linear(4)
        self.ce = layer.SoftMaxCrossEntropy()

    def forward(self, x):
        return self.l2(self.relu(self.l1(x)))

    def train_one_batch(self, x, y):
        out = self.forward(x)
        loss = self.ce(out, y)
        self.optimizer(loss)
        return out, loss


def _batch(dev, rng, b):
    return (tensor.from_numpy(rng.randn(b, 10).astype(np.float32), dev),
            tensor.from_numpy(rng.randint(0, 4, b).astype(np.int32), dev))


def _compiled_mlp(dev, rng, batch=32):
    m = _MLP()
    m.set_optimizer(opt.SGD(lr=0.1))
    tx, ty = _batch(dev, rng, batch)
    m.compile([tx], is_train=True, use_graph=True)
    return m, tx, ty


# ---- blame unit tests (pure diffing, no jax dispatch) ----------------------

def test_blame_reasons_unit():
    a32 = np.zeros((32, 10), np.float32)
    a48 = np.zeros((48, 10), np.float32)

    def s(arr, tag=0, static=None):
        return introspect.signature(([arr],), names=("arg",), tag=tag,
                                    static=static)

    r, d = introspect.blame(s(a32), s(a48))
    assert r == "batch_bucket"
    assert d == "arg `arg0` batch 32->48 crossed bucket 32->64"
    r, d = introspect.blame(s(a48), s(np.zeros((40, 10), np.float32)))
    assert r == "batch_bucket" and "within bucket 64" in d

    r, d = introspect.blame(s(a32), s(a32.astype(np.float16)))
    assert r == "dtype" and "float32->float16" in d
    r, _ = introspect.blame(s(a32), s(np.zeros((32, 12), np.float32)))
    assert r == "shape"
    r, _ = introspect.blame(s(a32), s(a32, tag=1))
    assert r == "new_step_tag"
    r, _ = introspect.blame(s(a32, static="a"), s(a32, static="b"))
    assert r == "static_args"
    r, _ = introspect.blame(s(a32), s(a32))
    assert r == "new_function"
    # every emitted reason is a member of the documented enum
    for prev, cur in (((a32,), (a48,)), ((a32,), (a32,))):
        r, _ = introspect.blame(s(prev[0]), s(cur[0]))
        assert r in introspect.RECOMPILE_REASONS


def test_blame_nearest_prior(dev, rng):
    """The blame diffs against the nearest prior signature, not an
    arbitrary ancestor: after seeing batches 32 and 48, a 49-batch
    retrace blames 48->49, not 32->49."""
    m, tx, ty = _compiled_mlp(dev, rng, 32)
    m(tx, ty)
    m(*_batch(dev, rng, 48))
    log = [r for r in observe.get_registry().recent
           if r.get("kind") == "recompile"]
    assert log and "32->48" in log[-1]["detail"]
    m(*_batch(dev, rng, 49))
    log = [r for r in observe.get_registry().recent
           if r.get("kind") == "recompile"]
    assert "48->49" in log[-1]["detail"]


# ---- recompile blame through the train path --------------------------------

def test_recompile_blame_batch_bucket(dev, rng, tmp_path):
    log_path = str(tmp_path / "ev.jsonl")
    observe.set_event_log(log_path)
    m, tx, ty = _compiled_mlp(dev, rng, 32)
    m(tx, ty)
    m(tx, ty)
    reg = observe.get_registry()
    assert reg.get("singa_recompile_total") is None  # cached: no retrace

    m(*_batch(dev, rng, 48))
    c = reg.get("singa_recompile_total")
    assert c is not None
    assert c.value(reason="batch_bucket", key="step") == 1
    recs = [r for r in EventLog.read(log_path) if r["kind"] == "recompile"]
    assert len(recs) == 1
    assert recs[0]["reason"] == "batch_bucket"
    assert recs[0]["detail"] == \
        "arg `arg0` batch 32->48 crossed bucket 32->64"
    assert recs[0]["key"] == "step"
    # no unknown reasons in any scenario here
    assert all(s["labels"].get("reason") != "unknown"
               for s in c.snapshot())


# ---- AOT compile-phase + cost/memory telemetry -----------------------------

def test_compile_phase_and_cost_gauges(dev, rng):
    m, tx, ty = _compiled_mlp(dev, rng, 8)
    m(tx, ty)
    reg = observe.get_registry()
    h = reg.get("singa_compile_phase_seconds")
    assert h is not None
    for ph in introspect.COMPILE_PHASES:
        assert h.count(phase=ph, key="step") == 1, ph
    assert h.sum(phase="compile", key="step") > 0

    assert reg.get("singa_xla_flops_per_step").value(key="step") > 0
    assert reg.get("singa_xla_bytes_accessed").value(key="step") > 0
    args_b = reg.get("singa_hbm_arguments_bytes")
    assert args_b is not None and args_b.value(key="step") > 0
    temps = reg.get("singa_hbm_temps_bytes")
    if temps is None or temps.value(key="step") <= 0:
        pytest.skip("memory_analysis reports no temp bytes here")
    outs = reg.get("singa_hbm_outputs_bytes")
    assert outs is not None and outs.value(key="step") > 0


def test_eval_path_goes_through_aot(dev, rng):
    m, tx, ty = _compiled_mlp(dev, rng, 8)
    m(tx, ty)
    m.eval()
    m(tx)
    h = observe.get_registry().get("singa_compile_phase_seconds")
    assert h.count(phase="compile", key="eval") >= 1


def test_mfu_gauge_from_peak_override(dev, rng):
    introspect.set_peak_tflops(1e-9)  # microscopic peak => mfu_pct > 0
    m, tx, ty = _compiled_mlp(dev, rng, 8)
    m(tx, ty)
    g = observe.get_registry().get("singa_mfu_pct")
    assert g is not None and g.value() > 0


# ---- Device.cost_analysis / PrintTimeProfiling (satellite) -----------------

def test_print_time_profiling_gflop_line(dev, rng, capsys):
    m, tx, ty = _compiled_mlp(dev, rng, 8)
    prev_v, prev_skip = dev.verbosity, dev.skip_iteration
    try:
        dev.SetVerbosity(2)
        dev.SetSkipIteration(0)
        dev.step_times = []
        dev.cost_analysis = None
        m(tx, ty)
        m(tx, ty)
        assert dev.cost_analysis  # populated at AOT build, not re-lowered
        assert float(dev.cost_analysis.get("flops", 0)) > 0
        dev.PrintTimeProfiling()
        out = capsys.readouterr().out
        assert "XLA cost" in out and "GFLOP/step" in out
        # graceful where cost_analysis() yields nothing (some backends)
        dev.cost_analysis = {}
        dev.PrintTimeProfiling()
        out = capsys.readouterr().out
        assert "time profiling" in out and "XLA cost" not in out
    finally:
        dev.SetVerbosity(prev_v)
        dev.SetSkipIteration(prev_skip)
        dev.step_times = []
        dev.cost_analysis = None


# ---- cached-path regression ------------------------------------------------

def test_cached_path_no_new_records(dev, rng, tmp_path):
    """ISSUE-3 acceptance: compile_count stays 1 over repeated same-shape
    steps and the cached path emits ONLY the per-step records PR 1
    already emitted — no compile/recompile/introspection records."""
    m, tx, ty = _compiled_mlp(dev, rng, 16)
    m(tx, ty)  # build + first step, before the log attaches
    log_path = str(tmp_path / "cached.jsonl")
    observe.set_event_log(log_path)
    for _ in range(3):
        m(tx, ty)
    recs = EventLog.read(log_path)
    assert [r["kind"] for r in recs] == ["step"] * 3
    reg = observe.get_registry()
    assert reg.get("singa_model_compile_total").value(batch_class="16") == 1
    assert reg.get("singa_recompile_total") is None
    # one executor for the one step tag, holding exactly one variant
    assert [len(ex) for ex in m._compiled_step.values()] == [1]


# ---- HLO capture + flight-recorder integration -----------------------------

def test_hlo_capture_and_flight_bundle(dev, rng, tmp_path):
    hlo_dir = str(tmp_path / "hlo")
    introspect.capture_hlo(hlo_dir)
    m, tx, ty = _compiled_mlp(dev, rng, 8)
    m(tx, ty)
    man = introspect.executable_manifest()
    ents = [e for e in man if e["key"] == "step"]
    assert ents and ents[-1]["hlo_path"]
    assert os.path.exists(ents[-1]["hlo_path"])
    assert os.path.exists(os.path.join(hlo_dir, "manifest.jsonl"))

    rec = health.FlightRecorder(out_dir=str(tmp_path))
    rec.record({"step": 1, "loss": 1.0})
    path = rec.dump(reason="nonfinite_grad", step=1)
    bundle = health.load_flight_bundle(path)
    execs = bundle["header"].get("executables")
    assert execs and any(e["key"] == "step" and e["fingerprint"]
                         for e in execs)


# ---- explain report --------------------------------------------------------

def test_explain_report_dict_and_text(dev, rng):
    m, tx, ty = _compiled_mlp(dev, rng, 8)
    prev_v, prev_skip = dev.verbosity, dev.skip_iteration
    try:
        dev.SetVerbosity(1)
        dev.SetSkipIteration(0)
        dev.step_times = []
        m(tx, ty)
        m(tx, ty)
        rep = introspect.explain(model=m, device=dev)
        assert rep["params"] > 0
        assert rep["gflops_per_step"] > 0
        assert set(rep["compile_phases_s"]) == set(
            introspect.COMPILE_PHASES)
        assert rep["hbm"].get("arguments", 0) > 0
        assert rep["step_ms_mean"] > 0
        text = introspect.format_explain(rep)
        assert "GFLOP/step" in text and "compile phases" in text
    finally:
        dev.SetVerbosity(prev_v)
        dev.SetSkipIteration(prev_skip)
        dev.step_times = []
        dev.cost_analysis = None


def test_cli_smoke(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "singa_tpu.introspect", "--config", "tiny",
         "--steps", "2", "--hlo-dir", str(tmp_path / "hlo")],
        cwd=_ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "GFLOP/step" in out.stdout
    assert "recompile history" in out.stdout
    assert "hlo:" in out.stdout  # capture wired through the CLI
