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


# ---- set-up measures itself: spans, the compile counter, setup_report ------

class _Enters:
    """The span paths opened while it is installed, in order."""

    def __enter__(self):
        self.paths = []
        self._cb = observe.add_span_listener(
            lambda *_a: None, on_enter=self.paths.append)
        return self

    def __exit__(self, *_exc):
        observe.remove_span_listener(self._cb)

    def leaves(self):
        return [p.rsplit("/", 1)[-1] for p in self.paths]


def _zoo_mlp(dev, rng, batch=8):
    from singa_tpu import models
    m = models.create_model("mlp", data_size=10, num_classes=4)
    m.set_optimizer(opt.SGD(lr=0.1))
    tx, ty = _batch(dev, rng, batch)
    m.compile([tx], is_train=True, use_graph=True)
    return m, tx, ty


def test_setup_spans_open_in_order_and_once(dev, rng):
    with _Enters() as seen:
        m, tx, ty = _zoo_mlp(dev, rng)
        m(tx, ty)
    setup = [p for p in seen.paths if p.rsplit("/", 1)[-1]
             in introspect.SETUP_SPANS]
    assert setup == [
        "model.create", "model.init", "opt.setup", "model.build",
        "model.build/opt.setup", "introspect.build",
        "introspect.build/trace", "introspect.build/lower",
        "introspect.build/compile", "model.step",
        "model.step/introspect.first_dispatch"]
    with _Enters() as again:
        m(tx, ty)
    assert again.paths == ["model.step"]


def test_first_dispatch_span_once_a_build(dev, rng):
    m, tx, ty = _compiled_mlp(dev, rng, 8)
    h = lambda: observe.get_registry().get("singa_span_seconds").count(
        span="model.step/introspect.first_dispatch")
    for _ in range(3):
        m(tx, ty)
    assert h() == 1
    m(*_batch(dev, rng, 6))        # a new signature: a build, a first call
    m(*_batch(dev, rng, 6))
    assert h() == 2


def test_phase_totals_equal_the_phase_spans(dev, rng):
    m, tx, ty = _compiled_mlp(dev, rng, 8)
    m(tx, ty)
    m.eval()
    m(tx)                          # a second staged build, under model.eval
    spans = observe.get_registry().get("singa_span_seconds")
    totals = introspect.compile_phase_totals()
    for ph in introspect.COMPILE_PHASES:
        spanned = sum(r["sum"] for r in spans.snapshot()
                      if r["labels"]["span"].endswith(
                          "introspect.build/" + ph))
        assert totals[ph] > 0
        assert abs(totals[ph] - spanned) < 1e-3, (ph, totals[ph], spanned)
    rep = introspect.setup_report()
    assert rep["builds"]["step"]["builds"] == 1
    assert rep["builds"]["eval"]["builds"] >= 1
    assert abs(sum(rep["builds"][k]["compile"] for k in ("step", "eval"))
               - totals["compile"]) < 1e-9


@pytest.mark.parametrize("span_name, where", [
    ("model.init", "model.init"), (None, "none"),
    ("a.span.nobody.declared", "other"),
    ("model.step/introspect.first_dispatch", "introspect.first_dispatch")])
def test_compile_is_booked_to_the_open_span(span_name, where):
    import contextlib
    import jax
    x = np.arange(5, dtype=np.float32)   # made without a program
    salt = float(len(where))             # a program no other case compiled
    with contextlib.ExitStack() as stack:
        for name in (span_name or "").split("/"):
            if name:
                stack.enter_context(observe.span(name))
        jax.jit(lambda x: x * 3 + salt)(x).block_until_ready()
    h = observe.get_registry().get("singa_xla_compile_seconds")
    assert h.count(source="backend", where=where) == 1
    assert h.sum(source="backend", where=where) > 0
    assert sum(r["count"] for r in h.snapshot()) == 1


@pytest.mark.parametrize("events, source", [
    (("/jax/core/compile/backend_compile_duration",), "backend"),
    (("/jax/compilation_cache/cache_retrieval_time_sec",
      "/jax/core/compile/backend_compile_duration"), "cache"),
    (("/jax/compilation_cache/compile_time_saved_sec",), None)])
def test_compile_listener_tells_cache_from_backend(events, source):
    """jax times a cache hit under the compile event too, after reporting
    the read from inside it: one observation a request, by its source."""
    for ev in events:
        introspect._on_jax_duration(ev, 0.25, fun_name="f")
    h = observe.get_registry().get("singa_xla_compile_seconds")
    if source is None:
        assert h is None
        return
    assert h.count(source=source, where="none") == 1
    assert h.sum(source=source, where="none") == 0.25
    # the mark does not outlive its request
    introspect._on_jax_duration(events[-1], 0.5)
    assert h.count(source="backend", where="none") \
        == (2 if source == "backend" else 1)


def test_warm_cache_dir_books_cache_and_no_backend_compile(tmp_path):
    import jax
    import jax.numpy as jnp
    from singa_tpu import warmstart
    h = lambda **lab: (observe.get_registry().get(
        "singa_xla_compile_seconds") or observe.histogram(
            "singa_xla_compile_seconds")).count(where="model.init", **lab)
    f = lambda x: jnp.tanh(x) * 11 + 5
    x = np.arange(6, dtype=np.float32)
    try:
        warmstart.configure_xla_cache(str(tmp_path / "xla"))
        with observe.span("model.init"):
            jax.jit(f)(x).block_until_ready()
        assert (h(source="backend"), h(source="cache")) == (1, 0)
        # "a second run": nothing compiled is left in the process
        jax.clear_caches()
        observe.get_registry().reset()
        with observe.span("model.init"):
            jax.jit(f)(x).block_until_ready()
        assert (h(source="backend"), h(source="cache")) == (0, 1)
    finally:
        warmstart._unconfigure_xla_cache()


def test_setup_report_nets_a_build_under_eval(dev, rng):
    m, tx, ty = _compiled_mlp(dev, rng, 8)
    m(tx, ty)
    m.eval()
    m(tx)
    spans = observe.get_registry().get("singa_span_seconds")
    rep = introspect.setup_report()
    gross = spans.sum(span="model.eval")
    build = spans.sum(span="model.eval/introspect.build")
    first = spans.sum(span="model.eval/introspect.first_dispatch")
    assert build > 0 and first > 0
    assert abs(rep["spans"]["model.eval"]["seconds"]
               - (gross - build - first)) < 1e-9
    assert rep["paths"]["model.eval"]["count"] == 1
    # and the build itself is net of its three phases
    phases = sum(spans.sum(span="model.eval/introspect.build/" + ph)
                 for ph in introspect.COMPILE_PHASES)
    assert abs(rep["paths"]["model.eval/introspect.build"]["seconds"]
               - (build - phases)) < 1e-9
    # the rows add up to the wall time of the outermost listed spans
    top = sum(r["sum"] for r in spans.snapshot()
              if r["labels"]["span"] in rep["paths"]
              and not any(a in introspect.SETUP_SPANS for a in
                          r["labels"]["span"].split("/")[:-1]))
    assert abs(sum(v["seconds"] for v in rep["spans"].values())
               - top) < 1e-6
    # every span a compile can be booked to is a span the report nets
    assert set(introspect.XLA_COMPILE_WHERE[:-2]) <= set(
        introspect.SETUP_SPANS)


def test_setup_report_of_an_empty_registry_and_explain_block(dev, rng):
    assert introspect.setup_report() == {
        "spans": {}, "paths": {}, "builds": {}, "compiles": {}}
    assert "set-up" not in introspect.format_explain(introspect.explain())
    m, tx, ty = _compiled_mlp(dev, rng, 8)
    m(tx, ty)
    text = introspect.format_explain(introspect.explain(model=m))
    block = text[text.index("set-up (seconds net of nested spans):"):]
    for leaf in ("model.init", "opt.setup", "model.build", "trace",
                 "lower", "compile", "introspect.first_dispatch"):
        assert f"\n  {leaf} " in block, leaf
    assert "xla backend under compile" in block


@pytest.mark.parametrize("suppressed", [False, True])
def test_tensor_numpy_opens_tensor_fetch(dev, rng, suppressed):
    import contextlib
    tx, _ty = _batch(dev, rng, 4)
    quiet = observe.suppress_spans() if suppressed \
        else contextlib.nullcontext()
    with _Enters() as seen, quiet:
        got = tx.numpy()
    assert got.shape == (4, 10)
    assert seen.paths == ([] if suppressed else ["tensor.fetch"])
    # a trace_span: named (above), on the stack while open, never a row
    # (the write sat between a fence's return and the next dispatch)
    assert observe.get_registry().get("singa_span_seconds") is None
    with observe.trace_span("tensor.fetch"):
        assert observe.current_span() == "tensor.fetch"
        with observe.span("inner"):
            pass
    h = observe.get_registry().get("singa_span_seconds")
    assert h.count(span="tensor.fetch/inner") == 1
    assert h.count(span="tensor.fetch") == 0


def test_observation_off_leaves_no_row_and_the_same_step():
    from singa_tpu.device import get_default_device
    dev = get_default_device()

    def run():
        dev.SetRandSeed(3)
        m, tx, ty = _compiled_mlp(dev, np.random.RandomState(0), 8)
        outs = [m(tx, ty) for _ in range(2)]
        return [np.asarray(t.data) for pair in outs for t in pair]

    on = run()
    assert observe.get_registry().get("singa_xla_compile_seconds") \
        is not None
    observe.get_registry().reset()
    introspect.reset()
    observe.enable(False)
    try:
        off = run()
        assert observe.get_registry().names() == []
        assert introspect.setup_report()["spans"] == {}
    finally:
        observe.enable(True)
    assert all(np.array_equal(a, b) for a, b in zip(on, off))
