"""observe: runtime metrics & tracing subsystem.

Covers the ISSUE-1 acceptance surface: counter/gauge/histogram semantics,
span nesting + timing, Prometheus text format (golden + line-by-line
parse), JSONL EventLog round-trip + rotation, the train-loop integration
(step histograms, compile/recompile counting per batch-size class, step
records), instrumentation overhead on the cached step path, StopTrace
idempotence, and xprof tolerance of truncated xplane files + span
surfacing.
"""

import json
import os
import re
import time

import numpy as np
import pytest

from singa_tpu import layer, model, observe, opt, tensor
from singa_tpu.observe import EventLog, MetricsRegistry


@pytest.fixture
def reg():
    """Clean process-global registry per test (and detach any EventLog)."""
    r = observe.get_registry()
    r.reset()
    observe.set_event_log(None)
    observe.enable(True)
    yield r
    r.reset()
    observe.set_event_log(None)
    observe.enable(True)


# ---- metric primitives -----------------------------------------------------

def test_counter_semantics(reg):
    c = observe.counter("singa_t_total", "h")
    c.inc()
    c.inc(2.5)
    assert c.value() == 3.5
    c.inc(op="x")
    c.inc(3, op="x")
    assert c.value(op="x") == 4.0
    assert c.value() == 3.5  # label sets are independent series
    with pytest.raises(ValueError):
        c.inc(-1)
    # get-or-create returns the same object; type conflict raises
    assert observe.counter("singa_t_total") is c
    with pytest.raises(ValueError):
        observe.gauge("singa_t_total")


def test_gauge_semantics(reg):
    g = observe.gauge("singa_t_gauge")
    g.set(5.0)
    g.inc(2)
    g.dec(3)
    assert g.value() == 4.0
    g.set(1.0, dev="0")
    assert g.value(dev="0") == 1.0


def test_histogram_semantics(reg):
    h = observe.histogram("singa_t_seconds", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count() == 4
    assert abs(h.sum() - 5.555) < 1e-9
    assert h.bucket_counts() == [1, 2, 3, 4]  # cumulative, +Inf last
    h.observe(0.5, kind="x")
    assert h.count(kind="x") == 1
    assert h.count() == 4


def test_metric_name_contract(reg):
    with pytest.raises(ValueError):
        observe.counter("not_singa_prefixed")
    with pytest.raises(ValueError):
        observe.counter("singa_Bad_Case")


# ---- spans -----------------------------------------------------------------

def test_span_nesting_and_timing(reg):
    with observe.span("outer"):
        assert observe.current_span() == "outer"
        with observe.span("inner", attr=1):
            assert observe.current_span() == "outer/inner"
            time.sleep(0.01)
    assert observe.current_span() is None
    h = reg.get("singa_span_seconds")
    assert h.count(span="outer") == 1
    assert h.count(span="outer/inner") == 1
    # the inner span slept 10ms; both spans must have recorded >= that
    assert h.sum(span="outer/inner") >= 0.01
    assert h.sum(span="outer") >= h.sum(span="outer/inner")


def test_span_survives_exception(reg):
    with pytest.raises(RuntimeError):
        with observe.span("boom"):
            raise RuntimeError("x")
    assert observe.current_span() is None
    assert reg.get("singa_span_seconds").count(span="boom") == 1


# ---- Prometheus exporter ---------------------------------------------------

def test_prometheus_text_golden():
    r = MetricsRegistry()
    c = r.counter("singa_x_total", "things done")
    c.inc(3)
    c.inc(2, op="a b")
    r.gauge("singa_g").set(2.5)
    h = r.histogram("singa_h_seconds", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    expected = (
        "# TYPE singa_g gauge\n"
        "singa_g 2.5\n"
        "# TYPE singa_h_seconds histogram\n"
        'singa_h_seconds_bucket{le="1"} 1\n'
        'singa_h_seconds_bucket{le="10"} 2\n'
        'singa_h_seconds_bucket{le="+Inf"} 2\n'
        "singa_h_seconds_sum 5.5\n"
        "singa_h_seconds_count 2\n"
        "# HELP singa_x_total things done\n"
        "# TYPE singa_x_total counter\n"
        "singa_x_total 3\n"
        'singa_x_total{op="a b"} 2\n'
    )
    assert r.to_prometheus_text() == expected


_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [^ ]+$")


def _assert_valid_prometheus(text):
    """Line-by-line: every line is a # HELP/# TYPE header or a sample,
    and every sample's metric family has a preceding # TYPE."""
    typed = set()
    n_samples = 0
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            assert kind in ("counter", "gauge", "histogram"), line
            typed.add(name)
            continue
        if line.startswith("# HELP "):
            continue
        assert _SAMPLE_RE.match(line), f"unparseable sample line: {line!r}"
        base = line.split("{")[0].split(" ")[0]
        family = re.sub(r"_(bucket|sum|count)$", "", base)
        assert base in typed or family in typed, \
            f"sample {base} has no # TYPE header"
        n_samples += 1
    return n_samples


def test_prometheus_text_parses(reg):
    observe.counter("singa_t_total").inc()
    h = observe.histogram("singa_t_seconds")
    h.observe(0.1, kind="a")
    observe.gauge("singa_t_gauge").set(-1.5)
    assert _assert_valid_prometheus(observe.to_prometheus_text()) > 3


# ---- EventLog --------------------------------------------------------------

def test_eventlog_roundtrip(tmp_path):
    p = str(tmp_path / "ev.jsonl")
    log = EventLog(p)
    recs = [{"kind": "step", "i": i, "v": 1.5 * i} for i in range(5)]
    for rec in recs:
        log.write(dict(rec))
    log.close()
    back = EventLog.read(p)
    assert len(back) == 5
    for orig, got in zip(recs, back):
        assert got["i"] == orig["i"] and got["v"] == orig["v"]
        assert "ts" in got  # stamped on write


def test_eventlog_rotation(tmp_path):
    p = str(tmp_path / "rot.jsonl")
    log = EventLog(p, max_bytes=300, backups=2)
    for i in range(50):
        log.write({"i": i, "pad": "x" * 40})
    log.close()
    assert os.path.exists(p) and os.path.exists(p + ".1")
    # newest record is in the live file; every surviving line parses
    live = EventLog.read(p)
    assert live and live[-1]["i"] == 49
    assert all("i" in r for r in EventLog.read(p + ".1"))


def test_eventlog_zero_backups_still_bounded(tmp_path):
    p = str(tmp_path / "nobak.jsonl")
    log = EventLog(p, max_bytes=300, backups=0)
    for i in range(50):
        log.write({"i": i, "pad": "x" * 40})
    log.close()
    assert os.path.getsize(p) <= 300  # truncated in place, no .1 file
    assert not os.path.exists(p + ".1")
    live = EventLog.read(p)
    assert live and live[-1]["i"] == 49


def test_eventlog_skips_torn_line(tmp_path):
    p = str(tmp_path / "torn.jsonl")
    with open(p, "w") as f:
        f.write('{"a":1}\n{"b":2}\n{"c": tr')  # crash mid-write
    assert EventLog.read(p) == [{"a": 1}, {"b": 2}]


def test_eventlog_explicit_flush_and_fsync_mode(tmp_path):
    """ISSUE-7 satellite: EventLog grows flush() and an fsync=True mode
    so a worker killed mid-run keeps the tail of its event log."""
    p = str(tmp_path / "fsync.jsonl")
    log = EventLog(p, fsync=True)
    log.write({"step": 1})
    # every write is already durable in fsync mode; flush() is the
    # explicit durability point (both signatures must be callable)
    log.flush()
    log.flush(fsync=True)
    rows = EventLog.read(p)
    assert len(rows) == 1 and rows[0]["step"] == 1
    log.close()
    log2 = EventLog(str(tmp_path / "plain.jsonl"))
    log2.write({"step": 2})
    log2.flush(fsync=True)  # opt-in fsync on a non-fsync log
    log2.flush()            # and the cheap flavor
    log2.close()


def test_eventlog_survives_sigkill(tmp_path):
    """Kill -9 a subprocess immediately after it logs step N: the last
    logged step must survive on disk (the PR-6 kill-resume post-mortem
    contract). The child imports only singa_tpu.observe — no jax."""
    import subprocess
    import sys
    p = str(tmp_path / "killed.jsonl")
    script = (
        "import os, signal, sys\n"
        f"sys.path.insert(0, {repr(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))})\n"
        "from singa_tpu.observe import EventLog\n"
        f"log = EventLog({p!r}, fsync=True)\n"
        "for i in range(20):\n"
        "    log.write({'kind': 'step', 'step': i})\n"
        "log.flush(fsync=True)\n"
        "os.kill(os.getpid(), signal.SIGKILL)\n")
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, timeout=60)
    assert proc.returncode == -9  # really SIGKILLed, no atexit ran
    rows = EventLog.read(p)
    assert rows and rows[-1]["step"] == 19


# ---- train-loop integration ------------------------------------------------

class _MLP(model.Model):
    def __init__(self):
        super().__init__()
        self.l1 = layer.Linear(16)
        self.relu = layer.ReLU()
        self.l2 = layer.Linear(4)
        self.loss_fn = layer.SoftMaxCrossEntropy()

    def forward(self, x):
        return self.l2(self.relu(self.l1(x)))

    def train_one_batch(self, x, y):
        out = self.forward(x)
        loss = self.loss_fn(out, y)
        self._optimizer(loss)
        return out, loss


def _compiled_mlp(dev, rng, batch=32):
    X = rng.randn(batch, 10).astype(np.float32)
    Y = rng.randint(0, 4, batch).astype(np.int32)
    m = _MLP()
    m.set_optimizer(opt.SGD(lr=0.1))
    tx, ty = tensor.from_numpy(X, dev), tensor.from_numpy(Y, dev)
    m.compile([tx], is_train=True, use_graph=True)
    return m, tx, ty


def test_train_step_telemetry(dev, rng, reg, tmp_path):
    """ISSUE-1 acceptance: a 3-step graph-mode run populates step-latency
    histograms, compile_count == 1 across same-shape calls (and again on
    a new batch-size class), valid Prometheus text, >=3 JSONL records."""
    log_path = str(tmp_path / "steps.jsonl")
    observe.set_event_log(log_path)
    m, tx, ty = _compiled_mlp(dev, rng)
    for _ in range(3):
        m(tx, ty)

    c = reg.get("singa_model_compile_total")
    assert c.value(batch_class="32") == 1  # one compile, not three
    assert reg.get("singa_model_recompile_total") is None
    h = reg.get("singa_step_seconds")
    assert h.count() == 3 and h.sum() > 0
    assert reg.get("singa_steps_total").value() == 3
    assert reg.get("singa_step_donated_bytes").value() > 0
    # optimizer instrumentation fired at trace time: 4 params, once —
    # nested under the AOT staging span's trace phase (the trace runs
    # inside introspect.build_compiled, each phase a span of its own)
    assert reg.get("singa_opt_updates_total").value(strategy="local") == 4
    assert reg.get("singa_span_seconds").count(
        span="introspect.build/trace/opt.apply_updates") == 1
    # and the per-step dispatch span fired once per step
    assert reg.get("singa_span_seconds").count(span="model.step") == 3

    n = _assert_valid_prometheus(observe.to_prometheus_text())
    assert n >= 3

    steps = [r for r in EventLog.read(log_path) if r["kind"] == "step"]
    assert len(steps) >= 3
    assert steps[0]["batch"] == 32 and steps[0]["seconds"] > 0
    assert [r["step"] for r in steps[:3]] == [1, 2, 3]

    # a new batch-size class retraces: compile for the new class +
    # recompile_total increments; the old class stays at 1
    X2 = rng.randn(16, 10).astype(np.float32)
    Y2 = rng.randint(0, 4, 16).astype(np.int32)
    m(tensor.from_numpy(X2, dev), tensor.from_numpy(Y2, dev))
    assert c.value(batch_class="16") == 1
    assert c.value(batch_class="32") == 1
    assert reg.get("singa_model_recompile_total").value(
        batch_class="16") == 1
    # and replaying either shape compiles nothing new
    m(tx, ty)
    assert c.value(batch_class="32") == 1


def test_instrumentation_overhead_cached_path(dev, rng, reg):
    """Cached-step overhead of the default instrumentation (no EventLog
    attached) stays small. The ISSUE budget is <5%; timer noise on a
    sub-ms CPU step makes that unassertable directly, so the bound here
    is generous (50% + 0.5ms absolute) over interleaved best-of-rounds
    medians (immune to CPU contention spikes) — it still catches
    pathological regressions like a per-step device sync or file
    write."""
    m, tx, ty = _compiled_mlp(dev, rng)

    def median_ms(n=30):
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            m(tx, ty)
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts)) * 1e3

    median_ms(10)  # warmup: compile + caches
    base, instrumented = [], []
    try:
        for _ in range(4):  # interleave so load spikes hit both arms
            observe.enable(False)
            base.append(median_ms())
            observe.enable(True)
            instrumented.append(median_ms())
    finally:
        observe.enable(True)
    best_base, best_inst = min(base), min(instrumented)
    assert best_inst <= best_base * 1.5 + 0.5, \
        f"instrumented {best_inst:.3f}ms vs base {best_base:.3f}ms"


def test_observe_dump(dev, rng, reg):
    m, tx, ty = _compiled_mlp(dev, rng)
    m(tx, ty)
    d = observe.dump()
    assert "singa_step_seconds" in d["metrics"]
    assert d["metrics"]["singa_steps_total"]["type"] == "counter"
    assert any(r["kind"] == "step" for r in d["recent_events"])
    # JSON-able end to end
    json.dumps(d)


# ---- Device.StopTrace idempotence (ISSUE-1 satellite) ---------------------

def test_stoptrace_idempotent(tmp_path):
    import jax
    from singa_tpu.device import get_default_device
    dev = get_default_device()
    assert dev.StopTrace() is None          # nothing started: clean None
    d1 = str(tmp_path / "t1")
    dev.StartTrace(d1)
    assert dev.StopTrace() == d1
    assert dev.StopTrace() is None          # second stop: clean None
    # profiler stopped under us (process-global): StopTrace still must
    # not raise, and must reset its flag so StartTrace works again
    d2 = str(tmp_path / "t2")
    dev.StartTrace(d2)
    jax.profiler.stop_trace()
    assert dev.StopTrace() == d2
    assert dev.StopTrace() is None
    d3 = str(tmp_path / "t3")
    dev.StartTrace(d3)                       # not wedged
    assert dev.StopTrace() == d3


# ---- xprof satellites ------------------------------------------------------

def test_xprof_tolerates_truncated_files(tmp_path):
    from singa_tpu import xprof
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "empty.xplane.pb").write_bytes(b"")
    # field 1, length-delimited, claims 100 bytes but only 3 follow
    (d / "torn.xplane.pb").write_bytes(b"\x0a\x64abc")
    # truncated mid-varint
    (d / "midvarint.xplane.pb").write_bytes(b"\x0a\xff")
    assert xprof.parse_xspace(str(d / "empty.xplane.pb")) == []
    assert xprof.op_table(str(tmp_path)) == []  # empty table, no raise
    assert xprof.hlo_category_table(str(tmp_path)) == []


def test_xprof_surfaces_spans(tmp_path, reg):
    import jax
    import jax.numpy as jnp
    from singa_tpu import xprof
    d = str(tmp_path)
    f = jax.jit(lambda x: (x @ x.T).sum())
    x = jnp.ones((128, 128), jnp.float32)
    f(x).block_until_ready()  # compile outside the capture
    jax.profiler.start_trace(d)
    with observe.span("obs.spanregion", step=1):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    rows = xprof.op_table(d)
    spans = [r for r in rows if r["category"] == "span"]
    assert any("obs.spanregion" in r["op"] for r in spans), \
        [r["op"] for r in rows][:20]
    st = xprof.span_table(d)
    assert any(r["op"] == "obs.spanregion" for r in st)
    assert all(r["total_ms"] > 0 for r in st)
    # the same span also landed in the live histogram: one name keys both
    assert reg.get("singa_span_seconds").count(span="obs.spanregion") == 1
    # span envelopes do not pollute the device-op accounting: device pct
    # still sums to ~100 on its own, span rows come after, and
    # category_table drops them (they wrap the same device time)
    devrows = [r for r in rows if r["category"] != "span"]
    assert abs(sum(r["pct"] for r in devrows) - 100.0) < 1e-6
    assert rows[:len(devrows)] == devrows
    assert not any(c["category"] == "span"
                   for c in xprof.category_table(rows))
