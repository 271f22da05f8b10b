"""Live diagnostics server (singa_tpu.diag): every endpoint served on an
ephemeral port inside tier-1 — golden /statusz sections, /metrics
exposing every goodput bucket and parsing as Prometheus text, /flightz
round-tripping a flight bundle, /healthz verdicts, /profilez capture,
and the no-leak lifecycle (idempotent stop; conftest teardown)."""

import json
import re
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from singa_tpu import (diag, goodput, health, layer, model, observe, opt,
                       tensor)
from singa_tpu.goodput import GOODPUT_BUCKETS
from singa_tpu.health import HealthMonitor, load_flight_bundle


class MLP(model.Model):
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


@pytest.fixture
def served(dev, rng, tmp_path):
    """A 3-step trained model with a HealthMonitor and a dumped flight
    bundle, behind a running diag server on an ephemeral port."""
    X = rng.randn(32, 10).astype(np.float32)
    Y = rng.randint(0, 4, 32).astype(np.int32)
    m = MLP()
    m.set_optimizer(opt.SGD(lr=0.1))
    tx, ty = tensor.from_numpy(X, dev), tensor.from_numpy(Y, dev)
    mon = HealthMonitor(out_dir=str(tmp_path))
    m.compile([tx], is_train=True, use_graph=True, health=mon)
    srv = observe.start_diag_server(port=0, model=m, device=dev)
    for _ in range(3):
        m(tx, ty)
    mon.recorder.dump(reason="manual", step=3)
    yield srv, m, tx, ty, mon
    diag.stop_diag_server()


def _get(srv, path, timeout=60.0):
    try:
        r = urllib.request.urlopen(srv.url + path, timeout=timeout)
        return r.status, r.headers, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.headers, e.read().decode()


_SAMPLE_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [^ ]+$")


def test_server_binds_ephemeral_port_and_is_singleton(served):
    srv = served[0]
    assert srv.port > 0
    assert srv.url.endswith(str(srv.port))
    # second start returns the running instance, no second port
    assert observe.start_diag_server(port=0) is srv
    assert diag.get_diag_server() is srv


def test_index_and_404(served):
    srv = served[0]
    st, _h, body = _get(srv, "/")
    assert st == 200 and "/statusz" in body
    st, _h, body = _get(srv, "/definitely_not_an_endpoint")
    assert st == 404


def test_metrics_endpoint(served):
    srv = served[0]
    st, headers, body = _get(srv, "/metrics")
    assert st == 200
    assert headers["Content-Type"].startswith("text/plain")
    # every enum bucket is exposed (acceptance criterion)
    for b in GOODPUT_BUCKETS:
        assert f'singa_time_seconds_total{{bucket="{b}"}}' in body, b
    # the run's own telemetry rode along and every line parses
    assert "singa_steps_total 3" in body
    for line in body.splitlines():
        if line.startswith("#"):
            continue
        assert _SAMPLE_RE.match(line), line
    # scraping flushed the residual: buckets sum tracks the run clock
    vals = {b: float(re.search(
        rf'singa_time_seconds_total{{bucket="{b}"}} ([^ \n]+)', body)
        .group(1)) for b in GOODPUT_BUCKETS}
    snap = goodput.get_tracker().snapshot()
    assert abs(sum(vals.values()) - snap["wall_s"]) \
        <= 0.1 * snap["wall_s"] + 0.05


def test_statusz_golden_sections(served):
    srv = served[0]
    st, _h, body = _get(srv, "/statusz")
    assert st == 200
    assert "== singa_tpu /statusz ==" in body
    # explain report (introspect): the compiled step + blame history
    assert "compile & memory explain" in body
    assert "step executable" in body
    assert "recompile history" in body
    # goodput breakdown with every bucket row
    assert "== goodput ==" in body
    for b in GOODPUT_BUCKETS:
        assert b in body
    # the 3-step run was productive: a nonzero step line
    m = re.search(r"step\s+([0-9.]+) s", body)
    assert m and float(m.group(1)) > 0.0, body
    # ISSUE-5: the overlap section (prefetch ring + async-ckpt state)
    assert "== overlap ==" in body
    assert "async-ckpt: pending=0" in body
    # ISSUE-6: the resilience section (controller + recovery counters)
    assert "== resilience ==" in body
    assert "saves=" in body and "restarts=" in body
    # ISSUE-10: the watchdog section (deadline table; not installed in
    # this fixture, so the pointer line is the golden content)
    assert "== watchdog ==" in body
    assert "not installed" in body
    # ISSUE-11: the serving section (no engine in this fixture, so the
    # pointer line is the golden content; the live-engine body is
    # covered in tests/test_engine.py)
    assert "== serving ==" in body
    assert "no ServingEngine running" in body
    assert "== health ==" in body


def test_statusz_watchdog_section_when_installed(served):
    from singa_tpu import watchdog
    srv = served[0]
    watchdog.install_watchdog(deadlines={"step": 0.75})
    try:
        st, _h, body = _get(srv, "/statusz")
        assert st == 200
        assert "== watchdog ==" in body
        assert "action=abort" in body
        assert "0.750(static)" in body
        assert "fleet_publish" in body      # every DEADLINE_OPS row
    finally:
        watchdog.uninstall_watchdog()


def test_stackz_dumps_all_threads(served):
    """ISSUE-10: /stackz serves the all-thread stack capture — thread
    names + daemon flags + frames — live, the same capture the hang
    bundle embeds."""
    srv = served[0]
    st, _h, body = _get(srv, "/stackz")
    assert st == 200
    assert "== threads ==" in body
    assert "MainThread" in body              # the test runner's thread
    assert "daemon" in body                  # the server's own threads
    # the capture names real frames: the server's serve loop is parked
    # somewhere in the stdlib's socketserver/selectors machinery
    assert " in " in body and ".py:" in body


def test_stackz_json_form(served):
    srv = served[0]
    st, _h, body = _get(srv, "/stackz?json=1")
    assert st == 200
    stacks = json.loads(body)
    assert isinstance(stacks, list) and stacks
    names = {s["name"] for s in stacks}
    assert "MainThread" in names
    me = next(s for s in stacks if s["name"] == "MainThread")
    assert me["daemon"] is False
    assert me["frames"] and all(
        {"file", "line", "func"} <= set(f) for f in me["frames"])
    # the main thread is parked in this very test's HTTP wait: the
    # capture must name a real calling frame, proving the wedged-frame
    # forensics a hang bundle depends on
    funcs = {f["func"] for f in me["frames"]}
    assert "test_stackz_json_form" in funcs


def test_healthz_verdict(served):
    srv, _m, _tx, _ty, mon = served
    st, _h, body = _get(srv, "/healthz")
    assert st == 200
    v = json.loads(body)
    assert v["status"] == "ok"          # 3 healthy steps
    assert v["policy"] == "warn"
    assert v["healthy_steps"] == 3
    assert v["last_step"]["step"] == 3


def test_healthz_unmonitored():
    srv = observe.start_diag_server(port=0)
    try:
        st, _h, body = _get(srv, "/healthz")
        assert st == 200
        assert json.loads(body)["status"] == "unmonitored"
    finally:
        diag.stop_diag_server()


def test_flightz_roundtrips_a_bundle(served, tmp_path):
    srv = served[0]
    st, _h, body = _get(srv, "/flightz")
    assert st == 200
    idx = json.loads(body)
    assert idx["bundles"] == ["flight_step3.jsonl"]
    st, headers, body = _get(srv, "/flightz?name=flight_step3.jsonl")
    assert st == 200
    assert headers["Content-Type"].startswith("application/x-ndjson")
    fetched = tmp_path / "fetched.jsonl"
    fetched.write_text(body)
    b = load_flight_bundle(str(fetched))
    assert b["header"]["reason"] == "manual"
    assert b["header"]["step"] == 3
    assert len(b["steps"]) == 3  # the ring carried all three steps


def test_flightz_rejects_bad_names(served):
    srv = served[0]
    st, _h, _b = _get(srv, "/flightz?name=../../etc/passwd")
    assert st == 400
    st, _h, _b = _get(srv, "/flightz?name=flight_step99.jsonl")
    assert st == 404


def test_profilez_capture(served):
    """On-demand xplane capture: steps already satisfied -> immediate
    stop; the response carries the trace dir + parsed top ops. (The
    first jax.profiler.start_trace in a process is slow — one-time
    init — hence the generous client timeout.)"""
    srv = served[0]
    st, _h, body = _get(srv, "/profilez?steps=0&seconds=0.2", timeout=120)
    assert st == 200
    rep = json.loads(body)
    assert rep["trace_dir"]
    assert rep["steps_requested"] == 0
    assert rep["steps_captured"] >= 0
    assert rep["truncated"] is False
    assert isinstance(rep["top_ops"], list)


def test_profilez_flags_truncation(served):
    """The seconds cap expiring before N steps pass must be visible in
    the response (operators are told to check it): the trace
    covers a shorter window than requested."""
    srv = served[0]
    # nobody is stepping: 5 requested steps can never arrive in 0.2s
    st, _h, body = _get(srv, "/profilez?steps=5&seconds=0.2", timeout=120)
    assert st == 200
    rep = json.loads(body)
    assert rep["steps_requested"] == 5
    assert rep["steps_captured"] < 5
    assert rep["truncated"] is True


def test_profilez_rejects_bad_params(served):
    srv = served[0]
    st, _h, _b = _get(srv, "/profilez?steps=abc")
    assert st == 400
    st, _h, _b = _get(srv, "/profilez?steps=0&seconds=soon")
    assert st == 400


def test_profilez_counts_steps(served):
    """?steps=N returns once N more train steps have been observed."""
    srv, m, tx, ty, _mon = served
    import threading

    def stepper():
        time.sleep(0.1)
        for _ in range(2):
            m(tx, ty)

    t = threading.Thread(target=stepper)
    t.start()
    try:
        st, _h, body = _get(srv, "/profilez?steps=2&seconds=30",
                            timeout=120)
    finally:
        t.join()
    assert st == 200
    assert json.loads(body)["steps_captured"] >= 2


def test_start_enriches_running_server_context():
    """A library can start the server early (no model); the training
    script's later start_diag_server(model=...) applies the context to
    the running instance instead of silently dropping it."""
    srv = observe.start_diag_server(port=0)
    try:
        assert srv.model is None
        sentinel_model, sentinel_dev = object(), object()
        again = observe.start_diag_server(port=0, model=sentinel_model,
                                          device=sentinel_dev,
                                          flight_dir="/tmp/flights")
        assert again is srv
        assert srv.model is sentinel_model
        assert srv.device is sentinel_dev
        assert srv.flight_dir == "/tmp/flights"
        # a context-free re-start does not wipe the enrichment
        observe.start_diag_server(port=0)
        assert srv.model is sentinel_model
    finally:
        diag.stop_diag_server()


def test_profilez_contended_cleans_up_trace_dir(served):
    """The 409 path (another capture owns the profiler) must not leave
    an orphan singa_profilez_* temp dir per polled request."""
    import glob
    import os
    import tempfile

    class BusyDevice:
        def StartTrace(self, d):
            raise RuntimeError("profiler already capturing")

    srv = served[0]
    srv.device = BusyDevice()
    pattern = os.path.join(tempfile.gettempdir(), "singa_profilez_*")
    before = set(glob.glob(pattern))
    st, _h, body = _get(srv, "/profilez?steps=0&seconds=0.1")
    assert st == 409
    assert "profiler already capturing" in json.loads(body)["error"]
    assert set(glob.glob(pattern)) == before


def test_profilez_retains_bounded_trace_dirs(served):
    """Repeated captures must not grow tmp without bound: only the
    newest _MAX_TRACE_DIRS capture dirs survive, older ones are
    deleted."""
    import os

    srv = served[0]
    dirs = []
    for _ in range(diag._MAX_TRACE_DIRS + 2):
        st, _h, body = _get(srv, "/profilez?steps=0&seconds=0.1",
                            timeout=120)
        assert st == 200
        dirs.append(json.loads(body)["trace_dir"])
    kept = dirs[-diag._MAX_TRACE_DIRS:]
    for d in dirs:
        assert os.path.isdir(d) == (d in kept)


def test_profilez_capture_aborts_on_server_stop():
    """A long ?seconds= capture holds the process-global profiler from a
    daemon handler thread that shutdown never joins — stopping the
    server must abort the poll loop and release the profiler."""
    import threading

    class StubDev:
        def __init__(self):
            self.stopped = False

        def StartTrace(self, d):
            pass

        def StopTrace(self):
            self.stopped = True

    stub = StubDev()
    srv = observe.start_diag_server(port=0, device=stub)
    res = {}

    def req():
        res["st"] = _get(srv, "/profilez?steps=999999&seconds=9999",
                         timeout=30)[0]

    t = threading.Thread(target=req, daemon=True)
    t.start()
    time.sleep(0.3)  # the capture loop is polling singa_steps_total
    assert not stub.stopped
    diag.stop_diag_server()
    t.join(timeout=10)
    assert not t.is_alive()
    assert stub.stopped  # profiler released, not held for 9999s


def test_stop_is_idempotent_and_restartable():
    srv = observe.start_diag_server(port=0)
    port1 = srv.port
    diag.stop_diag_server()
    diag.stop_diag_server()  # second stop: no-op
    assert diag.get_diag_server() is None
    srv2 = observe.start_diag_server(port=0)
    try:
        st, _h, _b = _get(srv2, "/metrics")
        assert st == 200
        assert (srv2.port, port1) != (0, 0)
    finally:
        diag.stop_diag_server()


def test_start_installs_goodput_tracker():
    assert goodput.get_tracker() is None  # conftest isolation
    srv = observe.start_diag_server(port=0)
    try:
        assert goodput.get_tracker() is not None
        st, _h, body = _get(srv, "/statusz")
        assert "== goodput ==" in body
    finally:
        diag.stop_diag_server()


def test_memz_without_ledger_is_503():
    srv = observe.start_diag_server(port=0)
    try:
        st, _h, body = _get(srv, "/memz")
        assert st == 503
        assert "no MemoryLedger installed" in body
    finally:
        diag.stop_diag_server()


def test_memz_serves_breakdown_live_mid_run(served):
    """Acceptance: /memz serves the live region breakdown mid-run —
    golden sections in the text view, reconciled totals and the
    timeline in the JSON view, the static introspect HBM estimate
    side-by-side, and the index advertising the endpoint."""
    from singa_tpu import memory
    from singa_tpu.memory import MEM_REGIONS
    srv, m, tx, ty, _mon = served
    memory.install_ledger()
    for _ in range(2):
        m(tx, ty)
    st, _h, body = _get(srv, "/memz")
    assert st == 200
    assert "== memory ==" in body
    for region in MEM_REGIONS:
        assert region in body, region
    assert "reconciliation" in body and "(OK)" in body
    assert "static estimate" in body          # the introspect view...
    assert "estimate-vs-actual" in body       # ...and the drift line
    assert "leak: slope" in body
    assert "timeline (newest last):" in body
    st, _h, body = _get(srv, "/memz?json=1")
    assert st == 200
    rep = json.loads(body)
    assert rep["installed"] is True
    assert sum(rep["regions"].values()) == rep["total_bytes"]
    assert rep["regions"]["params"] > 0       # the live params attribute
    assert len(rep["timeline"]) >= 2          # breakdown evolved mid-run
    assert rep["top_arrays"] and rep["static_hbm"]
    _st, _h, idx = _get(srv, "/")
    assert "/memz" in idx


def test_slo_without_tracker_is_503():
    srv = observe.start_diag_server(port=0)
    try:
        st, _h, body = _get(srv, "/slo")
        assert st == 503
        assert "no SLOTracker installed" in body
        st, _h, body = _get(srv, "/slo?json=1")
        assert st == 503
        assert json.loads(body) == {"installed": False}
    finally:
        diag.stop_diag_server()


def test_slo_endpoint_golden_sections():
    """ISSUE-12: /slo serves the declared objectives, per-objective
    attainment + burn rates, breach state, and the recent violating
    request ids WITH their timelines; ?json=1 is the structured form;
    /statusz grows the `== slo ==` section and the index advertises
    the endpoint."""
    from singa_tpu import slo
    from singa_tpu.slo import SLOConfig, SLOTracker
    cfg = SLOConfig(ttft_p99_s=0.1, availability=0.9,
                    eval_interval_s=1e9)
    tracker = SLOTracker(cfg, clock=lambda: 100.0).install()
    # one good, one violating record — with a synthetic timeline so
    # the violation renders its phase trail
    tracker.note_record({"ts": 99.0, "id": 1, "outcome": "completed",
                         "ttft_s": 0.01, "total_s": 0.2,
                         "tokens_per_sec": 40.0})
    tracker.note_record(
        {"ts": 99.5, "id": 2, "outcome": "completed", "ttft_s": 0.5,
         "total_s": 0.9, "tokens_per_sec": 10.0},
        timeline={"id": 2, "outcome": "completed", "new_tokens": 9,
                  "events": [["submit", 98.0, None],
                             ["queue", 98.001, None],
                             ["admit", 98.4, None],
                             ["terminal", 98.9,
                              {"outcome": "completed"}]]})
    srv = observe.start_diag_server(port=0)
    try:
        st, _h, body = _get(srv, "/slo")
        assert st == 200
        assert "== slo ==" in body
        assert "objectives: ttft_p99, availability" in body
        assert "ttft_p99" in body and "availability" in body
        assert "attainment 50.00%" in body       # 1 of 2 met the TTFT
        assert "burn" in body and "window requests: 2" in body
        assert "recent violations (1):" in body
        assert "req 2 [ttft_p99]" in body
        # the violating request's timeline trail renders inline
        assert "submit+0.000s" in body and "admit+0.400s" in body
        st, _h, body = _get(srv, "/slo?json=1")
        assert st == 200
        rep = json.loads(body)
        assert rep["installed"] is True
        assert rep["config"]["ttft_p99_s"] == 0.1
        assert rep["verdict"]["objectives"]["ttft_p99"]["attainment"] \
            == 0.5
        assert rep["violations"][0]["id"] == 2
        assert rep["violations"][0]["timeline"]["events"][0][0] \
            == "submit"
        st, _h, body = _get(srv, "/statusz")
        assert "== slo ==" in body
        _st, _h, idx = _get(srv, "/")
        assert "/slo" in idx
    finally:
        diag.stop_diag_server()
        slo.reset()


def test_capacityz_without_scaler_is_503():
    srv = observe.start_diag_server(port=0)
    try:
        st, _h, body = _get(srv, "/capacityz")
        assert st == 503
        assert "no ShadowScaler installed" in body
        st, _h, body = _get(srv, "/capacityz?json=1")
        assert st == 503
        assert json.loads(body) == {"installed": False}
    finally:
        diag.stop_diag_server()


def test_capacityz_golden_sections():
    """ISSUE-17: /capacityz serves the fleet headroom line, the
    per-replica table whose columns RECONCILE against the fleet-shard
    serving signals it derives from (slots = occupancy/slots, pages =
    page_util, headroom = 1 - the binding wall), the demand forecast,
    the decision tail with enum reason codes, and the counterfactual
    scorecard; ?json=1 is the structured form; /statusz grows the
    `== capacity ==` section and the index advertises the endpoint."""
    from singa_tpu import capacity
    # a scripted 2-replica fleet: r00 slot-bound at 75%, r01
    # page-bound at 60% — known signals the table must reconcile with
    serves = [
        {"slots": 4, "occupancy": 3, "page_util": 0.25,
         "queue_depth": 0, "ttft_p99_s": None, "decode_tok_s": None,
         "rps": 3.0},
        {"slots": 4, "occupancy": 1, "page_util": 0.6,
         "queue_depth": 0, "ttft_p99_s": None, "decode_tok_s": None,
         "rps": 1.2},
    ]

    def sample():
        return {"workers": [{"host": f"r{i:02d}", "serve": s,
                             "stale": False}
                            for i, s in enumerate(serves)],
                "admitted_rps": 4.2, "burn_fast": 0.0,
                "burn_slow": 0.0, "breaching": [], "shed_rate": 0.0}

    clock = iter(float(i) for i in range(100))
    s = capacity.ShadowScaler(sample=sample, interval_s=0.0,
                              clock=lambda: next(clock))
    s.install(poll=False)
    srv = observe.start_diag_server(port=0)
    try:
        for _ in range(3):
            s.evaluate()
        st, _h, body = _get(srv, "/capacityz")
        assert st == 200
        assert "== capacity ==" in body
        assert "fleet: 2 replica(s)" in body
        # the headroom figures reconcile against the shard signals:
        # r00's wall is slots at 3/4 (headroom 25%), r01's is pages at
        # 60% (headroom 40%); the fleet line carries the binding
        # replica's headroom and the summed sustainable rate
        # (3/.75 + 1.2/.6 = 6 rps)
        assert "headroom 25%" in body
        assert "sustainable 6.00 rps" in body
        r00 = next(ln for ln in body.splitlines()
                   if ln.startswith("r00"))
        assert "75%" in r00 and "slots" in r00 and "25%" in r00
        r01 = next(ln for ln in body.splitlines()
                   if ln.startswith("r01"))
        assert "60%" in r01 and "pages" in r01 and "40%" in r01
        assert "demand: fast" in body
        assert "steady" in body          # the decision tail
        assert "shadow accuracy:" in body
        st, _h, body = _get(srv, "/capacityz?json=1")
        assert st == 200
        rep = json.loads(body)
        assert rep["installed"] is True
        assert rep["snapshot"]["assessment"]["headroom_frac"] == 0.25
        assert rep["snapshot"]["assessment"]["replicas"][0]["wall"] \
            == "slots"
        assert rep["snapshot"]["assessment"]["replicas"][1]["wall"] \
            == "pages"
        assert len(rep["decisions"]) == 3
        assert all(r["reason"] in capacity.DECISION_REASONS
                   for r in rep["decisions"])
        st, _h, body = _get(srv, "/statusz")
        assert "== capacity ==" in body
        _st, _h, idx = _get(srv, "/")
        assert "/capacityz" in idx
    finally:
        diag.stop_diag_server()
        capacity.reset()


def test_statusz_serving_spec_lines(served):
    """ISSUE-13: the == serving == section renders the spec lines with
    the explicit no-data convention — 'spec: off' on a draftless
    engine, 'spec acceptance: no data' on a fresh spec engine, and the
    acceptance + draft-overhead lines once verify rounds ran."""
    from singa_tpu import device, models, tensor as stensor
    from singa_tpu import engine as eng
    srv = served[0]
    dev = device.best_device()
    m = models.create_model("gpt", vocab_size=61, max_seq=48, dim=32,
                            num_heads=2, num_layers=1)
    ids = stensor.from_numpy(
        np.random.RandomState(0).randint(0, 61, (1, 6))
        .astype(np.int32), device=dev)
    m.compile([ids], is_train=False, use_graph=False)
    m.eval()
    e = eng.ServingEngine(m, max_slots=1, page_size=8,
                          max_ctx=48).start()
    try:
        _st, _h, body = _get(srv, "/statusz")
        assert "== serving ==" in body
        assert "spec: off (no draft model)" in body
    finally:
        e.stop()
    d = models.create_model("gpt", vocab_size=61, max_seq=48, dim=32,
                            num_heads=2, num_layers=1)
    d.compile([ids], is_train=False, use_graph=False)
    d.eval()
    e = eng.ServingEngine(m, max_slots=1, page_size=8, max_ctx=48,
                          draft_model=d, spec_k=2).start()
    try:
        _st, _h, body = _get(srv, "/statusz")
        assert "spec acceptance: no data (0 verify rounds, k=2)" in body
        r = e.submit(np.arange(5, dtype=np.int32), 6)
        assert r.wait(300) and r.outcome == "completed"
        _st, _h, body = _get(srv, "/statusz")
        assert "spec acceptance " in body
        assert "spec draft overhead: params" in body
    finally:
        e.stop()


# ---- the serving control plane on the diag surface (ISSUE-15) --------------

def _stub_routed_router():
    """An installed router with one live stub replica and one finished
    request — enough state for every golden router row."""
    import threading

    from singa_tpu import router as rt

    class _Req:
        outcome, detail, ttft_s = "completed", None, 0.001
        tokens = [1, 2]

        def wait(self, timeout=None):
            return True

    class _Eng:
        def submit(self, prompt, max_new):
            return _Req()

        def stop(self, *a, **k):
            return []

    ctl = rt.ReplicaControl(_Eng())
    r = rt.Router(queue_limit=8, retry_total_s=10.0,
                  poll_wait_s=0.3).start()
    r.add_replica("ra", ctl.url, host="ra")
    h = r.submit(np.array([1, 2], np.int32), 2)
    assert h.wait(30) and h.outcome == "completed"
    return r, ctl


def test_routerz_golden_sections(served):
    """/routerz: 503 + guidance without a router; with one installed,
    the replica table carries state/inflight/dispatched/completed plus
    the shed/failover/retry counter line."""
    from singa_tpu import router as rt
    srv = served[0]
    status, _, body = _get(srv, "/routerz")
    assert status == 503
    assert "no Router installed" in body
    r, ctl = _stub_routed_router()
    try:
        status, _, body = _get(srv, "/routerz")
        assert status == 200
        assert "== router ==" in body
        assert re.search(r"queue 0/8\s+completed 1\s+rejected 0", body)
        assert "failover(replica_dead) 0" in body
        assert "failover(drain) 0" in body
        assert "retry_exhausted 0" in body
        assert re.search(r"ra\s+live\s+0\s+1\s+1", body)
        assert "uncalibrated" in body   # no shard intervals yet
    finally:
        r.stop()
        rt.reset()
        ctl.stop()


def test_statusz_serving_carries_router_rows(served):
    """The `== serving ==` section shows the router's control-plane
    rows (replica states + routed counts) even in a process with no
    local ServingEngine — the coordinator case."""
    from singa_tpu import router as rt
    srv = served[0]
    r, ctl = _stub_routed_router()
    try:
        status, _, body = _get(srv, "/statusz")
        assert status == 200
        assert "== serving ==" in body
        assert "router: replicas 1 live / 0 draining / 0 dead" in body
        assert "routed: completed 1, rejected 0 (shed 0" in body
        assert "replica ra: live" in body
        # the no-engine hint yields to the router rows
        assert "no ServingEngine running" not in body
    finally:
        r.stop()
        rt.reset()
        ctl.stop()


def test_fleetz_carries_router_section(served, tmp_path):
    """/fleetz appends the `== router ==` block after the fleet tables
    when a router is installed alongside the aggregator."""
    from singa_tpu import fleet
    from singa_tpu import router as rt
    srv = served[0]
    fleet.install_aggregator(str(tmp_path / "spool"))
    r, ctl = _stub_routed_router()
    try:
        status, _, body = _get(srv, "/fleetz")
        assert status == 200
        assert "== fleet ==" in body
        assert "== router ==" in body
        assert re.search(r"ra\s+live", body)
        # control plane renders after the data plane
        assert body.index("== router ==") > body.index("== fleet ==")
    finally:
        r.stop()
        rt.reset()
        ctl.stop()
        fleet.uninstall()


def test_tailz_golden_sections():
    """ISSUE-16: /tailz is 503 until any terminal request has been
    attributed; with records it ranks buckets by p99 CONTRIBUTION and
    names the top one; ?json=1 is the structured form (summary + a
    bounded record tail); the index advertises the endpoint."""
    from singa_tpu import slo
    srv = observe.start_diag_server(port=0)
    try:
        st, _h, body = _get(srv, "/tailz")
        assert st == 503
        assert "no attributed requests yet" in body
        st, _h, body = _get(srv, "/tailz?json=1")
        assert st == 503
        assert json.loads(body)["installed"] is False
        for i in range(4):
            slo.note_attribution(
                {"id": i, "outcome": "completed", "total_s": 0.1,
                 "attr": {"decode": 0.09, "prefill": 0.01}})
        slo.note_attribution(
            {"id": 9, "outcome": "completed", "trace": "tdead-9",
             "total_s": 1.0,
             "attr": {"decode": 0.09, "failover_replay": 0.91}})
        st, _h, body = _get(srv, "/tailz")
        assert st == 200
        assert "== tailz ==" in body
        assert "requests: 5" in body
        assert "top p99 contributor: failover_replay" in body
        assert "decode" in body and "% of wall" in body
        st, _h, body = _get(srv, "/tailz?json=1")
        assert st == 200
        rep = json.loads(body)
        assert rep["installed"] is True
        assert rep["summary"]["top"] == "failover_replay"
        assert rep["summary"]["buckets"]["decode"]["requests"] == 5
        assert rep["records"][-1]["trace"] == "tdead-9"
        _st, _h, idx = _get(srv, "/")
        assert "/tailz" in idx
    finally:
        diag.stop_diag_server()
        slo.tail_reset()


def test_routerz_json_form(served):
    """ISSUE-16 satellite: /routerz?json=1 serves the snapshot plus
    the terminal request timelines (trace id, hop marks, attribution)
    — and stays a 503 {"installed": false} without a router."""
    from singa_tpu import router as rt
    from singa_tpu import slo
    srv = served[0]
    status, _, body = _get(srv, "/routerz?json=1")
    assert status == 503
    assert json.loads(body) == {"installed": False}
    r, ctl = _stub_routed_router()
    try:
        status, _, body = _get(srv, "/routerz?json=1")
        assert status == 200
        rep = json.loads(body)
        assert rep["installed"] is True
        assert rep["snapshot"]["terminal"]["completed"] == 1
        tl = rep["requests"][0]
        assert tl["trace"] and tl["outcome"] == "completed"
        assert tl["attr"] and tl["total_s"] > 0
        # the text form now carries the recent-request tail too
        status, _, body = _get(srv, "/routerz")
        assert "recent requests:" in body
        assert f"[{tl['trace']}]" in body
    finally:
        r.stop()
        rt.reset()
        ctl.stop()
        slo.tail_reset()
