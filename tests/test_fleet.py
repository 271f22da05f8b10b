"""Fleet observability layer (ISSUE-7): shard writer round-trip, the
aggregator's merge/staleness/straggler verdicts, the merged Perfetto
trace, the /fleetz endpoints, and the multi-process straggler A/B —
the fault-injected slow worker must be detected within K steps and
attributed to the correct host."""

import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import jax.numpy as jnp  # noqa: E402

from singa_tpu import (diag, fleet, health, observe,  # noqa: E402
                       resilience)
from singa_tpu.parallel.communicator import Communicator  # noqa: E402


@pytest.fixture(autouse=True)
def _fleet_hygiene():
    yield
    resilience.clear_fault_plan()
    fleet.uninstall()


def _write_fake_shard(fleet_dir, host, pid, seq=1, ts=None, perf=0.0,
                      spans=(), steps=0, metrics=None, goodput=None,
                      name=None, mem=None, serve=None, capacity=None):
    """Hand-build one shard file in the documented format — the unit
    tests' stand-in for another process's ShardWriter (the writer end
    is covered by the round-trip test and the subprocess A/B)."""
    os.makedirs(fleet_dir, exist_ok=True)
    header = {"kind": "fleet_shard_header", "version": 1, "seq": seq,
              "host": host, "pid": pid,
              "ts": time.time() if ts is None else ts, "perf": perf,
              "started_ts": 0.0, "steps": steps}
    lines = [header,
             {"kind": "fleet_metrics", "metrics": metrics or {}},
             {"kind": "fleet_goodput", "goodput": goodput},
             {"kind": "fleet_health", "verdict": None},
             {"kind": "fleet_mem", "mem": mem},
             {"kind": "fleet_serve", "serve": serve},
             {"kind": "fleet_capacity", "capacity": capacity}]
    for nm, t0, dur, tid, kind in spans:
        lines.append({"kind": "fleet_span", "name": nm, "t0": t0,
                      "dur": dur, "tid": tid, "span_kind": kind})
    path = os.path.join(fleet_dir, (name or f"worker_{pid}")
                        + fleet.SHARD_SUFFIX)
    with open(path, "w", encoding="utf-8") as f:
        for rec in lines:
            f.write(json.dumps(rec) + "\n")
    return path


def _step_spans(dur, n=6, t0=100.0):
    return [("model.step", t0 + i, dur, 1, "span") for i in range(n)]


# ---- shard writer ----------------------------------------------------------

def test_shard_writer_publish_roundtrip(tmp_path):
    w = fleet.ShardWriter(str(tmp_path), interval_s=0, host="hostA",
                          name="worker_a")
    comm = Communicator()
    for _ in range(3):
        with observe.span("model.step"):
            comm.all_reduce(jnp.ones(()))
        observe.record_step(0.001)
    seq1 = w.publish()
    shard = fleet.read_shard(w.path)
    assert shard is not None
    h = shard["header"]
    assert h["seq"] == seq1 == 1 and h["host"] == "hostA"
    assert h["steps"] == 3
    # the clock handshake: paired epoch + monotonic samples
    assert h["ts"] > 0 and h["perf"] > 0
    kinds = {s["span_kind"] for s in shard["spans"]}
    assert kinds == {"span", "comm"}
    step_spans = [s for s in shard["spans"]
                  if s["name"].rsplit("/", 1)[-1] == "model.step"]
    assert len(step_spans) == 3
    assert "singa_steps_total" in shard["metrics"]
    # monotonic sequence + atomicity: a publish replaces, never appends
    assert w.publish() == 2
    assert fleet.read_shard(w.path)["header"]["seq"] == 2
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]
    w.close(final_publish=False)


def test_shard_writer_thread_publishes_and_uninstall_joins(tmp_path):
    w = fleet.start_shard_writer(str(tmp_path), interval_s=0.02,
                                 host="hostA")
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        shard = fleet.read_shard(w.path)
        if shard is not None and shard["header"]["seq"] >= 2:
            break
        time.sleep(0.01)
    assert fleet.read_shard(w.path)["header"]["seq"] >= 2
    assert any(t.name.startswith("singa-fleet-shard")
               for t in threading.enumerate())
    fleet.uninstall()
    assert not any(t.name.startswith("singa-fleet-shard")
                   for t in threading.enumerate() if t.is_alive())
    assert fleet.get_shard_writer() is None
    assert not observe.span_records_enabled()


def test_owned_temp_spool_dir_removed_on_uninstall():
    w = fleet.ShardWriter(None, interval_s=0)  # module-owned temp dir
    d = w.fleet_dir
    assert os.path.isdir(d)
    w.publish()
    fleet.uninstall()
    assert not os.path.exists(d)


# ---- merging ---------------------------------------------------------------

def test_merge_metric_snapshots_counters_histograms_gauges():
    def snap(ctr, gval, hcount, hsum):
        return {
            "singa_steps_total": {"type": "counter", "help": "",
                                  "samples": [{"labels": {},
                                               "value": ctr}]},
            "singa_hbm_bytes_in_use": {"type": "gauge", "help": "",
                                       "samples": [{"labels": {},
                                                    "value": gval}]},
            "singa_step_seconds": {"type": "histogram", "help": "",
                                   "samples": [{"labels": {},
                                                "count": hcount,
                                                "sum": hsum,
                                                "buckets": {"1": hcount,
                                                            "+Inf":
                                                                hcount}}]},
        }

    merged = fleet.merge_metric_snapshots(
        {"host0": snap(10, 100.0, 4, 0.4),
         "host1": snap(32, 300.0, 6, 1.2)})
    ctr = merged["singa_steps_total"]["series"][()]
    assert ctr["value"] == 42.0
    g = merged["singa_hbm_bytes_in_use"]["series"][()]
    assert g["per_host"] == {"host0": 100.0, "host1": 300.0}
    assert g["min"] == 100.0 and g["max"] == 300.0 and g["mean"] == 200.0
    h = merged["singa_step_seconds"]["series"][()]
    assert h["count"] == 10 and abs(h["sum"] - 1.6) < 1e-9
    assert h["buckets"]["+Inf"] == 10 and h["buckets"]["1"] == 10


# ---- straggler detection ---------------------------------------------------

def test_straggler_scored_against_fleet_median_and_attributed(tmp_path):
    d = str(tmp_path)
    _write_fake_shard(d, "host0", 100, spans=_step_spans(0.005), steps=6)
    _write_fake_shard(d, "host1", 101, spans=_step_spans(0.005), steps=6)
    _write_fake_shard(d, "host2", 102, spans=_step_spans(0.060), steps=6)
    agg = fleet.FleetAggregator(d, threshold=0.5)
    agg.poll()
    scores = agg.straggler_scores()
    assert set(scores) == {"host0", "host1", "host2"}
    # the slow host — and ONLY the slow host — scores above threshold
    assert scores["host2"] > 0.5
    assert scores["host0"] <= 0.5 and scores["host1"] <= 0.5
    # exported as singa_fleet_straggler_score{host=...}
    g = observe.get_registry().get("singa_fleet_straggler_score")
    assert g is not None and g.value(host="host2") > 0.5
    assert g.value(host="host0") <= 0.5


def test_straggler_scores_on_collective_signal_too(tmp_path):
    d = str(tmp_path)
    comm = [("comm.all_reduce", 100.0 + i, 0.001, 1, "comm")
            for i in range(6)]
    slow = [("comm.all_reduce", 100.0 + i, 0.055, 1, "comm")
            for i in range(6)]
    _write_fake_shard(d, "host0", 100, spans=comm)
    _write_fake_shard(d, "host1", 101, spans=slow)
    agg = fleet.FleetAggregator(d, threshold=0.5)
    agg.poll()
    scores = agg.straggler_scores()
    assert scores["host1"] > 0.5 and scores["host0"] <= 0.5


def test_sustained_straggler_warn_feeds_health_monitor(tmp_path):
    d = str(tmp_path)
    _write_fake_shard(d, "host0", 100, spans=_step_spans(0.005))
    _write_fake_shard(d, "host1", 101, spans=_step_spans(0.080))
    mon = health.HealthMonitor(policy="warn", out_dir=str(tmp_path))
    health.set_active_monitor(mon)
    agg = fleet.FleetAggregator(d, threshold=0.5, sustain=3)
    agg.poll()
    agg.poll()
    c = observe.get_registry().get("singa_health_anomaly_total")
    assert c is None or c.value(kind=health.KIND_STRAGGLER) == 0
    agg.poll()  # third consecutive poll above threshold -> sustained
    c = observe.get_registry().get("singa_health_anomaly_total")
    assert c.value(kind=health.KIND_STRAGGLER) == 1
    assert mon.last_action == "warn"
    assert agg.halt_verdict() is None  # warn policy: no halt
    sus = observe.get_registry().get(
        "singa_fleet_straggler_sustained_total")
    assert sus.value(host="host1") == 1
    # the verdict is attributed in the rollup too
    assert agg.rollup()["stragglers"] == ["host1"]


def test_sustained_straggler_halt_raises_from_training_hook(tmp_path):
    d = str(tmp_path)
    _write_fake_shard(d, "host0", 100, spans=_step_spans(0.005))
    _write_fake_shard(d, "host1", 101, spans=_step_spans(0.080))
    agg = fleet.FleetAggregator(d, threshold=0.5, sustain=1,
                                policy="halt", poll_interval_s=0.0)
    fleet.install_aggregator(aggregator=agg)
    with pytest.raises(fleet.FleetStragglerError) as ei:
        fleet.check_straggler_halt(step=4)
    assert ei.value.hosts == ("host1",)
    assert isinstance(ei.value, health.HealthError)
    assert "host1" in str(ei.value)


def test_restarted_worker_with_reset_seq_is_accepted(tmp_path):
    """Review fix: a relaunched worker reusing the shard path starts
    seq over at 1 — the aggregator must reset its state and accept the
    new incarnation, not ignore it until seq catches up."""
    d = str(tmp_path)
    _write_fake_shard(d, "host0", 100, seq=40, steps=40,
                      spans=_step_spans(0.005))
    agg = fleet.FleetAggregator(d)
    agg.poll()
    assert agg.workers()[0].seq == 40
    # the restart: same path, seq back to 1, fresh (slow) spans
    _write_fake_shard(d, "host0", 100, seq=1, steps=2,
                      spans=_step_spans(0.050))
    roll = agg.poll()
    w = agg.workers()[0]
    assert w.seq == 1 and w.steps == 2
    assert roll["workers"][0]["steps"] == 2


def test_removed_shard_file_prunes_ghost_worker(tmp_path):
    """Review fix: a shard file deleted from the spool (relaunch
    cleanup) must drop its worker from tracking instead of inflating
    counts and staleness forever."""
    d = str(tmp_path)
    p0 = _write_fake_shard(d, "host0", 100, spans=_step_spans(0.005))
    _write_fake_shard(d, "host1", 101, spans=_step_spans(0.005))
    agg = fleet.FleetAggregator(d)
    assert agg.poll()["n_workers"] == 2
    os.remove(p0)
    roll = agg.poll()
    assert roll["n_workers"] == 1
    assert [r["host"] for r in roll["workers"]] == ["host1"]


def test_host_collision_freshest_shard_owns_signal(tmp_path):
    """Review fix: a dead incarnation's lingering shard sharing a host
    label with its relaunch must not override the live signal — the
    newest publish wins regardless of scan order."""
    d = str(tmp_path)
    now = time.time()
    # "worker_99" sorts AFTER "worker_100": the stale-slow file is
    # scanned last but must not own host0's score
    _write_fake_shard(d, "host0", 100, ts=now,
                      spans=_step_spans(0.005), name="worker_100")
    _write_fake_shard(d, "host0", 99, ts=now - 120.0,
                      spans=_step_spans(0.200), name="worker_99")
    _write_fake_shard(d, "host1", 101, ts=now,
                      spans=_step_spans(0.005), name="worker_101")
    agg = fleet.FleetAggregator(d, threshold=0.5)
    agg.poll()
    scores = agg.straggler_scores()
    assert scores["host0"] <= 0.5, scores  # live (fast) shard won


def test_aggregator_policy_overrides_monitor_in_note_external(tmp_path):
    """Review fix: FleetAggregator(policy="warn") with an active
    HealthMonitor(policy="halt") — the sustained verdict must NOT flip
    the monitor (and /healthz) to halt: the resolved action is passed
    through note_external."""
    d = str(tmp_path)
    _write_fake_shard(d, "host0", 100, spans=_step_spans(0.005))
    _write_fake_shard(d, "host1", 101, spans=_step_spans(0.080))
    mon = health.HealthMonitor(policy="halt", out_dir=str(tmp_path))
    health.set_active_monitor(mon)
    agg = fleet.FleetAggregator(d, threshold=0.5, sustain=1,
                                policy="warn")
    agg.poll()
    assert mon.last_action == "warn"  # not "halt"
    c = observe.get_registry().get("singa_health_halt_total")
    assert c is None or c.value() == 0
    assert agg.halt_verdict() is None
    # anomaly still counted under its kind
    a = observe.get_registry().get("singa_health_anomaly_total")
    assert a.value(kind=health.KIND_STRAGGLER) == 1


def test_background_polling_thread_lifecycle(tmp_path):
    """Review fix: background_poll=True moves the spool rescans off the
    caller's thread; check_straggler_halt then only reads the sticky
    verdict, and uninstall joins the thread."""
    d = str(tmp_path)
    _write_fake_shard(d, "host0", 100, spans=_step_spans(0.005))
    _write_fake_shard(d, "host1", 101, spans=_step_spans(0.080))
    agg = fleet.FleetAggregator(d, threshold=0.5, sustain=1,
                                policy="halt", poll_interval_s=0.02,
                                background_poll=True)
    fleet.install_aggregator(aggregator=agg)
    assert any(t.name == "singa-fleet-agg" for t in threading.enumerate())
    deadline = time.monotonic() + 5.0
    while agg.halt_verdict() is None and time.monotonic() < deadline:
        time.sleep(0.01)
    with pytest.raises(fleet.FleetStragglerError):
        fleet.check_straggler_halt()
    fleet.uninstall()
    assert not any(t.name == "singa-fleet-agg"
                   for t in threading.enumerate() if t.is_alive())


def test_staleness_flags_dead_or_wedged_worker(tmp_path):
    d = str(tmp_path)
    _write_fake_shard(d, "host0", 100, ts=time.time())
    _write_fake_shard(d, "host1", 101, ts=time.time() - 60.0)  # wedged
    agg = fleet.FleetAggregator(d, stale_after_s=5.0)
    roll = agg.poll()
    assert roll["n_workers"] == 2 and roll["n_stale"] == 1
    by_host = {r["host"]: r for r in roll["workers"]}
    assert by_host["host1"]["stale"] and not by_host["host0"]["stale"]
    g = observe.get_registry().get("singa_fleet_shard_age_seconds")
    assert g.value(host="host1") > 5.0


# ---- merged trace ----------------------------------------------------------

def test_trace_export_schema_and_clock_alignment(tmp_path):
    d = str(tmp_path)
    # two workers observing the SAME wall-clock moment from different
    # monotonic clock bases: the handshake (ts, perf) must align them
    wall = 1_700_000_000.0
    _write_fake_shard(d, "host0", 100, ts=wall, perf=100.0,
                      spans=[("model.step", 101.0, 0.01, 7, "span")])
    _write_fake_shard(d, "host1", 101, ts=wall, perf=50.0,
                      spans=[("model.step", 51.0, 0.01, 8, "span"),
                             ("comm.all_reduce", 51.002, 0.05, 8,
                              "comm")])
    agg = fleet.FleetAggregator(d)
    agg.poll()
    out = str(tmp_path / "trace.json")
    fleet.install_aggregator(aggregator=agg)
    assert fleet.export_trace(out) == out
    with open(out, encoding="utf-8") as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    assert isinstance(events, list) and events
    names = [e for e in events if e.get("ph") == "M"
             and e.get("name") == "process_name"]
    assert len(names) == 2  # one track per worker
    assert {n["args"]["name"].split(" ")[0] for n in names} \
        == {"host0", "host1"}
    xs = [e for e in events if e.get("ph") == "X"]
    assert all(isinstance(e["name"], str) and "ts" in e and "dur" in e
               and "pid" in e and "tid" in e for e in xs)
    # both model.step slices started 1s after the handshake sample on
    # their OWN clocks -> identical aligned wall timestamps
    steps = [e for e in xs if e["name"] == "model.step"]
    assert len(steps) == 2
    assert abs(steps[0]["ts"] - steps[1]["ts"]) < 1.0  # us
    assert abs(steps[0]["ts"] - (wall + 1.0) * 1e6) < 1.0
    comm = [e for e in xs if e["cat"] == "comm"]
    assert comm and comm[0]["dur"] == pytest.approx(50_000.0)


# ---- /fleetz endpoints -----------------------------------------------------

def _get(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return r.status, r.read().decode("utf-8")


def test_fleetz_endpoints(tmp_path):
    d = str(tmp_path)
    _write_fake_shard(d, "host0", 100, spans=_step_spans(0.005), steps=9)
    _write_fake_shard(d, "host1", 101, spans=_step_spans(0.070), steps=4)
    agg = fleet.FleetAggregator(d, threshold=0.5, sustain=1)
    agg.poll()
    fleet.install_aggregator(aggregator=agg)
    srv = observe.start_diag_server(port=0)
    try:
        status, text = _get(srv.url + "/fleetz")
        assert status == 200
        assert "host0" in text and "host1" in text
        assert "STRAGGLER" in text  # host1 sustained after poll #1+#2
        assert "straggler" in text  # the score column header
        status, body = _get(srv.url + "/fleetz/trace")
        assert status == 200
        trace = json.loads(body)
        assert len([e for e in trace["traceEvents"]
                    if e.get("ph") == "M"
                    and e.get("name") == "process_name"]) == 2
        # the index page advertises the new endpoints
        _status, idx = _get(srv.url + "/")
        assert "/fleetz" in idx and "/fleetz/trace" in idx
    finally:
        diag.stop_diag_server()


def test_fleetz_without_aggregator_is_503(tmp_path):
    srv = observe.start_diag_server(port=0)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(srv.url + "/fleetz")
        assert ei.value.code == 503
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(srv.url + "/fleetz/trace")
        assert ei.value.code == 503
    finally:
        diag.stop_diag_server()


# ---- collective stamps + fault hook ----------------------------------------

def test_comm_stamp_records_and_fault_hook():
    observe.enable_span_records()
    plan = resilience.FaultPlan()
    plan.delay("comm.collective", 0.03, times=1)
    resilience.install_fault_plan(plan)
    comm = Communicator()  # world_size 1: identity, but stamped
    t0 = time.perf_counter()
    comm.all_reduce(jnp.ones(()))
    assert time.perf_counter() - t0 >= 0.03  # the injected delay landed
    assert plan.fired and plan.fired[0][0] == "comm.collective"
    h = observe.get_registry().get("singa_comm_host_seconds")
    assert h is not None and h.count(op="all_reduce") == 1
    assert h.sum(op="all_reduce") >= 0.03  # delay INSIDE the stamp
    recs = [r for r in observe.span_records() if r["kind"] == "comm"]
    assert recs and recs[-1]["name"] == "comm.all_reduce"
    assert recs[-1]["dur"] >= 0.03


# ---- controller integration ------------------------------------------------

def test_controller_surfaces_straggler_halt_with_exclude_hosts(tmp_path):
    from singa_tpu import layer, model as model_mod, opt, tensor
    from singa_tpu.device import get_default_device
    import numpy as np

    class Net(model_mod.Model):
        def __init__(self):
            super().__init__()
            self.fc = layer.Linear(4)
            self.sce = layer.SoftMaxCrossEntropy()

        def forward(self, x):
            return self.fc(x)

        def train_one_batch(self, x, y):
            loss = self.sce(self.forward(x), y)
            self.optimizer(loss)
            return loss

    dev = get_default_device()
    rng = np.random.RandomState(0)
    tx = tensor.from_numpy(rng.randn(8, 6).astype(np.float32), dev)
    ty = tensor.from_numpy(rng.randint(0, 4, 8).astype(np.int32), dev)
    m = Net()
    m.set_optimizer(opt.SGD(lr=0.1))
    m.compile([tx], is_train=True, use_graph=True)

    spool = tmp_path / "spool"
    _write_fake_shard(str(spool), "host0", 100,
                      spans=_step_spans(0.005))
    _write_fake_shard(str(spool), "hostS", 101,
                      spans=_step_spans(0.080))
    agg = fleet.FleetAggregator(str(spool), threshold=0.5, sustain=1,
                                policy="halt", poll_interval_s=0.0)
    fleet.install_aggregator(aggregator=agg)

    ctrl = resilience.TrainController(
        m, str(tmp_path / "ck"), save_every_steps=2,
        handle_signals=False)
    with pytest.raises(fleet.FleetStragglerError) as ei:
        ctrl.fit([(tx, ty)] * 6, epochs=1)
    rep = ei.value.resilience
    # the elastic-restart contract: the report names the host to exclude
    assert rep["exclude_hosts"] == ["hostS"]
    # the halt rode the HealthError save-then-stop path: a final
    # checkpoint exists and its manifest records the halt
    latest = resilience.latest_checkpoint(str(tmp_path / "ck"))
    assert latest is not None
    assert latest[1]["status"] == "halt"
    from singa_tpu import overlap
    overlap.wait_for_checkpoints()


# ---- the multi-process A/B -------------------------------------------------

def test_multiprocess_straggler_ab_detects_and_attributes(tmp_path):
    """ISSUE-7 acceptance (lean leg): MULTICHIP-style subprocess workers
    with a 50 ms FaultPlan delay on ONE worker's collectives; the
    coordinator must see that host's straggler score above threshold
    within 5 steps (others below), list every host on /fleetz, and
    export a schema-valid merged trace with one track per worker and
    the injected gap visible on the slow track."""
    out = str(tmp_path / "FLEET_test.json")
    rc = fleet.main(["--ab", "--synthetic", "--workers", "2",
                     "--steps", "6", "--step-sleep", "0.02",
                     "--delay", "0.05", "--timeout", "300",
                     "--out", out])
    with open(out, encoding="utf-8") as f:
        rec = json.load(f)
    assert rc == 0, rec
    assert rec["ok"] is True
    assert rec["detected"] is True
    assert rec["steps_at_detection"] <= 5
    assert rec["slow_host"] == "host1"
    assert rec["scores_at_detection"]["host1"] > rec["threshold"]
    assert rec["scores_at_detection"]["host0"] <= rec["threshold"]
    assert rec["fleetz_lists_all_hosts"] is True
    assert rec["trace_schema_ok"] is True
    assert rec["trace_tracks"] == 2
    assert rec["slow_gap_ms"] >= 40.0  # the injected 50 ms, visible


@pytest.mark.slow
def test_multiprocess_fleet_ab_full_model(tmp_path):
    """The full A/B (real tiny models on per-worker meshes), the leg
    that produces the committed FLEET_r01.json artifact."""
    out = str(tmp_path / "FLEET_full.json")
    rc = fleet.main(["--ab", "--workers", "3", "--steps", "10",
                     "--step-sleep", "0.03", "--delay", "0.05",
                     "--timeout", "500", "--out", out])
    with open(out, encoding="utf-8") as f:
        rec = json.load(f)
    assert rc == 0, rec
    assert rec["ok"] is True and rec["trace_tracks"] == 3


# ---- per-host memory (ISSUE-9) ---------------------------------------------

def test_shard_carries_memory_and_worst_hbm_host(tmp_path):
    """Shards carry the worker's memory-ledger region snapshot; the
    aggregator grows a per-host memory column and flags the worst-HBM
    host in the rollup, /fleetz and the singa_fleet_mem_bytes gauge."""
    from singa_tpu import memory
    d = str(tmp_path)
    # writer side: a real ledger snapshot rides the shard
    memory.install_ledger()
    pin = jnp.ones((256,), jnp.float32)  # something definitely live
    memory.get_ledger().snapshot()
    w = fleet.ShardWriter(d, interval_s=0, host="hostA", name="worker_a")
    try:
        w.publish()
        shard = fleet.read_shard(w.path)
        assert shard["mem"] is not None
        assert shard["mem"]["total_bytes"] >= pin.nbytes > 0
        assert set(shard["mem"]["regions"]) == set(memory.MEM_REGIONS)
    finally:
        w.close(final_publish=False)
    # aggregator side: a fatter fake host must win the worst-HBM flag
    _write_fake_shard(d, "hostB", 200, steps=5,
                      mem={"regions": {"params": 10 ** 9},
                           "total_bytes": 10 ** 9, "n_arrays": 3,
                           "step": 5})
    agg = fleet.FleetAggregator(d)
    roll = agg.poll()
    by_host = {r["host"]: r for r in roll["workers"]}
    assert by_host["hostB"]["mem_bytes"] == 10 ** 9
    assert by_host["hostA"]["mem_bytes"] > 0
    assert by_host["hostB"]["mem_regions"]["params"] == 10 ** 9
    assert roll["worst_mem_host"] == "hostB"
    assert roll["worst_mem_bytes"] == 10 ** 9
    g = observe.get_registry().get("singa_fleet_mem_bytes")
    assert g.value(host="hostB") == 10 ** 9
    fleet.install_aggregator(aggregator=agg)
    rep = fleet.fleet_report()
    assert "mem_mb" in rep                      # the new column
    assert "worst-HBM host: hostB (1000.0 MB)" in rep


def test_shard_without_ledger_and_report_without_mem(tmp_path):
    """No ledger installed: the shard's mem record is None, the rollup
    column is None, and /fleetz says so instead of inventing a worst
    host."""
    d = str(tmp_path)
    w = fleet.ShardWriter(d, interval_s=0, host="hostA", name="worker_a")
    try:
        w.publish()
        assert fleet.read_shard(w.path)["mem"] is None
    finally:
        w.close(final_publish=False)
    agg = fleet.FleetAggregator(d)
    roll = agg.poll()
    assert roll["workers"][0]["mem_bytes"] is None
    assert roll["worst_mem_host"] is None
    fleet.install_aggregator(aggregator=agg)
    assert "worst-HBM host: none (no memory shards)" \
        in fleet.fleet_report()


def _fake_serve(rps=3.5, att=0.75, breaching=("ttft_p99",),
                timelines=None, syncs=None):
    """A fleet_serve snapshot in the documented shape (the writer end
    — slo.fleet_serve_snapshot over a live engine — is covered in
    tests/test_slo.py)."""
    return {
        "engines": 1, "rps": rps, "queue_depth": 2, "occupancy": 3,
        "slots": 4, "pages_in_use": 6, "pages_total": 16,
        "page_util": 0.375, "kv_cache_bytes": 2_000_000,
        "ttft_p50_s": 0.012, "ttft_p99_s": 0.090,
        "finished": {"completed": 7, "evicted": 0, "rejected": 0,
                     "timeout": 1},
        "slo": {"objectives": {"ttft_p99": {"attainment": att,
                                            "burn_fast": 5.0,
                                            "burn_slow": 3.0,
                                            "breach": bool(breaching)}},
                "breaching": list(breaching), "window_requests": 8},
        "timelines": timelines or [],
        "syncs": syncs or [],
    }


def test_shard_carries_serve_and_fleetz_serving_columns(tmp_path):
    """ISSUE-12: the fleet_serve line rides shards into the rollup's
    per-replica serving view (RPS, queue, occupancy, page util, TTFT,
    kv-cache bytes, SLO attainment), /fleetz grows the serving table,
    and the per-host gauges export."""
    d = str(tmp_path)
    _write_fake_shard(d, "hostA", 100, steps=5, serve=_fake_serve())
    _write_fake_shard(d, "hostB", 101, steps=5)  # training-only worker
    agg = fleet.FleetAggregator(d)
    roll = agg.poll()
    by_host = {r["host"]: r for r in roll["workers"]}
    s = by_host["hostA"]["serve"]
    assert s["rps"] == 3.5 and s["queue_depth"] == 2
    assert s["occupancy"] == 3 and s["slots"] == 4
    assert s["page_util"] == 0.375
    assert s["kv_cache_bytes"] == 2_000_000
    assert s["ttft_p99_s"] == 0.090
    assert s["slo_attainment_pct"] == 75.0
    assert s["slo_breaching"] == ["ttft_p99"]
    assert by_host["hostB"]["serve"] is None
    g = observe.get_registry().get("singa_fleet_serve_rps")
    assert g.value(host="hostA") == 3.5
    g = observe.get_registry().get("singa_fleet_slo_attainment_pct")
    assert g.value(host="hostA") == 75.0
    fleet.install_aggregator(aggregator=agg)
    rep = fleet.fleet_report()
    assert "== fleet serving ==" in rep
    for col in ("rps", "queue", "occ", "pages", "ttft_p50_ms",
                "ttft_p99_ms", "kv_mb", "slo_pct", "breaching"):
        assert col in rep, col
    srv_line = next(ln for ln in rep.splitlines()
                    if ln.startswith("hostA") and "3.50" in ln)
    assert "3/4" in srv_line           # occupancy
    assert "38%" in srv_line           # page utilization
    assert "2.00" in srv_line          # kv MB
    assert "75.0" in srv_line          # slo attainment pct
    assert "ttft_p99" in srv_line      # breaching objective
    # a fleet with no serving workers renders no serving table
    _write_fake_shard(d, "hostA", 100, seq=2, serve=None)
    _write_fake_shard(d, "hostB", 101, seq=2)
    assert "== fleet serving ==" not in fleet.fleet_report()


def test_shard_carries_capacity_and_fleetz_headroom_column(tmp_path):
    """ISSUE-17: the fleet_capacity shard line (this replica's own
    headroom row, derived from the same serve signals its fleet_serve
    line publishes) rides into the rollup, and /fleetz's serving table
    grows the headroom column naming each replica's binding wall."""
    d = str(tmp_path)
    cap = {"headroom_frac": 0.25, "wall": "slots", "wall_util": 0.75,
           "sustainable_rps": 4.667, "source": "measured",
           "utils": {"slots": 0.75, "pages": 0.375, "queue": 0.5,
                     "ttft": None, "bandwidth": None},
           "rps": 3.5, "polls": 9, "decision": "hold",
           "reason": "steady", "demand_rps": 3.1,
           "accuracy": {"scored": 4, "tp": 1, "fp": 0, "fn": 0,
                        "tn": 3, "precision": 1.0, "recall": 1.0}}
    _write_fake_shard(d, "hostA", 100, steps=5, serve=_fake_serve(),
                      capacity=cap)
    _write_fake_shard(d, "hostB", 101, steps=5,
                      serve=_fake_serve(rps=1.0, breaching=()))
    agg = fleet.FleetAggregator(d)
    roll = agg.poll()
    by_host = {r["host"]: r for r in roll["workers"]}
    assert by_host["hostA"]["capacity"]["headroom_frac"] == 0.25
    assert by_host["hostA"]["capacity"]["wall"] == "slots"
    assert by_host["hostB"]["capacity"] is None
    fleet.install_aggregator(aggregator=agg)
    rep = fleet.fleet_report()
    assert "headroom" in rep
    line = next(ln for ln in rep.splitlines()
                if ln.startswith("hostA") and "3.50" in ln)
    assert "25%(slots)" in line
    # a worker without the line renders the explicit no-data dash
    line_b = next(ln for ln in rep.splitlines()
                  if ln.startswith("hostB") and "1.00" in ln)
    assert " - " in line_b
    # read_shard round-trips the line verbatim
    shard = fleet.read_shard(by_host["hostA"]["path"]) \
        if "path" in by_host["hostA"] else None
    if shard is not None:
        assert shard["capacity"] == cap


def test_merged_trace_carries_request_flows_clock_aligned(tmp_path):
    """The merged trace shows requests flowing through workers: one
    worker's serve timelines/syncs become queued/prefill/decode spans
    + engine_step slices + flow events, aligned onto the shared wall
    clock via the SAME handshake offset as its ordinary spans."""
    d = str(tmp_path)
    wall = 1_700_000_000.0
    tl = {"id": 42, "outcome": "completed", "prompt_tokens": 5,
          "new_tokens": 4, "slot": 1, "ttft_s": 0.4, "total_s": 0.9,
          "tokens_per_sec": 4.4,
          "events": [["submit", 100.0, None], ["queue", 100.001, None],
                     ["admit", 100.2, None], ["prefill", 100.21, None],
                     ["first_token", 100.4, None],
                     ["decode", 100.6, {"tokens": 2, "sync": 9}],
                     ["decode", 100.8, {"tokens": 4, "sync": 10}],
                     ["terminal", 100.9, {"outcome": "completed"}]],
          "syncs": [9, 10]}
    syncs = [{"sync": 9, "t0": 100.5, "dur": 0.2, "tid": 77,
              "slots": 1, "steps": 2, "tokens": 2},
             {"sync": 10, "t0": 100.75, "dur": 0.1, "tid": 77,
              "slots": 1, "steps": 2, "tokens": 2}]
    _write_fake_shard(d, "hostA", 100, ts=wall, perf=100.0,
                      spans=[("model.step", 101.0, 0.01, 7, "span")],
                      serve=_fake_serve(timelines=[tl], syncs=syncs))
    agg = fleet.FleetAggregator(d)
    agg.poll()
    trace = agg.trace_events()
    events = trace["traceEvents"]
    xs = [e for e in events if e.get("ph") == "X"]
    assert all("ts" in e and "dur" in e and "tid" in e for e in xs)
    # the request spans, offset-aligned: submit was 0.0s after the
    # handshake sample on the worker's clock -> ts == wall
    queued = next(e for e in xs if e["name"] == "req 42 queued")
    assert queued["ts"] == pytest.approx(wall * 1e6, abs=1.0)
    assert queued["dur"] == pytest.approx(0.2 * 1e6, abs=1.0)
    decode = next(e for e in xs if e["name"] == "req 42 decode")
    assert decode["tid"] == 900_101  # slot 1's track
    steps = [e for e in xs if e["name"] == "serving.engine_step"]
    assert len(steps) == 2 and all(e["tid"] == 77 for e in steps)
    from singa_tpu import slo
    flows = [e for e in events if e.get("cat") == "req_flow"
             and e.get("id") == slo.flow_event_id(100, 42)]
    assert [e["ph"] for e in flows] == ["s", "t", "f"]
    for ev in flows[1:]:  # each step lands INSIDE an engine_step slice
        assert any(s["tid"] == ev["tid"]
                   and s["ts"] <= ev["ts"] <= s["ts"] + s["dur"]
                   for s in steps), ev
    # the ordinary span slices still align (regression: same offset)
    step_span = next(e for e in xs if e["name"] == "model.step")
    assert step_span["ts"] == pytest.approx((wall + 1.0) * 1e6,
                                            abs=1.0)


def test_merged_trace_dedupes_engine_step_slices(tmp_path):
    """Review fix (ISSUE-12): when a worker's span ring already
    published serving.engine_step slices, the serve sync ring must not
    overlay near-identical duplicates on the same tid — the flow
    events bind inside the REAL span slices instead."""
    from singa_tpu import slo
    d = str(tmp_path)
    wall = 1_700_000_000.0
    tl = {"id": 7, "outcome": "completed", "prompt_tokens": 3,
          "new_tokens": 2, "slot": 0, "ttft_s": 0.1, "total_s": 0.3,
          "tokens_per_sec": 6.7,
          "events": [["submit", 100.0, None], ["queue", 100.001, None],
                     ["admit", 100.05, None],
                     ["prefill", 100.06, None],
                     ["first_token", 100.1, None],
                     ["decode", 100.3, {"tokens": 2, "sync": 5}],
                     ["terminal", 100.3, {"outcome": "completed"}]],
          "syncs": [5]}
    sync = {"sync": 5, "t0": 100.15, "dur": 0.15, "tid": 77,
            "slots": 1, "steps": 2, "tokens": 2}
    # the span ring carries the REAL engine_step slice, nested just
    # inside the sync interval on the same thread
    _write_fake_shard(
        d, "hostA", 100, ts=wall, perf=100.0,
        spans=[("serving.engine_step", 100.1501, 0.1498, 77, "span")],
        serve=_fake_serve(timelines=[tl], syncs=[sync]))
    agg = fleet.FleetAggregator(d)
    agg.poll()
    events = agg.trace_events()["traceEvents"]
    steps = [e for e in events if e.get("ph") == "X"
             and e.get("name") == "serving.engine_step"]
    assert len(steps) == 1            # the span slice, no sync overlay
    assert steps[0]["args"].get("path") is not None  # span-ring origin
    flows = [e for e in events if e.get("cat") == "req_flow"
             and e.get("id") == slo.flow_event_id(100, 7)]
    assert [e["ph"] for e in flows] == ["s", "f"]
    f = flows[-1]  # still binds inside the real span slice
    s = steps[0]
    assert s["tid"] == f["tid"] == 77
    assert s["ts"] <= f["ts"] <= s["ts"] + s["dur"]


def test_merged_trace_links_router_and_replicas_via_trace_ctx(
        tmp_path):
    """ISSUE-16: the merged trace carries ONE track per process (a
    single process_name per pid, the router's sorted on top), every
    req_flow id stays pid-scoped (two replicas serving request id 1
    never cross-link), and a router-minted trace id stitches ONE
    trace_ctx flow across processes — the router's s/f endpoints
    bracketing a binding step on EACH replica the request touched
    (the failover shape: victim's in-flight partial + winner), while
    a second traced request keeps its own flow to its own replica."""
    import numpy as np
    from singa_tpu import router as rt
    from singa_tpu import slo
    from tests.test_router import _StubEngine, _mk_router
    d = str(tmp_path)
    ctls = [rt.ReplicaControl(_StubEngine()) for _ in range(2)]
    r = _mk_router()
    for i, c in enumerate(ctls):
        r.add_replica(f"s{i}", c.url, host=f"s{i}")
    try:
        h1 = r.submit(np.array([3, 1], np.int32), 2)
        h2 = r.submit(np.array([5], np.int32), 2)
        assert h1.wait(30) and h2.wait(30)
        off = time.time() - time.perf_counter()
        q1 = next(t for e, t, _i in h1.events if e == "dispatch")
        w1 = ((q1 + off) + (h1.finished_ts + off)) / 2.0
        q2 = next(t for e, t, _i in h2.events if e == "dispatch")
        w2 = ((q2 + off) + (h2.finished_ts + off)) / 2.0

        def _tl(rid, trace, terminal=True, at=100.0):
            evs = [["submit", at, None], ["admit", at + 0.0001, None],
                   ["first_token", at + 0.0003, None]]
            if terminal:
                evs.append(["terminal", at + 0.0004,
                            {"outcome": "completed"}])
            return {"id": rid, "trace": trace, "slot": 0,
                    "outcome": "completed" if terminal else None,
                    "prompt_tokens": 2, "new_tokens": 2,
                    "ttft_s": 0.0003, "total_s": 0.0004,
                    "events": evs, "syncs": []}

        # victim replica: request 1 in flight (no terminal) when the
        # shard was last published; winner replica: request 1 replayed
        # to completion PLUS request 2 — note both processes reuse
        # LOCAL request id 1
        victim = _fake_serve(timelines=[], syncs=[])
        victim["active"] = [_tl(1, h1.trace, terminal=False)]
        _write_fake_shard(d, "hostA", 100, ts=w1 - 100.0, perf=0.0,
                          serve=victim)
        # (each request's steps sit inside ITS router window: the shard
        # clock maps 100.0 to w2, so request 1 is shifted by w1 - w2 —
        # under load h1 may finish before h2 is even dispatched)
        winner = _fake_serve(
            timelines=[_tl(1, h1.trace, at=100.0 + (w1 - w2)),
                       _tl(2, h2.trace)], syncs=[])
        _write_fake_shard(d, "hostB", 101, ts=w2 - 100.0, perf=0.0,
                          serve=winner)
        agg = fleet.FleetAggregator(d)
        agg.poll()
        events = agg.trace_events()["traceEvents"]
        # one track per process: a single process_name per pid, and
        # the router's synthetic process present and sorted on top
        pnames = [e for e in events if e.get("ph") == "M"
                  and e["name"] == "process_name"]
        by_pid = {}
        for e in pnames:
            by_pid.setdefault(e["pid"], []).append(e)
        assert all(len(v) == 1 for v in by_pid.values()), by_pid
        assert set(by_pid) >= {100, 101, os.getpid()}
        assert by_pid[os.getpid()][0]["args"]["name"] == \
            f"router (pid {os.getpid()})"
        # req_flow ids stay pid-scoped: replica 100's request 1 and
        # replica 101's request 1 can never join arrows
        for e in events:
            if e.get("cat") == "req_flow":
                assert e["id"].startswith(f"{e['pid']}:"), e
        # the failover request's trace_ctx flow: s and f on the router,
        # a binding step on BOTH replicas, strictly ordered s < t < f
        ctx = [e for e in events if e.get("cat") == slo.TRACE_CTX_CAT
               and e["id"] == h1.trace]
        s = [e for e in ctx if e["ph"] == "s"]
        t = [e for e in ctx if e["ph"] == "t"]
        f = [e for e in ctx if e["ph"] == "f"]
        assert len(s) == 1 and len(f) == 1
        assert s[0]["pid"] == os.getpid() == f[0]["pid"]
        assert f[0]["bp"] == "e"
        assert {e["pid"] for e in t} == {100, 101}
        for e in t:
            assert s[0]["ts"] < e["ts"] < f[0]["ts"], (s, e, f)
        # the clean request's flow touches ONLY its own replica
        ctx2 = [e for e in events if e.get("cat") == slo.TRACE_CTX_CAT
                and e["id"] == h2.trace]
        assert {e["pid"] for e in ctx2 if e["ph"] == "t"} == {101}
        assert {e["pid"] for e in ctx2 if e["ph"] in ("s", "f")} == \
            {os.getpid()}
    finally:
        r.stop()
        rt.reset()
        for c in ctls:
            c.stop()
        slo.tail_reset()
