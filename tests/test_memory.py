"""HBM memory observatory (singa_tpu.memory, ISSUE-9): the live
device-memory ledger over jax.live_arrays() — region attribution via
the birth-site hooks, the test-enforced reconciliation property (region
sums equal the live byte total at every snapshot, compile_count stays
1), the injected-leak A/B, OOM forensics round-tripped through
health.load_flight_bundle (incl. a subprocess leg), the pre-flight fit
estimator, and the record_hbm CPU fallback regression."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from singa_tpu import (health, introspect, layer, memory, model, observe,
                       opt, overlap, tensor)
from singa_tpu.health import HealthMonitor, load_flight_bundle
from singa_tpu.memory import MEM_REGIONS

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class MLP(model.Model):
    def __init__(self, hidden=16):
        super().__init__()
        self.l1 = layer.Linear(hidden)
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


def _build(dev, rng, batch=32, feat=10, momentum=0.9, health_mon=None):
    X = rng.randn(batch, feat).astype(np.float32)
    Y = rng.randint(0, 4, batch).astype(np.int32)
    m = MLP()
    m.set_optimizer(opt.SGD(lr=0.1, momentum=momentum))
    tx, ty = tensor.from_numpy(X, dev), tensor.from_numpy(Y, dev)
    m.compile([tx], is_train=True, use_graph=True, health=health_mon)
    return m, tx, ty


def _oom_error():
    """A resource-exhausted XlaRuntimeError: the real jaxlib class when
    it is constructible from Python, else a structural stand-in (the
    detector matches on mro name + message, not identity)."""
    msg = "RESOURCE_EXHAUSTED: Out of memory allocating 1234 bytes"
    try:
        from jaxlib.xla_extension import XlaRuntimeError
        try:
            return XlaRuntimeError(msg)
        except Exception:
            pass
    except ImportError:
        pass
    return type("XlaRuntimeError", (RuntimeError,), {})(msg)


# ---- reconciliation (acceptance criterion) ---------------------------------

def test_regions_reconcile_at_every_snapshot(dev, rng):
    m, tx, ty = _build(dev, rng)
    led = memory.install_ledger()
    for _ in range(4):
        m(tx, ty)
    assert len(led.timeline) == 4
    for snap in led.timeline:
        assert set(snap["regions"]) == set(MEM_REGIONS)
        assert sum(snap["regions"].values()) == snap["total_bytes"]
        assert sum(snap["counts"].values()) == snap["n_arrays"]
    # a fresh snapshot against a direct enumeration: identical
    snap = led.snapshot()
    direct = sum(int(a.nbytes) for a in jax.live_arrays())
    assert snap["total_bytes"] == direct
    assert sum(snap["regions"].values()) == direct


def test_params_and_opt_state_attribution(dev, rng):
    m, tx, ty = _build(dev, rng)
    led = memory.install_ledger()
    for _ in range(2):
        m(tx, ty)
    snap = led.timeline[-1]
    params_b = sum(int(t.data.nbytes) for t in m.get_params().values())
    opt_b = sum(int(a.nbytes) for a in m.optimizer.state_arrays())
    assert snap["regions"]["params"] == params_b > 0
    assert snap["regions"]["opt_state"] == opt_b > 0


def test_compile_count_stays_one_with_ledger(dev, rng):
    """Ledger snapshots are host-side bookkeeping: installing it must
    not retrace the step (acceptance criterion)."""
    m, tx, ty = _build(dev, rng)
    memory.install_ledger()
    for _ in range(3):
        m(tx, ty)
    c = observe.get_registry().get("singa_model_compile_total")
    assert sum(v for _n, _k, v in c.samples()) == 1
    r = observe.get_registry().get("singa_model_recompile_total")
    assert r is None or sum(v for _n, _k, v in r.samples()) == 0


def test_gauges_exported_for_every_region(dev, rng):
    m, tx, ty = _build(dev, rng)
    memory.install_ledger()
    m(tx, ty)
    text = observe.to_prometheus_text()
    for region in MEM_REGIONS:
        assert f'singa_mem_region_bytes{{region="{region}"}}' in text
    assert "singa_mem_total_bytes" in text
    assert "singa_mem_live_arrays" in text
    assert "singa_mem_snapshots_total 1" in text


# ---- the other birth sites -------------------------------------------------

def test_prefetch_ring_attribution(dev, rng):
    m, tx, ty = _build(dev, rng)
    led = memory.install_ledger()
    batches = [(tx, ty)] * 4
    p = overlap.DevicePrefetcher(iter(batches), model=m, size=2)
    try:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if led.snapshot()["regions"]["prefetch_ring"] > 0:
                break
            time.sleep(0.01)
        assert led.timeline[-1]["regions"]["prefetch_ring"] > 0
    finally:
        p.close()
    # close() untracks the ring: nothing attributes there any more
    assert led.snapshot()["regions"]["prefetch_ring"] == 0


def test_note_arrays_transient_attribution_dies_with_the_buffer():
    memory.install_ledger()
    led = memory.get_ledger()
    arrs = [jnp.zeros((4, 64), jnp.float32)]
    nb = int(arrs[0].nbytes)
    assert memory.note_arrays("kv_cache", arrs) == 1
    assert led.snapshot()["regions"]["kv_cache"] == nb
    del arrs
    # the weakref died with the buffer: no stale (or id-reused) entry
    assert led.snapshot()["regions"]["kv_cache"] == 0


def test_serving_decode_attributes_kv_cache(dev):
    from singa_tpu import models
    m = models.create_model("gpt", vocab_size=67, max_seq=32, dim=32,
                            num_heads=4, num_layers=2)
    ids = tensor.from_numpy(
        np.random.RandomState(0).randint(0, 67, (2, 6)).astype(np.int32),
        device=m and dev)
    m.compile([ids], is_train=False, use_graph=False)
    m.eval()
    led = memory.install_ledger()
    m.generate(np.random.RandomState(1).randint(0, 67, (2, 6)), 4,
               temperature=0.0)
    # the serving.decode span exit snapshotted while the caches lived
    assert any(s["regions"]["kv_cache"] > 0 for s in led.timeline), \
        [dict(s["regions"]) for s in led.timeline]


def test_flight_snapshot_attribution_with_monitor(dev, rng, tmp_path):
    mon = HealthMonitor(out_dir=str(tmp_path), snapshot_batch=True)
    m, tx, ty = _build(dev, rng, health_mon=mon)
    led = memory.install_ledger()
    for _ in range(2):
        m(tx, ty)
    # the retained step inputs (the flight recorder's batch source)
    snap = led.timeline[-1]
    assert snap["regions"]["flight_snapshot"] \
        == int(tx.data.nbytes) + int(ty.data.nbytes)


# ---- leak detection (acceptance criterion: injected-leak A/B) --------------

def test_clean_run_reports_zero_leak_verdicts(dev, rng):
    m, tx, ty = _build(dev, rng)
    led = memory.install_ledger()
    m.fit([(tx, ty)] * 24, epochs=1)
    assert led.leak is not None
    assert led.leak.verdicts == []
    c = observe.get_registry().get("singa_mem_leak_verdicts_total")
    assert c is None or sum(v for _n, _k, v in c.samples()) == 0


def test_injected_leak_flagged_within_20_steps(dev, rng, tmp_path):
    mon = HealthMonitor(policy="warn", out_dir=str(tmp_path))
    health.set_active_monitor(mon)
    m, tx, ty = _build(dev, rng)
    led = memory.install_ledger()

    class LeakySrc:
        """Retains one fresh 256 KB device batch per step — the classic
        accumulating-reference leak."""

        def __init__(self, n=24):
            self.n = n
            self.kept = []

        def __iter__(self):
            for i in range(self.n):
                junk = tensor.from_numpy(
                    np.full((64, 1024), float(i), np.float32), dev)
                self.kept.append(junk)
                yield (tx, ty)

    src = LeakySrc()
    m.fit(src, epochs=1)
    assert led.leak.verdicts, "leak never flagged"
    v = led.leak.verdicts[0]
    assert v["step"] <= 20
    assert v["slope_bytes_per_step"] > led.leak.min_slope_bytes
    # nothing registered those retained batches: the growth is (and is
    # named as) unattributed
    assert v["suspect_region"] == "unattributed"
    assert v["suspect_delta_bytes"] > 0
    # the verdict fed the health monitor under the warn policy
    assert v["action"] == "warn"
    a = observe.get_registry().get("singa_health_anomaly_total")
    assert a.value(kind=health.KIND_MEM_LEAK) == 1
    c = observe.get_registry().get("singa_mem_leak_verdicts_total")
    assert c.value(region="unattributed") == 1
    # one verdict per episode: the leak kept growing but did not re-fire
    assert len(led.leak.verdicts) == 1


def test_leak_halt_policy_flips_healthz_status(dev, rng, tmp_path):
    mon = HealthMonitor(policy="halt", out_dir=str(tmp_path))
    health.set_active_monitor(mon)
    memory.install_ledger(
        leak=memory.LeakDetector(warmup=2, window=4, sustain=2,
                                 min_slope_bytes=1024))
    led = memory.get_ledger()
    kept = []
    for i in range(12):
        kept.append(jnp.full((32, 1024), float(i), jnp.float32))
        with observe.span("model.step"):
            pass
        observe.record_step(0.001)
    assert led.leak.verdicts
    assert led.leak.verdicts[0]["action"] == "halt"
    assert mon.verdict()["status"] == "halt"


# ---- OOM forensics (acceptance criterion) ----------------------------------

def test_oom_forensics_bundle_roundtrip(dev, rng, tmp_path):
    m, tx, ty = _build(dev, rng)
    led = memory.install_ledger(out_dir=str(tmp_path))
    for _ in range(2):
        m(tx, ty)
    err = _oom_error()

    def boom(*_a, **_k):
        raise err

    (ex,) = m._compiled_step.values()
    assert len(ex) == 1, "expected a cached step variant"
    for variant in ex._execs.values():
        variant.run = boom
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        m(tx, ty)
    bundles = [f for f in os.listdir(tmp_path)
               if f.startswith("flight_oom_")]
    assert len(bundles) == 1
    b = load_flight_bundle(str(tmp_path / bundles[0]))
    assert b["header"]["reason"] == "oom"
    oom = b["header"]["oom"]
    assert "RESOURCE_EXHAUSTED" in oom["error"]
    assert oom["executable_key"] == "step"
    # region breakdown reconciles inside the bundle too
    assert sum(oom["regions"].values()) == oom["total_bytes"]
    assert oom["top_arrays"], "top-K largest arrays missing"
    assert oom["top_arrays"][0]["nbytes"] >= oom["top_arrays"][-1]["nbytes"]
    assert {"shape", "dtype", "region"} <= set(oom["top_arrays"][0])
    # the executable manifest pins what was running
    assert b["header"]["executables"]
    assert any(e["key"] == "step" for e in b["header"]["executables"])
    # the timeline rode along as flight_step lines
    assert len(b["steps"]) == b["header"]["n_steps"] >= 2
    c = observe.get_registry().get("singa_mem_oom_dumps_total")
    assert c.value() == 1


def test_oom_from_aot_executor_dumps_and_reraises(tmp_path):
    """The serving-side hook: an AotExecutor whose cached executable
    dies resource-exhausted dumps forensics and re-raises instead of
    falling back to jit (which would re-pay the same allocation)."""
    memory.install_ledger(out_dir=str(tmp_path))
    calls = {"n": 0}
    err = _oom_error()

    def fn(x):
        calls["n"] += 1
        if calls["n"] > 1:
            raise err
        return x + 1

    ex = introspect.AotExecutor(jax.jit(fn), "serving.prefill")
    ex(jnp.ones((2,)))  # builds + caches
    # poison the cached executable
    (variant,) = ex._execs.values()
    variant.run = lambda *a: (_ for _ in ()).throw(err)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        ex(jnp.ones((2,)))
    assert any(f.startswith("flight_oom_") for f in os.listdir(tmp_path))


def test_oom_forensics_subprocess_roundtrip(tmp_path):
    """A worker that dies of an (injected) OOM mid-step leaves a
    loadable post-mortem behind — the whole point of the forensics
    path: the process is gone, the bundle survives."""
    out = tmp_path / "oomdir"
    script = tmp_path / "oom_worker.py"
    script.write_text(f'''
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, {_ROOT!r})
import numpy as np
from singa_tpu import layer, memory, model, opt, tensor
from singa_tpu.device import get_default_device

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

dev = get_default_device()
rng = np.random.RandomState(0)
tx = tensor.from_numpy(rng.randn(32, 10).astype(np.float32), dev)
ty = tensor.from_numpy(rng.randint(0, 4, 32).astype(np.int32), dev)
m = MLP()
m.set_optimizer(opt.SGD(lr=0.1, momentum=0.9))
m.compile([tx], is_train=True, use_graph=True)
memory.install_ledger(out_dir={str(out)!r})
for _ in range(3):
    m(tx, ty)
err = type("XlaRuntimeError", (RuntimeError,), {{}})(
    "RESOURCE_EXHAUSTED: Out of memory allocating 9999999999 bytes")
def boom(*_a, **_k):
    raise err
for ex in m._compiled_step.values():
    for variant in ex._execs.values():
        variant.run = boom
m(tx, ty)  # dies here; the bundle must already be on disk
''')
    proc = subprocess.run([sys.executable, str(script)],
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "RESOURCE_EXHAUSTED" in proc.stderr
    bundles = [f for f in os.listdir(out) if f.startswith("flight_oom_")]
    assert len(bundles) == 1
    b = load_flight_bundle(str(out / bundles[0]))
    assert b["header"]["reason"] == "oom"
    # 3 per-step snapshots + the dump's own at-OOM snapshot
    assert len(b["steps"]) == 4          # the timeline survived the death
    assert b["header"]["oom"]["top_arrays"]
    assert b["header"]["executables"]    # the manifest pins the step


# ---- pre-flight fit --------------------------------------------------------

def test_estimate_fit_combines_static_and_ledger(dev, rng, monkeypatch):
    m, tx, ty = _build(dev, rng)
    memory.install_ledger()
    m(tx, ty)
    fit = memory.estimate_fit(model=m, batch=(tx, ty))
    assert fit["params_bytes"] == sum(
        int(t.data.nbytes) for t in m.get_params().values())
    assert fit["opt_state_bytes"] > 0
    assert fit["batch_bytes"] == int(tx.data.nbytes) + int(ty.data.nbytes)
    # the compiled step's analysis was harvested (introspect AOT build)
    assert fit["source"] == "executable"
    assert fit["exec_arguments_bytes"] and fit["exec_temps_bytes"] \
        is not None
    assert fit["estimated_peak_bytes"] >= fit["exec_arguments_bytes"]
    # CPU has no allocator limit: fits is honest-unknown...
    assert fit["limit_bytes"] is None and fit["fits"] is None
    # ...until the env override provides one (how TPU limits are
    # rehearsed on the tier-1 backend)
    monkeypatch.setenv("SINGA_TPU_HBM_LIMIT_BYTES", str(10 ** 9))
    fit = memory.estimate_fit(model=m)
    assert fit["fits"] is True and fit["headroom_frac"] > 0.9
    monkeypatch.setenv("SINGA_TPU_HBM_LIMIT_BYTES", "1024")
    fit = memory.estimate_fit(model=m)
    assert fit["fits"] is False


def test_estimate_fit_before_compile_uses_ledger_side(dev, rng):
    m = MLP()
    m.set_optimizer(opt.SGD(lr=0.1))
    tx = tensor.from_numpy(rng.randn(8, 10).astype(np.float32), dev)
    m.compile([tx], is_train=True, use_graph=False)  # no jitted step
    fit = memory.estimate_fit(model=m, batch=(tx,))
    assert fit["source"] == "ledger"
    assert fit["estimated_peak_bytes"] \
        == fit["params_bytes"] + fit["opt_state_bytes"] \
        + fit["batch_bytes"]


# ---- satellites ------------------------------------------------------------

def test_record_hbm_falls_back_to_ledger_total_on_cpu(dev):
    """ISSUE-9 satellite regression: memory_stats() is None on the CPU
    backend — record_hbm used to silently export nothing; now
    singa_hbm_bytes_in_use always exists, fed by the live-array total."""
    assert dev.jax_device.memory_stats() is None  # the premise
    pin = jnp.ones((128,), jnp.float32)  # something definitely live
    observe.record_hbm(dev)
    g = observe.get_registry().get("singa_hbm_bytes_in_use")
    assert g is not None
    assert g.value() >= pin.nbytes


def test_record_hbm_fallback_is_disabled_with_observe(dev):
    observe.enable(False)
    try:
        observe.record_hbm(dev)
        assert observe.get_registry().get("singa_hbm_bytes_in_use") is None
    finally:
        observe.enable(True)


# ---- lifecycle -------------------------------------------------------------

def test_install_is_idempotent_and_uninstall_detaches(dev, rng):
    led = memory.install_ledger()
    assert memory.install_ledger() is led
    assert memory.get_ledger() is led
    memory.uninstall_ledger()
    assert memory.get_ledger() is None
    # steps after uninstall take no snapshots
    m, tx, ty = _build(dev, rng)
    m(tx, ty)
    assert len(led.timeline) == 0


def test_sampler_thread_lifecycle():
    led = memory.install_ledger(sample_interval_s=0.02)
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and not led.timeline:
        time.sleep(0.01)
    assert led.timeline, "sampler never snapshotted"
    names = [t.name for t in threading.enumerate()]
    assert "singa-mem-sampler" in names
    memory.uninstall_ledger()
    assert "singa-mem-sampler" not in [
        t.name for t in threading.enumerate() if t.is_alive()]


def test_register_provider_rejects_unknown_region():
    with pytest.raises(ValueError):
        memory.register_provider("heap", object(), lambda: ())
    with pytest.raises(ValueError):
        memory.note_arrays("heap", [])


def test_memz_report_text(dev, rng):
    # without a ledger: the not-installed text, no crash
    assert "no MemoryLedger installed" in memory.memz_report()
    m, tx, ty = _build(dev, rng)
    memory.install_ledger()
    for _ in range(2):
        m(tx, ty)
    rep = memory.memz_report()
    assert "== memory ==" in rep
    for region in MEM_REGIONS:
        assert region in rep
    assert "reconciliation" in rep and "(OK)" in rep
    assert "static estimate" in rep       # the introspect view
    assert "estimate-vs-actual" in rep    # ...side-by-side drift line
    assert "leak: slope" in rep
    j = memory.memz_json()
    assert j["installed"] is True
    assert sum(j["regions"].values()) == j["total_bytes"]
    assert j["timeline"] and j["static_hbm"]


def test_explain_report_carries_memory_sections(dev, rng):
    m, tx, ty = _build(dev, rng)
    memory.install_ledger()
    m(tx, ty)
    rep = introspect.explain(model=m, device=dev)
    assert rep["mem_regions"]["params"] > 0
    assert rep["memory_fit"]["source"] == "executable"
    text = introspect.format_explain(rep)
    assert "live memory (ledger):" in text
    assert "memory fit:" in text


# ---- review-driven hardening (ISSUE-9 review) ------------------------------

def test_dead_model_and_optimizer_providers_are_cleaned_up(dev, rng):
    """Rebuilding models in a long-lived process must not accumulate
    dead provider closures: the weakref callbacks drop the entries
    when the tracked objects die."""
    import gc
    m, tx, ty = _build(dev, rng)
    m(tx, ty)  # _build_step_impl registers the model-side providers
    with memory._lock:
        before = len(memory._providers)
    assert before >= 3  # params + flight_snapshot + opt_state
    del m, tx, ty
    gc.collect()
    with memory._lock:
        after = len(memory._providers)
    assert after == 0, f"{after} dead provider(s) survived GC"


def test_reset_reaps_a_raw_sampler_ledger():
    """A MemoryLedger built WITHOUT install_ledger still registers its
    sampler thread module-wide, so the conftest teardown (memory.reset)
    can join it instead of letting it mutate gauges across tests."""
    led = memory.MemoryLedger(sample_interval_s=0.02)
    assert any(t.name == "singa-mem-sampler"
               for t in threading.enumerate() if t.is_alive())
    memory.reset()
    assert not any(t.name == "singa-mem-sampler"
                   for t in threading.enumerate() if t.is_alive())
    assert led.timeline is not None  # object still usable, just closed


def test_oom_bundle_defaults_to_flight_recorder_dir(tmp_path):
    """With no explicit out_dir the bundle lands in the active
    monitor's recorder directory — the one /flightz indexes — not an
    unindexed CWD."""
    flights = tmp_path / "flights"
    health.set_active_monitor(HealthMonitor(out_dir=str(flights)))
    memory.install_ledger()  # out_dir=None: follow the monitor
    path = memory.dump_oom_bundle(exc=_oom_error(), key="step")
    assert os.path.dirname(path) == str(flights)
    assert os.path.basename(path).startswith("flight_oom_")
    b = load_flight_bundle(path)
    assert b["header"]["reason"] == "oom"


def test_note_arrays_skipped_without_ledger_on_decode(dev):
    """The serving hook is gated on an installed ledger: a decode call
    with no consumer must not accumulate transient notes."""
    from singa_tpu import models
    m = models.create_model("gpt", vocab_size=53, max_seq=24, dim=32,
                            num_heads=4, num_layers=1)
    ids = tensor.from_numpy(
        np.random.RandomState(0).randint(0, 53, (1, 4)).astype(np.int32),
        device=dev)
    m.compile([ids], is_train=False, use_graph=False)
    m.eval()
    m.generate(np.random.RandomState(1).randint(0, 53, (1, 4)), 2,
               temperature=0.0)
    with memory._lock:
        assert len(memory._transients) == 0


def test_successive_oom_bundles_do_not_overwrite(tmp_path):
    """Two OOMs at the same step count (a serving process that catches
    and carries on) must leave two bundles, not one."""
    memory.install_ledger(out_dir=str(tmp_path))
    p1 = memory.dump_oom_bundle(exc=_oom_error(), key="serving.prefill")
    p2 = memory.dump_oom_bundle(exc=_oom_error(), key="serving.prefill")
    assert p1 != p2
    assert os.path.isfile(p1) and os.path.isfile(p2)
    assert load_flight_bundle(p2)["header"]["reason"] == "oom"


def test_estimate_fit_floor_beats_stale_executable(dev, rng):
    """A stale (smaller) step executable from another model must not
    under-report a bigger model's requirement: the measured
    params+opt+batch floor wins and `source` says so."""
    m, tx, ty = _build(dev, rng)
    m(tx, ty)  # builds the "step" executable for the SMALL model
    big = MLP(hidden=2048)
    big.set_optimizer(opt.SGD(lr=0.1, momentum=0.9))
    btx = tensor.from_numpy(rng.randn(32, 10).astype(np.float32), dev)
    big.compile([btx], is_train=True, use_graph=False)
    fit = memory.estimate_fit(model=big, batch=(btx,))
    floor = fit["params_bytes"] + fit["opt_state_bytes"] \
        + fit["batch_bytes"]
    assert fit["estimated_peak_bytes"] >= floor
    assert fit["source"] == "ledger"  # the stale executable lost


def test_leak_detector_respects_observe_disabled(dev, rng):
    """Detection still runs with observability off, but no gauges,
    counters or events mutate (the record_* no-op contract)."""
    memory.install_ledger(
        leak=memory.LeakDetector(warmup=1, window=2, sustain=1,
                                 min_slope_bytes=16))
    led = memory.get_ledger()
    observe.enable(False)
    kept = []
    try:
        for i in range(6):
            kept.append(jnp.full((64, 64), float(i), jnp.float32))
            led._on_step(0.001)  # record_step is off; drive directly
    finally:
        observe.enable(True)
    assert led.leak.verdicts  # the verdict itself still fired
    assert observe.get_registry().get(
        "singa_mem_leak_slope_bytes") is None
    assert observe.get_registry().get(
        "singa_mem_leak_verdicts_total") is None
