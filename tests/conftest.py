"""Test fixture: virtual 8-device CPU mesh.

SURVEY.md §4's lesson: the reference cannot test collectives without a
cluster; we can — shard_map over forced host devices. This must run before
any JAX backend initialization.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# an ambient persistent compile cache must not feed the suite a hit:
# jax reads this variable at import, so drop it first
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import threading  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Non-daemon worker pools orbax creates process-wide on first use and
# keeps for the process lifetime (checkpointer.close() reaps them, but
# the pools are shared across checkpointers) — legitimate residents, not
# leaks. Anything non-daemon outside this list IS a leak.
_ORBAX_POOL_THREADS = ("metadata_store", "array_type_handler",
                       "base_pytree_ch", "utils_thread")


@pytest.fixture(autouse=True)
def _metrics_isolation():
    """Every test starts with a clean process-global MetricsRegistry
    (observe.MetricsRegistry.reset), no EventLog attached, and the
    instrumentation enabled — counter state accumulated by one test can
    no longer leak into another's assertions. Teardown also stops any
    diag server and uninstalls the goodput tracker, so tests never leak
    HTTP ports, server threads, or span listeners — and (ISSUE-5)
    asserts the test left no async checkpoint pending, no prefetcher
    thread alive, and no stray non-daemon thread behind."""
    from singa_tpu import (audit, capacity, diag, engine, fleet,
                           goodput, health, introspect, memory,
                           observe, regress, router, slo, warmstart,
                           watchdog)
    # warm-store isolation: an ambient SINGA_TPU_COMPILE_CACHE (set by
    # an operator shell) must not leak a shared on-disk cache into the
    # suite — pop it for the test's duration and restore on teardown;
    # warmstart.reset() also detaches the XLA persistent-cache config
    _warm_env = os.environ.pop("SINGA_TPU_COMPILE_CACHE", None)
    warmstart.reset()
    diag.stop_diag_server()
    goodput.uninstall()
    audit.reset()
    regress.reset()
    router.reset()
    fleet.uninstall()
    engine.reset()
    capacity.reset()
    slo.reset()
    engine.clear_request_listeners()
    memory.reset()
    watchdog.uninstall_watchdog()
    health.set_active_monitor(None)
    observe.get_registry().reset()
    observe.set_event_log(None)
    observe.enable(True)
    introspect.reset()  # signature history / manifest / peak override
    yield
    diag.stop_diag_server()
    goodput.uninstall()
    # watchdog teardown (ISSUE-10): the checker thread joined and the
    # installed watchdog + its span listener dropped. Same capture-
    # then-clean pattern as the fleet/memory checks below: the leak is
    # recorded first and cleaned regardless, so one leaky test fails
    # itself without cascading into the suite.
    leaked_wd = [t.name for t in threading.enumerate()
                 if t.is_alive() and t.name.startswith("singa-watchdog")]
    from singa_tpu import watchdog as _watchdog
    _watchdog.uninstall_watchdog()
    assert not leaked_wd, (
        f"watchdog thread(s) left running: {leaked_wd} — call "
        "watchdog.uninstall_watchdog() before the test ends")
    # audit teardown (ISSUE-18): the correctness observatory reset —
    # its canary prober / shadow replayer / fingerprint-timer /
    # quarantine-drain threads (singa-audit-*) joined and the router
    # terminal-request listener detached. Runs BEFORE the router check
    # because the observatory drives the router (drain threads call
    # Router.drain_replica; the replayer holds a router listener).
    # Capture-then-clean like every block here: the leak is recorded
    # first and cleaned regardless, so one leaky test fails itself
    # without cascading into the suite.
    leaked_audit = [t.name for t in threading.enumerate()
                    if t.is_alive()
                    and t.name.startswith("singa-audit")]
    audit.reset()
    assert not leaked_audit, (
        f"audit thread(s) left running: {leaked_audit} — call "
        "AuditObservatory.stop() / ParamFingerprinter.stop() (or "
        "audit.reset()) before the test ends")
    # regress teardown (ISSUE-19): the regression detector uninstalled
    # — its observe span listener and engine request listener detached,
    # any singa-regress-profile-* capture threads joined, and the
    # baseline store's JSONL handle closed. Runs BEFORE the tail/SLO
    # listener checks below, which would otherwise misread the
    # detector's request listener as a raw leak. Capture-then-clean
    # like every block here: the leak is recorded first and cleaned
    # regardless, so one leaky test fails itself without cascading
    # into the suite.
    leaked_regress = [t.name for t in threading.enumerate()
                      if t.is_alive()
                      and t.name.startswith("singa-regress")]
    regress.reset()
    assert not leaked_regress, (
        f"regress thread(s) left running: {leaked_regress} — call "
        "RegressionDetector.uninstall() (or regress.reset()) before "
        "the test ends")
    # router teardown (ISSUE-15): the installed router stopped — its
    # dispatcher/health/sender threads joined, replica subprocesses
    # reaped, and every still-pending request drained with a TERMINAL
    # outcome (rejected, reason "drain" — the zero-loss contract holds
    # even through test teardown). Runs BEFORE the engine check because
    # a router-owned ReplicaControl wraps an engine. Capture-then-clean:
    # the leak is recorded first and cleaned regardless, so one leaky
    # test fails itself without cascading into the suite.
    leaked_route = [t.name for t in threading.enumerate()
                    if t.is_alive()
                    and t.name.startswith("singa-route")]
    router.reset()
    assert not leaked_route, (
        f"router thread(s) left running: {leaked_route} — call "
        "Router.stop() / ReplicaControl.stop() (or router.reset()) "
        "before the test ends")
    # serving-engine teardown (ISSUE-11): every live engine stopped —
    # the admission queue drained (in-flight requests finished
    # "evicted"), the singa-serve-* decode thread joined, the page pool
    # freed and its kv_cache provider unregistered. Capture-then-clean
    # like the fleet/memory checks: the leak is recorded first and
    # cleaned regardless, so one leaky test fails itself without
    # cascading into the suite.
    leaked_serve = [t.name for t in threading.enumerate()
                    if t.is_alive() and t.name.startswith("singa-serve")]
    engine.reset()
    assert not leaked_serve, (
        f"serving-engine thread(s) left running: {leaked_serve} — call "
        "ServingEngine.stop() (or engine.reset()) before the test ends")
    # tail-attribution teardown (ISSUE-16): the installed TailCollector
    # detached from the engine's listener list and the per-request
    # attribution ring cleared. Runs BEFORE the SLO check below, which
    # would otherwise misread the collector's listener as a raw leak.
    _tc = slo.get_tail()
    slo.tail_reset()
    leaked_tail = [getattr(cb, "__qualname__", str(cb))
                   for cb in engine.request_listeners()
                   if _tc is not None and cb == _tc._on_request]
    assert not leaked_tail, (
        "TailCollector listener left attached after slo.tail_reset() "
        f"({leaked_tail}) — install_tail() must detach via tail_reset()")
    # SLO-tracker teardown (ISSUE-12): the installed tracker is
    # uninstalled silently (like the memory ledger), but a RAW engine
    # request listener a test registered itself must be removed by the
    # test — capture-then-clean: the leak is recorded first, every
    # listener cleared regardless, so one leaky test fails itself
    # without cascading into the suite.
    _tr = slo.get_tracker()
    leaked_slo = [getattr(cb, "__qualname__", str(cb))
                  for cb in engine.request_listeners()
                  if _tr is None or cb != _tr._on_request]
    slo.reset()
    engine.clear_request_listeners()
    assert not leaked_slo, (
        f"engine request listener(s) leaked: {leaked_slo} — "
        "engine.remove_request_listener() (or register through "
        "slo.SLOTracker.install, which slo.reset() detaches) before "
        "the test ends")
    # capacity teardown (ISSUE-17): the shadow scaler uninstalled —
    # its singa-capacity-* poll thread joined and the JSONL decision
    # ledger closed — and the measured decode floor dropped. Runs
    # AFTER the SLO check (the scaler samples the tracker, never
    # registers engine listeners) and before the generic stray-thread
    # sweep. Capture-then-clean like the blocks above: the leak is
    # recorded first and cleaned regardless, so one leaky test fails
    # itself without cascading into the suite.
    leaked_cap = [t.name for t in threading.enumerate()
                  if t.is_alive()
                  and t.name.startswith("singa-capacity")]
    capacity.reset()
    assert not leaked_cap, (
        f"capacity poll thread(s) left running: {leaked_cap} — call "
        "ShadowScaler.uninstall() (or capacity.reset()) before the "
        "test ends")
    # memory-ledger teardown (ISSUE-9): the ledger uninstalled (its
    # step/span listeners detached, the sampler thread joined) and all
    # region providers/transient notes dropped. Leaked sampler threads
    # are CAPTURED first and cleaned regardless, matching the
    # fleet/overlap pattern, so one leaky test fails itself without
    # cascading into the suite.
    leaked_mem = [t.name for t in threading.enumerate()
                  if t.is_alive() and t.name.startswith("singa-mem")]
    memory.reset()
    assert not leaked_mem, (
        f"memory-ledger sampler thread(s) left running: {leaked_mem} — "
        "memory.uninstall_ledger() (or ledger.close()) before the test "
        "ends")
    # fleet teardown (ISSUE-7): every shard-writer thread joined, the
    # aggregator dropped, the span-record ring disabled, and any spool
    # temp dir the fleet module created removed. Like the async-ckpt
    # check below, the leak is CAPTURED first and cleaned regardless,
    # so one leaky test fails itself without cascading into the suite.
    leaked_fleet = [t.name for t in threading.enumerate()
                    if t.is_alive()
                    and t.name.startswith("singa-fleet")]
    fleet.uninstall()
    assert not leaked_fleet, (
        f"fleet shard-writer thread(s) left running: {leaked_fleet} — "
        "close() the ShardWriter / stop_shard_writer() before the test "
        "ends")
    from singa_tpu import overlap
    pending = overlap.pending_checkpoints()
    # drain regardless so ONE leaky test doesn't cascade into the rest
    # of the suite; re-raise a deferred write failure as this test's
    overlap.wait_for_checkpoints()
    assert pending == 0, (
        f"{pending} async checkpoint save(s) left pending — call "
        "overlap.wait_for_checkpoints() (or load_checkpoint) before "
        "the test ends")
    stray_prefetch = [t.name for t in threading.enumerate()
                      if t.is_alive()
                      and t.name.startswith("singa-prefetch")]
    assert not stray_prefetch, (
        f"prefetcher thread(s) leaked: {stray_prefetch} — close() the "
        "DevicePrefetcher (Model.fit does this on every exit path)")
    # warm-store teardown (ISSUE-20): the store disabled, its lookup
    # ring/counters cleared, and the process-wide XLA persistent-cache
    # config detached — a test that enabled a per-test cache dir must
    # not leave later tests silently writing compile artifacts into it.
    # warmstart spawns no threads, so the generic sweep below needs no
    # dedicated prefix; the env var popped at setup is restored here.
    warmstart.reset()
    if _warm_env is not None:
        os.environ["SINGA_TPU_COMPILE_CACHE"] = _warm_env
    stray = [t.name for t in threading.enumerate()
             if t.is_alive() and t is not threading.main_thread()
             and not t.daemon
             and not t.name.startswith(_ORBAX_POOL_THREADS)]
    assert not stray, f"non-daemon thread(s) leaked: {stray}"


@pytest.fixture
def dev():
    from singa_tpu.device import get_default_device
    return get_default_device()


@pytest.fixture
def rng():
    return np.random.RandomState(0)


@pytest.fixture
def train_mode():
    from singa_tpu import autograd
    prev = autograd.training
    autograd.training = True
    yield
    autograd.training = prev
