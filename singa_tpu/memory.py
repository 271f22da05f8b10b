"""HBM memory observatory: the live device-memory ledger.

The reference's signature feature is a buffered graph ANALYZED for memory
reuse (scheduler.cc per SURVEY §0) — but neither it nor our introspect
layer can answer "what is on the device right now, and who put it
there": `observe.record_hbm` mirrors `jax.Device.memory_stats()` (None
on backends without allocator stats, e.g. the tier-1 CPU suite) and
introspect's `memory_analysis` is a static per-executable ESTIMATE.
This module is the dynamic half of the memory model:

  - **MemoryLedger**: enumerates `jax.live_arrays()` (backend-agnostic,
    so it works — and is testable — on CPU) and attributes every live
    buffer to a declared region from `MEM_REGIONS` via lightweight
    registration hooks at the sites where arrays are born: model params
    (`model.py`), optimizer slots (`opt.py`), the device prefetch ring
    (`overlap.py`), serving KV caches (`serving.py`), and
    flight-recorder batch snapshots (`health.py`). Anything unclaimed
    lands in `unattributed` — so the regions always RECONCILE: the sum
    of `singa_mem_region_bytes{region=...}` equals the live-array byte
    total at every snapshot, by construction (test-enforced).

  - **Timeline ring**: one bounded deque of per-step snapshots (the
    ledger snapshots on every `model.step` span exit, and on
    `serving.decode` so KV caches are visible mid-call), exported as
    `singa_mem_region_bytes` / `singa_mem_live_arrays` gauges and the
    `/memz` diag endpoint (breakdown + timeline + the static introspect
    HBM view side-by-side, for estimate-vs-actual drift).

  - **Leak detector**: a sustained positive slope of total live bytes
    after warmup feeds `HealthMonitor.note_external(KIND_MEM_LEAK)`
    under the monitor's (or an explicit) warn/halt policy; the region
    with the largest positive delta over the window names the suspect.

  - **OOM forensics**: every compiled program's dispatch
    (`introspect.AotExecutor.dispatch`: the model step, the eval
    forward, the serving programs) calls `handle_oom()` on a resource-exhausted `XlaRuntimeError` before
    re-raising — a FlightRecorder-style JSONL bundle (timeline, region
    breakdown, top-K largest live arrays with shapes/dtypes, the
    executable manifest) lands on disk, round-tripped by
    `health.load_flight_bundle`, so a production OOM dies with a
    post-mortem instead of a bare RESOURCE_EXHAUSTED.

  - **Pre-flight fit**: `estimate_fit(model, batch)` combines
    introspect's arguments/temps/outputs analysis with the ledger's
    param+opt bytes against the device limit (memory_stats
    `bytes_limit`, or `SINGA_TPU_HBM_LIMIT_BYTES`), surfaced in the
    explain report.

Overhead contract: every snapshot is host-side bookkeeping over object
identities — nothing traces, so `compile_count` stays 1 with the ledger
installed (test-enforced).
"""

from __future__ import annotations

import json
import os
import threading
import time
import weakref
from collections import deque

import jax

from . import observe

# ---- regions (the lint in tools/check_metrics_names.py greps this) --------

#: Every region a live device buffer can be attributed to. Attribution
#: is first-match in THIS order (params before opt_state before caches),
#: with `unattributed` the catch-all — so each array lands in exactly
#: one region and the per-region bytes always sum to the live total.
MEM_REGIONS = ("params", "opt_state", "prefetch_ring", "kv_cache",
               "flight_snapshot", "unattributed")
REGION_PARAMS = "params"
REGION_OPT_STATE = "opt_state"
REGION_PREFETCH_RING = "prefetch_ring"
REGION_KV_CACHE = "kv_cache"
REGION_FLIGHT_SNAPSHOT = "flight_snapshot"
REGION_UNATTRIBUTED = "unattributed"

#: span leaves whose exit triggers a ledger snapshot. Train steps are
#: NOT snapshotted at span exit — the model.step span closes after the
#: donated pre-step buffers died but before the new state is assigned
#: back, so params would misattribute; steps ride the post-commit
#: `observe.add_step_listener` hook instead. The serving decode span
#: exit is the only moment the KV caches are live host-visible buffers;
#: the engine's per-sync step span keeps the page-pool occupancy on the
#: /memz timeline for processes that only serve (no train steps), and
#: the engine-prefill span catches the admission seam, where a new
#: request's pages were just written into the pool.
SNAPSHOT_SPAN_LEAVES = ("serving.decode", "serving.engine_step",
                        "serving.engine_prefill")

#: top-K largest live arrays embedded in an OOM bundle
OOM_TOP_K = 16


# ---- birth-site registry ---------------------------------------------------
# Providers persist independently of any installed ledger: the hooks in
# model/opt/overlap fire at object-construction time, which may predate
# install_ledger(). Each provider is a zero-arg callable returning the
# CURRENT arrays of its region (params change identity every donated
# step, so a snapshot must re-ask, not cache ids).

_lock = threading.RLock()
_providers: "dict[tuple[str, int], callable]" = {}
_transients: "dict[int, tuple[weakref.ref, str]]" = {}


def _check_region(region: str):
    if region not in MEM_REGIONS:
        raise ValueError(f"region {region!r} not in {MEM_REGIONS}")


def _cleanup_providers(key_id: int, regions):
    """Weakref callback factory: when a tracked object dies, its
    provider entries are dropped — without this, a long-lived process
    that rebuilds models/optimizers would accumulate dead closures in
    _providers and every snapshot would keep calling them."""

    def _cb(_ref):
        with _lock:
            for rg in regions:
                _providers.pop((rg, key_id), None)

    return _cb


def register_provider(region: str, key, fn):
    """Register `fn() -> arrays` as the current contents of `region`
    (keyed, so re-registration for the same object replaces). The hook
    is a dict write — cheap enough for construction paths."""
    _check_region(region)
    with _lock:
        _providers[(region, id(key) if not isinstance(key, int) else key)] \
            = fn
    return fn


def unregister_provider(region: str, key):
    with _lock:
        _providers.pop(
            (region, id(key) if not isinstance(key, int) else key), None)


def region_has_provider(region: str) -> bool:
    """True when a persistent birth-site provider owns `region` — the
    serving decode path consults this to skip its transient
    note_arrays(kv_cache) once an engine's page pool is registered
    (the provider is authoritative; a second transient claim would be
    redundant weakref churn on every call)."""
    _check_region(region)
    with _lock:
        return any(rg == region for (rg, _k) in _providers)


def _iter_arrays(obj):
    """Yield every jax.Array reachable from `obj` (tuples/lists/dicts,
    Tensor-likes via `.data`); non-array leaves are skipped."""
    if obj is None:
        return
    if isinstance(obj, jax.Array):
        yield obj
        return
    data = getattr(obj, "data", None)
    if isinstance(data, jax.Array):
        yield data
        return
    if isinstance(obj, dict):
        for v in obj.values():
            yield from _iter_arrays(v)
    elif isinstance(obj, (tuple, list)):
        for v in obj:
            yield from _iter_arrays(v)


def note_arrays(region: str, tree):
    """Transiently attribute every array in `tree` to `region` for as
    long as the buffers stay alive (weakref-keyed, so a freed buffer —
    or an id reused after GC — can never be misattributed). The
    serving decode uses this for KV caches, health for flight-recorder
    batch snapshots."""
    _check_region(region)
    n = 0
    with _lock:
        for a in _iter_arrays(tree):
            aid = id(a)

            def _drop(_ref, _aid=aid):
                with _lock:
                    _transients.pop(_aid, None)

            try:
                _transients[aid] = (weakref.ref(a, _drop), region)
                n += 1
            except TypeError:
                continue  # unexpected non-weakrefable leaf: skip
    return n


def track_model(model):
    """model.py's birth-site hook (called from `_build_step_impl`):
    params follow the model's CURRENT param buffers (donation replaces
    them every step), and the retained step inputs — kept for the
    flight recorder's batch provider — attribute to `flight_snapshot`
    while a health monitor is attached."""
    key_id = id(model)
    ref = weakref.ref(model, _cleanup_providers(
        key_id, (REGION_PARAMS, REGION_FLIGHT_SNAPSHOT)))

    def params():
        m = ref()
        if m is None:
            return ()
        try:
            return [t.data for t in m.get_params().values()]
        except Exception:
            return ()

    def flight():
        m = ref()
        if m is None or getattr(m, "_health_monitor", None) is None:
            return ()
        return getattr(m, "_last_input_arrs", None) or ()

    register_provider(REGION_PARAMS, key_id, params)
    register_provider(REGION_FLIGHT_SNAPSHOT, key_id, flight)


def track_optimizer(opt):
    """opt.py's birth-site hook (called from `Optimizer.setup`): slot
    buffers + the step counter, re-read per snapshot (strategies with
    lazily growing state — sparse residuals — stay covered)."""
    key_id = id(opt)
    ref = weakref.ref(opt, _cleanup_providers(key_id,
                                              (REGION_OPT_STATE,)))

    def slots():
        o = ref()
        if o is None:
            return ()
        try:
            return list(o.state_arrays())
        except Exception:
            return ()

    register_provider(REGION_OPT_STATE, key_id, slots)


def track_prefetcher(prefetcher):
    """overlap.py's birth-site hook (DevicePrefetcher.__init__): the
    on-device batches currently parked in the ring."""
    key_id = id(prefetcher)
    ref = weakref.ref(prefetcher, _cleanup_providers(
        key_id, (REGION_PREFETCH_RING,)))

    def ring():
        p = ref()
        if p is None:
            return ()
        try:
            items = list(p._ring)  # may include the _END sentinel:
        except Exception:          # _iter_arrays yields nothing for it
            return ()
        out = []
        for it in items:
            out.extend(_iter_arrays(it))
        return out

    register_provider(REGION_PREFETCH_RING, key_id, ring)


def untrack(region: str, obj):
    """Drop a birth-site registration (DevicePrefetcher.close)."""
    unregister_provider(region, obj)


def total_live_bytes() -> int:
    """Byte total over `jax.live_arrays()` — the backend-agnostic
    answer `observe.record_hbm` falls back to when the device exposes
    no allocator stats (the tier-1 CPU path)."""
    return sum(int(getattr(a, "nbytes", 0) or 0)
               for a in jax.live_arrays())


_fallback_cache = [float("-inf"), 0]  # [monotonic ts, bytes]


def hbm_fallback_bytes(max_age_s: float = 0.5) -> int:
    """The per-step-rate-safe spelling of `total_live_bytes` for
    `observe.record_hbm`: the installed ledger's latest snapshot total
    when one exists (O(1)), else a direct enumeration throttled to one
    per `max_age_s` — record_hbm runs on EVERY step, and a long-lived
    process can hold thousands of live arrays."""
    led = _ledger
    if led is not None and led.timeline:
        return int(led.timeline[-1]["total_bytes"])
    now = time.monotonic()
    if now - _fallback_cache[0] < max_age_s:
        return _fallback_cache[1]
    v = total_live_bytes()
    _fallback_cache[0] = now
    _fallback_cache[1] = v
    return v


# ---- leak detection --------------------------------------------------------

class LeakDetector:
    """Sustained-growth watchdog over the ledger's total-bytes series.

    After `warmup` snapshots, a least-squares slope over the last
    `window` snapshots above `min_slope_bytes` (per step) for `sustain`
    consecutive checks is a leak verdict: counted per suspect region
    (`singa_mem_leak_verdicts_total{region=...}`), fed to the active
    `HealthMonitor.note_external(KIND_MEM_LEAK)` under `policy` (None =
    the monitor's own warn/halt), and held until the slope drops back
    under the threshold (one verdict per episode, not one per step).
    """

    def __init__(self, warmup: int = 5, window: int = 8,
                 min_slope_bytes: float = 4096.0, sustain: int = 3,
                 policy: "str | None" = None):
        if policy is not None and policy not in ("warn", "halt"):
            raise ValueError(f"policy {policy!r} not in ('warn','halt')")
        self.warmup = int(warmup)
        self.window = max(2, int(window))
        self.min_slope_bytes = float(min_slope_bytes)
        self.sustain = int(sustain)
        self.policy = policy
        self.slope = 0.0
        self.verdicts: list = []
        self._seen = 0
        self._over = 0
        self._flagged = False

    @staticmethod
    def _fit_slope(ys):
        n = len(ys)
        xm = (n - 1) / 2.0
        ym = sum(ys) / n
        num = sum((i - xm) * (y - ym) for i, y in enumerate(ys))
        den = sum((i - xm) ** 2 for i in range(n))
        return num / den if den else 0.0

    def check(self, timeline, step=None) -> "dict | None":
        """Feed one snapshot tick; returns the verdict dict when a new
        leak episode is flagged, else None."""
        self._seen += 1
        if self._seen <= self.warmup or len(timeline) < self.window:
            return None
        tail = list(timeline)[-self.window:]
        self.slope = self._fit_slope([s["total_bytes"] for s in tail])
        if observe.is_enabled():
            observe.gauge(
                "singa_mem_leak_slope_bytes",
                "live-bytes growth per step over the leak-detector "
                "window").set(self.slope)
        if self.slope <= self.min_slope_bytes:
            self._over = 0
            self._flagged = False
            return None
        self._over += 1
        if self._over < self.sustain or self._flagged:
            return None
        self._flagged = True
        deltas = {r: tail[-1]["regions"].get(r, 0)
                  - tail[0]["regions"].get(r, 0) for r in MEM_REGIONS}
        suspect = max(deltas, key=lambda r: deltas[r])
        verdict = {
            "step": int(step) if step is not None else None,
            "slope_bytes_per_step": round(self.slope, 1),
            "suspect_region": suspect,
            "suspect_delta_bytes": int(deltas[suspect]),
            "window": self.window,
            "ts": round(time.time(), 6),
        }
        self.verdicts.append(verdict)
        assert suspect in MEM_REGIONS
        if observe.is_enabled():
            observe.counter(
                "singa_mem_leak_verdicts_total",
                "sustained live-bytes growth verdicts, by suspect region"
            ).inc(region=suspect)
            observe.get_registry().emit(
                {"kind": "mem", "event": "leak", **verdict})
        from . import health
        mon = health.active_monitor()
        if mon is not None:
            action = self.policy
            if action is None:
                action = "halt" if mon.policy == "halt" else "warn"
            try:
                verdict["action"] = mon.note_external(
                    health.KIND_MEM_LEAK, detail=dict(verdict),
                    step=step, action=action)
            except Exception:
                pass  # the monitor must never break the step path
        return verdict


# ---- the ledger ------------------------------------------------------------

class MemoryLedger:
    """Live device-memory ledger: snapshot on demand (or per step via
    the span listener `install_ledger` wires), keep a bounded timeline,
    export gauges, and run the leak detector.

    `interval_steps`: snapshot every Nth `model.step` exit (1 = every
    step). `sample_interval_s > 0` additionally starts a daemon sampler
    thread (``singa-mem-sampler``) for processes that never step (pure
    serving); `close()`/`uninstall_ledger`/`reset()` joins it (sampling
    ledgers register module-wide so the conftest teardown can reap one
    a test leaked even without install_ledger).

    `out_dir=None` (the default) means OOM bundles follow the active
    HealthMonitor's recorder directory — the one `/flightz` indexes —
    falling back to the CWD; pass an explicit path to pin it.
    """

    def __init__(self, timeline: int = 512, interval_steps: int = 1,
                 sample_interval_s: float = 0.0, leak: "LeakDetector | "
                 "bool | None" = True, out_dir: "str | None" = None,
                 top_k: int = OOM_TOP_K):
        self.timeline: "deque[dict]" = deque(maxlen=int(timeline))
        self.interval_steps = max(1, int(interval_steps))
        self.out_dir = str(out_dir) if out_dir is not None else None
        self.top_k = int(top_k)
        self.enabled = True
        self.leak = (LeakDetector() if leak is True
                     else (leak or None))
        self.steps_seen = 0
        self._snap_lock = threading.Lock()
        self._stop = threading.Event()
        self._thread = None
        if sample_interval_s > 0:
            self._thread = threading.Thread(
                target=self._sample_loop, args=(float(sample_interval_s),),
                name="singa-mem-sampler", daemon=True)
            with _lock:
                _samplers.append(self)
            self._thread.start()

    # -- attribution -------------------------------------------------------
    @staticmethod
    def _region_ids() -> "dict[int, str]":
        """id(array) -> region, built fresh from the providers and the
        transient notes; first region in MEM_REGIONS order wins."""
        with _lock:
            providers = list(_providers.items())
            transients = list(_transients.items())
        by_region: "dict[str, set[int]]" = {r: set() for r in MEM_REGIONS}
        for (region, _key), fn in providers:
            try:
                for a in _iter_arrays(fn()):
                    by_region[region].add(id(a))
            except Exception:
                continue  # a broken provider must not break the step
        for aid, (ref, region) in transients:
            if ref() is not None:
                by_region[region].add(aid)
        ids: "dict[int, str]" = {}
        for region in MEM_REGIONS:
            for aid in by_region[region]:
                ids.setdefault(aid, region)
        return ids

    def snapshot(self, step: "int | None" = None) -> dict:
        """One reconciled breakdown of everything live right now. The
        region sums equal the `jax.live_arrays()` byte total by
        construction — every live array is counted exactly once."""
        with self._snap_lock:
            ids = self._region_ids()
            regions = {r: 0 for r in MEM_REGIONS}
            counts = {r: 0 for r in MEM_REGIONS}
            total = 0
            n = 0
            for a in jax.live_arrays():
                r = ids.get(id(a), REGION_UNATTRIBUTED)
                nb = int(getattr(a, "nbytes", 0) or 0)
                regions[r] += nb
                counts[r] += 1
                total += nb
                n += 1
            snap = {
                "ts": round(time.time(), 6),
                "step": int(step) if step is not None
                else self.steps_seen,
                "regions": regions,
                "counts": counts,
                "total_bytes": total,
                "n_arrays": n,
            }
            self.timeline.append(snap)
            self._export(snap)
            return snap

    @staticmethod
    def _export(snap: dict):
        if not observe.is_enabled():
            return
        g = observe.gauge(
            "singa_mem_region_bytes",
            "live device bytes attributed to each ledger region")
        for region in MEM_REGIONS:
            g.set(float(snap["regions"][region]), region=region)
        observe.gauge("singa_mem_total_bytes",
                      "total live device bytes (jax.live_arrays)"
                      ).set(float(snap["total_bytes"]))
        observe.gauge("singa_mem_live_arrays",
                      "live device arrays (jax.live_arrays)"
                      ).set(float(snap["n_arrays"]))
        observe.counter("singa_mem_snapshots_total",
                        "memory-ledger snapshots taken").inc()

    def top_arrays(self, k: "int | None" = None) -> list:
        """The K largest live arrays, freshly attributed: [{nbytes,
        shape, dtype, region}] — the OOM bundle's "who is biggest"."""
        ids = self._region_ids()
        rows = []
        for a in jax.live_arrays():
            rows.append({
                "nbytes": int(getattr(a, "nbytes", 0) or 0),
                "shape": list(getattr(a, "shape", ()) or ()),
                "dtype": str(getattr(a, "dtype", "?")),
                "region": ids.get(id(a), REGION_UNATTRIBUTED),
            })
        rows.sort(key=lambda r: -r["nbytes"])
        return rows[:(k or self.top_k)]

    def timeline_copy(self) -> list:
        """A consistent copy of the timeline ring. Readers on OTHER
        threads (diag handlers, the fleet shard writer, the OOM dump)
        must use this: iterating the deque raw races the training
        thread's append (RuntimeError: deque mutated during
        iteration)."""
        with self._snap_lock:
            return list(self.timeline)

    def region_bytes(self) -> "dict | None":
        """The latest snapshot's {regions, total_bytes, n_arrays, step}
        — what a fleet shard carries per publish."""
        if not self.timeline:
            return None
        s = self.timeline[-1]
        return {"regions": dict(s["regions"]),
                "total_bytes": s["total_bytes"],
                "n_arrays": s["n_arrays"], "step": s["step"]}

    # -- step plumbing -----------------------------------------------------
    def _on_step(self, _seconds):
        """observe.add_step_listener hook: fires at the END of
        record_step, after the model committed the step's new state
        buffers, so params/opt attribute to arrays that are live."""
        if not self.enabled:
            return
        self.steps_seen += 1
        if self.steps_seen % self.interval_steps:
            return
        self.snapshot(step=self.steps_seen)
        if self.leak is not None:
            # locked copy: a concurrent sampler thread's append must
            # not blow up the window iteration
            self.leak.check(self.timeline_copy(), step=self.steps_seen)

    def _on_span(self, path, _seconds, _attrs):
        if not self.enabled:
            return
        if path.rsplit("/", 1)[-1] in SNAPSHOT_SPAN_LEAVES:
            self.snapshot()

    def _sample_loop(self, interval_s: float):
        while not self._stop.wait(interval_s):
            try:
                if self.enabled:
                    self.snapshot()
            except Exception:
                pass  # sampling must never kill the thread

    def close(self):
        self._stop.set()
        t = self._thread
        self._thread = None
        if t is not None:
            t.join(timeout=5.0)
        with _lock:
            if self in _samplers:
                _samplers.remove(self)


# ---- module singleton ------------------------------------------------------

_ledger: "MemoryLedger | None" = None
_samplers: "list[MemoryLedger]" = []  # ledgers with a live sampler thread


def install_ledger(**kwargs) -> MemoryLedger:
    """Install (or return) the process MemoryLedger and wire it to the
    span stream: every `model.step` (and `serving.decode`) exit takes a
    snapshot. Idempotent — a second call returns the running ledger."""
    global _ledger
    with _lock:
        if _ledger is not None:
            return _ledger
        _ledger = MemoryLedger(**kwargs)
        observe.add_step_listener(_ledger._on_step)
        observe.add_span_listener(_ledger._on_span)
        return _ledger


def uninstall_ledger():
    """Remove the ledger: span listener detached, sampler thread joined.
    Birth-site providers stay registered (they belong to the objects,
    not the ledger); `reset()` clears those too."""
    global _ledger
    with _lock:
        led = _ledger
        _ledger = None
    if led is not None:
        observe.remove_step_listener(led._on_step)
        observe.remove_span_listener(led._on_span)
        led.close()


def get_ledger() -> "MemoryLedger | None":
    return _ledger


def reset():
    """Full teardown (the conftest contract): ledger uninstalled,
    every sampler thread joined (including a raw MemoryLedger a test
    built without install_ledger), every provider and transient note
    dropped, the record_hbm fallback cache invalidated."""
    uninstall_ledger()
    with _lock:
        stray = list(_samplers)
    for led in stray:
        led.close()
    with _lock:
        _providers.clear()
        _transients.clear()
    _fallback_cache[0] = float("-inf")
    _fallback_cache[1] = 0


# ---- OOM forensics ---------------------------------------------------------

def is_resource_exhausted(exc) -> bool:
    """True for the XLA allocator's RESOURCE_EXHAUSTED XlaRuntimeError
    (matched structurally — jaxlib moves the class between releases)."""
    if exc is None:
        return False
    names = {c.__name__ for c in type(exc).__mro__}
    if "XlaRuntimeError" not in names:
        return False
    return "RESOURCE_EXHAUSTED" in str(exc)


def dump_oom_bundle(exc=None, key=None, out_dir=None,
                    ledger: "MemoryLedger | None" = None) -> str:
    """Write the OOM post-mortem bundle (JSONL, `flight_oom_step<N>`,
    round-tripped by `health.load_flight_bundle`): a header carrying
    the region breakdown, the top-K largest live arrays, the fit
    estimate and the executable manifest, then the memory timeline as
    `flight_step` lines and the recent EventLog tail."""
    led = ledger if ledger is not None else _ledger
    one_shot = led is None
    if one_shot:
        led = MemoryLedger(timeline=1, leak=None)
    snap = led.snapshot()
    top = led.top_arrays()
    execs = None
    try:
        from . import introspect
        execs = introspect.executable_manifest()[-8:] or None
    except Exception:
        pass
    fit = None
    try:
        fit = estimate_fit()
    except Exception:
        pass
    d = out_dir or led.out_dir
    if d is None:
        # default to the directory /flightz indexes (the active
        # monitor's flight recorder), so an OOM post-mortem shows up
        # next to the anomaly bundles instead of landing in an
        # unindexed CWD
        from . import health
        mon = health.active_monitor()
        d = getattr(getattr(mon, "recorder", None), "out_dir", None) \
            or "."
    os.makedirs(d, exist_ok=True)
    c = observe.get_registry().get("singa_steps_total")
    step = int(c.value()) if c is not None else led.steps_seen
    path = os.path.join(d, f"flight_oom_step{step}.jsonl")
    k = 1
    while os.path.exists(path):
        # a second OOM at the same step count (a serving process that
        # catches and carries on) must not overwrite the first
        # post-mortem
        k += 1
        path = os.path.join(d, f"flight_oom_step{step}_{k}.jsonl")
    tail = list(observe.get_registry().recent)[-64:]
    timeline = led.timeline_copy()
    header = {
        "kind": "flight_header", "ts": round(time.time(), 6),
        "reason": "oom", "step": step,
        "n_steps": len(timeline), "n_events": len(tail),
        "oom": {
            "error": str(exc)[:2000] if exc is not None else None,
            "executable_key": key,
            "regions": dict(snap["regions"]),
            "total_bytes": snap["total_bytes"],
            "n_arrays": snap["n_arrays"],
            "top_arrays": top,
            "fit": fit,
        },
        "executables": execs,
    }
    with open(path, "w", encoding="utf-8") as f:
        f.write(json.dumps(header, separators=(",", ":"),
                           default=str) + "\n")
        for s in timeline:
            f.write(json.dumps({"kind": "flight_step", **s},
                               separators=(",", ":"), default=str) + "\n")
        for ev in tail:
            f.write(json.dumps({"kind": "flight_event", "event": ev},
                               separators=(",", ":"), default=str) + "\n")
    if one_shot:
        led.close()
    return path


def handle_oom(exc, key=None, out_dir=None) -> "str | None":
    """The dispatch-site hook (`introspect.AotExecutor.dispatch`):
    dump the forensics bundle for a resource-exhausted error and
    return its path. Never raises — the original OOM must propagate,
    not a forensics failure."""
    if not is_resource_exhausted(exc):
        return None
    try:
        path = dump_oom_bundle(exc=exc, key=key, out_dir=out_dir)
        # counted only once the bundle actually exists on disk — an
        # unwritable out_dir must not advance the counter
        observe.counter("singa_mem_oom_dumps_total",
                        "OOM forensics bundles written").inc()
        observe.get_registry().emit(
            {"kind": "mem", "event": "oom", "bundle": path,
             "executable_key": key, "error": str(exc)[:500]})
        return path
    except Exception:
        return None


# ---- pre-flight fit --------------------------------------------------------

def device_limit_bytes(device=None) -> "int | None":
    """The device HBM limit: allocator stats when the backend has them,
    else the `SINGA_TPU_HBM_LIMIT_BYTES` override (how the CPU tier
    tests the fit math), else None (unknown)."""
    jd = getattr(device, "jax_device", device)
    if jd is None:
        try:
            jd = jax.devices()[0]
        except Exception:
            jd = None
    stats = None
    if jd is not None:
        try:
            stats = jd.memory_stats()
        except Exception:
            stats = None
    if stats and stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    env = os.environ.get("SINGA_TPU_HBM_LIMIT_BYTES")
    if env:
        try:
            return int(float(env))
        except ValueError:
            return None
    return None


def estimate_fit(model=None, batch=None, device=None) -> dict:
    """Pre-flight "does this training step fit" estimate: introspect's
    static per-executable analysis (arguments/outputs/temps/generated
    code of the compiled step) combined with the ledger's measured
    param + optimizer bytes, against the device limit. `fits` is None
    when no limit is known (CPU without the env override)."""
    from . import introspect
    params_b = opt_b = 0
    if model is not None:
        try:
            params_b = sum(int(getattr(t.data, "nbytes", 0) or 0)
                           for t in model.get_params().values())
        except Exception:
            params_b = 0
        o = getattr(model, "_optimizer", None)
        if o is not None:
            try:
                opt_b = sum(int(getattr(a, "nbytes", 0) or 0)
                            for a in o.state_arrays())
            except Exception:
                opt_b = 0
    elif _ledger is not None and _ledger.timeline:
        regions = _ledger.timeline[-1]["regions"]
        params_b = int(regions.get(REGION_PARAMS, 0))
        opt_b = int(regions.get(REGION_OPT_STATE, 0))
    batch_b = sum(int(getattr(a, "nbytes", 0) or 0)
                  for a in _iter_arrays(batch)) if batch is not None else 0
    step = introspect.last_build("step")
    mem = dict((step or {}).get("memory") or {})
    exec_total = sum(int(v) for v in mem.values())
    # the executable's own requirement: arguments (which include the
    # donated params/opt slots and the batch) + outputs + temps +
    # generated code. last_build("step") is PROCESS-GLOBAL, so when a
    # DIFFERENT (larger) model is being sized the stale executable must
    # not under-report: the measured params+opt+batch floor always
    # applies, and `source` says which side won.
    floor = params_b + opt_b + batch_b
    estimated = max(exec_total, floor)
    dev = device if device is not None \
        else getattr(model, "_device", None)
    limit = device_limit_bytes(dev)
    rep = {
        "params_bytes": params_b,
        "opt_state_bytes": opt_b,
        "batch_bytes": batch_b,
        "exec_arguments_bytes": mem.get("arguments"),
        "exec_outputs_bytes": mem.get("outputs"),
        "exec_temps_bytes": mem.get("temps"),
        "exec_generated_code_bytes": mem.get("generated_code"),
        "estimated_peak_bytes": int(estimated),
        "limit_bytes": limit,
        "fits": (estimated <= limit) if limit else None,
        "headroom_frac": round(1.0 - estimated / limit, 4)
        if limit else None,
        "source": "executable" if exec_total >= floor and exec_total
        else "ledger",
    }
    return rep


# ---- /memz reports ---------------------------------------------------------

def _mb(b) -> str:
    return f"{(b or 0) / 1e6:10.2f} MB"


def memz_json(timeline_tail: int = 64, include_top: bool = True) -> dict:
    """The /memz?json=1 body: latest breakdown, timeline, leak state,
    the static introspect HBM view, and the fit estimate. The text
    view passes include_top=False — top_arrays costs a fresh
    live-array attribution pass it never renders."""
    from . import introspect
    led = _ledger
    out: dict = {"installed": led is not None}
    if led is None:
        return out
    if not led.timeline:
        led.snapshot()
    tl = led.timeline_copy()  # diag handler thread vs training appends
    s = tl[-1]
    out.update({
        "regions": dict(s["regions"]),
        "counts": dict(s["counts"]),
        "total_bytes": s["total_bytes"],
        "n_arrays": s["n_arrays"],
        "step": s["step"],
        "timeline": [{"step": t["step"], "ts": t["ts"],
                      "total_bytes": t["total_bytes"],
                      "regions": dict(t["regions"])}
                     for t in tl[-timeline_tail:]],
    })
    if include_top:
        out["top_arrays"] = led.top_arrays(8)
    if led.leak is not None:
        out["leak"] = {
            "slope_bytes_per_step": round(led.leak.slope, 1),
            "min_slope_bytes": led.leak.min_slope_bytes,
            "verdicts": list(led.leak.verdicts),
        }
    step = introspect.last_build("step")
    out["static_hbm"] = dict((step or {}).get("memory") or {})
    try:
        out["fit"] = estimate_fit()
    except Exception:
        out["fit"] = None
    return out


def memz_report() -> str:
    """Text block for /memz (and /statusz-style reading): the region
    breakdown table, the reconciliation line, the static introspect
    HBM view side-by-side, the leak state and the timeline tail."""
    rep = memz_json(timeline_tail=8, include_top=False)
    lines = ["== memory =="]
    if not rep.get("installed"):
        lines.append("no MemoryLedger installed "
                     "(singa_tpu.memory.install_ledger())")
        return "\n".join(lines)
    lines.append(f"{'region':<16} {'bytes':>14} {'MB':>13} {'arrays':>7}")
    for region in MEM_REGIONS:
        b = rep["regions"].get(region, 0)
        lines.append(f"{region:<16} {b:>14}{_mb(b)} "
                     f"{rep['counts'].get(region, 0):>7}")
    lines.append(f"{'TOTAL':<16} {rep['total_bytes']:>14}"
                 f"{_mb(rep['total_bytes'])} {rep['n_arrays']:>7}")
    region_sum = sum(rep["regions"].values())
    ok = "OK" if region_sum == rep["total_bytes"] else "BROKEN"
    lines.append(f"reconciliation: region sum {region_sum} == live "
                 f"total {rep['total_bytes']} ({ok})")
    static = rep.get("static_hbm") or {}
    if static:
        lines.append("static estimate (introspect, step executable): "
                     + " | ".join(f"{k} {v / 1e6:.2f} MB"
                                  for k, v in sorted(static.items())))
        live_po = (rep["regions"].get(REGION_PARAMS, 0)
                   + rep["regions"].get(REGION_OPT_STATE, 0))
        est_args = static.get("arguments")
        if est_args:
            drift = (live_po - est_args) / est_args * 100.0
            lines.append(f"estimate-vs-actual: live params+opt "
                         f"{live_po / 1e6:.2f} MB vs executable "
                         f"arguments {est_args / 1e6:.2f} MB "
                         f"({drift:+.1f}% drift)")
    else:
        lines.append("static estimate: none (no step executable built)")
    leak = rep.get("leak")
    if leak is not None:
        lines.append(f"leak: slope {leak['slope_bytes_per_step']} B/step "
                     f"(threshold {leak['min_slope_bytes']:g}), "
                     f"{len(leak['verdicts'])} verdict(s)")
        for v in leak["verdicts"][-3:]:
            lines.append(f"  step {v['step']}: suspect "
                         f"{v['suspect_region']} "
                         f"(+{v['suspect_delta_bytes']} B over "
                         f"{v['window']} steps)")
    fit = rep.get("fit")
    if fit:
        lim = fit.get("limit_bytes")
        lines.append(
            f"fit: estimated peak {fit['estimated_peak_bytes'] / 1e6:.2f}"
            f" MB vs limit "
            + (f"{lim / 1e6:.2f} MB -> "
               f"{'fits' if fit['fits'] else 'DOES NOT FIT'} "
               f"(headroom {fit['headroom_frac'] * 100.0:.1f}%)"
               if lim else "unknown (no allocator stats; set "
               "SINGA_TPU_HBM_LIMIT_BYTES)"))
    lines.append("timeline (newest last): " + "  ".join(
        f"s{t['step']}:{t['total_bytes'] / 1e6:.1f}MB"
        for t in rep.get("timeline", [])))
    return "\n".join(lines)


__all__ = [
    "MEM_REGIONS", "MemoryLedger", "LeakDetector",
    "install_ledger", "uninstall_ledger", "get_ledger", "reset",
    "register_provider", "unregister_provider", "region_has_provider",
    "note_arrays",
    "track_model", "track_optimizer", "track_prefetcher", "untrack",
    "total_live_bytes", "hbm_fallback_bytes",
    "is_resource_exhausted", "dump_oom_bundle",
    "handle_oom", "estimate_fit", "device_limit_bytes",
    "memz_report", "memz_json", "SNAPSHOT_SPAN_LEAVES", "OOM_TOP_K",
]
