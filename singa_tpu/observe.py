"""Runtime metrics & tracing: the live-telemetry layer.

The reference's only observability is the scheduler's per-op CUDA-event
table printed after N iterations (scheduler.cc:240-295), mirrored here by
the post-hoc xplane parser (xprof.py) — both tell you nothing while a job
is running. This module is the runtime layer every perf/robustness change
measures itself against:

  - `MetricsRegistry` with `Counter` / `Gauge` / `Histogram` (fixed
    log-scale buckets, stdlib only — no prometheus_client dependency),
  - `span(name, **attrs)`: a nesting context manager that records wall
    time into the `singa_span_seconds` histogram AND forwards to
    `jax.profiler.TraceAnnotation`, so the same spans appear in xplane
    traces that `xprof.op_table` decodes (category "span") — one name
    correlates the live histogram with the post-hoc device timeline,
  - exporters: `to_prometheus_text()` (pull-style scrape body) and a
    rotating JSONL `EventLog` for step/serving/bench records.

Metric-name contract (enforced at registration AND by
tools/check_metrics_names.py): names match ^singa_[a-z0-9_]+$ and a name
is registered with exactly one type. Semantics under jit: helpers called
from *traced* code (optimizer apply loops, communicator collectives) fire
once per compilation, not per step — they record the traced program's
shape (calls per step, bytes per step); wall-clock per executed step comes
from the host-side callers (`record_step`, serving wrappers).
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
from collections import deque

_NAME_RE = re.compile(r"^singa_[a-z0-9_]+$")

#: the collective vocabulary (parallel.communicator's call sites). The
#: `op=` label is contractually low-cardinality (lint rule 5): values
#: recorded by record_comm/record_comm_host are proven members of this
#: tuple, with unknown callers coerced to the trailing "other" bucket
#: rather than minting unbounded label values.
COMM_OPS = ("all_reduce", "all_reduce_half", "all_gather", "broadcast",
            "reduce_scatter", "all_reduce_max", "agree_any",
            "sparse_all_reduce_topk", "sparse_all_reduce_threshold",
            "other")

#: the attention call sites that choose between a Pallas kernel and a jnp
#: reference (ops.attention), and the paths they can take: the compiled
#: kernel, the same kernel in interpret mode (how CPU tests run it), or
#: the reference math.
ATTN_SITES = ("flash_fwd", "flash_bwd", "paged", "flash_decode")
ATTN_PATHS = ("kernel", "interpret", "reference")

#: the paths a gated short convolution's chain can take (ops.shortconv):
#: its Pallas kernels compiled, the same in interpret mode, or the jnp chain
SHORTCONV_PATHS = ("kernel", "interpret", "xla")

#: the paths a chunked gated delta rule can take (ops.gated_delta): the
#: same three
DELTA_RULE_PATHS = SHORTCONV_PATHS

#: what a softmax cross-entropy's targets are, and where its backward's
#: log-sum-exp comes from (record_cross_entropy)
CE_TARGETS = ("integer", "dense")
CE_LSE = ("kept", "rebuilt")

# Log-scale bucket boundaries (seconds): 1e-6 .. 1e3, ratio sqrt(10).
# Wide enough for a 2us collective and a 15-minute XLA compile alike.
DEFAULT_BUCKETS = tuple(10.0 ** (e / 2.0) for e in range(-12, 7))


def _label_key(labels: dict):
    return tuple(sorted(labels.items()))


def _esc(v) -> str:
    return str(v).replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n")


def _fmt_labels(key) -> str:
    if not key:
        return ""
    return "{" + ",".join(f'{k}="{_esc(v)}"' for k, v in key) + "}"


def _fmt_num(v: float) -> str:
    f = float(v)
    if f != f:
        return "NaN"     # canonical Prometheus spellings: a health gauge
    if f == float("inf"):
        return "+Inf"    # legitimately holds NaN/Inf on an anomaly step
    if f == float("-inf"):
        return "-Inf"
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return format(f, "g")


class _Metric:
    kind = "untyped"

    def __init__(self, name: str, help: str = ""):
        if not _NAME_RE.match(name):
            raise ValueError(
                f"metric name {name!r} must match {_NAME_RE.pattern}")
        self.name = name
        self.help = help
        # mutations are read-modify-write; serving threads update the
        # same series concurrently, so each metric carries its own lock
        # (uncontended acquire is ~100ns — noise on the step path)
        self._mlock = threading.Lock()


class Counter(_Metric):
    """Monotonic counter; `inc` with optional labels."""

    kind = "counter"

    def __init__(self, name, help=""):
        super().__init__(name, help)
        self._values = {}

    def inc(self, n: float = 1.0, **labels):
        if n < 0:
            raise ValueError(f"counter {self.name} cannot decrease")
        k = _label_key(labels)
        with self._mlock:
            self._values[k] = self._values.get(k, 0.0) + n

    def value(self, **labels) -> float:
        return self._values.get(_label_key(labels), 0.0)

    def samples(self):
        for k, v in sorted(self._values.items()):
            yield self.name, k, v

    def snapshot(self):
        return [{"labels": dict(k), "value": v}
                for k, v in sorted(self._values.items())]


class Gauge(_Metric):
    """Point-in-time value; `set`/`inc`/`dec` with optional labels."""

    kind = "gauge"

    def __init__(self, name, help=""):
        super().__init__(name, help)
        self._values = {}

    def set(self, v: float, **labels):
        with self._mlock:
            self._values[_label_key(labels)] = float(v)

    def inc(self, n: float = 1.0, **labels):
        k = _label_key(labels)
        with self._mlock:
            self._values[k] = self._values.get(k, 0.0) + n

    def dec(self, n: float = 1.0, **labels):
        self.inc(-n, **labels)

    def value(self, **labels) -> float:
        return self._values.get(_label_key(labels), 0.0)

    def samples(self):
        for k, v in sorted(self._values.items()):
            yield self.name, k, v

    def snapshot(self):
        return [{"labels": dict(k), "value": v}
                for k, v in sorted(self._values.items())]


class Histogram(_Metric):
    """Fixed-bucket histogram (cumulative-le on export, like Prometheus).

    Buckets are static log-scale upper bounds; `observe` is O(#buckets)
    worst case (linear scan — ~19 comparisons, cheap enough for the step
    path) and tracks per-label-set count/sum alongside.
    """

    kind = "histogram"

    def __init__(self, name, help="", buckets=None):
        super().__init__(name, help)
        self.buckets = tuple(sorted(buckets or DEFAULT_BUCKETS))
        self._series = {}  # label key -> [counts list, count, sum]

    def _row(self, labels):
        k = _label_key(labels)
        row = self._series.get(k)
        if row is None:
            row = self._series[k] = [[0] * (len(self.buckets) + 1), 0, 0.0]
        return row

    def observe(self, v: float, **labels):
        i = len(self.buckets)  # overflow (+Inf) slot
        for j, ub in enumerate(self.buckets):
            if v <= ub:
                i = j
                break
        with self._mlock:
            row = self._row(labels)
            row[0][i] += 1
            row[1] += 1
            row[2] += float(v)

    def count(self, **labels) -> int:
        return self._series.get(_label_key(labels), [None, 0, 0.0])[1]

    def sum(self, **labels) -> float:
        return self._series.get(_label_key(labels), [None, 0, 0.0])[2]

    def bucket_counts(self, **labels):
        """Cumulative counts per upper bound (+Inf last)."""
        row = self._series.get(_label_key(labels))
        if row is None:
            return [0] * (len(self.buckets) + 1)
        out, acc = [], 0
        for c in row[0]:
            acc += c
            out.append(acc)
        return out

    def snapshot(self):
        out = []
        for k, (counts, n, s) in sorted(self._series.items()):
            cum, acc = {}, 0
            for ub, c in zip(self.buckets, counts):
                acc += c
                cum[_fmt_num(ub)] = acc
            cum["+Inf"] = n
            out.append({"labels": dict(k), "count": n, "sum": s,
                        "buckets": cum})
        return out


class EventLog:
    """Rotating JSONL sink for step/serving/bench records.

    `write(record)` appends one compact JSON line (a `ts` epoch field is
    stamped if absent). When the file would exceed `max_bytes` it rotates
    shift-style: path -> path.1 -> ... -> path.<backups> (oldest dropped).
    """

    def __init__(self, path: str, max_bytes: int = 10_000_000,
                 backups: int = 3, fsync: bool = False):
        self.path = str(path)
        self.max_bytes = int(max_bytes)
        self.backups = int(backups)
        # fsync=True makes every write durable against POWER LOSS, not
        # just process death (write() already flush()es to the kernel,
        # which survives a SIGKILL'd worker) — the kill-resume path's
        # post-mortem log must not end before its last logged step
        self.fsync = bool(fsync)
        self._lock = threading.Lock()
        self._fh = None

    def _open(self):
        if self._fh is None or self._fh.closed:
            d = os.path.dirname(os.path.abspath(self.path))
            os.makedirs(d, exist_ok=True)
            self._fh = open(self.path, "a", encoding="utf-8")
        return self._fh

    def _rotate(self):
        if self._fh is not None and not self._fh.closed:
            self._fh.close()
        self._fh = None
        if self.backups <= 0:
            # no backups: truncate in place so max_bytes still holds
            if os.path.exists(self.path):
                os.remove(self.path)
            return
        for i in range(self.backups - 1, 0, -1):
            src, dst = f"{self.path}.{i}", f"{self.path}.{i + 1}"
            if os.path.exists(src):
                os.replace(src, dst)
        if os.path.exists(self.path):
            os.replace(self.path, f"{self.path}.1")

    def write(self, record: dict):
        if "ts" not in record:
            record = {"ts": round(time.time(), 6), **record}
        line = json.dumps(record, separators=(",", ":"), default=str) + "\n"
        with self._lock:
            fh = self._open()
            if fh.tell() + len(line) > self.max_bytes and fh.tell() > 0:
                self._rotate()
                fh = self._open()
            fh.write(line)
            fh.flush()
            if self.fsync:
                os.fsync(fh.fileno())

    def flush(self, fsync: "bool | None" = None):
        """Push buffered lines to the OS (and with `fsync` — defaulting
        to the log's own mode — to stable storage). write() already
        flushes per line, so this exists for callers that need an
        explicit durability point (a worker about to be killed)."""
        with self._lock:
            if self._fh is not None and not self._fh.closed:
                self._fh.flush()
                if self.fsync if fsync is None else fsync:
                    os.fsync(self._fh.fileno())

    def close(self):
        with self._lock:
            if self._fh is not None and not self._fh.closed:
                self._fh.close()
            self._fh = None

    @staticmethod
    def read(path: str):
        """Parse one JSONL file back into a list of dicts (skips
        torn/partial trailing lines rather than raising — a crash
        mid-write must not make the whole log unreadable)."""
        out = []
        if not os.path.exists(path):
            return out
        with open(path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    out.append(json.loads(line))
                except ValueError:
                    continue
        return out


class MetricsRegistry:
    """Process-wide metric store: get-or-create by (name, type), one type
    per name (re-registering under a different type raises — the same
    contract tools/check_metrics_names.py lints statically)."""

    def __init__(self):
        self._metrics = {}
        self._lock = threading.Lock()
        self.event_log: EventLog | None = None
        self.recent = deque(maxlen=512)  # last emitted records, in memory

    def _register(self, cls, name, help, **kw):
        with self._lock:
            m = self._metrics.get(name)
            if m is not None:
                if not isinstance(m, cls):
                    raise ValueError(
                        f"metric {name!r} already registered as {m.kind}, "
                        f"cannot re-register as {cls.kind}")
                return m
            m = self._metrics[name] = cls(name, help, **kw)
            return m

    def counter(self, name, help="") -> Counter:
        return self._register(Counter, name, help)

    def gauge(self, name, help="") -> Gauge:
        return self._register(Gauge, name, help)

    def histogram(self, name, help="", buckets=None) -> Histogram:
        return self._register(Histogram, name, help, buckets=buckets)

    def get(self, name):
        return self._metrics.get(name)

    def names(self):
        return sorted(self._metrics)

    def reset(self):
        with self._lock:
            self._metrics.clear()
            self.recent.clear()

    def emit(self, record: dict):
        """Route a structured record to the in-memory ring and, when one
        is attached, the JSONL EventLog."""
        if "ts" not in record:
            record = {"ts": round(time.time(), 6), **record}
        self.recent.append(record)
        log = self.event_log
        if log is not None:
            log.write(record)

    # ---- exporters -------------------------------------------------------
    def to_prometheus_text(self) -> str:
        """Prometheus text exposition format (0.0.4): per metric a
        `# HELP` / `# TYPE` header then its samples; histograms expand to
        cumulative `_bucket{le=...}` + `_sum` + `_count`."""
        lines = []
        for name in sorted(self._metrics):
            m = self._metrics[name]
            if m.help:
                lines.append(f"# HELP {name} {_esc(m.help)}")
            lines.append(f"# TYPE {name} {m.kind}")
            if isinstance(m, Histogram):
                for k, (counts, n, s) in sorted(m._series.items()):
                    acc = 0
                    for ub, c in zip(m.buckets, counts):
                        acc += c
                        lk = _fmt_labels(k + (("le", _fmt_num(ub)),))
                        lines.append(f"{name}_bucket{lk} {acc}")
                    lk = _fmt_labels(k + (("le", "+Inf"),))
                    lines.append(f"{name}_bucket{lk} {n}")
                    lines.append(f"{name}_sum{_fmt_labels(k)} {repr(s)}")
                    lines.append(f"{name}_count{_fmt_labels(k)} {n}")
            else:
                for _nm, k, v in m.samples():
                    lines.append(f"{name}{_fmt_labels(k)} {_fmt_num(v)}")
        return "\n".join(lines) + ("\n" if lines else "")

    def snapshot(self) -> dict:
        return {name: {"type": m.kind, "help": m.help,
                       "samples": m.snapshot()}
                for name, m in sorted(self._metrics.items())}


# ---- process-wide default registry ----------------------------------------

_default = MetricsRegistry()
_enabled = True
_tls = threading.local()
_step_cb = None
_span_listeners: list = []  # (exit_cb, enter_cb | None) pairs
_step_listeners: list = []  # post-step callbacks (memory ledger)


def add_span_listener(cb, on_enter=None):
    """Register `cb(path, seconds, attrs)` to be called when any
    `span()` region exits (path is the slash-joined span path, seconds
    its wall time), and optionally `on_enter(path)` when one opens.
    Listeners fire in registration order, children before parents
    (spans exit LIFO), and exceptions are swallowed — a broken listener
    must never break the instrumented code path. singa_tpu.goodput uses
    this to classify run wall time into goodput/badput buckets without
    re-instrumenting the span sites (the enter hook lets it reserve
    in-flight spans so a mid-span scrape doesn't misbook them)."""
    _span_listeners.append((cb, on_enter))
    return cb


def remove_span_listener(cb):
    """Unregister a span listener added with add_span_listener (no-op
    if it was never registered). Equality, not identity: bound methods
    compare equal across attribute accesses but are distinct objects."""
    _span_listeners[:] = [p for p in _span_listeners if p[0] != cb]


def add_step_listener(cb):
    """Register `cb(seconds)` to run at the END of record_step — i.e.
    after the model committed the step's new state buffers, unlike the
    model.step SPAN exit, which fires while the donated pre-step
    buffers are already freed but the new ones not yet assigned. The
    memory ledger snapshots from here so params attribute to live
    arrays. Exceptions are swallowed; unlike `set_step_callback`
    (single slot, introspect's MFU hook), this is a listener list."""
    _step_listeners.append(cb)
    return cb


def remove_step_listener(cb):
    """Unregister a step listener (equality match, like spans)."""
    _step_listeners[:] = [c for c in _step_listeners if c != cb]


def start_diag_server(port=None, **kwargs):
    """Start the live diagnostics HTTP server (singa_tpu.diag): /metrics,
    /healthz, /statusz, /flightz, /profilez on an ephemeral port by
    default (port=0), or `SINGA_TPU_DIAG_PORT` when `port` is None.
    Returns the running DiagServer. Lazy import: the server is stdlib
    only, but observe must stay import-light."""
    from . import diag
    return diag.start_diag_server(port=port, **kwargs)


def set_step_callback(cb):
    """Register (or clear with None) a hook fed each record_step's
    dispatch wall seconds. singa_tpu.introspect uses it to derive the
    `singa_mfu_pct` gauge from the AOT-harvested flops without adding
    any work to the step path when no executable has been introspected."""
    global _step_cb
    _step_cb = cb


def get_registry() -> MetricsRegistry:
    return _default


def enable(flag: bool = True):
    """Master switch for the built-in instrumentation hooks (the
    record_* helpers become no-ops; explicit metric objects still work)."""
    global _enabled
    _enabled = bool(flag)


def is_enabled() -> bool:
    return _enabled


def counter(name, help="") -> Counter:
    return _default.counter(name, help)


def gauge(name, help="") -> Gauge:
    return _default.gauge(name, help)


def histogram(name, help="", buckets=None) -> Histogram:
    return _default.histogram(name, help, buckets=buckets)


def set_event_log(log: "EventLog | str | None"):
    """Attach a JSONL EventLog (or a path, or None to detach) that every
    emitted step/serving/bench record is appended to."""
    if isinstance(log, str):
        log = EventLog(log)
    _default.event_log = log
    return log


def get_event_log():
    return _default.event_log


def to_prometheus_text() -> str:
    return _default.to_prometheus_text()


def dump(path: str | None = None) -> dict:
    """One JSON-able snapshot of every registered metric (and the recent
    in-memory event records). With `path`, also written to disk — the
    pull-less analog of a Prometheus scrape for batch jobs."""
    data = {"ts": round(time.time(), 6),
            "metrics": _default.snapshot(),
            "recent_events": list(_default.recent)}
    if path:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(data, f, indent=1, default=str)
    return data


# ---- spans -----------------------------------------------------------------

SPAN_TRACE_PREFIX = "singa.span/"

# Span-record ring: bounded deque of finished span/collective regions as
# {"name", "t0" (perf_counter at enter), "dur", "tid", "kind"} dicts —
# the raw material singa_tpu.fleet serializes into per-worker telemetry
# shards and the merged Perfetto trace. Off (None) by default: the ring
# costs one dict per span exit, which only a fleet shard writer needs.
_span_records: "deque | None" = None


def enable_span_records(capacity: int = 4096) -> None:
    """Start buffering finished spans (and collective host stamps) into
    a bounded in-memory ring of `capacity` records. Idempotent; a second
    call resizes the ring, keeping the newest records."""
    global _span_records
    old = _span_records
    ring = deque(old or (), maxlen=int(capacity))
    _span_records = ring


def disable_span_records() -> None:
    """Drop the ring and stop buffering (fleet teardown)."""
    global _span_records
    _span_records = None


def span_records_enabled() -> bool:
    return _span_records is not None


def span_records() -> list:
    """A snapshot (copy) of the current ring, oldest first."""
    ring = _span_records
    return list(ring) if ring is not None else []


def _record_span_entry(name, t0, dur, kind="span"):
    ring = _span_records
    if ring is not None:
        ring.append({"name": name, "t0": round(float(t0), 7),
                     "dur": round(float(dur), 7),
                     "tid": threading.get_ident(), "kind": kind})


def note_span(name, t0, dur, kind="span", tid=None):
    """Append one SYNTHETIC finished-span record to the span ring — a
    region measured by other means (wall stamps across a process
    startup, a reconstructed phase) that should ride the same
    shard -> merged-trace pipeline as `span()` regions. `t0` is a
    perf_counter stamp (the clock the fleet handshake aligns); `tid`
    places the slice on a chosen track (default: the calling thread).
    No-op while the ring is off, like every span exit."""
    ring = _span_records
    if ring is not None:
        ring.append({"name": str(name), "t0": round(float(t0), 7),
                     "dur": round(max(0.0, float(dur)), 7),
                     "tid": int(tid) if tid is not None
                     else threading.get_ident(),
                     "kind": str(kind)})


def current_span() -> "str | None":
    stack = getattr(_tls, "span_stack", None)
    return stack[-1] if stack else None


class suppress_spans:
    """Context manager: `span()` regions entered on THIS thread while
    active are no-ops (no histogram, no trace annotation, no listener
    callbacks). For background worker threads whose internal waits must
    not be attributed as run wall time — the overlap prefetcher runs
    its source iterator under this, so a wrapped NumpyBatchIter's own
    data.wait spans don't book overlapped producer time into the
    goodput `data_wait` bucket the prefetch exists to drain. Reentrant
    (a depth counter, not a flag)."""

    def __enter__(self):
        _tls.suppress = getattr(_tls, "suppress", 0) + 1
        return self

    def __exit__(self, exc_type, exc, tb):
        _tls.suppress = max(0, getattr(_tls, "suppress", 1) - 1)
        return False


def spans_suppressed() -> bool:
    """True while `suppress_spans` is active on the calling thread —
    for metric sites that should also stay quiet on suppressed worker
    threads (data.py's consumer-blocked histogram: a background
    prefetch producer is not the training loop)."""
    return bool(getattr(_tls, "suppress", 0))


class span:
    """`with span("serving.prefill", tokens=4096): ...`

    Nests: the recorded label is the slash-joined path of enclosing spans
    ("model.step/opt.apply_updates"), so one histogram
    (`singa_span_seconds{span=...}`) holds the whole hierarchy. The same
    path (prefixed `singa.span/`) is forwarded to
    `jax.profiler.TraceAnnotation`, so an active `Device.StartTrace`
    capture carries these spans and `xprof.op_table` surfaces them next
    to the per-HLO device rows. Safe with no jax and inside jit tracing
    (annotation + wall time then describe the trace, not the step).
    """

    __slots__ = ("name", "attrs", "path", "_t0", "_ann", "_off")

    #: a row in `singa_span_seconds` at exit (`trace_span` keeps none)
    timed = True

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self.path = None
        self._ann = None
        self._off = False

    def __enter__(self):
        if getattr(_tls, "suppress", 0):
            self._off = True  # suppress_spans active on this thread
            return self
        stack = getattr(_tls, "span_stack", None)
        if stack is None:
            stack = _tls.span_stack = []
        self.path = f"{stack[-1]}/{self.name}" if stack else self.name
        stack.append(self.path)
        try:
            import jax
            self._ann = jax.profiler.TraceAnnotation(
                SPAN_TRACE_PREFIX + self.path, **self.attrs)
            self._ann.__enter__()
        except Exception:
            self._ann = None  # no jax / no profiler: hist-only span
        for _cb, enter_cb in tuple(_span_listeners):
            if enter_cb is not None:
                try:
                    enter_cb(self.path)
                except Exception:
                    pass
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._off:
            return False
        dt = time.perf_counter() - self._t0
        if self._ann is not None:
            try:
                self._ann.__exit__(exc_type, exc, tb)
            except Exception:
                pass
        stack = getattr(_tls, "span_stack", None)
        if stack and stack[-1] == self.path:
            stack.pop()
        if _enabled and self.timed:
            _default.histogram(
                "singa_span_seconds",
                "wall seconds per span() region (label: slash-joined "
                "span path)").observe(dt, span=self.path)
        _record_span_entry(self.path, self._t0, dt)
        for cb, _enter_cb in tuple(_span_listeners):
            try:
                cb(self.path, dt, self.attrs)
            except Exception:
                pass  # a listener must never break the spanned code
        return False


class trace_span(span):
    """A span that names its interval and is not timed into the registry:
    the nesting path, the `TraceAnnotation`, the ring and the listeners as
    `span`, no row in `singa_span_seconds`. For a site between a fence's
    return and the next dispatch, where the device waits for the host:
    with the one histogram write of a timed span in `Tensor.numpy()` a
    training loop read 0.15-0.5 % slower on the chip in every run, with
    the rest of the span and no write in none (PERF.md section 6, PR 36:
    microseconds there decide whether the prefetcher's next batch runs
    beside the dispatch or before it)."""

    __slots__ = ()
    timed = False


# ---- framework instrumentation hooks ---------------------------------------
# Called from the hot paths (model/opt/serving/communicator/bench). Each is
# a no-op when disabled; none of them may raise into the training loop.

def record_compile(batch_class, recompile: bool = False,
                   donated_bytes: int | None = None):
    """A new compiled step variant: first-ever -> compile, later
    batch-size classes / step tags -> recompile. `batch_class` is the
    leading batch dim (the retrace trigger under jit)."""
    if not _enabled:
        return
    bc = str(batch_class)
    if recompile:
        counter("singa_model_recompile_total",
                "step retraces beyond the first compile, per batch-size "
                "class").inc(batch_class=bc)
    counter("singa_model_compile_total",
            "compiled step variants, per batch-size class"
            ).inc(batch_class=bc)
    if donated_bytes is not None:
        gauge("singa_step_donated_bytes",
              "bytes of state+opt buffers donated into the compiled "
              "step").set(float(donated_bytes))


def record_hbm(device):
    """Per-step HBM gauges via jax.Device.memory_stats (the hook
    device.get_gpu_mem_size reads). On backends without allocator
    stats (the tier-1 CPU path, where memory_stats() is None) the
    in-use gauge falls back to the memory ledger's live-array byte
    total, so `singa_hbm_bytes_in_use` ALWAYS exists instead of the
    gauges silently vanishing."""
    if not _enabled:
        return
    try:
        stats = getattr(device.jax_device, "memory_stats", lambda: None)()
    except Exception:
        stats = None
    if not stats:
        try:
            from . import memory
            # O(1) from the ledger's latest snapshot when installed,
            # else a throttled enumeration — this hook runs per step
            total = memory.hbm_fallback_bytes()
        except Exception:
            return
        gauge("singa_hbm_bytes_in_use",
              "device bytes in use").set(float(total))
        return
    if "bytes_in_use" in stats:
        gauge("singa_hbm_bytes_in_use",
              "device bytes in use").set(float(stats["bytes_in_use"]))
    if "bytes_limit" in stats:
        gauge("singa_hbm_bytes_limit",
              "device bytes limit").set(float(stats["bytes_limit"]))
    if "peak_bytes_in_use" in stats:
        gauge("singa_hbm_peak_bytes_in_use",
              "peak device bytes in use").set(
            float(stats["peak_bytes_in_use"]))


def record_step(seconds: float, batch=None, tag=0, device=None):
    """One Model train step (un-fenced dispatch wall time: on an async
    backend this is submit latency; fenced latency is the verbosity>0
    `dev.step_times` path / `singa_step_fenced_seconds`)."""
    if not _enabled:
        return
    histogram("singa_step_seconds",
              "train step dispatch wall seconds").observe(seconds)
    c = counter("singa_steps_total", "train steps invoked")
    c.inc()
    if device is not None:
        record_hbm(device)
    if _step_cb is not None:
        try:
            _step_cb(seconds)
        except Exception:
            pass  # a derived-metric hook must never break the step
    for listener in tuple(_step_listeners):
        try:
            listener(seconds)
        except Exception:
            pass  # a listener must never break the step
    _default.emit({"kind": "step", "step": int(c.value()),
                   "seconds": round(seconds, 9),
                   "batch": batch, "tag": tag})


def record_step_fenced(seconds: float):
    """Fenced (block_until_ready) step latency — recorded by the
    verbosity>0 profiling path alongside dev.step_times."""
    if not _enabled:
        return
    histogram("singa_step_fenced_seconds",
              "train step fenced wall seconds").observe(seconds)
    if _step_cb is not None:
        # fenced latency is the honest MFU denominator; feed it too (the
        # callback drops physically impossible un-fenced samples itself)
        try:
            _step_cb(seconds)
        except Exception:
            pass


def record_opt_update(n_params: int, seconds: float, strategy: str):
    """Optimizer apply-updates pass. Under graph mode this runs inside
    the jit trace, so it fires once per compilation (it measures trace
    cost and the per-step param count); on the eager path it fires per
    step."""
    if not _enabled:
        return
    counter("singa_opt_updates_total",
            "parameter updates applied (per trace under jit)"
            ).inc(n_params, strategy=strategy)
    histogram("singa_opt_apply_seconds",
              "apply-updates wall seconds (trace cost under jit)"
              ).observe(seconds, strategy=strategy)


def record_comm(op: str, nbytes: int, world_size: int = 1):
    """One collective in the program. Called at trace time under jit
    (shapes are static, so bytes are exact): counters describe the
    compiled step's communication — multiply by singa_steps_total for
    cumulative wire traffic; device time per collective comes from the
    xprof tables (the collectives are wrapped in named scopes)."""
    if not _enabled:
        return
    if op not in COMM_OPS:
        op = COMM_OPS[-1]  # "other": never mint unbounded op= values
    counter("singa_comm_calls_total",
            "collectives in traced/eager programs").inc(op=op)
    if world_size > 1:
        counter("singa_comm_bytes_total",
                "payload bytes per traced collective"
                ).inc(float(nbytes), op=op)


def record_attention_dispatch(site: str, path: str):
    """The implementation one attention call site chose. Called at trace
    time (once per call site per compilation, like record_comm), so the
    counter describes the compiled programs: on a TPU backend any
    `path="reference"` or `"interpret"` count means a kernel was
    silently not used — the smoke run fails on it."""
    assert site in ATTN_SITES and path in ATTN_PATHS, (site, path)
    if not _enabled:
        return
    counter("singa_attention_dispatch_total",
            "attention call sites traced, by site and the path taken "
            "(kernel|interpret|reference)").inc(site=site, path=path)


def record_shortconv_dispatch(path: str):
    """The implementation one short convolution's chain took, counted at
    trace time like record_attention_dispatch."""
    assert path in SHORTCONV_PATHS, path
    if not _enabled:
        return
    counter("singa_shortconv_dispatch_total",
            "short-convolution chains traced, by the path taken "
            "(kernel|interpret|xla)").inc(path=path)


def record_delta_rule_dispatch(path: str):
    """The implementation one chunked delta rule took, counted at trace
    time like record_shortconv_dispatch."""
    assert path in DELTA_RULE_PATHS, path
    if not _enabled:
        return
    counter("singa_delta_rule_dispatch_total",
            "chunked delta rules traced, by the path taken "
            "(kernel|interpret|xla)").inc(path=path)


# the plan gauges: what the latest traced step of a model does, one number
# by `kind` (each model names its kinds), set at trace time by
# `record_plan` and readable with no chip
PLANS = {
    "singa_moe_plan":
        "the latest traced step's expert layers, by kind: experts routed "
        "over, experts this device holds, choices a token (k), rows of the "
        "sorted buffer (tokens x min(k, held): the worst case; the grouped "
        "products follow the rows really routed, the passes over the buffer "
        "the rung that holds them), the least rung of the buffer's ladder, "
        "blocks recomputed in the backward pass; where the model says so, "
        "dense_layers (leading layers without experts), sigmoid (1: the "
        "router scores by a sigmoid, not a softmax), bias (1: a selection "
        "bias that the step moves)",
    "singa_blockdiff_plan":
        "the latest traced block-diffusion training step, by kind: block "
        "(positions a block), rows (of the doubled input, through every "
        "layer), loss_rows (the noised half: the head's and the loss's), "
        "pairs_inside (query, key pairs a head inside the mask: S^2 + S b "
        "a sequence), pairs_square (the doubled sequence's square), "
        "recomputed_blocks (rebuilt in the backward pass)",
    "singa_conv_plan":
        "the latest traced step's gated short convolutions, by kind: "
        "layers that run one, channels (the stream's width: each has its "
        "own filter), taps a filter, and how many of those layers sit in "
        "blocks rebuilt in the backward pass",
    "singa_loop_plan":
        "the latest traced step of a looped model, by kind: passes, blocks, "
        "block applications, regions recomputed in the backward pass, casts "
        "of one shared weight to the compute dtype (0: no amp)",
    "singa_linear_attn_plan":
        "the latest traced step's gated delta-rule layers, by kind "
        "(layers|heads|key_dim|value_dim|chunk|chunks|recomputed_blocks)",
}
# the kinds of `singa_linear_attn_plan` (models/olmo_hybrid.py)
LINEAR_ATTN_KINDS = ("layers", "heads", "key_dim", "value_dim", "chunk",
                     "chunks", "recomputed_blocks")


def record_plan(name, **kinds):
    """Set the plan gauge `name` (one of `PLANS`) by kind."""
    if not _enabled:
        return
    g = gauge(name, PLANS[name])
    for kind, v in kinds.items():
        g.set(v, kind=kind)


def record_flash_tiles(site: str, visited: int, masked: int, square: int,
                       skipped: int = 0, window: int | None = None,
                       block_diffusion: int | None = None,
                       steps: tuple = (0, 0), dq_rows: tuple | None = None):
    """The tile schedule one flash call site was traced with
    (ops.attention.flash_plan), in sub-tiles a (batch x head) row: how
    many the kernel computes, how many of those add a mask, and the whole
    score square. Causal calls visit little over half and mask only the
    sub-tiles the diagonal crosses; others visit all and mask none. Under
    a sliding window (`window`: its width, 0 for none) `skipped` counts
    the sub-tiles of blocks under the diagonal that lie wholly left of
    the window, neither computed nor fetched; under the block-diffusion
    mask (`block_diffusion`: its block length, 0 for another mask) every
    sub-tile that is not visited, the clean x noised quadrant's among them.
    `steps`: (grid steps a (batch x head) row, summed over the site's
    Mosaic calls; how many of them work no tile: a step costs its
    overhead whether or not it works one). `dq_rows` (a backward's): (the
    query rows whose dq its sweep by k block holds, the rows before them,
    which a `_dq` call of their own makes). A gauge: it holds the site's
    latest trace."""
    assert site in ATTN_SITES, site
    if not _enabled:
        return
    g = gauge("singa_flash_tiles",
              "sub-tiles a (batch x head) row in the latest traced flash "
              "call, by site and kind (visited|masked|square|skipped: left "
              "of a sliding window, or outside the block-diffusion mask; "
              "window: its width, 0 for none; block_diffusion: the mask's "
              "block length, 0 for another mask; steps|idle_steps: grid "
              "steps a row, and those of them that work no tile; "
              "dq_rows_held|dq_rows_rest: a backward's query rows whose dq "
              "its sweep holds, and those a _dq call makes)")
    for kind, n in (("visited", visited), ("masked", masked),
                    ("square", square), ("skipped", skipped),
                    ("window", window or 0),
                    ("block_diffusion", block_diffusion or 0),
                    ("steps", steps[0]), ("idle_steps", steps[1])):
        g.set(n, site=site, kind=kind)
    if dq_rows is not None:
        g.set(dq_rows[0], site=site, kind="dq_rows_held")
        g.set(dq_rows[1], site=site, kind="dq_rows_rest")


def record_step_outputs(batch_sharded: int, mean_reduced: int):
    """How the latest traced data-parallel step (Model under DistOpt on a
    mesh) hands back the outputs of train_one_batch: `batch_sharded`
    leave as one global array sharded over the data axis, each device
    keeping the rows it computed (no gather in the step); `mean_reduced`
    are scalars averaged over the shards and replicated. A gauge: it
    holds the latest trace."""
    if not _enabled:
        return
    g = gauge("singa_step_outputs",
              "outputs of the latest traced data-parallel step, by how "
              "they left it (batch_sharded|mean_reduced)")
    g.set(batch_sharded, kind="batch_sharded")
    g.set(mean_reduced, kind="mean_reduced")


def record_grad_reduce(summary: dict):
    """What the compiler made of the all-reduces of the latest built
    data-parallel step (Model under DistOpt on a mesh), as
    `introspect.all_reduce_summary` reads the compiled text: how many
    the step holds (`collectives`; the combiner packs the psums the tape
    issued), the bytes they reduce, and of both how much is in an
    asynchronous form, beside compute (`async`, `async_bytes`); the rest
    blocks the core. 0 asynchronous on a TPU mesh means this compiler no
    longer honours `Communicator.overlap_compile_options`. A gauge: it
    holds the latest build."""
    if not _enabled:
        return
    g = gauge("singa_grad_reduce",
              "all-reduces of the latest built data-parallel step "
              "(collectives|async|bytes|async_bytes)")
    for kind, n in summary.items():
        g.set(n, kind=kind)


def record_cross_entropy(targets: str, lse: str):
    """The form of the latest traced softmax cross-entropy backward:
    `targets` "integer" (class indices: the loss is lse - logits[target],
    nothing of the logits' size is formed) or "dense" (a distribution);
    `lse` "kept" (the backward reads the forward's log-sum-exp) or
    "rebuilt" (it takes the sums again: dense targets, or an operator
    replayed in a recomputed region, whose second forward makes them).
    A gauge: 1 on the latest trace's pair, 0 on the others."""
    assert targets in CE_TARGETS and lse in CE_LSE, (targets, lse)
    if not _enabled:
        return
    g = gauge("singa_cross_entropy",
              "the latest traced softmax cross-entropy backward, by the "
              "targets' kind (integer|dense) and where its log-sum-exp "
              "comes from (kept|rebuilt): 1 on the pair it took")
    for t in CE_TARGETS:
        for l in CE_LSE:
            g.set(int((t, l) == (targets, lse)), targets=t, lse=l)


def record_comm_host(op: str, start: float, seconds: float):
    """Host-side entry/exit stamp of one collective CALL SITE
    (parallel.communicator wraps every collective body in one). Under
    jit this fires at trace time and measures trace cost; on the eager
    path (and in the fleet harness's per-step host collective) it is
    real per-call wall time — the per-host timing the fleet straggler
    detector scores. Also lands in the span-record ring (kind "comm")
    when one is enabled, so collectives appear on the merged trace."""
    if not _enabled:
        return
    label = op if op in COMM_OPS else COMM_OPS[-1]
    histogram("singa_comm_host_seconds",
              "host wall seconds per collective call site (trace cost "
              "under jit, per-call on the eager path)"
              ).observe(seconds, op=label)
    _record_span_entry(f"comm.{op}", start, seconds, kind="comm")


def record_decode(kind: str, seconds: float, new_tokens: int, batch: int,
                  ttft: float | None = None, prompt_tokens: int = 0):
    """One serving decode call (end-to-end, fenced)."""
    if not _enabled:
        return
    histogram("singa_serving_decode_seconds",
              "end-to-end decode seconds").observe(seconds, kind=kind)
    if ttft is not None:
        histogram("singa_serving_ttft_seconds",
                  "time to first token (prefill + first sample)"
                  ).observe(ttft, kind=kind)
    counter("singa_serving_tokens_total",
            "generated tokens").inc(float(new_tokens), kind=kind)
    counter("singa_serving_requests_total",
            "decode calls").inc(kind=kind)
    tps = new_tokens / seconds if seconds > 0 else 0.0
    gauge("singa_serving_tokens_per_sec",
          "last decode call's generation rate").set(tps, kind=kind)
    gauge("singa_serving_batch_occupancy",
          "sequences in the last decode batch").set(float(batch), kind=kind)
    _default.emit({"kind": "serving", "decode": kind,
                   "seconds": round(seconds, 6),
                   "ttft_seconds": round(ttft, 6) if ttft is not None
                   else None,
                   "new_tokens": new_tokens, "batch": batch,
                   "prompt_tokens": prompt_tokens,
                   "tokens_per_sec": round(tps, 3)})


def record_prefetch(depth: "int | None" = None,
                    blocked_s: "float | None" = None,
                    produced: bool = False):
    """DevicePrefetcher telemetry (singa_tpu.overlap): ring occupancy,
    consumer blocked-time on an empty ring (the wall time its data.wait
    span also feeds into the goodput `data_wait` bucket), and batches
    the producer moved to the device."""
    if not _enabled:
        return
    if depth is not None:
        gauge("singa_prefetch_ring_depth",
              "on-device batches ready in the prefetch ring"
              ).set(float(depth))
    if blocked_s is not None:
        histogram("singa_prefetch_blocked_seconds",
                  "wall seconds the consumer blocked on an empty "
                  "prefetch ring").observe(blocked_s)
    if produced:
        counter("singa_prefetch_batches_total",
                "batches the prefetcher moved to the device").inc()


def record_ckpt_async(pending: int, blocking_s: "float | None" = None):
    """Async-checkpoint telemetry (singa_tpu.overlap): in-flight save
    count, and — when a save just started — how long it blocked the
    caller before handing the write to the background thread."""
    if not _enabled:
        return
    gauge("singa_checkpoint_async_pending",
          "async checkpoint saves started but not yet durable"
          ).set(float(pending))
    if blocking_s is not None:
        histogram("singa_checkpoint_async_blocking_seconds",
                  "wall seconds save_checkpoint blocked before returning "
                  "(async path)").observe(blocking_s)
        counter("singa_checkpoint_async_total",
                "async checkpoint saves started").inc()


def record_checkpoint_bytes(nbytes: int):
    """Bytes of the checkpoint/snapshot flush that just completed
    (model.save_checkpoint's orbax tree, Snapshot.flush's store)."""
    if not _enabled:
        return
    gauge("singa_checkpoint_bytes_written",
          "bytes in the last checkpoint/snapshot flush").set(float(nbytes))


def record_scaler_decision(rec: dict):
    """Mirror one shadow-scaler decision record (singa_tpu.capacity's
    ledger line) into the in-memory event ring and any attached
    EventLog, so scaling decisions interleave with the step/serving/
    bench records they were made from. Counters/gauges stay in
    capacity._metrics — this is only the event-stream copy."""
    if not _enabled:
        return
    # kind last: the ledger line carries its own kind ("decision")
    _default.emit({**rec, "kind": "scaler_decision"})


def record_regress_verdict(rec: dict):
    """Mirror one regression conviction (singa_tpu.regress's verdict
    record) into the in-memory event ring and any attached EventLog, so
    convictions interleave with the step/serving records that produced
    them. Counters/gauges stay in regress._metrics — this is only the
    event-stream copy."""
    if not _enabled:
        return
    _default.emit({**rec, "kind": "regress_verdict"})


__all__ = [
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "EventLog",
    "span", "suppress_spans", "spans_suppressed", "current_span",
    "get_registry", "enable", "is_enabled", "COMM_OPS",
    "counter", "gauge", "histogram", "set_event_log", "get_event_log",
    "to_prometheus_text", "dump", "DEFAULT_BUCKETS", "SPAN_TRACE_PREFIX",
    "set_step_callback", "add_span_listener", "remove_span_listener",
    "add_step_listener", "remove_step_listener",
    "start_diag_server",
    "trace_span",
    "enable_span_records", "disable_span_records", "span_records",
    "span_records_enabled", "note_span",
    "record_step", "record_step_fenced",
    "record_compile", "record_hbm", "record_opt_update", "record_comm",
    "record_comm_host",
    "record_decode", "record_scaler_decision",
    "record_regress_verdict", "record_checkpoint_bytes",
    "record_prefetch", "record_ckpt_async", "record_plan",
]
