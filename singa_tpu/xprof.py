"""Per-op trace analysis: parse jax.profiler xplane dumps into op time tables.

The reference's deepest profiling level is the scheduler's per-op CUDA-event
table (reference src/core/scheduler/scheduler.cc:240-295: per-op fwd/bwd
times printed after N iterations).  The TPU analog is the XLA profiler's
xplane trace: every HLO op's device-side execution interval.  TensorBoard's
profile plugin is the usual consumer, but it isn't available here — and a
framework should be able to read its own profiles — so this module decodes
the `*.xplane.pb` protobuf wire format directly (same approach as
`sonnx/onnx_pb.py`: a ~100-line reader for the handful of message types we
need, no protobuf dependency).

Schema (tsl/profiler/protobuf/xplane.proto):
  XSpace        { repeated XPlane planes = 1; }
  XPlane        { int64 id=1; string name=2; repeated XLine lines=3;
                  map<int64,XEventMetadata> event_metadata=4;
                  map<int64,XStatMetadata> stat_metadata=5; }
  XLine         { int64 id=1; string name=2; int64 timestamp_ns=3;
                  repeated XEvent events=4; }
  XEvent        { int64 metadata_id=1; int64 offset_ps=2;
                  int64 duration_ps=3; repeated XStat stats=5; }
  XEventMetadata{ int64 id=1; string name=2; string display_name=4; }
  XStat         { int64 metadata_id=1; double double_value=2;
                  uint64 uint64=3; int64 int64=4; string str=5; }
  XStatMetadata { int64 id=1; string name=2; }

Usage:
    dev.StartTrace(logdir); ...steps...; dev.StopTrace()
    table = xprof.op_table(logdir)          # list of dicts, sorted by time
    print(xprof.format_table(table))
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict


# ---- protobuf wire reader (subset) ----------------------------------------

class _Truncated(Exception):
    """Varint/field ran past the end of the buffer (a torn/partial
    .xplane.pb, e.g. the profiler died mid-write)."""


def _read_varint(buf: bytes, pos: int):
    result = 0
    shift = 0
    n = len(buf)
    while True:
        if pos >= n:
            raise _Truncated(pos)
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not (b & 0x80):
            return result, pos
        shift += 7


def _fields(buf: bytes):
    """Yield (field_number, wire_type, value) for one message body.

    Truncated or malformed tails (partial varint, length running past the
    buffer, unknown wire type) END the iteration instead of raising: a
    torn profile yields the events written so far, and a zero-length file
    yields nothing — op_table then returns an empty table rather than
    blowing up the caller's post-run reporting.
    """
    pos = 0
    n = len(buf)
    try:
        while pos < n:
            key, pos = _read_varint(buf, pos)
            field, wire = key >> 3, key & 7
            if wire == 0:          # varint
                val, pos = _read_varint(buf, pos)
            elif wire == 1:        # 64-bit
                if pos + 8 > n:
                    return
                val = buf[pos:pos + 8]
                pos += 8
            elif wire == 2:        # length-delimited
                ln, pos = _read_varint(buf, pos)
                if ln > n - pos:
                    return         # length past the end: torn write
                val = buf[pos:pos + ln]
                pos += ln
            elif wire == 5:        # 32-bit
                if pos + 4 > n:
                    return
                val = buf[pos:pos + 4]
                pos += 4
            else:
                return             # unknown wire type: not our schema
            yield field, wire, val
    except _Truncated:
        return


def _zigzag(v: int) -> int:
    # xplane uses plain int64 (not sint64); varints of negatives are rare
    # here and 2^63-wrapped; treat as signed two's-complement.
    return v - (1 << 64) if v >= (1 << 63) else v


# ---- xplane model ----------------------------------------------------------

class _Plane:
    __slots__ = ("name", "lines", "event_meta", "stat_meta", "event_stats")

    def __init__(self):
        self.name = ""
        self.lines = []          # list[(line_name, [(meta_id, dur_ps, stats)])]
        self.event_meta = {}     # id -> name
        self.stat_meta = {}      # id -> name
        self.event_stats = {}    # id -> [raw XStat bytes] (from metadata)

    def meta_stats(self, meta_id):
        """Decoded {stat_name: value} attached to an event's METADATA
        (XLA puts per-op constants here: hlo_category, flops,
        raw_bytes_accessed, shape_with_layout, ...)."""
        out = {}
        for raw in self.event_stats.get(meta_id, ()):
            sid, val = _parse_stat(raw)
            nm = self.stat_meta.get(sid)
            if nm:
                out[nm] = val
        return out


def _parse_event(buf: bytes):
    meta_id = 0
    dur_ps = 0
    stats = []
    for f, w, v in _fields(buf):
        if f == 1:
            meta_id = v
        elif f == 3:
            dur_ps = _zigzag(v)
        elif f == 5 and w == 2:
            stats.append(v)
    return meta_id, dur_ps, stats


def _parse_stat(buf: bytes):
    """Return (metadata_id, value) with value decoded by wire type."""
    import struct
    meta_id = 0
    val = None
    for f, w, v in _fields(buf):
        if f == 1:
            meta_id = v
        elif f == 2 and w == 1:
            val = struct.unpack("<d", v)[0]
        elif f in (3, 7):
            val = v
        elif f == 4:
            val = _zigzag(v)
        elif f in (5, 6):
            try:
                val = v.decode("utf-8", "replace")
            except Exception:
                val = v
    return meta_id, val


def _parse_line(buf: bytes):
    name = ""
    events = []
    for f, w, v in _fields(buf):
        if f == 2 and w == 2:
            name = v.decode("utf-8", "replace")
        elif f == 4 and w == 2:
            events.append(_parse_event(v))
    return name, events


def _parse_metadata_entry(buf: bytes, name_field: int = 2):
    """map<int64, X*Metadata> entry -> (id, name, [raw XStat bytes])."""
    key = 0
    name = ""
    display = ""
    stats = []
    for f, w, v in _fields(buf):
        if f == 1 and w == 0:
            key = v
        elif f == 2 and w == 2:
            # value message (X*Metadata)
            for f2, w2, v2 in _fields(v):
                if f2 == name_field and w2 == 2:
                    name = v2.decode("utf-8", "replace")
                elif f2 == 4 and w2 == 2:      # display_name
                    display = v2.decode("utf-8", "replace")
                elif f2 == 5 and w2 == 2:      # XEventMetadata.stats
                    stats.append(v2)
    return key, (display or name), stats


def _parse_plane(buf: bytes) -> _Plane:
    p = _Plane()
    for f, w, v in _fields(buf):
        if f == 2 and w == 2:
            p.name = v.decode("utf-8", "replace")
        elif f == 3 and w == 2:
            p.lines.append(_parse_line(v))
        elif f == 4 and w == 2:
            k, nm, st = _parse_metadata_entry(v)
            p.event_meta[k] = nm
            if st:
                p.event_stats[k] = st
        elif f == 5 and w == 2:
            k, nm, _ = _parse_metadata_entry(v)
            p.stat_meta[k] = nm
    return p


def parse_xspace(path: str):
    """Parse one .xplane.pb file -> list of _Plane."""
    with open(path, "rb") as f:
        buf = f.read()
    planes = []
    for f_, w, v in _fields(buf):
        if f_ == 1 and w == 2:
            planes.append(_parse_plane(v))
    return planes


# ---- aggregation -----------------------------------------------------------

_CATEGORY_RULES = [
    ("span", re.compile(r"^singa\.span/")),
    ("conv", re.compile(r"^(%?)conv(?!ert)", re.I)),
    ("matmul", re.compile(r"^(%?)(dot|gemm|matmul)", re.I)),
    ("fusion", re.compile(r"^(%?)fusion", re.I)),
    ("allreduce", re.compile(r"(all-reduce|allreduce)", re.I)),
    ("allgather", re.compile(r"(all-gather|allgather)", re.I)),
    ("copy", re.compile(r"^(%?)(copy|transpose|bitcast)", re.I)),
    ("reduce", re.compile(r"^(%?)reduce", re.I)),
    ("infeed/outfeed", re.compile(r"(infeed|outfeed)", re.I)),
]


def _category(op_name: str) -> str:
    for cat, rx in _CATEGORY_RULES:
        if rx.search(op_name):
            return cat
    return "other"


def find_xplane_files(logdir: str):
    return sorted(glob.glob(
        os.path.join(logdir, "**", "*.xplane.pb"), recursive=True))


def op_table(logdir: str, device_only: bool = True,
             include_async: bool = False):
    """Aggregate per-op device time across all traces under `logdir`.

    Returns a list of dicts sorted by total_ms desc:
      {op, category, total_ms, count, avg_us, pct}
    Only device planes (TPU/GPU/host-CPU XLA ops) are counted; python-side
    planes are skipped so the table reflects accelerator time, like the
    reference's per-op table reflects CUDA-event time.

    A TPU device plane carries several lines: 'XLA Ops' is the exclusive
    compute timeline (what this table reports), 'Async XLA Ops' are
    DMA/copy events that OVERLAP compute (their durations double-count
    wall-clock — excluded unless `include_async`), and 'Steps'/'XLA
    Modules' are per-step envelopes (always excluded).

    Spans emitted by `observe.span()` (TraceAnnotation names prefixed
    `singa.span/`) are surfaced as rows with category "span". They live
    on the HOST planes (python-thread lines), so they are collected from
    ALL planes before the device filter. Span wall time is a host-side
    ENVELOPE around device work, so it is kept in a separate pct pool
    and the span rows are appended AFTER the device rows: the device
    ops' pct still sums to ~100 of device time and their ordering is
    untouched, while each span's pct is relative to the span total.
    """
    all_planes = [p for path in find_xplane_files(logdir)
                  for p in parse_xspace(path)]
    dev_planes = [p for p in all_planes if "/device:" in p.name.lower()]
    planes = all_planes
    if device_only and dev_planes:
        planes = dev_planes  # real accelerator planes (TPU/GPU)
    # else: CPU-only traces put XLA op events on the /host:CPU plane —
    # fall back to every plane that has op lines so tests work on CPU.
    total_ps = defaultdict(int)
    count = defaultdict(int)
    span_ps = defaultdict(int)
    span_count = defaultdict(int)
    for plane in all_planes:
        # observe.span annotations: any plane, any line (host threads);
        # strip the "#attr=val#" metadata suffix TraceMe appends
        for _line_name, events in plane.lines:
            for meta_id, dur_ps, _stats in events:
                op = plane.event_meta.get(meta_id, "")
                if op.startswith("singa.span/"):
                    op = op.split("#", 1)[0]
                    span_ps[op] += dur_ps
                    span_count[op] += 1
    for plane in planes:
        for line_name, events in plane.lines:
            nm = line_name.lower()
            if ("module" in nm or "step" in nm or "overlay" in nm
                    or "framework" in nm):
                continue  # per-step/module envelopes, not leaf ops
            if "async" in nm and not include_async:
                continue  # overlapped DMA: double-counts wall-clock
            for meta_id, dur_ps, _stats in events:
                op = plane.event_meta.get(meta_id, f"op#{meta_id}")
                if op.startswith("singa.span/"):
                    continue  # span envelopes have their own pool above
                total_ps[op] += dur_ps
                count[op] += 1

    def make_rows(ps_map, n_map):
        grand = sum(ps_map.values()) or 1
        rows = [
            {
                "op": op,
                "category": _category(op),
                "total_ms": ps / 1e9,
                "count": n_map[op],
                "avg_us": ps / 1e6 / max(n_map[op], 1),
                "pct": 100.0 * ps / grand,
            }
            for op, ps in ps_map.items()
        ]
        rows.sort(key=lambda r: -r["total_ms"])
        return rows

    return make_rows(total_ps, count) + make_rows(span_ps, span_count)


def top_ops(path_or_table, k: int = 10):
    """Top-k ops by total device time: the explain report's "where did
    the step actually go" section. Accepts a trace logdir (runs
    `op_table` on it) or an already-built op_table row list. Span
    envelope rows are excluded — a span is host wall time AROUND the
    device ops already in the ranking."""
    rows = op_table(path_or_table) if isinstance(path_or_table, str) \
        else [dict(r) for r in path_or_table]
    # drop span envelopes and python-frame TraceMe rows ("$file.py:NN fn",
    # present on CPU-only traces where host planes stand in for device
    # planes) — neither is an op the device executed
    rows = [r for r in rows if r.get("category") != "span"
            and not r.get("op", "").startswith("$")]
    rows.sort(key=lambda r: -r.get("total_ms", 0.0))
    return rows[:int(k)]


def diff_op_tables(before, after):
    """Per-op time delta between two op_table row lists: the evidence
    bundle's "which ops got slower" section, useful standalone for any
    before/after trace pair.

    Returns rows sorted by regression contribution (delta_ms desc):
      {op, category, before_ms, after_ms, delta_ms, ratio,
       pct_of_regression}
    `ratio` is after/before (None for ops absent on one side — a new op
    diffs against 0, a vanished op contributes its negative delta).
    `pct_of_regression` is each op's share of the total POSITIVE delta,
    so the top rows name the regression even when other ops got faster.
    Span envelope rows and python-frame "$file.py" TraceMe rows are
    excluded, matching top_ops — the diff ranks device ops."""
    def fold(rows):
        out = {}
        for r in rows or []:
            if r.get("category") == "span" \
                    or str(r.get("op", "")).startswith("$"):
                continue
            op = r.get("op")
            if op is None:
                continue
            prev = out.get(op)
            if prev is None:
                out[op] = dict(r)
            else:  # same op split across planes: sum it
                prev["total_ms"] = (prev.get("total_ms") or 0.0) \
                    + (r.get("total_ms") or 0.0)
        return out

    b, a = fold(before), fold(after)
    rows = []
    for op in set(b) | set(a):
        bm = float((b.get(op) or {}).get("total_ms") or 0.0)
        am = float((a.get(op) or {}).get("total_ms") or 0.0)
        rows.append({
            "op": op,
            "category": (a.get(op) or b.get(op) or {}).get("category"),
            "before_ms": round(bm, 6),
            "after_ms": round(am, 6),
            "delta_ms": round(am - bm, 6),
            "ratio": round(am / bm, 4) if bm > 0.0 and op in a
            else None,
        })
    pos = sum(r["delta_ms"] for r in rows if r["delta_ms"] > 0.0)
    for r in rows:
        r["pct_of_regression"] = (
            round(100.0 * r["delta_ms"] / pos, 2)
            if pos > 0.0 and r["delta_ms"] > 0.0 else 0.0)
    rows.sort(key=lambda r: -r["delta_ms"])
    return rows


def span_table(logdir: str):
    """Just the observe.span() rows of op_table (category "span"),
    with the `singa.span/` prefix stripped — the bridge between the
    live `singa_span_seconds` histogram and the post-hoc trace: both
    key on the same slash-joined span path.

    Each row carries a `depth` column (0 = top-level span, 1 = one
    enclosing span, ...) derived from the slash-joined path, so nested
    spans (health/step inside fit_epoch, opt.apply_updates inside
    model.step) group correctly in reports: sort or indent by depth and
    the hierarchy reads straight off the table."""
    rows = [dict(r) for r in op_table(logdir, device_only=False)
            if r["category"] == "span"]
    for r in rows:
        r["op"] = r["op"][len("singa.span/"):]
        r["depth"] = r["op"].count("/")
    grand = sum(r["total_ms"] for r in rows) or 1.0
    for r in rows:
        r["pct"] = 100.0 * r["total_ms"] / grand
    return rows


def hlo_category_table(logdir: str, steps: int = 1):
    """Per-HLO-category time/bytes/flops table from the XLA-attached event
    metadata (stat names `hlo_category`, `raw_bytes_accessed`,
    `model_flops`). This is the honest profile: unlike the compile-time
    cost analysis, the durations are measured and the categories are
    XLA's own (convolution fusion / loop fusion / copy / formatting...).
    `steps`: divide totals to get per-step numbers. Returns rows sorted by
    time: {category, ms, gbytes, tflops, pct, achieved_gbs, tflops_s}."""
    planes = [p for path in find_xplane_files(logdir)
              for p in parse_xspace(path)]
    dev = [p for p in planes if "/device:" in p.name.lower()]
    agg = defaultdict(lambda: [0, 0.0, 0.0])
    for plane in (dev or planes):
        for line_name, events in plane.lines:
            if line_name != "XLA Ops":
                continue
            for meta_id, dur_ps, _ in events:
                st = plane.meta_stats(meta_id)
                a = agg[st.get("hlo_category", "?")]
                a[0] += dur_ps
                a[1] += float(st.get("raw_bytes_accessed") or 0)
                a[2] += float(st.get("model_flops") or st.get("flops") or 0)
    grand_ps = sum(a[0] for a in agg.values()) or 1
    rows = []
    for cat, (ps, b, fl) in agg.items():
        ms = ps / 1e9 / steps
        sec = ps / 1e12
        rows.append({
            "category": cat,
            "ms": ms,
            "gbytes": b / 1e9 / steps,
            "tflops": fl / 1e12 / steps,
            "pct": 100.0 * ps / grand_ps,
            "achieved_gbs": (b / steps) / (ms / 1e3) / 1e9 if ms else 0.0,
            "tflops_s": (fl / 1e12) / sec if sec else 0.0,
        })
    rows.sort(key=lambda r: -r["ms"])
    return rows


def category_table(rows):
    """Collapse an op_table into per-category totals. Span rows are
    dropped: a span is a host-side envelope AROUND the device ops
    already counted in the other categories — including it would
    double-count that time and deflate every real category's pct
    (span wall times live in span_table / singa_span_seconds)."""
    agg = defaultdict(lambda: [0.0, 0])
    for r in rows:
        if r["category"] == "span":
            continue
        agg[r["category"]][0] += r["total_ms"]
        agg[r["category"]][1] += r["count"]
    grand = sum(v[0] for v in agg.values()) or 1
    out = [
        {"category": c, "total_ms": ms, "count": n,
         "pct": 100.0 * ms / grand}
        for c, (ms, n) in agg.items()
    ]
    out.sort(key=lambda r: -r["total_ms"])
    return out


def format_table(rows, top: int = 25) -> str:
    lines = [f"{'op':<56} {'cat':<10} {'total_ms':>9} {'count':>6} "
             f"{'avg_us':>9} {'pct':>6}"]
    for r in rows[:top]:
        lines.append(
            f"{r['op'][:56]:<56} {r['category']:<10} {r['total_ms']:>9.3f} "
            f"{r['count']:>6} {r['avg_us']:>9.1f} {r['pct']:>5.1f}%")
    rest = rows[top:]
    if rest:
        ms = sum(r["total_ms"] for r in rest)
        pct = sum(r["pct"] for r in rest)
        lines.append(f"{'... ' + str(len(rest)) + ' more':<56} {'':<10} "
                     f"{ms:>9.3f} {'':>6} {'':>9} {pct:>5.1f}%")
    return "\n".join(lines)
