"""Compile & memory introspection: recompile blame, AOT cost/memory
telemetry, and the `explain` report.

On TPUs the two dominant invisible costs are XLA compilation and HBM.
PR 1 *counts* recompiles (`singa_model_recompile_total`) without saying
why one happened, and nothing reported flops/step or the HBM breakdown —
the "fast as the hardware allows" goal was unmeasurable. This module is
the build-time half of observability, in three parts:

1. **Recompile blame.** Every AOT build records the executable's abstract
   call signature (leaf shapes/dtypes, step tag, static args, donation
   set). When a later build for the same key arrives, the new signature
   is diffed against the nearest prior one and a structured reason is
   emitted — `singa_recompile_total{reason=...}` with a FIXED
   low-cardinality enum (`RECOMPILE_REASONS`) plus a detail string
   ("arg `arg0` batch 32->48 crossed bucket 32->64") into the EventLog.

2. **AOT cost/memory telemetry.** `build_compiled` routes a jitted
   callable through the explicit `trace -> lower -> compile` stages,
   timing each phase into `singa_compile_phase_seconds{phase=...}`, and
   harvests `compiled.cost_analysis()` / `memory_analysis()` into
   `singa_xla_flops_per_step`, `singa_xla_bytes_accessed` and the
   `singa_hbm_{arguments,outputs,temps,generated_code}_bytes` gauges.
   The step build also populates `Device.cost_analysis` (un-deadening
   `PrintTimeProfiling` verbosity>=2) and registers a per-step callback
   that derives `singa_mfu_pct` from the platform peak-flops table
   (override: `set_peak_tflops` / `SINGA_TPU_PEAK_TFLOPS` /
   `config.PEAK_TFLOPS`). All of this happens at build/retrace time —
   the cached step path dispatches the same executable bytes `jax.jit`
   would have cached, with zero added per-step work.

3. **`explain` report.** `python -m singa_tpu.introspect` (a few preset
   models) prints params, GFLOPs/step, the HBM breakdown, compile-phase
   times, recompile history, and — given an xplane dir — the top-K ops
   by device time (`xprof.top_ops`).
   `capture_hlo(dir)` additionally dumps each executable's HLO text
   (manifest + fingerprint); FlightRecorder bundles reference the
   manifest so an anomaly dump pins the exact executable.

4. **Warm staging (singa_tpu.warmstart).** When the warm store is
   enabled (`SINGA_TPU_COMPILE_CACHE` / `warmstart.enable`),
   `build_compiled` looks the (key, signature-fingerprint) pair up in
   the serialized-executable store before staging
   (`load_executable`) and, on a fresh build, exports the jitted
   callable into it (`export_executable`). Both cold and warm builds
   then stage through the exported module's round-trip, so the XLA
   persistent cache key is identical across process lifetimes — a
   restarted replica's "compile" is a disk read. Every lookup result
   (hit|miss|stale|corrupt) is counted, recorded on the build record,
   and emitted with the compile/recompile EventLog record. With the
   store disabled (the default) the staging path is bit-unchanged.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import threading
import time

from . import config, observe

# ---- enums (the lint in tools/check_metrics_names.py greps these) ---------

#: Low-cardinality blame reasons for `singa_recompile_total{reason=...}`.
#: batch_bucket: only a leading (batch) dim changed — the detail string
#:   names the power-of-two batch-size class crossed (PR 1's framing).
#: shape: a non-batch dim changed. dtype: a leaf dtype flipped.
#: new_step_tag: a different static step tag (DistOpt partial rotation).
#: static_args / arg_count / donation: the non-array signature changed.
#: new_function: an identical signature rebuilt from a fresh callable
#:   (e.g. a re-built serving decode fn for the same shapes).
#: unknown: none of the tracked fields differ — should not appear in
#:   practice; its presence is itself a signal the blame logic is blind.
RECOMPILE_REASONS = ("batch_bucket", "shape", "dtype", "new_step_tag",
                     "static_args", "arg_count", "donation",
                     "new_function", "unknown")
REASON_BATCH_BUCKET = "batch_bucket"
REASON_SHAPE = "shape"
REASON_DTYPE = "dtype"
REASON_NEW_STEP_TAG = "new_step_tag"
REASON_STATIC_ARGS = "static_args"
REASON_ARG_COUNT = "arg_count"
REASON_DONATION = "donation"
REASON_NEW_FUNCTION = "new_function"
REASON_UNKNOWN = "unknown"

#: Build phases for `singa_compile_phase_seconds{phase=...}`: trace (the
#: python step function -> jaxpr), lower (jaxpr -> StableHLO), compile
#: (the XLA backend build — on TPU by far the dominant term).
COMPILE_PHASES = ("trace", "lower", "compile")
PHASE_TRACE = "trace"
PHASE_LOWER = "lower"
PHASE_COMPILE = "compile"

#: Where a compile (or a read of the persistent cache) happened, for
#: `singa_xla_compile_seconds{where=...}`: the leaf of the span open on the
#: compiling thread when it is one of these; `other` under any other span,
#: `none` outside every span (a caller's own programs: the benchmark's
#: reference and checks). `compile` is the phase span of a staged build.
XLA_COMPILE_WHERE = ("model.init", "opt.setup", "model.create",
                     "model.build", "compile", "introspect.first_dispatch",
                     "model.jit_fallback", "model.step", "model.eval",
                     "data.wait", "tensor.fetch", "serving.engine_step",
                     "serving.engine_prefill", "other", "none")
WHERE_OTHER = "other"
WHERE_NONE = "none"
#: `source=` of the same histogram: the backend compiled the program, or
#: the persistent cache held it.
XLA_COMPILE_SOURCES = ("backend", "cache")
SOURCE_BACKEND = "backend"
SOURCE_CACHE = "cache"

#: Span leaves `setup_report` sums, each net of the others nested in it:
#: the spans set-up's work happens under, and the spans a compile can be
#: booked to (so that a build under `model.eval` is not counted twice).
SETUP_SPANS = ("model.create", "model.init", "opt.setup", "model.build",
               "introspect.warm_load", "introspect.build", "trace", "lower",
               "compile", "introspect.first_dispatch", "model.jit_fallback",
               "model.step", "model.eval", "data.wait", "tensor.fetch",
               "serving.engine_step", "serving.engine_prefill")

#: Executable keys (the `key=` label on the gauges/histograms above).
EXEC_KEYS = ("step", "eval", "serving.prefill", "serving.decode_scan",
             "serving.beam")

# ---- per-platform peaks (public spec sheets) -------------------------------

#: Dense bf16 peak TFLOP/s by TPU generation.
PEAK_TFLOPS_BF16 = [
    ("v6", 918.0), ("trillium", 918.0),
    ("v5p", 459.0),
    ("v5 lite", 197.0), ("v5e", 197.0), ("v5litepod", 197.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
]

#: HBM bandwidth GB/s by generation (roofline readouts).
PEAK_HBM_GBS = [
    ("v6", 1638.0), ("trillium", 1638.0),
    ("v5p", 2765.0),
    ("v5 lite", 819.0), ("v5e", 819.0), ("v5litepod", 819.0),
    ("v4", 1228.0),
    ("v3", 900.0),
    ("v2", 700.0),
]


def chip_peak(device_kind: str, table):
    """The table's peak for `device_kind`, or None for a kind no row
    names — an unlisted chip gets no MFU, never a neighbour's peak."""
    kind = (device_kind or "").lower()
    for key, peak in table:
        if key in kind:
            return peak
    return None


_peak_override: "float | None" = None


def set_peak_tflops(v: "float | None"):
    """Override the platform peak used by the MFU gauge (None = table)."""
    global _peak_override
    _peak_override = float(v) if v else None
    return _peak_override


def peak_tflops(device_kind: "str | None" = None) -> "float | None":
    """Peak TFLOP/s for MFU: explicit override > SINGA_TPU_PEAK_TFLOPS /
    config.PEAK_TFLOPS > the per-generation table for `device_kind`."""
    if _peak_override is not None:
        return _peak_override
    cfg = getattr(config, "PEAK_TFLOPS", None)
    if cfg:
        return float(cfg)
    kind = device_kind if device_kind is not None else _step_device_kind
    return chip_peak(kind or "", PEAK_TFLOPS_BF16)


# ---- state -----------------------------------------------------------------

MAX_HISTORY = 64

_history: dict = {}    # key -> [signature dicts]
_builds: dict = {}     # key -> [build records]
_blames: list = []     # chronological blame records
_manifest: list = []   # executable manifest ({key, fingerprint, hlo_path})
_hlo_dir: "str | None" = None
_step_flops = 0.0
_step_device_kind = ""


def reset():
    """Clear all introspection state (tests: the conftest metric-isolation
    fixture calls this next to MetricsRegistry.reset)."""
    global _hlo_dir, _step_flops, _step_device_kind, _peak_override
    _history.clear()
    _builds.clear()
    del _blames[:]
    del _manifest[:]
    _hlo_dir = None
    _step_flops = 0.0
    _step_device_kind = ""
    _peak_override = None
    observe.set_step_callback(None)


# ---- abstract call signatures ---------------------------------------------

def _aval(a):
    shape = getattr(a, "shape", None)
    dt = getattr(a, "dtype", None)
    return (tuple(shape) if shape is not None else (),
            str(dt) if dt is not None else type(a).__name__)


def signature(args, names=None, tag=None, static=None, donated=(),
              batch_hint=None):
    """Abstract call signature of a positional-arg tuple: one
    (name, shape, dtype) entry per array leaf (containers expand to
    `name0`, `name1`, ...), plus the non-array dimensions a retrace can
    key on — step tag, static-arg repr, donation set, and the true batch
    size (`batch_hint`) when the traced leading dim is a padded bucket."""
    import jax
    leaves = []
    seq = args if isinstance(args, (tuple, list)) else (args,)
    for i, a in enumerate(seq):
        nm = names[i] if names and i < len(names) else f"a{i}"
        if isinstance(a, (tuple, list, dict)):
            flat, _ = jax.tree_util.tree_flatten(a)
            for j, leaf in enumerate(flat):
                leaves.append((f"{nm}{j}",) + _aval(leaf))
        else:
            leaves.append((nm,) + _aval(a))
    return {"tag": tag, "static": static, "donated": tuple(donated),
            "leaves": leaves,
            "batch_hint": int(batch_hint) if batch_hint else None}


def _bucket(n) -> int:
    """Power-of-two batch-size class containing n (PR 1's batch_class)."""
    n = int(n)
    return n if n <= 1 else 1 << (n - 1).bit_length()


def blame(prev: dict, cur: dict):
    """Diff two signatures into (reason, detail). `reason` is always a
    member of RECOMPILE_REASONS; `detail` is the human-readable one-liner
    that lands in the EventLog record."""
    if prev.get("tag") != cur.get("tag"):
        return (REASON_NEW_STEP_TAG,
                f"step tag {prev.get('tag')}->{cur.get('tag')}")
    if prev.get("static") != cur.get("static"):
        return (REASON_STATIC_ARGS,
                f"static args {prev.get('static')}->{cur.get('static')}")
    if prev.get("donated") != cur.get("donated"):
        return (REASON_DONATION,
                f"donated argnums {prev.get('donated')}"
                f"->{cur.get('donated')}")
    pl = {n: (s, d) for n, s, d in prev["leaves"]}
    cl = {n: (s, d) for n, s, d in cur["leaves"]}
    if set(pl) != set(cl):
        added = sorted(set(cl) - set(pl))[:4]
        gone = sorted(set(pl) - set(cl))[:4]
        return (REASON_ARG_COUNT,
                f"{len(pl)}->{len(cl)} array args"
                + (f" (+{','.join(added)})" if added else "")
                + (f" (-{','.join(gone)})" if gone else ""))
    for n, cs, cd in cur["leaves"]:
        ps, pd = pl[n]
        if pd != cd:
            return REASON_DTYPE, f"arg `{n}` dtype {pd}->{cd}"
    for n, cs, cd in cur["leaves"]:
        ps, _pd = pl[n]
        if ps == cs:
            continue
        if ps and cs and len(ps) == len(cs) and ps[1:] == cs[1:]:
            ho = prev.get("batch_hint") or ps[0]
            hn = cur.get("batch_hint") or cs[0]
            bo, bn = _bucket(ho), _bucket(hn)
            if bo != bn:
                return (REASON_BATCH_BUCKET,
                        f"arg `{n}` batch {ho}->{hn} "
                        f"crossed bucket {bo}->{bn}")
            return (REASON_BATCH_BUCKET,
                    f"arg `{n}` batch {ho}->{hn} within bucket {bn}")
        return REASON_SHAPE, f"arg `{n}` shape {ps}->{cs}"
    return (REASON_NEW_FUNCTION,
            "identical signature rebuilt from a fresh callable")


def _nearest(history, sig):
    """The prior signature with the fewest differences from `sig`, so the
    blame names what actually changed rather than diffing against an
    arbitrary ancestor (e.g. a long-gone step tag)."""
    best, best_score = None, None
    for prev in reversed(history):
        score = 0
        if prev.get("tag") != sig.get("tag"):
            score += 100
        if prev.get("static") != sig.get("static"):
            score += 100
        pl = {n: (s, d) for n, s, d in prev["leaves"]}
        cl = {n: (s, d) for n, s, d in sig["leaves"]}
        score += 10 * len(set(pl) ^ set(cl))
        score += sum(1 for n in set(pl) & set(cl) if pl[n] != cl[n])
        if best_score is None or score < best_score:
            best, best_score = prev, score
            if score == 0:
                break
    return best


# ---- metric plumbing (enum-guarded: see tools/check_metrics_names.py) -----

def _count_recompile(reason, key):
    if reason not in RECOMPILE_REASONS:
        reason = REASON_UNKNOWN
    if observe.is_enabled():
        observe.counter(
            "singa_recompile_total",
            "retraces after the first compile, by structured blame reason"
        ).inc(reason=reason, key=key)


def _observe_phase(phase, key, seconds):
    assert phase in COMPILE_PHASES, phase
    if observe.is_enabled():
        observe.histogram(
            "singa_compile_phase_seconds",
            "AOT build wall seconds per phase (trace|lower|compile)"
        ).observe(seconds, phase=phase, key=key)


def compile_phase_totals() -> dict:
    """{phase: total wall seconds} accumulated so far in
    singa_compile_phase_seconds, summed across build keys — the
    replica cold-start observatory diffs two samples of this to know
    how much of a startup window went to trace/lower/compile (vs the
    python-side model build around them). Zeros before any build (or
    with observe disabled)."""
    out = {p: 0.0 for p in COMPILE_PHASES}
    h = observe.get_registry().get("singa_compile_phase_seconds")
    if h is None:
        return out
    for row in h.snapshot():
        ph = (row.get("labels") or {}).get("phase")
        if ph in out:
            out[ph] += float(row.get("sum") or 0.0)
    return out


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_compiling = threading.local()


def _on_jax_duration(event, seconds, **_kw):
    """jax.monitoring listener: one observation of
    `singa_xla_compile_seconds` a program jax asked its backend for. jax
    times every such request under `_COMPILE_EVENT`, a program found in
    the persistent cache too; it reports the cache's read first, from
    inside the request and on the same thread, which is how the two are
    told apart. The seconds are the request's, either way."""
    if event == _CACHE_EVENT:
        _compiling.from_cache = True
        return
    if event != _COMPILE_EVENT:
        return
    source = SOURCE_CACHE if getattr(_compiling, "from_cache", False) \
        else SOURCE_BACKEND
    _compiling.from_cache = False
    if not observe.is_enabled():
        return
    path = observe.current_span()
    where = path.rsplit("/", 1)[-1] if path else WHERE_NONE
    if where not in XLA_COMPILE_WHERE:
        where = WHERE_OTHER
    observe.histogram(
        "singa_xla_compile_seconds",
        "wall seconds of each program jax had compiled or read from the "
        "persistent cache, by source and by the span it fell under"
    ).observe(seconds, source=source, where=where)


if __name__ != "__main__":
    # once a process, in the module that stages every program (run as a
    # script, the canonical module that `__main__` hands over to listens)
    from jax import monitoring as _monitoring
    _monitoring.register_event_duration_secs_listener(_on_jax_duration)


def setup_report() -> dict:
    """Where set-up's seconds went, read from the registry and the build
    records as they stand (nothing is kept here):

    spans     {leaf: {"seconds", "count"}} over SETUP_SPANS, each span net
              of the SETUP_SPANS nested in it (goodput's rule: a child's
              whole time comes off its nearest listed ancestor), so the
              rows add up to wall time and not past it
    paths     the same by whole span path, for a reader that has to tell
              an `opt.setup` under `model.build` from one that is not
    builds    {key: {"builds", "trace", "lower", "compile"}}: the staged
              builds' phases, summed a key
    compiles  {where: {source: {"seconds", "count"}}} of
              singa_xla_compile_seconds
    """
    reg = observe.get_registry()
    rows = {}
    h = reg.get("singa_span_seconds")
    for row in (h.snapshot() if h is not None else ()):
        path = row["labels"].get("span", "")
        if path.rsplit("/", 1)[-1] in SETUP_SPANS:
            rows[path] = [float(row["sum"]), int(row["count"])]
    gross = {p: s for p, (s, _n) in rows.items()}
    for path, seconds in gross.items():
        parts = path.split("/")
        for i in range(len(parts) - 1, 0, -1):
            if parts[i - 1] in SETUP_SPANS:
                anc = "/".join(parts[:i])
                if anc in rows:     # else still open: nothing to net yet
                    rows[anc][0] -= seconds
                break
    spans = {}
    for path, (s, n) in rows.items():
        leaf = spans.setdefault(path.rsplit("/", 1)[-1],
                                {"seconds": 0.0, "count": 0})
        leaf["seconds"] += s
        leaf["count"] += n
    builds = {}
    for key, recs in _builds.items():
        b = builds[key] = {"builds": len(recs)}
        for ph in COMPILE_PHASES:
            b[ph] = sum(float(r["phases"].get(ph, 0.0)) for r in recs)
    compiles = {}
    h = reg.get("singa_xla_compile_seconds")
    for row in (h.snapshot() if h is not None else ()):
        lab = row["labels"]
        compiles.setdefault(lab.get("where", WHERE_NONE), {})[
            lab.get("source", SOURCE_BACKEND)] = {
                "seconds": float(row["sum"]), "count": int(row["count"])}
    return {"spans": spans,
            "paths": {p: {"seconds": s, "count": n}
                      for p, (s, n) in sorted(rows.items())},
            "builds": builds, "compiles": compiles}


def _set_hbm_gauges(mem, key):
    # spelled out (no loop over a name table) so the static metric-name
    # lint sees every registration
    if not observe.is_enabled():
        return
    if "arguments" in mem:
        observe.gauge("singa_hbm_arguments_bytes",
                      "executable argument-buffer bytes"
                      ).set(float(mem["arguments"]), key=key)
    if "outputs" in mem:
        observe.gauge("singa_hbm_outputs_bytes",
                      "executable output-buffer bytes"
                      ).set(float(mem["outputs"]), key=key)
    if "temps" in mem:
        observe.gauge("singa_hbm_temps_bytes",
                      "executable temporary-buffer bytes"
                      ).set(float(mem["temps"]), key=key)
    if "generated_code" in mem:
        observe.gauge("singa_hbm_generated_code_bytes",
                      "executable generated-code bytes"
                      ).set(float(mem["generated_code"]), key=key)


def note_step_flops(flops):
    """Record the flops of the step executable actually being dispatched
    (model.py calls this on variant switch), so MFU is computed with the
    running variant's flops rather than the most recently BUILT one —
    a partial-batch build must not skew later full-batch readings."""
    global _step_flops
    _step_flops = float(flops or 0.0)


def _mfu_callback(seconds):
    """Fed each step's wall seconds by observe.record_step (un-fenced
    dispatch time) and record_step_fenced (honest device latency, when
    verbosity profiling is on). Un-fenced dispatch on an async backend
    can return in microseconds while the device still computes; in
    steady state it converges to the true step time (the loop is
    device-throughput-bound), but a sample implying more than the
    hardware peak is physically impossible and is DROPPED rather than
    poisoning the gauge."""
    peak = peak_tflops(_step_device_kind)
    if not peak or not _step_flops or seconds <= 0:
        return
    mfu = _step_flops / seconds / 1e12 / peak * 100.0
    if mfu > 100.0 and _peak_override is None:
        return  # async-dispatch artifact, not physics
    observe.gauge(
        "singa_mfu_pct",
        "model flops utilization of the last step, percent of the "
        "platform bf16 peak (flops/step / step_seconds / peak)"
    ).set(mfu)


# ---- harvesting ------------------------------------------------------------

def _harvest_cost(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca or {})


def _harvest_memory(compiled, args) -> dict:
    mem = {}
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is not None:
        for field, name in (("argument_size_in_bytes", "arguments"),
                            ("output_size_in_bytes", "outputs"),
                            ("temp_size_in_bytes", "temps"),
                            ("generated_code_size_in_bytes",
                             "generated_code")):
            v = getattr(ma, field, None)
            if v is not None:
                mem[name] = int(v)
    if not mem.get("arguments"):
        # backends without memory stats: the argument bytes at least are
        # always derivable from the abstract inputs
        import jax
        flat, _ = jax.tree_util.tree_flatten(args)
        mem["arguments"] = int(sum(
            int(getattr(a, "nbytes", 0) or 0) for a in flat))
    return mem


_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_HLO_ALL_REDUCE = re.compile(
    r"^\s+(?:ROOT )?%?[\w.\-]+ = (.*?) all-reduce(-start)?\(")
_HLO_FUSION = re.compile(
    r"^\s+(?:ROOT )?%?([\w.\-]+) = .*? fusion\(.*\bcalls=%?([\w.\-]+)")
# an array in an HLO type: its dtype's bits (none for pred) and its dims
_HLO_ARRAY = re.compile(r"\b(?:pred|[a-z]+?(\d+)\w*)\[([\d,]*)\]")
# libtpu's name for the fusion that opens an asynchronous collective
# whose further steps ride inside the compute fusions after it
ASYNC_COLLECTIVE_START = "async-collective-start"


def _hlo_shape_bytes(shape: str) -> int:
    """Bytes of an HLO result type's arrays: `f32[1024,50257]{...}`, or a
    tuple of them."""
    total = 0
    for bits, dims in _HLO_ARRAY.findall(shape):
        n = int(bits or 8)
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n // 8
    return total


def all_reduce_summary(hlo_text: str) -> dict:
    """The all-reduces of one compiled (scheduled) module, each counted
    once in the form the compiler left it in: {"collectives", "async",
    "bytes", "async_bytes"}, bytes being the reduced results' sizes.

    Blocking: an `all-reduce(` instruction of the entry or of a loop's
    body; the core runs it and nothing else until it is done. One that
    carries `frontend_attributes={async_collective_name=...}` is blocking
    too: it WAS a start/done pair whose halves the scheduler put next to
    each other, and XLA joined them again (convert_async_collectives_to_
    sync keeps the start's name there).
    Asynchronous: an `all-reduce-start(` (its `-done` comes later); or,
    on a TPU, a fusion named `async-collective-start*` whose computation
    holds the all-reduce's first step: the further steps run inside the
    compute fusions scheduled between it and its `async-collective-done*`
    (the all-reduce instructions in those fused computations are the same
    reduction's pieces, and are not counted again)."""
    held = {}      # computation -> [(bytes, is a -start)] of its all-reduces
    fused = set()  # computations some fusion instruction calls
    opened = []    # computations the async-collective-start fusions call
    cur = None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _HLO_COMPUTATION.match(line)
            if m:
                cur = m.group(1)
            continue
        if " all-reduce" in line:
            m = _HLO_ALL_REDUCE.match(line)
            if m:
                held.setdefault(cur, []).append(
                    (_hlo_shape_bytes(m.group(1)), bool(m.group(2))))
                continue
        if " fusion(" in line:
            m = _HLO_FUSION.match(line)
            if m:
                fused.add(m.group(2))
                if m.group(1).startswith(ASYNC_COLLECTIVE_START):
                    opened.append(m.group(2))
    plain = [r for comp, rs in held.items() if comp not in fused
             for r in rs]
    in_fusions = [b for comp in opened for b, _ in held.get(comp, ())]
    paired = [b for b, start in plain if start]
    return {"collectives": len(plain) + len(in_fusions),
            "async": len(paired) + len(in_fusions),
            "bytes": sum(b for b, _ in plain) + sum(in_fusions),
            "async_bytes": sum(paired) + sum(in_fusions)}


def all_reduces_of(variant) -> dict:
    """`all_reduce_summary` of a built `AotVariant`: from its build's
    record where the build had the text in hand (HLO capture on), else
    from the executable's text, printed now."""
    return (variant.record or {}).get("all_reduces") \
        or all_reduce_summary(variant.run.as_text())


def _write_hlo(text, key, fingerprint):
    try:
        os.makedirs(_hlo_dir, exist_ok=True)
        safe = key.replace(".", "_").replace("/", "_")
        sha = hashlib.sha256(text.encode()).hexdigest()[:16]
        path = os.path.join(_hlo_dir, f"{safe}_{sha}.hlo.txt")
        if not os.path.exists(path):
            with open(path, "w", encoding="utf-8") as f:
                f.write(text)
        with open(os.path.join(_hlo_dir, "manifest.jsonl"), "a",
                  encoding="utf-8") as f:
            f.write(json.dumps(
                {"key": key, "fingerprint": fingerprint, "hlo_sha": sha,
                 "path": path, "ts": round(time.time(), 6)}) + "\n")
        return path
    except OSError:
        return None


def capture_hlo(dir_path: "str | None"):
    """Enable (path) or disable (None) per-executable HLO-text capture.
    Each later build writes `<key>_<sha>.hlo.txt` plus a `manifest.jsonl`
    line under the directory; the in-memory `executable_manifest()` (and
    through it every FlightRecorder bundle header) carries the paths."""
    global _hlo_dir
    _hlo_dir = str(dir_path) if dir_path else None
    return _hlo_dir


def executable_manifest():
    """Every AOT-built executable this process has seen: {key,
    fingerprint, hlo_path (when capture_hlo was on), ts}."""
    return [dict(e) for e in _manifest]


def latest_fingerprint(key: str) -> "str | None":
    """The newest manifest fingerprint for `key`, or None before any
    build. regress.py anchors each latency baseline to this: a baseline
    whose fingerprint no longer matches is compile-cause evidence, and
    only fingerprint-MATCHED baselines compare across restarts."""
    for e in reversed(_manifest):
        if e.get("key") == key:
            return e.get("fingerprint")
    return None


def last_build(key: str) -> "dict | None":
    """The most recent build record for `key` (phases, cost, memory,
    blame) — benchmark/run.py reads its `hlo_path`."""
    recs = _builds.get(key)
    return dict(recs[-1]) if recs else None


def blame_history():
    """Chronological recompile-blame records ({key, reason, detail, ...})."""
    return [dict(b) for b in _blames]


# ---- the AOT build ---------------------------------------------------------

def _sig_fingerprint(key: str, sig: dict) -> str:
    """16-hex fingerprint of (key, abstract call signature) — the
    identity executables are manifested, blamed, and warm-stored
    under. Deliberately signature-based rather than HLO-based: it must
    be computable BEFORE any staging, so a warm restart can look the
    store up without first paying the trace the store exists to skip."""
    return hashlib.sha256(
        (key + "|" + json.dumps(
            {"tag": sig.get("tag"), "static": sig.get("static"),
             "donated": list(sig.get("donated") or ()),
             "leaves": [[n, list(s), d] for n, s, d in sig["leaves"]]},
            sort_keys=True, default=str)).encode()).hexdigest()[:16]


def _stage(fn, args, compiler_options=None):
    """Explicit trace -> lower -> compile of one jitted callable, with
    per-phase wall timing. Raises whatever the staging machinery
    raises; callers decide the fallback. Each phase is a span too (a
    child of the caller's `introspect.build`), so a profiler trace holds
    the three as intervals. compiler_options: the
    program's own XLA options ({name: value}), handed to the compile
    whichever callable is staged: the warm store's deserialized module
    is a jit of its own and carries none."""
    t0 = time.perf_counter()
    with observe.span(PHASE_TRACE):
        traced = fn.trace(*args)
    t1 = time.perf_counter()
    with observe.span(PHASE_LOWER):
        lowered = traced.lower()
    t2 = time.perf_counter()
    with observe.span(PHASE_COMPILE):
        compiled = lowered.compile(compiler_options=compiler_options)
    t3 = time.perf_counter()
    return compiled, {"trace": t1 - t0, "lower": t2 - t1,
                      "compile": t3 - t2}


# Typed-key blob framing. jax.export serializes an extended PRNG-key
# dtype (`key<fry>`), but the deserialized module does not stage with
# device-committed arguments: `Exported.call` pins each argument with a
# sharding constraint of the key's LOGICAL rank on its physical
# `tensor<2xui32>` ("sharding doesn't match tensor rank: 0 != 1", jax
# 0.9.0), and every training step threads a committed dev.rng_state — so
# no train step would ever warm-hit. The bridge exports an adapter that
# speaks raw uint32 key-data at the boundary (wrap_key_data on the way
# in, key_data on the way out) and frames the blob with the key
# positions so deserialization rebuilds a transparent wrapper: the
# caller still passes/receives typed keys and never sees the framing.
_KEY_BLOB_MAGIC = b"SGXK1"


def _key_leaves(tree):
    """[(flat_leaf_index, impl_name), ...] for every typed-PRNG-key
    leaf of `tree` (works on concrete arrays and eval_shape structs)."""
    import jax
    out = []
    for i, leaf in enumerate(jax.tree_util.tree_leaves(tree)):
        dt = getattr(leaf, "dtype", None)
        if dt is not None and jax.dtypes.issubdtype(dt, jax.dtypes.prng_key):
            out.append((i, str(dt._impl.name)))
    return out


def _serialize_executable(fn, args) -> "bytes | None":
    """`jax.export` blob (StableHLO) of jitted `fn` specialized to the
    concrete `args` tuple, or None when the function resists exporting
    (e.g. unserializable custom calls): the caller then builds fresh and
    skips the store write. Typed PRNG keys in the signature are bridged
    to raw key-data (see _KEY_BLOB_MAGIC above); note the adapter is a
    plain jit, so buffer donation declared on `fn` does not survive into
    the stored module."""
    import jax
    from jax import export as jexport
    try:
        keys_in = _key_leaves(args)
        out_sds = jax.eval_shape(fn, *args)
        keys_out = _key_leaves(out_sds)
        if not keys_in and not keys_out:
            return jexport.export(fn)(*args).serialize()
        in_td = jax.tree_util.tree_structure(tuple(args))
        out_td = jax.tree_util.tree_structure(out_sds)

        def adapter(*raw):
            ls = list(jax.tree_util.tree_leaves(raw))
            for i, impl in keys_in:
                ls[i] = jax.random.wrap_key_data(ls[i], impl=impl)
            out = fn(*jax.tree_util.tree_unflatten(in_td, ls))
            ols = list(jax.tree_util.tree_leaves(out))
            for i, _impl in keys_out:
                ols[i] = jax.random.key_data(ols[i])
            return jax.tree_util.tree_unflatten(out_td, ols)

        raw_leaves = list(jax.tree_util.tree_leaves(tuple(args)))
        for i, _impl in keys_in:
            raw_leaves[i] = jax.random.key_data(raw_leaves[i])
        raw_args = jax.tree_util.tree_unflatten(in_td, raw_leaves)
        fb = jexport.export(jax.jit(adapter))(*raw_args).serialize()
        header = json.dumps(
            {"keys_in": keys_in, "keys_out": keys_out}).encode("utf-8")
        return (_KEY_BLOB_MAGIC + len(header).to_bytes(4, "big")
                + header + fb)
    except Exception:
        return None


def _deserialize_executable(blob: bytes):
    """A fresh jit-wrapped callable over the deserialized exported
    module, or None when the blob does not deserialize — the warm store
    treats that as a corrupt entry. Staging the returned callable
    re-traces only the exported module's call wrapper
    (depth-independent), and its XLA cache key is stable across
    processes — the property the warm-start layer's cold path relies on
    by staging through this same round-trip. Key-framed blobs (see
    _KEY_BLOB_MAGIC) come back wrapped so the caller passes and receives
    typed PRNG keys exactly as it would with the original function."""
    import jax
    from jax import export as jexport
    try:
        if not blob.startswith(_KEY_BLOB_MAGIC):
            return jax.jit(jexport.deserialize(blob).call)
        off = len(_KEY_BLOB_MAGIC)
        n = int.from_bytes(blob[off:off + 4], "big")
        header = json.loads(blob[off + 4:off + 4 + n].decode("utf-8"))
        keys_in = [(int(i), str(impl)) for i, impl in header["keys_in"]]
        keys_out = [(int(i), str(impl)) for i, impl in header["keys_out"]]
        exp = jexport.deserialize(blob[off + 4 + n:])
    except Exception:
        return None

    def call(*a):
        ls = list(jax.tree_util.tree_leaves(a))
        td = jax.tree_util.tree_structure(tuple(a))
        for i, _impl in keys_in:
            ls[i] = jax.random.key_data(ls[i])
        out = exp.call(*jax.tree_util.tree_unflatten(td, ls))
        ols = list(jax.tree_util.tree_leaves(out))
        otd = jax.tree_util.tree_structure(out)
        for i, impl in keys_out:
            ols[i] = jax.random.wrap_key_data(ols[i], impl=impl)
        return jax.tree_util.tree_unflatten(otd, ols)

    return jax.jit(call)


def export_executable(fn, args, key, fingerprint) -> "bytes | None":
    """Serialize jitted `fn` specialized to the concrete `args` tuple
    and write it into the warm store under (key, fingerprint). Returns
    the blob, or None when the store is disabled, the function resists
    exporting, or the store write fails — in every case the caller
    simply proceeds without persistence."""
    from . import warmstart
    store = warmstart.get_store()
    if store is None:
        return None
    blob = _serialize_executable(fn, args)
    if blob is None:
        return None
    if store.save(key, fingerprint, blob) is None:
        return None
    return blob


def load_executable(key, fingerprint, *, count: bool = True):
    """Load + deserialize the warm-store entry for (key, fingerprint).
    Returns (callable, result, seconds): the callable is a jit-wrapped
    deserialized module ready for `_stage` (None unless `result` is
    "hit"), and result is a member of warmstart.CACHE_RESULTS — or
    (None, None, 0.0) with the store disabled. Integrity failures
    (unreadable meta, sha-256 mismatch, undeserializable blob) classify
    as corrupt; a meta whose fingerprint or jax version does not match
    classifies as stale; both delete the entry so the fresh rebuild
    re-exports a clean replacement. With count=False the caller records
    the classification itself (`build_compiled` does, after staging
    confirms the artifact actually compiles)."""
    from . import warmstart
    store = warmstart.get_store()
    if store is None:
        return None, None, 0.0
    t0 = time.perf_counter()
    blob, result = store.load(key, fingerprint)
    warm_fn = None
    if blob is not None:
        warm_fn = _deserialize_executable(blob)
        if warm_fn is None:
            result = warmstart.RESULT_CORRUPT
            store.discard(key, fingerprint)
    seconds = time.perf_counter() - t0
    if count:
        warmstart.note_lookup(key, fingerprint, result, seconds)
    return warm_fn, result, seconds


def build_compiled(fn, args, key, sig=None, device=None,
                   compiler_options=None):
    """Build `fn` (a jax.jit-wrapped callable) for `args` through the
    explicit trace -> lower -> compile stages, every compile under
    `compiler_options` (see `_stage`).

    Times each phase into `singa_compile_phase_seconds`, harvests cost /
    memory analysis into the `singa_xla_*` / `singa_hbm_*` gauges,
    registers the signature for recompile blame, and returns
    (compiled_executable, build_record). Returns (None, None) when AOT
    staging fails for any reason — the caller falls back to the plain jit
    call, so telemetry can never break dispatch.

    With the warm store enabled (singa_tpu.warmstart), staging goes
    through the serialized-executable layer: a warm build loads the
    stored blob and stages its deserialized module (near-zero trace;
    compile is an XLA persistent-cache disk hit), a cold build exports
    first and stages the same round-trip so the persistent cache is
    seeded under the process-stable module key, and any stale/corrupt
    entry — or a warm artifact that fails to stage — falls back to the
    fresh path and re-exports. The lookup classification lands on the
    build record (`warm`) and the EventLog compile record.
    """
    from . import warmstart
    if sig is None:
        sig = signature(args)
    fingerprint = _sig_fingerprint(key, sig)
    warmstart.maybe_enable_from_env()
    warm_result = None
    warm_fn = None
    load_s = 0.0
    if warmstart.is_enabled():
        # separate leaf span, also mapped to the goodput `compile`
        # bucket: a warm restart's disk time is still compile-bucket
        # time — there is just ~none of it
        with observe.span("introspect.warm_load", key=key):
            warm_fn, warm_result, load_s = load_executable(
                key, fingerprint, count=False)
    compiled = phases = None
    # span -> the goodput `compile` bucket (and nets out of any mapped
    # enclosing span, e.g. a first-call model.eval)
    with observe.span("introspect.build", key=key):
        if warm_fn is not None:
            try:
                compiled, phases = _stage(warm_fn, args, compiler_options)
            except Exception:
                # deserialized but will not stage on this backend: the
                # same trust verdict as a bad blob — drop the entry and
                # rebuild fresh below (which re-exports a replacement)
                warm_result = warmstart.RESULT_CORRUPT
                st = warmstart.get_store()
                if st is not None:
                    st.discard(key, fingerprint)
        if compiled is None and warmstart.is_enabled():
            # cold build WITH the store: export first, then stage the
            # deserialized round-trip — one compile that (a) proves the
            # stored blob reproduces, and (b) seeds the XLA persistent
            # cache with the exact module a warm restart stages (the
            # exported module's cache key is stable across processes;
            # the original python callable's is not)
            blob = export_executable(fn, args, key, fingerprint)
            rt = _deserialize_executable(blob) if blob else None
            if rt is not None:
                try:
                    compiled, phases = _stage(rt, args, compiler_options)
                except Exception:
                    compiled = None
        if compiled is None:
            try:
                compiled, phases = _stage(fn, args, compiler_options)
            except Exception:
                return None, None
    if warm_result is not None:
        warmstart.note_lookup(key, fingerprint, warm_result, load_s)
    _observe_phase(PHASE_TRACE, key, phases["trace"])
    _observe_phase(PHASE_LOWER, key, phases["lower"])
    _observe_phase(PHASE_COMPILE, key, phases["compile"])
    cost = _harvest_cost(compiled)
    mem = _harvest_memory(compiled, args)
    if observe.is_enabled():
        observe.gauge("singa_xla_flops_per_step",
                      "XLA cost-analysis flops of the compiled executable"
                      ).set(float(cost.get("flops", 0.0) or 0.0), key=key)
        observe.gauge("singa_xla_bytes_accessed",
                      "XLA cost-analysis bytes accessed per execution"
                      ).set(float(cost.get("bytes accessed", 0.0) or 0.0),
                            key=key)
        _set_hbm_gauges(mem, key)
    hlo_path = all_reduces = None
    if _hlo_dir:
        try:
            text = compiled.as_text()
        except Exception:
            text = None
        if text:
            hlo_path = _write_hlo(text, key, fingerprint)
            # while the text is here: a step of 24 layers is 10-15 MB of
            # it, a second or so to print again (all_reduces_of)
            all_reduces = all_reduce_summary(text)
    rec = {"key": key, "fingerprint": fingerprint, "phases": phases,
           "cost": cost, "memory": mem, "hlo_path": hlo_path,
           "all_reduces": all_reduces, "warm": warm_result,
           "ts": round(time.time(), 6)}
    _register_build(key, sig, rec, device=device)
    return compiled, rec


def _register_build(key, sig, rec, device=None):
    hist = _history.setdefault(key, [])
    recompile = bool(hist)
    reason = detail = None
    if recompile:
        reason, detail = blame(_nearest(hist, sig), sig)
        _count_recompile(reason, key)
        _blames.append({"key": key, "reason": reason, "detail": detail,
                        "fingerprint": rec["fingerprint"],
                        "ts": rec["ts"]})
        del _blames[:-4 * MAX_HISTORY]
    hist.append(sig)
    del hist[:-MAX_HISTORY]
    rec.update({"recompile": recompile, "reason": reason, "detail": detail})
    _builds.setdefault(key, []).append(rec)
    del _builds[key][:-MAX_HISTORY]
    _manifest.append({"key": key, "fingerprint": rec["fingerprint"],
                      "hlo_path": rec["hlo_path"], "ts": rec["ts"]})
    del _manifest[:-4 * MAX_HISTORY]
    if observe.is_enabled():
        observe.get_registry().emit({
            "kind": "recompile" if recompile else "compile",
            "key": key, "reason": reason, "detail": detail,
            "fingerprint": rec["fingerprint"],
            "phases": {k: round(v, 6) for k, v in rec["phases"].items()},
            "flops": rec["cost"].get("flops"),
            # warm-store classification (hit|miss|stale|corrupt), None
            # when the store is disabled — the recompile-blame EventLog
            # doubles as the warm-start audit trail
            "warm": rec.get("warm"),
        })
    if key == "step":
        global _step_flops, _step_device_kind
        _step_flops = float(rec["cost"].get("flops", 0.0) or 0.0)
        if device is not None:
            _step_device_kind = getattr(
                device.jax_device, "device_kind", "") or ""
            if rec["cost"]:
                # refresh on EVERY step build (not just the first): after
                # a retrace, PrintTimeProfiling must report the current
                # variant's flops, and an empty {} seeded by the model's
                # profiling fallback must not pin the field forever
                device.cost_analysis = dict(rec["cost"])
        if _step_flops > 0:
            observe.set_step_callback(_mfu_callback)


def _leaf_avals(args):
    """The default cache key: every array leaf's (shape, dtype)."""
    import jax
    return tuple(_aval(a) for a in jax.tree_util.tree_leaves(args))


class AotVariant:
    """What one abstract signature of an `AotExecutor` resolved to.

    run     the compiled executable, or None when plain jit owns the
            signature (staging failed, or the executable rejected a call)
    record  `build_compiled`'s record of it (phases, cost, memory, warm)
    flops   the record's cost-analysis flops; 0.0 once jit owns it
    fresh   True from the `prepare` that built it until its first dispatch
    cold    the next jit call of this signature traces and compiles
    """

    __slots__ = ("run", "record", "flops", "fresh", "cold")

    def __init__(self, run, record):
        self.run = run
        self.record = record
        self.flops = float(
            ((record or {}).get("cost") or {}).get("flops", 0) or 0)
        self.fresh = True
        self.cold = run is None


class AotExecutor:
    """The one place a compiled program is staged, cached and fallen back
    from. Wraps a jitted callable so every distinct abstract signature is
    built through `build_compiled` (phase timing, cost/memory harvest,
    recompile blame, the warm store) and later calls dispatch the cached
    executable. Falls back to the plain jit call when staging or dispatch
    fails — jit then (re)traces exactly as it always did; a failed
    signature is negative-cached so the fallback never re-pays staging a
    call — but never on an out-of-memory error: that dumps the forensics
    bundle and propagates.

    `ex(*args)` is `ex.dispatch(ex.prepare(*args), args)`; a caller that
    times the dispatch apart from the build, or needs what the dispatch
    resolved to (Model), makes the two calls itself.

    names / donated / tag / static / device go into every signature and
    build this executor registers, as `signature` and `build_compiled`
    take them. cache_key(args) is the per-call cache key: by default every
    leaf's aval; a caller whose signature can only change through a few of
    its arguments (the training step: inputs, and how many optimizer
    arrays) keys on those, so a cached call does O(inputs) host work.
    compiler_options are the XLA options `fn` was jitted with (the
    caller's `jax.jit(..., compiler_options=)`, so that the jit fall-back
    compiles under them too): every build compiles under them, and they
    are part of `static`, so the signature, its fingerprint and the warm
    store never serve a build made under other options."""

    __slots__ = ("fn", "key", "names", "donated", "tag", "static",
                 "device", "cache_key", "compiler_options", "_execs")

    def __init__(self, fn, key, names=None, donated=(), tag=None,
                 static=None, device=None, cache_key=_leaf_avals,
                 compiler_options=None):
        self.fn = fn
        self.key = key
        self.names = names
        # the jit's donate_argnums, recorded into every signature this
        # executor registers: donation is part of the compiled module's
        # identity (input-output aliasing), so the warm store must not
        # key a donated variant and an undonated one identically
        self.donated = tuple(donated)
        self.tag = tag
        self.compiler_options = dict(compiler_options or {})
        if self.compiler_options:
            static = (f"{static or ''} compiler_options="
                      f"{sorted(self.compiler_options.items())}")
        self.static = static
        self.device = device
        self.cache_key = cache_key
        self._execs = {}

    def __len__(self):
        """How many abstract signatures this executor has resolved."""
        return len(self._execs)

    def prepare(self, *args, batch_hint=None):
        """The variant `args` dispatch to, staged on first sight of their
        signature. One key and one dict lookup when it is cached.
        batch_hint: the true batch size when the traced leading dim is a
        padded bucket (recompile blame names the real sizes)."""
        k = self.cache_key(args)
        v = self._execs.get(k)
        if v is None:
            sig = signature(args, names=self.names, tag=self.tag,
                            static=self.static, donated=self.donated,
                            batch_hint=batch_hint)
            v = self._execs[k] = AotVariant(*build_compiled(
                self.fn, args, self.key, sig, device=self.device,
                compiler_options=self.compiler_options))
        return v

    def give_to_jit(self, variant):
        """Negative-cache a variant: plain jit owns its signature from now
        on (correctness over telemetry, and no rebuild-a-call churn); its
        next call compiles cold."""
        variant.run = None
        variant.flops = 0.0
        variant.cold = True

    def _oom(self, exc) -> bool:
        """Device allocator exhausted? Then a jit fallback would re-pay
        the same allocation and die the same way: dump the OOM forensics
        bundle (timeline, region breakdown, top-K arrays, executable
        manifest) and tell the caller to let it propagate."""
        from . import memory
        if not memory.is_resource_exhausted(exc):
            return False
        memory.handle_oom(exc, key=self.key)
        return True

    def dispatch(self, variant, args):
        """Run `args` through the variant `prepare` resolved them to. The
        first call of a new executable runs under a span of its own: a
        load of the program that the build put off shows there."""
        if variant.fresh:
            variant.fresh = False
            with observe.span("introspect.first_dispatch", key=self.key):
                return self.dispatch(variant, args)
        if variant.run is not None:
            try:
                return variant.run(*args)
            except Exception as exec_exc:
                if self._oom(exec_exc):
                    raise
                # the executable rejected the call (e.g. an argument
                # changed shape in a way the cache key cannot see)
                self.give_to_jit(variant)
        try:
            if variant.cold:
                # this jit call traces and compiles: the mapped span
                # books it to the goodput `compile` bucket instead of the
                # enclosing serving/step span
                variant.cold = False
                with observe.span("model.jit_fallback"):
                    return self.fn(*args)
            return self.fn(*args)
        except Exception as jit_exc:
            self._oom(jit_exc)
            raise

    def __call__(self, *args):
        return self.dispatch(self.prepare(*args), args)


# ---- the explain report ----------------------------------------------------

def explain(model=None, device=None, xplane=None, top=10) -> dict:
    """Gather everything this module knows into one report dict:
    per-key build records, recompile history, the executable manifest,
    and (given a model/device) params, GFLOPs/step, the HBM breakdown,
    mean step time, achieved TFLOP/s and MFU; with `xplane`, the top-K
    ops by measured device time."""
    import numpy as np
    rep = {
        "builds": {k: [dict(r) for r in v] for k, v in _builds.items()},
        "recompiles": blame_history(),
        "executables": executable_manifest(),
    }
    if model is not None:
        try:
            rep["params"] = int(sum(
                int(np.prod(t.shape)) if t.shape else 1
                for t in model.get_params().values()))
        except Exception:
            pass
    step = last_build("step")
    flops = 0.0
    if step:
        flops = float(step["cost"].get("flops", 0.0) or 0.0)
        rep["gflops_per_step"] = flops / 1e9
        rep["bytes_accessed_per_step"] = float(
            step["cost"].get("bytes accessed", 0.0) or 0.0)
        rep["hbm"] = dict(step.get("memory") or {})
        rep["compile_phases_s"] = {
            k: round(v, 6) for k, v in (step.get("phases") or {}).items()}
        rep["fingerprint"] = step.get("fingerprint")
    if device is not None and device.step_times:
        mean_s = sum(device.step_times) / len(device.step_times)
        rep["step_ms_mean"] = mean_s * 1e3
        if flops and mean_s > 0:
            ach = flops / mean_s / 1e12
            rep["achieved_tflops"] = ach
            peak = peak_tflops(
                getattr(device.jax_device, "device_kind", ""))
            if peak:
                rep["peak_tflops"] = peak
                rep["mfu_pct"] = ach / peak * 100.0
    if xplane:
        from . import xprof
        rep["top_ops"] = [
            {"op": r["op"], "category": r["category"],
             "total_ms": round(r["total_ms"], 3),
             "pct": round(r["pct"], 1)}
            for r in xprof.top_ops(xplane, top)]
    rep["setup"] = setup_report()
    # the dynamic half of the memory model (singa_tpu.memory): live
    # region breakdown when a ledger is installed, and the pre-flight
    # fit estimate combining this module's static analysis with the
    # ledger's measured param+opt bytes
    try:
        from . import memory
        led = memory.get_ledger()
        if led is not None and led.timeline:
            rep["mem_regions"] = dict(led.timeline[-1]["regions"])
        if model is not None:
            rep["memory_fit"] = memory.estimate_fit(model=model,
                                                    device=device)
    except Exception:
        pass
    return rep


def _mb(b):
    return f"{(b or 0) / 1e6:.2f} MB"


def _format_setup(setup: dict) -> list:
    """The "set-up" block of the explain page: a `setup_report`'s spans in
    SETUP_SPANS' order, then its compiles by where they fell."""
    spans, compiles = setup.get("spans") or {}, setup.get("compiles") or {}
    if not spans and not compiles:
        return []
    lines = ["set-up (seconds net of nested spans):"]
    for leaf in SETUP_SPANS:
        if leaf in spans:
            lines.append(f"  {leaf:<26} {spans[leaf]['seconds']:>9.3f}s "
                         f"x{spans[leaf]['count']}")
    for where in XLA_COMPILE_WHERE:
        for source, v in sorted((compiles.get(where) or {}).items()):
            lines.append(f"  xla {source:<7} under {where:<26} "
                         f"{v['seconds']:>9.3f}s x{v['count']}")
    return lines


def format_explain(rep: dict) -> str:
    lines = ["== singa_tpu introspect: compile & memory explain =="]
    if "params" in rep:
        lines.append(f"params: {rep['params'] / 1e6:.3f} M")
    if "gflops_per_step" in rep:
        lines.append(f"step executable [{rep.get('fingerprint', '?')}]: "
                     f"{rep['gflops_per_step']:.4f} GFLOP/step, "
                     f"{_mb(rep.get('bytes_accessed_per_step'))} accessed")
    ph = rep.get("compile_phases_s")
    if ph:
        lines.append("  compile phases: " + "  ".join(
            f"{p} {ph.get(p, 0.0):.3f}s" for p in COMPILE_PHASES))
    hbm = rep.get("hbm")
    if hbm:
        lines.append("  HBM: " + " | ".join(
            f"{k} {_mb(v)}" for k, v in sorted(hbm.items())))
    if "step_ms_mean" in rep:
        tail = ""
        if "achieved_tflops" in rep:
            tail = f" -> {rep['achieved_tflops']:.4f} TFLOP/s achieved"
            if "mfu_pct" in rep:
                tail += (f" (MFU {rep['mfu_pct']:.2f}% of "
                         f"{rep['peak_tflops']:g} peak)")
        lines.append(f"  step time: {rep['step_ms_mean']:.3f} ms mean"
                     + tail)
    for key, recs in sorted(rep.get("builds", {}).items()):
        if key == "step":
            continue
        r = recs[-1]
        fl = float(r["cost"].get("flops", 0.0) or 0.0)
        lines.append(f"{key} executable [{r['fingerprint']}]: "
                     f"{fl / 1e9:.4f} GFLOP, compile "
                     f"{r['phases'].get('compile', 0.0):.3f}s")
    mr = rep.get("mem_regions")
    if mr:
        live = " | ".join(f"{k} {_mb(v)}" for k, v in sorted(mr.items())
                          if v)
        lines.append(f"  live memory (ledger): {live or 'empty'}")
    fit = rep.get("memory_fit")
    if fit:
        lim = fit.get("limit_bytes")
        lines.append(
            f"  memory fit: est peak {_mb(fit['estimated_peak_bytes'])}"
            + (f" vs limit {_mb(lim)} -> "
               f"{'fits' if fit['fits'] else 'DOES NOT FIT'}"
               if lim else " (device limit unknown)"))
    lines.extend(_format_setup(rep.get("setup") or {}))
    blames = rep.get("recompiles", [])
    lines.append(f"recompile history ({len(blames)}):")
    for b in blames:
        lines.append(f"  [{b['key']}] {b['reason']}: {b['detail']}")
    execs = rep.get("executables", [])
    if execs:
        lines.append(f"executables ({len(execs)}):")
        for e in execs:
            lines.append(f"  {e['key']}@{e['fingerprint']}"
                         + (f"  hlo: {e['hlo_path']}" if e.get("hlo_path")
                            else ""))
    tops = rep.get("top_ops")
    if tops:
        lines.append(f"top {len(tops)} ops by device time (xplane):")
        for r in tops:
            lines.append(f"  {r['op'][:60]:<60} {r['total_ms']:>8.3f} ms "
                         f"{r['pct']:>5.1f}%")
    return "\n".join(lines)


# ---- CLI: python -m singa_tpu.introspect ----------------------------------

_CLI_PRESETS = {
    # name -> (create_model name, its arguments, batch, a sample's shape)
    "tiny": ("mlp", dict(data_size=16, num_classes=10), 8, (16,)),
    "mlp": ("mlp", dict(data_size=64, num_classes=10), 32, (64,)),
    "cnn": ("cnn", dict(num_channels=3), 4, (3, 28, 28)),
    "resnet18": ("resnet18", dict(num_channels=3), 4, (3, 32, 32)),
    "gpt": ("gpt", dict(vocab_size=8192, max_seq=64, dim=128, num_heads=4,
                        num_layers=2), 2, (64,)),
}


def _build_cli_model(cfg: str):
    """(model, inputs, targets) of one preset, on seeded random data."""
    import numpy as np
    from . import device, models, tensor
    name, kwargs, batch, shape = _CLI_PRESETS[cfg]
    dev = device.best_device()
    rng = np.random.RandomState(0)
    m = models.create_model(name, **kwargs)
    if name == "gpt":
        ids = rng.randint(0, kwargs["vocab_size"],
                          (batch,) + shape).astype(np.int32)
        return (m, tensor.from_numpy(ids, device=dev),
                tensor.from_numpy(np.roll(ids, -1, axis=1), device=dev))
    x = rng.standard_normal((batch,) + shape).astype(np.float32)
    y = rng.randint(0, 10, batch).astype(np.int32)
    return (m, tensor.Tensor(data=x, device=dev),
            tensor.from_numpy(y, device=dev))


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m singa_tpu.introspect",
        description="Compile & memory explain report: build a preset "
                    "model, run a few steps through the AOT-staged path, "
                    "and print GFLOPs/step, the HBM breakdown, "
                    "compile-phase times and the recompile history.")
    ap.add_argument("--config", default="tiny",
                    choices=sorted(_CLI_PRESETS))
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--no-retrace", dest="retrace", action="store_false",
                    default=True,
                    help="skip the 3/4-batch re-step that demonstrates "
                         "recompile blame")
    ap.add_argument("--xplane", default=None, metavar="DIR",
                    help="xplane trace dir: append the top-K ops by "
                         "measured device time (xprof.top_ops)")
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--hlo-dir", default=None, metavar="DIR",
                    help="capture each executable's HLO text + manifest")
    ap.add_argument("--peak-tflops", type=float, default=None,
                    help="override the platform peak for the MFU line")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    import numpy as np
    import jax
    from . import opt as opt_mod, tensor
    if args.peak_tflops:
        set_peak_tflops(args.peak_tflops)
    if args.hlo_dir:
        capture_hlo(args.hlo_dir)
    m, tx, ty = _build_cli_model(args.config)
    dev = tx.device
    m.set_optimizer(opt_mod.SGD(lr=0.1, momentum=0.9))
    m.compile([tx], is_train=True, use_graph=True)
    dev.SetVerbosity(1)
    dev.SetSkipIteration(0)
    for _ in range(max(args.steps, 1)):
        m(tx, ty)
    b = int(tx.shape[0])
    if args.retrace and b >= 4:
        nb = (3 * b) // 4
        x2 = np.asarray(jax.device_get(tx.data))[:nb]
        y2 = np.asarray(jax.device_get(ty.data))[:nb]
        m(tensor.Tensor(data=x2, device=dev),
          tensor.from_numpy(y2, device=dev))
    rep = explain(model=m, device=dev, xplane=args.xplane, top=args.top)
    if args.json:
        print(json.dumps(rep, default=str))
    else:
        print(format_explain(rep))
    return 0


__all__ = [
    "RECOMPILE_REASONS", "COMPILE_PHASES", "EXEC_KEYS",
    "PEAK_TFLOPS_BF16", "PEAK_HBM_GBS", "chip_peak",
    "set_peak_tflops", "peak_tflops",
    "signature", "blame", "build_compiled", "AotExecutor",
    "export_executable", "load_executable",
    "note_step_flops",
    "capture_hlo", "executable_manifest", "latest_fingerprint",
    "last_build", "blame_history",
    "compile_phase_totals", "setup_report",
    "XLA_COMPILE_WHERE", "XLA_COMPILE_SOURCES", "SETUP_SPANS",
    "explain", "format_explain", "reset", "main",
]

if __name__ == "__main__":
    import sys as _sys
    # run through the canonical package module so CLI state (hlo capture,
    # peak override) and the model's build records live in ONE instance
    from singa_tpu import introspect as _canonical
    _sys.exit(_canonical.main())
