#!/usr/bin/env python
"""Lint: every metric registered anywhere in the package obeys the naming
contract.

Walks singa_tpu/ (plus the top-level bench drivers) with `ast`, collects
every call of the form `<registry|observe>.counter("name", ...)` /
`.gauge(...)` / `.histogram(...)` — and bare `counter("name")` etc. from
`from ... import counter` style — whose first argument is a string
literal, then fails if

  1. a name does not match ^singa_[a-z0-9_]+$, or
  2. the same name is registered under two different metric types
     (the runtime registry raises on this too; the lint catches it
     before any code runs), or
  3. a counter's name does not end in `_total` (the Prometheus counter
     convention — dashboards and recording rules key on it), or
  4. the same non-empty help string is registered for two DIFFERENT
     metric names (copy-pasted helps make /metrics output ambiguous;
     every name must describe itself), or
  5. a `reason=` / `phase=` / `bucket=` / `region=` / `op=` /
     `outcome=` / `objective=` / `kv_dtype=` / `verdict=` /
     `replica=` / `attr=` / `decision=` / `leg=` / `cause=` /
     `result=` / `source=` / `where=` label value on a metric record call
     (.inc/.set/.observe/.dec) does not come from a declared enum: these
     labels are CONTRACTUALLY low-cardinality (introspect.py's
     RECOMPILE_REASONS / COMPILE_PHASES, goodput.py's GOODPUT_BUCKETS,
     memory.py's MEM_REGIONS, watchdog.py's DEADLINE_OPS, observe.py's
     COMM_OPS, engine.py's REQUEST_OUTCOMES and KV_DTYPES, slo.py's
     REQUEST_PHASES / SLO_OBJECTIVES / LATENCY_ATTR — the tail
     counter's `attr=` values are exactly the latency-attribution
     buckets — serving.py's KV_DTYPES and
     SPEC_VERDICTS, router.py's ROUTE_REASONS / ROUTE_OUTCOMES /
     REPLICA_STATES / STARTUP_PHASES — the router's `reason=` values
     are exactly shed / replica_dead / drain / retry_exhausted, the
     cold-start histogram's `phase=` values are exactly
     STARTUP_PHASES, and `replica=`
     names are allowed only from functions guarding against
     REPLICA_STATES, i.e. the bounded replica registry, and
     capacity.py's SCALE_DECISIONS / DECISION_REASONS — the shadow
     scaler's `decision=` values are exactly scale_up / scale_down /
     hold and its `reason=` values the fixed reason-code enum — and
     audit.py's AUDIT_LEGS / AUDIT_VERDICTS — the correctness
     observatory's `leg=` values are exactly fingerprint / canary /
     replay and its `verdict=` values exactly match / mismatch /
     error — and regress.py's REGRESS_CAUSES — the regression
     observatory's `cause=` values are exactly compile /
     workload_shift / contention / host / unknown — and warmstart.py's
     CACHE_RESULTS — the warm-store lookup counter's `result=` values
     are exactly hit / miss / stale / corrupt — and introspect.py's
     XLA_COMPILE_SOURCES / XLA_COMPILE_WHERE — the compile histogram's
     `source=` values are exactly backend / cache and its `where=`
     values the declared span leaves plus other / none),
     so a string literal must be a
     member of a module-level ALL-CAPS tuple of string literals, a NAME
     must be a module-level constant whose value is a member, and a
     dynamic expression is allowed only inside a function that references
     the enum tuple (i.e. guards membership against it) — anything else
     could mint unbounded label values, or
  6. a `host=` label value on a metric record call is free-form: the
     fleet layer's host labels are CONTRACTUALLY bounded by the cluster
     topology, so a string literal is rejected outright and a dynamic
     value is allowed only inside a function that references
     `distributed.topology()` or `distributed.host_label()` (the only
     minters of host identities — same enclosing-guard style as rule 5).

Dynamic names (f-strings, e.g. observe.record_bench's singa_bench_* gauges)
cannot be checked statically; the runtime ValueError in observe._Metric covers
those. Run as a script (exit 1 on violations) or via
tests/test_metrics_lint.py in the tier-1 pass.
"""

from __future__ import annotations

import ast
import os
import re
import sys

NAME_RE = re.compile(r"^singa_[a-z0-9_]+$")
METRIC_FUNCS = {"counter", "gauge", "histogram"}

_HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(_HERE)
DEFAULT_PATHS = [os.path.join(ROOT, "singa_tpu")]


def iter_py_files(paths):
    for p in paths:
        if os.path.isfile(p) and p.endswith(".py"):
            yield p
        elif os.path.isdir(p):
            for dirpath, _dirs, files in os.walk(p):
                for f in sorted(files):
                    if f.endswith(".py"):
                        yield os.path.join(dirpath, f)


def _parse(path):
    with open(path, encoding="utf-8") as f:
        return ast.parse(f.read(), filename=path)


def registrations_in(path, tree=None):
    """Yield (name, metric_type, help_or_None, lineno) for literal metric
    registrations in one file. `help` is the second positional arg or the
    `help=` keyword when it is a string literal (dynamic helps are left
    to the runtime). Parse errors are a lint failure upstream (tier-1
    would catch them anyway), so let them raise."""
    if tree is None:
        tree = _parse(path)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if isinstance(func, ast.Attribute):
            fname = func.attr
        elif isinstance(func, ast.Name):
            fname = func.id
        else:
            continue
        if fname not in METRIC_FUNCS:
            continue
        if not node.args:
            continue
        first = node.args[0]
        if not (isinstance(first, ast.Constant)
                and isinstance(first.value, str)):
            continue
        help_node = node.args[1] if len(node.args) > 1 else next(
            (kw.value for kw in node.keywords if kw.arg == "help"), None)
        help_text = help_node.value \
            if (isinstance(help_node, ast.Constant)
                and isinstance(help_node.value, str)) else None
        yield first.value, fname, help_text, node.lineno


# Enum-guarded label kwargs: values must be provably low-cardinality
# (reason/phase: introspect.py's RECOMPILE_REASONS / COMPILE_PHASES and
# slo.py's REQUEST_PHASES; bucket: goodput.py's GOODPUT_BUCKETS;
# region: memory.py's MEM_REGIONS; op: watchdog.py's DEADLINE_OPS /
# observe.py's COMM_OPS; outcome: engine.py's REQUEST_OUTCOMES;
# objective: slo.py's SLO_OBJECTIVES; kv_dtype: serving.py's /
# engine.py's KV_DTYPES; verdict: serving.py's SPEC_VERDICTS;
# reason/outcome also: router.py's ROUTE_REASONS / ROUTE_OUTCOMES;
# phase also: router.py's STARTUP_PHASES (cold-start observatory);
# replica: router.py's bounded registry, guarded via REPLICA_STATES;
# attr: slo.py's LATENCY_ATTR (tail-latency attribution buckets);
# decision: capacity.py's SCALE_DECISIONS, with the shadow scaler's
# reason= values from capacity.py's DECISION_REASONS; leg: audit.py's
# AUDIT_LEGS, with the correctness observatory's verdict= values from
# audit.py's AUDIT_VERDICTS; cause: regress.py's REGRESS_CAUSES — the
# regression observatory's attributed-cause enum; result: warmstart.py's
# CACHE_RESULTS — the warm-store lookup classification
# hit|miss|stale|corrupt; source/where: introspect.py's
# XLA_COMPILE_SOURCES / XLA_COMPILE_WHERE — what jax's compile events
# are booked under).
ENUM_LABEL_KWARGS = ("reason", "phase", "bucket", "region", "op",
                     "outcome", "objective", "kv_dtype", "verdict",
                     "replica", "attr", "decision", "leg", "cause",
                     "result", "source", "where")
RECORD_FUNCS = {"inc", "set", "observe", "dec"}

# Rule 6: `host=` label values must originate in the cluster topology.
# These are the blessed minters (singa_tpu/distributed.py); a recording
# function must reference one of them (as a bare name or an attribute)
# to prove its host values came from there.
HOST_LABEL_KWARG = "host"
HOST_SOURCE_NAMES = ("host_label", "topology")


def _module_enum_info(tree):
    """(enums, consts): module-level ALL-CAPS `NAME = ("a", "b", ...)`
    tuples of string literals, and ALL-CAPS `NAME = "literal"` string
    constants."""
    enums = {}
    consts = {}
    for node in tree.body:
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            continue
        name = node.targets[0].id
        if not name.isupper():
            continue
        v = node.value
        if isinstance(v, ast.Tuple) and v.elts and all(
                isinstance(e, ast.Constant) and isinstance(e.value, str)
                for e in v.elts):
            enums[name] = tuple(e.value for e in v.elts)
        elif isinstance(v, ast.Constant) and isinstance(v.value, str):
            consts[name] = v.value
    return enums, consts


def label_enum_problems(tree):
    """Yield (lineno, message) for reason=/phase=/bucket= label values on
    metric record calls that cannot be traced to a declared enum tuple
    (rule 5 in the module docstring), and for `host=` label values that
    cannot be traced to the cluster topology (rule 6)."""
    enums, consts = _module_enum_info(tree)
    allowed = {v for vals in enums.values() for v in vals}
    out = []

    def fn_guards(fn):
        return any(isinstance(n, ast.Name) and n.id in enums
                   for n in ast.walk(fn))

    def fn_host_guards(fn):
        for n in ast.walk(fn):
            if isinstance(n, ast.Name) and n.id in HOST_SOURCE_NAMES:
                return True
            if isinstance(n, ast.Attribute) \
                    and n.attr in HOST_SOURCE_NAMES:
                return True
        return False

    def visit(node, guarded, host_guarded=False):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            guarded = guarded or fn_guards(node)
            host_guarded = host_guarded or fn_host_guards(node)
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in RECORD_FUNCS):
            for kw in node.keywords:
                if kw.arg == HOST_LABEL_KWARG:
                    v = kw.value
                    if isinstance(v, ast.Constant) \
                            and isinstance(v.value, str):
                        out.append((
                            v.lineno,
                            f"host= label value {v.value!r} is a "
                            "free-form literal; host labels must come "
                            "from distributed.topology() / "
                            "host_label()"))
                    elif not host_guarded:
                        out.append((
                            v.lineno,
                            "host= label value is dynamic and the "
                            "enclosing function does not reference "
                            "distributed.topology()/host_label() — "
                            "derive host identities from the cluster "
                            "topology"))
                    continue
                if kw.arg not in ENUM_LABEL_KWARGS:
                    continue
                v = kw.value
                if isinstance(v, ast.Constant) and isinstance(v.value, str):
                    if v.value not in allowed:
                        out.append((
                            v.lineno,
                            f"{kw.arg}= label value {v.value!r} is not a "
                            "member of any declared enum tuple (e.g. "
                            "RECOMPILE_REASONS / COMPILE_PHASES)"))
                elif isinstance(v, ast.Name) and v.id in consts:
                    if consts[v.id] not in allowed:
                        out.append((
                            v.lineno,
                            f"{kw.arg}= label constant {v.id} = "
                            f"{consts[v.id]!r} is not a member of any "
                            "declared enum tuple"))
                elif not guarded:
                    out.append((
                        v.lineno,
                        f"{kw.arg}= label value is dynamic and the "
                        "enclosing function does not reference a "
                        "declared enum tuple (guard membership against "
                        "it, e.g. `assert x in COMPILE_PHASES`)"))
        for child in ast.iter_child_nodes(node):
            visit(child, guarded, host_guarded)

    visit(tree, False)
    return out


def check(paths=None):
    """Return a list of violation strings (empty = clean)."""
    problems = []
    seen = {}       # name -> (type, file, line)
    help_seen = {}  # help text -> (name, file, line)
    for path in iter_py_files(paths or DEFAULT_PATHS):
        rel = os.path.relpath(path, ROOT)
        tree = _parse(path)
        for line, msg in label_enum_problems(tree):
            problems.append(f"{rel}:{line}: {msg}")
        for name, mtype, help_text, line in registrations_in(path, tree):
            if not NAME_RE.match(name):
                problems.append(
                    f"{rel}:{line}: metric name {name!r} does not match "
                    f"{NAME_RE.pattern}")
                continue
            if mtype == "counter" and not name.endswith("_total"):
                problems.append(
                    f"{rel}:{line}: counter {name!r} must end in '_total' "
                    "(Prometheus counter convention)")
            prev = seen.get(name)
            if prev is None:
                seen[name] = (mtype, rel, line)
            elif prev[0] != mtype:
                problems.append(
                    f"{rel}:{line}: metric {name!r} registered as {mtype} "
                    f"but already a {prev[0]} at {prev[1]}:{prev[2]}")
            if help_text:
                hprev = help_seen.get(help_text)
                if hprev is None:
                    help_seen[help_text] = (name, rel, line)
                elif hprev[0] != name:
                    problems.append(
                        f"{rel}:{line}: metric {name!r} reuses the help "
                        f"string of {hprev[0]!r} ({hprev[1]}:{hprev[2]}); "
                        "help strings must be unique per metric")
    return problems


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    problems = check(argv or None)
    for p in problems:
        print(p, file=sys.stderr)
    if problems:
        print(f"{len(problems)} metric-name violation(s)", file=sys.stderr)
        return 1
    print("metric names OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
