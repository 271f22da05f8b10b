"""Device time by the program's own scopes.

The program names its device work: every instruction of a traced step
carries, in its `op_name`, the path of the layers it was recorded under
(`jit(step)/TransformerBlock_3/attn/jvp()/dot_general`; on the backward pass
behind a leading `bwd`, `jit(step)/bwd/TransformerBlock_3/fc1/transpose(
jvp())/dot_general`), and the optimizer's update, the health statistics and
the `amp` casts each sit under one fixed scope (`opt`, `health`, `amp_cast`).
The engine's programs put `prefill.b<bucket>` or `decode` above the same
layer names. The trace names a device operation by its HLO instruction, so,
like kernels.py, this reads the compiled text the program captured for the
run (introspect.capture_hlo) and sums the reduced trace's self times by
scope.

One executable at a time: instruction names repeat across executables. A
fusion counts under its own `op_name`, which is its root's;
`mixed_fusion_seconds` and `fused_elsewhere_seconds` say how much time that
can misplace.

    python3 benchmark/scopes.py benchmark/.out/<cell> [--key <executable>]

prints, from the trace and the HLO a `--trace 1` run left there, for every
captured executable that ran in the traced stretch (or those under `--key`:
`step`, `serving_engine_prefill`, `serving_engine_step`) device time by
scope (depth 2) and phase: ms a program, share of its busy time, calls. The
trace is split by the device plane's "XLA Modules" line, so each
executable's table holds its own operations only.
"""

import functools
import glob
import os
import re
import sys

GROUPS = ("backbone", "head_loss", "opt", "other", "unscoped")

# First component of a layer path -> group (`*`: any ending): the names
# get_params() keys the model's layers and tables by. Whatever else has a
# scope is `other`.
_GROUP_OF = (
    ("tok_embed", "backbone"), ("pos_embed", "backbone"),
    ("TransformerBlock_*", "backbone"), ("ln_f", "backbone"),
    ("head", "head_loss"), ("sce", "head_loss"),
    ("opt", "opt"),
)
# A component that decides the group wherever it stands in the path: the
# `amp` casts are recorded inside the layer whose parameter they cast.
_ANYWHERE = (("amp_cast", "other"),)
# The engine's programs put one scope of their own above the layers'.
_PROGRAM = re.compile(r"(prefill\.b\d+|decode)$")

_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")
_WRAPPER = re.compile(r"^(\w+)\((.*)\)$")
# Components jax's control flow writes into a name (a `lax.scan` body reads
# `while/body/closed_call/...`): wrappers like `jvp()`, not program scopes.
_CONTROL = frozenset(("while", "body", "cond", "closed_call", "checkpoint"))


def _split(path):
    """Components of a `/`-joined path, parentheses kept whole."""
    out, depth, cur = [], 0, ""
    for ch in path:
        if ch == "/" and depth == 0:
            out.append(cur)
            cur = ""
            continue
        depth += (ch == "(") - (ch == ")")
        cur += ch
    return out + [cur]


def _peel(component):
    """Plain components inside `component`: `transpose(jvp(a/b))` -> [a, b];
    `jit(f)` names a function, not a scope -> []; `x` -> [x]."""
    m = _WRAPPER.match(component)
    if not m:
        return [component] if component else []
    if m.group(1) in ("jit", "pjit"):
        return []
    return [c for part in _split(m.group(2)) for c in _peel(part)]


def parse_op_name(op_name):
    """(phase, scope path as a tuple) of one `op_name`: the primitive (last
    component) dropped, the `jit()`, `jvp()` and `transpose()` wrappers
    peeled. `bwd` where the name holds `transpose(` or the tape's leading
    `bwd`, else `fwd`. An empty path: no program scope in the name (a bare
    primitive, or the name of the argument a copy reads). Where XLA merged
    instructions it joins their names with `;`."""
    op_name = op_name.split(";")[0]    # instructions XLA merged: the first
    path = [c for part in _split(op_name)[:-1] for c in _peel(part)
            if c not in _CONTROL]
    bwd = "transpose(" in op_name
    if path and path[0] == "bwd":
        bwd, path = True, path[1:]
    return ("bwd" if bwd else "fwd"), tuple(path)


def layer_path(path):
    """`path` less an engine program's own leading scope (`prefill.b<bucket>`,
    `decode`): what is left reads like the step's paths."""
    return path[1:] if path and _PROGRAM.match(path[0]) else path


def group_of(path):
    """The group of a scope path; None (no `op_name`) and () are unscoped."""
    if not path:
        return "unscoped"
    path = layer_path(path)
    for name, group in _ANYWHERE:
        if name in path:
            return group
    for name, group in _GROUP_OF:
        if path and (path[0] == name or (
                name.endswith("*") and path[0].startswith(name[:-1]))):
            return group
    return "other"


def parse_hlo(text):
    """{instruction: {"phase", "path", "opcode", "computation", "entry",
    "calls"}} of one module's text. `path` is None where the instruction
    has no `op_name` (the compiler made it); parameters are left out (they
    never run)."""
    out, comp, entry = {}, None, False
    for line in text.splitlines():
        h = _HEADER.match(line)     # at column 0; instructions are indented
        if h:
            comp, entry = h.group(2), bool(h.group(1))
            continue
        m = _INSTR.match(line)
        if not m or comp is None:
            continue
        name, rest = m.groups()
        oc = _OPCODE.search(rest)
        opcode = oc.group(1) if oc else ""
        if opcode == "parameter":
            continue
        on = _OP_NAME.search(rest)
        phase, path = parse_op_name(on.group(1)) if on else ("fwd", None)
        calls = _CALLS.search(rest) if opcode == "fusion" else None
        out[name] = {"phase": phase, "path": path, "opcode": opcode,
                     "computation": comp, "entry": entry,
                     "calls": calls.group(1) if calls else None}
    return out


def texts(hlo_dir, key=None):
    """Paths of the texts captured under `key` (files `<key>_<sha>.hlo.txt`;
    every key when None), sorted."""
    return [p for p in sorted(glob.glob(os.path.join(hlo_dir, "*.hlo.txt")))
            if key is None or os.path.basename(p).rsplit("_", 1)[0] == key]


@functools.lru_cache(maxsize=None)   # the readers of one run ask alike
def instructions(hlo_dir, key="step"):
    """parse_hlo of one executable: the one captured under `key` in
    `hlo_dir`, or the text `hlo_dir` itself where that is a file. Raises
    ValueError when `key` has no text, or more than one (instruction names
    repeat across executables, so two texts cannot share one table), or
    when the text names no operation at all."""
    files = [hlo_dir] if os.path.isfile(hlo_dir) else texts(hlo_dir, key)
    if len(files) != 1:
        raise ValueError(f"{len(files)} executables captured under {key!r} "
                         f"in {hlo_dir}; need exactly one")
    with open(files[0], encoding="utf-8") as f:
        table = parse_hlo(f.read())
    if not any(i["path"] is not None for i in table.values()):
        raise ValueError(f"no instruction of {files[0]} carries an op_name")
    return table


def scope_map(hlo_dir, key="step"):
    """{instruction: (phase, scope path)} for the executable `key`."""
    return {n: (i["phase"], i["path"])
            for n, i in instructions(hlo_dir, key).items()}


def _group(table, name):
    """The group of a traced operation; one that the text does not name is
    unscoped, like an instruction with no `op_name` or no scope in it."""
    i = table.get(name)
    return group_of(i["path"] if i else None)


def _part(instr):
    """Which part of `unscoped` an instruction is: `not_in_hlo` (the trace
    names it, the text does not), `no_op_name` (the compiler made it),
    `no_scope` (an `op_name` with no program scope: the program's gap)."""
    if instr is None:
        return "not_in_hlo"
    return "no_op_name" if instr["path"] is None else "no_scope"


def _scoped(hlo_dir, key):
    """`instructions`, refusing (ValueError) a text with no program scope
    in it: a program from before the scopes, of which a share of 0 would
    be a claim."""
    table = instructions(hlo_dir, key)
    if not any(i["path"] for i in table.values()):
        raise ValueError(f"the text of {key!r} holds no program scope")
    return table


def scope_seconds(trace, hlo_dir, group, key="step"):
    """(seconds, calls) of the traced stretch spent in the instructions of
    `group`. Raises ValueError on a text with no program scope in it."""
    table = _scoped(hlo_dir, key)
    names = [n for n in trace["self_s"] if _group(table, n) == group]
    return (sum(trace["self_s"][n] for n in names),
            sum(trace["calls"].get(n, 0) for n in names))


def unscoped_parts(trace, hlo_dir, key="step"):
    """{part: [seconds, [(seconds, instruction)] largest first]} of group
    `unscoped` (see _part)."""
    table, out = instructions(hlo_dir, key), {}
    for n, t in trace["self_s"].items():
        if _group(table, n) == "unscoped":
            cur = out.setdefault(_part(table.get(n)), [0.0, []])
            cur[0] += t
            cur[1].append((t, n))
    for cur in out.values():
        cur[1].sort(reverse=True)
    return out


def programs_run(trace, hlo_dir, key="step"):
    """How often the executable `key` ran in the traced stretch, from the
    device plane's events: every instruction of its entry computation runs
    once a program, so the median of their event counts."""
    table = instructions(hlo_dir, key)
    counts = sorted(c for n, c in trace["calls"].items()
                    if n in table and table[n]["entry"])
    return counts[len(counts) // 2] if counts else 0


def mixed_fusion_seconds(trace, hlo_dir, key="step", among=GROUPS):
    """Seconds spent in fusions whose body holds scoped instructions of
    more than one of the groups `among`: how far counting a fusion under
    its root's scope can mislead. (An `amp` cast fused into the matmul it
    feeds makes that fusion one of two groups, `other` and the layer's.)"""
    table = instructions(hlo_dir, key)
    body = {}
    for i in table.values():
        if i["path"] and group_of(i["path"]) in among:
            body.setdefault(i["computation"], set()).add(group_of(i["path"]))
    return sum(t for n, t in trace["self_s"].items()
               if n in table and table[n]["calls"]
               and len(body.get(table[n]["calls"], ())) > 1)


def fused_elsewhere_seconds(trace, hlo_dir, group, key="step"):
    """(seconds, calls) of the fusions that are counted under another group
    (their root's) and hold instructions of `group` in their body: the most
    that counting at the root can have kept from `group`. Raises ValueError
    on a text with no program scope in it."""
    table = _scoped(hlo_dir, key)
    holds = {i["computation"] for i in table.values()
             if i["path"] and group_of(i["path"]) == group}
    names = [n for n in trace["self_s"]
             if n in table and table[n]["calls"] in holds
             and group_of(table[n]["path"]) != group]
    return (sum(trace["self_s"][n] for n in names),
            sum(trace["calls"].get(n, 0) for n in names))


def share(trace, seconds):
    """`seconds` as a share of the device's busy time, in percent."""
    return 100.0 * seconds / trace["busy_s"]


def reader(read):
    """A layer metric's `read(record, trace)` that returns None, and says
    why on stderr, where there is nothing to read: no trace or no busy time,
    no captured text (ValueError from `instructions`, OSError), a text
    without names or without scopes. A traced run's result line is built by
    the readers, so one that raises costs the cell its result; anything
    else is a fault of the reader and does raise, in benchmark/tests
    first."""
    @functools.wraps(read)
    def safe(record, trace):
        if not trace or not trace.get("busy_s") or not record.get("hlo_dir"):
            return None
        try:
            return read(record, trace)
        except (ValueError, OSError) as e:
            print(f"{read.__module__}: nothing to read "
                  f"({type(e).__name__}: {e})", file=sys.stderr)
            return None
    return safe


def share_reader(seconds, *args):
    """The reader of `seconds(trace, hlo_dir, *args)[0]` (scope_seconds,
    fused_elsewhere_seconds) as a share of the device's busy time."""
    def read(record, trace):
        return share(trace, seconds(trace, record["hlo_dir"], *args)[0])
    read.__module__ = f"scopes.{seconds.__name__}{args!r}"   # for stderr
    return reader(read)


# -- the command ---------------------------------------------------------------

MODULES_LINE = "XLA Modules"
TOP = 15


def by_module(ops, modules):
    """{module event name: [operation event]}: each of `ops` under the one
    of `modules` that holds its start (events are (name, start, end));
    None for an operation that no module event holds."""
    import bisect
    modules = sorted(modules, key=lambda m: m[1])
    starts, out = [m[1] for m in modules], {}
    for ev in ops:
        k = bisect.bisect_right(starts, ev[1]) - 1
        name = modules[k][0] if k >= 0 and ev[1] < modules[k][2] else None
        out.setdefault(name, []).append(ev)
    return out


def program_events(xplane):
    """by_module of the first chip's plane: the operations of
    trace_reduce.load under the events of the plane's "XLA Modules" line
    (`jit_step(<id>)`, one id an executable)."""
    from jax.profiler import ProfileData
    import trace_reduce
    devices, _spans, _window = trace_reduce.load(xplane)
    plane = min(devices)
    return by_module(devices[plane], [
        (ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
        for pl in ProfileData.from_file(xplane).planes if pl.name == plane
        for line in pl.lines if line.name == MODULES_LINE
        for ev in line.events])


def module_of(path, events):
    """The module event name under which the text `path` ran: its
    `HloModule` name before the id, and every operation seen under it named
    by the text (the buckets of one jitted function share the name and
    number their instructions differently). None: it did not run, or two
    module events fit and cannot be told apart."""
    with open(path, encoding="utf-8") as f:
        head = re.match(r"HloModule ([\w.\-]+)", f.readline())
    table = instructions(path)
    fits = [m for m, evs in events.items()
            if m and head and m.rsplit("(", 1)[0] == head.group(1)
            and all(n in table for n, _s, _e in evs)]
    return fits[0] if len(fits) == 1 else None


def table_lines(trace, hlo_dir, key="step", top=TOP):
    table = instructions(hlo_dir, key)
    steps = programs_run(trace, hlo_dir, key) or 1
    busy = trace["busy_s"]
    ms = lambda s: 1e3 * s / steps
    row = lambda name, s, calls="": \
        f"{name:<52} {ms(s):>10.3f} {100 * s / busy:>7.2f} {calls:>8}"
    lines = [f"{steps} programs in the traced stretch, busy {busy:.4f} s of "
             f"{trace['window_s']:.4f} s ({ms(busy):.3f} ms a program)",
             f"{'':<52} {'ms/program':>10} {'% busy':>7} {'calls':>8}",
             "-- by group"]
    for g in GROUPS:
        s, c = scope_seconds(trace, hlo_dir, g, key)
        lines.append(row(g, s, c))
    parts = unscoped_parts(trace, hlo_dir, key)
    for p in ("no_scope", "no_op_name", "not_in_hlo"):
        lines.append(row(f"  unscoped: {p}", parts.get(p, [0.0])[0]))
    kernel = {}     # the Pallas kernels, by the name their call gives them
    for n, t in trace["self_s"].items():
        i = table.get(n)
        if i and i["opcode"] == "custom-call" and i["path"] \
                and i["path"][-1].startswith("singa_"):
            cur = kernel.setdefault(f"{i['path'][-1]} {i['phase']}", [0.0, 0])
            cur[0] += t
            cur[1] += trace["calls"].get(n, 0)
    lines.append("-- kernels, inside their layers' groups")
    lines += [row(k, t, c) for k, (t, c) in sorted(kernel.items())]
    lines.append("-- fusions counted at their root")
    lines.append(row("holding two groups",
                     mixed_fusion_seconds(trace, hlo_dir, key)))
    lines.append(row("  the same, not counting group other (amp casts)",
                     mixed_fusion_seconds(
                         trace, hlo_dir, key,
                         [g for g in GROUPS if g != "other"])))
    lines.append(row("holding opt, counted under another group",
                     *fused_elsewhere_seconds(trace, hlo_dir, "opt", key)))

    def ranked(fold):
        """[((scope at depth 2, phase), [seconds, calls])], largest first;
        `fold` writes a trailing index of the first component as `*`, so
        that the blocks read as one."""
        by = {}
        for n, t in trace["self_s"].items():
            i = table.get(n)
            if i and i["path"]:
                p = layer_path(i["path"])[:2] or i["path"][:1]
                if fold:
                    p = (re.sub(r"_\d+$", "_*", p[0]),) + p[1:]
                k = ("/".join(p), i["phase"])
            else:
                k = (f"<{_part(i)}>", "")
            cur = by.setdefault(k, [0.0, 0])
            cur[0] += t
            cur[1] += trace["calls"].get(n, 0)
        return sorted(by.items(), key=lambda kv: -kv[1][0])

    for fold, title in ((True, "indexed layers folded"), (False, "as is")):
        lines.append(f"-- by scope (depth 2) and phase, {title}, top {top}")
        rows = ranked(fold)
        for (scope, phase), (s, c) in rows[:top]:
            lines.append(row(f"{scope} {phase}".rstrip(), s, c))
        rest = rows[top:]
        lines.append(row(f"({len(rest)} more)", sum(v[0] for _k, v in rest),
                         sum(v[1] for _k, v in rest)))
    for p, (_s, worst) in parts.items():
        lines.append(f"-- largest unscoped instructions: {p}")
        lines += [row("  " + n, t, trace["calls"].get(n, 0))
                  for t, n in worst[:5]]
    by_opcode = {}
    for t, n in parts.get("no_op_name", [0.0, []])[1]:
        cur = by_opcode.setdefault(table[n]["opcode"], [0.0, 0])
        cur[0] += t
        cur[1] += trace["calls"].get(n, 0)
    if by_opcode:
        lines.append("-- compiler-made instructions (no op_name) by opcode")
        lines += [row("  " + oc, t, c) for oc, (t, c) in
                  sorted(by_opcode.items(), key=lambda kv: -kv[1][0])[:6]]
    return lines


def main(argv):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("out_dir", help="benchmark/.out/<cell> of a --trace 1 run")
    ap.add_argument("--key", help="executable to read (default: each that ran)")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import trace_reduce
    xplane = trace_reduce.find_xplane(os.path.join(args.out_dir, "trace"))
    whole = trace_reduce.reduce(os.path.join(args.out_dir, "trace"))
    if not whole or not whole["busy_s"]:
        print(f"scopes.py: no device trace under {args.out_dir}/trace",
              file=sys.stderr)
        return 1
    _devices, spans, window = trace_reduce.load(xplane)
    events = program_events(xplane)
    print(f"device busy {whole['busy_s']:.4f} s of {whole['window_s']:.4f} s "
          f"traced; executables that ran: {len(events)}")
    seen = set()
    for path in texts(os.path.join(args.out_dir, "hlo"), args.key):
        name = os.path.basename(path)
        module = module_of(path, events)
        seen.add(module)
        if module is None:
            print(f"\n== {name}: did not run in the traced stretch")
            continue
        lo, hi = window or (min(e[1] for e in events[module]),
                            max(e[2] for e in events[module]))
        trace = trace_reduce.summarize(events[module], spans, lo, hi)
        scope = next((i["path"][0] for i in instructions(path).values()
                      if i["path"] and _PROGRAM.match(i["path"][0])), "")
        print(f"\n== {name} ({scope or 'no program scope'}) ran as {module}: "
              f"{100 * trace['busy_s'] / whole['busy_s']:.2f} % of busy time")
        print("\n".join(table_lines(trace, path)))
    for module in [] if args.key else sorted(set(events) - seen, key=str):
        s = trace_reduce.union_seconds(events[module], *(window or ()))
        print(f"\n== {module}: no captured text "
              f"({100 * s / whole['busy_s']:.2f} % of busy time)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
