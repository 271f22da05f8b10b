"""From a profiler trace (.xplane.pb) to device busy time, idle gaps and the
device-operation table. Reads the trace with jax.profiler.ProfileData only.

The arithmetic works on plain tuples so that tests can feed it hand-made
events: an event is (name, start_s, end_s).
"""

import glob
import os

SPAN_PREFIX = "singa.span/"
WINDOW_SPAN = "benchmark.traced"   # run.py's annotation around the traced stretch
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"


def union_seconds(events, lo=None, hi=None):
    """Length of the union of the events' intervals, clipped to [lo, hi]."""
    total, end = 0.0, None
    for _n, s, e in sorted(events, key=lambda ev: ev[1]):
        s = s if lo is None else max(s, lo)
        e = e if hi is None else min(e, hi)
        if e <= s:
            continue
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def idle_gaps(events, lo, hi):
    """[(start, end)] inside [lo, hi] covered by no event."""
    gaps, cur = [], lo
    for _n, s, e in sorted(events, key=lambda ev: ev[1]):
        if s > cur:
            gaps.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        gaps.append((cur, hi))
    return [(a, b) for a, b in gaps if b > a]


def self_times(events):
    """{name: seconds} with each event's time less that of the events
    nested inside it (a `while` does not count its body twice)."""
    out, stack = {}, []   # stack of [name, end, self]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            n, _e, t = stack.pop()
            out[n] = out.get(n, 0.0) + t

    for n, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([n, e, e - s])
    close(float("inf"))
    return out


def name_gap(gap, spans):
    """What the host was doing in an idle gap: the innermost program span
    that covers the gap's middle, else `after:` the span that ended last
    before it, else `none`."""
    mid = 0.5 * (gap[0] + gap[1])
    over = [sp for sp in spans if sp[1] <= mid < sp[2]]
    if over:
        return min(over, key=lambda sp: sp[2] - sp[1])[0]
    before = [sp for sp in spans if sp[2] <= mid]
    return "after:" + max(before, key=lambda sp: sp[2])[0] if before \
        else "none"


def summarize(device_events, spans, lo, hi, top=10):
    """The reduction, on one chip's events. `device_events` hold every
    device operation; `spans` the program's host spans on the same clock."""
    busy = union_seconds(device_events, lo, hi)
    inside = [(n, max(s, lo), min(e, hi)) for n, s, e in device_events
              if min(e, hi) > max(s, lo)]
    by_gap = {}
    for g in idle_gaps(inside, lo, hi):
        k = name_gap(g, spans)
        by_gap[k] = by_gap.get(k, 0.0) + g[1] - g[0]
    rank = lambda d: [[k, v] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    selfs = self_times(inside)
    calls = {}
    for n, _s, _e in inside:
        calls[n] = calls.get(n, 0) + 1
    return {"window_s": hi - lo, "busy_s": busy,
            "idle_share": 1.0 - busy / (hi - lo),
            "self_s": selfs, "calls": calls, "device_ops": rank(selfs),
            "idle_gaps": rank(by_gap)}


def op_name(event_name):
    """The trace names a device operation by its whole HLO instruction,
    `%fusion.3 = bf16[...] fusion(...)`: keep the instruction's name."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load(path):
    """({device plane name: [event]}, [host span], (lo, hi) of WINDOW_SPAN
    or None). Times in seconds on the trace's clock."""
    from jax.profiler import ProfileData
    devices, spans, window = {}, [], None
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (op_name(ev.name), ev.start_ns * 1e-9,
                         ev.end_ns * 1e-9) for ev in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                    elif ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name[len(SPAN_PREFIX):],
                                      ev.start_ns * 1e-9, ev.end_ns * 1e-9))
    return devices, spans, window


def reduce(trace_dir):
    """The summary of the fullest-traced run under `trace_dir`, busy time
    averaged over the chips; None when there is nothing to read."""
    path = find_xplane(trace_dir)
    if path is None:
        return None
    devices, spans, window = load(path)
    if not devices:
        return None
    if window is None:
        evs = [e for d in devices.values() for e in d]
        window = (min(e[1] for e in evs), max(e[2] for e in evs))
    per_chip = [summarize(evs, spans, *window) for evs in devices.values()]
    out = dict(per_chip[0])     # tables from the first chip
    out["busy_s"] = sum(c["busy_s"] for c in per_chip) / len(per_chip)
    out["idle_share"] = 1.0 - out["busy_s"] / out["window_s"]
    out["chips"] = len(per_chip)
    return out
