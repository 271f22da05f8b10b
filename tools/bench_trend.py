#!/usr/bin/env python
"""Bench trend: the reader the BENCH_*.json trajectory never had.

Every round leaves `BENCH_rNN.json` / `BENCHDEC_rNN.json` /
`MULTICHIP_rNN.json` / `RESILIENCE_rNN.json` / `FLEET_rNN.json`
artifacts in the repo root, but nothing reads them ACROSS rounds — a
regression between round N and N+1 is invisible unless a human diffs
JSON by hand. This tool aggregates them into one trend table
(metric x round) and flags regressions beyond a threshold against the
BEST prior round, exiting non-zero so a CI step (or the tier-1 wrapper
test) fails on a measured slide.

Record formats tolerated (all of which exist in the repo today):
  - a single JSON object with "metric"/"value" (BENCH_r06 style),
  - JSONL, one such record per line (BENCHDEC style),
  - the early wrapper format {"n", "cmd", "rc", "tail", "parsed"} —
    `parsed` is used when it is a record; otherwise the round degrades
    to a synthetic `<family>_run_ok` 0/1 metric from `rc`,
  - harness records with an "ok" bool and no "metric"
    (MULTICHIP/RESILIENCE/FLEET style) -> `<family>_ok` 0/1.

Direction is inferred from the record's `unit` (or the metric name):
times ("s", "ms", "seconds", `*_ms`/`*_s` suffixes), memory
footprints ("bytes" unit, `*_bytes` suffix — MEM_r*.json's region
records), serving latencies (any metric naming `ttft` or a
`*_p50`/`*_p99` percentile — BENCHDEC_r06's engine TTFT records, even
when unit-less), and replica cold-start walls (any metric naming
`startup`/`cold`/`spawn` — SERVE_r*.json's replica_startup_total_s /
router_cold_spawn_first_token_s), shadow-scaler oscillation counts
(any metric naming `flap` or `decision_churn` — CAPACITY_r*.json's
capacity_decision_flaps), and correctness-observatory incident counts
(any metric naming `divergence`, `miscompare`, or `false_positive` —
AUDIT_r*.json's audit_divergence_count / audit_canary_miscompare_count
/ audit_false_positive_count, where more wrong-token incidents or
false alarms at the same injected fault is the regression), and the
regression observatory's outputs (any metric naming `detect_windows`
— REG_r*.json's detection latency, where convicting the same injected
slowdown later is the regression — plus `regress_*_total` incident
counters and `false_positives`) regress UP,
everything else
(throughput, ratios, ok-flags) regresses DOWN. Rate units ("tokens/s") always win over the
name heuristics, and SLO `attainment` metrics plus speculative-decode
`accept`/`acceptance` rates and capacity `headroom` fractions are
higher-is-better even though they may
end in percentile-looking suffixes (`_pct`) — a drop in attainment,
acceptance, or headroom is the regression (SLO_r*.json / BENCHDEC_r07
/ CAPACITY_r*.json records).

Usage: `python tools/bench_trend.py [DIR|FILES...] [--threshold 0.05]`
(default DIR = the repo root). `--latest-only` restricts regression
checks to metrics present in the newest round (default: any round may
regress against its best predecessor).
"""

from __future__ import annotations

import json
import os
import re
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(_HERE)

ROUND_RE = re.compile(r"^([A-Z]+)_r(\d+)\.json$")

#: units whose metrics regress by going UP (latency- and footprint-like)
LOWER_BETTER_UNITS = ("s", "ms", "us", "seconds", "sec", "bytes")
LOWER_BETTER_SUFFIXES = ("_ms", "_s", "_seconds", "_latency", "_bytes",
                         "_p50", "_p99")
#: name substrings that mark a latency metric regardless of unit — the
#: serving bench's TTFT records must trip the gate even when a round
#: wrote them unit-less; `dropped`/`lost`/`failover` are the router
#: harness's loss-and-disruption counts (SERVE_rNN's
#: router_lost_requests / router_failover_requests), where any rise —
#: including zero-to-nonzero — is the regression;
#: `startup`/`cold`/`spawn` are the replica cold-start observatory's
#: wall times (SERVE_rNN's replica_startup_total_s /
#: router_cold_spawn_first_token_s), where slower spin-up is the
#: regression
#: `flap`/`decision_churn` are the capacity observatory's shadow-
#: scaler oscillation counts (CAPACITY_rNN's capacity_decision_flaps),
#: where any rise means the hysteresis got worse at damping bursts
#: `delay` covers reaction-time counts like CAPACITY_rNN's
#: capacity_scale_up_delay_polls — reacting later is the regression
#: `divergence`/`miscompare`/`false_positive` are the correctness
#: observatory's incident counts (AUDIT_rNN's audit_divergence_count /
#: audit_canary_miscompare_count / audit_false_positive_count), where
#: any rise — especially zero-to-nonzero false positives — is the
#: regression
#: `detect_windows` is the regression observatory's detection latency
#: (REG_rNN's regress_contention_detect_windows /
#: regress_compile_detect_windows) — convicting the same injected
#: slowdown LATER is the regression
LOWER_BETTER_SUBSTRINGS = ("ttft", "dropped", "lost", "failover",
                           "startup", "cold", "spawn", "flap",
                           "decision_churn", "delay", "divergence",
                           "miscompare", "false_positive",
                           "detect_windows")
#: name substrings that mark a higher-is-better metric even when a
#: lower-better suffix would otherwise match — SLO attainment records
#: end in `_pct` (and the percentile suffixes), but a DROP in
#: attainment is the regression; speculative-decoding `accept`/
#: `acceptance` rates (a spec-decoding A/B's records) likewise regress
#: DOWN even when written unit-less or percentile-suffixed; capacity
#: `headroom` fractions (CAPACITY_rNN) regress DOWN too — shrinking
#: headroom at the same load is the capacity regression; `hit_rate` is
#: the warm-store's compile_cache_hit_rate (WARM_rNN), where a restart
#: that compiles where it used to load regresses DOWN
HIGHER_BETTER_SUBSTRINGS = ("attainment", "accept", "headroom",
                            "hit_rate")


def parse_records(path: str, family: str):
    """Best-effort (round-tolerant) record extraction from one artifact.
    Returns a list of {"metric", "value", "unit"} dicts; unreadable
    files yield an empty list rather than raising — one corrupt round
    must not blind the whole trend."""
    try:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    except OSError:
        return []
    raws = []
    try:
        raws = [json.loads(text)]
    except ValueError:
        for line in text.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                raws.append(json.loads(line))
            except ValueError:
                continue
    out = []
    for raw in raws:
        if not isinstance(raw, dict):
            continue
        parsed = raw.get("parsed")
        if isinstance(parsed, dict) \
                and isinstance(parsed.get("metric"), str) \
                and isinstance(parsed.get("value"), (int, float)):
            # only adopt `parsed` when it IS a metric record; a wrapper
            # whose parsed dict holds something else must keep its own
            # rc so the round still degrades to <family>_run_ok below
            raw = dict(parsed)
        if isinstance(raw.get("metric"), str) \
                and isinstance(raw.get("value"), (int, float)) \
                and not isinstance(raw.get("value"), bool):
            out.append({"metric": raw["metric"],
                        "value": float(raw["value"]),
                        "unit": str(raw.get("unit") or "")})
        elif "ok" in raw:
            out.append({"metric": f"{family.lower()}_ok",
                        "value": 1.0 if raw.get("ok") else 0.0,
                        "unit": "bool"})
        elif "rc" in raw:
            out.append({"metric": f"{family.lower()}_run_ok",
                        "value": 1.0 if raw.get("rc") == 0 else 0.0,
                        "unit": "bool"})
    return out


def collect(paths):
    """{(family, round) -> [records]} from artifact files/directories."""
    files = []
    for p in paths:
        if os.path.isdir(p):
            for name in sorted(os.listdir(p)):
                if ROUND_RE.match(name):
                    files.append(os.path.join(p, name))
        elif ROUND_RE.match(os.path.basename(p)):
            files.append(p)
    rounds = {}
    for path in files:
        m = ROUND_RE.match(os.path.basename(path))
        family, rnd = m.group(1), int(m.group(2))
        rounds.setdefault((family, rnd), []).extend(
            parse_records(path, family))
    return rounds


def trend_table(rounds):
    """{metric -> {"unit", "by_round": {round -> value}}} — rounds are
    namespaced per family so BENCH r06 and BENCHDEC r05 don't collide
    (metric names already differ; the round axis is per family)."""
    table = {}
    for (family, rnd), recs in sorted(rounds.items()):
        for rec in recs:
            row = table.setdefault(
                rec["metric"], {"family": family, "unit": rec["unit"],
                                "by_round": {}})
            row["by_round"][rnd] = rec["value"]
    return table


def lower_is_better(metric: str, unit: str) -> bool:
    u = (unit or "").strip().lower()
    if "/" in u:
        # a rate (tokens/s, items/s): higher is better — and this must
        # win over the name-suffix heuristic, or a `*_tok_s` throughput
        # metric would be misread as a latency
        return False
    if any(sub in metric.lower() for sub in HIGHER_BETTER_SUBSTRINGS):
        # SLO attainment: named like a percentile (`_pct`, `_p99`
        # fragments) but a fall is the regression
        return False
    if u in LOWER_BETTER_UNITS:
        return True
    if any(sub in metric.lower() for sub in LOWER_BETTER_SUBSTRINGS):
        return True
    m = metric.lower()
    if m.startswith(("regress_", "singa_regress_")) \
            and m.endswith("_total"):
        # the regression observatory's incident counters
        # (regress_verdicts_total, regress_bundles_total mirrors):
        # more convictions/bundles at the SAME injected fault means
        # the detector got noisier — only the `_total` counters; the
        # other regress_* fields (roundtrip ok-flags) stay
        # higher-is-better
        return True
    return any(metric.endswith(sfx) for sfx in LOWER_BETTER_SUFFIXES)


def find_regressions(table, threshold: float = 0.05,
                     latest_only: bool = False):
    """[(metric, round, value, best_prior_round, best_prior, delta_frac)]
    — a round regresses when it is worse than the BEST prior round by
    more than `threshold` (fractional). With latest_only, only each
    metric's newest round is judged."""
    out = []
    for metric, row in sorted(table.items()):
        lb = lower_is_better(metric, row["unit"])
        rnds = sorted(row["by_round"])
        judge = rnds[-1:] if latest_only else rnds[1:]
        for rnd in judge:
            prior = [r for r in rnds if r < rnd]
            if not prior:
                continue
            vals = {r: row["by_round"][r] for r in prior}
            best_r = min(vals, key=lambda r: vals[r]) if lb \
                else max(vals, key=lambda r: vals[r])
            best = vals[best_r]
            v = row["by_round"][rnd]
            if best == 0:
                worse = (v > 0) if lb else (v < 0)
                delta = float("inf") if worse else 0.0
            else:
                delta = (v - best) / abs(best) if lb \
                    else (best - v) / abs(best)
            if delta > threshold:
                out.append((metric, rnd, v, best_r, best, delta))
    return out


def format_table(table, max_rounds: int = 8) -> str:
    """Human-readable metric x round table (newest `max_rounds`)."""
    all_rounds = sorted({r for row in table.values()
                         for r in row["by_round"]})[-max_rounds:]
    width = max([len(m) for m in table] or [6])
    lines = [" ".join([f"{'metric':<{width}}"]
                      + [f"{'r%02d' % r:>12}" for r in all_rounds])]
    for metric, row in sorted(table.items()):
        cells = []
        for r in all_rounds:
            v = row["by_round"].get(r)
            cells.append(f"{v:>12.4g}" if v is not None else f"{'-':>12}")
        lines.append(" ".join([f"{metric:<{width}}"] + cells))
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse
    p = argparse.ArgumentParser(
        prog="python tools/bench_trend.py",
        description="aggregate BENCH_*/BENCHDEC_*/MULTICHIP_*/... round "
                    "artifacts into a trend table and fail on regression")
    p.add_argument("paths", nargs="*", default=None,
                   help="artifact files or directories (default: repo "
                        "root)")
    p.add_argument("--threshold", type=float, default=0.05,
                   help="fractional regression tolerance vs the best "
                        "prior round (default 0.05)")
    p.add_argument("--latest-only", action="store_true",
                   help="judge only each metric's newest round")
    args = p.parse_args(argv)
    rounds = collect(args.paths or [ROOT])
    if not rounds:
        print("no *_rNN.json artifacts found", file=sys.stderr)
        return 0
    table = trend_table(rounds)
    print(format_table(table))
    regs = find_regressions(table, threshold=args.threshold,
                            latest_only=args.latest_only)
    for metric, rnd, v, best_r, best, delta in regs:
        print(f"REGRESSION {metric}: r{rnd:02d}={v:.6g} is "
              f"{delta * 100.0:.1f}% worse than best prior "
              f"r{best_r:02d}={best:.6g}", file=sys.stderr)
    if regs:
        print(f"{len(regs)} regression(s) beyond "
              f"{args.threshold * 100.0:.0f}%", file=sys.stderr)
        return 1
    print(f"no regressions beyond {args.threshold * 100.0:.0f}% "
          f"across {len(rounds)} round artifact(s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
