"""tools/bench_trend.py (ISSUE-7 satellite): the BENCH_*/BENCHDEC_*/
MULTICHIP_* round artifacts finally have a reader — aggregated into a
metric x round trend table, with regressions beyond a threshold vs the
best prior round flagged and turned into a non-zero exit. Driven by
checked-in fixture records so the tier-1 pass exercises exactly the
formats the repo's real artifacts use."""

import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "tools"))

import bench_trend  # noqa: E402

_FIX = os.path.join(_ROOT, "tests", "fixtures", "bench_trend")
CLEAN = os.path.join(_FIX, "clean")
REGRESS = os.path.join(_FIX, "regress")


def test_collect_tolerates_every_artifact_format():
    rounds = bench_trend.collect([CLEAN])
    # single record, JSONL, wrapper {rc, parsed}, harness {ok} formats
    assert ("TOY", 1) in rounds and ("TOY", 2) in rounds
    assert ("WRAP", 1) in rounds and ("HARN", 1) in rounds
    by_metric = bench_trend.trend_table(rounds)
    assert by_metric["toy_train_tok_s"]["by_round"] == {
        1: 100.0, 2: 104.0, 3: 101.0}
    assert by_metric["toy_step_ms"]["by_round"] == {2: 10.0, 3: 10.2}
    # wrapper with parsed=null degrades to a run_ok 0/1 metric
    assert by_metric["wrap_run_ok"]["by_round"] == {1: 1.0}
    assert by_metric["harn_ok"]["by_round"] == {1: 1.0, 2: 1.0}


def test_wrapper_with_non_record_parsed_keeps_rc_fallback(tmp_path):
    """Review fix: a wrapper whose `parsed` dict is NOT a metric record
    must still degrade to the rc-based <family>_run_ok metric instead
    of vanishing from the trend."""
    p = tmp_path / "WRAP_r01.json"
    p.write_text('{"n":1,"cmd":"x","rc":0,"tail":"",'
                 '"parsed":{"tail":"not a record"}}')
    recs = bench_trend.parse_records(str(p), "WRAP")
    assert recs == [{"metric": "wrap_run_ok", "value": 1.0,
                     "unit": "bool"}]
    # and a parsed dict that IS a record still wins over the rc
    p2 = tmp_path / "WRAP_r02.json"
    p2.write_text('{"n":2,"cmd":"x","rc":1,"tail":"",'
                  '"parsed":{"metric":"m","value":7.0,"unit":"x/s"}}')
    recs = bench_trend.parse_records(str(p2), "WRAP")
    assert recs == [{"metric": "m", "value": 7.0, "unit": "x/s"}]


def test_direction_inference():
    assert bench_trend.lower_is_better("toy_step_ms", "ms")
    assert bench_trend.lower_is_better("resume_restore_s", "")
    assert not bench_trend.lower_is_better("toy_train_tok_s", "tokens/s")
    assert not bench_trend.lower_is_better("goodput_ratio", "")


def test_clean_fixtures_have_no_regressions():
    table = bench_trend.trend_table(bench_trend.collect([CLEAN]))
    assert bench_trend.find_regressions(table, threshold=0.05) == []


def test_regressions_flagged_against_best_prior_round():
    table = bench_trend.trend_table(bench_trend.collect([REGRESS]))
    regs = bench_trend.find_regressions(table, threshold=0.05)
    by_metric = {m: (rnd, v, best_r, best, delta)
                 for m, rnd, v, best_r, best, delta in regs}
    # throughput: r03=90 vs BEST prior r02=110 (not r01=100) -> ~18%
    rnd, v, best_r, best, delta = by_metric["toy_train_tok_s"]
    assert (rnd, v, best_r, best) == (3, 90.0, 2, 110.0)
    assert abs(delta - 20.0 / 110.0) < 1e-9
    # latency regresses UP: r03=13ms vs best prior 10ms -> 30%
    rnd, v, best_r, best, delta = by_metric["toy_step_ms"]
    assert (rnd, v, best) == (3, 13.0, 10.0) and delta > 0.25
    # a harness flipping ok->not-ok is a regression too
    assert "harn_ok" in by_metric
    # a looser threshold forgives the throughput slide but not the
    # ok-flag collapse — nor the router reliability records (0->2 lost
    # is delta inf, 1->4 failovers is +300%; reliability slides are
    # built to outlive any sane threshold) — nor the capacity
    # observatory's oscillation/reaction counts (flaps 1->3, churn
    # 3->6, delay 2->4: all at or beyond +100%) — nor the audit
    # correctness records (divergence 6->11, miscompares 3->9,
    # false positives 0->2 is delta inf) — nor the regression
    # observatory's records (contention detect latency 3->9 windows,
    # clean-arm false positives 0->3 is delta inf, verdicts_total
    # 2->7)
    loose = bench_trend.find_regressions(table, threshold=0.5)
    assert {m for m, *_ in loose} == {"harn_ok", "router_lost_requests",
                                      "router_failover_requests",
                                      "capacity_decision_flaps",
                                      "capacity_decision_churn",
                                      "capacity_scale_up_delay_polls",
                                      "audit_divergence_count",
                                      "audit_canary_miscompare_count",
                                      "audit_false_positive_count",
                                      "regress_contention_detect_windows",
                                      "regress_false_positives",
                                      "regress_verdicts_total"}


def test_cli_exit_codes(capsys):
    assert bench_trend.main([CLEAN]) == 0
    out = capsys.readouterr()
    assert "toy_train_tok_s" in out.out and "no regressions" in out.out
    assert bench_trend.main([REGRESS]) == 1
    out = capsys.readouterr()
    assert "REGRESSION" in out.err
    assert "toy_train_tok_s" in out.err


def test_latest_only_mode():
    table = bench_trend.trend_table(bench_trend.collect([REGRESS]))
    regs = bench_trend.find_regressions(table, threshold=0.05,
                                        latest_only=True)
    # same verdicts here (the regressions ARE in the latest rounds),
    # but each metric is judged at most once
    metrics = [m for m, *_ in regs]
    assert len(metrics) == len(set(metrics))
    assert "toy_train_tok_s" in metrics


def test_smoke_on_repo_artifacts():
    """The tool parses every real committed round artifact without
    raising (exit code not pinned: future rounds may legitimately
    regress and that is the tool's job to report)."""
    rounds = bench_trend.collect([bench_trend.ROOT])
    assert rounds  # BENCH_r06, MULTICHIP_r*..: the repo carries artifacts
    table = bench_trend.trend_table(rounds)
    assert "multichip_ok" in table
    assert bench_trend.format_table(table)
    bench_trend.find_regressions(table)


def test_bytes_metrics_default_to_lower_is_better():
    """ISSUE-9 satellite: memory footprints regress UP — both via the
    "bytes" unit and the `_bytes` name suffix (MEM_r*.json records);
    rate units still win over the name heuristic."""
    assert bench_trend.lower_is_better("mem_total_bytes", "bytes")
    assert bench_trend.lower_is_better("toy_hbm_bytes", "")
    assert bench_trend.lower_is_better("mem_est_peak_bytes", "bytes")
    assert not bench_trend.lower_is_better("kv_bytes", "bytes/s")


def test_ttft_and_percentile_metrics_lower_is_better():
    """ISSUE-11 satellite: serving latencies regress UP — `ttft`
    anywhere in the name (even unit-less, how a round might write a
    derived field) and `_p50`/`_p99` percentile suffixes; rate units
    still win so a throughput metric can never be misread."""
    assert bench_trend.lower_is_better("engine_ttft_p99_s", "s")
    assert bench_trend.lower_is_better("toy_serve_ttft_p99", "")
    assert bench_trend.lower_is_better("baseline_ttft_p50", "")
    assert bench_trend.lower_is_better("decode_step_p99", "")
    assert not bench_trend.lower_is_better("toy_serve_engine_tok_s",
                                           "tokens/s")


def test_ttft_fixture_regression_flagged():
    """The checked-in SERVE fixtures carry a unit-less ttft p99 series:
    improving in clean/ (no flag), +50% in regress/ (flagged UP) — a
    serving-latency slide trips the trend gate like a training one."""
    clean = bench_trend.trend_table(bench_trend.collect([CLEAN]))
    assert clean["toy_serve_ttft_p99"]["by_round"] == {1: 0.030,
                                                      2: 0.028}
    assert not [r for r in bench_trend.find_regressions(clean)
                if r[0] == "toy_serve_ttft_p99"]
    table = bench_trend.trend_table(bench_trend.collect([REGRESS]))
    regs = {m: (rnd, v, best_r, best, delta)
            for m, rnd, v, best_r, best, delta
            in bench_trend.find_regressions(table, threshold=0.05)}
    rnd, v, best_r, best, delta = regs["toy_serve_ttft_p99"]
    assert (rnd, v, best_r, best) == (2, 0.045, 1, 0.030)
    assert abs(delta - 0.5) < 1e-9


def test_bytes_fixture_regression_flagged():
    """The checked-in fixtures carry a toy_hbm_bytes series: flat in
    clean/ (no flag), +50% in regress/ (flagged UP against the best —
    i.e. smallest — prior round)."""
    clean = bench_trend.trend_table(bench_trend.collect([CLEAN]))
    assert clean["toy_hbm_bytes"]["by_round"] == {2: 1000000.0,
                                                 3: 990000.0}
    assert not [r for r in bench_trend.find_regressions(clean)
                if r[0] == "toy_hbm_bytes"]
    table = bench_trend.trend_table(bench_trend.collect([REGRESS]))
    regs = {m: (rnd, v, best_r, best, delta)
            for m, rnd, v, best_r, best, delta
            in bench_trend.find_regressions(table, threshold=0.05)}
    rnd, v, best_r, best, delta = regs["toy_hbm_bytes"]
    assert (rnd, v, best_r, best) == (3, 1500000.0, 2, 1000000.0)
    assert abs(delta - 0.5) < 1e-9


def test_attainment_metrics_higher_is_better():
    """ISSUE-12 satellite: SLO attainment records end in `_pct` (a
    lower-better suffix) but a DROP in attainment is the regression —
    the `attainment` substring overrides the suffix heuristic; rate
    units and plain percentiles keep their directions."""
    assert not bench_trend.lower_is_better(
        "gpt_serve_engine_slo_attainment_pct_cfg", "pct")
    assert not bench_trend.lower_is_better(
        "toy_serve_slo_attainment_pct", "")
    # plain percentile/TTFT metrics are still lower-is-better
    assert bench_trend.lower_is_better("toy_serve_ttft_p99", "")
    assert bench_trend.lower_is_better("engine_latency_p99", "")


def test_attainment_fixture_regression_flagged():
    """The checked-in SLO fixtures carry an attainment series:
    improving in clean/ (99 -> 100, no flag), dropping in regress/
    (100 -> 90, flagged DOWN against the best prior round)."""
    clean = bench_trend.trend_table(bench_trend.collect([CLEAN]))
    assert clean["toy_serve_slo_attainment_pct"]["by_round"] \
        == {1: 99.0, 2: 100.0}
    assert not [r for r in bench_trend.find_regressions(clean)
                if r[0] == "toy_serve_slo_attainment_pct"]
    table = bench_trend.trend_table(bench_trend.collect([REGRESS]))
    regs = {m: (rnd, v, best_r, best, delta)
            for m, rnd, v, best_r, best, delta
            in bench_trend.find_regressions(table, threshold=0.05)}
    rnd, v, best_r, best, delta = regs["toy_serve_slo_attainment_pct"]
    assert (rnd, v, best_r, best) == (2, 90.0, 1, 100.0)
    assert abs(delta - 0.1) < 1e-9


def test_acceptance_metrics_higher_is_better():
    """ISSUE-13 satellite: speculative-decoding `accept`/`acceptance`
    metrics are higher-is-better even when percentile-suffixed or
    unit-less — a falling acceptance rate is the regression; rate units
    and plain percentiles keep their directions."""
    assert not bench_trend.lower_is_better(
        "gpt_specdec_acceptance_rate_pct_cfg", "pct")
    assert not bench_trend.lower_is_better("toy_spec_accepted_tokens", "")
    assert not bench_trend.lower_is_better(
        "toy_spec_acceptance_rate_pct", "")
    # non-accept percentiles/TTFTs still regress UP
    assert bench_trend.lower_is_better("toy_spec_ttft_p99", "")
    assert bench_trend.lower_is_better("gpt_specdec_step_ms", "ms")


def test_acceptance_fixture_regression_flagged():
    """The checked-in SPEC fixtures carry an acceptance-rate series:
    improving in clean/ (82 -> 88, no flag), dropping in regress/
    (88 -> 66, flagged DOWN against the best prior round)."""
    clean = bench_trend.trend_table(bench_trend.collect([CLEAN]))
    assert clean["toy_spec_acceptance_rate_pct"]["by_round"] \
        == {1: 82.0, 2: 88.0}
    assert not [r for r in bench_trend.find_regressions(clean)
                if r[0] == "toy_spec_acceptance_rate_pct"]
    table = bench_trend.trend_table(bench_trend.collect([REGRESS]))
    regs = {m: (rnd, v, best_r, best, delta)
            for m, rnd, v, best_r, best, delta
            in bench_trend.find_regressions(table, threshold=0.05)}
    rnd, v, best_r, best, delta = regs["toy_spec_acceptance_rate_pct"]
    assert (rnd, v, best_r, best) == (2, 66.0, 1, 88.0)
    assert abs(delta - 22.0 / 88.0) < 1e-9


def test_loss_and_failover_counts_lower_is_better():
    """ISSUE-15: the router harness's dropped/lost/failover counts are
    lower-better regardless of unit — a reliability slide is a
    regression even though the records are plain counts — while rate
    units still win (a hypothetical failovers-handled/s throughput)."""
    assert bench_trend.lower_is_better("router_lost_requests", "count")
    assert bench_trend.lower_is_better("router_failover_requests",
                                       "count")
    assert bench_trend.lower_is_better("requests_dropped", "")
    assert not bench_trend.lower_is_better("failover_handled_per_s",
                                           "items/s")


def test_startup_metrics_lower_is_better():
    """ISSUE-16 satellite: the replica cold-start observatory's wall
    times — `startup`/`cold`/`spawn` anywhere in the name — regress UP
    even when a round wrote them unit-less; rate units still win."""
    assert bench_trend.lower_is_better("replica_startup_total_s", "s")
    assert bench_trend.lower_is_better(
        "router_cold_spawn_first_token_s", "")
    assert bench_trend.lower_is_better("toy_spawn_to_ready", "")
    assert bench_trend.lower_is_better("cold_start_p99", "")
    assert not bench_trend.lower_is_better("cold_starts_handled_per_s",
                                           "items/s")


def test_startup_fixture_regression_flagged():
    """The SERVE r05/r06 fixture rounds carry the cold-start records:
    improving in clean/ (2.0 -> 1.9, no flag), +20% in regress/
    (flagged UP against the best prior round) — a spin-up slide trips
    the trend gate like a latency one."""
    clean = bench_trend.trend_table(bench_trend.collect([CLEAN]))
    assert clean["replica_startup_total_s"]["by_round"] == {5: 2.0,
                                                           6: 1.9}
    assert not [r for r in bench_trend.find_regressions(clean)
                if r[0] in ("replica_startup_total_s",
                            "router_cold_spawn_first_token_s")]
    table = bench_trend.trend_table(bench_trend.collect([REGRESS]))
    regs = {m: (rnd, v, best_r, best, delta)
            for m, rnd, v, best_r, best, delta
            in bench_trend.find_regressions(table, threshold=0.05)}
    rnd, v, best_r, best, delta = regs["replica_startup_total_s"]
    assert (rnd, v, best_r, best) == (6, 2.4, 5, 2.0)
    assert abs(delta - 0.2) < 1e-9
    # the flat cold-spawn series is NOT flagged (2.4 -> 2.4)
    assert "router_cold_spawn_first_token_s" not in regs


def test_capacity_metrics_directions():
    """ISSUE-17 satellite: capacity `headroom` fractions are
    higher-is-better (shrinking headroom at the same load is the
    regression), while shadow-scaler oscillation (`flap`,
    `decision_churn`) and reaction-time (`delay`) counts regress UP;
    rate units still win over every name heuristic."""
    assert not bench_trend.lower_is_better(
        "capacity_cooldown_headroom_frac", "frac")
    assert not bench_trend.lower_is_better("fleet_headroom_pct", "")
    assert bench_trend.lower_is_better("capacity_decision_flaps",
                                       "count")
    assert bench_trend.lower_is_better("capacity_decision_churn", "")
    assert bench_trend.lower_is_better("capacity_scale_up_delay_polls",
                                       "polls")
    assert not bench_trend.lower_is_better("decisions_per_s", "items/s")


def test_capacity_fixture_regressions_flagged():
    """The checked-in CAP fixture rounds carry the capacity
    observatory's records: headroom up / flaps+churn+delay down in
    clean/ (no flag), and in regress/ a headroom DROP (0.32 -> 0.24)
    plus flap (1 -> 3), churn (3 -> 6), and delay (2 -> 4) RISES, all
    flagged against the best prior round."""
    clean = bench_trend.trend_table(bench_trend.collect([CLEAN]))
    assert clean["capacity_cooldown_headroom_frac"]["by_round"] \
        == {1: 0.30, 2: 0.32}
    assert clean["capacity_decision_flaps"]["by_round"] == {1: 2.0,
                                                           2: 1.0}
    assert not [r for r in bench_trend.find_regressions(clean)
                if r[0].startswith("capacity_")]
    table = bench_trend.trend_table(bench_trend.collect([REGRESS]))
    regs = {m: (rnd, v, best_r, best, delta)
            for m, rnd, v, best_r, best, delta
            in bench_trend.find_regressions(table, threshold=0.05)}
    rnd, v, best_r, best, delta = regs["capacity_cooldown_headroom_frac"]
    assert (rnd, v, best_r, best) == (2, 0.24, 1, 0.32)
    assert abs(delta - 0.08 / 0.32) < 1e-9
    rnd, v, best_r, best, delta = regs["capacity_decision_flaps"]
    assert (rnd, v, best_r, best) == (2, 3.0, 1, 1.0)
    assert abs(delta - 2.0) < 1e-9
    assert regs["capacity_decision_churn"][1] == 6.0
    assert regs["capacity_scale_up_delay_polls"][1] == 4.0


def test_router_loss_fixture_regression_flagged():
    """The SERVE r03/r04 fixture rounds carry the router reliability
    records: flat-at-zero loss in clean/ (no flag — zero staying zero
    is the contract), and in regress/ a 0->2 lost-request jump (delta
    inf: zero-to-nonzero is always flagged) plus a 1->4 failover
    rise."""
    clean = bench_trend.trend_table(bench_trend.collect([CLEAN]))
    assert clean["router_lost_requests"]["by_round"] == {3: 0.0,
                                                        4: 0.0}
    assert not [r for r in bench_trend.find_regressions(clean)
                if r[0].startswith("router_")]
    table = bench_trend.trend_table(bench_trend.collect([REGRESS]))
    regs = {m: (rnd, v, best_r, best, delta)
            for m, rnd, v, best_r, best, delta
            in bench_trend.find_regressions(table, threshold=0.05)}
    rnd, v, best_r, best, delta = regs["router_lost_requests"]
    assert (rnd, v, best_r, best) == (4, 2.0, 3, 0.0)
    assert delta == float("inf")
    rnd, v, best_r, best, delta = regs["router_failover_requests"]
    assert (rnd, v, best_r, best) == (4, 4.0, 3, 1.0)
    assert abs(delta - 3.0) < 1e-9


def test_regress_observatory_metrics_lower_is_better():
    """ISSUE-19 satellite: the regression observatory's outputs —
    detection latency (`detect_windows`), clean-arm false positives,
    and the `regress_*_total` incident counters — regress UP (a good
    detector convicts the same injected slowdown FASTER, with fewer
    false alarms), while the non-counter regress fields (bundle
    round-trip ok-flags) stay higher-is-better."""
    assert bench_trend.lower_is_better(
        "regress_contention_detect_windows", "windows")
    assert bench_trend.lower_is_better(
        "regress_compile_detect_windows", "")
    assert bench_trend.lower_is_better("regress_false_positives",
                                       "count")
    assert bench_trend.lower_is_better("regress_verdicts_total",
                                       "count")
    assert bench_trend.lower_is_better("singa_regress_bundles_total",
                                       "")
    assert not bench_trend.lower_is_better("regress_bundle_roundtrip",
                                           "bool")
    assert not bench_trend.lower_is_better("regressions_handled_per_s",
                                           "items/s")


def test_regress_fixture_regressions_flagged():
    """The checked-in REG fixture rounds carry the --ab harness's
    records: detection latency down / false positives flat at zero in
    clean/ (no flag), and in regress/ a detect-latency rise (3 -> 9
    windows), a 0 -> 3 clean-arm false-positive jump (delta inf) and a
    verdicts_total rise (2 -> 7), all flagged against the best prior
    round; the flat compile leg and the bundle round-trip flag are
    not."""
    clean = bench_trend.trend_table(bench_trend.collect([CLEAN]))
    assert clean["regress_contention_detect_windows"]["by_round"] \
        == {1: 3.0, 2: 2.0}
    assert clean["regress_false_positives"]["by_round"] \
        == {1: 0.0, 2: 0.0}
    assert not [r for r in bench_trend.find_regressions(clean)
                if r[0].startswith("regress_")]
    table = bench_trend.trend_table(bench_trend.collect([REGRESS]))
    regs = {m: (rnd, v, best_r, best, delta)
            for m, rnd, v, best_r, best, delta
            in bench_trend.find_regressions(table, threshold=0.05)}
    rnd, v, best_r, best, delta = \
        regs["regress_contention_detect_windows"]
    assert (rnd, v, best_r, best) == (2, 9.0, 1, 3.0)
    assert abs(delta - 2.0) < 1e-9
    rnd, v, best_r, best, delta = regs["regress_false_positives"]
    assert (v, best) == (3.0, 0.0) and delta == float("inf")
    assert regs["regress_verdicts_total"][1] == 7.0
    assert "regress_compile_detect_windows" not in regs
    assert "regress_bundle_roundtrip" not in regs


def test_audit_metrics_lower_is_better():
    """ISSUE-18 satellite: the correctness observatory's divergence,
    canary-miscompare and false-positive counts regress UP (a healthy
    fleet's audit should find LESS wrong over time, and a clean arm
    must stay at zero false positives), while the AUD harness ok flag
    stays higher-is-better."""
    assert bench_trend.lower_is_better("audit_divergence_count",
                                       "count")
    assert bench_trend.lower_is_better(
        "audit_canary_miscompare_count", "count")
    assert bench_trend.lower_is_better("audit_false_positive_count",
                                       "count")
    assert bench_trend.lower_is_better("audit_lost_requests", "count")
    assert not bench_trend.lower_is_better("aud_ok", "bool")


def test_audit_fixture_regressions_flagged():
    """The checked-in AUD fixture rounds carry the audit harness's
    records: divergence down, miscompares flat, false positives /
    lost requests flat at zero in clean/ (no flag — zero staying zero
    is the contract), and in regress/ a divergence (6 -> 11) and
    miscompare (3 -> 9) RISE plus a 0 -> 2 false-positive jump
    (delta inf — any clean-arm false positive is a regression), all
    flagged against the best prior round."""
    clean = bench_trend.trend_table(bench_trend.collect([CLEAN]))
    assert clean["audit_divergence_count"]["by_round"] == {1: 6.0,
                                                          2: 5.0}
    assert clean["audit_false_positive_count"]["by_round"] \
        == {1: 0.0, 2: 0.0}
    assert not [r for r in bench_trend.find_regressions(clean)
                if r[0].startswith("audit_")]
    table = bench_trend.trend_table(bench_trend.collect([REGRESS]))
    regs = {m: (rnd, v, best_r, best, delta)
            for m, rnd, v, best_r, best, delta
            in bench_trend.find_regressions(table, threshold=0.05)}
    rnd, v, best_r, best, delta = regs["audit_divergence_count"]
    assert (rnd, v, best_r, best) == (2, 11.0, 1, 6.0)
    assert abs(delta - 5.0 / 6.0) < 1e-9
    assert regs["audit_canary_miscompare_count"][1] == 9.0
    rnd, v, best_r, best, delta = regs["audit_false_positive_count"]
    assert (v, best) == (2.0, 0.0) and delta == float("inf")
    assert "audit_lost_requests" not in regs


def test_warm_start_metrics_directions():
    """ISSUE-20 satellite: the warm-store's `hit_rate` is
    higher-is-better — a restart that compiles where it used to load
    regresses DOWN — while `spawn_to_first_token_s` keeps the `spawn`
    lower-better rule even when written unit-less; rate units still
    win over both."""
    assert not bench_trend.lower_is_better("compile_cache_hit_rate",
                                           "ratio")
    assert not bench_trend.lower_is_better("compile_cache_hit_rate", "")
    assert bench_trend.lower_is_better("spawn_to_first_token_s", "s")
    assert bench_trend.lower_is_better("spawn_to_first_token_cold_s", "")
    assert bench_trend.lower_is_better("warmab_warm_compile_s", "s")
    assert not bench_trend.lower_is_better("cache_hits_per_s", "items/s")


def test_warm_fixture_regressions_flagged():
    """The checked-in WARM fixture rounds: clean/ improves
    spawn-to-first-token (1.2 -> 1.15) at a held 1.0 hit rate (no
    flags); regress/ slows the warm spawn (1.2 -> 1.8, flagged UP) and
    halves the hit rate (1.0 -> 0.5, flagged DOWN), both against the
    best prior round."""
    clean = bench_trend.trend_table(bench_trend.collect([CLEAN]))
    assert clean["spawn_to_first_token_s"]["by_round"] == {1: 1.2,
                                                          2: 1.15}
    assert clean["compile_cache_hit_rate"]["by_round"] == {1: 1.0,
                                                           2: 1.0}
    assert not [r for r in bench_trend.find_regressions(clean)
                if r[0] in ("spawn_to_first_token_s",
                            "compile_cache_hit_rate")]
    table = bench_trend.trend_table(bench_trend.collect([REGRESS]))
    regs = {m: (rnd, v, best_r, best, delta)
            for m, rnd, v, best_r, best, delta
            in bench_trend.find_regressions(table, threshold=0.05)}
    rnd, v, best_r, best, delta = regs["spawn_to_first_token_s"]
    assert (rnd, v, best_r, best) == (2, 1.68, 1, 1.2)
    assert abs(delta - 0.4) < 1e-9
    rnd, v, best_r, best, delta = regs["compile_cache_hit_rate"]
    assert (rnd, v, best_r, best) == (2, 0.7, 1, 1.0)
    assert abs(delta - 0.3) < 1e-9
