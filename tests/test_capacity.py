"""Capacity observatory & shadow autoscaler (ISSUE-17): the per-replica
headroom model reduced from measured fleet-shard signals with the
binding wall NAMED, the dual-EWMA demand forecaster with burst
detection and time-to-saturation, and the shadow scaler whose
hysteresis (cooldown + direction-change damping) provably bounds
flapping under seeded bursty arrivals — every decision carrying an
enum reason code into the JSONL ledger, counterfactually scored
tp/fp/fn/tn once its horizon passes. Nothing here actuates: the ledger
is the evidence PR 18's actuator will be judged against."""

import threading
import time

import numpy as np

from singa_tpu import capacity, observe
from singa_tpu.capacity import (CAPACITY_WALLS, DECISION_REASONS,
                                SCALE_DECISIONS, SHADOW_OUTCOMES,
                                CapacityModel, DemandForecaster,
                                ShadowScaler)


def _serve(slots=4, occupancy=2, page_util=0.25, queue_depth=0,
           ttft_p99_s=None, decode_tok_s=None, rps=2.0):
    """A synthetic fleet-shard `serve` dict (slo.fleet_serve_snapshot's
    shape, the fields the model reads)."""
    return {"slots": slots, "occupancy": occupancy,
            "page_util": page_util, "queue_depth": queue_depth,
            "ttft_p99_s": ttft_p99_s, "decode_tok_s": decode_tok_s,
            "rps": rps}


def _workers(*serves, stale=()):
    return [{"host": f"r{i:02d}", "serve": s,
             "stale": i in stale} for i, s in enumerate(serves)]


# ---- enums -----------------------------------------------------------------

def test_enums():
    assert CAPACITY_WALLS == ("slots", "pages", "queue", "ttft",
                              "bandwidth")
    assert SCALE_DECISIONS == ("scale_up", "scale_down", "hold")
    assert DECISION_REASONS == ("burn_sustained", "headroom_deficit",
                                "burst_arrival", "headroom_surplus",
                                "cooldown", "damped", "steady",
                                "insufficient_data")
    assert SHADOW_OUTCOMES == ("tp", "fp", "fn", "tn")


# ---- the capacity model ----------------------------------------------------

def test_model_names_the_binding_wall():
    m = CapacityModel(ttft_slo_s=1.0, decode_floor_tok_s=100.0)
    # slots binds: 3/4 occupied beats every other fraction
    r = m.assess_replica(_serve(occupancy=3, rps=3.0))
    assert r["wall"] == "slots" and r["wall_util"] == 0.75
    assert r["headroom_frac"] == 0.25
    # sustainable extrapolates through the wall: 3 rps / 0.75
    assert r["sustainable_rps"] == 4.0 and r["source"] == "measured"
    # pages bind when the pool runs hotter than the slots
    r = m.assess_replica(_serve(occupancy=1, page_util=0.9))
    assert r["wall"] == "pages" and r["wall_util"] == 0.9
    # queue: depth/(factor*slots), capped at 1 — a queue as deep as
    # the slot count IS saturation
    r = m.assess_replica(_serve(occupancy=2, queue_depth=9))
    assert r["wall"] == "queue" and r["wall_util"] == 1.0
    assert r["headroom_frac"] == 0.0
    # ttft: p99 against the SLO target
    r = m.assess_replica(_serve(occupancy=1, ttft_p99_s=0.8))
    assert r["wall"] == "ttft" and r["wall_util"] == 0.8
    # bandwidth: measured decode tok/s against the roofline ceiling
    r = m.assess_replica(_serve(occupancy=1, decode_tok_s=85.0))
    assert r["wall"] == "bandwidth" and r["wall_util"] == 0.85
    # every wall name the model can emit is in the enum
    assert set(r["utils"]) == set(CAPACITY_WALLS)


def test_model_gates_optional_walls():
    # without a TTFT target or a decode floor those walls are absent
    m = CapacityModel()
    r = m.assess_replica(_serve(ttft_p99_s=5.0, decode_tok_s=1e9))
    assert r["utils"]["ttft"] is None
    assert r["utils"]["bandwidth"] is None
    assert r["wall"] == "slots"
    # the module-level measured floor (a decode roofline) feeds
    # the bandwidth wall when the model has no explicit one
    capacity.note_decode_floor(200.0)
    assert capacity.get_decode_floor() == 200.0
    r = CapacityModel().assess_replica(
        _serve(occupancy=0, page_util=0.0, decode_tok_s=190.0))
    assert r["wall"] == "bandwidth" and r["wall_util"] == 0.95
    capacity.note_decode_floor(None)
    assert capacity.get_decode_floor() is None


def test_model_peak_floor_survives_cooldown():
    """The burst lesson: the engine's lifetime TTFT percentiles lag the
    live load, so post-burst extrapolation collapses toward the
    current rps — the model never reports less than the rate a replica
    has already proven sustaining (source flips to "peak")."""
    m = CapacityModel()
    r = m.assess_replica(_serve(occupancy=4, rps=8.0))
    assert (r["sustainable_rps"], r["source"]) == (8.0, "measured")
    # cooldown: near-idle signals would extrapolate to 2.0 rps
    r = m.assess_replica(_serve(occupancy=2, rps=1.0))
    assert (r["sustainable_rps"], r["source"]) == (8.0, "peak")
    # at true idle (wall under min_util) the extrapolation is noise:
    # only the peak is reported
    r = m.assess_replica(_serve(occupancy=0, page_util=0.01, rps=0.0))
    assert (r["sustainable_rps"], r["source"]) == (8.0, "peak")
    # peaks are per-host: another replica starts from nothing
    r = m.assess_replica(_serve(occupancy=0, page_util=0.01, rps=0.0),
                         host="other")
    assert r["sustainable_rps"] is None and r["source"] is None


def test_fleet_assess_rollup():
    m = CapacityModel()
    a = m.assess(_workers(_serve(occupancy=3, rps=3.0),
                          _serve(occupancy=1, rps=1.0),
                          _serve(occupancy=4, rps=9.0),
                          stale={2}))
    # the stale replica is excluded from every fleet figure...
    assert a["n_replicas"] == 2
    assert a["rps"] == 4.0
    # ...fleet headroom is the WORST fresh replica's (the binding one)
    assert a["headroom_frac"] == 0.25
    # ...sustainable is summed over fresh replicas (3/.75 + 1/.25)
    assert a["sustainable_rps"] == 8.0
    # ...but its row still renders, flagged
    assert len(a["replicas"]) == 3 and a["replicas"][2]["stale"]
    empty = m.assess([])
    assert empty["n_replicas"] == 0
    assert empty["headroom_frac"] is None
    assert empty["sustainable_rps"] is None


# ---- the demand forecaster -------------------------------------------------

def test_forecaster_dual_ewma_and_burst():
    f = DemandForecaster(fast_tau_s=1.0, slow_tau_s=10.0,
                         burst_ratio=1.5, min_rate=0.1)
    assert f.demand_rps() is None and not f.burst()
    f.update(2.0, now=0.0)
    assert f.fast == f.slow == 2.0 and not f.burst()
    # a step to 10 rps: the fast estimate closes most of the gap in a
    # couple of time constants, the slow one barely moves
    for i in range(1, 5):
        f.update(10.0, now=float(i))
    assert f.fast > 9.0
    assert f.slow < 6.0
    assert f.burst()  # fast pulled > 1.5x away from slow
    snap = f.snapshot()
    assert snap["burst"] and snap["samples"] == 5
    assert snap["fast_rps"] > snap["slow_rps"]
    # growing toward a capacity line: finite positive forecast
    tts = f.time_to_saturation(50.0)
    assert tts is not None and tts > 0.0
    # already past the line: saturated NOW
    assert f.time_to_saturation(5.0) == 0.0
    assert f.time_to_saturation(None) is None
    # settled (fast == slow): not growing — never, at this trend
    g = DemandForecaster()
    g.update(3.0, now=0.0)
    g.update(3.0, now=1.0)
    assert g.time_to_saturation(50.0) is None
    assert not g.burst()


def test_forecaster_idle_is_not_a_burst():
    """The min_rate floor: noise around zero must not read as a burst
    (0.02 rps is 2x of 0.01 rps but nobody is arriving)."""
    f = DemandForecaster(fast_tau_s=0.5, slow_tau_s=10.0, min_rate=0.1)
    f.update(0.0, now=0.0)
    for i in range(1, 6):
        f.update(0.05, now=float(i))
    assert not f.burst()


# ---- the shadow scaler: policy, hysteresis, ledger, scoring ----------------

class _Feed:
    """A scripted sample()/clock pair: each evaluate() consumes one
    (admitted_rps, burn) step at a fixed 1s cadence, against a steady
    2-replica fleet with a known sustainable rate (occupancy 2/4,
    1 rps each -> 2 rps measured / 4 rps sustainable fleet-wide)."""

    def __init__(self, steps):
        self.steps = list(steps)
        self.i = 0

    def clock(self):
        return float(self.i)

    def sample(self):
        admitted, burn = self.steps[min(self.i,
                                        len(self.steps) - 1)]
        self.i += 1
        return {"workers": _workers(_serve(rps=1.0), _serve(rps=1.0)),
                "admitted_rps": admitted, "burn_fast": burn,
                "burn_slow": burn, "breaching": [],
                "shed_rate": 0.0}


def _scaler(feed, **kw):
    kw.setdefault("interval_s", 0.0)
    kw.setdefault("burn_sustain", 2)
    kw.setdefault("down_sustain", 2)
    kw.setdefault("cooldown_polls", 3)
    kw.setdefault("damp_polls", 2)
    kw.setdefault("horizon_s", 4.0)
    return ShadowScaler(CapacityModel(), DemandForecaster(
        fast_tau_s=0.5, slow_tau_s=5.0),
        sample=feed.sample, clock=feed.clock, **kw)


def test_scaler_burn_sustained_scale_up_and_cooldown():
    # burn ignites at step 2 and stays: scale_up exactly when the
    # streak reaches burn_sustain, then cooldown holds
    feed = _Feed([(2.0, 0.0)] * 2 + [(2.0, 5.0)] * 6)
    s = _scaler(feed)
    recs = [s.evaluate() for _ in range(8)]
    assert [r["decision"] for r in recs[:2]] == ["hold", "hold"]
    assert recs[0]["reason"] == "steady"
    up = next(r for r in recs if r["decision"] == "scale_up")
    assert up["reason"] == "burn_sustained"
    assert up["poll"] == 4  # streak 2 at the 2nd burning poll
    after = [r for r in recs if r["poll"] > up["poll"]]
    assert all(r["decision"] == "hold" and r["reason"] == "cooldown"
               for r in after[:s.cooldown_polls])
    # every record carries the enum contract + the signal trail
    for r in recs:
        assert r["decision"] in SCALE_DECISIONS
        assert r["reason"] in DECISION_REASONS
        assert r["sustainable_rps"] == 4.0
        assert r["replicas"] == 2


def test_scaler_scale_down_needs_quiet_sustained_surplus():
    # demand far under down_frac * sustainable, burn quiet: scale_down
    # after down_sustain polls; the burn_sustained path never fires
    feed = _Feed([(0.1, 0.0)] * 8)
    s = _scaler(feed)
    recs = [s.evaluate() for _ in range(6)]
    down = next(r for r in recs if r["decision"] == "scale_down")
    assert down["reason"] == "headroom_surplus"
    assert down["poll"] == s.down_sustain
    # ...but the same surplus with burn hot holds instead (never
    # scale down a burning fleet)
    feed = _Feed([(0.1, 5.0)] * 4)
    s = _scaler(feed, burn_sustain=99)
    recs = [s.evaluate() for _ in range(4)]
    assert all(r["decision"] != "scale_down" for r in recs)


def test_scaler_damping_blocks_direction_flip():
    """After a scale_down, a want in the OPPOSITE direction must
    persist for damp_polls polls (reason damped) before it may emit —
    with the cooldown in front of it, a one-poll blip can never flip
    the direction."""
    feed = _Feed([(0.1, 0.0)] * 3      # surplus -> scale_down
                 + [(8.0, 5.0)] * 12)  # immediate hard reversal
    s = _scaler(feed, cooldown_polls=2, damp_polls=2)
    recs = [s.evaluate() for _ in range(12)]
    down = next(r for r in recs if r["decision"] == "scale_down")
    up = next(r for r in recs if r["decision"] == "scale_up")
    between = [r for r in recs if down["poll"] < r["poll"] < up["poll"]]
    # the gap is the cooldown then the damper, in that order
    assert [r["reason"] for r in between] \
        == ["cooldown", "cooldown", "damped", "damped"]
    assert up["poll"] == down["poll"] + 5
    assert s.direction_changes() == 1


def test_scaler_insufficient_data_and_headroom_deficit():
    # no workers at all: insufficient_data, never a scale decision
    class Empty:
        i = 0

        def clock(self):
            self.i += 1
            return float(self.i)

        def sample(self):
            return {"workers": [], "admitted_rps": None,
                    "burn_fast": None, "burn_slow": None}

    e = Empty()
    s = ShadowScaler(sample=e.sample, clock=e.clock, interval_s=0.0)
    r = s.evaluate()
    assert (r["decision"], r["reason"]) == ("hold",
                                            "insufficient_data")
    # demand over sustainable without any burn yet: the forecast alone
    # justifies the (shadow) scale_up
    feed = _Feed([(10.0, 0.0)] * 4)
    s = _scaler(feed, burn_sustain=99)
    recs = [s.evaluate() for _ in range(4)]
    up = next(r for r in recs if r["decision"] == "scale_up")
    assert up["reason"] == "headroom_deficit"


def test_hysteresis_bounds_flaps_under_bursty_arrivals():
    """The property the hysteresis exists for: under SEEDED bursty
    arrivals (rate and burn flipping on random 1-6 poll episodes) the
    emitted direction changes are bounded by the cooldown structure —
    consecutive scale decisions are at least cooldown_polls+1 polls
    apart, so flaps can never exceed polls/(cooldown_polls+1) — and
    every decision/reason lands inside the enums."""
    rng = np.random.RandomState(1234)
    steps, mode = [], 0
    while len(steps) < 160:
        mode = 1 - mode
        for _ in range(int(rng.randint(1, 7))):
            if mode:
                steps.append((float(8.0 + rng.rand() * 6.0),
                              float(3.0 + rng.rand() * 3.0)))
            else:
                steps.append((float(rng.rand() * 0.3), 0.0))
    feed = _Feed(steps)
    s = _scaler(feed, cooldown_polls=4, damp_polls=2)
    recs = [s.evaluate() for _ in range(160)]
    for r in recs:
        assert r["decision"] in SCALE_DECISIONS
        assert r["reason"] in DECISION_REASONS
    emitted = [r["poll"] for r in recs if r["decision"] != "hold"]
    assert emitted, "a bursty feed must provoke scale decisions"
    gaps = [b - a for a, b in zip(emitted, emitted[1:])]
    assert all(g >= s.cooldown_polls + 1 for g in gaps), gaps
    assert s.direction_changes() <= len(recs) // (s.cooldown_polls + 1)
    # the ring mirrors the emitted sequence
    ring = s.decisions()
    assert [r["poll"] for r in ring] == [r["poll"] for r in recs]


def test_ledger_jsonl_roundtrip(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    feed = _Feed([(2.0, 0.0)] * 2 + [(2.0, 5.0)] * 4 + [(2.0, 0.0)] * 8)
    s = _scaler(feed, ledger_path=path, horizon_s=3.0)
    s.install(poll=False)
    try:
        recs = [s.evaluate() for _ in range(14)]
    finally:
        capacity.uninstall()
    back = capacity.read_ledger(path)
    decisions = [r for r in back if r["kind"] == "decision"]
    scores = [r for r in back if r["kind"] == "score"]
    assert {r["kind"] for r in back} == {"decision", "score"}
    # every poll wrote exactly one decision line, in order, and the
    # JSON round-trips the record the ring holds (modulo the outcome
    # fields scoring adds in place after the write)
    assert [r["poll"] for r in decisions] == [r["poll"] for r in recs]
    for disk, live in zip(decisions, recs):
        for k in ("decision", "reason", "demand_rps",
                  "sustainable_rps", "burn_fast", "burn_streak"):
            assert disk[k] == live[k], k
    # scores reference real polls and carry enum outcomes
    assert scores
    polls = {r["poll"] for r in decisions}
    for sc in scores:
        assert sc["poll"] in polls
        assert sc["outcome"] in SHADOW_OUTCOMES
    # a missing file is an empty ledger, not an error
    assert capacity.read_ledger(str(tmp_path / "absent.jsonl")) == []
    # garbage lines are skipped, valid ones survive
    p2 = tmp_path / "mixed.jsonl"
    p2.write_text('not json\n{"kind": "decision", "poll": 1}\n\n[1]\n')
    assert capacity.read_ledger(str(p2)) == [{"kind": "decision",
                                              "poll": 1}]


def test_counterfactual_scoring_grades_all_four_outcomes():
    """Scoring replays each decision against the burn samples inside
    (ts, ts+horizon]: scale_up predicts a burn episode, hold/scale_down
    predict its absence — tp/fp/fn/tn, precision and recall."""
    # quiet -> burn (the early holds become fn, the scale_up tp) ->
    # long quiet tail (cooldown holds become tn)
    feed = _Feed([(2.0, 0.0)] * 2 + [(2.0, 5.0)] * 4
                 + [(2.0, 0.0)] * 10)
    s = _scaler(feed, horizon_s=3.0)
    for _ in range(16):
        s.evaluate()
    acc = s.accuracy()
    assert acc["scored"] == sum(acc[o] for o in SHADOW_OUTCOMES)
    assert acc["scored"] >= 10
    assert acc["tp"] >= 1    # the scale_up preceded real burn
    assert acc["fn"] >= 1    # the pre-sustain holds sat inside burn
    assert acc["tn"] >= 1    # the quiet tail
    assert acc["precision"] == 1.0  # no scale_up fired without burn
    assert acc["recall"] == round(
        acc["tp"] / (acc["tp"] + acc["fn"]), 4)
    # a scale_up whose burn never materializes is a false positive
    feed = _Feed([(10.0, 0.0)] * 12)   # headroom_deficit ups, no burn
    s = _scaler(feed, burn_sustain=99, horizon_s=3.0)
    for _ in range(12):
        s.evaluate()
    acc = s.accuracy()
    assert acc["fp"] >= 1 and acc["tp"] == 0
    assert acc["precision"] == 0.0


def test_scaler_exports_metrics():
    feed = _Feed([(2.0, 0.0)] * 2 + [(2.0, 5.0)] * 4)
    s = _scaler(feed)
    for _ in range(6):
        s.evaluate()
    reg = observe.get_registry()
    assert reg.get("singa_capacity_polls_total").value() == 6
    dec = reg.get("singa_scaler_decisions_total")
    assert dec.value(decision="hold", reason="steady") >= 1
    assert dec.value(decision="scale_up",
                     reason="burn_sustained") == 1
    assert reg.get("singa_capacity_headroom_frac").value() == 0.5
    assert reg.get("singa_capacity_sustainable_rps").value() == 4.0
    assert reg.get("singa_capacity_demand_rps").value() is not None


# ---- singleton / lifecycle -------------------------------------------------

def test_install_reset_and_poll_thread_lifecycle():
    feed = _Feed([(1.0, 0.0)] * 4)
    s = ShadowScaler(sample=feed.sample, interval_s=0.01)
    s.install()
    try:
        assert capacity.get_scaler() is s
        t = [t for t in threading.enumerate()
             if t.name.startswith("singa-capacity-poll-")]
        assert len(t) == 1
        deadline = time.monotonic() + 10.0
        while s.snapshot()["polls"] < 2 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert s.snapshot()["polls"] >= 2
    finally:
        capacity.reset()
    assert capacity.get_scaler() is None
    assert not [t for t in threading.enumerate()
                if t.name.startswith("singa-capacity")]
    # a second install replaces (and uninstalls) the first
    a = ShadowScaler(sample=feed.sample, interval_s=0.0)
    b = ShadowScaler(sample=feed.sample, interval_s=0.0)
    a.install(poll=False)
    b.install(poll=False)
    assert capacity.get_scaler() is b
    capacity.reset()


def test_capacity_report_renders_every_section():
    assert "no ShadowScaler installed" in capacity.capacity_report()
    feed = _Feed([(2.0, 0.0)] * 2 + [(2.0, 5.0)] * 4)
    s = _scaler(feed)
    s.install(poll=False)
    try:
        for _ in range(6):
            s.evaluate()
        rep = capacity.capacity_report()
        assert rep.startswith("== capacity ==")
        assert "fleet: 2 replica(s)" in rep
        assert "sustainable 4.00 rps" in rep
        assert "headroom 50%" in rep
        assert "demand: fast" in rep
        # the table header + a per-replica row naming the wall
        assert "wall" in rep and "sust_rps" in rep
        assert "r00" in rep and "r01" in rep
        assert "slots" in rep
        assert "scale_up [burn_sustained]" in rep
        assert "shadow accuracy:" in rep
        j = capacity.capacity_json()
        assert j["installed"] and len(j["decisions"]) == 6
        assert j["snapshot"]["config"]["cooldown_polls"] == 3
    finally:
        capacity.uninstall()
    assert capacity.capacity_json() == {"installed": False}


def test_default_sample_and_fleet_snapshot_reconcile(gpt_engine=None):
    """default_sample() and fleet_capacity_snapshot() against a LIVE
    engine: the local fallback row is the slo.fleet_serve_snapshot
    dict, the shard line's headroom row derives from the same signals,
    and with nothing serving both report nothing."""
    assert capacity.fleet_capacity_snapshot() is None
    s = capacity.default_sample()
    assert s["workers"] == [] and s["burn_fast"] is None
    from singa_tpu import device, engine as eng, models, slo, tensor
    dev = device.best_device()
    m = models.create_model("gpt", vocab_size=97, max_seq=64, dim=64,
                            num_heads=4, num_layers=2)
    ids = tensor.from_numpy(
        np.random.RandomState(0).randint(0, 97, (2, 8))
        .astype(np.int32), device=dev)
    m.compile([ids], is_train=False, use_graph=False)
    m.eval()
    e = eng.ServingEngine(m, max_slots=2, page_size=8, max_ctx=64,
                          steps_per_sync=2).start()
    try:
        rng = np.random.RandomState(5)
        hs = [e.submit(rng.randint(0, 97, (6,)), 5) for _ in range(3)]
        for h in hs:
            assert h.wait(300) and h.outcome == "completed"
        s = capacity.default_sample()
        assert len(s["workers"]) == 1
        serve = s["workers"][0]["serve"]
        assert serve["slots"] == 2
        assert serve["decode_tok_s"] is None \
            or serve["decode_tok_s"] > 0.0
        # no router installed: admitted falls back to the serve rps
        assert s["admitted_rps"] == serve["rps"]
        snap = capacity.fleet_capacity_snapshot()
        assert snap is not None
        assert snap["wall"] in CAPACITY_WALLS
        row = CapacityModel().assess_replica(serve)
        assert snap["wall"] == row["wall"]
        assert snap["utils"]["slots"] == row["utils"]["slots"]
    finally:
        e.stop()
        slo.reset()
