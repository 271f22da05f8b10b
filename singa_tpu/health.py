"""Training-health telemetry: in-graph numerics, watchdog, flight recorder.

observe.py (PR 1) measures *performance* — step latency, compile counts,
wire bytes. Nothing watches *model health*: a NaN'd gradient or a silently
exploding loss produces no signal until the checkpoint is already
poisoned. This module is the MegaScale-style per-step health layer on top
of it, in two halves:

In-graph (`StepStatsCollector`): the optimizer strategies feed every
(grad, param-update) pair into a trace-time collector while the jitted
step is being built, so the step program itself computes a small
`step_stats` pytree — global grad norm, per-layer-group param/update
norms and update-to-param ratios, NaN/Inf counts over grads and the loss
(grad-norm + isfinite-count fused into ONE variadic reduction per
gradient — a single pass over the grad bytes, no host syncs beyond the
step's own output fetch). The pytree is returned alongside the step
outputs, so reading it costs one small transfer. Under a mesh the counts
are `pmax`'d (post-reduction grads are replicated under dense/half, so a
psum would inflate them world_size-fold), the norms `pmean`'d, and the
anomaly flag rides `Communicator.agree_any`, so every shard sees the SAME
verdict — a policy fires on all hosts in the same step, never diverging
param state.

Host-side (`HealthMonitor`): feeds the stats into `singa_health_*`
metrics, maintains an EMA-based loss-spike score (EMA is cross-step state,
which a functional jitted step cannot carry without changing its
signature — the loss value itself IS in-graph; the EMA fold over steps
happens here, on the value the step already shipped), and applies a
configurable policy on anomaly:

  - "warn":       count + event + flight-recorder dump, training continues
  - "skip_step":  the UPDATE IS DISCARDED IN-GRAPH — the compiled step
                  selects the pre-step params/opt state when the agreed
                  nonfinite flag fires (mixed-precision overflow-skip
                  machinery, generalized), so params stay exactly
                  bit-identical on every shard. Loss-spike anomalies
                  (host-side EMA) cannot retroactively un-commit an
                  already-applied update; they downgrade to warn.
  - "halt":       dump, then raise HealthError out of the train loop.

Flight recorder: a bounded ring of the last N steps' stats plus the
recent EventLog tail, dumped to a JSONL bundle (optional offending-batch
snapshot via snapshot.py) the moment an anomaly fires — post-mortems do
not depend on having had logging enabled. `load_flight_bundle` round-trips
a bundle back into dicts/arrays.
"""

from __future__ import annotations

import json
import os
import time
from collections import deque

import jax

from . import observe

POLICIES = ("warn", "skip_step", "halt")

# Anomaly kinds (the `kind` label on singa_health_anomaly_total)
KIND_NONFINITE_GRAD = "nonfinite_grad"
KIND_NONFINITE_LOSS = "nonfinite_loss"
KIND_LOSS_SPIKE = "loss_spike"
KIND_GRAD_NORM = "grad_norm_limit"
KIND_STRAGGLER = "straggler"  # fleet sustained-straggler verdict
KIND_MEM_LEAK = "mem_leak"    # memory-ledger sustained-growth verdict
KIND_HANG = "hang"            # watchdog deadline-breach abort verdict
KIND_SLO = "slo"              # SLO tracker sustained burn-rate breach
KIND_DIVERGENCE = "divergence"  # audit correctness verdict (wrong tokens)
KIND_REGRESSION = "regression"  # regress sustained-latency-regression verdict


class HealthError(RuntimeError):
    """Raised by the `halt` policy; carries the flight-bundle path.

    `partial` is filled in by supervising loops on the way out:
    `Model.fit` attaches {"epoch", "steps_completed", "losses",
    "last_loss"} so a halt does not discard the epoch's progress, and
    `resilience.TrainController` additionally attaches its run report
    as `.resilience` after the save-then-stop path ran."""

    def __init__(self, msg, bundle_path=None, stats=None, partial=None):
        super().__init__(msg)
        self.bundle_path = bundle_path
        self.stats = stats
        self.partial = partial


# ---- trace-time collector hook ---------------------------------------------
# The optimizer apply loops run inside the jitted step's trace; the model
# installs a collector around the user step function and the strategies
# feed it. A plain module global (not thread-local): one step traces at a
# time, and the eager path is likewise per-call with try/finally.

_collector = None

# The monitor the diag server's /healthz reports on: last one attached
# through Model.set_health_monitor (or set explicitly). Process-wide on
# purpose — the diagnostics surface answers for THE training job.
_active_monitor = None


def set_active_monitor(monitor):
    """Register (or clear, with None) the process's reporting monitor."""
    global _active_monitor
    _active_monitor = monitor
    return monitor


def active_monitor():
    """The monitor /healthz reports on, or None."""
    return _active_monitor


def collector():
    """The active StepStatsCollector, or None when health is off."""
    return _collector


def _set_collector(c):
    global _collector
    _collector = c


class StepStatsCollector:
    """Accumulates in-graph health statistics while the step traces.

    `group_of` maps id(param Tensor) -> layer-group name (the model passes
    the first path component of each param's get_params() name, so
    "l1.W" and "l1.b" both group under "l1"). Unknown params land in
    group "other".
    """

    def __init__(self, group_of=None):
        self.group_of = group_of or {}
        self.loss = None
        self._gsq = None         # sum of squared grad entries (fp32)
        self._nonfinite = None   # count of non-finite grad entries (int32)
        self._groups = {}        # group -> [param_sq, update_sq]

    # -- feeding (called at trace time from the optimizer loops; what the
    # collector adds to the step program goes under the device scope
    # `health`) ------------------------------------------------------------
    @jax.named_scope("health")
    def observe_loss(self, loss_arr):
        import jax.numpy as jnp
        self.loss = jnp.asarray(loss_arr).astype(jnp.float32)

    @staticmethod
    def _stats_pass(g, new, old):
        """(sum g^2, finite-grad-entry count, sum new^2, sum (new-old)^2)
        in ONE variadic lax.reduce per parameter: the elementwise
        transforms (square, isfinite, diff^2) are the reduce's operand
        producers — XLA fuses them into the reduction loop, so this is a
        single pass over the buffers — and the combiner is plain
        addition per slot, which XLA's Reduce contract REQUIRES to be
        associative+commutative (folding the transform into the combiner
        would compute garbage on any backend that merges partial
        accumulators through it, e.g. TPU tree reductions). Separate
        jnp.sum calls do NOT get re-fused on the CPU backend: measured
        9x slower as split passes on an 8M-element grad, and the merged
        4-slot reduce is another ~30% cheaper than two 2-slot ones."""
        import jax.numpy as jnp
        from jax import lax
        f32 = jnp.float32
        if g.dtype != f32:
            g = g.astype(f32)
        if new.dtype != f32:
            new = new.astype(f32)
        if old.dtype != f32:
            old = old.astype(f32)
        d = new - old
        operands = (g * g, jnp.isfinite(g).astype(f32), new * new, d * d)
        if g.ndim == 0:
            return operands
        zero = jnp.zeros((), f32)
        return lax.reduce(
            operands, (zero, zero, zero, zero),
            lambda acc, v: (acc[0] + v[0], acc[1] + v[1],
                            acc[2] + v[2], acc[3] + v[3]),
            tuple(range(g.ndim)))

    @jax.named_scope("health")
    def observe(self, param, grad_arr, old_arr, new_arr):
        """One (param, post-reduction grad, pre/post-update value)."""
        import jax.numpy as jnp
        g = jnp.asarray(grad_arr)
        new = jnp.asarray(new_arr)
        old = jnp.asarray(old_arr)
        if g.shape != new.shape:
            # defensive: a strategy fed mismatched buffers; fall back to
            # two reduces rather than mis-zip one fused pass
            gsq, fin, _, _ = self._stats_pass(g, g, g)
            _, _, psq, usq = self._stats_pass(new, new, old)
        else:
            gsq, fin, psq, usq = self._stats_pass(g, new, old)
        nf = jnp.int32(g.size) - fin.astype(jnp.int32)
        self._gsq = gsq if self._gsq is None else self._gsq + gsq
        self._nonfinite = nf if self._nonfinite is None \
            else self._nonfinite + nf
        grp = self.group_of.get(id(param), "other")
        slot = self._groups.setdefault(grp, [None, None])
        slot[0] = psq if slot[0] is None else slot[0] + psq
        slot[1] = usq if slot[1] is None else slot[1] + usq

    # -- finalize (still at trace time) ------------------------------------
    @jax.named_scope("health")
    def finalize(self, comm=None):
        """Reduce the accumulators into the step_stats pytree of scalars.

        With a Communicator on a >1 mesh axis: non-finite counts are
        pmax'd — the collector observes POST-reduction gradients, which
        are fully replicated under the dense/half strategies, so a psum
        would inflate the count world_size-fold; pmax yields the true
        count there and the worst shard's count for per-shard
        (partial/sparse) gradients. Norms are pmean'd (for replicated
        grads the mean IS the common value; otherwise it is the agreed
        per-shard summary). Every shard returns the SAME stats, so
        policies fire in lockstep.
        """
        import jax.numpy as jnp
        f32 = jnp.float32
        loss = self.loss if self.loss is not None \
            else jnp.asarray(jnp.nan, f32)
        gsq = self._gsq if self._gsq is not None else jnp.zeros((), f32)
        nf_g = self._nonfinite if self._nonfinite is not None \
            else jnp.zeros((), jnp.int32)
        nf_l = (1 - jnp.isfinite(loss).astype(jnp.int32))
        dist = comm is not None and comm.world_size > 1
        if dist:
            ws = comm.world_size
            nf_g = comm.all_reduce_max(nf_g)
            nf_l = comm.all_reduce_max(nf_l)
            gsq = comm.all_reduce(gsq) / ws
            loss = comm.all_reduce(loss) / ws
        stats = {
            "loss": loss,
            "grad_norm": jnp.sqrt(gsq),
            "nonfinite_grads": nf_g,
            "nonfinite_loss": nf_l,
        }
        groups = {}
        for grp, (psq, usq) in sorted(self._groups.items()):
            if dist:
                psq = comm.all_reduce(psq) / ws
                usq = comm.all_reduce(usq) / ws
            pn = jnp.sqrt(psq)
            un = jnp.sqrt(usq)
            groups[grp] = {
                "param_norm": pn,
                "update_norm": un,
                # update-to-param ratio: the classic LR sanity signal
                # (healthy ~1e-3; >>1e-2 diverging, <<1e-4 stalled)
                "update_ratio": un / jnp.maximum(pn, 1e-12),
            }
        stats["groups"] = groups
        # the agreed anomaly flag drives the in-graph skip select; under a
        # mesh it rides the dedicated agreement collective so the verdict
        # is cross-host by construction even for strategies whose grads
        # are not fully replicated
        bad = (nf_g + nf_l) > 0
        if comm is not None:
            bad = comm.agree_any(bad)
        stats["anomaly"] = bad.astype(jnp.int32)
        return stats


@jax.named_scope("health")
def apply_skip(stats, old_arrays, new_arrays):
    """In-graph conditional commit: when the agreed anomaly flag is set,
    keep every pre-step array (params, opt slots — the step-counter
    increment rolls back too, like a loss-scaler's overflow skip);
    otherwise take the updated ones. Runs inside the jitted step, so the
    skip lands on all shards in the same step with zero host round-trip.

    `new_arrays` may be LONGER than `old_arrays`: strategies with lazily
    created optimizer state (sparse error-feedback residuals) grow slots
    during the first traced step. Those slots have no pre-step buffer to
    select — their pre-step value is their creation-time init (zeros) —
    so on skip they roll back to zeros and on healthy steps they commit;
    zip-truncating them instead would drop the tail from the step output
    and reset the residuals every step.
    """
    import jax.numpy as jnp
    bad = stats["anomaly"] > 0
    out = [jnp.where(bad, o, n) for o, n in zip(old_arrays, new_arrays)]
    out.extend(jnp.where(bad, jnp.zeros_like(n), n)
               for n in new_arrays[len(old_arrays):])
    return out


# ---- flight recorder -------------------------------------------------------

class FlightRecorder:
    """Bounded ring of the last `capacity` steps' health stats; `dump`
    writes the ring + the recent EventLog tail to a JSONL bundle (plus an
    optional offending-batch snapshot via snapshot.py)."""

    def __init__(self, capacity=64, out_dir=".", event_tail=64):
        self.ring = deque(maxlen=int(capacity))
        self.out_dir = str(out_dir)
        self.event_tail = int(event_tail)
        self.last_bundle = None

    def record(self, rec: dict):
        self.ring.append(rec)

    def dump(self, reason: str, step: int, batch_arrays=None,
             path: str | None = None) -> str:
        """Write `flight_step<N>.jsonl` (header line, then one line per
        ring entry, then the EventLog tail) and return its path. With
        `batch_arrays` (list of host arrays), the offending batch is
        snapshotted next to it through snapshot.py as `<bundle>_batch.*`
        so the post-mortem can replay the exact inputs."""
        os.makedirs(self.out_dir, exist_ok=True)
        if path is None:
            path = os.path.join(self.out_dir, f"flight_step{int(step)}.jsonl")
        tail = list(observe.get_registry().recent)[-self.event_tail:]
        snap_prefix = None
        if batch_arrays:
            import numpy as np
            from .snapshot import Snapshot
            try:
                # memory-ledger birth-site hook: device buffers held
                # for this snapshot attribute to `flight_snapshot`
                # while they stay alive (host copies are ignored)
                from . import memory
                memory.note_arrays(memory.REGION_FLIGHT_SNAPSHOT,
                                   list(batch_arrays))
            except Exception:
                pass
            snap_prefix = os.path.splitext(path)[0] + "_batch"
            with Snapshot(snap_prefix, mode_write=True) as s:
                for i, a in enumerate(batch_arrays):
                    s.write(f"input{i}", np.asarray(a))
        try:
            # pin the exact executables that produced the anomalous step:
            # introspect's manifest carries a fingerprint per AOT build
            # (+ the HLO-text path when capture_hlo was on)
            from . import introspect
            execs = introspect.executable_manifest()[-8:] or None
        except Exception:
            execs = None
        header = {"kind": "flight_header", "ts": round(time.time(), 6),
                  "reason": reason, "step": int(step),
                  "n_steps": len(self.ring), "n_events": len(tail),
                  "batch_snapshot": snap_prefix,
                  "executables": execs}
        with open(path, "w", encoding="utf-8") as f:
            f.write(json.dumps(header, separators=(",", ":"),
                               default=str) + "\n")
            for rec in self.ring:
                f.write(json.dumps({"kind": "flight_step", **rec},
                                   separators=(",", ":"),
                                   default=str) + "\n")
            for ev in tail:
                # nested, not splatted: the event's own "kind" (step/
                # serving/health) must not clobber the line marker
                f.write(json.dumps({"kind": "flight_event", "event": ev},
                                   separators=(",", ":"),
                                   default=str) + "\n")
        self.last_bundle = path
        return path


def load_flight_bundle(path: str) -> dict:
    """Round-trip a FlightRecorder bundle: {"header", "steps", "events",
    "batch"} — `batch` is {name: ndarray} when the bundle carried a
    snapshot (loaded through snapshot.py), else None."""
    rows = observe.EventLog.read(path)
    header = next((r for r in rows if r.get("kind") == "flight_header"), {})
    out = {
        "header": header,
        "steps": [r for r in rows if r.get("kind") == "flight_step"],
        "events": [r["event"] for r in rows
                   if r.get("kind") == "flight_event" and "event" in r],
        "batch": None,
    }
    prefix = header.get("batch_snapshot")
    if prefix:
        try:
            from .snapshot import Snapshot
            s = Snapshot(prefix, mode_write=False)
            out["batch"] = {n: s.read(n).numpy() for n in s.names()}
        except (OSError, FileNotFoundError):
            pass  # bundle moved without its sidecar; stats still load
    return out


# ---- host-side monitor -----------------------------------------------------

class HealthMonitor:
    """Watches the per-step stats, exports `singa_health_*` metrics,
    applies the anomaly policy, and owns the flight recorder.

    ema_decay/spike_factor: the loss EMA and an EMA of absolute deviation
    (a robust scale estimate) update only on finite losses; a step whose
    deviation exceeds `spike_factor` x the deviation-EMA after
    `warmup_steps` healthy steps scores as a spike anomaly.
    grad_norm_limit: optional hard ceiling on the global grad norm.
    snapshot_batch: include the offending batch in the bundle (costs one
    host fetch of the inputs, only on anomaly steps).
    """

    def __init__(self, policy="warn", ema_decay=0.98, spike_factor=10.0,
                 warmup_steps=10, grad_norm_limit=None, window=64,
                 out_dir=".", snapshot_batch=False, recorder=None,
                 dump_cooldown=None):
        if policy not in POLICIES:
            raise ValueError(f"policy {policy!r} not in {POLICIES}")
        self.policy = policy
        self.ema_decay = float(ema_decay)
        self.spike_factor = float(spike_factor)
        self.warmup_steps = int(warmup_steps)
        self.grad_norm_limit = grad_norm_limit
        self.snapshot_batch = bool(snapshot_batch)
        self.recorder = recorder or FlightRecorder(capacity=window,
                                                   out_dir=out_dir)
        # re-dump suppression inside one anomaly EPISODE (consecutive
        # anomalous steps): a permanently diverged run must not write a
        # bundle — full ring serialization + optional batch snapshot —
        # every single step. The first anomaly after a healthy step
        # always dumps; within an episode, re-dump only after the ring
        # has fully turned over (default: the ring capacity), when the
        # bundle actually contains new information.
        self.dump_cooldown = int(dump_cooldown
                                 if dump_cooldown is not None
                                 else self.recorder.ring.maxlen)
        self._ema = None
        self._dev_ema = None
        self._healthy_steps = 0
        self._prev_anomalous = False
        self._last_dump_step = None
        self.last_action = None

    # -- metric plumbing ---------------------------------------------------
    @staticmethod
    def _metrics():
        # observe.gauge/counter spelled out (no aliases) so the static
        # lint (tools/check_metrics_names.py) sees every registration
        return {
            "loss": observe.gauge(
                "singa_health_loss",
                "last train-step loss seen by the health layer"),
            "grad_norm": observe.gauge(
                "singa_health_grad_norm",
                "global gradient L2 norm, last step"),
            "spike": observe.gauge(
                "singa_health_spike_score",
                "loss deviation / EMA deviation (robust z-score)"),
            "nonfinite": observe.gauge(
                "singa_health_nonfinite_grads",
                "non-finite gradient entries, last step"),
            "param_norm": observe.gauge(
                "singa_health_param_norm",
                "per-layer-group parameter L2 norm"),
            "update_norm": observe.gauge(
                "singa_health_update_norm",
                "per-layer-group update L2 norm"),
            "update_ratio": observe.gauge(
                "singa_health_update_ratio",
                "per-layer-group update-to-param norm ratio"),
            "anomaly": observe.counter(
                "singa_health_anomaly_total",
                "training anomalies by kind"),
            "skipped": observe.counter(
                "singa_health_skipped_steps_total",
                "train steps whose update was discarded"),
            "halt": observe.counter(
                "singa_health_halt_total",
                "halt-policy firings"),
            "overflow": observe.counter(
                "singa_health_overflow_total",
                "AMP steps with non-finite grads "
                "(loss-scale-overflow analog)"),
        }

    def verdict(self) -> dict:
        """One JSON-able health summary (the diag server's /healthz
        body): the last action taken, the policy, and the most recent
        step's recorded stats."""
        last = self.recorder.ring[-1] if self.recorder.ring else None
        return {
            "status": self.last_action or "idle",
            "policy": self.policy,
            "healthy_steps": self._healthy_steps,
            "last_step": last,
            "last_bundle": self.recorder.last_bundle,
        }

    def note_external(self, kind: str, detail=None, step=None,
                      action=None) -> str:
        """An out-of-band anomaly from OUTSIDE the step path — the fleet
        aggregator's sustained-straggler verdict (KIND_STRAGGLER) is the
        first producer. Counted, ring-recorded and policy-mapped like a
        step anomaly, but it never raises here: the producer usually
        runs off the training thread, where a raise would vanish. Under
        the halt policy this sets the monitor's status to "halt" (so
        /healthz flips to 503) and the TRAINING-LOOP side hook —
        `fleet.check_straggler_halt`, called by TrainController every
        step — does the raising. Returns the mapped action
        ("warn" | "halt"); skip_step has no meaning for an anomaly that
        is not a pending update, so it maps to warn. `action` overrides
        the policy mapping when the PRODUCER already resolved one — the
        fleet aggregator's own policy may differ from the monitor's,
        and the two surfaces must not disagree about whether a halt
        happened."""
        if action is not None and action not in ("warn", "halt"):
            raise ValueError(f"action {action!r} not in ('warn','halt')")
        m = self._metrics()
        m["anomaly"].inc(kind=kind)
        rec = {"external": kind, "detail": detail,
               "step": int(step) if step is not None else None,
               "anomaly_kinds": [kind]}
        self.recorder.record(rec)
        if action is None:
            action = "halt" if self.policy == "halt" else "warn"
        if action == "halt":
            m["halt"].inc()
        self.last_action = action
        observe.get_registry().emit(
            {"kind": "health", "external": kind, "detail": detail,
             "policy": self.policy, "action": action})
        return action

    def _spike_score(self, loss: float) -> float:
        import math
        if not math.isfinite(loss):
            return 0.0  # non-finite is its own anomaly kind, not a spike
        if self._ema is None:
            self._ema = loss
            self._dev_ema = 0.0
            return 0.0
        dev = abs(loss - self._ema)
        score = dev / (self._dev_ema + 1e-8) \
            if self._healthy_steps >= self.warmup_steps else 0.0
        d = self.ema_decay
        self._ema = d * self._ema + (1 - d) * loss
        self._dev_ema = d * self._dev_ema + (1 - d) * dev
        return score

    # -- the per-step entry point ------------------------------------------
    def on_step(self, stats: dict, step: int, batch_provider=None,
                amp: bool = False, in_graph_skip: bool = False) -> str:
        """Feed one step's (host-fetched) stats. Returns the action taken:
        "ok" | "warn" | "skip" | (raises HealthError on halt).
        `batch_provider`: zero-arg callable yielding host copies of the
        step inputs — only invoked on an anomaly with snapshot_batch set.
        `in_graph_skip`: the caller's compiled step already applied the
        skip select for nonfinite anomalies (Model graph mode does)."""
        m = self._metrics()
        loss = float(stats.get("loss", float("nan")))
        grad_norm = float(stats.get("grad_norm", 0.0))
        nf_g = int(stats.get("nonfinite_grads", 0))
        nf_l = int(stats.get("nonfinite_loss", 0))
        spike = self._spike_score(loss)
        m["loss"].set(loss)
        m["grad_norm"].set(grad_norm)
        m["spike"].set(spike)
        m["nonfinite"].set(nf_g)
        groups = stats.get("groups") or {}
        for grp, gs in groups.items():
            m["param_norm"].set(float(gs["param_norm"]), group=grp)
            m["update_norm"].set(float(gs["update_norm"]), group=grp)
            m["update_ratio"].set(float(gs["update_ratio"]), group=grp)

        kinds = []
        if nf_g > 0:
            kinds.append(KIND_NONFINITE_GRAD)
        if nf_l > 0:
            kinds.append(KIND_NONFINITE_LOSS)
        if spike > self.spike_factor:
            kinds.append(KIND_LOSS_SPIKE)
        if self.grad_norm_limit is not None \
                and grad_norm > float(self.grad_norm_limit):
            kinds.append(KIND_GRAD_NORM)

        rec = {"step": int(step), "loss": loss, "grad_norm": grad_norm,
               "nonfinite_grads": nf_g, "nonfinite_loss": nf_l,
               "spike_score": round(spike, 6),
               "groups": {g: {k: float(v) for k, v in gs.items()}
                          for g, gs in groups.items()},
               "anomaly_kinds": kinds}
        self.recorder.record(rec)
        if not kinds:
            self._healthy_steps += 1
            self._prev_anomalous = False
            self.last_action = "ok"
            return "ok"

        for k in kinds:
            m["anomaly"].inc(kind=k)
        nonfinite = nf_g > 0 or nf_l > 0
        if amp and nf_g > 0:
            # the mixed-precision overflow signal: with skip_step this IS
            # the loss-scaler's overflow machinery (skip update, keep
            # params) minus the scale adjustment bf16 doesn't need
            m["overflow"].inc()
        do_dump = (not self._prev_anomalous
                   or self._last_dump_step is None
                   or int(step) - self._last_dump_step
                   >= self.dump_cooldown)
        self._prev_anomalous = True
        bundle = self.recorder.last_bundle
        if do_dump:
            batch = None
            if self.snapshot_batch and batch_provider is not None:
                try:
                    batch = batch_provider()
                except Exception:
                    batch = None
            bundle = self.recorder.dump(reason=",".join(kinds), step=step,
                                        batch_arrays=batch)
            self._last_dump_step = int(step)
        observe.get_registry().emit(
            {"kind": "health", "step": int(step), "anomaly": kinds,
             "policy": self.policy, "bundle": bundle, "loss": loss,
             "grad_norm": grad_norm, "nonfinite_grads": nf_g})
        if self.policy == "halt":
            m["halt"].inc()
            self.last_action = "halt"
            raise HealthError(
                f"training halted at step {step}: {','.join(kinds)} "
                f"(flight bundle: {bundle})", bundle_path=bundle, stats=rec)
        if self.policy == "skip_step" and nonfinite and in_graph_skip:
            # the compiled step already kept the pre-step params on every
            # shard; this is the host-side acknowledgement
            m["skipped"].inc()
            self.last_action = "skip"
            return "skip"
        # warn — or skip_step on an anomaly the in-graph select cannot
        # cover (loss spike: the update is already committed)
        self.last_action = "warn"
        return "warn"


def record_nan_logits(n: int, kind: str):
    """Serving-side NaN watch: non-finite logits seen during one decode
    call (prefill + every generated position)."""
    if n <= 0 or not observe.is_enabled():
        return
    observe.counter("singa_health_nan_logits_total",
                    "non-finite logit entries seen while decoding"
                    ).inc(float(n), kind=kind)


__all__ = [
    "POLICIES", "HealthError", "StepStatsCollector", "collector",
    "KIND_STRAGGLER", "KIND_MEM_LEAK", "KIND_HANG", "KIND_SLO",
    "KIND_DIVERGENCE", "KIND_REGRESSION",
    "apply_skip", "FlightRecorder", "load_flight_bundle", "HealthMonitor",
    "record_nan_logits", "set_active_monitor", "active_monitor",
]
