"""Driver benchmark: training throughput on synthetic data, self-validating.

Mirrors the reference harness (examples/cifar_distributed_cnn/benchmark.py:
34-92): synthetic data, time `iters` graph-mode train steps after warmup,
report throughput. Prints ONE JSON line whose headline is
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
plus self-validation fields so the number can be *believed*:
  - flops_per_step: XLA cost analysis of the exact compiled step
  - step_ms_{median,mean,p10,p90}: per-step latency distribution, each step
    fenced by a device->host fetch (immune to broken async block paths)
  - model_tflops / mfu_vs_peak: achieved FLOP rate vs the chip's bf16 peak
  - mfu_suspect: true if the pipelined reading implies >100% MFU; in that
    case the headline value falls back to the fenced per-step reading.

Models: resnet50 (img/s, MXU conv path) and gpt (tokens/s, flash-attention
path).
"""

import argparse
import json
import sys
import time


# Per-generation peaks (public spec sheets) live in singa_tpu.introspect —
# one table feeds this harness, the MFU gauge, and the explain report.
# >100% of the flops peak is a broken harness by definition, whatever the
# dtype; the HBM table drives the roofline readout (bound = memory when
# bytes/BW exceeds flops/peak).
from singa_tpu.introspect import (  # noqa: E402
    PEAK_TFLOPS_BF16 as _PEAK_TFLOPS,
    PEAK_HBM_GBS as _PEAK_HBM_GBS,
    chip_peak as _chip_peak,
)


def _chip_peak_tflops(device_kind: str):
    return _chip_peak(device_kind, _PEAK_TFLOPS)


def require_tpu(script: str):
    """The TPU device a benchmark runs on. With none attached: one line
    on stderr and exit code 2 — a CPU run at shrunk shapes is a
    different program, not a smaller measurement of this one."""
    from singa_tpu import device
    try:
        return device.create_tpu_device()
    except RuntimeError as e:
        print(f"{script}: {e}", file=sys.stderr)
        sys.exit(2)


def use_compile_cache(warm_root=None):
    """Turn on the XLA persistent compile cache for a bench run. It
    lives where `JAX_COMPILATION_CACHE_DIR` says when that is set, else
    under `<warm_root>/xla` with the warm store enabled
    (`--compile-cache`), else in the fixed `.jax_cache/` beside this
    file."""
    import os
    from singa_tpu import warmstart
    if warm_root:
        warmstart.enable(warm_root)
    else:
        warmstart.configure_xla_cache(os.path.join(
            os.path.dirname(os.path.abspath(__file__)), ".jax_cache"))


def build_bench_model(model="resnet50", batch=32, size=224, dtype="float32",
                      gpt_dim=2048, gpt_layers=8, gpt_heads=16,
                      gpt_vocab=8192, dev=None, seed=0):
    """Build one bench model plus a synthetic batch on `dev`.

    Shared by the timed harness below and `python -m singa_tpu.introspect`
    (the explain report describes the exact executables the bench times).
    Returns (model, tx, ty, items_per_step, unit, model_factory).
    """
    import numpy as np
    from singa_tpu import device, models, tensor

    dev = dev or device.best_device()
    rng = np.random.RandomState(seed)
    if model == "gpt":
        seq = size if size > 32 else 512
        def model_factory():
            return models.create_model(
                "gpt", vocab_size=gpt_vocab, max_seq=seq, dim=gpt_dim,
                num_heads=gpt_heads, num_layers=gpt_layers)

        m = model_factory()
        ids = rng.randint(0, gpt_vocab, (batch, seq)).astype(np.int32)
        tgt = np.roll(ids, -1, axis=1).astype(np.int32)
        tx = tensor.from_numpy(ids, device=dev)
        ty = tensor.from_numpy(tgt, device=dev)
        return m, tx, ty, batch * seq, "tokens/s", model_factory
    if model == "mlp":
        def model_factory():
            return models.create_model("mlp", data_size=size,
                                       num_classes=10)

        m = model_factory()
        x_np = rng.standard_normal((batch, size)).astype(np.float32)
        y_np = rng.randint(0, 10, batch).astype(np.int32)
        tx = tensor.Tensor(data=x_np, device=dev, dtype=dtype)
        ty = tensor.from_numpy(y_np, device=dev)
        return m, tx, ty, batch, "img/s", model_factory

    def model_factory():
        return models.create_model(model, num_channels=3)

    m = model_factory()
    x_np = rng.standard_normal((batch, 3, size, size)).astype(np.float32)
    y_np = rng.randint(0, 10, batch).astype(np.int32)
    tx = tensor.Tensor(data=x_np, device=dev, dtype=dtype)
    ty = tensor.from_numpy(y_np, device=dev)
    return m, tx, ty, batch, "img/s", model_factory


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50",
                   choices=["resnet50", "resnet18", "cnn", "gpt"])
    p.add_argument("--batch", type=int, default=None,
                   help="default: 32 (resnet/cnn), 8 (gpt)")
    p.add_argument("--size", type=int, default=None,
                   help="image side (resnet) / sequence length (gpt); "
                        "default: 224 (resnet/cnn), 1024 (gpt)")
    p.add_argument("--iters", type=int, default=100)
    p.add_argument("--warmup", type=int, default=5)
    p.add_argument("--step-samples", type=int, default=30,
                   help="steps to time individually for the latency "
                        "distribution")
    p.add_argument("--dtype", default="float32",
                   choices=["float32", "bfloat16"])
    p.add_argument("--gpt-dim", type=int, default=2048,
                   help="gpt model width. The default (2048, 8 layers, "
                        "b8 s1024) is the compute-bound regime: ~62%% MFU "
                        "on v5e. Small widths (512) are memory-bound and "
                        "show ~31%% — that's the model's arithmetic "
                        "intensity, not the framework (PROFILE.md)")
    p.add_argument("--gpt-layers", type=int, default=8)
    p.add_argument("--gpt-heads", type=int, default=16)
    p.add_argument("--amp", action="store_true", default=None,
                   help="mixed precision: bf16 compute, fp32 master "
                        "weights (compile(amp='bfloat16')). Default: on "
                        "(the canonical TPU training mode); --no-amp for "
                        "pure fp32")
    p.add_argument("--no-amp", dest="amp", action="store_false")
    p.add_argument("--trace", default=None, metavar="DIR",
                   help="capture an xplane trace of the timed loop into DIR "
                        "and print a per-op device-time table (singa_tpu."
                        "xprof) to stderr — the TPU analog of the "
                        "reference's scheduler per-op profile")
    p.add_argument("--health", action="store_true",
                   help="after the main run, re-time the loop with the "
                        "training-health layer (singa_tpu.health) attached "
                        "and record the in-graph stats' per-step overhead "
                        "vs the no-health run into the JSON "
                        "(health_ms_per_step / health_overhead_pct), so "
                        "regressions in the stats cost show in BENCH_*.json")
    p.add_argument("--mem", action="store_true",
                   help="after the main run, install the device-memory "
                        "ledger (singa_tpu.memory) and A/B the fenced "
                        "step time with per-step snapshots on vs off "
                        "(paired, alternating order — same protocol as "
                        "--health), then record the overhead "
                        "(mem_ms_per_step / mem_overhead_pct), the "
                        "region breakdown, the reconciliation check, "
                        "the compile-count delta (must be 0: snapshots "
                        "are host-side only) and the pre-flight fit "
                        "estimate into the JSON record")
    p.add_argument("--watchdog", action="store_true",
                   help="measure the watchdog guard's per-step cost "
                        "(paired alternating enabled/disabled samples, "
                        "same protocol as --mem) and record "
                        "watchdog_ms_per_step / watchdog_overhead_pct "
                        "/ watchdog_compile_delta; target <=1% with "
                        "compile_count unchanged")
    p.add_argument("--regress", action="store_true",
                   help="measure the regression detector's per-step "
                        "cost (singa_tpu.regress): paired alternating "
                        "listener-attached/detached samples plus a "
                        "direct measurement of the span-listener feed "
                        "(same protocol as --watchdog) and record "
                        "regress_us_per_step / regress_overhead_pct / "
                        "regress_compile_delta; target <=1% with "
                        "compile_count unchanged")
    p.add_argument("--mem-out", default=None, metavar="FILE",
                   help="with --mem: also write the focused memory "
                        "records as JSONL (the MEM_r*.json artifact "
                        "tools/bench_trend.py aggregates)")
    p.add_argument("--explain", action="store_true",
                   help="add the AOT introspection fields to the JSON "
                        "record (singa_tpu.introspect): mfu_pct, "
                        "compile_{trace,lower,backend}_s phase times and "
                        "hbm_temps_bytes of the compiled step — mirrored "
                        "into singa_bench_* gauges like every other "
                        "field")
    p.add_argument("--goodput", action="store_true",
                   help="install the goodput tracker (singa_tpu.goodput) "
                        "for the whole run and emit the wall-time bucket "
                        "breakdown (goodput_<bucket>_s) + goodput_ratio "
                        "into the JSON record and the singa_bench_* "
                        "mirror")
    p.add_argument("--overlap", action="store_true",
                   help="A/B the overlap layer (singa_tpu.overlap): time "
                        "a fit over a sleep-injected iterator with device "
                        "prefetch off vs on and emit dispatch_us_per_step "
                        "(un-fenced call wall time — host dispatch cost "
                        "on an async backend), the goodput data_wait/step "
                        "bucket deltas per arm, and overlap_speedup into "
                        "the JSON record + singa_bench_* mirror")
    p.add_argument("--ckpt-async", action="store_true",
                   help="time save_checkpoint: async blocking portion "
                        "(ckpt_blocking_s, the device->host snapshot) vs "
                        "time to durable (ckpt_total_s, includes the "
                        "wait_for_checkpoints barrier) vs the fully "
                        "synchronous write (ckpt_sync_s)")
    p.add_argument("--resume", action="store_true",
                   help="resilience cold-vs-resumed A/B: a controller "
                        "run with periodic async saves is killed "
                        "mid-epoch (injected fault), then auto-resumed "
                        "in a fresh model from the latest valid "
                        "checkpoint; records resume_restore_s, "
                        "steps_replayed and the goodput "
                        "checkpoint-bucket delta of each arm into the "
                        "JSON record + singa_bench_* mirror")
    p.add_argument("--diag-port", type=int, default=None, metavar="PORT",
                   help="serve the live diagnostics HTTP endpoints "
                        "(/metrics /healthz /statusz /flightz /profilez) "
                        "on PORT (0 = ephemeral) while the bench runs; "
                        "implies --goodput")
    p.add_argument("--fleet-dir", default=None, metavar="DIR",
                   help="publish this process's telemetry shard "
                        "(metrics + goodput + spans) into DIR while the "
                        "bench runs, so a fleet coordinator aggregating "
                        "DIR sees the bench as one more worker "
                        "(singa_tpu.fleet)")
    p.add_argument("--metrics-out", default=None, metavar="FILE",
                   help="write the observe registry as Prometheus text "
                        "after the run (step histograms, compile counts, "
                        "and the bench numbers as singa_bench_* gauges)")
    p.add_argument("--events-out", default=None, metavar="FILE",
                   help="attach a JSONL EventLog: per-step records during "
                        "the run plus the final bench record, same schema "
                        "as runtime telemetry")
    p.add_argument("--compile-cache", default=None, metavar="DIR",
                   help="enable the warm store (singa_tpu.warmstart) "
                        "rooted at DIR: staged builds persist serialized "
                        "executables + the XLA compile cache there and a "
                        "second run loads them — with --goodput the "
                        "compile bucket collapses on the warm run; the "
                        "record gains a compile_cache section")
    args = p.parse_args()
    if args.amp is None:
        args.amp = True
    # per-model defaults; the resnet50 headline metric name
    # (resnet50_train_throughput_b32_s224_...) is pinned across rounds
    if args.batch is None:
        args.batch = 8 if args.model == "gpt" else 32
    if args.size is None:
        args.size = 1024 if args.model == "gpt" else 224

    import numpy as np
    import jax
    from singa_tpu import models, observe, opt, tensor

    if args.events_out:
        observe.set_event_log(args.events_out)

    dev = require_tpu("bench.py")
    # before any staged build so the FIRST compile already lands in the
    # cache (and, with --compile-cache, exports into the warm store)
    use_compile_cache(args.compile_cache)

    goodput_tracker = None
    if args.goodput or args.diag_port is not None:
        from singa_tpu import goodput as goodput_mod
        # installed before the model exists so warmup compiles land in
        # the `compile` bucket
        goodput_tracker = goodput_mod.install()

    fleet_writer = None
    if args.fleet_dir:
        from singa_tpu import fleet
        # started before the build so compile-era spans ride the shards
        fleet_writer = fleet.start_shard_writer(args.fleet_dir,
                                                interval_s=0.5)

    seq = args.size if args.size > 32 else 512  # gpt: attn-flops formula
    m, tx, ty, items_per_step, unit, model_factory = build_bench_model(
        model=args.model, batch=args.batch, size=args.size,
        dtype=args.dtype, gpt_dim=args.gpt_dim, gpt_layers=args.gpt_layers,
        gpt_heads=args.gpt_heads, dev=dev)

    sgd = opt.SGD(lr=0.1, momentum=0.9, weight_decay=1e-5)
    m.set_optimizer(sgd)
    m.compile([tx], is_train=True, use_graph=True,
              amp="bfloat16" if args.amp else None)

    if args.diag_port is not None:
        srv = observe.start_diag_server(port=args.diag_port, model=m,
                                        device=dev)
        print(f"# diag server: {srv.url} "
              "(/metrics /healthz /statusz /flightz /profilez)",
              file=sys.stderr)

    # Always run >=1 untimed step: compiles the graph and guarantees
    # out/loss exist for the fence below even with --warmup 0.
    for _ in range(max(args.warmup, 1)):
        out, loss = m(tx, ty)
    float(np.asarray(jax.device_get(loss.data)))  # hard fence: fetch to host

    # ---- pipelined throughput (reference harness semantics) --------------
    if args.trace:
        dev.StartTrace(args.trace)
    t0 = time.perf_counter()
    for _ in range(args.iters):
        out, loss = m(tx, ty)
    # Fence via device->host fetch of the final loss: it depends on the
    # whole step chain and cannot complete before the compute does, even if
    # a backend's block_until_ready is a no-op.
    final_loss = float(np.asarray(jax.device_get(loss.data)))
    elapsed = time.perf_counter() - t0
    throughput_pipelined = args.iters * items_per_step / elapsed
    if args.trace:
        dev.StopTrace()
        from singa_tpu import xprof
        rows = xprof.op_table(args.trace)
        print(f"# per-op device time over {args.iters} steps "
              f"({args.trace}):", file=sys.stderr)
        print(xprof.format_table(rows, top=30), file=sys.stderr)
        print("# by XLA hlo_category (measured time + raw bytes + flops, "
              "per step):", file=sys.stderr)
        print(xprof.format_hlo_categories(
            xprof.hlo_category_table(args.trace, steps=args.iters)),
            file=sys.stderr)

    # ---- fenced per-call latency distribution ----------------------------
    # Each call fenced by a host fetch: this bounds true step latency from
    # above (includes the host<->device round-trip) and proves steps
    # actually execute.
    step_ms = []
    for _ in range(args.step_samples):
        t1 = time.perf_counter()
        out, loss = m(tx, ty)
        np.asarray(jax.device_get(loss.data))
        step_ms.append((time.perf_counter() - t1) * 1e3)
    step_ms_arr = np.asarray(step_ms)
    med_ms = float(np.median(step_ms_arr))
    throughput_stepwise = items_per_step / (med_ms / 1e3)

    # ---- health-stat overhead (--health) ---------------------------------
    # A second, identically-shaped model with the in-graph numerics
    # telemetry compiled into its step (warn policy, so nothing skips).
    # The two executables are sampled as adjacent-in-time PAIRS with the
    # in-pair order alternating, and the overhead is the median of the
    # paired deltas over the median base — pairing cancels the slow load
    # drift of a shared host that makes block-wise or single-loop
    # comparisons swing by >10% run to run. The delta is the cost of the
    # fused grad-norm/isfinite/update-norm reductions plus the per-step
    # stats fetch.
    # --explain must describe the executable the timed run above used;
    # snapshot it NOW, before the --health arm compiles a second,
    # health-instrumented step under the same "step" introspect key
    explain_build = None
    if args.explain:
        from singa_tpu import introspect
        explain_build = introspect.last_build("step") or {}

    health_ms_per_step = None
    health_overhead_pct = None
    if args.health:
        import tempfile

        from singa_tpu import health as health_mod
        mh = model_factory()
        mh.set_optimizer(opt.SGD(lr=0.1, momentum=0.9, weight_decay=1e-5))
        # spike watchdog off (inf threshold): early-training loss decline
        # would otherwise trip a flight-recorder dump INSIDE a timed
        # sample (file I/O in the measurement); bundles go to a temp dir,
        # never the caller's CWD
        mh.compile([tx], is_train=True, use_graph=True,
                   amp="bfloat16" if args.amp else None,
                   health=health_mod.HealthMonitor(
                       policy="warn", spike_factor=float("inf"),
                       out_dir=tempfile.mkdtemp(prefix="bench_health_")))

        def fenced_ms(mm):
            t1 = time.perf_counter()
            _o, ls = mm(tx, ty)
            np.asarray(jax.device_get(ls.data))
            return (time.perf_counter() - t1) * 1e3

        for _ in range(max(args.warmup, 1)):
            mh(tx, ty)
        fenced_ms(mh)
        fenced_ms(m)  # both arms warm
        bases, healths = [], []
        for i in range(3 * args.step_samples):
            if i % 2 == 0:
                bases.append(fenced_ms(m))
                healths.append(fenced_ms(mh))
            else:
                healths.append(fenced_ms(mh))
                bases.append(fenced_ms(m))
        deltas = np.asarray(healths) - np.asarray(bases)
        base_ms = float(np.median(np.asarray(bases)))
        health_ms_per_step = base_ms + float(np.median(deltas))
        health_overhead_pct = 100.0 * float(np.median(deltas)) / base_ms

    # ---- device-memory ledger overhead + breakdown (--mem) ---------------
    # Same paired-alternating protocol as --health: the delta is the
    # host-side cost of one jax.live_arrays() enumeration + attribution
    # per step. The compile-count delta is asserted into the record —
    # the ledger never traces, so installing it must not retrace.
    mem_fields = {}
    if args.mem:
        from singa_tpu import memory as memory_mod

        led = memory_mod.install_ledger()

        def fenced_mem_ms():
            t1 = time.perf_counter()
            _o, ls = m(tx, ty)
            np.asarray(jax.device_get(ls.data))
            return (time.perf_counter() - t1) * 1e3

        cc = observe.get_registry().get("singa_model_compile_total")
        compiles_before = sum(v for _n, _k, v in cc.samples()) if cc else 0
        fenced_mem_ms()  # both arms warm (the first snapshot builds
        fenced_mem_ms()  # the provider id sets)
        offs, ons = [], []
        for i in range(2 * args.step_samples):
            if i % 2 == 0:
                led.enabled = False
                offs.append(fenced_mem_ms())
                led.enabled = True
                ons.append(fenced_mem_ms())
            else:
                led.enabled = True
                ons.append(fenced_mem_ms())
                led.enabled = False
                offs.append(fenced_mem_ms())
        led.enabled = True
        deltas = np.asarray(ons) - np.asarray(offs)
        mem_base_ms = float(np.median(np.asarray(offs)))
        mem_ms_per_step = mem_base_ms + float(np.median(deltas))
        mem_overhead_pct = 100.0 * float(np.median(deltas)) / mem_base_ms
        cc = observe.get_registry().get("singa_model_compile_total")
        compiles_after = sum(v for _n, _k, v in cc.samples()) if cc else 0
        snap = led.snapshot()
        # reconciliation against an INDEPENDENT enumeration (snapshot
        # accumulates regions and total in one pass, so comparing
        # those two against each other would be a tautology)
        reconciled = (sum(snap["regions"].values())
                      == snap["total_bytes"]
                      == memory_mod.total_live_bytes())
        fit = memory_mod.estimate_fit(model=m, device=dev)
        mem_fields = {
            "mem_ms_per_step": round(mem_ms_per_step, 3),
            "mem_overhead_pct": round(mem_overhead_pct, 2),
            "mem_compile_delta": int(compiles_after - compiles_before),
            "mem_reconciled": bool(reconciled),
            "mem_total_bytes": snap["total_bytes"],
            "mem_live_arrays": snap["n_arrays"],
            "mem_params_bytes": snap["regions"]["params"],
            "mem_opt_state_bytes": snap["regions"]["opt_state"],
            "mem_unattributed_bytes": snap["regions"]["unattributed"],
            "mem_est_peak_bytes": fit["estimated_peak_bytes"],
            "mem_limit_bytes": fit["limit_bytes"],
        }
        if args.mem_out:
            mem_ok = bool(reconciled
                          and compiles_after == compiles_before)
            with open(args.mem_out, "w", encoding="utf-8") as f:
                for metric, value, mu in (
                        # the overhead as an ms delta, so bench_trend's
                        # direction inference (lower-is-better on ms /
                        # _bytes) judges every record correctly
                        ("mem_overhead_ms", float(np.median(deltas)),
                         "ms"),
                        ("mem_ms_per_step", mem_ms_per_step, "ms"),
                        ("mem_total_bytes", snap["total_bytes"],
                         "bytes"),
                        ("mem_params_bytes", snap["regions"]["params"],
                         "bytes"),
                        ("mem_est_peak_bytes",
                         fit["estimated_peak_bytes"], "bytes")):
                    f.write(json.dumps(
                        {"metric": metric, "value": round(float(value), 4),
                         "unit": mu, "model": args.model}) + "\n")
                f.write(json.dumps({
                    "ok": mem_ok, "reconciled": bool(reconciled),
                    "compile_delta": int(compiles_after
                                         - compiles_before),
                    "overhead_pct": round(mem_overhead_pct, 2),
                    "regions": snap["regions"],
                    "model": args.model}) + "\n")
        memory_mod.uninstall_ledger()

    # ---- watchdog guard overhead (--watchdog) -----------------------------
    # The guard adds pure host work per step: arm (deadline resolve +
    # dict insert) + disarm (dict remove + one p99 recompute). That is
    # ~10us against a >=ms step — BELOW what the paired-A/B protocol
    # can resolve on a noisy shared host (a 300ms CPU step swings more
    # per sample than the guard costs per thousand). So the headline is
    # a DIRECT measurement: the median of many timed arm/disarm cycles
    # against the measured base step, with the paired A/B delta kept as
    # a sanity field and the compile-count delta asserted (the guard is
    # host-side only and must never retrace).
    watchdog_fields = {}
    if args.watchdog:
        from singa_tpu import watchdog as watchdog_mod

        wd = watchdog_mod.install_watchdog(floor_s=600.0,
                                           poll_interval_s=0.25)

        def fenced_wd_ms():
            t1 = time.perf_counter()
            _o, ls = m(tx, ty)
            np.asarray(jax.device_get(ls.data))
            return (time.perf_counter() - t1) * 1e3

        cc = observe.get_registry().get("singa_model_compile_total")
        wd_compiles_before = sum(
            v for _n, _k, v in cc.samples()) if cc else 0
        fenced_wd_ms()  # both arms warm
        fenced_wd_ms()
        offs, ons = [], []
        for i in range(2 * args.step_samples):
            if i % 2 == 0:
                wd.enabled = False
                offs.append(fenced_wd_ms())
                wd.enabled = True
                ons.append(fenced_wd_ms())
            else:
                wd.enabled = True
                ons.append(fenced_wd_ms())
                wd.enabled = False
                offs.append(fenced_wd_ms())
        wd.enabled = True
        # direct guard cost: batches of arm/disarm cycles, median batch
        # (the step path arms exactly one `step` guard per step)
        batch_n, batches = 200, []
        for _ in range(15):
            t1 = time.perf_counter()
            for _ in range(batch_n):
                with watchdog_mod.guard("step"):
                    pass
            batches.append((time.perf_counter() - t1) / batch_n)
        guard_us = float(np.median(np.asarray(batches))) * 1e6
        deltas = np.asarray(ons) - np.asarray(offs)
        wd_base_ms = float(np.median(np.asarray(offs)))
        wd_overhead_pct = 100.0 * (guard_us / 1e3) / wd_base_ms
        cc = observe.get_registry().get("singa_model_compile_total")
        wd_compiles_after = sum(
            v for _n, _k, v in cc.samples()) if cc else 0
        step_state = wd.op_state("step")
        watchdog_fields = {
            "watchdog_guard_us": round(guard_us, 3),
            "watchdog_ms_per_step": round(wd_base_ms + guard_us / 1e3,
                                          3),
            "watchdog_overhead_pct": round(wd_overhead_pct, 4),
            "watchdog_ab_delta_pct": round(
                100.0 * float(np.median(deltas)) / wd_base_ms, 2),
            "watchdog_compile_delta": int(wd_compiles_after
                                          - wd_compiles_before),
            "watchdog_step_samples": len(step_state.samples),
            "watchdog_step_deadline_s": step_state.deadline(),
            "watchdog_ok": bool(
                wd_overhead_pct <= 1.0
                and wd_compiles_after == wd_compiles_before),
        }
        watchdog_mod.uninstall_watchdog()

    # ---- regression-detector overhead (--regress) -------------------------
    # Same story as the watchdog guard: the detector adds pure host work
    # per step — one span-listener callback (leaf split, signal map,
    # lock, deque append; every `window`th call also closes a window:
    # a sorted() median + the CUSUM update). Far below what the paired
    # A/B resolves on a noisy host, so the headline is the DIRECT
    # median of many timed feed calls against the measured base step,
    # with the paired delta as a sanity field and the compile-count
    # delta asserted (the detector is host-side only and must never
    # retrace).
    regress_fields = {}
    if args.regress:
        from singa_tpu import regress as regress_mod

        # h high enough that noisy benchmark steps never convict
        # mid-measurement (a conviction writes a bundle — not a cost
        # the steady-state number should include)
        det = regress_mod.RegressionDetector(
            warmup_samples=16, window=8, h=1e9).install()

        def fenced_rg_ms():
            t1 = time.perf_counter()
            _o, ls = m(tx, ty)
            np.asarray(jax.device_get(ls.data))
            return (time.perf_counter() - t1) * 1e3

        cc = observe.get_registry().get("singa_model_compile_total")
        rg_compiles_before = sum(
            v for _n, _k, v in cc.samples()) if cc else 0
        # idempotent toggles (add_span_listener is append-only; remove
        # drops every equal copy, so detach-then-attach never doubles)
        def rg_off():
            observe.remove_span_listener(det._on_span)

        def rg_on():
            observe.remove_span_listener(det._on_span)
            observe.add_span_listener(det._on_span)

        fenced_rg_ms()  # both arms warm
        fenced_rg_ms()
        offs, ons = [], []
        for i in range(2 * args.step_samples):
            if i % 2 == 0:
                rg_off()
                offs.append(fenced_rg_ms())
                rg_on()
                ons.append(fenced_rg_ms())
            else:
                rg_on()
                ons.append(fenced_rg_ms())
                rg_off()
                offs.append(fenced_rg_ms())
        rg_on()
        rg_base_ms = float(np.median(np.asarray(offs)))
        # direct feed cost at steady state: freeze the baseline on
        # constant samples (z stays 0, no convictions), then time
        # batches of listener calls — each 8th closes a real window
        base_s = rg_base_ms / 1e3
        for _ in range(16):
            det._on_span("model.step", base_s, {})
        batch_n, batches = 200, []
        for _ in range(15):
            t1 = time.perf_counter()
            for _ in range(batch_n):
                det._on_span("model.step", base_s, {})
            batches.append((time.perf_counter() - t1) / batch_n)
        feed_us = float(np.median(np.asarray(batches))) * 1e6
        deltas = np.asarray(ons) - np.asarray(offs)
        rg_overhead_pct = 100.0 * (feed_us / 1e3) / rg_base_ms
        cc = observe.get_registry().get("singa_model_compile_total")
        rg_compiles_after = sum(
            v for _n, _k, v in cc.samples()) if cc else 0
        rg_state = det.signal_state("model.step") or {}
        regress_fields = {
            "regress_us_per_step": round(feed_us, 3),
            "regress_ms_per_step": round(rg_base_ms + feed_us / 1e3,
                                         3),
            "regress_overhead_pct": round(rg_overhead_pct, 4),
            "regress_ab_delta_pct": round(
                100.0 * float(np.median(deltas)) / rg_base_ms, 2),
            "regress_compile_delta": int(rg_compiles_after
                                         - rg_compiles_before),
            "regress_windows": int(rg_state.get("windows") or 0),
            "regress_ok": bool(
                rg_overhead_pct <= 1.0
                and rg_compiles_after == rg_compiles_before),
        }
        regress_mod.uninstall()

    # ---- overlap layer A/B (--overlap / --ckpt-async) --------------------
    # the record's goodput_* fields must describe the REAL benchmarked
    # run: snapshot before the A/B arms feed the same tracker synthetic
    # sleep-injected stalls and extra checkpoint saves
    goodput_snap = None
    if goodput_tracker is not None and (args.overlap or args.ckpt_async
                                        or args.resume):
        goodput_snap = goodput_tracker.snapshot(final=True)
    overlap_fields = {}
    if args.overlap:
        from singa_tpu import goodput as goodput_mod
        tracker = goodput_mod.install()  # idempotent with --goodput
        # dispatch-path cost: un-fenced call wall time — on an async
        # backend the device runs behind, so this is the host-side
        # dispatch the fast path trims; fenced medians are above
        for _ in range(3):
            m(tx, ty)
        samp = []
        for _ in range(max(10, args.step_samples)):
            t1 = time.perf_counter()
            out, loss = m(tx, ty)
            samp.append(time.perf_counter() - t1)
        np.asarray(jax.device_get(loss.data))  # fence before the A/B
        pipelined_now = elapsed / args.iters
        sleep_s = min(max(pipelined_now / 3.0, 0.002), 0.05)
        n_ab = 12

        class _SlowSrc:  # the injected host-side stall per batch
            def __iter__(self):
                for _ in range(n_ab):
                    time.sleep(sleep_s)
                    yield (tx, ty)

        def _fit_arm(prefetch):
            s0 = tracker.snapshot()["buckets"]
            t1 = time.perf_counter()
            m.fit(_SlowSrc(), epochs=1, prefetch_to_device=prefetch)
            wall = time.perf_counter() - t1
            s1 = tracker.snapshot()["buckets"]
            return wall, {k: s1[k] - s0[k] for k in s1}

        wall_off, bk_off = _fit_arm(0)
        wall_on, bk_on = _fit_arm(2)
        overlap_fields = {
            "dispatch_us_per_step":
                round(float(np.median(np.asarray(samp))) * 1e6, 2),
            "overlap_sleep_s": round(sleep_s, 4),
            "overlap_batches": n_ab,
            "overlap_wall_off_s": round(wall_off, 4),
            "overlap_wall_on_s": round(wall_on, 4),
            "overlap_speedup": round(wall_off / wall_on, 4)
            if wall_on > 0 else None,
            "overlap_data_wait_off_s": round(bk_off["data_wait"], 4),
            "overlap_data_wait_on_s": round(bk_on["data_wait"], 4),
            "overlap_step_off_s": round(bk_off["step"], 4),
            "overlap_step_on_s": round(bk_on["step"], 4),
        }
    if args.ckpt_async:
        import shutil
        import tempfile

        from singa_tpu import overlap as overlap_mod
        ckdir = tempfile.mkdtemp(prefix="bench_ckpt_")
        try:
            m.save_checkpoint(ckdir, step=0)  # warm orbax's pools
            overlap_mod.wait_for_checkpoints()
            t1 = time.perf_counter()
            m.save_checkpoint(ckdir, step=1)
            blocking_s = time.perf_counter() - t1
            overlap_mod.wait_for_checkpoints()
            total_s = time.perf_counter() - t1
            overlap_fields["ckpt_blocking_s"] = round(blocking_s, 4)
            overlap_fields["ckpt_total_s"] = round(total_s, 4)
            t1 = time.perf_counter()
            m.save_checkpoint(ckdir, step=2, async_save=False)
            overlap_fields["ckpt_sync_s"] = round(
                time.perf_counter() - t1, 4)
        finally:
            shutil.rmtree(ckdir, ignore_errors=True)

    # ---- resilience cold-vs-resumed A/B (--resume) -----------------------
    if args.resume:
        import shutil
        import tempfile

        from singa_tpu import goodput as goodput_mod
        from singa_tpu import resilience as res_mod
        tracker = goodput_mod.install()  # idempotent with --goodput
        ckdir = tempfile.mkdtemp(prefix="bench_resume_")
        n_steps, save_every, kill_at = 8, 3, 7
        data = [(tx, ty)] * n_steps
        try:
            def _arm_model():
                mm = model_factory()
                mm.set_optimizer(opt.SGD(lr=0.1, momentum=0.9,
                                         weight_decay=1e-5))
                mm.compile([tx], is_train=True, use_graph=True,
                           amp="bfloat16" if args.amp else None)
                return mm

            # cold arm: fresh start under the controller, killed at
            # step `kill_at` by an injected fault — it leaves durable
            # checkpoints behind (manifest of step 3 flushed by save 6)
            res_mod.install_fault_plan(
                res_mod.FaultPlan().fail("step", step=kill_at))
            # build/compile OUTSIDE the timed region, like the warm arm
            cold_model = _arm_model()
            b0 = tracker.snapshot()["buckets"]
            t1 = time.perf_counter()
            killed = False
            try:
                res_mod.TrainController(
                    cold_model, ckdir, save_every_steps=save_every,
                    max_restarts=0, handle_signals=False).fit(data)
            except RuntimeError as e:
                # ONLY the injected kill is expected; a genuine failure
                # must not be recorded as a valid cold arm
                if "injected fault" not in str(e):
                    raise
                killed = True
            if not killed:
                raise RuntimeError(
                    f"--resume cold arm completed; the injected kill at "
                    f"step {kill_at} never fired")
            cold_wall = time.perf_counter() - t1
            res_mod.clear_fault_plan()
            from singa_tpu import overlap as overlap_mod
            overlap_mod.wait_for_checkpoints()
            b1 = tracker.snapshot()["buckets"]

            # resumed arm: fresh model, same dir — restore + replay +
            # finish the remaining steps
            ctrl = res_mod.TrainController(
                _arm_model(), ckdir, save_every_steps=save_every,
                handle_signals=False)
            t1 = time.perf_counter()
            rep = ctrl.fit(data)
            warm_wall = time.perf_counter() - t1
            b2 = tracker.snapshot()["buckets"]
            overlap_fields.update({
                "resume_steps": n_steps,
                "resume_killed_at_step": kill_at,
                # batches the resumed arm consumed without training to
                # reach its checkpoint — which is also the step it
                # resumed from (single-epoch arm), so record it once
                "resume_steps_replayed": rep["resumed_step"],
                "resume_restore_s": rep["resume_restore_s"],
                "resume_cold_wall_s": round(cold_wall, 4),
                "resume_warm_wall_s": round(warm_wall, 4),
                "resume_ckpt_cold_s": round(
                    b1["checkpoint"] - b0["checkpoint"], 4),
                "resume_ckpt_warm_s": round(
                    b2["checkpoint"] - b1["checkpoint"], 4),
                "resume_step_warm_s": round(b2["step"] - b1["step"], 4),
            })
        finally:
            res_mod.clear_fault_plan()
            shutil.rmtree(ckdir, ignore_errors=True)

    # ---- self-validation against physics ---------------------------------
    ca = m.step_cost_analysis()
    flops_per_step = float(ca.get("flops", 0.0)) if ca else 0.0
    bytes_per_step = float(ca.get("bytes accessed", 0.0)) if ca else 0.0
    # XLA's cost analysis credits custom-calls ZERO flops, so the Pallas
    # flash-attention kernels vanish from the gpt model's count. Add the
    # analytic causal-attention work (fwd 2 matmuls + bwd ~2.5x fwd,
    # halved for causal masking) so MFU reflects the executed math; the
    # uncorrected figure is kept as mfu_xla_counted.
    attn_flops = 0.0
    if args.model == "gpt" and flops_per_step:
        per_layer_fwd = 0.5 * 4 * args.batch * seq * seq * args.gpt_dim
        attn_flops = args.gpt_layers * per_layer_fwd * 3.5
    kind = getattr(dev.jax_device, "device_kind", "")
    peak = _chip_peak_tflops(kind)
    peak_bw = _chip_peak(kind, _PEAK_HBM_GBS)
    # achieved rate from the amortized pipelined loop (the fenced per-call
    # numbers include the transfer round-trip, so they underestimate MFU)
    pipelined_s_per_step = elapsed / args.iters
    model_tflops = ((flops_per_step + attn_flops) / pipelined_s_per_step
                    / 1e12 if flops_per_step else None)
    mfu = model_tflops / peak if (model_tflops and peak) else None
    mfu_xla = (flops_per_step / pipelined_s_per_step / 1e12 / peak
               if (flops_per_step and peak) else None)
    suspect = bool(mfu and mfu > 1.0)

    # Roofline readout: which wall does this step lean on?  The bytes floor
    # uses XLA's "bytes accessed" (an over-count of true HBM traffic — fused
    # intermediates never reach HBM), so an effective BW above the chip's
    # peak means fusion eliminated that much traffic, not broken physics.
    compute_floor_ms = (flops_per_step / (peak * 1e12) * 1e3
                        if (flops_per_step and peak) else None)
    hbm_floor_ms = (bytes_per_step / (peak_bw * 1e9) * 1e3
                    if (bytes_per_step and peak_bw) else None)
    bound = None
    if compute_floor_ms and hbm_floor_ms:
        bound = "memory" if hbm_floor_ms > compute_floor_ms else "compute"
    effective_bw_gbs = (bytes_per_step / pipelined_s_per_step / 1e9
                        if bytes_per_step else None)
    # "bytes accessed" over-counts true HBM traffic (fused intermediates
    # never leave VMEM); when the implied BW exceeds the chip's physical
    # peak, say so IN THE ARTIFACT rather than leaving a reader to trend
    # an impossible number (the measured raw-bytes roofline lives in the
    # --trace tables / PROFILE.md).
    bytes_metric = None
    if effective_bw_gbs and peak_bw and effective_bw_gbs > peak_bw:
        bytes_metric = "xla_overcount"

    # Headline: pipelined if physically plausible, else the fenced number.
    value = throughput_stepwise if suspect else throughput_pipelined

    # Baseline: the reference publishes no absolute numbers (BASELINE.md);
    # use any number recorded in BASELINE.json "published". With no
    # published number, 0.0 + note — never report fake parity.
    vs = 0.0
    vs_northstar = None
    vs_a100 = None
    baseline_used = None
    note = "no published reference baseline for this metric " \
           "(BASELINE.md); vs_baseline not computable"
    try:
        import os
        here = os.path.dirname(os.path.abspath(__file__))
        with open(os.path.join(here, "BASELINE.json")) as f:
            pub = json.load(f).get("published", {})
        # AMP runs compare against the CudaGPU AMP figure, fp32 runs
        # against the fp32 figure (derivation: BASELINE.md).
        key = f"{args.model}_img_per_sec" + ("" if args.amp else "_fp32")
        base = pub.get(key)
        if base:
            vs = value / float(base)
            vs_northstar = vs / 1.2   # >=1.0 => north-star (1.2x) met
            baseline_used = f"{key}={base} (V100, BASELINE.md)"
            note = None
        a100 = pub.get(f"{args.model}_img_per_sec_a100_amp")
        if a100 and args.amp:
            vs_a100 = value / float(a100)
    except Exception:
        pass
    rec = {
        "metric": f"{args.model}_train_throughput_b{args.batch}_s{args.size}"
                  f"_{args.dtype}" + ("_amp_bf16" if args.amp else ""),
        "value": round(value, 2),
        "unit": unit,
        "vs_baseline": round(vs, 3),
        "vs_northstar_1_2x": round(vs_northstar, 3)
        if vs_northstar is not None else None,
        "vs_a100_amp": round(vs_a100, 3) if vs_a100 is not None else None,
        "baseline_used": baseline_used,
        "throughput_pipelined": round(throughput_pipelined, 2),
        "throughput_stepwise_fenced": round(throughput_stepwise, 2),
        "roundtrip_ms_median": round(med_ms, 3),
        "roundtrip_ms_p10": round(float(np.percentile(step_ms_arr, 10)), 3),
        "roundtrip_ms_p90": round(float(np.percentile(step_ms_arr, 90)), 3),
        "pipelined_ms_per_step": round(pipelined_s_per_step * 1e3, 3),
        "flops_per_step": flops_per_step,
        "bytes_per_step": bytes_per_step,
        "device_kind": kind or "unknown",
        "peak_tflops_bf16": peak,
        "peak_hbm_gbs": peak_bw,
        "model_tflops": round(model_tflops, 3) if model_tflops else None,
        "mfu_vs_peak": round(mfu, 4) if mfu else None,
        "attn_flops_per_step": attn_flops or None,
        "mfu_xla_counted": round(mfu_xla, 4)
        if (mfu_xla is not None and attn_flops) else None,
        "mfu_suspect": suspect,
        "health_ms_per_step": round(health_ms_per_step, 3)
        if health_ms_per_step is not None else None,
        "health_overhead_pct": round(health_overhead_pct, 2)
        if health_overhead_pct is not None else None,
        "compute_floor_ms": round(compute_floor_ms, 3)
        if compute_floor_ms else None,
        "hbm_floor_ms": round(hbm_floor_ms, 3) if hbm_floor_ms else None,
        "roofline_bound": bound,
        "effective_bw_gbs": round(effective_bw_gbs, 1)
        if effective_bw_gbs else None,
        "bytes_metric": bytes_metric,
        "final_loss": final_loss,
    }
    if note:
        rec["note"] = note
    if goodput_tracker is not None:
        # one FINAL snapshot: commits the held last step + flushes the
        # unattributed residual, so the bucket fields (and the counters
        # --metrics-out exports below) sum to the run's wall clock
        # (each lands in singa_bench_goodput_* via record_bench); a
        # pre-A/B snapshot taken above wins, so --overlap/--ckpt-async
        # arms can't skew the headline ratio
        snap = goodput_snap if goodput_snap is not None \
            else goodput_tracker.snapshot(final=True)
        rec["goodput_ratio"] = round(snap["goodput_ratio"], 4)
        rec["goodput_window_ratio"] = round(
            snap["window_goodput_ratio"], 4)
        rec["goodput_wall_s"] = round(snap["wall_s"], 3)
        for bucket_name, seconds in snap["buckets"].items():
            rec[f"goodput_{bucket_name}_s"] = round(seconds, 4)
    if mem_fields:
        rec.update(mem_fields)  # mirrored into singa_bench_* below
    if watchdog_fields:
        rec.update(watchdog_fields)  # mirrored into singa_bench_* below
    if regress_fields:
        rec.update(regress_fields)  # mirrored into singa_bench_* below
    if overlap_fields:
        rec.update(overlap_fields)  # mirrored into singa_bench_* below
    if args.compile_cache:
        from singa_tpu import warmstart
        ws = warmstart.snapshot()
        rec["compile_cache"] = {
            "root": ws["root"], "lookups": ws["lookups"],
            "hit_rate": ws["hit_rate"], "exports": ws["exports"],
            "entries": ws.get("entries"),
            "store_bytes": ws.get("store_bytes")}
        if ws["hit_rate"] is not None:
            rec["compile_cache_hit_rate"] = round(ws["hit_rate"], 4)
    if args.explain:
        # the timed step compiled through the AOT stages (model.py); use
        # the build record snapshotted before the --health arm rather
        # than re-lowering anything
        b = explain_build or {}
        ph = b.get("phases") or {}
        mem = b.get("memory") or {}
        rec.update({
            "mfu_pct": round(mfu * 100.0, 2) if mfu else None,
            "compile_trace_s": round(ph["trace"], 4)
            if "trace" in ph else None,
            "compile_lower_s": round(ph["lower"], 4)
            if "lower" in ph else None,
            "compile_backend_s": round(ph["compile"], 4)
            if "compile" in ph else None,
            "hbm_temps_bytes": mem.get("temps"),
        })
    # one schema: the BENCH_*.json record also lands in the registry
    # (singa_bench_* gauges) and the EventLog, next to the per-step
    # telemetry the run itself produced
    observe.record_bench(rec)
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(observe.to_prometheus_text())
    if fleet_writer is not None:
        from singa_tpu import fleet
        # final publish carries the bench record's singa_bench_* gauges
        fleet.stop_shard_writer()
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
