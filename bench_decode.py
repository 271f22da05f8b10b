"""Serving benchmark: KV-cached autoregressive decode, self-validating.

The reference's serving path re-runs the whole ONNX graph per token
(reference python/singa/sonnx.py:1951, examples/onnx/gpt2/gpt2.py); its
throughput is not the bar — the chip's weight-streaming roofline is.
Each decode step must re-read every weight plus the KV cache, so the
floor is

    step_time >= (weight_bytes + kv_bytes_read) / HBM_peak

This script measures tok/s for a GPT config, computes that roofline from
the actual parameter/cache byte counts, and reports achieved-vs-roofline
so the serving number can be *believed* (same philosophy as bench.py).
`--trace DIR` captures an xplane trace of the timed decode and prints
per-op and per-HLO-category tables (singa_tpu.xprof) to stderr.

Prints ONE JSON line:
  {"metric": "gpt_decode_tok_s_...", "value": N, "unit": "tokens/s", ...}
"""

import argparse
import json
import sys
import time


def _chip_peak_bw(kind: str):
    from bench import _PEAK_HBM_GBS, _chip_peak
    return _chip_peak(kind, _PEAK_HBM_GBS)


def _kv_suffix(kv_dtype):
    """Metric-name suffix for the KV storage mode — ONE spelling for
    every bench family so a new mode can't fork the trend history."""
    return {"int8": "_kv8", "int4": "_kv4"}.get(kv_dtype, "")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--dim", type=int, default=1024)
    p.add_argument("--layers", type=int, default=8)
    p.add_argument("--heads", type=int, default=16)
    p.add_argument("--kv-heads", type=int, default=None,
                   help="GQA: kv heads < heads shrinks the KV cache — "
                        "the binding term of the decode roofline")
    p.add_argument("--rope", action="store_true",
                   help="rotary position embeddings instead of the "
                        "learned table")
    p.add_argument("--vocab", type=int, default=8192)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--prompt", type=int, default=128)
    p.add_argument("--new", type=int, default=512)
    p.add_argument("--dtype", default="bfloat16",
                   choices=["float32", "bfloat16", "int8"])
    p.add_argument("--kv-dtype", default=None,
                   choices=[None, "int8", "int4"],
                   help="quantized KV cache (per-head-per-position "
                        "scales): int8, or packed-nibble int4 (two "
                        "values per byte — half the int8 stream again)")
    p.add_argument("--reps", type=int, default=3,
                   help="timed full-decode calls (median reported)")
    p.add_argument("--trace", default=None, metavar="DIR")
    p.add_argument("--explain", action="store_true",
                   help="add AOT introspection fields (singa_tpu."
                        "introspect) for the prefill/decode executables: "
                        "compile-phase times, HBM temp bytes, and the "
                        "recompile-blame history of this run")
    p.add_argument("--spec", action="store_true",
                   help="speculative-decoding A/B: train the target "
                        "AND a small draft GPT on a seeded structured "
                        "workload (so the draft genuinely predicts the "
                        "target — acceptance is measured, not "
                        "assumed), then time greedy decode spec-off vs "
                        "spec-on at bit-identical outputs; records "
                        "wall tokens/s, acceptance rate, and drafted/"
                        "accepted/wasted token counts")
    p.add_argument("--spec-k", type=int, default=3,
                   help="draft tokens proposed per verify round")
    p.add_argument("--spec-draft-layers", type=int, default=1,
                   help="draft model depth")
    p.add_argument("--spec-draft-dim", type=int, default=None,
                   help="draft model width (default: target dim // 4)")
    p.add_argument("--spec-train-steps", type=int, default=30,
                   help="quick training steps for the TARGET on the "
                        "seeded cyclic workload (what makes the draft "
                        "agree)")
    p.add_argument("--spec-draft-train-steps", type=int, default=None,
                   help="training steps for the draft (default 4x the "
                        "target's — the draft is tiny, its steps are "
                        "cheap, and acceptance is the whole game)")
    p.add_argument("--spec-seed", type=int, default=0,
                   help="workload RNG seed (training data + prompts)")
    p.add_argument("--spec-out", default=None, metavar="FILE",
                   help="append the spec records as JSON lines "
                        "(BENCHDEC_rNN.json style)")
    p.add_argument("--serve", action="store_true",
                   help="serving A/B: a seeded Poisson request workload "
                        "with heterogeneous prompt/output lengths "
                        "against the continuous-batching engine "
                        "(singa_tpu.engine, paged KV cache) vs the "
                        "static-batch baseline at EQUAL KV-cache HBM "
                        "budget; reports sustained tokens/s and "
                        "p50/p99 TTFT for both arms")
    p.add_argument("--serve-requests", type=int, default=24,
                   help="requests in the Poisson workload (per arm)")
    p.add_argument("--serve-rps", type=float, default=None,
                   help="mean arrival rate (default: sized so arrivals "
                        "finish in ~2s wall)")
    p.add_argument("--serve-seed", type=int, default=0,
                   help="workload RNG seed (arrivals + lengths)")
    p.add_argument("--serve-prompt-lens", default="8,48", metavar="LO,HI",
                   help="uniform prompt-length range")
    p.add_argument("--serve-new-lens", default="4,64", metavar="LO,HI",
                   help="output-length range")
    p.add_argument("--serve-new-dist", default="bimodal",
                   choices=["uniform", "bimodal"],
                   help="output-length distribution: uniform over "
                        "[LO,HI], or bimodal (75%% short requests near "
                        "LO, 25%% long near HI — the heavy-tailed shape "
                        "production traffic has, and the one a static "
                        "max-length batch pays for hardest)")
    p.add_argument("--serve-slots", type=int, default=None,
                   help="engine decode slots (default 2x --batch)")
    p.add_argument("--serve-page-size", type=int, default=8,
                   help="KV-cache page size (tokens)")
    p.add_argument("--serve-steps-per-sync", type=int, default=4,
                   help="decode steps between admission/eviction syncs")
    p.add_argument("--serve-out", default=None, metavar="FILE",
                   help="append the serve records as JSON lines "
                        "(BENCHDEC_rNN.json style)")
    p.add_argument("--serve-slo-ttft-p99", type=float, default=1.0,
                   help="declared p99 TTFT target (seconds) both arms "
                        "are scored against (singa_tpu.slo)")
    p.add_argument("--serve-slo-latency-p99", type=float, default=30.0,
                   help="declared p99 request-latency target (seconds)")
    p.add_argument("--serve-slo-availability", type=float, default=0.99,
                   help="declared availability target (non-timeout/"
                        "evicted fraction)")
    p.add_argument("--serve-slo-tok-s", type=float, default=0.0,
                   help="per-request tokens/sec floor (0 disables the "
                        "objective)")
    p.add_argument("--compile-cache", default=None, metavar="DIR",
                   help="enable the warm store (singa_tpu.warmstart) "
                        "rooted at DIR: the decode/prefill/spec "
                        "executables persist there and a rerun loads "
                        "them instead of compiling")
    args = p.parse_args()

    from bench import require_tpu, use_compile_cache
    args.dev = require_tpu("bench_decode.py")
    # before any staged build, so every mode's executables persist
    use_compile_cache(args.compile_cache)

    if args.spec:
        return spec_main(args)
    if args.serve:
        return serve_main(args)

    import numpy as np
    import jax
    from singa_tpu import models, tensor

    dev = args.dev
    T = args.prompt + args.new
    m = models.create_model(
        "gpt", vocab_size=args.vocab, max_seq=T, dim=args.dim,
        num_heads=args.heads, num_layers=args.layers,
        num_kv_heads=args.kv_heads,
        pos_encoding="rope" if args.rope else "learned")
    rng = np.random.RandomState(0)
    ids = tensor.from_numpy(
        rng.randint(0, args.vocab, (args.batch, args.prompt))
        .astype(np.int32), device=dev)
    m.compile([ids], is_train=False, use_graph=False)
    m.eval()
    prompt = rng.randint(0, args.vocab, (args.batch, args.prompt))

    dt = None if args.dtype == "float32" else args.dtype
    # warmup = compile
    m.generate(prompt, args.new, temperature=0.0, dtype=dt,
               kv_dtype=args.kv_dtype)
    # prefill-only executable (prompt -> 1 token): timed separately so
    # long-prompt serving reports prefill latency, not just decode tok/s
    # (prefill runs the flash kernel, O(S0) memory)
    m.generate(prompt, 1, temperature=0.0, dtype=dt,
               kv_dtype=args.kv_dtype)

    # per-call overhead (jit dispatch + host<->device roundtrip)
    import jax.numpy as jnp
    triv = jax.jit(lambda x: x + 1)
    z = jax.block_until_ready(triv(jnp.zeros(8)))
    ohs = []
    for _ in range(5):
        t0 = time.perf_counter()
        np.asarray(jax.device_get(triv(z)))
        ohs.append(time.perf_counter() - t0)
    call_overhead = float(np.median(ohs))

    if args.trace:
        dev.StartTrace(args.trace)
    times = []
    for _ in range(args.reps):
        t0 = time.perf_counter()
        out = m.generate(prompt, args.new, temperature=0.0, dtype=dt,
                         kv_dtype=args.kv_dtype)
        times.append(time.perf_counter() - t0)
    if args.trace:
        dev.StopTrace()
    med = float(np.median(times))
    tok_s = args.batch * args.new / med
    steps_s = args.new / med

    # prefill latency: the (prompt -> 1 token) executable IS prefill +
    # one sample (max_new=1 runs no cached decode step), so only the
    # per-call overhead is stripped
    pf_times = []
    for _ in range(args.reps):
        t0 = time.perf_counter()
        m.generate(prompt, 1, temperature=0.0, dtype=dt,
                   kv_dtype=args.kv_dtype)
        pf_times.append(time.perf_counter() - t0)
    prefill_s = max(float(np.median(pf_times)) - call_overhead, 0.0)

    # ---- weight-streaming roofline --------------------------------------
    # bytes every decode step must move: all params once (embedding gather
    # reads only B rows — exclude the table, count head + pos + blocks)
    # plus the K and V caches of every layer (the masked attention reads
    # the full preallocated T rows regardless of position).
    E, H, L, V = args.dim, args.heads, args.layers, args.vocab
    Hkv = args.kv_heads or H
    bpe = {"float32": 4, "bfloat16": 2, "int8": 1}[args.dtype]
    D = E // H
    # per block: Wq+Wo (2 E^2) + Wk,Wv (2 E*Hkv*D) + W1,W2 (8 E^2)
    block_params = 10 * E * E + 2 * E * Hkv * D
    head_params = E * V
    weight_bytes = (L * block_params + head_params) * bpe
    # KV cache follows the ACTIVATION dtype: bf16 under both "bfloat16"
    # and "int8" (weight-only quantization), fp32 under "float32";
    # GQA holds Hkv heads, not H
    kv_bpe = {"int8": 1.0, "int4": 0.5}.get(
        args.kv_dtype, 4.0 if args.dtype == "float32" else 2.0)
    kv_bytes = int(L * 2 * args.batch * Hkv * T * D * kv_bpe)  # K+V
    if args.kv_dtype in ("int8", "int4"):
        # per-(head, position) fp32 scales travel with the cache
        kv_bytes += L * 2 * args.batch * Hkv * T * 4
    per_step_bytes = weight_bytes + kv_bytes
    kind = getattr(dev.jax_device, "device_kind", "")
    peak_bw = _chip_peak_bw(kind)
    floor_ms = per_step_bytes / (peak_bw * 1e9) * 1e3 if peak_bw else None
    step_ms = 1e3 / steps_s
    vs_roofline = (floor_ms / step_ms) if floor_ms else None
    if floor_ms:
        # register the implied decode ceiling (batch tokens per floor-
        # bound step) so the capacity model's bandwidth wall holds the
        # serving engine's measured decode tok/s against this chip's
        # roofline instead of guessing
        from singa_tpu import capacity
        capacity.note_decode_floor(args.batch / (floor_ms / 1e3))

    if args.trace:
        from singa_tpu import xprof
        n_steps = args.reps * args.new
        print(f"# per-op device time over {args.reps} decodes x {args.new} "
              f"tokens ({args.trace}):", file=sys.stderr)
        print(xprof.format_table(xprof.op_table(args.trace), top=30),
              file=sys.stderr)
        print("# by XLA hlo_category (per decoded token, prefill "
              "amortized in):", file=sys.stderr)
        print(xprof.format_hlo_categories(
            xprof.hlo_category_table(args.trace, steps=n_steps)),
            file=sys.stderr)

    nparams = (L * block_params + head_params + V * E + T * E)
    rec = {
        "metric": f"gpt_decode_tok_s_d{args.dim}_l{args.layers}"
                  f"_v{args.vocab}"
                  f"_b{args.batch}_p{args.prompt}_n{args.new}_{args.dtype}"
                  + (f"_gqa{Hkv}" if Hkv != H else "")
                  + ("_rope" if args.rope else "")
                  + _kv_suffix(args.kv_dtype),
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "steps_per_s": round(steps_s, 1),
        "step_ms": round(step_ms, 4),
        "params_m": round(nparams / 1e6, 1),
        "weight_mb_per_step": round(weight_bytes / 1e6, 1),
        "kv_mb_per_step": round(kv_bytes / 1e6, 1),
        "roofline_floor_ms": round(floor_ms, 4) if floor_ms else None,
        "frac_of_roofline": round(vs_roofline, 3) if vs_roofline else None,
        "call_overhead_ms": round(call_overhead * 1e3, 1),
        # wall minus the per-call dispatch/roundtrip overhead: the rate the
        # decode loop itself sustains
        "tok_s_ex_overhead": round(
            args.batch * args.new / max(med - call_overhead, 1e-9), 1),
        "step_ms_ex_overhead": round(
            max(med - call_overhead, 1e-9) / args.new * 1e3, 4),
        "device_kind": kind or "unknown",
        "peak_hbm_gbs": peak_bw,
        "decode_total_s": round(med, 3),
        # flash-kernel prefill over the S0-token prompt, ex call overhead
        # (the decode phase's tok/s above includes prefill amortized in;
        # at long prompts read both numbers). None when the overhead
        # subtraction clamped to ~0 (host jitter exceeded the prefill
        # itself) — an absurd rate must never enter a committed artifact.
        "prefill_ms": round(prefill_s * 1e3, 2)
        if prefill_s > 1e-3 else None,
        "prefill_tok_s": round(args.batch * args.prompt / prefill_s, 1)
        if prefill_s > 1e-3 else None,
        # decode rate with BOTH the call overhead and the prefill phase
        # removed: the steady-state cached-step rate at long prompts.
        # None when the residual is below measurement noise (a few ms
        # of host jitter) — an absurd clamped rate must never enter a
        # committed artifact.
        "tok_s_ex_prefill": (
            round(args.batch * args.new
                  / (med - call_overhead - prefill_s), 1)
            if med - call_overhead - prefill_s > 5e-3 else None),
        "out_shape": list(out.shape),
    }
    if args.explain:
        from singa_tpu import introspect
        for key, prefix in (("serving.prefill", "prefill"),
                            ("serving.decode_scan", "decode")):
            b = introspect.last_build(key) or {}
            ph = b.get("phases") or {}
            mem = b.get("memory") or {}
            rec[f"{prefix}_compile_trace_s"] = \
                round(ph["trace"], 4) if "trace" in ph else None
            rec[f"{prefix}_compile_lower_s"] = \
                round(ph["lower"], 4) if "lower" in ph else None
            rec[f"{prefix}_compile_backend_s"] = \
                round(ph["compile"], 4) if "compile" in ph else None
            rec[f"{prefix}_hbm_temps_bytes"] = mem.get("temps")
        rec["recompiles"] = [
            {"key": b["key"], "reason": b["reason"], "detail": b["detail"]}
            for b in introspect.blame_history()]
    print(json.dumps(rec))
    return 0


def _pct(xs, p):
    from singa_tpu.engine import pctile
    return pctile(xs, p)


def _slo_config(args):
    from singa_tpu import slo
    return slo.SLOConfig(
        ttft_p99_s=args.serve_slo_ttft_p99,
        latency_p99_s=args.serve_slo_latency_p99,
        availability=args.serve_slo_availability,
        min_tokens_per_sec=args.serve_slo_tok_s
        if args.serve_slo_tok_s > 0 else None,
        # windows sized to cover the whole arm: the bench scores the
        # run, not a trailing slice of it
        window_s=3600.0, fast_window_s=60.0, slow_window_s=3600.0)


def _slo_fields(att_map, cfg):
    """Per-arm SLO fields from an attainment map ({objective:
    {"attainment", ...}}): per-objective attainment percent + whole-run
    burn rate, and the worst-objective `slo_attainment_pct` headline
    the standalone trend record carries."""
    from singa_tpu import slo
    fields = {}
    worst = None
    for obj, a in att_map.items():
        at = a.get("attainment")
        if at is None:
            continue
        pct = round(100.0 * at, 2)
        fields[f"slo_{obj}_pct"] = pct
        worst = pct if worst is None else min(worst, pct)
        burn = slo.burn_rate(at, cfg.target_fraction(obj))
        fields[f"slo_{obj}_burn"] = round(burn, 3) \
            if burn is not None else None
    fields["slo_attainment_pct"] = worst
    return fields


def spec_main(args):
    """The --spec A/B: one seeded structured workload, greedy decode
    with and without draft-model speculation, at BIT-IDENTICAL outputs.

    Speculative decoding's win is workload-dependent — it buys tokens
    only when the draft predicts the target — so the bench constructs a
    workload where draft quality is real and measurable instead of
    relying on random weights (where any small draft's acceptance is
    ~0): both models take `--spec-train-steps` quick training steps on
    a seeded cyclic-successor stream (x[t+1] = (x[t]+1) % V), the kind
    of low-entropy structure a small draft genuinely learns. The
    recorded acceptance rate is MEASURED over the timed decodes — the
    speedup claim and its cause land in the same record. Outputs are
    asserted token-identical between arms (the spec algorithm's
    greedy-equivalence guarantee, checked here on the bench config
    too, not just in tier-1)."""
    import numpy as np

    from singa_tpu import models, observe, opt as sopt, tensor

    dev = args.dev
    V = args.vocab
    T = args.prompt + args.new + 1
    ddim = args.spec_draft_dim or max(32, args.dim // 4)
    dheads = max(1, args.heads // 4)
    K = args.spec_k

    def build(dim, layers, heads):
        return models.create_model(
            "gpt", vocab_size=V, max_seq=T, dim=dim, num_heads=heads,
            num_layers=layers, num_kv_heads=args.kv_heads
            if dim == args.dim else None,
            pos_encoding="rope" if args.rope else "learned")

    rng = np.random.RandomState(args.spec_seed)

    def cyc_batch(b, s):
        starts = rng.randint(0, V, (b, 1))
        ids = (starts + np.arange(s)[None, :]) % V
        return ids.astype(np.int32)

    def train(m, steps, lr):
        ids0 = cyc_batch(8, min(48, T - 1))
        tx = tensor.from_numpy(ids0, device=dev)
        m.set_optimizer(sopt.SGD(lr=lr))
        m.compile([tx], is_train=True, use_graph=False)
        m.train()
        last = None
        for _ in range(steps):
            ids = cyc_batch(8, min(48, T - 1))
            x = tensor.from_numpy(ids, device=dev)
            y = tensor.from_numpy(((ids + 1) % V).astype(np.int32),
                                  device=dev)
            _o, loss = m.train_one_batch(x, y)
            last = float(np.asarray(
                loss.numpy() if hasattr(loss, "numpy") else loss))
        m.eval()
        return last

    m = build(args.dim, args.layers, args.heads)
    loss_t = train(m, args.spec_train_steps, 0.3)
    d = build(ddim, args.spec_draft_layers, dheads)
    dsteps = args.spec_draft_train_steps \
        if args.spec_draft_train_steps is not None \
        else 4 * args.spec_train_steps
    loss_d = train(d, dsteps, 1.0)

    dt = None if args.dtype == "float32" else args.dtype
    prompt = cyc_batch(args.batch, args.prompt)
    # warmup = compile (both arms, both (new) and (1) signatures)
    m.generate(prompt, args.new, temperature=0.0, dtype=dt,
               kv_dtype=args.kv_dtype)
    m.generate(prompt, 1, temperature=0.0, dtype=dt,
               kv_dtype=args.kv_dtype)
    m.generate(prompt, args.new, temperature=0.0, dtype=dt,
               kv_dtype=args.kv_dtype, draft_model=d, spec_k=K)
    m.generate(prompt, 1, temperature=0.0, dtype=dt,
               kv_dtype=args.kv_dtype, draft_model=d, spec_k=K)

    reg = observe.get_registry()

    def spec_counts():
        c = reg.get("singa_spec_tokens_total")
        if c is None:
            return {v: 0.0 for v in ("drafted", "accepted", "bonus")}
        return {v: c.value(verdict=v) or 0.0
                for v in ("drafted", "accepted", "bonus")}

    def timed(fn, reps):
        ts = []
        out = None
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn()
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts)), out

    off_s, off_out = timed(
        lambda: m.generate(prompt, args.new, temperature=0.0, dtype=dt,
                           kv_dtype=args.kv_dtype), args.reps)
    base_counts = spec_counts()
    spec_s, spec_out = timed(
        lambda: m.generate(prompt, args.new, temperature=0.0, dtype=dt,
                           kv_dtype=args.kv_dtype, draft_model=d,
                           spec_k=K), args.reps)
    # per-decode counts: the delta spans all `reps` timed decodes
    # (identical seeded runs), while value/wall_s describe ONE median
    # rep — divide so the record's token counts match its timing
    counts = {k: (spec_counts()[k] - base_counts[k]) / args.reps
              for k in base_counts}
    if not np.array_equal(off_out, spec_out):
        raise RuntimeError(
            "spec-on output diverged from plain greedy — the "
            "greedy-equivalence guarantee is broken; do not trust "
            "this record")
    off_ttft, _ = timed(
        lambda: m.generate(prompt, 1, temperature=0.0, dtype=dt,
                           kv_dtype=args.kv_dtype), args.reps)
    spec_ttft, _ = timed(
        lambda: m.generate(prompt, 1, temperature=0.0, dtype=dt,
                           kv_dtype=args.kv_dtype, draft_model=d,
                           spec_k=K), args.reps)

    tok = args.batch * args.new
    off_tok_s = tok / off_s
    spec_tok_s = tok / spec_s
    drafted = int(counts["drafted"])
    accepted = int(counts["accepted"])
    acceptance = accepted / drafted if drafted else None
    cfg = (f"d{args.dim}_l{args.layers}_v{V}_b{args.batch}"
           f"_p{args.prompt}_n{args.new}_k{K}_dd{ddim}"
           f"_dl{args.spec_draft_layers}"
           + _kv_suffix(args.kv_dtype))
    base = {
        "unit": "tokens/s", "batch": args.batch, "new": args.new,
        "reps": args.reps,
        "spec_k": K, "train_steps": args.spec_train_steps,
        "draft_train_steps": dsteps,
        "train_loss_target": round(loss_t, 4) if loss_t else None,
        "train_loss_draft": round(loss_d, 4) if loss_d else None,
        "matched_outputs": True,
        "device_kind": getattr(dev.jax_device, "device_kind", "")
        or "unknown",
    }
    recs = [
        {"metric": f"gpt_specdec_tok_s_{cfg}",
         "value": round(spec_tok_s, 1), **base,
         "wall_s": round(spec_s, 4),
         "drafted_tokens": drafted, "accepted_tokens": accepted,
         "wasted_tokens": drafted - accepted,
         "bonus_tokens": int(counts["bonus"]),
         "ttft_ms": round(spec_ttft * 1e3, 2)},
        {"metric": f"gpt_specdec_off_tok_s_{cfg}",
         "value": round(off_tok_s, 1), **base,
         "wall_s": round(off_s, 4),
         "ttft_ms": round(off_ttft * 1e3, 2)},
        {"metric": f"gpt_specdec_speedup_x_{cfg}",
         "value": round(spec_tok_s / off_tok_s, 3) if off_tok_s
         else None, "unit": "x", "spec_k": K},
    ]
    if acceptance is not None:
        recs.append(
            {"metric": f"gpt_specdec_acceptance_rate_pct_{cfg}",
             "value": round(100.0 * acceptance, 2), "unit": "pct",
             "spec_k": K, "drafted_tokens": drafted,
             "accepted_tokens": accepted})
    for arm, t in (("spec", spec_ttft), ("off", off_ttft)):
        recs.append({"metric": f"gpt_specdec_{arm}_ttft_s_{cfg}",
                     "value": round(t, 5), "unit": "s"})
    for rec in recs:
        observe.record_bench(rec)
        print(json.dumps(rec))
    if args.spec_out:
        with open(args.spec_out, "a", encoding="utf-8") as f:
            for rec in recs:
                f.write(json.dumps(rec) + "\n")
    return 0


def serve_main(args):
    """The --serve A/B: one seeded Poisson workload, two serving arms.

    Arm 1 (engine): the continuous-batching ServingEngine — per-request
    admission, paged KV cache sized to the SAME byte budget as the
    baseline's static cache (num_pages * page_size == batch * T rows),
    eviction at each request's own output length.

    Arm 2 (static): the serving.py status quo — requests queue until
    `--batch` of them form a batch (or the previous batch finished),
    prompts pad to the max prompt length, and EVERY sequence decodes the
    max output length; first tokens exist only when the whole batch
    returns, which is what the TTFT numbers show.

    tokens/s counts only USEFUL tokens (each request's own max_new) so
    the static arm is not credited for the padding it decodes."""
    import threading
    import numpy as np

    from singa_tpu import engine, models, observe, tensor

    dev = args.dev
    p_lo, p_hi = (int(x) for x in args.serve_prompt_lens.split(","))
    n_lo, n_hi = (int(x) for x in args.serve_new_lens.split(","))
    B = args.batch
    T = p_hi + n_hi
    ps = args.serve_page_size
    slots = args.serve_slots or 2 * B
    n_req = args.serve_requests
    rps = args.serve_rps or max(4.0, n_req / 2.0)

    m = models.create_model(
        "gpt", vocab_size=args.vocab, max_seq=T, dim=args.dim,
        num_heads=args.heads, num_layers=args.layers,
        num_kv_heads=args.kv_heads,
        pos_encoding="rope" if args.rope else "learned")
    rng0 = np.random.RandomState(0)
    ids = tensor.from_numpy(
        rng0.randint(0, args.vocab, (B, p_hi)).astype(np.int32),
        device=dev)
    m.compile([ids], is_train=False, use_graph=False)
    m.eval()
    dt = None if args.dtype == "float32" else args.dtype

    # ---- the workload (shared by both arms, fully seeded; the same
    # generator the router's kill-and-replace harness replays) ------------
    from singa_tpu import serving
    wl = serving.poisson_workload(
        args.serve_seed, n_req, rps, args.vocab, (p_lo, p_hi),
        (n_lo, n_hi), new_dist=args.serve_new_dist)
    arrivals, prompts, new_lens = \
        wl["arrivals"], wl["prompts"], wl["new_lens"]
    useful = int(np.sum(new_lens))

    def replay(submit_fn):
        """Submit each request at its arrival offset; returns per-request
        (arrive_ts, handle-ish)."""
        t0 = time.perf_counter()
        out = []
        for i in range(n_req):
            dt_s = t0 + arrivals[i] - time.perf_counter()
            if dt_s > 0:
                time.sleep(dt_s)
            out.append((time.perf_counter(), submit_fn(i)))
        return t0, out

    # ---- arm 1: the continuous-batching engine --------------------------
    num_pages = -(-B * T // ps)  # EQUAL HBM: pool rows == static rows
    eng = engine.ServingEngine(
        m, max_slots=slots, page_size=ps, num_pages=num_pages,
        max_ctx=T, dtype=dt, kv_dtype=args.kv_dtype,
        steps_per_sync=args.serve_steps_per_sync,
        queue_limit=max(128, 2 * n_req)).start()
    # warm every prompt bucket the workload will hit (+ the decode
    # executable), so the timed arm measures serving, not XLA
    for b in sorted({eng._bucket(len(pr)) for pr in prompts}):
        w = eng.submit(np.zeros(min(b, T - 2), np.int32) + 1, 2)
        if not w.wait(300):
            raise RuntimeError(f"engine warmup (bucket {b}) stalled "
                               "after 300s")
    # the SLO tracker scores the MEASURED workload only: installed
    # after warmup, so compile-time TTFTs don't burn the budget
    from singa_tpu import slo
    slo_cfg = _slo_config(args)
    # capacity covers the whole arm: the default 4096-record ring
    # would silently score only the tail of a bigger workload
    tracker = slo.SLOTracker(slo_cfg,
                             capacity=max(4096, 2 * n_req)).install()
    # tail attribution rides the same terminal-request stream: the
    # engine arm's record reports which LATENCY_ATTR bucket owned the
    # measured p99 (the /tailz view, folded into BENCHDEC)
    slo.install_tail()
    _t0, handles = replay(
        lambda i: eng.submit(prompts[i], int(new_lens[i])))
    stuck = [h.id for _, h in handles if not h.wait(600)]
    if stuck:
        # fail like the static arm does, not with a None-math crash or
        # a silently bogus record built from half-finished handles
        raise RuntimeError(
            f"engine arm stalled: requests {stuck} not terminal "
            "after 600s")
    # handle timestamps share one clock (time.monotonic): wall = first
    # submit -> last terminal
    eng_wall = max((h.finished_ts or 0) for _, h in handles) \
        - min(h.submitted for _, h in handles)
    eng_done = [h for _, h in handles if h.outcome == "completed"]
    eng_ttft = [h.ttft_s for _, h in handles if h.ttft_s is not None]
    eng_tok = sum(len(h.tokens) for h in eng_done)
    eng_report = eng.report()
    eng.stop()
    eng_verdict = tracker.evaluate()
    eng_slo = _slo_fields(eng_verdict["objectives"], slo_cfg)
    eng_slo["slo_breaching"] = eng_verdict["breaching"]
    eng_tail = slo.tail_summary()
    if eng_tail["requests"]:
        eng_slo["tail_top_bucket"] = eng_tail["top"]
        top = eng_tail["buckets"].get(eng_tail["top"]) or {}
        eng_slo["tail_top_p99_contrib_s"] = top.get("p99_s")
        eng_slo["tail_attributed_requests"] = eng_tail["requests"]
    slo.reset()

    # ---- arm 2: static batching over the same schedule ------------------
    # warmup = compile the one static signature
    wp = rng0.randint(0, args.vocab, (B, p_hi)).astype(np.int32)
    m.generate(wp, n_hi, temperature=0.0, dtype=dt,
               kv_dtype=args.kv_dtype)

    sq = []
    sdone = {}
    slock = threading.Lock()
    sstop = threading.Event()

    def static_worker():
        while True:
            with slock:
                batch = sq[:B]
                del sq[:len(batch)]
            if not batch:
                if sstop.is_set():
                    return
                time.sleep(0.002)
                continue
            mat = np.zeros((B, p_hi), np.int32)
            for j, (i, _ts) in enumerate(batch):
                mat[j, :len(prompts[i])] = prompts[i]
            m.generate(mat, n_hi, temperature=0.0, dtype=dt,
                       kv_dtype=args.kv_dtype)
            tdone = time.perf_counter()
            with slock:
                for i, _ts in batch:
                    sdone[i] = tdone

    wt = threading.Thread(target=static_worker, daemon=True)
    wt.start()

    def static_submit(i):
        with slock:
            sq.append((i, time.perf_counter()))
        return i

    st0, shandles = replay(static_submit)
    deadline = time.perf_counter() + 600
    while True:
        with slock:
            if len(sdone) == n_req:
                break
            done_n = len(sdone)
        if not wt.is_alive():
            sstop.set()
            raise RuntimeError(
                f"static-arm worker died with {done_n}/{n_req} "
                "requests finished (its m.generate raised — rerun "
                "with a smaller config)")
        if time.perf_counter() > deadline:
            sstop.set()
            raise RuntimeError(
                f"static arm stalled: {done_n}/{n_req} after 600s")
        time.sleep(0.005)
    sstop.set()
    wt.join(timeout=30)
    st_wall = max(sdone.values()) - (st0 + float(arrivals[0]))
    # a static batch emits its first token only when the whole batch
    # call returns: TTFT = completion - arrival
    st_ttft = [sdone[i] - (st0 + float(arrivals[i]))
               for i in range(n_req)]
    # the static arm has no engine feeding a tracker; score the SAME
    # objectives with slo's pure math over the measured latencies (a
    # static request is terminal when its batch returns, so TTFT ==
    # total latency; rate = its useful tokens over that latency)
    st_records = [{"ts": 0.0, "outcome": "completed",
                   "ttft_s": st_ttft[i], "total_s": st_ttft[i],
                   "tokens_per_sec": int(new_lens[i]) / st_ttft[i]
                   if st_ttft[i] > 0 else None}
                  for i in range(n_req)]
    st_slo = _slo_fields(slo.attainment(st_records, slo_cfg), slo_cfg)

    eng_tok_s = eng_tok / eng_wall if eng_wall > 0 else 0.0
    st_tok_s = useful / st_wall if st_wall > 0 else 0.0
    cfg = (f"d{args.dim}_l{args.layers}_v{args.vocab}_b{B}"
           f"_p{p_lo}to{p_hi}_n{n_lo}to{n_hi}_r{n_req}"
           + _kv_suffix(args.kv_dtype))
    base = {
        "unit": "tokens/s",
        "requests": n_req, "rps": round(rps, 2),
        "prompt_lens": [p_lo, p_hi], "new_lens": [n_lo, n_hi],
        "useful_tokens": useful,
        "kv_budget_rows": B * T,
        "device_kind": getattr(dev.jax_device, "device_kind", "")
        or "unknown",
    }
    recs = [
        {"metric": f"gpt_serve_engine_tok_s_{cfg}",
         "value": round(eng_tok_s, 1), **base,
         "completed": len(eng_done),
         "slots": slots, "page_size": ps, "num_pages": num_pages,
         "pool_mb": round(eng_report["pool_bytes"] / 1e6, 2),
         "steps_per_sync": args.serve_steps_per_sync,
         "ttft_p50_s": round(_pct(eng_ttft, 0.5), 4),
         "ttft_p99_s": round(_pct(eng_ttft, 0.99), 4),
         "wall_s": round(eng_wall, 3), **eng_slo},
        {"metric": f"gpt_serve_static_tok_s_{cfg}",
         "value": round(st_tok_s, 1), **base,
         "batch": B, "decoded_tokens": n_req * n_hi,
         "ttft_p50_s": round(_pct(st_ttft, 0.5), 4),
         "ttft_p99_s": round(_pct(st_ttft, 0.99), 4),
         "wall_s": round(st_wall, 3), **st_slo},
        {"metric": f"gpt_serve_speedup_x_{cfg}",
         "value": round(eng_tok_s / st_tok_s, 3) if st_tok_s else None,
         "unit": "x", "requests": n_req,
         "ttft_p99_ratio": round(
             _pct(st_ttft, 0.99) / _pct(eng_ttft, 0.99), 3)
         if eng_ttft and _pct(eng_ttft, 0.99) > 0 else None},
    ]
    # TTFT as records of their OWN, not just fields: tools/bench_trend
    # extracts top-level metric/value pairs only, so a latency series
    # must be a record for the regression gate to see it across rounds
    for arm, ttfts in (("engine", eng_ttft), ("static", st_ttft)):
        for pname, p in (("p50", 0.5), ("p99", 0.99)):
            v = _pct(ttfts, p)
            if v is not None:
                recs.append(
                    {"metric": f"gpt_serve_{arm}_ttft_{pname}_s_{cfg}",
                     "value": round(v, 4), "unit": "s",
                     "requests": n_req, "rps": round(rps, 2)})
    # SLO attainment as records of their OWN (not just per-arm fields):
    # bench_trend classifies `attainment` higher-is-better, so a
    # declared-objective slide trips the gate across rounds
    for arm, fields in (("engine", eng_slo), ("static", st_slo)):
        v = fields.get("slo_attainment_pct")
        if v is not None:
            recs.append(
                {"metric": f"gpt_serve_{arm}_slo_attainment_pct_{cfg}",
                 "value": v, "unit": "pct", "requests": n_req,
                 "slo_ttft_p99_s": args.serve_slo_ttft_p99,
                 "slo_latency_p99_s": args.serve_slo_latency_p99,
                 "slo_availability": args.serve_slo_availability})
    for rec in recs:
        observe.record_bench(rec)
        print(json.dumps(rec))
    if args.serve_out:
        with open(args.serve_out, "a", encoding="utf-8") as f:
            for rec in recs:
                f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
