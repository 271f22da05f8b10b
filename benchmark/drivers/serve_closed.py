"""Driver `serve_closed`: `ServingEngine` under a closed loop of clients.

Each client submits a request, waits for its reply and submits the next at
once. The clients live on the engine's own request listener (it fires when a
request ends), so the load comes from no thread of its own. Clients start
during set-up so that the window opens on a full, ragged batch; when the
window closes the engine is stopped and nothing waits for requests in flight.
The rate counts every output token emitted inside the window, whichever
request it belongs to; latencies come from requests submitted and finished
inside it.
"""

import threading
import time

import numpy as np

import reference
import traffic


def check_tokens(m, reqs, n_head, max_ctx, drop_last_blocks=0):
    """(worst gap, tokens equal to the argmax, tokens): the gap is the
    distance, as a share of the position's logit range, of a generated
    token's reference logit below that position's maximum. One
    teacher-forced fp32 forward over prompt plus output for each request,
    padded to the context so that it compiles once (a causal model's
    logits do not depend on what follows). `drop_last_blocks` asks a
    deliberately wrong reference, to show what the gate tells apart."""
    params = {k: v.data for k, v in m.get_params().items()}
    worst, exact, total = 0.0, 0, 0
    for r in reqs:
        s0, n = len(r.prompt), len(r.tokens)
        ids = np.zeros((1, max_ctx), np.int32)
        ids[0, :s0] = r.prompt
        ids[0, s0:s0 + n - 1] = r.tokens[:-1]
        lg = np.asarray(reference.logits(
            params, ids, n_head, drop_last_blocks)[0, s0 - 1:s0 + n - 1])
        if not np.isfinite(lg).all():
            return float("inf"), exact, total + n
        top, low = lg.max(-1), lg.min(-1)
        got = lg[np.arange(n), np.asarray(r.tokens)]
        worst = max(worst, float(((top - got) / (top - low)).max()))
        exact += int((got == top).sum())
        total += n
    return worst, exact, total


def run(cell):
    from singa_tpu import engine, models, tensor
    args, win, chk = cell.model_args, cell.window, cell.check
    eng_args = dict(cell.system["engine"])
    eng_args["prompt_buckets"] = tuple(eng_args["prompt_buckets"])
    dev = cell.dev
    dev.SetRandSeed(cell.seed31)
    work = traffic.generate(cell.traffic, args["vocab_size"],
                            eng_args["max_ctx"], cell.seed)

    m = models.create_model("gpt", **args)
    m.compile([tensor.from_numpy(work[0][0][None, :32], device=dev)],
              is_train=False, use_graph=False)
    m.eval()
    before = cell.dispatch_counts()
    eng = engine.ServingEngine(m, **eng_args)
    eng.start()

    mine, state = [], {"next": 0, "open": True, "error": None}
    turn = threading.Lock()   # the first submits race the first replies

    def submit_next():
        with turn:
            prompt, n = work[state["next"] % len(work)]
            state["next"] += 1
        mine.append(eng.submit(prompt, n))

    def on_done(req, _timeline):
        # a client: its reply came, so it sends its next request at once
        try:
            if state["open"] and not req.synthetic:
                submit_next()
        except Exception as e:   # the engine swallows a listener's error
            state["error"] = repr(e)

    try:
        # every bucket this traffic maps to, and the decode; no other
        eng.prewarm(sorted({len(p) for p, _n in work}))
        kernels_ok, kernel_facts = cell.kernel_check(
            before, ("flash_fwd", "paged"), "serving.engine_step")
        engine.add_request_listener(on_done)
        for _ in range(cell.traffic["clients"]):
            submit_next()
        time.sleep(win["ramp_s"])

        emitted = lambda: sum(len(r.tokens) for r in list(mine))
        mark = cell.compile_mark()
        steps0, tokens0 = eng.report()["steps"], emitted()
        t0m, t0 = time.monotonic(), time.perf_counter()
        if cell.trace:
            time.sleep(win["trace_from_s"])
            cell.trace_start()
            time.sleep(win["trace_s"])
            cell.trace_stop()
        time.sleep(max(0.0, cell.seconds - (time.perf_counter() - t0)))
        t1m = time.monotonic()
        tokens, steps = emitted() - tokens0, eng.report()["steps"] - steps0
        state["open"] = False
        peak = cell.memory_peak()
        compiled_inside = cell.compile_mark() != mark
        pool_bytes = eng.pool_bytes()
    finally:
        engine.remove_request_listener(on_done)
        eng.stop()

    window = t1m - t0m
    snap = list(mine)
    done = [r for r in snap if r.finished_ts is not None
            and t0m <= r.finished_ts <= t1m]
    good = [r for r in done if r.outcome == engine.OUTCOME_COMPLETED
            and len(r.tokens) == r.max_new]
    inside = [r for r in good if r.submitted >= t0m]
    attempted = [r for r in snap if t0m <= r.submitted <= t1m]
    bad = set(done) - set(good)
    failed = [r for r in attempted if r in bad]
    first_tokens = sum(1 for r in snap if r.first_token_ts is not None
                       and t0m <= r.first_token_ts <= t1m)
    ttft = [1e3 * (r.first_token_ts - r.submitted) for r in inside]
    tpot = [1e3 * (r.finished_ts - r.first_token_ts) / (len(r.tokens) - 1)
            for r in inside if len(r.tokens) > 1]
    qdelay = [1e3 * (r.admitted - r.submitted) for r in inside]

    rng = np.random.default_rng(cell.seed)
    sample = [good[i] for i in rng.choice(
        len(good), min(chk["sample"], len(good)), replace=False)]
    look = lambda **kw: check_tokens(m, sample, args["num_heads"],
                                     eng_args["max_ctx"], **kw) \
        if sample else (float("inf"), 0, 0)
    worst, exact, total = look()
    worst_skip, _, _ = look(drop_last_blocks=1)
    checks = {
        "tokens_within_reference_gap": worst <= chk["logit_gap_of_range"],
        "gap_tells_a_skipped_block": worst_skip > chk["logit_gap_of_range"],
        "all_requests_completed": not failed and len(good) > 0
            and state["error"] is None,
        "kernel_paths": kernels_ok,
        "no_compile_in_window": not compiled_inside,
    }
    return {
        "checks": {k: bool(v) for k, v in checks.items()}, "attempted": len(attempted), "failed": len(failed),
        "memory_peak_bytes": peak,
        "values": {
            "serve_tokens_per_s": tokens / window,
            "setup_s": t0 - cell.t0,
            "ms_per_decode_step": 1e3 * window / steps if steps else None,
            "slot_occupancy": 100.0 * (tokens - first_tokens)
                / (steps * eng.max_slots) if steps else None,
            "ttft_p50_ms": cell.pctile(ttft, 0.5),
            "tpot_p50_ms": cell.pctile(tpot, 0.5),
            "hbm_peak_gb": peak / 1e9 or None},
        "notes": {
            "window_s": window, "decode_steps": steps,
            "tokens_emitted_in_window": tokens,
            "tokens_of_requests_finished_in_window":
                sum(len(r.tokens) for r in good),
            "ttft_p95_ms": cell.pctile(ttft, 0.95),
            "tpot_p95_ms": cell.pctile(tpot, 0.95),
            "queue_delay_p95_ms": cell.pctile(qdelay, 0.95),
            "finished_in_window": len(done),
            "submitted_and_finished_in_window": len(inside),
            "requests_per_s": len(good) / window,
            "queue_delay_p50_ms": cell.pctile(qdelay, 0.5),
            "prompt_tokens_per_s": sum(len(r.prompt) for r in good) / window,
            "listener_error": state["error"],
            "reference_sample": len(sample), "reference_tokens": total,
            "reference_worst_gap_of_range": worst,
            "reference_worst_gap_skipping_a_block": worst_skip,
            "reference_argmax_agreement": exact / total if total else None,
            "pool_bytes": pool_bytes, **kernel_facts},
    }
