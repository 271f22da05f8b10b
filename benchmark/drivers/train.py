"""Driver `train`: the graph-mode training step of `Model` on one chip,
fed from a cycled pool of seeded batches through the device prefetcher.

The window is the time between two fences (a loss fetch fences its step and
every step before it), at least `--seconds`; the rate divides by it.
"""

import itertools
import math
import time

import numpy as np

import flops
import reference
import traffic


def run(cell):
    from singa_tpu import models, opt, overlap, tensor
    sysm, win, chk = cell.system, cell.window, cell.check
    args = cell.model_args
    dev = cell.dev
    dev.SetRandSeed(cell.seed31)
    pool = traffic.generate(cell.traffic, args["vocab_size"],
                            args["max_seq"], cell.seed)
    B, S = pool[0][0].shape

    before = cell.dispatch_counts()
    m = models.create_model("gpt", **args)
    m.set_optimizer(getattr(opt, sysm["optimizer"])(lr=sysm["lr"]))
    # the eager init pass needs only some input: keep it small
    m.compile([tensor.from_numpy(pool[0][0][:1, :128], device=dev)],
              is_train=True, use_graph=sysm["use_graph"], amp=sysm["amp"])

    # the reference on the first batch, on the initial weights (the step
    # donates and replaces them): its loss, its logits for the first
    # sequence, and those logits with the deepest block left out, which the
    # tolerance has to tell apart from the right ones
    params = {k: v.data for k, v in m.get_params().items()}
    ids0, tgt0 = pool[0]
    H = args["num_heads"]
    ref = reference.loss(params, ids0, tgt0, H)
    ref_lg = np.asarray(reference.logits(params, ids0[:1], H)[0])
    skip_lg = np.asarray(reference.logits(params, ids0[:1], H,
                                          drop_last_blocks=1)[0])
    del params

    out, loss = m(tensor.from_numpy(ids0, device=dev),
                  tensor.from_numpy(tgt0, device=dev))
    first = float(loss.numpy())
    # error as a share of the spread of the reference's logits
    err = lambda lg: float(np.sqrt(np.mean((lg - ref_lg) ** 2))
                           / np.std(ref_lg))
    logit_err, skip_err = err(np.asarray(out.data[0])), err(skip_lg)
    del out, ref_lg, skip_lg
    kernels_ok, kernel_facts = cell.kernel_check(
        before, ("flash_fwd", "flash_bwd"), "step")

    fetched, steps = [], 0
    batches = itertools.cycle(pool[1:] + pool[:1])
    with overlap.prefetch_to_device(batches, m,
                                    size=sysm["prefetch"]) as feed:
        for _ in range(win["warm_steps"]):
            loss = m(*next(feed))[1]     # the logits are dropped at once
        warm = float(loss.numpy())                       # fence
        mark = cell.compile_mark()
        t0 = time.perf_counter()
        while True:
            if cell.trace and steps == win["trace_from_step"]:
                cell.trace_start()
            loss = m(*next(feed))[1]
            steps += 1
            if steps % win["fetch_every"] == 0:
                fetched.append(float(loss.numpy()))      # fence
                if cell.trace and steps == win["trace_from_step"] \
                        + win["trace_steps"]:
                    cell.trace_stop()
                t1 = time.perf_counter()
                if t1 - t0 >= cell.seconds:
                    break
        if cell.tracing():      # a window too short to reach the last step
            cell.trace_stop()
        peak = cell.memory_peak()
        compiled_inside = cell.compile_mark() != mark

    window = t1 - t0
    tokens_per_s = steps * B * S / window
    fpt = flops.gpt2_train_flops_per_token(args, S)
    kind = dev.jax_device.device_kind
    finite = [math.isfinite(x) for x in fetched]
    k = min(3, len(fetched) // 2)
    rel = abs(first - ref) / abs(ref)
    checks = {
        "loss_equals_reference": rel <= chk["loss_rtol"],
        "logits_equal_reference": logit_err <= chk["logit_rms_tol"],
        "tolerance_tells_a_skipped_block": skip_err > chk["logit_rms_tol"],
        "losses_finite": all(finite) and math.isfinite(first),
        "loss_falls": k > 0 and np.mean(fetched[-k:]) < np.mean(fetched[:k]),
        "kernel_paths": kernels_ok,
        "no_compile_in_window": not compiled_inside,
    }
    return {
        "checks": {k: bool(v) for k, v in checks.items()}, "attempted": steps,
        "failed": finite.count(False) * win["fetch_every"],
        "memory_peak_bytes": peak,
        "values": {"train_tokens_per_s": tokens_per_s,
                   "setup_s": t0 - cell.t0,
                   "step_ms": 1e3 * window / steps,
                   "hbm_peak_gb": peak / 1e9 or None,
                   "flash_shape": [B, args["num_heads"], S,
                                   args["dim"] // args["num_heads"]],
                   "device_kind": kind},
        "notes": {
            "window_s": window, "steps": steps, "batch": [B, S],
            "loss_first": first, "loss_reference": ref,
            "loss_rel_diff": rel, "logit_rms_error": logit_err,
            "logit_rms_error_skipping_a_block": skip_err,
            "loss_after_warm_up": warm, "losses_fetched": fetched,
            "flops_per_token": fpt, "params_held": flops.gpt2_params_held(args),
            "model_flops_utilization":
                tokens_per_s * fpt / flops.peak(kind, "bf16_flops")
                if kind in flops.PEAKS else None,
            **kernel_facts},
    }
