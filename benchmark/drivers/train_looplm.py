"""Driver `train_looplm`: the graph-mode training step of the looped
language model (`models.create_model("looplm")`) on one chip, fed from a
cycled pool of seeded batches through the device prefetcher: the window,
fences, prefetcher and compile mark of drivers/train.py, with this model,
its reference (reference_looplm.py) and its FLOPs (flops_looplm.py).

The step hands back the loss, the T passes' mean cross-entropies, the mean
exit distribution and the last pass's logits at 128 positions, and it
changes the parameters. `correct` holds the first four to the reference's
forward on the first batch and the change of EVERY parameter to the
reference's gradient put through Adam's first step (update_check.py): the
backward pass, the rebuilt regions, the sum of a shared weight's T
gradients and the optimizer. It also holds the limits to telling wrong
models apart: the last pass left out, one block left out of the last pass,
one left out of the first, and a shared weight's gradient taken from its
last use alone.
"""

import itertools
import math
import time

import numpy as np

import flops
import flops_looplm
import reference_looplm as reference
import traffic
import update_check


def sample_rows(args, n):
    """The flat positions whose last-pass logits the step hands back."""
    return np.linspace(0, n - 1, min(args["sample"], n)).astype(np.int32)


def reference_readings(params, ids, tgt, args, chk, lr):
    """What the reference says of the first batch on the initial weights:
    ({"loss", "ce", "p", "sample"}, the same of the wrong models, the
    parameters expected after the first step, the update error a gradient
    from the last pass alone would read)."""
    rows = sample_rows(args, ids.size)
    ref = reference.loss_parts(params, ids, tgt, args, rows=rows)
    wrong = {
        "dropping_the_last_pass": reference.loss_parts(
            params, ids, tgt, args, rows=rows, passes=args["ut_steps"] - 1),
        "dropping_a_block_in_one_pass": reference.loss_parts(
            params, ids, tgt, args, rows=rows, skip=tuple(chk["skip"])),
        "dropping_a_block_in_the_first_pass": reference.loss_parts(
            params, ids, tgt, args, rows=rows,
            skip=tuple(chk["skip_early"])),
    }
    grads, last = reference.grads(params, ids, tgt, args)
    expected = update_check.Expected(params, grads, lr, 0.0)
    one_pass = expected.error_of_gradient(grads, last)
    return ref, wrong, expected, one_pass


def compare(got, ref, wrong, one_pass, chk):
    """(checks, notes) of `got` = {"loss", "ce", "p", "sample", "update":
    update_check's summary of the first step} against the reference's
    readings. The control that puts a lower-precision reference in the
    program's place goes through this same function."""
    ref_lg = np.asarray(ref["sample"], np.float32)
    # error as a share of the spread of the reference's logits
    err = lambda lg: float(np.sqrt(np.mean(
        (np.asarray(lg, np.float32) - ref_lg) ** 2)) / np.std(ref_lg))
    logit_err = err(got["sample"])
    wrong_err = {k: err(w["sample"]) for k, w in wrong.items()}
    rel = abs(got["loss"] - ref["loss"]) / abs(ref["loss"])
    ce_rel = float(np.max(np.abs(np.subtract(got["ce"], ref["ce"]))
                          / np.abs(ref["ce"])))
    p_abs = float(np.max(np.abs(np.subtract(got["p"], ref["p"]))))
    # the wrong models by the other limits too (the model a pass short has
    # T - 1 parts: its loss only). The last pass's logits tell a dropped
    # pass and a block dropped late; the exit distribution, which does not
    # read the last pass's gate, tells a block dropped early
    wrong_loss = {k: abs(w["loss"] - ref["loss"]) / abs(ref["loss"])
                  for k, w in wrong.items()}
    T = len(ref["ce"])
    wrong_parts = {k: {
        "pass_ce_rel_diff": float(np.max(np.abs(np.subtract(
            w["ce"], ref["ce"])) / np.abs(ref["ce"]))),
        "exit_abs_diff": float(np.max(np.abs(np.subtract(w["p"], ref["p"]))))}
        for k, w in wrong.items() if len(w["ce"]) == T}
    checks = {
        "loss_equals_reference": rel <= chk["loss_rtol"],
        "pass_losses_equal_reference": ce_rel <= chk["pass_ce_rtol"],
        "exit_distribution_equals_reference": p_abs <= chk["exit_atol"],
        "logits_equal_reference": logit_err <= chk["logit_rms_tol"],
        "first_update_equals_reference":
            got["update"]["worst_leaf"] <= chk["update_tol"],
        "tolerance_tells_a_dropped_pass":
            wrong_err["dropping_the_last_pass"] > chk["logit_rms_tol"],
        "tolerance_tells_a_dropped_block":
            wrong_err["dropping_a_block_in_one_pass"] > chk["logit_rms_tol"],
        "tolerance_tells_a_block_dropped_in_the_first_pass":
            wrong_parts["dropping_a_block_in_the_first_pass"]
            ["exit_abs_diff"] > chk["exit_atol"],
        "tolerance_tells_a_gradient_of_one_pass_alone":
            one_pass["worst_leaf"] > chk["update_tol"],
    }
    notes = {
        "loss_first": got["loss"], "loss_reference": ref["loss"],
        "loss_rel_diff": rel, "pass_ce": list(map(float, got["ce"])),
        "pass_ce_reference": ref["ce"], "pass_ce_rel_diff": ce_rel,
        "exit_mean": list(map(float, got["p"])),
        "exit_mean_reference": ref["p"], "exit_abs_diff": p_abs,
        "logit_rms_error": logit_err,
        "first_update_error": got["update"],
        "update_error_of_one_pass_s_gradient": one_pass,
        **{"logit_rms_error_" + k: v for k, v in wrong_err.items()},
        **{"loss_rel_diff_" + k: v for k, v in wrong_loss.items()},
        **{f"{part}_{k}": v for k, parts in wrong_parts.items()
           for part, v in parts.items()}}
    return checks, notes


def run(cell):
    from singa_tpu import models, opt, overlap, tensor
    sysm, win, chk = cell.system, cell.window, cell.check
    args = cell.model_args
    dev = cell.dev
    dev.SetRandSeed(cell.seed31)
    pool = traffic.generate(cell.traffic, args["vocab_size"], None, cell.seed)
    B, S = pool[0][0].shape

    before = cell.dispatch_counts()
    m = models.create_model("looplm", recompute=sysm["recompute"], **args)
    m.set_optimizer(getattr(opt, sysm["optimizer"])(lr=sysm["lr"]))
    # the eager init pass needs only some input: keep it small
    m.compile([tensor.from_numpy(pool[0][0][:1, :128], device=dev)],
              is_train=True, use_graph=sysm["use_graph"], amp=sysm["amp"])

    # the reference on the first batch, on the initial weights (the step
    # donates and replaces them), before the step takes the memory
    ids0, tgt0 = pool[0]
    ref, wrong, expected, one_pass = reference_readings(
        {k: v.data for k, v in m.get_params().items()}, ids0, tgt0, args,
        chk, sysm["lr"])

    loss, ce, p_mean, sample = m(tensor.from_numpy(ids0, device=dev),
                                 tensor.from_numpy(tgt0, device=dev))
    first = float(loss.numpy())
    got = {"loss": first, "ce": np.asarray(ce.data),
           "p": np.asarray(p_mean.data), "sample": np.asarray(sample.data),
           "update": expected.error_of_step(
               {k: v.data for k, v in m.get_params().items()})}
    first_checks, first_notes = compare(got, ref, wrong, one_pass, chk)
    del sample, got, ref, wrong, expected
    kernels_ok, kernel_facts = cell.kernel_check(
        before, ("flash_fwd", "flash_bwd"), "step")

    fetched, steps = [], 0
    batches = itertools.cycle(pool[1:] + pool[:1])
    with overlap.prefetch_to_device(batches, m,
                                    size=sysm["prefetch"]) as feed:
        for _ in range(win["warm_steps"]):
            loss = m(*next(feed))[0]
        warm = float(loss.numpy())                       # fence
        mark = cell.compile_mark()
        t0 = time.perf_counter()
        while True:
            if cell.trace and steps == win["trace_from_step"]:
                cell.trace_start()
            loss = m(*next(feed))[0]
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
    fpt = flops_looplm.train_flops_per_token(args, S)
    kind = dev.jax_device.device_kind
    finite = [math.isfinite(x) for x in fetched]
    k = min(3, len(fetched) // 2)
    checks = {
        **first_checks,
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
                   "device_kind": kind,
                   "model_flops_per_step": fpt * B * S},
        "notes": {
            "window_s": window, "steps": steps, "batch": [B, S],
            **first_notes,
            "loss_after_warm_up": warm, "losses_fetched": fetched,
            "flops_per_token": fpt,
            "flops_per_token_by_part": flops_looplm.parts_per_token(args, S),
            "params_held": flops_looplm.params_held(args),
            "model_flops_utilization":
                tokens_per_s * fpt / flops.peak(kind, "bf16_flops")
                if kind in flops.PEAKS else None,
            **kernel_facts},
    }
