"""Driver `train_moe_lm`: the graph-mode training step of the sparse model
with sliding and full attention layers (`models.create_model("mellum")`) on
one chip, fed from a cycled pool of seeded batches through the device
prefetcher: the window, fences, prefetcher and compile mark of
drivers/train.py, with this model, its reference (reference_mellum.py) and
its FLOPs (flops_mellum.py).

The step hands back the loss, the logits at 128 positions and, for each
layer, the rows routed to each expert this chip holds, and it changes the
parameters. `correct` holds the first three to the reference's forward on
the first batch and the change of EVERY parameter to the reference's
gradient put through Adam's first step (update_check.py): the backward
pass through the sort, the grouped products and the router, the windowed
kernels' three gradients, the rebuilt regions and the optimizer. What the
limits are worth is control_mellum.py's to show, through this file's
`compare`: the wrong models (reference_mellum.WRONG) and the reference in
bfloat16 throughout. A timed run computes one reference forward and one
gradient.
"""

import itertools
import math
import time

import numpy as np

import flops
import flops_mellum
import reference_mellum as reference
import traffic
import update_check


def sample_rows(args, n):
    """The flat positions whose logits the step hands back."""
    return np.linspace(0, n - 1, min(args["sample"], n)).astype(np.int32)


def reference_readings(params, ids, tgt, args, lr, wrong=()):
    """What the reference says of the first batch on the initial weights:
    ({"loss", "rows", "sample"}, the same of each wrong model named, the
    parameters expected after the first step)."""
    rows = sample_rows(args, ids.size)
    ref = reference.loss_parts(params, ids, tgt, args, rows=rows)
    # the expert the wrong model leaves out: the held one that the
    # reference routes most rows to (one that no token reaches could be
    # left out of any model unseen)
    busiest = int(np.argmax(ref["rows"].sum(0)))
    wrong = {name: reference.loss_parts(
        params, ids, tgt, args, rows=rows, wrong=name, expert=busiest)
        for name in wrong}
    grads = reference.grads(params, ids, tgt, args)
    return ref, wrong, update_check.Expected(params, grads, lr, 0.0)


def compare(got, ref, wrong, chk):
    """(checks, notes) of `got` = {"loss", "sample", "rows", "update":
    update_check's summary of the first step} against the reference's
    readings, and of each wrong model in `wrong` (none in a timed run)
    against the limit that has to tell it. The control that puts a
    lower-precision reference in the program's place goes through this
    same function."""
    ref_lg = np.asarray(ref["sample"], np.float32)
    # error as a share of the spread of the reference's logits
    err = lambda lg: float(np.sqrt(np.mean(
        (np.asarray(lg, np.float32) - ref_lg) ** 2)) / np.std(ref_lg))
    logit_err = err(got["sample"])
    wrong_err = {k: err(w["sample"]) for k, w in wrong.items()}
    rel = abs(got["loss"] - ref["loss"]) / abs(ref["loss"])
    rows_off = np.abs(np.asarray(got["rows"], np.float64) - ref["rows"])
    # a pair that goes to another expert leaves one count and joins another
    moved = float(rows_off.sum() / 2)
    checks = {
        "loss_equals_reference": rel <= chk["loss_rtol"],
        "logits_equal_reference": logit_err <= chk["logit_rms_tol"],
        "rows_routed_equal_reference": moved <= chk["rows_moved_tol"],
        "first_update_equals_reference":
            got["update"]["worst_leaf"] <= chk["update_tol"],
        **{"tolerance_tells_" + k: e > chk["logit_rms_tol"]
           for k, e in wrong_err.items()},
    }
    notes = {
        "loss_first": got["loss"], "loss_reference": ref["loss"],
        "loss_rel_diff": rel, "logit_rms_error": logit_err,
        "rows_routed_first": np.asarray(got["rows"]).tolist(),
        "rows_routed_reference": ref["rows"].tolist(),
        "rows_worst_diff": float(rows_off.max()),
        "rows_moved": moved,
        "first_update_error": got["update"],
        **{"logit_rms_error_" + k: v for k, v in wrong_err.items()},
        **{"loss_rel_diff_" + k: abs(w["loss"] - ref["loss"])
           / abs(ref["loss"]) for k, w in wrong.items()}}
    return checks, notes


def build(cell):
    """The model as the cell runs it, compiled, on its initial weights:
    every weight by the program's own initialisers from the mix's
    `weights_seed`, the embedding then scaled to the mix's `embed_std`."""
    from singa_tpu import models, opt, tensor
    sysm = cell.system
    # the weights decide how many rows a step routes to this chip's experts,
    # at the start and as the routers train: no --seed changes them, so none
    # changes the amount of work (traffic.py's rule); --seed draws the ids
    cell.dev.SetRandSeed(sysm["weights_seed"])
    m = models.create_model("mellum", recompute=sysm["recompute"],
                            **cell.model_args)
    m.set_optimizer(getattr(opt, sysm["optimizer"])(lr=sysm["lr"]))
    # the eager init pass needs only some input: keep it small
    m.compile([tensor.from_numpy(
        np.zeros((1, 128), np.int32), device=cell.dev)],
        is_train=True, use_graph=sysm["use_graph"], amp=sysm["amp"])
    # an embedding that outweighs the layers' outputs keeps a token's
    # identity in the stream, so its experts are its own: with the plain
    # initialiser every token of a fresh model goes to the same few
    W = tensor.to_numpy(m.get_params()["tok_embed.W"])
    m.set_params({"tok_embed.W": W * (sysm["embed_std"] / W.std())})
    return m


def step_memory(key="step"):
    """Bytes the compiled step needs live by the compiler's own count: its
    arguments (the state, donated and written in place, and the batch) and
    its temporaries at their peak. The allocator's peak is the process's,
    and the reference's gradient runs in this process first."""
    from singa_tpu import introspect
    mem = (introspect.last_build(key) or {}).get("memory") or {}
    return mem.get("arguments", 0) + mem.get("temps", 0)


def run(cell):
    from singa_tpu import overlap, tensor
    from singa_tpu.models import mellum
    sysm, win, chk = cell.system, cell.window, cell.check
    args = cell.model_args
    dev = cell.dev
    pool = traffic.generate(cell.traffic, args["vocab_size"], None, cell.seed)
    B, S = pool[0][0].shape

    before = cell.dispatch_counts()
    m = build(cell)

    # the reference on the first batch, on the initial weights (the step
    # donates and replaces them), before the step takes the memory
    ids0, tgt0 = pool[0]
    built = time.perf_counter()
    ref, wrong, expected = reference_readings(
        {k: v.data for k, v in m.get_params().items()}, ids0, tgt0, args,
        sysm["lr"])
    referred = time.perf_counter()

    loss, sample, rows = m(tensor.from_numpy(ids0, device=dev),
                           tensor.from_numpy(tgt0, device=dev))
    first = float(loss.numpy())
    got = {"loss": first, "sample": np.asarray(sample.data),
           "rows": np.asarray(rows.data),
           "update": expected.error_of_step(
               {k: v.data for k, v in m.get_params().items()})}
    first_checks, first_notes = compare(got, ref, wrong, chk)
    del sample, got, ref, wrong, expected
    kernels_ok, kernel_facts = cell.kernel_check(
        before, ("flash_fwd", "flash_bwd"), "step")

    fetched, routed, steps = [], [], 0
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
            loss, _, rows = m(*next(feed))
            routed.append(rows)     # (layers, held) numbers: read later
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
        peak, allocator_peak = step_memory(), cell.memory_peak()
        compiled_inside = cell.compile_mark() != mark

    window = t1 - t0
    tokens_per_s = steps * B * S / window
    routed = np.stack([np.asarray(r.data) for r in routed])  # (steps, L, H)
    mellum.record_rows(routed[-1])
    # a traced run's device metrics are of the traced steps: their rows.
    # The routers train on this chip's partial sum, so the rows drift
    # through the window: the mix names a stretch near the window's mean,
    # and the notes give both
    traced = routed[win["trace_from_step"]:
                    win["trace_from_step"] + win["trace_steps"]]
    if not len(traced):
        traced = routed
    mean_rows = (traced if cell.trace else routed).mean(0)
    per_step = flops_mellum.train_flops_per_step(args, B, S, mean_rows)
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
    load = routed.max(-1) / routed.mean(-1)     # (steps, L)
    return {
        "checks": {k: bool(v) for k, v in checks.items()}, "attempted": steps,
        "failed": finite.count(False) * win["fetch_every"],
        "memory_peak_bytes": peak,
        "values": {"train_tokens_per_s": tokens_per_s,
                   "setup_s": t0 - cell.t0,
                   "step_ms": 1e3 * window / steps,
                   "hbm_peak_gb": peak / 1e9 or None,
                   "device_kind": kind,
                   "model_flops_per_step": per_step,
                   "moe_rows": mean_rows.tolist(),
                   "expert_load_imbalance": float(load.mean()),
                   "model_args": args, "batch": [B, S]},
        "notes": {
            "window_s": window, "steps": steps, "batch": [B, S],
            **first_notes,
            "loss_after_warm_up": warm, "losses_fetched": fetched,
            "rows_routed_a_layer": {
                "mean": routed.sum(-1).mean(0).tolist(),
                "least": routed.sum(-1).min(0).tolist(),
                "most": routed.sum(-1).max(0).tolist(),
                "worst_case": B * S * min(args["experts_per_token"],
                                          args["experts_held"])},
            "rows_routed_a_step": {
                "window_mean": float(routed.sum((1, 2)).mean()),
                "traced_steps_mean": float(traced.sum((1, 2)).mean()),
                "every_step": routed.sum((1, 2)).tolist()},
            "allocator_peak_bytes": allocator_peak,
            "setup_parts_s": {"to_built": built - cell.t0,
                              "reference": referred - built,
                              "first_step_to_window": t0 - referred},
            "expert_load_largest_over_mean": {
                "mean": float(load.mean()), "most": float(load.max())},
            "flops_per_step": per_step,
            "flops_per_step_by_part": flops_mellum.parts_per_step(
                args, B, S, mean_rows),
            "params_held": flops_mellum.params_held(args),
            "model_flops_utilization":
                tokens_per_s / (B * S) * per_step
                / flops.peak(kind, "bf16_flops")
                if kind in flops.PEAKS else None,
            **kernel_facts},
    }
