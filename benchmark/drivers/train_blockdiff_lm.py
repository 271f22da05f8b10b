"""Driver `train_blockdiff_lm`: the graph-mode training step of the sparse
model trained by diffusion over blocks (`models.create_model("sdar")`) on
one chip, fed from a cycled pool of seeded batches through the device
prefetcher: the window, fences, prefetcher and compile mark of
drivers/train_moe_lm.py, with this model, its reference (reference_sdar.py)
and its FLOPs (flops_sdar.py).

A batch is (ids, masked, weight): the ids by the one traffic generator over
the data rows of the vocabulary (every row but the last, which stands for
[MASK]), the mask and the weight a position by `data.block_diffusion_noise`
from --seed, as a user's loop would draw them. The step noises on the
device, feeds the doubled sequence [noised ; clean] and hands back the
loss, the logits at 128 positions of the noised half and, for each layer,
the rows routed to each expert this chip holds, and it changes the
parameters. `train_tokens_per_s` counts DATA tokens (steps x batch x seq),
not the doubled rows: a user pays for tokens learned from.

`correct` holds loss, logits and rows to the reference's forward on the
first batch and the change of every parameter but five (`compared`) to the
reference's gradient put through Adam's first step (update_check.py; the
step is then undone, `restart`): the backward pass through
the `_bd` kernels' three gradients, the sort, the grouped products, the
router, the norms on q and k, the rebuilt regions and the optimizer. What
the limits are worth is control_sdar.py's to show, through this file's
`compare`: the wrong models (reference_sdar.WRONG) and the reference in
bfloat16 throughout. A timed run computes one reference forward and one
gradient.
"""

import itertools
import math
import time

import numpy as np

import flops
import flops_sdar
import reference_sdar as reference
import traffic
import update_check


def sample_rows(args, shape):
    """The flat positions of the noised half (batch, seq) whose logits the
    step hands back: the program's own choice."""
    from singa_tpu.models import sdar
    return sdar.sample_positions(*shape, args["sample"])


def compared(tree, args):
    """`tree` ({parameter: array}) less the last block's expert layer and
    the norm before it: what the first step's update is compared over. In
    the last block only the masked positions carry a gradient (nothing
    reads the clean half's output, and an unmasked position's weight is 0);
    on fresh weights they all hold the one [MASK] embedding and go to the
    same 8 of the 128 experts, of which this chip holds one: the whole
    gradient of these five leaves is that expert's few hundred rows, each
    weighted up to 1 / t = 1000, and one (row, expert) pair the program
    routes elsewhere than the reference (`rows_moved_tol` allows a hundred)
    moves it by tens of percent (the mix's `check.reasons.update_tol` has
    the readings). The same code is held to the reference in the five
    blocks before, where every held expert has a gradient."""
    last = f"TransformerBlock_{args['num_layers'] - 1}."
    return {k: v for k, v in tree.items() if not (
        k.startswith(last) and (".moe." in k or k.endswith("ln2.gamma")))}


def batches(cell, seed=None):
    """The pool: [(ids, masked, weight)], each (batch, seq). `seed`
    (--seed where None) draws the ids (over the data rows: the last row
    held is [MASK]), and the noise with them."""
    from singa_tpu import data
    p, args = cell.traffic, cell.model_args
    seed = cell.seed if seed is None else seed
    rng = np.random.default_rng([seed, 0xB10C])
    pool = traffic.generate(p, args["vocab_size"] - 1, None, seed)
    return [(ids, *data.block_diffusion_noise(
        rng, ids.shape, args["block_length"], p["rate_min"])[:2])
        for ids, _next in pool]


def reference_readings(params, batch, args, lr, wrong=()):
    """What the reference says of the first batch on the initial weights:
    ({"loss", "rows", "sample"}, the same of each wrong model named, the
    parameters expected after the first step)."""
    rows = sample_rows(args, batch[0].shape)
    ref = reference.loss_parts(params, *batch, args, rows=rows)
    # the expert the wrong model leaves out: the held one that the
    # reference routes most rows to (one that no row reaches could be left
    # out of any model unseen)
    busiest = int(np.argmax(ref["rows"].sum(0)))
    wrong = {name: reference.loss_parts(
        params, *batch, args, rows=rows, wrong=name, expert=busiest)
        for name in wrong}
    grads = reference.grads(params, *batch, args)
    return ref, wrong, update_check.Expected(
        compared(params, args), compared(grads, args), lr, 0.0)


def compare(got, ref, wrong, chk):
    """(checks, notes) of `got` = {"loss", "sample", "rows", "update":
    update_check's summary of the first step} against the reference's
    readings, and of each wrong model in `wrong` (none in a timed run)
    against the limits: one of the logits' and the loss's has to tell it
    (a loss weighted wrongly has the right logits). The control that puts
    a lower-precision reference in the program's place goes through this
    same function."""
    ref_lg = np.asarray(ref["sample"], np.float32)
    # error as a share of the spread of the reference's logits
    err = lambda lg: float(np.sqrt(np.mean(
        (np.asarray(lg, np.float32) - ref_lg) ** 2)) / np.std(ref_lg))
    off = lambda loss: abs(loss - ref["loss"]) / abs(ref["loss"])
    logit_err, rel = err(got["sample"]), off(got["loss"])
    wrong_err = {k: err(w["sample"]) for k, w in wrong.items()}
    wrong_rel = {k: off(w["loss"]) for k, w in wrong.items()}
    rows_off = np.abs(np.asarray(got["rows"], np.float64) - ref["rows"])
    # a pair that goes to another expert leaves one count and joins another
    moved = float(rows_off.sum() / 2)
    checks = {
        "loss_equals_reference": rel <= chk["loss_rtol"],
        "logits_equal_reference": logit_err <= chk["logit_rms_tol"],
        "rows_routed_equal_reference": moved <= chk["rows_moved_tol"],
        "first_update_equals_reference":
            got["update"]["worst_leaf"] <= chk["update_tol"],
        **{"tolerance_tells_" + k: wrong_err[k] > chk["logit_rms_tol"]
           or wrong_rel[k] > chk["loss_rtol"] for k in wrong},
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
        **{"loss_rel_diff_" + k: v for k, v in wrong_rel.items()}}
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
    # and the mask
    cell.dev.SetRandSeed(sysm["weights_seed"])
    m = models.create_model("sdar", recompute=sysm["recompute"],
                            **cell.model_args)
    m.set_optimizer(getattr(opt, sysm["optimizer"])(lr=sysm["lr"]))
    # the eager init pass needs only some doubled input: keep it small
    m.compile([tensor.from_numpy(
        np.zeros((1, 256), np.int32), device=cell.dev)],
        is_train=True, use_graph=sysm["use_graph"], amp=sysm["amp"])
    # an embedding that outweighs the layers' outputs keeps a token's
    # identity in the stream, so its experts are its own (the Mellum mix's
    # reason)
    W = tensor.to_numpy(m.get_params()["tok_embed.W"])
    m.set_params({"tok_embed.W": W * (sysm["embed_std"] / W.std())})
    return m


def restart(m, start):
    """Put the model back on the weights `start` ({name: host array}) with
    a fresh optimizer state: the first step was --seed's, for the
    comparison with the reference, and the run goes on without it. On fresh
    weights every masked row holds the one [MASK] embedding, so one Adam
    step moves its router logits for all of them at once (by lr x 2048
    entries of order 1: 0.16 against a spread of 1.4), and that first
    step's data settled which held experts the masked rows of a layer
    keep: 2,048 rows an expert, a run in three 0.4 to 0.6 % slower
    (`check.reasons.weights_seed`). From the same state, warmed up on the
    same batches, every seed's window starts alike."""
    import jax.numpy as jnp
    m.set_params(start)
    m.optimizer.load_state_arrays(
        [jnp.zeros_like(a) for a in m.optimizer.state_arrays()])


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
    from singa_tpu.models import sdar
    sysm, win, chk = cell.system, cell.window, cell.check
    args = cell.model_args
    dev = cell.dev
    pool = batches(cell)
    B, S = pool[0][0].shape

    before = cell.dispatch_counts()
    m = build(cell)

    # the reference on the first batch, on the initial weights (the step
    # donates and replaces them), before the step takes the memory
    built = time.perf_counter()
    ref, wrong, expected = reference_readings(
        {k: v.data for k, v in m.get_params().items()}, pool[0], args,
        sysm["lr"])
    referred = time.perf_counter()

    start = {k: np.asarray(v.data) for k, v in m.get_params().items()}
    loss, sample, rows = m(*(tensor.from_numpy(a, device=dev)
                             for a in pool[0]))
    first = float(loss.numpy())
    got = {"loss": first, "sample": np.asarray(sample.data),
           "rows": np.asarray(rows.data),
           "update": expected.error_of_step(
               {k: v.data for k, v in m.get_params().items()})}
    first_checks, first_notes = compare(got, ref, wrong, chk)
    del sample, got, ref, wrong, expected
    kernels_ok, kernel_facts = cell.kernel_check(
        before, ("flash_fwd", "flash_bwd"), "step")
    restart(m, start)
    del start

    fetched, routed, steps = [], [], 0
    # the warm-up steps' batches come from the mix's `weights_seed`, like
    # the weights (see `restart`): the first step, held to the reference,
    # and the window are --seed's
    warm_pool = batches(cell, sysm["weights_seed"])[:win["warm_steps"]]
    assert len(warm_pool) == win["warm_steps"], "pool under warm_steps"
    feed_from = itertools.chain(
        warm_pool, itertools.cycle(pool[1:] + pool[:1]))
    with overlap.prefetch_to_device(feed_from, m,
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
    sdar.record_rows(routed[-1])
    # a traced run's device metrics are of the traced steps: their rows.
    # The routers train on this chip's partial sum, so the rows drift
    # through the window: the mix names a stretch near the window's mean,
    # and the notes give both
    traced = routed[win["trace_from_step"]:
                    win["trace_from_step"] + win["trace_steps"]]
    if not len(traced):
        traced = routed
    mean_rows = (traced if cell.trace else routed).mean(0)
    per_step = flops_sdar.train_flops_per_step(args, B, S, mean_rows)
    kind = dev.jax_device.device_kind
    finite = [math.isfinite(x) for x in fetched]
    # the loss of a step is a sum over the positions its own mask hides,
    # weighted 1 / rate: it swings from batch to batch by more than it
    # falls in a few steps. The pool is cycled, so a fetched loss meets the
    # same batch under the same mask again `period` fetches later: the fall
    # is read over those pairs (over thirds of the window where it is too
    # short to hold one)
    period = math.lcm(len(pool), win["fetch_every"]) // win["fetch_every"]
    pairs = list(zip(fetched, fetched[period:]))
    k = max(1, len(fetched) // 3)
    falls = np.mean([b - a for a, b in pairs]) < 0 if pairs else \
        len(fetched) >= 2 and np.mean(fetched[-k:]) < np.mean(fetched[:k])
    checks = {
        **first_checks,
        "losses_finite": all(finite) and math.isfinite(first),
        "loss_falls": falls,
        "kernel_paths": kernels_ok,
        "no_compile_in_window": not compiled_inside,
    }
    # (steps, L); a layer that routes this chip nothing reads 0
    load = routed.max(-1) / np.maximum(routed.mean(-1), 1e-9)
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
            "rows_a_step": 2 * B * S,
            **first_notes,
            "loss_after_warm_up": warm, "losses_fetched": fetched,
            "loss_fall_on_the_same_batches": [b - a for a, b in pairs],
            "rows_routed_a_layer": {
                "mean": routed.sum(-1).mean(0).tolist(),
                "least": routed.sum(-1).min(0).tolist(),
                "most": routed.sum(-1).max(0).tolist(),
                "worst_case": 2 * B * S * min(args["experts_per_token"],
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
            "flops_per_step_by_part": flops_sdar.parts_per_step(
                args, B, S, mean_rows),
            "params_held": flops_sdar.params_held(args),
            "model_flops_utilization":
                tokens_per_s / (B * S) * per_step
                / flops.peak(kind, "bf16_flops")
                if kind in flops.PEAKS else None,
            **kernel_facts},
    }
