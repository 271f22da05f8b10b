"""Driver `train_lfm2_lm`: the graph-mode training step of the sparse model
of gated short convolutions and attention layers
(`models.create_model("lfm2")`) on one chip, fed from a cycled pool of
seeded batches through the device prefetcher: the window, fences,
prefetcher and compile mark of drivers/train_moe_lm.py, with this model,
its reference (reference_lfm2.py) and its FLOPs (flops_lfm2.py).

The step hands back the loss, the logits at 128 positions and, for each
layer, the rows routed to each expert this chip holds and the pairs sent to
each of ALL experts; it changes the parameters and, after the optimizer,
each sparse layer's selection bias (a state). `correct` holds the loss, the
logits and the pairs to the reference's forward on the first batch, the
change of EVERY parameter to the reference's gradient put through Adam's
first step (update_check.py) and the bias after the step to the
reference's: the backward pass through the convolutions' chain, the
sigmoid routing under the bias, the sort, the grouped products, the causal
kernels' three gradients at 16k, the rebuilt regions, the optimizer and the
bias's own update. The first step has to exercise the bias, which is zero on
a fresh model: `build` draws it from the mix's `weights_seed`
(`system.bias_std`: a checkpoint taken mid-training). What the limits are
worth is control_lfm2.py's to show, through this file's `compare`: the
wrong models (reference_lfm2.WRONG) and the reference in bfloat16
throughout. A timed run computes one reference forward and one gradient.
The compared step is then undone (`restart`) and the warm-up runs on the
mix's `weights_seed`'s batches, so that every seed's window starts from
one state.
"""

import itertools
import math
import time

import numpy as np

import flops
import flops_lfm2
import reference_lfm2 as reference
import traffic
import update_check
# what takes the model's arguments and nothing of the model: the positions
# sampled, the compiled step's bytes by the compiler's count
from drivers.train_moe_lm import sample_rows, step_memory


def initial_bias(sysm, args):
    """(L, E): the selection bias the cell starts from, N(0, bias_std^2)
    on the sparse layers by the mix's `weights_seed`, zeros on a dense
    layer's row."""
    L, E = len(args["layer_types"]), args["num_experts"]
    bias = np.zeros((L, E), np.float32)
    rng = np.random.default_rng([sysm["weights_seed"], 0xB1A5])
    d = args["num_dense_layers"]
    bias[d:] = rng.normal(0.0, sysm["bias_std"], (L - d, E))
    return bias


def reference_readings(params, bias, ids, tgt, args, lr, wrong=()):
    """What the reference says of the first batch on the initial weights
    and bias: ({"loss", "rows", "load", "bias", "sample"}, the same of
    each wrong model named, the parameters expected after the first
    step)."""
    rows = sample_rows(args, ids.size)
    ref = reference.loss_parts(params, bias, ids, tgt, args, rows=rows)
    # the expert the wrong model leaves out: the held one that the
    # reference routes most rows to (one that no token reaches could be
    # left out of any model unseen)
    busiest = int(np.argmax(ref["rows"].sum(0)))
    wrong = {name: reference.loss_parts(
        params, bias, ids, tgt, args, rows=rows, wrong=name, expert=busiest)
        for name in wrong}
    grads = reference.grads(params, bias, ids, tgt, args)
    return ref, wrong, update_check.Expected(params, grads, lr, 0.0)


def pairs_moved(load, ref_load):
    """(token, choice) pairs sent to another expert than the reference's:
    a pair that moves leaves one count and joins another."""
    return float(np.abs(np.asarray(load, np.float64) - ref_load).sum() / 2)


def bias_error(bias, ref_bias, before):
    """|got - expected| over |expected - before|, summed over the layers'
    entries: a bias left as it was reads 1, one moved the other way 2, an
    entry whose load lies within the pairs moved of the mean 2 / entries."""
    moved = np.abs(np.asarray(ref_bias) - before).sum()
    return float(np.abs(np.asarray(bias) - ref_bias).sum() / moved)


def logit_error(lg, ref_lg, outliers):
    """(RMS error of the sampled logits over the spread of the
    reference's, without the `outliers` positions that read furthest off;
    the same over every position). A token one of whose (token, choice)
    pairs the program sends to another expert than the reference reads
    far off on its own: `pairs_moved` counts those and has its own limit;
    the bulk is what a precision or a wrong formula moves."""
    lg, ref_lg = (np.asarray(a, np.float32) for a in (lg, ref_lg))
    sq = np.sort(np.mean((lg - ref_lg) ** 2, axis=-1))      # a position
    rms = lambda a: float(np.sqrt(np.mean(a)) / np.std(ref_lg))
    return rms(sq[:len(sq) - outliers]), rms(sq)


def compare(got, ref, wrong, chk, bias_before, held):
    """(checks, notes) of `got` = {"loss", "sample", "rows", "load",
    "bias", "update": update_check's summary of the first step} against
    the reference's readings, and of each wrong model in `wrong` (none in a
    timed run) against the limits: one of the logits', the loss's and the
    pairs' has to tell it. `held`: the slice of the experts this chip
    holds. The control that puts a lower-precision reference in the
    program's place goes through this same function."""
    err = lambda lg: logit_error(lg, ref["sample"], chk["logit_outliers"])
    off = lambda loss: abs(loss - ref["loss"]) / abs(ref["loss"])
    (logit_err, logit_err_all), rel = err(got["sample"]), off(got["loss"])
    moved = pairs_moved(got["load"], ref["load"])
    bias_err = bias_error(got["bias"], ref["bias"], bias_before)
    readings = {k: (*err(w["sample"]), off(w["loss"]),
                    pairs_moved(w["load"], ref["load"]))
                for k, w in wrong.items()}
    told = lambda bulk, _all, loss, pairs: bulk > chk["logit_rms_tol"] \
        or loss > chk["loss_rtol"] or pairs > chk["pairs_moved_tol"]
    checks = {
        "loss_equals_reference": rel <= chk["loss_rtol"],
        "logits_equal_reference": logit_err <= chk["logit_rms_tol"],
        "pairs_routed_equal_reference": moved <= chk["pairs_moved_tol"],
        # the rows the grouped products ran on are the held experts' part
        # of the load the bias is moved by
        "rows_are_the_held_experts_load": np.array_equal(
            np.asarray(got["rows"]), np.asarray(got["load"])[:, held]),
        "first_update_equals_reference":
            got["update"]["worst_leaf"] <= chk["update_tol"],
        "bias_after_step_equals_reference": bias_err <= chk["bias_tol"],
        **{"tolerance_tells_" + k: told(*v) for k, v in readings.items()},
    }
    notes = {
        "loss_first": got["loss"], "loss_reference": ref["loss"],
        "loss_rel_diff": rel, "logit_rms_error": logit_err,
        "logit_rms_error_every_position": logit_err_all,
        "load_first": np.asarray(got["load"]).tolist(),
        "load_reference": ref["load"].tolist(),
        "pairs_moved": moved, "bias_update_error": bias_err,
        "first_update_error": got["update"],
        **{f"{name}_{k}": r for k, v in readings.items() for name, r in zip(
            ("logit_rms_error", "logit_rms_error_every_position",
             "loss_rel_diff", "pairs_moved"), v)}}
    return checks, notes


def build(cell):
    """The model as the cell runs it, compiled, on its initial weights and
    bias: every weight by the program's own initialisers from the mix's
    `weights_seed`, the embedding then scaled to the mix's `embed_std`,
    the bias by `initial_bias`."""
    from singa_tpu import models, opt, tensor
    sysm = cell.system
    # the weights decide how many rows a step routes to this chip's experts,
    # at the start and as the routers train: no --seed changes them, so none
    # changes the amount of work (traffic.py's rule); --seed draws the ids
    cell.dev.SetRandSeed(sysm["weights_seed"])
    m = models.create_model("lfm2", recompute=sysm["recompute"],
                            **cell.model_args)
    m.set_optimizer(getattr(opt, sysm["optimizer"])(lr=sysm["lr"]))
    # the eager init pass needs only some input: keep it small
    m.compile([tensor.from_numpy(
        np.zeros((1, 128), np.int32), device=cell.dev)],
        is_train=True, use_graph=sysm["use_graph"], amp=sysm["amp"])
    # an embedding that outweighs the layers' outputs keeps the stream,
    # which `amp` holds in fp32, more exact than the bf16 products that
    # add to it (the Mellum mix's scaling; the reasons are this mix's)
    W = tensor.to_numpy(m.get_params()["tok_embed.W"])
    m.set_params({"tok_embed.W": W * (sysm["embed_std"] / W.std())})
    bias = initial_bias(sysm, cell.model_args)
    m.set_states({f"TransformerBlock_{i}.moe.b": bias[i]
                  for i in m.sparse_layers()})
    return m


def restart(m, start, bias):
    """Put the model back on the weights `start` ({name: host array}) and
    the bias (L, E), with a fresh optimizer state: the first step was
    --seed's, for the comparison with the reference, and the run goes on
    without it (drivers/train_blockdiff_lm.py's rule, and its reason: the
    routers train on this chip's partial sum, fast, and what they have
    learnt by the window's start decides how many rows every later step
    routes here; from one state, warmed up on the same batches, every
    seed's window starts alike: the mix's `check.reasons.weights_seed`)."""
    import jax.numpy as jnp
    m.set_params(start)
    m.set_states({f"TransformerBlock_{i}.moe.b": bias[i]
                  for i in m.sparse_layers()})
    m.optimizer.load_state_arrays(
        [jnp.zeros_like(a) for a in m.optimizer.state_arrays()])


def run(cell):
    from singa_tpu import overlap, tensor
    from singa_tpu.models import lfm2
    sysm, win, chk = cell.system, cell.window, cell.check
    args = cell.model_args
    dev = cell.dev
    pool = traffic.generate(cell.traffic, args["vocab_size"], None, cell.seed)
    B, S = pool[0][0].shape
    dense = args["num_dense_layers"]
    held = slice(args["expert_offset"],
                 args["expert_offset"] + args["experts_held"])

    before = cell.dispatch_counts()
    m = build(cell)

    # the reference on the first batch, on the initial weights (the step
    # donates and replaces them), before the step takes the memory
    ids0, tgt0 = pool[0]
    bias0 = m.router_bias()
    built = time.perf_counter()
    ref, wrong, expected = reference_readings(
        {k: v.data for k, v in m.get_params().items()}, bias0, ids0, tgt0,
        args, sysm["lr"])
    referred = time.perf_counter()

    start = {k: np.asarray(v.data) for k, v in m.get_params().items()}
    loss, sample, rows, load = m(tensor.from_numpy(ids0, device=dev),
                                 tensor.from_numpy(tgt0, device=dev))
    first = float(loss.numpy())
    got = {"loss": first, "sample": np.asarray(sample.data),
           "rows": np.asarray(rows.data), "load": np.asarray(load.data),
           "bias": m.router_bias(),
           "update": expected.error_of_step(
               {k: v.data for k, v in m.get_params().items()})}
    first_checks, first_notes = compare(got, ref, wrong, chk, bias0, held)
    del sample, got, ref, wrong, expected
    kernels_ok, kernel_facts = cell.kernel_check(
        before, ("flash_fwd", "flash_bwd"), "step")
    restart(m, start, bias0)
    del start

    fetched, counted, steps = [], [], 0
    # the warm-up steps' batches come from the mix's `weights_seed`, like
    # the weights (see `restart`): the first step, held to the reference,
    # and the window are --seed's
    warm_pool = traffic.generate(cell.traffic, args["vocab_size"], None,
                                 sysm["weights_seed"])
    batches = itertools.chain(
        itertools.islice(itertools.cycle(warm_pool), win["warm_steps"]),
        itertools.cycle(pool[1:] + pool[:1]))
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
            loss, _, rows, load = m(*next(feed))
            counted.append((rows, load))    # numbers a layer: read later
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
    # (steps, L, held) and (steps, L, E); a dense layer's row is zeros
    routed, loads = (np.stack([np.asarray(c[i].data) for c in counted])
                     for i in (0, 1))
    bias = m.router_bias()
    lfm2.record_rows(routed[-1], loads[-1], bias, dense)
    # a traced run's device metrics are of the traced steps: their rows
    traced = routed[win["trace_from_step"]:
                    win["trace_from_step"] + win["trace_steps"]]
    if not len(traced):
        traced = routed
    mean_rows = (traced if cell.trace else routed).mean(0)
    per_step = flops_lfm2.train_flops_per_step(args, B, S, mean_rows)
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
    # largest over mean, (steps, sparse layers): among the experts held
    # here, and among all of them (what the bias acts on)
    # (a layer that routes this chip nothing in some step reads 0)
    over_mean = lambda a: a[:, dense:].max(-1) / np.maximum(
        a[:, dense:].mean(-1), 1e-9)
    held_load, all_load = over_mean(routed), over_mean(loads)
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
                   "expert_load_imbalance": float(held_load.mean()),
                   "router_load_imbalance": float(all_load.mean()),
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
            "router_load_largest_over_mean": {
                "first_steps": all_load[:5].mean(0).tolist(),
                "last_steps": all_load[-5:].mean(0).tolist(),
                "mean": float(all_load.mean()), "most": float(all_load.max())},
            "expert_load_largest_over_mean": {
                "mean": float(held_load.mean()),
                "most": float(held_load.max())},
            "bias_ends_a_layer": [[float(b.min()), float(b.max())]
                                  for b in bias[dense:]],
            "allocator_peak_bytes": allocator_peak,
            "setup_parts_s": {"to_built": built - cell.t0,
                              "reference": referred - built,
                              "first_step_to_window": t0 - referred},
            "flops_per_step": per_step,
            "flops_per_step_by_part": flops_lfm2.parts_per_step(
                args, B, S, mean_rows),
            "params_held": flops_lfm2.params_held(args),
            "model_flops_utilization":
                tokens_per_s / (B * S) * per_step
                / flops.peak(kind, "bf16_flops")
                if kind in flops.PEAKS else None,
            **kernel_facts},
    }
