"""Driver `train_dp`: drivers/train.py's training step data-parallel over
the cell's chips: `opt.DistOpt(<optimizer>, mesh=data_parallel_mesh(chips))`
in its default strategy (plain all-reduce of every gradient, no
compression, no sparsification), the global batch split over the mesh by
the model's own input sharding.

train.py's `run` is one function and may not be edited, so its loop is
repeated here. What differs: the optimizer; the reference's loss over the
global batch is taken a chip's rows at a time, so that it fits beside the
replica; `train_tokens_per_s` counts the global batch; and three checks
more, of what exists only across chips. The prefetcher's input shards are
(batch / chips, seq) on each chip. The first step's change of every
parameter equals the reference's gradient OVER THE GLOBAL BATCH put through
Adam's first step (update_check.py), and the limit tells the update one
chip's share of the batch alone would give: what a step without its
all-reduce applies. After the warm steps every chip holds the same
parameters, bit for bit.
"""

import itertools
import math
import time

import numpy as np

import flops
import reference
import reference_grad
import traffic
import update_check


def shard_on(array, device):
    """The part of a (replicated) array that `device` holds."""
    return next(s.data for s in array.addressable_shards
                if s.device == device)


def replicas_differ(params, home):
    """The names of the parameters that some chip holds otherwise than
    `home` does (and of those not held once a chip)."""
    import jax
    import jax.numpy as jnp
    out = []
    for k, a in params.items():
        base = shard_on(a, home)
        if len({s.device for s in a.addressable_shards}) \
                != len(a.sharding.device_set) or not all(
                bool(jnp.array_equal(jax.device_put(s.data, home), base))
                for s in a.addressable_shards if s.device != home):
            out.append(k)
    return out


def run(cell):
    from singa_tpu import models, opt, overlap, tensor
    from singa_tpu.parallel import data_parallel_mesh
    sysm, win, chk = cell.system, cell.window, cell.check
    args = cell.model_args
    dev = cell.dev
    dev.SetRandSeed(cell.seed31)
    pool = traffic.generate(cell.traffic, args["vocab_size"],
                            args["max_seq"], cell.seed)
    B, S = pool[0][0].shape
    n = sysm["chips"]
    rows = B // n

    before = cell.dispatch_counts()
    m = models.create_model("gpt", **args)
    m.set_optimizer(opt.DistOpt(
        getattr(opt, sysm["optimizer"])(lr=sysm["lr"]),
        mesh=data_parallel_mesh(n)))
    # the eager init pass needs only some input: keep it small
    m.compile([tensor.from_numpy(pool[0][0][:1, :128], device=dev)],
              is_train=True, use_graph=sysm["use_graph"], amp=sysm["amp"])

    # the reference on the first batch, on the initial weights: its loss (a
    # chip's rows at a time: every part has as many positions, so the mean
    # of the parts' means is the batch's), its logits for the first
    # sequence, and those with the deepest block left out
    params = {k: v.data for k, v in m.get_params().items()}
    ids0, tgt0 = pool[0]
    H = args["num_heads"]
    ref = float(np.mean([
        reference.loss(params, ids0[i:i + rows], tgt0[i:i + rows], H)
        for i in range(0, B, rows)]))
    ref_lg = np.asarray(reference.logits(params, ids0[:1], H)[0])
    skip_lg = np.asarray(reference.logits(params, ids0[:1], H,
                                          drop_last_blocks=1)[0])
    # the reference's gradient over the global batch, and over the first
    # chip's rows alone (each a mean over its own positions)
    g_share = reference_grad.grads(params, ids0[:rows], tgt0[:rows], H)
    g_rest = reference_grad.grads(params, ids0[rows:], tgt0[rows:], H)
    g = {k: (rows * g_share[k] + (B - rows) * g_rest.pop(k)) / B
         for k in list(g_rest)}
    expected = update_check.Expected(params, g, sysm["lr"],
                                     chk["update_floor"])
    share_err = expected.error_of_gradient(g, g_share)
    del params, g, g_share

    out, loss = m(tensor.from_numpy(ids0, device=dev),
                  tensor.from_numpy(tgt0, device=dev))
    first = float(loss.numpy())
    home = dev.jax_device
    update_err = expected.error_of_step(
        {k: shard_on(v.data, home) for k, v in m.get_params().items()})
    del expected
    err = lambda lg: float(np.sqrt(np.mean((lg - ref_lg) ** 2))
                           / np.std(ref_lg))
    logit_err, skip_err = err(np.asarray(out.data[0])), err(skip_lg)
    del out, ref_lg, skip_lg
    kernels_ok, kernel_facts = cell.kernel_check(
        before, ("flash_fwd", "flash_bwd"), "step")

    fetched, steps, shards = [], 0, {}
    batches = itertools.cycle(pool[1:] + pool[:1])
    with overlap.prefetch_to_device(batches, m,
                                    size=sysm["prefetch"]) as feed:
        for _ in range(win["warm_steps"]):
            xb, yb = next(feed)
            loss = m(xb, yb)[1]          # the logits are dropped at once
        shards = {s.device.id: tuple(s.data.shape)
                  for s in xb.data.addressable_shards}
        warm = float(loss.numpy())                       # fence
        differ = replicas_differ(
            {k: v.data for k, v in m.get_params().items()}, home)
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
        "input_shards_on_every_chip":
            len(shards) == n and set(shards.values()) == {(rows, S)},
        "first_update_equals_reference":
            update_err["worst_leaf"] <= chk["update_tol"],
        "tolerance_tells_one_chip_s_share_of_the_batch":
            share_err["worst_leaf"] > chk["update_tol"],
        "params_equal_on_every_chip": not differ,
    }
    return {
        "checks": {k: bool(v) for k, v in checks.items()}, "attempted": steps,
        "failed": finite.count(False) * win["fetch_every"],
        "memory_peak_bytes": peak,
        "values": {"train_tokens_per_s": tokens_per_s,
                   "setup_s": t0 - cell.t0,
                   "step_ms": 1e3 * window / steps,
                   "hbm_peak_gb": peak / 1e9 or None,
                   "flash_shape": [rows, args["num_heads"], S,
                                   args["dim"] // args["num_heads"]],
                   "device_kind": kind},
        "notes": {
            "window_s": window, "steps": steps, "batch": [B, S],
            "input_shards": {str(d): list(s) for d, s in shards.items()},
            "loss_first": first, "loss_reference": ref,
            "loss_rel_diff": rel, "logit_rms_error": logit_err,
            "logit_rms_error_skipping_a_block": skip_err,
            "first_update_error": update_err,
            "update_error_of_one_chip_s_share": share_err,
            "params_that_differ_between_chips": differ[:8],
            "loss_after_warm_up": warm, "losses_fetched": fetched,
            "flops_per_token": fpt, "params_held": flops.gpt2_params_held(args),
            "model_flops_utilization":
                tokens_per_s * fpt / (n * flops.peak(kind, "bf16_flops"))
                if kind in flops.PEAKS else None,
            **kernel_facts},
    }
