"""What `train_sdar_blockdiff_4k`'s limits are worth, by hand on the chip:

    python3 benchmark/control_sdar.py --seed <n> [<n> ...]
        [--weights-seed <n>] [--workload <cell>]

puts (a) the reference computed in bfloat16 throughout (weights, norms,
rotary tables, the router and its softmax's input, logits, the gradient) in
the program's place and sends it through the driver's own comparison
(drivers/train_blockdiff_lm.py `compare`) against the fp32 reference: the
nearest precision below the one the configuration states, which has to come
out as NOT correct; and reads (b) each wrong model of reference_sdar.WRONG
(`tolerance_tells_<name>`: the logits' limit, or for a loss weighted wrongly
the loss's, has to lie under it), which a timed run does not pay for: noised
queries that also see the clean keys of their own block (the leak), the
plain causal mask over the doubled sequence, positions 0..2S-1, the norms on
q and k left out, the 1/t weight left out, the busiest held expert left out.
`--weights-seed` puts another set of initial weights in the place of the
mix's. Prints the checks and the readings, one line a seed; no step of the
program runs.
"""

import argparse
import json
import os
import sys

import run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="train_sdar_blockdiff_4k")
    ap.add_argument("--seed", type=int, nargs="+", default=[0])
    ap.add_argument("--weights-seed", type=int, default=None)
    a = ap.parse_args(argv)
    bench = run.load_json("BENCHMARK.json")
    spec = next(w for w in bench["workloads"] if w["name"] == a.workload)
    spec = dict(spec, config_file=next(
        c["file"] for c in bench["configs"] if c["name"] == spec["config"]))
    from singa_tpu import warmstart
    warmstart.configure_xla_cache(os.path.join(run.ROOT, ".jax_cache"))
    for seed in a.seed:
        print(json.dumps(control(spec, seed, weights_seed=a.weights_seed),
                         default=float), flush=True)


def control(spec, seed, dev=None, weights_seed=None):
    import jax.numpy as jnp
    import numpy as np
    from singa_tpu import device
    import reference_sdar as reference
    import update_check
    cell = run.Cell(spec, seed, 0, False, dev or device.create_tpu_device())
    driver = run.load_module("drivers", cell.driver)
    args, chk, lr = cell.model_args, cell.check, cell.system["lr"]
    if weights_seed is not None:
        cell.system["weights_seed"] = weights_seed
    batch = driver.batches(cell)[0]
    # the program's own initial weights, as the driver makes them
    m = driver.build(cell)
    params = {k: v.data for k, v in m.get_params().items()}
    ref, wrong, expected = driver.reference_readings(
        params, batch, args, lr, wrong=reference.WRONG)
    low = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
    got = reference.loss_parts(low, *batch, args,
                               rows=driver.sample_rows(args, batch[0].shape))
    grads = reference.grads(low, *batch, args)
    del low
    got["update"] = expected.error_of_step({
        k: params[k] + update_check.adam_first_step(
            grads[k].astype(jnp.float32), lr) for k in params})
    got["sample"] = np.asarray(got["sample"].astype(jnp.float32))
    checks, notes = driver.compare(got, ref, wrong, chk)
    return {"cell": cell.name, "seed": cell.seed,
            "weights_seed": cell.system["weights_seed"],
            "reference_in": "bfloat16",
            "correct": all(checks.values()), "checks": checks,
            "notes": notes}


if __name__ == "__main__":
    sys.exit(main())
