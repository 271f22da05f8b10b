"""What `train_mellum2_moe_8k`'s limits are worth, by hand on the chip:

    python3 benchmark/control_mellum.py --seed <n> [<n> ...]
        [--weights-seed <n>] [--workload <cell>]

puts the reference computed in bfloat16 throughout (weights, norms, rotary
tables, the router and its softmax's input, logits, the gradient) in the
program's place and sends it through the driver's own comparison
(drivers/train_moe_lm.py `compare`) against the fp32 reference: the nearest
precision below the one the configuration states, which has to come out as
NOT correct. The same comparison reads each wrong model of
reference_mellum.WRONG (`tolerance_tells_<name>`: the logits' limit has to
lie under it), which a timed run does not pay for. `--weights-seed` puts
another set of initial weights in the place of the mix's. Prints the checks
and the readings, one line a seed; no step of the program runs.
"""

import argparse
import json
import os
import sys

import run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="train_mellum2_moe_8k")
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
    import reference_mellum as reference
    import traffic
    import update_check
    cell = run.Cell(spec, seed, 0, False, dev or device.create_tpu_device())
    driver = run.load_module("drivers", cell.driver)
    args, chk, lr = cell.model_args, cell.check, cell.system["lr"]
    if weights_seed is not None:
        cell.system["weights_seed"] = weights_seed
    ids, tgt = traffic.generate(cell.traffic, args["vocab_size"], None,
                                cell.seed)[0]
    # the program's own initial weights, as the driver makes them
    m = driver.build(cell)
    params = {k: v.data for k, v in m.get_params().items()}
    ref, wrong, expected = driver.reference_readings(
        params, ids, tgt, args, lr, wrong=reference.WRONG)
    low = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
    got = reference.loss_parts(low, ids, tgt, args,
                               rows=driver.sample_rows(args, ids.size))
    grads = reference.grads(low, ids, tgt, args)
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
