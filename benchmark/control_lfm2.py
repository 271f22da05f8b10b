"""What `train_lfm2_conv_moe_16k`'s limits are worth, by hand on the chip:

    python3 benchmark/control_lfm2.py --seed <n> [<n> ...]
        [--weights-seed <n>] [--workload <cell>]

puts the reference computed in bfloat16 throughout (weights, norms, rotary
tables, the convolutions' chain, the router and its sigmoid's input, the
bias, logits, the gradient) in the program's place and sends it through the
driver's own comparison (drivers/train_lfm2_lm.py `compare`) against the
fp32 reference: the nearest precision below the one the configuration
states, which has to come out as NOT correct. The same comparison reads
each wrong model of reference_lfm2.WRONG (`tolerance_tells_<name>`: one of
the logits', the loss's and the pairs' limits has to lie under it), which a
timed run does not pay for. `--weights-seed` puts another set of initial
weights and bias in the place of the mix's. Prints the checks and the
readings, one line a seed; no step of the program runs.
"""

import argparse
import json
import os
import sys

import run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="train_lfm2_conv_moe_16k")
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
    import reference_lfm2 as reference
    import traffic
    import update_check
    cell = run.Cell(spec, seed, 0, False, dev or device.create_tpu_device())
    driver = run.load_module("drivers", cell.driver)
    args, chk, lr = cell.model_args, cell.check, cell.system["lr"]
    if weights_seed is not None:
        cell.system["weights_seed"] = weights_seed
    ids, tgt = traffic.generate(cell.traffic, args["vocab_size"], None,
                                cell.seed)[0]
    # the program's own initial weights and bias, as the driver makes them
    m = driver.build(cell)
    params = {k: v.data for k, v in m.get_params().items()}
    bias = m.router_bias()
    ref, wrong, expected = driver.reference_readings(
        params, bias, ids, tgt, args, lr, wrong=reference.WRONG)
    low = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
    low_bias = jnp.asarray(bias, jnp.bfloat16)
    got = reference.loss_parts(low, low_bias, ids, tgt, args,
                               rows=driver.sample_rows(args, ids.size))
    grads = reference.grads(low, low_bias, ids, tgt, args)
    del low
    got["update"] = expected.error_of_step({
        k: params[k] + update_check.adam_first_step(
            grads[k].astype(jnp.float32), lr) for k in params})
    got["sample"] = np.asarray(got["sample"].astype(jnp.float32))
    held = slice(args["expert_offset"],
                 args["expert_offset"] + args["experts_held"])
    checks, notes = driver.compare(got, ref, wrong, chk, bias, held)
    return {"cell": cell.name, "seed": cell.seed,
            "weights_seed": cell.system["weights_seed"],
            "reference_in": "bfloat16",
            "correct": all(checks.values()), "checks": checks,
            "notes": notes}


if __name__ == "__main__":
    sys.exit(main())
