"""Device time and error of a chunked gated delta rule alone, on both paths.

    python3 tools/delta_bench.py [--shape 1,4096,30,96,192]  # one TPU chip
        [--chunk 64] [--precision default highest] [--heads 1 3 6]

For the XLA form (`ops.gated_delta._rule_xla`: batched fusions, a
triangular solve, a scan over the chunks and autodiff's backward) and the
Pallas kernels (`singa_delta_fwd` / `singa_delta_bwd`) with their products
at each `--precision` given (the triangular solve's at HIGHEST always) and
at most each `--heads` an iteration of their loop over the heads
(`ops.gated_delta._HEADS`) it
runs the forward and the forward + backward 20 times under the profiler
and prints ms a call (the device's busy time in the trace over the calls),
the share of `benchmark/flops_olmo_hybrid.delta_cost`'s least time, and
the largest difference of o and of each input's gradient from the XLA
form computed with every product at HIGHEST, relative to the largest
magnitude of each. Inputs as the layer makes them: q and k L2-normalised,
beta in (0, 2), g = log alpha in (-1.5, 0). Exits 2 where no TPU is
attached.
"""

import argparse
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402
from jax import lax                                     # noqa: E402

import flops                                            # noqa: E402
import flops_olmo_hybrid                                # noqa: E402
import trace_reduce                                     # noqa: E402
from singa_tpu.ops import gated_delta as G              # noqa: E402


def device_ms(fn, args, calls=20):
    """(device ms a call of the jitted `fn`, its last result): busy time
    in the trace of `calls` calls after a warm one, over the calls."""
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    out_dir = tempfile.mkdtemp()
    jax.profiler.start_trace(out_dir)
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    devices, _, _ = trace_reduce.load(trace_reduce.find_xplane(out_dir))
    shutil.rmtree(out_dir, ignore_errors=True)
    busy = trace_reduce.union_seconds(next(iter(devices.values())))
    return 1e3 * busy / calls, out


def inputs(B, S, H, dk, dv, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q = G.l2_normalize(mk(B, S, H, dk)) * dk ** -0.5
    k = G.l2_normalize(mk(B, S, H, dk))
    g = -jnp.asarray(rng.uniform(0.0, 1.5, (B, S, H)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 2.0, (B, S, H)), jnp.float32)
    return (q, k, mk(B, S, H, dv), g, beta), mk(B, S, H, dv)


def rows(rule, args, do):
    """((forward ms, o), (forward + backward ms, (o, five gradients)))."""
    def both(*a):
        o, vjp = jax.vjp(rule, *a[:5])
        return (o, *vjp(a[5]))
    return device_ms(rule, args), device_ms(both, (*args, do))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="1,4096,30,96,192")
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--precision", nargs="*", default=["default", "highest"])
    ap.add_argument("--heads", nargs="*", type=int, default=[1, 3, 6])
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("delta_bench: no TPU attached", file=sys.stderr)
        return 2
    B, S, H, dk, dv = (int(x) for x in a.shape.split(","))
    C = a.chunk
    args, do = inputs(B, S, H, dk, dv)
    kind = jax.devices()[0].device_kind
    least = [1e3 * flops.roofline_seconds(*flops_olmo_hybrid.delta_cost(
        B, S, H, dk, dv, C, bwd), kind)[0] for bwd in (False, True)]
    least[1] += least[0]
    print(f"{kind} (B, S, H, dk, dv) = ({B}, {S}, {H}, {dk}, {dv}), chunks "
          f"of {C}; plan: {G.delta_rule_plan(H, C, dk, dv)}; least ms: "
          f"forward {least[0]:.4f}, forward + backward {least[1]:.4f}")

    plan = G.delta_rule_plan
    G.delta_rule_plan = lambda *_: None
    xla = lambda *x: G.chunked_delta_rule(*x, chunk=C)
    with jax.default_matmul_precision("highest"):
        _, want = device_ms(
            lambda *x: (lambda o, vjp: (o, *vjp(x[5])))(
                *jax.vjp(xla, *x[:5])), (*args, do), calls=1)
    diff = lambda got, ref: float(jnp.max(jnp.abs(got - ref))
                                  / jnp.max(jnp.abs(ref)))
    names = ("o", "dq", "dk", "dv", "dg", "dbeta")

    def show(label, f, b):
        (fm, _), (bm, got) = f, b
        errs = "  ".join(f"{n} {diff(x, y):.1e}"
                         for n, x, y in zip(names, got, want))
        print(f"{label:18s} forward {fm:8.4f} ms ({100 * least[0] / fm:5.1f}"
              f" %)  forward + backward {bm:8.4f} ms ({100 * least[1] / bm:5.1f}"
              f" %)  rel err: {errs}")

    show("xla (default)", *rows(xla, args, do))
    G.delta_rule_plan = plan
    products, most = G._PRODUCTS, G._HEADS
    for p in a.precision:
        for h in a.heads:
            G._PRODUCTS, G._HEADS = getattr(lax.Precision, p.upper()), h
            # a new function each time: jit's cache is keyed by the function
            show(f"kernel ({p}, {G.delta_rule_plan(H, C, dk, dv).heads})",
                 *rows(lambda *x: G.chunked_delta_rule(*x, chunk=C), args,
                       do))
    G._PRODUCTS, G._HEADS = products, most
    return 0


if __name__ == "__main__":
    sys.exit(main())
