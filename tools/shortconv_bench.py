"""Device time of a short convolution's chain alone, on both paths.

    chiprun -- python3 tools/shortconv_bench.py [--shape 1,16384,2048]
        [--dtype bfloat16] [--taps 3] [--tiles 512,256,512 ...]

For the `jnp` chain (`ops.shortconv._gated_taps_xla`, XLA's fusions) and
the Pallas kernels at the plan's tiles (and at each `--tiles rows_fwd,
rows_bwd,cols` given) it runs the forward and the forward + backward 30
times under the profiler and prints ms a call (the device's busy time in
the trace over the calls: by the host's clock a call cannot read under its
~0.35 ms of dispatch), the share of `benchmark/flops_lfm2.mix_cost`'s least
time (bytes over 819 GB/s) and the largest difference from the `jnp`
chain's results, relative to their largest magnitude. Exits 2 where no TPU
is attached. The kernels' tiles (`ops.shortconv.shortconv_plan`) were set
from this (PERF.md section 6, PR 39).
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

import flops                                            # noqa: E402
import flops_lfm2                                       # noqa: E402
import trace_reduce                                     # noqa: E402
from singa_tpu.ops import shortconv as SC               # noqa: E402


def device_ms(fn, args, calls=30):
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


def rows(chain, bcu, w, dy):
    """((forward ms, result), (forward + backward ms, results)) of one
    chain (bcu, w) -> y."""
    def both(a, b, g):
        y, vjp = jax.vjp(chain, a, b)
        return (y, *vjp(g))
    return device_ms(chain, (bcu, w)), device_ms(both, (bcu, w, dy))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="1,16384,2048")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--taps", type=int, default=3)
    ap.add_argument("--tiles", nargs="*", default=[])
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("shortconv_bench: no TPU attached", file=sys.stderr)
        return 2
    B, S, D = (int(x) for x in a.shape.split(","))
    dtype = jnp.dtype(a.dtype)
    rng = np.random.default_rng(0)
    bcu = jnp.asarray(rng.standard_normal((B, S, 3 * D)), dtype)
    dy = jnp.asarray(rng.standard_normal((B, S, D)), dtype)
    w = jnp.asarray(rng.uniform(-1, 1, (D, a.taps)) * a.taps ** -0.5,
                    jnp.float32)
    kind = jax.devices()[0].device_kind
    cfg = {"dim": D, "conv_taps": a.taps}
    least = [1e3 * flops.roofline_seconds(*flops_lfm2.mix_cost(
        cfg, B, S, bwd, dtype.itemsize), kind)[0] for bwd in (False, True)]
    least[1] += least[0]
    plan = SC.shortconv_plan(S, D, a.taps)
    print(f"{kind} ({B}, {S}, {D}) {dtype.name} taps={a.taps}; plan {plan}; "
          f"least ms: forward {least[0]:.4f}, forward + backward "
          f"{least[1]:.4f}")
    (fx, want), (bx, wants) = rows(SC._gated_taps_xla, bcu, w, dy)
    print(f"xla              forward {fx:8.4f} ms ({100 * least[0] / fx:5.1f} "
          f"%)  forward + backward {bx:8.4f} ms ({100 * least[1] / bx:5.1f} %)")
    tiles = [plan] + [SC.ConvTiles(*(int(x) for x in t.split(",")))
                      for t in a.tiles]
    diff = lambda got, ref: float(
        jnp.max(jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32)))
        / jnp.max(jnp.abs(ref.astype(jnp.float32))))
    for t in tiles:
        chain = lambda x, v: SC._gated_taps_kernel(x, v, t, False)
        try:
            (fk, got), (bk, gots) = rows(chain, bcu, w, dy)
        except Exception as e:      # tiles past the scoped VMEM
            print(f"kernel {tuple(t)}: does not compile "
                  f"({str(e).splitlines()[0][:80]})")
            continue
        err = max([diff(got, want)] + [diff(g, r) for g, r in
                                       zip(gots, wants)])
        mark = " (plan)" if t == plan and t is tiles[0] else ""
        print(f"kernel {str(tuple(t)):17s} forward {fk:8.4f} ms "
              f"({100 * least[0] / fk:5.1f} %)  forward + backward {bk:8.4f}"
              f" ms ({100 * least[1] / bk:5.1f} %)  max rel diff "
              f"{err:.2e}{mark}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
