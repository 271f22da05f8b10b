"""Device time of the flash kernels at one shape, a band at a time.

    chiprun -- python3 tools/flash_bench.py [--shape 4,16,1024,64]
        [--dtype bfloat16] [--causal 1] [--bands 128,256,512,0]

For each band (0: a block worked whole; the plan's own choice is marked)
it runs `_flash_fwd_pallas` and the fused `_flash_bwd_pallas` 30 times
under the profiler and prints the median device time of the Mosaic call
and the largest difference from `attention_reference`. Times come from the
trace (benchmark/trace_reduce.py): by the host's clock a call cannot read
under its ~0.35 ms of dispatch. Exits 2 where no TPU is attached. The block
and band choices in `ops/attention.py::flash_plan` were set from this
(PERF.md section 6, PR 26).
"""

import argparse
import os
import shutil
import statistics
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402

import trace_reduce                                     # noqa: E402
from singa_tpu.ops import attention as A                # noqa: E402


def kernel_ms(fn, args, calls=30):
    """Median device ms of the events named like the kernel."""
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
    times = [e - s for n, s, e in next(iter(devices.values()))
             if "singa_flash" in n]
    return 1e3 * statistics.median(times) * (len(times) // calls), out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="4,16,1024,64")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--causal", type=int, default=1)
    ap.add_argument("--bands", default="128,256,512,0")
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("flash_bench: no TPU attached", file=sys.stderr)
        return 2
    shape = tuple(int(x) for x in a.shape.split(","))
    causal, dtype = bool(a.causal), jnp.dtype(a.dtype)
    s, d = shape[2], shape[3]
    scale = d ** -0.5
    rng = np.random.default_rng(0)
    q, k, v, do = (jnp.asarray(rng.standard_normal(shape), dtype)
                   for _ in range(4))
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    ref, vjp = jax.vjp(lambda *x: A.attention_reference(*x, causal), *f32)
    grads = vjp(do.astype(jnp.float32))
    plan = A.flash_plan(s, s, d, causal, dtype)
    err = lambda got, want: max(
        float(jnp.max(jnp.abs(g.astype(jnp.float32) - w)))
        for g, w in zip(got, want))
    print(f"{jax.devices()[0].device_kind} {shape} {dtype.name} "
          f"causal={causal}; plan: {plan}")
    for band in (int(b) for b in a.bands.split(",")):
        if band and plan.fwd.block_q % band:
            continue
        t = A.FlashTiles(plan.fwd.block_q, plan.fwd.block_k, band, 0, 0, 0)
        ms, (o, lse) = kernel_ms(lambda *x: A._flash_fwd_pallas(
            *x, causal, scale, t, False), (q, k, v))
        mark = " (plan)" if band == plan.fwd.band else ""
        print(f"forward  band {band:4d}: {ms:.4f} ms a call, "
              f"max |diff| {err([o], [ref]):.4f}{mark}")
        try:
            ms, g = kernel_ms(lambda *x: A._flash_bwd_pallas(
                *x, causal, scale, t, True, False), (q, k, v, o, lse, do))
        except Exception as e:      # a whole 1024-block overflows VMEM
            print(f"backward band {band:4d}: does not compile "
                  f"({str(e).splitlines()[0][:80]})")
            continue
        mark = " (plan)" if plan.bwd and band == plan.bwd.band else ""
        print(f"backward band {band:4d}: {ms:.4f} ms a call, "
              f"max |diff| {err(g, grads):.4f}{mark}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
