"""Device time of the flash kernels at one shape, a band at a time.

    chiprun -- python3 tools/flash_bench.py [--shape 4,16,1024,64]
        [--dtype bfloat16] [--causal 1] [--bands 128,256,512,0]
    chiprun -- python3 tools/flash_bench.py --shape 1,32,8192,128
        --block-diffusion 4

For each band (0: a block worked whole; the plan's own choice is marked)
it runs `_flash_fwd_pallas` and the fused `_flash_bwd_pallas` 30 times
under the profiler and prints the median device time of the Mosaic call
and the largest difference from `attention_reference`. Times come from the
trace (benchmark/trace_reduce.py): by the host's clock a call cannot read
under its ~0.35 ms of dispatch. Exits 2 where no TPU is attached. The block
and band choices in `ops/attention.py::flash_plan` were set from this
(PERF.md section 6, PR 26).

With `--block-diffusion <b>` (the shape's sequence is the doubled one) it
prints, at the plan's own schedule, the forward and the split backward
(dq + dkv) under the block-diffusion mask, the causal mask and no mask: ms
a call, the grid steps a row and those that work no tile, and ns a visited
sub-tile (the plan's count, in the pass's own bands), which is what a mask
that skips tiles should leave as it is (PERF.md section 6, PR 37).
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
    """Device ms a call of `fn` in the events named like the kernels: the
    median of each kernel's events, summed over the kernels."""
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
    times = {}
    for n, s, e in next(iter(devices.values())):
        if "singa_flash" in n:
            times.setdefault(n, []).append(e - s)
    return 1e3 * sum(statistics.median(t) * (len(t) // calls)
                     for t in times.values()), out


def block_diffusion_table(A, shape, dtype, block, heads=2, calls=30):
    """Rows (mask, pass, ms a call, grid steps a row, idle steps, visited
    sub-tiles a row, max |diff| from the reference on the first `heads`
    heads) for module `A`'s kernels at the plan's schedule."""
    s, d = shape[2], shape[3]
    scale = d ** -0.5
    rng = np.random.default_rng(0)
    q, k, v, do = (jnp.asarray(rng.standard_normal(shape), dtype)
                   for _ in range(4))
    some = lambda x: x[:, :heads].astype(jnp.float32)
    err = lambda got, want: max(
        float(jnp.max(jnp.abs(some(g) - w))) for g, w in zip(got, want))
    rows = []
    for mask, causal, bd in (("block_diffusion", False, block),
                             ("causal", True, None), ("none", False, None)):
        ref, vjp = jax.vjp(lambda *x: A.attention_reference(
            *x, causal, None, None, bd), some(q), some(k), some(v))
        grads = vjp(some(do))
        plan = A.flash_plan(s, s, d, causal, dtype, block_diffusion=bd)
        ms, (o, lse) = kernel_ms(lambda *x: A._flash_fwd_pallas(
            *x, causal, scale, plan.fwd, False, None, bd), (q, k, v), calls)
        steps = getattr(plan, "steps", ((0, 0), (0, 0)))
        rows.append((mask, "forward", ms, *steps[0], plan.fwd.visited,
                     err([o], [ref])))
        ms, g = kernel_ms(lambda *x: A._flash_bwd_pallas(
            *x, causal, scale, plan.bwd, 0, False, None, None, bd),
            (q, k, v, o, lse, do), calls)
        rows.append((mask, "dq + dkv", ms, *steps[1], plan.bwd.visited,
                     err(g, grads)))
    return rows


def print_block_diffusion(A, shape, dtype, block):
    bh = shape[0] * shape[1]
    rows = block_diffusion_table(A, shape, dtype, block)
    whole = {p: ms / n for m, p, ms, _, _, n, _ in rows if m == "none"}
    for mask, what, ms, steps, idle, visited, diff in rows:
        print(f"{mask:16s} {what:9s} {ms:8.4f} ms a call, steps {steps:4d} "
              f"idle {idle:3d}, {visited:5d} sub-tiles a row, "
              f"{1e6 * ms / bh / visited:7.1f} ns a sub-tile "
              f"({ms / visited / whole[what]:.3f} of the unmasked pass's), "
              f"max |diff| {diff:.4f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="4,16,1024,64")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--causal", type=int, default=1)
    ap.add_argument("--bands", default="128,256,512,0")
    ap.add_argument("--block-diffusion", type=int, default=None)
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("flash_bench: no TPU attached", file=sys.stderr)
        return 2
    shape = tuple(int(x) for x in a.shape.split(","))
    causal, dtype = bool(a.causal), jnp.dtype(a.dtype)
    if a.block_diffusion is not None:
        print(f"{jax.devices()[0].device_kind} {shape} {dtype.name} "
              f"block_diffusion={a.block_diffusion}")
        print_block_diffusion(A, shape, dtype, a.block_diffusion)
        return 0
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
                *x, causal, scale, t, s, False), (q, k, v, o, lse, do))
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
