"""chip_smoke.py — the quickest proof that the main path still starts on
the chip.

One process, public API only, seeded random weights, no network. With no
arguments it needs one TPU chip and runs three phases at published width:

  train_gpt       GPT-2-small (12 x d768, 12 heads, vocab 50257), batch 8 x
                  seq 1024, amp bf16, a few graph steps on a fixed batch
  train_resnet50  ResNet-50, batch 32 @ 224, amp bf16, a few graph steps
  serve_gpt       the same GPT-2-small in bf16 behind ServingEngine, a
                  handful of seeded prompts of mixed length

With `--chips 4` it needs exactly four chips and runs only `train_dp4`:
the GPT-2-small step data-parallel over a 4-chip mesh against its
single-chip twin.

Every phase prints one JSON line when it finishes. A failed check raises:
the traceback prints, the exit code is non-zero and no result line is
printed. The last line of a passing run is exactly
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

GPT2_SMALL = dict(vocab_size=50257, max_seq=1024, dim=768, num_heads=12,
                  num_layers=12)
TRAIN_GPT = dict(batch=8, seq=1024, steps=5, lr=0.03)
TRAIN_RESNET = dict(batch=32, size=224, steps=5, lr=0.001)
# page_size % 8 == 0 and head-packed P*D = 2*64 = 128 lanes satisfy the
# compiled paged kernel's alignment gate (ops.attention.paged_attention)
SERVE = dict(max_slots=8, page_size=16, max_ctx=1024,
             prompt_buckets=(64, 256, 512),
             prompt_lens=(17, 60, 130, 250, 300, 500),
             max_new=(32, 40, 48, 64, 36, 56))
# amp bf16 on both sides: per-shard batches of 2 and the whole batch of 8
# round differently, so the losses agree closely, not bitwise (1e-5
# relative on the chip in PR 21)
TRAIN_DP4 = dict(chips=4, batch=8, seq=1024, steps=3, lr=0.03, rtol=1e-3)
SEED = 0


class SmokeFailure(Exception):
    """A check on a phase's result did not hold."""


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


def emit(rec):
    print(json.dumps(rec), flush=True)


def on_devices(arr):
    return {d.id for d in arr.devices()}


def run_phase(name, fn, *args):
    """Run one phase and print its line: wall seconds, the seconds its
    staged builds took (from introspect: trace and lower are Python and
    paid every run, compile is what a warm cache saves), and whatever
    the phase checked."""
    from singa_tpu import introspect
    c0 = introspect.compile_phase_totals()
    t0 = time.perf_counter()
    checked = fn(*args)
    seconds = time.perf_counter() - t0
    built = {k: round(v - c0[k], 3)
             for k, v in introspect.compile_phase_totals().items()}
    emit({"phase": name, "seconds": round(seconds, 3),
          "compile_seconds": round(sum(built.values()), 3),
          "compile_phases": built, "checked": checked})


def dispatch_counts():
    """{(site, path): traced call sites} from the attention dispatch
    counter — which implementation each attention call site took."""
    from singa_tpu import observe
    c = observe.get_registry().get("singa_attention_dispatch_total")
    return {(site, path): int(c.value(site=site, path=path))
            for site in observe.ATTN_SITES
            for path in observe.ATTN_PATHS} if c is not None else {}


def compiled_text(key):
    """The compiled HLO text of the newest executable built under `key`
    (introspect.capture_hlo is on for the whole run)."""
    from singa_tpu import introspect
    rec = introspect.last_build(key)
    check(rec is not None and rec.get("hlo_path"),
          f"no staged build recorded for {key!r}")
    with open(rec["hlo_path"], encoding="utf-8") as f:
        return f.read()


def check_kernels(before, sites, key, at_least):
    """No silent reference path: since `before`, every attention call
    site in `sites` was traced onto its compiled Pallas kernel and none
    onto anything else, and the executable built under `key` holds at
    least `at_least` Mosaic custom calls."""
    delta = {k: v - before.get(k, 0) for k, v in dispatch_counts().items()
             if v - before.get(k, 0)}
    for site in sites:
        check(delta.get((site, "kernel"), 0) > 0,
              f"no compiled {site} kernel was traced: {delta}")
    off = {f"{s}/{p}": n for (s, p), n in delta.items() if p != "kernel"}
    check(not off, f"attention fell back from the kernel: {off}")
    n = compiled_text(key).count("tpu_custom_call")
    check(n >= at_least, f"{n} tpu_custom_call in the compiled {key}")
    return {"attention_paths": {f"{s}/{p}": n for (s, p), n
                                in sorted(delta.items())},
            f"tpu_custom_calls_in_{key}": n}


def gpt_batch(cfg, dev):
    import numpy as np
    from singa_tpu import tensor
    rng = np.random.RandomState(SEED)
    ids = rng.randint(0, GPT2_SMALL["vocab_size"],
                      (cfg["batch"], cfg["seq"])).astype(np.int32)
    tgt = np.roll(ids, -1, axis=1).astype(np.int32)
    return (tensor.from_numpy(ids, device=dev),
            tensor.from_numpy(tgt, device=dev))


def train_losses(m, tx, ty, steps):
    """`steps` graph-mode steps on one fixed batch; each loss is fetched,
    which fences the step."""
    import numpy as np
    losses = []
    for _ in range(steps):
        _out, loss = m(tx, ty)
        losses.append(float(loss.numpy()))
    check(bool(np.isfinite(losses).all()), f"non-finite loss: {losses}")
    return losses, loss


def check_on_device(m, loss, dev):
    want = {dev.jax_device.id}
    check(on_devices(loss.data) == want,
          f"loss on {loss.data.devices()}, expected {dev.jax_device}")
    for name, p in m.get_params().items():
        check(on_devices(p.data) == want,
              f"param {name} on {p.data.devices()}")


def train_gpt(dev, cfg=TRAIN_GPT):
    from singa_tpu import models, opt
    dev.SetRandSeed(SEED)
    before = dispatch_counts()
    m = models.create_model("gpt", **GPT2_SMALL)
    m.set_optimizer(opt.SGD(lr=cfg["lr"], momentum=0.9))
    tx, ty = gpt_batch(cfg, dev)
    m.compile([tx], is_train=True, use_graph=True, amp="bfloat16")
    losses, loss = train_losses(m, tx, ty, cfg["steps"])
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check_on_device(m, loss, dev)
    # one forward kernel and at least one backward kernel per layer
    kernels = check_kernels(before, ("flash_fwd", "flash_bwd"), "step",
                            2 * GPT2_SMALL["num_layers"])
    return {"losses": losses, "params_and_loss_on": str(dev.jax_device),
            **kernels}


def train_resnet50(dev, cfg=TRAIN_RESNET):
    import numpy as np
    from singa_tpu import models, opt, tensor
    dev.SetRandSeed(SEED)
    rng = np.random.RandomState(SEED)
    b, s = cfg["batch"], cfg["size"]
    tx = tensor.from_numpy(
        rng.standard_normal((b, 3, s, s)).astype(np.float32), device=dev)
    ty = tensor.from_numpy(rng.randint(0, 10, b).astype(np.int32),
                           device=dev)
    m = models.create_model("resnet50", num_channels=3)
    m.set_optimizer(opt.SGD(lr=cfg["lr"], momentum=0.9))
    m.compile([tx], is_train=True, use_graph=True, amp="bfloat16")
    losses, loss = train_losses(m, tx, ty, cfg["steps"])
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check_on_device(m, loss, dev)
    return {"losses": losses, "params_and_loss_on": str(dev.jax_device)}


def serve_requests(m, prompts, cfg, use_kernel):
    """Drive one engine through start/submit/wait/stop; every request
    must finish "completed" with the asked number of tokens."""
    from singa_tpu import engine
    max_new = cfg["max_new"]
    eng = engine.ServingEngine(
        m, max_slots=cfg["max_slots"], page_size=cfg["page_size"],
        max_ctx=cfg["max_ctx"], prompt_buckets=cfg["prompt_buckets"],
        dtype="bfloat16", use_kernel=use_kernel)
    eng.start()
    try:
        reqs = [eng.submit(p, n) for p, n in zip(prompts, max_new)]
        for r in reqs:
            check(r.wait(600.0), f"request {r.id} still in flight")
    finally:
        eng.stop()
    for r, n in zip(reqs, max_new):
        check(r.outcome == engine.OUTCOME_COMPLETED and len(r.tokens) == n,
              f"request {r.id}: {r.outcome} ({r.detail}), "
              f"{len(r.tokens)}/{n} tokens")
    return [list(r.tokens) for r in reqs]


def serve_gpt(dev, cfg=SERVE):
    import numpy as np
    from singa_tpu import models, tensor
    dev.SetRandSeed(SEED)
    rng = np.random.RandomState(SEED)
    V = GPT2_SMALL["vocab_size"]
    prompts = [rng.randint(0, V, n).astype(np.int32)
               for n in cfg["prompt_lens"]]
    m = models.create_model("gpt", **GPT2_SMALL)
    m.compile([tensor.from_numpy(prompts[0][None], device=dev)],
              is_train=False, use_graph=False)
    m.eval()

    before = dispatch_counts()
    streams = serve_requests(m, prompts, cfg, use_kernel=None)
    kernels = check_kernels(before, ("flash_fwd", "paged"),
                            "serving.engine_step",
                            GPT2_SMALL["num_layers"])

    # the engine's first token against the fp32 forward path's logits at
    # the prompt's last position. ISSUE 21 asks for equality with the
    # argmax; the engine computes in bf16, so where the two leading
    # logits are closer than bf16 resolves the argmax lands on the other
    # one (1 request of 6 on the chip, 4e-6 of the range below the top).
    # The gate is therefore: the token's fp32 logit is within 2^-8 of the
    # logits' range (one bf16 ulp of it) of the top — a wrong position or
    # a wrong page would miss by orders of magnitude more.
    exact, worst = 0, 0.0
    for p, toks in zip(prompts, streams):
        logits = tensor.to_numpy(
            m(tensor.from_numpy(p[None], device=dev)))[0, -1]
        check(bool(np.isfinite(logits).all()), "non-finite forward logits")
        exact += int(toks[0] == int(np.argmax(logits)))
        gap = float(logits.max() - logits[toks[0]]) \
            / float(logits.max() - logits.min())
        worst = max(worst, gap)
    check(worst <= 2.0 ** -8,
          f"a first token sits {worst:.4f} of the logit range below the "
          "forward path's argmax")

    ref_streams = serve_requests(m, prompts, cfg, use_kernel=False)
    same = sum(int(a == b) for s, r in zip(streams, ref_streams)
               for a, b in zip(s, r))
    total = sum(len(s) for s in streams)
    return {"requests": len(prompts), "tokens": total, **kernels,
            "first_token_equals_forward_argmax":
                f"{exact}/{len(prompts)}",
            "first_token_worst_gap_of_range": round(worst, 6),
            "stream_agreement_with_reference_engine":
                round(same / total, 4)}


def train_dp4(dev, cfg=TRAIN_DP4):
    import jax
    from singa_tpu import models, opt, overlap, tensor
    from singa_tpu.parallel import data_parallel_mesh
    n = cfg["chips"]

    def build(optimizer):
        dev.SetRandSeed(SEED)
        m = models.create_model("gpt", **GPT2_SMALL)
        m.set_optimizer(optimizer)
        m.compile([tx], is_train=True, use_graph=True, amp="bfloat16")
        return m

    tx, ty = gpt_batch(cfg, dev)
    twin = build(opt.SGD(lr=cfg["lr"], momentum=0.9))
    w0 = {k: tensor.to_numpy(v).copy()
          for k, v in twin.get_params().items()}
    single, _ = train_losses(twin, tx, ty, cfg["steps"])
    del twin

    mesh = data_parallel_mesh(n)
    m = build(opt.DistOpt(opt.SGD(lr=cfg["lr"], momentum=0.9), mesh=mesh))
    m.set_params(w0)
    losses, _ = train_losses(m, tx, ty, 1)
    # from here the batch goes in the way a data pipeline feeds it:
    # already split over the mesh by the model's own input sharding
    with overlap.prefetch_to_device(
            iter([(tx, ty)] * (cfg["steps"] - 1)), m) as it:
        for xb, yb in it:
            on = {s.device.id: s.data.shape
                  for s in xb.data.addressable_shards}
            check(len(on) == n and set(on.values())
                  == {(cfg["batch"] // n, cfg["seq"])},
                  f"input shards: {on}")
            more, _ = train_losses(m, xb, yb, 1)
            losses += more
    worst = max(abs(a - b) / abs(b) for a, b in zip(losses, single))
    check(worst <= cfg["rtol"],
          f"DP losses {losses} vs single-chip {single}: rel {worst:.2e}")
    want = {d.id for d in jax.devices()}
    for name, p in m.get_params().items():
        check(on_devices(p.data) == want
              and p.data.sharding.is_fully_replicated,
              f"param {name}: {p.data.sharding}")
    n_allreduce = compiled_text("step").count("all-reduce")
    check(n_allreduce > 0, "no all-reduce in the compiled DP step")
    return {"losses_dp": losses, "losses_single_chip": single,
            "max_rel_diff": worst, "rtol": cfg["rtol"],
            "input_shards_on_devices": n, "params_replicated_on": n,
            "all_reduce_in_step": n_allreduce}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the data-parallel phase, on four chips")
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke.py: no TPU: jax.devices() reports {devs}",
              file=sys.stderr)
        return 2
    if args.chips == 4 and len(devs) != 4:
        print(f"chip_smoke.py --chips 4 needs exactly 4 TPU devices, "
              f"found {len(devs)}", file=sys.stderr)
        return 2

    from singa_tpu import device, introspect, native, warmstart
    cache_dir = warmstart.configure_xla_cache(
        os.path.join(HERE, ".jax_cache"))
    introspect.capture_hlo(os.path.join(HERE, ".smoke_out", "hlo"))
    kind = devs[0].device_kind
    peak = introspect.chip_peak(kind, introspect.PEAK_TFLOPS_BF16)
    emit({"setup": {
        "jax": jax.__version__, "devices": [str(d) for d in devs],
        "compile_cache_dir": cache_dir,
        "compile_cache_entries_at_start": len(os.listdir(cache_dir)),
        "peak_tflops_bf16": peak,
        "peak_hbm_gbs": introspect.chip_peak(
            kind, introspect.PEAK_HBM_GBS),
        "native_recordio": "so" if native.lib() else "python",
        "native_snapshot": "so" if native.snapshot_lib() else "python"}})
    check(peak, f"device kind {kind!r} matches no row of the peak table")

    dev = device.create_tpu_device()
    if args.chips == 4:
        run_phase("train_dp4", train_dp4, dev)
    else:
        run_phase("train_gpt", train_gpt, dev)
        run_phase("train_resnet50", train_resnet50, dev)
        run_phase("serve_gpt", serve_gpt, dev)
    emit({"ok": True, "device": {
        "platform": devs[0].platform, "kind": kind, "count": len(devs)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
