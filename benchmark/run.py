"""One run of one benchmark cell:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in BENCHMARK.json; its configuration, traffic mix,
driver and per-layer metric readers are files found by the names there (see
benchmark/README.md). The last line of stdout is the result; anything else
worth a number goes on an earlier line. Exits 2 without a result when the
attached device is not a TPU or the chip count is not the cell's.
"""

import time

T0 = time.perf_counter()   # set-up time runs from here

import argparse
import importlib.util
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, ".out")
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts), encoding="utf-8") as f:
        return json.load(f)


def load_module(kind, name):
    """benchmark/<kind>/<name>.py, loaded by the name in a data file."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """What a driver gets: the cell's data, the device, the clocks and the
    checks both drivers share. Tests build one by hand on the CPU."""

    def __init__(self, spec, seed, seconds, trace, dev, out_dir=OUT, t0=T0):
        self.name = spec["name"]
        self.config = load_json(spec["config_file"]) \
            if "config_file" in spec else spec["config_data"]
        mix = spec.get("traffic_data") or load_json(
            "benchmark", "traffic", spec["traffic"] + ".json")
        self.driver = mix["driver"]
        self.system, self.traffic = mix["system"], mix["traffic"]
        self.window, self.check = mix["window"], mix["check"]
        self.model_args = self.config["create_model"]
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        # the device RNG takes 31 bits; numpy takes the whole seed
        self.seed31 = self.seed % (2 ** 31 - 1)
        self.dev = dev
        self.out_dir = os.path.join(out_dir, self.name)
        self.trace_dir = os.path.join(self.out_dir, "trace")
        self.hlo_dir = os.path.join(self.out_dir, "hlo")
        self.t0 = t0
        self._jit_compiles = 0
        self._tracing = None

    @staticmethod
    def pctile(xs, p):
        """Nearest-rank percentile; None when empty."""
        xs = sorted(xs)
        return xs[min(len(xs) - 1, int(p * len(xs)))] if xs else None

    # -- set-up --------------------------------------------------------------
    def prepare(self):
        """Compile cache, HLO capture and the compile counter."""
        import jax
        from singa_tpu import introspect, warmstart
        shutil.rmtree(self.out_dir, ignore_errors=True)
        warmstart.configure_xla_cache(os.path.join(ROOT, ".jax_cache"))
        introspect.capture_hlo(self.hlo_dir)

        def on_event(name, _secs, **_kw):
            if name.endswith("backend_compile_duration"):
                self._jit_compiles += 1
        jax.monitoring.register_event_duration_secs_listener(on_event)

    def compile_mark(self):
        """Everything that counts a compilation, to compare across the
        window: the program's staged builds and jax's own backend compiles."""
        from singa_tpu import introspect
        return (tuple(sorted(introspect.compile_phase_totals().items())),
                self._jit_compiles)

    # -- checks --------------------------------------------------------------
    def dispatch_counts(self):
        """{(site, path): traced attention call sites}."""
        from singa_tpu import observe
        c = observe.get_registry().get("singa_attention_dispatch_total")
        return {(s, p): int(c.value(site=s, path=p))
                for s in observe.ATTN_SITES
                for p in observe.ATTN_PATHS} if c is not None else {}

    def kernel_check(self, before, sites, key):
        """(ok, facts): since `before` every site in `sites` was traced onto
        its compiled Pallas kernel and none onto anything else, and the
        executable built under `key` holds the cell's least number of
        Mosaic custom calls."""
        from singa_tpu import introspect
        delta = {k: v - before.get(k, 0)
                 for k, v in self.dispatch_counts().items()
                 if v - before.get(k, 0)}
        rec = introspect.last_build(key)
        n = 0
        if rec and rec.get("hlo_path"):
            with open(rec["hlo_path"], encoding="utf-8") as f:
                n = f.read().count("tpu_custom_call")
        ok = all(delta.get((s, "kernel"), 0) > 0 for s in sites) \
            and all(p == "kernel" for _s, p in delta) \
            and n >= self.check["min_custom_calls"]
        return ok, {"attention_paths": {f"{s}/{p}": v for (s, p), v
                                        in sorted(delta.items())},
                    f"tpu_custom_calls_in_{key}": n}

    def memory_peak(self):
        stats = self.dev.jax_device.memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0))

    # -- tracing -------------------------------------------------------------
    def trace_start(self):
        """Start the profiler (no Python tracer: it slows the host) and
        open the annotation that marks the traced stretch."""
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        from trace_reduce import WINDOW_SPAN
        self._tracing = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._tracing.__enter__()

    def tracing(self):
        return self._tracing is not None

    def trace_stop(self):
        import jax
        self._tracing.__exit__(None, None, None)
        self._tracing = None
        jax.profiler.stop_trace()


def result_line(bench, cell, rec, dev_info):
    """The contract's last line from a driver's record."""
    mine = lambda m: cell.name in m.get("workloads", [cell.name])
    rec = dict(rec, hlo_dir=cell.hlo_dir)   # readers find the kernels there
    metrics, reduced = {}, None
    if cell.trace:
        import trace_reduce
        reduced = trace_reduce.reduce(cell.trace_dir)
        for m in filter(mine, bench["per_layer"]):
            v = load_module("layer_metrics", m["name"]).read(rec, reduced)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in filter(mine, bench["end_to_end"]):
            metrics[m["name"]] = {"value": rec["values"][m["name"]],
                                  "unit": m["unit"]}
    device = dict(dev_info, memory_peak_bytes=rec["memory_peak_bytes"])
    line = {"correct": all(rec["checks"].values()),
            "attempted": rec["attempted"], "failed": rec["failed"],
            "metrics": metrics, "device": device}
    if reduced:
        device["busy_s"], device["window_s"] = \
            reduced["busy_s"], reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json("BENCHMARK.json")
    spec = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if spec is None:
        print(f"run.py: no cell {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    spec = dict(spec, config_file=next(
        c["file"] for c in bench["configs"] if c["name"] == spec["config"]))

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) != spec["chips"]:
        print(f"run.py: cell {spec['name']} needs {spec['chips']} TPU "
              f"chip(s); jax.devices() reports {devs}", file=sys.stderr)
        return 2

    import flops
    from singa_tpu import device
    flops.peak(devs[0].device_kind, "bf16_flops")   # unknown kind: error
    cell = Cell(spec, args.seed, args.seconds or bench["run_seconds"],
                bool(args.trace), device.create_tpu_device())
    cell.prepare()
    rec = load_module("drivers", cell.driver).run(cell)
    # an earlier line: the checks one by one, and the driver's notes
    print(json.dumps({"cell": cell.name, "seed": cell.seed,
                      "checks": rec["checks"], "notes": rec.get("notes")},
                     default=float), flush=True)   # numpy scalars in notes
    line = result_line(bench, cell, rec, {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)})
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
