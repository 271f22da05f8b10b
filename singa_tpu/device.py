"""Device abstraction over jax.Device.

Reference parity: SINGA's C++ `Device` (include/singa/core/device.h:57) owns
op submission (`Exec` -> immediate or graph), memory blocks, sync, graph
replay, and profiling verbosity; `Platform` (device.h:311) discovers GPUs and
Python wraps it thinly (python/singa/device.py:29-135).

TPU-native redesign: XLA owns memory and the compiled graph, so `Device` here
is a *policy object*: which jax.Device tensors land on, whether Model-level
graph (jit) buffering is on, profiling verbosity, and the per-device PRNG
stream (the reference keeps curand state in `Context`, common.h:99-128).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# process-global: jax.profiler allows one active trace per process
_active_trace_dir: "str | None" = None


class Device:
    """A compute device. Holds placement + graph/profiling policy + RNG."""

    def __init__(self, jax_device: "jax.Device", id: int = 0, lang: str = "kTpu"):
        self.jax_device = jax_device
        self.id = id
        self.lang = lang
        # Graph buffering flag: mirrors Device::graph_enabled_ toggled by
        # EnableGraph (device.h:142). When True, Model.train_one_batch traces
        # into a jitted executable instead of running eagerly.
        self.graph_enabled = False
        # Profiling verbosity 0-3 + warmup skip, mirrors device.h:115-129.
        self.verbosity = 0
        self.skip_iteration = 5
        # Filled by Model when verbosity > 0 (replaces the reference's
        # per-node cudaEvent timing, scheduler.cc:240-295).
        self.step_times = []       # seconds per profiled step
        # XLA cost analysis of the compiled step: populated at AOT build
        # time by singa_tpu.introspect (model.py routes every step build
        # through explicit lower/compile stages), so the verbosity>=2
        # GFLOP/TFLOP-s lines below print real numbers with no extra
        # re-lowering pass.
        self.cost_analysis = None
        # Per-device PRNG stream (reference: curandGenerator in Context).
        self._rng_key = jax.random.key(0, impl="threefry2x32")
        self._rng_key = jax.device_put(self._rng_key, jax_device)

    # ---- RNG ------------------------------------------------------------
    def SetRandSeed(self, seed: int):
        self._rng_key = jax.device_put(
            jax.random.key(int(seed), impl="threefry2x32"), self.jax_device)

    @jax.named_scope("rng")   # in a traced step: the key plumbing's scope
    def rand_key(self):
        """Split off a fresh PRNG key (functional curandGenerate analog)."""
        self._rng_key, sub = jax.random.split(self._rng_key)
        return sub

    @property
    def rng_state(self):
        return self._rng_key

    @rng_state.setter
    def rng_state(self, key):
        # Normalize RAW uint32 keys (legacy jax.random.PRNGKey) to TYPED
        # keys: the framework threads rng_state through jitted/shard_mapped
        # steps, and a mid-stream dtype flip (typed <-> raw) fragments the
        # executable cache into variants with different buffer layouts —
        # an INVALID_ARGUMENT buffer-count crash at dispatch time.
        try:
            if (isinstance(key, jax.Array)
                    and not jnp.issubdtype(key.dtype, jax.dtypes.prng_key)
                    and key.ndim == 1 and key.shape[0] == 2
                    and key.dtype == jnp.uint32):
                key = jax.random.wrap_key_data(key)
        except TypeError:
            # tracers/abstract values: shape/dtype probing above can raise
            # on them; they pass through untouched. Anything else (e.g. a
            # malformed key array) propagates — silently threading a bad
            # key would fragment the executable cache, the exact failure
            # this normalization exists to prevent.
            pass
        self._rng_key = key

    # ---- graph control (parity with core_device.i) ----------------------
    def EnableGraph(self, enable: bool = True):
        self.graph_enabled = enable

    def ResetGraph(self):
        # XLA owns the executable cache; Model drops its compiled step.
        pass

    def Sync(self):
        """Fence: wait for all queued device work (Device::Sync). A TPU
        runs its programs in order, so a tiny computation waited on
        finishes only after everything queued before it. (A tiny
        transfer does not: on the TPU it returned while queued matmuls
        still ran — transfers ride their own stream. The CPU backend
        may run independent programs side by side: there, block on the
        arrays you need.)"""
        (jax.device_put(np.zeros((), np.float32), self.jax_device)
         + 0).block_until_ready()

    # ---- profiling (device.h:115-129) -----------------------------------
    def SetVerbosity(self, v: int):
        self.verbosity = int(v)

    def SetSkipIteration(self, n: int):
        self.skip_iteration = int(n)

    def PrintTimeProfiling(self):
        """Per-step timing summary (reference Graph::PrintTimeProfiling,
        scheduler.cc:240-295; fwd/bwd split is replaced by whole-step wall
        time + XLA cost analysis since XLA fuses across the phases)."""
        if not self.step_times:
            print("time profiling: no steps recorded "
                  "(SetVerbosity(>=1) before training)")
            return
        t = np.asarray(self.step_times)
        print(f"time profiling: {len(t)} steps, "
              f"mean {t.mean() * 1e3:.3f} ms, std {t.std() * 1e3:.3f} ms, "
              f"min {t.min() * 1e3:.3f} ms")
        if self.verbosity >= 2 and self.cost_analysis:
            ca = self.cost_analysis
            flops = ca.get("flops", 0.0)
            bytes_ = ca.get("bytes accessed", 0.0)
            achieved = flops / max(t.mean(), 1e-12) / 1e12
            print(f"  XLA cost: {flops / 1e9:.2f} GFLOP/step, "
                  f"{bytes_ / 1e6:.1f} MB accessed/step, "
                  f"{achieved:.2f} TFLOP/s achieved")
            try:
                from .introspect import peak_tflops
                peak = peak_tflops(
                    getattr(self.jax_device, "device_kind", ""))
            except Exception:
                peak = None
            if peak:
                print(f"  MFU: {achieved / peak * 100.0:.2f}% of "
                      f"{peak:g} TFLOP/s peak")
        if self.verbosity >= 3 and self.cost_analysis:
            for k, v in sorted(self.cost_analysis.items()):
                if isinstance(v, (int, float)):
                    print(f"  {k}: {v:.3g}")

    # ---- trace capture ---------------------------------------------------
    # The reference's deepest profiling level is per-op CUDA-event tables
    # (scheduler.cc:276-295). The TPU analog is an xplane trace: per-HLO
    # timelines viewable in TensorBoard/xprof/Perfetto. jax.profiler is
    # process-global, so the active-trace flag lives at module level —
    # Start/Stop pair up correctly across different Device objects.
    def StartTrace(self, log_dir: str):
        """Begin capturing a jax profiler trace into `log_dir`."""
        global _active_trace_dir
        if _active_trace_dir is not None:
            raise RuntimeError(
                f"a trace into {_active_trace_dir} is already active; "
                "StopTrace() it first (the profiler is process-global)")
        jax.profiler.start_trace(log_dir)
        _active_trace_dir = log_dir

    def StopTrace(self) -> "str | None":
        """Stop the capture; returns the log dir. Idempotent: with no
        trace active (never started, or already stopped — including by a
        second StopTrace or by jax.profiler directly) it returns None
        cleanly instead of raising, so shutdown paths can call it
        unconditionally."""
        global _active_trace_dir
        out = _active_trace_dir
        if out is not None:
            try:
                jax.profiler.stop_trace()
            except Exception:
                # someone stopped the process-global profiler under us;
                # idempotence beats raising — the flag reset below keeps
                # future StartTrace working either way
                pass
            finally:
                _active_trace_dir = None  # never wedge future StartTrace
        return out

    # ---- info ------------------------------------------------------------
    @property
    def platform(self) -> str:
        return self.jax_device.platform

    def is_host(self) -> bool:
        return self.jax_device.platform == "cpu"

    def __repr__(self):
        return f"Device(lang={self.lang}, id={self.id}, jax={self.jax_device})"


class _Platform:
    """Device discovery, mirrors `Platform` (device.h:311-386)."""

    def __init__(self):
        self._cache = {}

    def _accel_devices(self):
        """The attached TPU chips — empty on a host-only machine, never
        the CPU devices under another name."""
        return [d for d in jax.devices() if d.platform == "tpu"]

    def GetNumGPUs(self) -> int:  # name kept for parity; counts accelerators
        return len(self._accel_devices())

    def num_tpus(self) -> int:
        return self.GetNumGPUs()

    def device(self, kind: str, idx: int) -> Device:
        key = (kind, idx)
        if key not in self._cache:
            if kind == "host":
                jd = jax.local_devices(backend="cpu")[idx]
                self._cache[key] = Device(jd, id=idx, lang="kCpp")
            else:
                devs = self._accel_devices()
                if not devs:
                    raise RuntimeError(
                        "no TPU attached: jax.devices() reports "
                        f"{[d.platform for d in jax.devices()]}; use "
                        "get_default_device()/best_device() for the host")
                jd = devs[idx]
                self._cache[key] = Device(jd, id=idx, lang="kTpu")
        return self._cache[key]


platform = _Platform()

# ---- module-level API (parity with python/singa/device.py) ---------------

_default_device: Device | None = None


def get_default_device() -> Device:
    """Host CPU device (reference returns the singleton CppCPU)."""
    global _default_device
    if _default_device is None:
        _default_device = platform.device("host", 0)
    return _default_device


def create_tpu_device(set_default: bool = False) -> Device:
    """First attached TPU chip (reference: create_cuda_gpu). Raises
    RuntimeError on a machine with no TPU."""
    d = platform.device("accel", 0)
    if set_default:
        global _default_device
        _default_device = d
    return d


def create_tpu_device_on(device_id: int) -> Device:
    """TPU chip by index (reference: create_cuda_gpu_on, device.py:103)."""
    return platform.device("accel", device_id)


# Aliases so code written against the reference API keeps working.
create_cuda_gpu = create_tpu_device
create_cuda_gpu_on = create_tpu_device_on


def create_cpu_device() -> Device:
    return get_default_device()


def best_device() -> Device:
    """The fastest attached device: TPU if present, else host CPU. A
    measurement or smoke path must assert `.platform` on the result, or
    call create_tpu_device(), which raises with no TPU."""
    return platform.device("accel", 0) if platform._accel_devices() \
        else get_default_device()


def enable_lazy_alloc(flag: bool):
    """No-op: XLA allocates lazily by construction (ref device.py:133)."""
    del flag


# ---- reference-name query parity (python/singa/device.py:29-99) ---------
# "GPU" queries answer for the attached accelerators (TPU chips here);
# OpenCL was never compiled into the reference's Python wheels either, so
# those queries mirror its disabled-build behavior.

def get_num_gpus() -> int:
    return platform.GetNumGPUs()


def get_gpu_ids():
    return list(range(platform.GetNumGPUs()))


def get_gpu_mem_size(id: int):  # noqa: A002  (name mandated by parity)
    dev = platform.device("accel", id)
    stats = getattr(dev.jax_device, "memory_stats", lambda: None)()
    if stats:
        return (stats.get("bytes_limit", 0), stats.get("bytes_in_use", 0))
    return (0, 0)


def device_query(id: int, verbose=False):  # noqa: A002
    dev = platform.device("accel", id)
    info = {"id": id, "kind": getattr(dev.jax_device, "device_kind", "?"),
            "platform": dev.platform}
    if verbose:
        print(info)
    return info


def create_cuda_gpus(num: int):
    """A list of the first `num` accelerator Devices."""
    return [platform.device("accel", i) for i in range(num)]


def create_cuda_gpus_on(device_ids):
    return [platform.device("accel", i) for i in device_ids]


def get_num_opencl_platforms():
    raise AssertionError(
        "built without OpenCL (parity with the reference's USE_OPENCL=OFF "
        "wheels); use the TPU/CPU devices")


def get_num_opencl_devices():
    raise AssertionError(
        "built without OpenCL (parity with the reference's USE_OPENCL=OFF "
        "wheels); use the TPU/CPU devices")


def create_opencl_device():
    raise AssertionError(
        "built without OpenCL (parity with the reference's USE_OPENCL=OFF "
        "wheels); use the TPU/CPU devices")


create_tpu_devices = create_cuda_gpus
