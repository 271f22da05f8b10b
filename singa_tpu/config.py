"""Build/feature flags.

Reference parity: SINGA exports CMake flags to Python through the generated
SWIG config module (`src/api/config.i.in:21-27`) as `singa_wrap.USE_CUDA`,
`USE_DIST`, etc., and tests key off them (`test/python/test_dist.py:25`).
Here there is no compile step: flags are discovered from the live JAX
runtime — lazily, so importing singa_tpu never initializes a JAX backend
(tests must be able to pick the CPU platform first).
"""

import os

# CUDA is never compiled in: this framework is TPU-native by construction.
USE_CUDA = False
USE_OPENCL = False
USE_DNNL = False

#: Distributed is always available: collectives run over ICI/DCN through XLA
#: (single-process multi-device via shard_map, multi-host via
#: jax.distributed). The reference gates this on an MPI/NCCL build.
USE_DIST = True

#: ONNX support is always on: sonnx ships its own protobuf wire codec
#: (singa_tpu/sonnx/onnx_pb.py), no `onnx` package needed.
USE_ONNX = True

CUDNN_VERSION = 0  # parity constant; no cuDNN on TPU

#: Default number of simulated host devices for CPU-mesh tests. Mirrors the
#: reference's lack of a fake communicator (SURVEY.md §4 "lesson").
HOST_DEVICE_COUNT = int(os.environ.get("SINGA_TPU_HOST_DEVICES", "8"))

#: Peak-flops override (TFLOP/s) for the MFU gauge and explain report
#: (singa_tpu.introspect). None = use the per-generation table keyed on
#: jax.Device.device_kind; set SINGA_TPU_PEAK_TFLOPS (or call
#: introspect.set_peak_tflops) for custom parts or derated clocks.
PEAK_TFLOPS = (float(os.environ["SINGA_TPU_PEAK_TFLOPS"])
               if os.environ.get("SINGA_TPU_PEAK_TFLOPS") else None)


def use_tpu() -> bool:
    """True when at least one TPU chip is attached. Initializes the JAX
    backend on first call — do not call at import time. A backend that
    fails to initialize raises: a broken chip is not "no chip"."""
    import jax
    return any(d.platform == "tpu" for d in jax.devices())


def __getattr__(name):
    if name == "USE_TPU":
        return use_tpu()
    raise AttributeError(f"module 'singa_tpu.config' has no attribute {name!r}")
