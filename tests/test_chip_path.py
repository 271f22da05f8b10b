"""The chip path fails loudly, the CPU test path stays explicit.

Nothing that is meant for the TPU may quietly run somewhere else: device
constructors raise with no chip, `config.use_tpu` names "tpu" only and lets
an initialisation error through, the entry points that measure or smoke the
chip exit non-zero here, an unlisted device kind gets no peak, a stale
native library is never loaded, and every attention call site records which
implementation it took — which is what `chip_smoke.py` gates on.
"""

import ctypes
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from singa_tpu import config, device, introspect, native, observe
from singa_tpu.ops import attention as A

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- devices ---------------------------------------------------------------

@pytest.mark.parametrize("make", [
    lambda: device.create_tpu_device(),
    lambda: device.create_tpu_device_on(0),
    lambda: device.create_tpu_devices(1),
], ids=["create_tpu_device", "create_tpu_device_on", "create_tpu_devices"])
def test_tpu_constructors_raise_without_a_tpu(make):
    """This suite runs on CPU devices: there is no chip to hand out, and a
    CPU device labelled kTpu would be a lie."""
    with pytest.raises(RuntimeError, match="no TPU attached"):
        make()


def test_host_only_machine_counts_no_accelerators():
    assert device.get_num_gpus() == 0 and device.get_gpu_ids() == []
    assert device.best_device() is device.get_default_device()
    assert device.best_device().platform == "cpu"


class _FakeDevice:
    def __init__(self, platform):
        self.platform = platform


@pytest.mark.parametrize("platforms,want", [
    (["cpu"], False), (["gpu"], False), (["cpu", "tpu"], True),
])
def test_use_tpu_names_tpu_only(monkeypatch, platforms, want):
    monkeypatch.setattr(jax, "devices",
                        lambda: [_FakeDevice(p) for p in platforms])
    assert config.use_tpu() is want
    assert config.USE_TPU is want


def test_use_tpu_lets_an_init_error_surface(monkeypatch):
    """A chip that fails to initialise must not read as "no chip"."""
    def boom():
        raise RuntimeError("UNAVAILABLE: TPU backend setup error")
    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError, match="UNAVAILABLE"):
        config.use_tpu()


# ---- entry points ------------------------------------------------------------

@pytest.mark.parametrize("script", ["chip_smoke.py"])
def test_entry_points_exit_nonzero_without_a_tpu(script):
    """No shrunk CPU run, no `_cpu` metric, no `ok` line: one line saying
    why, and a non-zero exit code."""
    r = subprocess.run([sys.executable, os.path.join(_ROOT, script)],
                       cwd=_ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert r.stdout.strip() == ""


def test_smoke_gate_fails_on_a_path_that_is_not_the_kernel():
    """What makes a chip_smoke phase fail when attention falls back: on
    this CPU backend the flash call site takes interpret mode, which the
    gate refuses as it would refuse the reference path on the chip."""
    import chip_smoke  # conftest puts the repo root on sys.path
    before = chip_smoke.dispatch_counts()
    q = jnp.ones((1, 2, 128, 64), jnp.float32)
    jax.jit(lambda q: A.flash_attention(q, q, q, True)).lower(q)
    with pytest.raises(chip_smoke.SmokeFailure, match="flash_fwd"):
        chip_smoke.check_kernels(before, ("flash_fwd",), "step", 1)


# ---- which attention implementation ran ---------------------------------------

def _dispatch(site, path):
    c = observe.get_registry().get("singa_attention_dispatch_total")
    return 0 if c is None else int(c.value(site=site, path=path))


def test_attention_call_sites_record_their_path():
    q = jnp.ones((1, 2, 128, 64), jnp.float32)
    jax.grad(lambda q: A.flash_attention(q, q, q, True).sum())(q)
    assert _dispatch("flash_fwd", "interpret") == 1
    assert _dispatch("flash_bwd", "interpret") == 1
    # 100 rows tile by no block of 8-aligned rows >= 100: reference path
    r = jnp.ones((1, 2, 100, 64), jnp.float32)
    jax.grad(lambda r: A.flash_attention(r, r, r, True).sum())(r)
    assert _dispatch("flash_fwd", "reference") == 1
    assert _dispatch("flash_bwd", "reference") == 1
    # decode-side sites: off-TPU the default is the jnp reference
    qd = jnp.ones((2, 1, 2, 128), jnp.float32)
    pool = jnp.ones((4, 1, 8, 128), jnp.float32)
    table = jnp.zeros((2, 2), jnp.int32)
    lens = jnp.array([3, 9], jnp.int32)
    A.paged_attention(qd, pool, pool, table, lens, 8)
    A.paged_attention(qd, pool, pool, table, lens, 8, use_kernel=True)
    assert _dispatch("paged", "reference") == 1
    assert _dispatch("paged", "interpret") == 1
    cache = jnp.ones((2, 1, 16, 128), jnp.float32)
    A.flash_decode(qd, cache, cache, lens)
    assert _dispatch("flash_decode", "reference") == 1
    assert _dispatch("flash_decode", "kernel") == 0


# ---- peaks ---------------------------------------------------------------------

@pytest.mark.parametrize("kind,tflops", [
    ("TPU v5 lite", 197.0), ("TPU v5e", 197.0), ("TPU v5p", 459.0),
    ("TPU v5", None), ("TPU v7x", None), ("cpu", None),
])
def test_unlisted_device_kind_gets_no_peak(kind, tflops):
    """A bare "v5" row once credited every unlisted v5 kind with v5p's
    peak; a kind no row names gets None, and so no MFU."""
    assert introspect.chip_peak(kind, introspect.PEAK_TFLOPS_BF16) == tflops


# ---- native libraries -------------------------------------------------------------

@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
def test_stale_native_library_is_never_loaded(tmp_path, monkeypatch):
    """A `lib*.so` copied along with a tree says nothing by its mtime. The
    library that is loaded carries the hash of the committed source;
    builds of any other source are left alone and never chosen."""
    shutil.copy(os.path.join(os.path.dirname(native.__file__), "recordio.cc"),
                tmp_path / "recordio.cc")
    stale = [tmp_path / "librecordio.so", tmp_path / "librecordio-0123abc.so"]
    for f in stale:
        f.write_bytes(b"not a shared object")
        os.utime(f, (2e9, 2e9))  # newer than the source
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    so = native._compile("recordio")
    assert so is not None and os.path.basename(so).startswith("librecordio-")
    assert so not in {str(f) for f in stale}
    assert ctypes.CDLL(so).rio_writer_open is not None
    assert native._compile("recordio") == so  # found again, not rebuilt
