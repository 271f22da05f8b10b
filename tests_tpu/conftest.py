"""TPU-gated tests: run on the real chip (ambient platform, no CPU pin).

These are NOT part of the CPU-mesh suite (tests/); run explicitly with
`python -m pytest tests_tpu/ -q` on a machine with a TPU attached. The
device is probed by a fixture, after collection: importing or collecting
these files touches no backend, and off-TPU every test skips.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def dev():
    """The first TPU chip as a singa_tpu Device; skips off-TPU."""
    import jax
    from singa_tpu import device
    if jax.devices()[0].platform != "tpu":
        pytest.skip("no TPU attached")
    return device.create_tpu_device()


@pytest.fixture(autouse=True)
def _needs_tpu(dev):
    """Everything under tests_tpu/ needs the chip."""
