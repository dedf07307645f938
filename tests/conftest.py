"""Test environment: JAX on the CPU, on purpose, split into 8 virtual
devices.

``JAX_PLATFORMS=cpu`` before the first ``import jax`` is all it takes
(utils/device.py: the one way to say "CPU on purpose"); sharding tests
run against ``--xla_force_host_platform_device_count=8`` exactly as the
driver's multi-chip dry-run does. The device itself is exercised by
``python chip_smoke.py`` on a chip machine, never by this suite.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

from dfs_tpu.utils.device import enable_compile_cache  # noqa: E402

# the heavily-unrolled sha256/gear kernels are slow to compile; keep
# compiled executables across test runs
enable_compile_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(1234)


@pytest.fixture(scope="session")
def example_files():
    """The reference's de-facto fixtures (examples/, SURVEY.md §4) recreated
    synthetically: small text, html, and binary payloads."""
    r = np.random.default_rng(7)
    return {
        "teste.txt": b"Arquivo de teste para upload.\n",
        "pag1.html": (b"<html><head><title>p</title></head><body>"
                      + b"<p>hello world</p>" * 12 + b"</body></html>"),
        "id.jpg": r.integers(0, 256, size=9506, dtype=np.uint8).tobytes(),
        "pl.png": r.integers(0, 256, size=2154, dtype=np.uint8).tobytes(),
        "empty.bin": b"",
        "tiny.bin": b"ab",
    }
