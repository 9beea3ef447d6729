"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests must see the
real (single) host device; multi-device tests spawn subprocesses that
set --xla_force_host_platform_device_count themselves."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_py(code: str, *, devices: int = 1, timeout: int = 600):
    """Run a python snippet in a subprocess with N fake host devices."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    if devices > 1:
        # fake host devices are CPU devices: never reach for a chip
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices}"
        )
    return subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, env=env, timeout=timeout,
    )


@pytest.fixture(scope="session")
def rng_key():
    import jax

    return jax.random.key(0)
