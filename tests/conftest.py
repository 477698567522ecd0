"""Shared test utilities.

Multi-device tests run in SUBPROCESSES with their own XLA_FLAGS so the main
pytest process keeps the default single CPU device (per the assignment:
smoke tests must see 1 device).
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_subprocess(code: str, n_devices: int = 8, timeout: int = 600) -> str:
    """Run python code in a fresh process with n fake CPU devices."""
    env = dict(os.environ)
    # The fake devices are CPU devices: never let the child claim a TPU.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["TF_CPP_MIN_LOG_LEVEL"] = "3"
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env)
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={proc.returncode})\n"
            f"stdout:\n{proc.stdout[-4000:]}\nstderr:\n{proc.stderr[-4000:]}")
    return proc.stdout


@pytest.fixture
def subproc():
    return run_subprocess
