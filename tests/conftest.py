# NOTE: no XLA_FLAGS / device-count overrides here — smoke tests and benches
# must see the single real CPU device.  Multi-device scenarios run in
# subprocesses (tests/helpers/) that set the flag themselves.
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_helper(script: str, *args, devices: int = 8, timeout: int = 900):
    """Run tests/helpers/<script> in a subprocess with N virtual CPU
    devices (held to the CPU: a chip belongs to one process)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "helpers", script),
         *map(str, args)],
        capture_output=True, text=True, timeout=timeout, env=env)
    if proc.returncode != 0:
        raise AssertionError(
            f"{script} failed (rc={proc.returncode})\n--- stdout ---\n"
            f"{proc.stdout[-4000:]}\n--- stderr ---\n{proc.stderr[-4000:]}")
    return proc.stdout


@pytest.fixture(scope="session")
def helper():
    return run_helper
