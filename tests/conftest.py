import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
SRC = REPO / "src"
sys.path.insert(0, str(SRC))


def run_subprocess(code: str, devices: int = 8, timeout: int = 480):
    """Run a python snippet in a fresh process with N host devices (for
    multi-device tests — the main pytest process keeps 1 device).  The
    child is held to the CPU: the virtual devices are host devices, and a
    parent that holds an accelerator would otherwise starve it."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(SRC)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    if res.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}")
    return res.stdout


@pytest.fixture
def subproc():
    return run_subprocess
