"""Shared test utilities.

NOTE: no XLA_FLAGS here — unit tests run on the single real CPU device (the
brief requires smoke tests see 1 device). Multi-device tests spawn a
subprocess with ``--xla_force_host_platform_device_count`` via
``run_multidevice``.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_multidevice(code: str, ndev: int = 8, timeout: int = 600):
    """Run ``code`` in a subprocess with ``ndev`` fake host devices, pinned
    to the CPU on purpose (the mesh is simulated on the host)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{proc.stdout}\n"
            f"STDERR:\n{proc.stderr}"
        )
    return proc.stdout


@pytest.fixture(scope="session")
def multidevice():
    return run_multidevice
