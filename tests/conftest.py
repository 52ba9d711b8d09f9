"""Shared fixtures.  NOTE: no XLA_FLAGS device-count forcing here — smoke
tests and benches must see the real single CPU device; multi-device tests
spawn subprocesses that set the flag themselves."""
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture(scope="session")
def cpu_mesh():
    # Meshes come from repro.compat.make_mesh, the one place that spells
    # jax's mesh API (lint rule REP001).
    from repro.compat import AxisType, make_mesh
    return make_mesh((1, 1), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)


@pytest.fixture(scope="session")
def rules(cpu_mesh):
    from repro.distributed.sharding import MeshRules
    return MeshRules.for_mesh(cpu_mesh)


def run_subprocess(code: str, devices: int = 8, timeout: int = 600) -> str:
    """Run ``code`` in a fresh python with N fake XLA host devices."""
    import subprocess
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert res.returncode == 0, f"subprocess failed:\n{res.stdout}\n{res.stderr}"
    return res.stdout
