"""Smoke test for the *ambient* global wisdom cache (CI, not pytest).

The pytest modules deliberately pin their own cache paths, so none of them
exercise the production path where ``global_tuning_cache()`` resolves
``$REPRO_TUNING_CACHE`` and two processes share it implicitly.  This script
does, in two child processes: phase 1 auto-tunes with no explicit cache so
the plan and the calibrated machine profile land in the env-pointed wisdom
file; phase 2 must be *served* from that file — a cache hit and a loaded
machine section, no re-tuning.  The parent never imports jax, so on an
accelerator each child in turn can hold the device.

Run directly: ``REPRO_TUNING_CACHE=/tmp/w.json PYTHONPATH=src python
tests/global_cache_smoke.py`` (the name does not match ``test_*`` on
purpose — pytest must not collect it).
"""
import os
import subprocess
import sys
import tempfile

PHASE1 = """
import os
from repro.compat import make_mesh
from repro.core import global_tuning_cache, tune
mesh = make_mesh((1, 1), ("data", "model"))
plan = tune((8, 8, 16), mesh, top_k=1, repeats=1)
assert plan.source == "measured", plan
stats = global_tuning_cache().stats()
assert stats["plans"] >= 1, f"plan not persisted: {stats}"
assert stats["machines"] >= 1, f"calibration not persisted: {stats}"
assert os.path.exists(os.environ["REPRO_TUNING_CACHE"])
print("phase1 ok:", stats)
"""

PHASE2 = """
from repro.compat import make_mesh
from repro.core import global_tuning_cache, tune
mesh = make_mesh((1, 1), ("data", "model"))
plan = tune((8, 8, 16), mesh, top_k=1, repeats=1)
stats = global_tuning_cache().stats()
assert stats["hits"] == 1, f"expected ambient cache hit, got {stats}"
assert stats["machines"] == 1, f"machine section not loaded: {stats}"
assert plan.source == "measured" and plan.measured_s > 0
print("phase2 ok: served from ambient wisdom")
"""


def main() -> int:
    os.environ.setdefault(
        "REPRO_TUNING_CACHE",
        os.path.join(tempfile.mkdtemp(), "tuning.json"))
    for phase in (PHASE1, PHASE2):
        rc = subprocess.run([sys.executable, "-c", phase]).returncode
        if rc:
            return rc
    return 0


if __name__ == "__main__":
    sys.exit(main())
