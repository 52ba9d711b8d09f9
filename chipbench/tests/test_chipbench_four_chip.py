"""The four-chip cell on four fake CPU devices (``--cpu-rehearsal``, in a
child process that has them): a sound run is ``correct`` with its output
sharded over the four; the control and each planted fault are not."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 17
FOUR_CHIP = ("c2c1024x4.roundtrip",
             ("bf16", "unchanged", "half", "altered", "no_exchange"))


def test_four_chip_cell_on_fake_devices():
    workload, faults = FOUR_CHIP
    script = (
        "import contextlib, io, json, sys\n"
        "from chipbench import run\n"
        "for b in sys.argv[1:]:\n"
        "    buf = io.StringIO()\n"
        "    extra = [] if b == 'sound' else ['--broken', b]\n"
        "    with contextlib.redirect_stdout(buf):\n"
        f"        rc = run.main(['--workload', '{workload}', '--seed',"
        f" '{SEED}', '--seconds', '0.3', '--cpu-rehearsal'] + extra)\n"
        "    last = buf.getvalue().splitlines()[-1]\n"
        "    print(json.dumps([b, rc, json.loads(last)]))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=f"{ROOT}{os.pathsep}{ROOT / 'src'}")
    res = subprocess.run([sys.executable, "-c", script, "sound", *faults],
                         capture_output=True, text=True, timeout=600,
                         env=env, cwd=ROOT)
    assert res.returncode == 0, res.stderr[-4000:]
    rows = [json.loads(line) for line in res.stdout.splitlines()
            if line.startswith("[")]
    assert [r[0] for r in rows] == ["sound", *faults]
    for name, rc, result in rows:
        assert rc == 0
        assert result["device"]["count"] == 4
        assert result["correct"] is (name == "sound"), (name, result["checks"])
        assert result["checks"]["unsharded"]["value"] == 0 or name != "sound"
