"""Whole runs of every cell on the CPU (``--cpu-rehearsal``, 16 per axis):
a sound run is ``correct``; the control and each planted fault that the
cell can have are not; without a TPU a run exits 2 and prints no result."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import run as bench_run

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 17          # past 32 signed bits, as the driver's seeds are
ONE_CHIP = {"c2c512.roundtrip": ("bf16", "unchanged", "half", "altered"),
            "ppb512.timestep": ("bf16", "unchanged", "half", "altered")}


@pytest.fixture
def cpu_env(monkeypatch):
    # ``--cpu-rehearsal`` sets these; monkeypatch puts them back after.
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))


def rehearse(capsys, workload, *extra):
    rc = bench_run.main(["--workload", workload, "--seed", str(SEED),
                         "--seconds", "0.3", "--cpu-rehearsal", *extra])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    assert out[-2] == "platform: cpu"
    return json.loads(out[-1])


@pytest.mark.parametrize("workload,broken", [
    (w, b) for w, faults in ONE_CHIP.items() for b in (None,) + faults])
def test_one_chip_cell(cpu_env, capsys, workload, broken):
    extra = ["--broken", broken] if broken else []
    result = rehearse(capsys, workload, *extra)
    assert result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is (broken is None), result["checks"]
    assert list(result)[-1] == "checks"


def test_traced_rehearsal_reports_no_device_metric(cpu_env, capsys):
    result = rehearse(capsys, "c2c512.roundtrip", "--trace", "1")
    assert result["correct"] is True
    assert result["metrics"] == {} and "breakdown" not in result


def test_no_tpu_exits_2_and_prints_no_result(cpu_env, capsys):
    rc = bench_run.main(["--workload", "c2c512.roundtrip", "--seed", "1",
                         "--seconds", "1"])
    captured = capsys.readouterr()
    assert rc == 2
    assert captured.out == ""
    assert "no TPU" in captured.err


def test_only_the_benchmark_files_is_not_a_run(tmp_path):
    # A checkout holding BENCHMARK.json and chipbench/ alone (no program)
    # fails and prints no result.
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "c2c512.roundtrip",
         "--seed", "3", "--seconds", "1"], capture_output=True, text=True,
        timeout=300, cwd=tmp_path,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert res.returncode != 0
    assert res.stdout.strip() == ""
