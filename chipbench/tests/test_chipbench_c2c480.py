"""The mixed-radix cell ``c2c480.roundtrip`` on the CPU (``--cpu-rehearsal``,
16 per axis): its files are found, a sound run is ``correct``, and the
bf16 control is not.  At 16 per axis the rehearsal runs c2c512's program,
so the planted faults are left to that cell's rehearsal, and mixed-radix
lines are covered by ``tests/test_mixed_radix.py``."""
import pytest

from chipbench import catalog
from chipbench.tests.test_chipbench_runs import cpu_env, rehearse  # noqa: F401

CELL = "c2c480.roundtrip"


def test_cell_is_480_cubed_on_one_chip_on_the_plan_fft_path():
    bench = catalog.load_benchmark()
    cell = catalog.cell(bench, CELL)
    cfg = catalog.config(bench, cell["config"])
    assert (cell["chips"], cell["traffic"]) == (1, "roundtrip")
    assert cfg["grid"] == [480] * 3 and cfg["dtype"] == "complex64"
    assert (cfg["entry"], cfg["reference"]) == ("plan_fft", "fftn")
    assert set(catalog.part("limits", CELL)) == {"inverse_err",
                                                 "drift_per_step"}


@pytest.mark.parametrize("broken", [None, "bf16"])
def test_rehearsal(cpu_env, capsys, broken):  # noqa: F811
    extra = ["--broken", broken] if broken else []
    result = rehearse(capsys, CELL, *extra)
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is (broken is None), result["checks"]
