"""LogP perf model consistency: the analytic transpose volume must match
the all-to-all bytes in the compiled pipeline's HLO."""
import math

import pytest

from conftest import run_subprocess
from repro.core.decomp import local_shape, pencil, slab
from repro.core.perfmodel import (CPU_CORE, TPU_V5E, fft_total_flops,
                                  predict_fft_time)
from repro.core.redistribute import transpose_cost_bytes


def test_fft_flops_formula():
    n = 64
    got = fft_total_flops((n, n, n))
    assert got == pytest.approx(5 * n ** 3 * 3 * math.log2(n), rel=1e-6)


def test_predict_monotone_in_ranks():
    grid = (256, 256, 256)
    t4 = predict_fft_time(grid, pencil("a", "b"), {"a": 2, "b": 2}, TPU_V5E)
    t16 = predict_fft_time(grid, pencil("a", "b"), {"a": 4, "b": 4}, TPU_V5E)
    assert t16["t_comp_s"] < t4["t_comp_s"]


def test_overlap_bounds():
    import dataclasses
    grid = (128, 128, 128)
    m0 = dataclasses.replace(CPU_CORE, overlap=0.0)
    m1 = dataclasses.replace(CPU_CORE, overlap=1.0)
    t0 = predict_fft_time(grid, slab("a"), {"a": 4}, m0)
    t1 = predict_fft_time(grid, slab("a"), {"a": 4}, m1)
    # Eq. 2: perfect overlap = max(), bulk = sum
    assert t1["t_total_s"] == pytest.approx(
        max(t0["t_comp_s"], t0["t_comm_s"]), rel=1e-6)
    assert t0["t_total_s"] == pytest.approx(
        t0["t_comp_s"] + t0["t_comm_s"], rel=1e-6)


def test_transpose_bytes_match_hlo():
    """Analytic per-rank transpose volume == HLO all-to-all operand bytes."""
    out = run_subprocess("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding
from repro.compat import AxisType, make_mesh
mesh = make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,)*2)
from repro.core import make_decomposition, make_spec, build_pipeline
from repro.distributed.roofline import parse_hlo_collectives
dec = make_decomposition("pencil", ("data", "model"))
spec = make_spec(mesh, (16, 16, 16), dec, ("fft",)*3)
arg = jax.ShapeDtypeStruct((16, 16, 16), jnp.complex64,
                           sharding=NamedSharding(mesh, spec.in_spec()))
with mesh:
    co = jax.jit(build_pipeline(mesh, spec)).lower(arg).compile()
colls, per_kind = parse_hlo_collectives(co.as_text(), 8)
print("a2a_ops", len([c for c in colls if c.kind == "all-to-all"]))
print("a2a_bytes", per_kind.get("all-to-all", 0))
""", devices=8)
    vals = dict(l.split() for l in out.strip().splitlines())
    assert int(vals["a2a_ops"]) == 2          # one per stage boundary
    # analytic: per-device block c64(8B); redist1 over "data"(2),
    # redist2 over "model"(4)
    d1 = local_shape(pencil("data", "model").stages[0], (16, 16, 16),
                     {"data": 2, "model": 4})
    d2 = local_shape(pencil("data", "model").stages[1], (16, 16, 16),
                     {"data": 2, "model": 4})
    v1 = transpose_cost_bytes(d1, 8, 2)       # wire bytes (off-device part)
    v2 = transpose_cost_bytes(d2, 8, 4)
    # HLO operand bytes count the full shuffled block (incl. the diagonal
    # kept locally): full = wire * n/(n-1)
    full = v1 * 2 / 1 + v2 * 4 / 3
    got = float(vals["a2a_bytes"])
    assert got == pytest.approx(full, rel=0.35), (got, full)


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize("platform,kind,want", [
    ("cpu", "cpu", CPU_CORE),
    ("tpu", "TPU v5 lite", TPU_V5E),
    ("tpu", "TPU v4", None),          # a TPU with no row in the table
])
def test_default_machine_keys_on_device_kind(monkeypatch, platform, kind,
                                             want):
    """Machine constants come from ``device_kind``: an accelerator kind
    missing from ``ACCELERATORS`` raises instead of borrowing v5e's."""
    import jax

    from repro.core import tuner
    from repro.core.perfmodel import default_machine
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice(platform, kind)])
    if want is None:
        with pytest.raises(ValueError, match="TPU v4"):
            default_machine()
    else:
        assert default_machine() is want
        assert tuner.default_machine() is want
