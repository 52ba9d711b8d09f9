"""``backend="pallas"`` as a first-class tuner backend, plus the regressions
fixed alongside it: the matmul x64 downcast, empty-batch kernel crashes, and
early backend-name validation.  Multi-device coverage runs in subprocesses
with 8 fake host devices (see conftest.run_subprocess)."""
import numpy as np
import pytest

from conftest import run_subprocess


# ---------------------------------------------------------------------------
# Regression: matmul backend silently downcast float64 to complex64
# ---------------------------------------------------------------------------

def test_matmul_backend_preserves_float64_regression():
    """Under jax.enable_x64, the matmul backend hardcoded complex64 planes,
    silently losing double precision.  The complex dtype must now derive
    from the input, and the values must match numpy at f64 tolerance."""
    out = run_subprocess("""
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from repro.core.transforms import apply_1d
r = np.random.default_rng(0)
x = r.standard_normal((4, 32))          # float64 under x64
y = apply_1d(jnp.asarray(x), -1, "fft", backend="matmul")
print("c2c_dtype", y.dtype)
print("c2c_ok", int(np.allclose(np.asarray(y), np.fft.fft(x, axis=-1),
                                rtol=1e-10, atol=1e-9)))
yr = apply_1d(jnp.asarray(x), -1, "rfft", backend="matmul")
print("rfft_dtype", yr.dtype)
print("rfft_ok", int(np.allclose(np.asarray(yr), np.fft.rfft(x, axis=-1),
                                 rtol=1e-10, atol=1e-9)))
xc = x + 1j * r.standard_normal((4, 32))
yc = apply_1d(jnp.asarray(xc), 0, "fft", backend="matmul")
print("cin_dtype", yc.dtype)
print("cin_ok", int(np.allclose(np.asarray(yc), np.fft.fft(xc, axis=0),
                                rtol=1e-10, atol=1e-9)))
""", devices=1)
    vals = dict(l.split() for l in out.strip().splitlines())
    assert vals["c2c_dtype"] == "complex128"
    assert vals["rfft_dtype"] == "complex128"
    assert vals["cin_dtype"] == "complex128"
    assert vals["c2c_ok"] == vals["rfft_ok"] == vals["cin_ok"] == "1"


def test_matmul_backend_complex64_unchanged():
    """Without x64 the matmul backend still computes in complex64."""
    import jax.numpy as jnp
    from repro.core.transforms import apply_1d
    r = np.random.default_rng(1)
    x = r.standard_normal((3, 16)).astype(np.float32)
    y = apply_1d(jnp.asarray(x), -1, "fft", backend="matmul")
    assert y.dtype == jnp.complex64
    np.testing.assert_allclose(np.asarray(y), np.fft.fft(x, axis=-1),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Satellite: early backend validation in plan_fft / apply_1d
# ---------------------------------------------------------------------------

def test_plan_fft_rejects_unknown_backend(cpu_mesh):
    from repro.core.api import plan_fft
    with pytest.raises(ValueError, match=r"unknown backend 'cufft'"):
        plan_fft(cpu_mesh, (8, 8), backend="cufft")
    # the error names the supported set
    with pytest.raises(ValueError, match="xla, matmul, pallas"):
        plan_fft(cpu_mesh, (8, 8), backend="fftw")


def test_apply_1d_rejects_unknown_backend():
    import jax.numpy as jnp
    from repro.core.transforms import apply_1d
    with pytest.raises(ValueError, match="unknown backend"):
        apply_1d(jnp.zeros((2, 8), jnp.complex64), -1, "fft",
                 backend="cufft")


# ---------------------------------------------------------------------------
# apply_1d parity: the pallas backend against xla, every kind it serves
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["fft", "ifft", "rfft", "dct2", "dst2"])
def test_apply_1d_pallas_matches_xla(kind):
    import jax.numpy as jnp
    from repro.core.transforms import apply_1d
    r = np.random.default_rng(2)
    if kind in ("fft", "ifft"):
        x = (r.standard_normal((3, 24)) + 1j * r.standard_normal((3, 24))
             ).astype(np.complex64)
    else:
        x = r.standard_normal((3, 24)).astype(np.float32)
    got = np.asarray(apply_1d(jnp.asarray(x), -1, kind, backend="pallas"))
    ref = np.asarray(apply_1d(jnp.asarray(x), -1, kind, backend="xla"))
    scale = max(np.max(np.abs(ref)), 1e-9)
    np.testing.assert_allclose(got / scale, ref / scale, atol=2e-5)


def test_apply_1d_pallas_irfft_roundtrip():
    import jax.numpy as jnp
    from repro.core.transforms import apply_1d
    r = np.random.default_rng(4)
    x = r.standard_normal((2, 20)).astype(np.float32)
    half = apply_1d(jnp.asarray(x), -1, "rfft", backend="pallas")
    back = apply_1d(half, -1, "irfft", backend="pallas", irfft_n=20)
    np.testing.assert_allclose(np.asarray(back), x, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Tuner: pallas is enumerated, priced, persisted, and restricted away
# ---------------------------------------------------------------------------

def test_enumerate_candidates_includes_pallas(cpu_mesh):
    from repro.core.tuner import BACKENDS, enumerate_candidates
    assert BACKENDS == ("xla", "matmul", "pallas")
    cands = enumerate_candidates((8, 8, 8), cpu_mesh, ("fft",) * 3)
    assert {c.backend for c in cands} >= {"xla", "matmul", "pallas"}
    # restricted enumerations honor the subset
    only = enumerate_candidates((8, 8, 8), cpu_mesh, ("fft",) * 3,
                                backends=("xla", "matmul"))
    assert {c.backend for c in only} == {"xla", "matmul"}


TUNE_COMMON = """
import os, tempfile, numpy as np, jax, jax.numpy as jnp
os.environ["REPRO_TUNING_CACHE"] = os.path.join(tempfile.mkdtemp(),
                                                "global.json")
from repro.compat import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
from repro.core import TunedPlan, TuningCache, tune, tuning_key
path = os.path.join(tempfile.mkdtemp(), "tuning.json")
"""


def test_pallas_wisdom_roundtrips_and_restricted_caller_skips_it():
    """Acceptance: a backend="pallas" wisdom entry survives the JSON cache
    round trip, is served back to unrestricted callers, and is *skipped*
    (re-tuned, not crashed on) by a backends=("xla","matmul") caller —
    whose winner must then not be pallas and must not be persisted."""
    out = run_subprocess(TUNE_COMMON + """
grid = (8, 8, 16)
key = tuning_key(grid=grid, mesh_shape=(2, 4), mesh_axes=("data", "model"),
                 kinds=("fft",) * 3, dtype="complex64", inverse=False,
                 platform=jax.default_backend())
seed = TunedPlan(decomp="pencil", mesh_axes=("data", "model"),
                 backend="pallas", n_chunks=1, predicted_s=1e-4,
                 measured_s=2e-4, source="measured", baseline_s=3e-4,
                 ts=123.0)
c = TuningCache(path)
c.put(key, seed)
# fresh cache object = fresh process: the entry must come back from JSON
reread = TuningCache(path).get(key)
print("roundtrip", int(reread == seed))
print("reread_backend", reread.backend)
# an unrestricted auto caller is served the pallas hit verbatim
served = tune(grid, mesh, cache=TuningCache(path))
print("served_backend", served.backend)
# a restricted caller must skip the pallas hit and re-tune
p_r = tune(grid, mesh, cache=TuningCache(path),
           backends=("xla", "matmul"), top_k=1, repeats=1)
print("restricted_backend_ok", int(p_r.backend in ("xla", "matmul")))
print("restricted_source", p_r.source)
# ...and must not have overwritten the pallas wisdom on disk
after = TuningCache(path).get(key)
print("wisdom_intact", int(after == seed))
""")
    vals = dict(l.split() for l in out.strip().splitlines())
    assert vals["roundtrip"] == "1"
    assert vals["reread_backend"] == "pallas"
    assert vals["served_backend"] == "pallas"
    assert vals["restricted_backend_ok"] == "1"
    assert vals["restricted_source"] == "measured"
    assert vals["wisdom_intact"] == "1"


# ---------------------------------------------------------------------------
# End-to-end pipelines on the fake 8-device mesh
# ---------------------------------------------------------------------------

def test_pallas_pipeline_matches_xla_pencil_and_chunked_slab():
    """Acceptance: pallas plans match xla at fp32 tolerance on a 3-D pencil
    and on a chunked slab, including a heterogeneous chunk schedule."""
    out = run_subprocess("""
import numpy as np, jax, jax.numpy as jnp
from repro.compat import make_mesh
from repro.core.api import plan_fft
mesh = make_mesh((2, 4), ("data", "model"))
r = np.random.default_rng(5)
x = (r.standard_normal((16, 16, 32)) + 1j * r.standard_normal((16, 16, 32))
     ).astype(np.complex64)
xj = jnp.asarray(x)

def close(a, b, tol=2e-4):
    a, b = np.asarray(a), np.asarray(b)
    s = max(np.max(np.abs(b)), 1e-9)
    return int(np.allclose(a / s, b / s, atol=tol))

ref = plan_fft(mesh, (16, 16, 32), decomp="pencil").forward(xj)
pen = plan_fft(mesh, (16, 16, 32), decomp="pencil",
               backend="pallas").forward(xj)
print("pencil_ok", close(pen, ref))
slab = plan_fft(mesh, (16, 16, 32), decomp="slab", backend="pallas",
                n_chunks=4).forward(xj)
print("chunked_slab_ok", close(slab, ref))
het = plan_fft(mesh, (16, 16, 32), decomp="pencil", backend="pallas",
               n_chunks=(2, 4)).forward(xj)
print("hetero_sched_ok", close(het, ref))
inv = plan_fft(mesh, (16, 16, 32), decomp="pencil", backend="pallas")
print("roundtrip_ok", close(inv.inverse(inv.forward(xj)), x, 1e-4))
""")
    vals = dict(l.split() for l in out.strip().splitlines())
    assert vals["pencil_ok"] == "1"
    assert vals["chunked_slab_ok"] == "1"
    assert vals["hetero_sched_ok"] == "1"
    assert vals["roundtrip_ok"] == "1"


def test_persisted_pallas_plan_replays_through_plan_fft():
    """Acceptance: a persisted pallas TunedPlan replays through plan_fft
    (cache hit, no re-tuning) and matches the xla plan's output."""
    out = run_subprocess(TUNE_COMMON + """
from repro.core.api import plan_fft
grid = (16, 16, 32)
key = tuning_key(grid=grid, mesh_shape=(2, 4), mesh_axes=("data", "model"),
                 kinds=("fft",) * 3, dtype="complex64", inverse=False,
                 platform=jax.default_backend())
seed = TunedPlan(decomp="pencil", mesh_axes=("data", "model"),
                 backend="pallas", n_chunks=2, predicted_s=1e-4,
                 measured_s=2e-4, source="measured", baseline_s=3e-4)
c = TuningCache(path)
c.put(key, seed)
plan = plan_fft(mesh, grid, tuning="auto", tune_cache=TuningCache(path))
print("backend", plan.backend)
r = np.random.default_rng(6)
x = (r.standard_normal(grid) + 1j * r.standard_normal(grid)
     ).astype(np.complex64)
got = np.asarray(plan.forward(jnp.asarray(x)))
ref = np.asarray(plan_fft(mesh, grid, decomp="pencil").forward(
    jnp.asarray(x)))
s = max(np.max(np.abs(ref)), 1e-9)
print("match_xla", int(np.allclose(got / s, ref / s, atol=2e-4)))
""")
    vals = dict(l.split() for l in out.strip().splitlines())
    assert vals["backend"] == "pallas"
    assert vals["match_xla"] == "1"


def test_compiled_pallas_refuses_float64(cpu_mesh, monkeypatch):
    """Off the CPU platform the Pallas kernel is compiled by Mosaic, which
    has no f64: a complex128 pallas plan is refused when it is planned,
    and the tuner never enumerates one."""
    import jax.numpy as jnp

    from repro.core.api import plan_fft
    from repro.core.plan import TuningCache
    from repro.core.tuner import tune
    from repro.kernels import ops
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    assert ops.supports_dtype(jnp.complex64)
    assert not ops.supports_dtype(jnp.complex128)
    with pytest.raises(ValueError, match="no complex128 path"):
        plan_fft(cpu_mesh, (8, 8, 8), dtype=jnp.complex128,
                 backend="pallas")
    with pytest.raises(ValueError, match="no valid plan"):
        tune((8, 8, 8), cpu_mesh, mode="heuristic", dtype=jnp.complex128,
             backends=("pallas",), cache=TuningCache())


def test_pallas_refuses_lines_over_its_vmem(cpu_mesh):
    """A grid with lines the kernel cannot take (N = 4096 does not fit its
    VMEM) is refused when a pallas plan is made, and the tuner drops
    pallas for it instead of proposing a plan that cannot compile."""
    from repro.core.api import plan_fft
    from repro.core.plan import TuningCache
    from repro.core.tuner import tune
    with pytest.raises(ValueError, match=r"lines of length \[4096\]"):
        plan_fft(cpu_mesh, (4096, 4, 4), backend="pallas")
    with pytest.raises(ValueError, match="no valid plan"):
        tune((4096, 4, 4), cpu_mesh, mode="heuristic",
             backends=("pallas",), cache=TuningCache())
    plan = tune((4096, 4, 4), cpu_mesh, mode="heuristic",
                cache=TuningCache())
    assert plan.backend != "pallas"
