"""First-class ``DistributedFFT`` plan objects: plan-once/execute-many.

Covers the plan API redesign's acceptance criteria: wrappers delegate to
plans (bit-identical results), a reused plan performs no tuning / spec /
plan-cache work per call, ``sharded_in=True`` round-trips from a
pre-sharded input, precision-preserving dtype promotion (float64 ->
complex128 under x64), the ``PoissonSolver`` pairing, and the deprecation
of explicit knobs under tuning.

Mesh-dependent paths run in subprocesses on a fake 8-device (2x4) mesh
(see tests/README.md); introspection and warning checks run in-process on
the session's single CPU device.
"""
import warnings

import numpy as np
import pytest

from conftest import run_subprocess

COMMON = """
import os, numpy as np, jax, jax.numpy as jnp
from repro.compat import AxisType, make_mesh
mesh = make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,)*2)
from repro.core import (DistributedFFT, GLOBAL_PLAN_CACHE, PoissonSolver,
                        TuningCache, fft3d, ifft3d, plan_fft, poisson_solve)
rng = np.random.default_rng(0)
x = (rng.standard_normal((8, 8, 16)) + 1j*rng.standard_normal((8, 8, 16))).astype(np.complex64)
ref = np.fft.fftn(x)
"""


# ---------------------------------------------------------------------------
# In-process: introspection, dtype policy, deprecation
# ---------------------------------------------------------------------------

def test_plan_introspection(cpu_mesh):
    from jax.sharding import NamedSharding

    from repro.core import plan_fft
    plan = plan_fft(cpu_mesh, (16, 8, 8), kinds=("rfft", "fft", "fft"),
                    batch_shape=(3,), precompiled=False)
    assert plan.grid == (16, 8, 8)
    assert plan.eff_grid[0] == 9          # 16//2+1, no padding on 1-dev mesh
    assert plan.kinds == ("rfft", "fft", "fft")
    assert plan.batch_shape == (3,)
    assert plan.in_struct.shape == (3, 16, 8, 8)
    assert str(plan.in_struct.dtype) == "float32"   # R2C takes real input
    assert plan.out_struct.shape == (3, 9, 8, 8)
    assert str(plan.out_struct.dtype) == "complex64"
    assert plan.inv_in_struct.shape == plan.out_struct.shape
    assert plan.inv_out_struct.shape == plan.in_struct.shape
    assert str(plan.inv_out_struct.dtype) == "float32"  # irfft is real-out
    assert isinstance(plan.in_sharding, NamedSharding)
    assert isinstance(plan.out_sharding, NamedSharding)
    rep = plan.describe()
    for token in ("pencil", "xla", "n_chunks=1", "rfft", "static default"):
        assert token in rep, rep


C2C3 = ("fft",) * 3
PPB = ("fft", "fft", "dct2")


@pytest.mark.parametrize("platform,grid,dtype,kinds,want", [
    ("cpu", (512,) * 3, "complex64", C2C3, "xla"),
    ("tpu", (480,) * 3, "complex64", C2C3, "pallas"),
    ("tpu", (512,) * 3, "complex64", C2C3, "pallas"),
    ("tpu", (512,) * 3, "float32", PPB, "pallas"),
    ("tpu", (1024, 1024, 512), "complex64", C2C3, "pallas"),
    ("tpu", (2048, 16, 16), "float32", C2C3, "pallas"),
    ("tpu", (4096, 16, 16), "complex64", C2C3, "xla"),
    ("tpu", (1031, 16, 16), "complex64", C2C3, "xla"),
    ("tpu", (512,) * 3, "complex128", C2C3, "xla"),
    ("tpu", (512,) * 3, "float64", PPB, "xla"),
    ("tpu", (512,) * 3, "float32", ("rfft", "fft", "fft"), "xla"),
])
def test_default_backend_rule(platform, grid, dtype, kinds, want):
    """With no ``backend=``, a plan takes the Pallas kernel on TPU devices
    where the kernel takes every line (up to 2048, not 4096 or a prime as
    large as 1031) in a dtype without f64, for C2C and R2R kinds; xla on
    the CPU, for float64 and for R2C.  The reason names the cause."""
    from repro.core.transforms import default_backend
    backend, why = default_backend(platform, grid, dtype, kinds)
    assert backend == want
    assert why.startswith(f"{want}: ")
    if platform == "cpu":
        assert why == "xla: cpu devices"
    elif want == "pallas":
        assert why == "pallas: tpu, lines " + ", ".join(
            str(n) for n in sorted(set(grid))) + " fit"
    elif "rfft" in kinds:
        assert "rfft" in why
    elif dtype in ("complex128", "float64"):
        assert f"no {dtype} path on tpu" in why
    else:
        assert f"lines of length [{grid[0]}]" in why


@pytest.mark.parametrize("backend", [None, "xla", "matmul", "pallas"])
def test_plan_backend_cause_is_counted_and_described(cpu_mesh, backend):
    """On the CPU a plan built without ``backend=`` runs xla by the default
    rule; an explicit ``backend=`` is kept.  ``plan_cache_stats()
    ["backend"]`` counts each build under its backend and cause, and
    ``describe()`` states the reason."""
    from repro.core import plan_cache_stats, plan_fft
    before = plan_cache_stats()["backend"]
    plan = plan_fft(cpu_mesh, (8, 8, 8), backend=backend,
                    precompiled=False)
    after = plan_cache_stats()["backend"]
    want, cause = (("xla", "default") if backend is None
                   else (backend, "caller"))
    assert plan.backend == want
    assert after[want][cause] == before[want][cause] + 1
    assert sum(map(sum, (c.values() for c in after.values()))) == \
        sum(map(sum, (c.values() for c in before.values()))) + 1
    reason = ("xla: cpu devices" if backend is None
              else f"{backend}: named by the caller")
    assert plan.backend_why == reason
    assert f"  backend: {reason}" in plan.describe().splitlines()


def test_plan_fft_validates_arguments(cpu_mesh):
    from repro.core import plan_fft
    with pytest.raises(ValueError, match="2 transform dims"):
        plan_fft(cpu_mesh, (16,))
    with pytest.raises(ValueError, match="kinds"):
        plan_fft(cpu_mesh, (8, 8), kinds=("fft",))
    with pytest.raises(ValueError, match="tuning"):
        plan_fft(cpu_mesh, (8, 8), tuning="bogus")


def test_plan_rejects_wrong_shape(cpu_mesh):
    import jax.numpy as jnp

    from repro.core import plan_fft
    plan = plan_fft(cpu_mesh, (8, 8), precompiled=False)
    with pytest.raises(ValueError, match="plan expects"):
        plan.forward(jnp.zeros((4, 4), jnp.complex64))


def test_forward_dtype_promotion_matches_precision():
    """Satellite: real input promotes to the MATCHING complex dtype — no
    silent float64 -> complex64 downcast."""
    import jax.numpy as jnp

    from repro.core.api import _forward_plan_dtype, _inverse_plan_dtype
    c2c = ("fft", "fft")
    assert _forward_plan_dtype(np.float32, c2c) == jnp.dtype(jnp.complex64)
    assert _forward_plan_dtype(np.complex64, c2c) == jnp.dtype(jnp.complex64)
    # R2C / R2R pipelines keep real input real.
    assert _forward_plan_dtype(np.float32, ("rfft", "fft")) == \
        jnp.dtype(jnp.float32)
    assert _forward_plan_dtype(np.float32, ("fft", "dct2")) == \
        jnp.dtype(jnp.float32)
    # Inverse wrappers key the paired plan on the forward input dtype.
    assert _inverse_plan_dtype(np.complex64, ("rfft", "fft")) == \
        jnp.dtype(jnp.float32)
    assert _inverse_plan_dtype(np.complex64, c2c) == jnp.dtype(jnp.complex64)


def test_explicit_knobs_under_tuning_deprecated(cpu_mesh):
    """Satellite: decomp/backend/n_chunks are silently overridden by the
    tuner — passing them with tuning != 'off' now warns (once, naming every
    offending knob)."""
    import jax.numpy as jnp

    from repro.core import TuningCache, fftnd
    x = jnp.asarray((np.random.default_rng(0).standard_normal((8, 8))
                     + 0j).astype(np.complex64))
    with pytest.warns(DeprecationWarning, match="decomp/n_chunks"):
        fftnd(x, mesh=cpu_mesh, decomp="slab", n_chunks=1,
              mesh_axes=("model",), tuning="heuristic",
              tune_cache=TuningCache(None))


def test_no_deprecation_warning_when_tuning_off(cpu_mesh):
    import jax.numpy as jnp

    from repro.core import fftnd
    x = jnp.asarray((np.random.default_rng(0).standard_normal((8, 8))
                     + 0j).astype(np.complex64))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        fftnd(x, mesh=cpu_mesh, decomp="pencil", n_chunks=1, tuning="off")
    assert not [w for w in caught
                if issubclass(w.category, DeprecationWarning)]


def test_plan_cache_lru_bound():
    """The compiled-executable cache under the memo is LRU-bounded too —
    without this, memo eviction would release the plan handle but the
    executable would live on in the global cache forever."""
    from repro.core.plan import PlanCache
    pc = PlanCache(capacity=2)
    for i in range(4):
        pc.get_or_create(i, lambda i=i: f"exe{i}")
    assert pc.stats()["plans"] == 2
    assert pc.stats()["capacity"] == 2
    pc.get_or_create(2, lambda: "rebuilt")       # touch: now most recent
    pc.get_or_create(9, lambda: "exe9")          # evicts 3, not 2
    assert pc.get_or_create(2, lambda: "rebuilt").executable == "exe2"


def test_plan_fft_dim_groups_implies_hybrid(cpu_mesh):
    """dim_groups without decomp= must select hybrid on any mesh, not
    raise against a defaulted pencil."""
    from repro.core import plan_fft
    plan = plan_fft(cpu_mesh, (8, 8, 16), dim_groups=((0, 1), (2,)),
                    precompiled=False)
    assert plan.decomp == "hybrid"
    assert plan._fwd_spec.decomp.dim_groups == ((0, 1), (2,))
    with pytest.raises(ValueError, match="hybrid"):
        plan_fft(cpu_mesh, (8, 8, 16), decomp="pencil",
                 dim_groups=((0, 1), (2,)), precompiled=False)


def test_plan_fft_accepts_chunk_schedule(cpu_mesh):
    """Tentpole: n_chunks= takes a per-hop sequence — one entry per
    redistribution hop, forward hop order — carried on the spec, shown by
    describe(), and inverted hop-aware for the inverse pipeline."""
    from repro.core import plan_fft
    plan = plan_fft(cpu_mesh, (8, 8, 16), n_chunks=(4, 2),
                    precompiled=False)
    assert plan.chunk_schedule == (4, 2)
    assert plan.n_chunks == 4                       # scalar view: deepest
    assert "per-hop (4, 2)" in plan.describe()
    # the inverse executes the hops LIFO, so its schedule is reversed
    assert plan._inv_spec.chunk_schedule == (2, 4)
    # a wrong-length schedule names the hop count in the error
    with pytest.raises(ValueError, match="2 redistribution hops"):
        plan_fft(cpu_mesh, (8, 8, 16), n_chunks=(4, 2, 2),
                 precompiled=False)
    with pytest.raises(ValueError, match=">= 1"):
        plan_fft(cpu_mesh, (8, 8, 16), n_chunks=(0, 2), precompiled=False)


def test_compile_key_includes_chunk_schedule(cpu_mesh):
    """Two plans differing only in their per-hop schedule must compile to
    different executables (distinct plan-cache keys)."""
    from repro.core import GLOBAL_PLAN_CACHE, plan_fft
    s0 = GLOBAL_PLAN_CACHE.stats()
    plan_fft(cpu_mesh, (4, 4, 8), n_chunks=(2, 1))
    plan_fft(cpu_mesh, (4, 4, 8), n_chunks=(1, 2))
    s1 = GLOBAL_PLAN_CACHE.stats()
    assert s1["misses"] == s0["misses"] + 2
    # and an identical schedule is a cache hit, not a third compile
    plan_fft(cpu_mesh, (4, 4, 8), n_chunks=(1, 2))
    assert GLOBAL_PLAN_CACHE.stats()["misses"] == s1["misses"]


def test_plan_memo_lru_bound(cpu_mesh, monkeypatch):
    """Satellite: the wrapper plan memo is LRU-bounded so long-running
    serving processes sweeping many (grid, mesh, dtype) keys cannot grow
    plan handles (and their compiled executables) without bound."""
    import jax.numpy as jnp

    from repro.core import fftnd
    from repro.core.api import clear_plan_memo, plan_memo_stats

    monkeypatch.setenv("REPRO_PLAN_MEMO_SIZE", "2")
    clear_plan_memo()
    try:
        rng = np.random.default_rng(0)
        for n in (4, 8, 16, 32):
            x = jnp.asarray((rng.standard_normal((n, 4))
                             + 0j).astype(np.complex64))
            fftnd(x, mesh=cpu_mesh, precompiled=False)
            assert plan_memo_stats()["plans"] <= 2
        stats = plan_memo_stats()
        assert stats["plans"] == 2
        assert stats["capacity"] == 2
        assert stats["misses"] == 4
        assert stats["evictions"] == 2
        # reuse of a resident key must not evict it (LRU, not FIFO): touch
        # the (32, 4) plan, insert a new key, and the touched plan survives
        x32 = jnp.asarray((rng.standard_normal((32, 4))
                           + 0j).astype(np.complex64))
        fftnd(x32, mesh=cpu_mesh, precompiled=False)
        n_before = plan_memo_stats()["plans"]
        x64 = jnp.asarray((rng.standard_normal((64, 4))
                           + 0j).astype(np.complex64))
        fftnd(x64, mesh=cpu_mesh, precompiled=False)
        assert plan_memo_stats()["plans"] == n_before == 2
    finally:
        clear_plan_memo()


# ---------------------------------------------------------------------------
# Subprocess (8-device mesh): reuse, sharded-in, wrapper parity
# ---------------------------------------------------------------------------

def test_plan_reuse_hits_no_caches():
    """Acceptance: a reused plan's second .forward() does no plan-cache or
    tuner-cache work at all — the executable is held by the plan."""
    out = run_subprocess(COMMON + """
cache = TuningCache(None)
plan = plan_fft(mesh, (8, 8, 16), tuning="heuristic", tune_cache=cache)
y1 = plan.forward(jnp.asarray(x))
jax.block_until_ready(y1)
s_plan = GLOBAL_PLAN_CACHE.stats()
s_tune = cache.stats()
y2 = plan.forward(jnp.asarray(x))
jax.block_until_ready(y2)
print("plan_cache_stable", int(GLOBAL_PLAN_CACHE.stats() == s_plan))
print("tuner_cache_stable", int(cache.stats() == s_tune))
print("identical", int(np.array_equal(np.asarray(y1), np.asarray(y2))))
""")
    vals = dict(l.split() for l in out.strip().splitlines())
    assert vals["plan_cache_stable"] == "1"
    assert vals["tuner_cache_stable"] == "1"
    assert vals["identical"] == "1"


def test_wrapper_bit_identical_to_plan_api():
    """Acceptance: fft3d/ifft3d are thin shims over the same plan — results
    are bit-identical, and repeated wrapper calls reuse one memoized plan."""
    out = run_subprocess(COMMON + """
from repro.core.api import plan_memo_stats
plan = plan_fft(mesh, (8, 8, 16))
y_plan = plan(jnp.asarray(x))
y_wrap = fft3d(jnp.asarray(x), mesh=mesh)
print("fwd_identical", int(np.array_equal(np.asarray(y_plan),
                                          np.asarray(y_wrap))))
x_plan = plan.inverse(y_plan)
x_wrap = ifft3d(y_wrap, mesh=mesh)
print("inv_identical", int(np.array_equal(np.asarray(x_plan),
                                          np.asarray(x_wrap))))
n1 = plan_memo_stats()["plans"]
fft3d(jnp.asarray(x), mesh=mesh)
ifft3d(y_wrap, mesh=mesh)
print("memo_stable", int(plan_memo_stats()["plans"] == n1))
""")
    vals = dict(l.split() for l in out.strip().splitlines())
    assert vals["fwd_identical"] == "1"
    assert vals["inv_identical"] == "1"
    assert vals["memo_stable"] == "1"


def test_sharded_in_roundtrip():
    """Acceptance: sharded_in=True accepts an input already laid out in the
    stage-0 sharding, produces identical results, and chains zero-copy into
    the inverse (forward out sharding == inverse in sharding)."""
    out = run_subprocess(COMMON + """
plan = plan_fft(mesh, (8, 8, 16))
xs = jax.device_put(jnp.asarray(x), plan.in_sharding)
print("presharded", int(xs.sharding == plan.in_sharding))
y0 = plan.forward(jnp.asarray(x))
y1 = plan.forward(xs, sharded_in=True)
print("identical", int(np.array_equal(np.asarray(y0), np.asarray(y1))))
print("out_equiv", int(y1.sharding.is_equivalent_to(plan.out_sharding,
                                                    y1.ndim)))
xb = plan.inverse(y1, sharded_in=True)
print("rt", float(np.max(np.abs(np.asarray(xb) - x))))
print("fwd", float(np.max(np.abs(np.asarray(y1) - ref)) / np.max(np.abs(ref))))
""")
    vals = dict(l.split() for l in out.strip().splitlines())
    assert vals["presharded"] == "1"
    assert vals["identical"] == "1"
    assert vals["out_equiv"] == "1"
    assert float(vals["rt"]) < 1e-5
    assert float(vals["fwd"]) < 1e-5


def test_donate_execution_matches():
    out = run_subprocess(COMMON + """
plan = plan_fft(mesh, (8, 8, 16))
y0 = np.asarray(plan.forward(jnp.asarray(x)))
xd = jax.device_put(jnp.asarray(x), plan.in_sharding)
y1 = np.asarray(plan.forward(xd, sharded_in=True, donate=True))
print("identical", int(np.array_equal(y0, y1)))
""")
    assert out.split()[-1] == "1"


def test_wrapper_memoized_plan_never_donates_input():
    """Regression: plans reached through the memoized wrappers (``fftnd``)
    are shared across callers and must never compile with implicit
    donation — a wrapper call must leave the caller's input array live and
    unchanged, and explicit donation into the shared plan must be refused."""
    out = run_subprocess(COMMON + """
from repro.core import fftnd
xj = jnp.asarray(x)
snap = np.asarray(xj)
y = fftnd(xj, mesh=mesh, ndim=3)
jax.block_until_ready(y)
print("input_live", int(not xj.is_deleted()))
print("input_intact", int(np.array_equal(np.asarray(xj), snap)))
from repro.core.api import _wrapper_plan
plan = _wrapper_plan(mesh, (8, 8, 16), ("fft",)*3, (), jnp.complex64,
                     None, None, None, None, "off", None, True)
print("memo_shared", int(plan.shared))
try:
    plan(xj, donate=True)
    print("donate_refused", 0)
except ValueError as e:
    print("donate_refused", int("shared" in str(e)))
""")
    vals = dict(l.split() for l in out.strip().splitlines())
    assert vals == {"input_live": "1", "input_intact": "1",
                    "memo_shared": "1", "donate_refused": "1"}, out


def test_precompiled_false_jit_path():
    out = run_subprocess(COMMON + """
plan = plan_fft(mesh, (8, 8, 16), precompiled=False)
y = plan(jnp.asarray(x))
print("fwd", float(np.max(np.abs(np.asarray(y) - ref)) / np.max(np.abs(ref))))
s = GLOBAL_PLAN_CACHE.stats()
print("no_plan_cache_use", int(s["plans"] == 0))
""")
    vals = dict(l.split() for l in out.strip().splitlines())
    assert float(vals["fwd"]) < 1e-5
    assert vals["no_plan_cache_use"] == "1"


def test_float64_precision_preserved_under_x64():
    """Satellite: float64 input must ride a complex128 pipeline end to end
    (the old auto-cast forced complex64 and silently halved precision)."""
    out = run_subprocess("""
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from repro.compat import AxisType, make_mesh
mesh = make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,)*2)
from repro.core import fftnd, poisson_solve
rng = np.random.default_rng(0)
xr = rng.standard_normal((8, 8, 16))            # float64
y = fftnd(jnp.asarray(xr), mesh=mesh)
print("dtype", y.dtype)
ref = np.fft.fftn(xr)
print("err", float(np.max(np.abs(np.asarray(y) - ref)) / np.max(np.abs(ref))))
rhs = rng.standard_normal((16, 16, 16)); rhs -= rhs.mean()
phi = poisson_solve(jnp.asarray(rhs), mesh=mesh)
print("phidtype", phi.dtype)
dx = 2*np.pi/16
p = np.asarray(phi)
lap = (sum(np.roll(p, s, a) for a in range(3) for s in (1, -1)) - 6*p)/dx**2
print("res", float(np.max(np.abs(lap - rhs)) / np.max(np.abs(rhs))))
# R2R stages must also run at double precision (a complex64 round trip
# inside dct2 would cap the roundtrip error at ~1e-7):
from repro.core import ifftnd
xd = rng.standard_normal((8, 8, 8))
kk = ("fft", "fft", "dct2")
yd = fftnd(jnp.asarray(xd), mesh=mesh, kinds=kk)
xdb = ifftnd(yd, mesh=mesh, kinds=kk)
print("dctrt", float(np.max(np.abs(np.real(np.asarray(xdb)) - xd))))
""")
    vals = dict(l.split() for l in out.strip().splitlines())
    assert vals["dtype"] == "complex128"
    assert float(vals["err"]) < 1e-12          # double precision, not single
    assert vals["phidtype"] == "float64"
    assert float(vals["res"]) < 1e-10
    assert float(vals["dctrt"]) < 1e-12        # dct2 stayed in complex128


def test_poisson_solver_single_resolution_and_reuse():
    """PoissonSolver: one paired plan per topology (forward+inverse share a
    single tuning resolution), cached eigenvalues, reusable across solves
    with no per-call planning."""
    out = run_subprocess(COMMON + """
n = 16
rhs = rng.standard_normal((n, n, n)).astype(np.float32); rhs -= rhs.mean()
solver = PoissonSolver(mesh, (n, n, n))
phi1 = solver(jnp.asarray(rhs))
jax.block_until_ready(phi1)
s = GLOBAL_PLAN_CACHE.stats()
phi2 = solver(jnp.asarray(rhs))
jax.block_until_ready(phi2)
print("cache_stable", int(GLOBAL_PLAN_CACHE.stats() == s))
print("identical", int(np.array_equal(np.asarray(phi1), np.asarray(phi2))))
dx = 2*np.pi/n
p = np.asarray(phi1)
lap = (sum(np.roll(p, s, a) for a in range(3) for s in (1, -1)) - 6*p)/dx**2
print("res", float(np.max(np.abs(lap - rhs)) / np.max(np.abs(rhs))))
print("describe_ok", int("PoissonSolver" in solver.describe()))
""")
    vals = dict(l.split() for l in out.strip().splitlines())
    assert vals["cache_stable"] == "1"
    assert vals["identical"] == "1"
    assert float(vals["res"]) < 1e-4
    assert vals["describe_ok"] == "1"


def test_poisson_solver_joint_tuning_objective():
    """Satellite: PoissonSolver tunes ONCE per topology under the joint
    fwd+scale+inv objective — a single tuning resolution whose evidence
    shows in describe() — instead of a forward-only winner the inverse
    just has to live with."""
    out = run_subprocess(COMMON + """
import warnings
warnings.simplefilter("ignore")
cache = TuningCache(None)
solver = PoissonSolver(mesh, (16, 16, 16), tuning="heuristic",
                       tune_cache=cache)
print("objective", solver.plan.tuned.objective)
d = solver.describe()
print("joint_desc", int("joint fwd+scale+inv" in d))
print("single_resolution", int("single resolution" in d))
print("tuner_tag", int("[fwd+scale+inv]" in d))
n = 16
rhs = np.asarray((np.random.default_rng(1).standard_normal((n, n, n)))
                 .astype(np.float32))
rhs -= rhs.mean()
phi = np.asarray(solver(jnp.asarray(rhs)))
dx = 2*np.pi/n
lap = (sum(np.roll(phi, s, a) for a in range(3) for s in (1, -1))
       - 6*phi)/dx**2
print("res", float(np.max(np.abs(lap - rhs)) / np.max(np.abs(rhs))))
""")
    vals = dict(l.split() for l in out.strip().splitlines())
    assert vals["objective"] == "fwd+scale+inv"
    assert vals["joint_desc"] == "1"
    assert vals["single_resolution"] == "1"
    assert vals["tuner_tag"] == "1"
    assert float(vals["res"]) < 1e-4


def test_poisson_auto_tuning_uses_joint_measurement_key():
    """Auto mode measures candidates on the full fwd+scale+inv round trip
    and persists exactly one wisdom entry per topology, under the joint
    op= key — a fresh process is served from it without re-measuring."""
    out = run_subprocess(COMMON + """
import json, os, tempfile, warnings
warnings.simplefilter("ignore")
path = os.path.join(tempfile.mkdtemp(), "tuning.json")
cache = TuningCache(path)
solver = PoissonSolver(mesh, (8, 8, 16), tuning="auto", tune_cache=cache)
raw = json.load(open(path))
keys = list(raw["plans"])
print("nkeys", len(keys))
print("joint_key", int(all("op=fwd+scale+inv" in k for k in keys)))
print("source", solver.plan.tuned.source)
print("measured_pos", int(solver.plan.tuned.measured_s > 0))
c2 = TuningCache(path)
s2 = PoissonSolver(mesh, (8, 8, 16), tuning="auto", tune_cache=c2)
print("hit", c2.stats()["hits"])
print("same_plan", int(s2.plan.tuned == solver.plan.tuned))
""")
    vals = dict(l.split() for l in out.strip().splitlines())
    assert int(vals["nkeys"]) == 1
    assert vals["joint_key"] == "1"
    assert vals["source"] == "measured"
    assert vals["measured_pos"] == "1"
    assert int(vals["hit"]) == 1
    assert vals["same_plan"] == "1"


def test_poisson_solve_forwards_precompiled():
    """Satellite: precompiled= is no longer silently dropped — the False
    path must bypass the compiled-plan cache entirely."""
    out = run_subprocess(COMMON + """
n = 16
rhs = rng.standard_normal((n, n, n)).astype(np.float32); rhs -= rhs.mean()
phi_pre = poisson_solve(jnp.asarray(rhs), mesh=mesh, precompiled=True)
n_plans = GLOBAL_PLAN_CACHE.stats()["plans"]
print("compiled_plans", int(n_plans >= 1))
GLOBAL_PLAN_CACHE.clear()
phi_jit = poisson_solve(jnp.asarray(rhs), mesh=mesh, precompiled=False)
print("jit_no_cache", int(GLOBAL_PLAN_CACHE.stats()["plans"] == 0))
print("diff", float(np.max(np.abs(np.asarray(phi_pre) - np.asarray(phi_jit)))))
""")
    vals = dict(l.split() for l in out.strip().splitlines())
    assert vals["compiled_plans"] == "1"
    assert vals["jit_no_cache"] == "1"
    assert float(vals["diff"]) < 1e-5


def test_complex_operand_dct_stage_runs_one_fft_per_line(cpu_mesh,
                                                        monkeypatch):
    """A DCT stage after periodic C2C stages sees a complex operand and
    traces one C2C per line for both planes: a PPB solve traces 6 local
    C2Cs (3 per direction; 8 when each plane had its own), a C2C plan 3 per
    direction.  Only such a stage carries the ``r2r_complex`` scope."""
    import jax
    import jax.numpy as jnp

    from repro.core import PoissonSolver, plan_fft, transforms
    from repro.core.pipeline import build_pipeline

    grid = (16, 16, 16)
    ppb = PoissonSolver(cpu_mesh, grid, topology=("periodic", "periodic",
                                                  "bounded"),
                        precompiled=False)
    c2c = plan_fft(cpu_mesh, grid, precompiled=False)
    dct = plan_fft(cpu_mesh, grid, kinds=("dct2",) * 3, precompiled=False)
    traced = []
    c2c_fn = transforms._c2c

    def counting(x, axis, *, inverse, backend):
        traced.append("inverse" if inverse else "forward")
        return c2c_fn(x, axis, inverse=inverse, backend=backend)

    monkeypatch.setattr(transforms, "_c2c", counting)
    rhs = np.random.default_rng(0).standard_normal(grid).astype(np.float32)
    jax.block_until_ready(ppb.solve(jnp.asarray(rhs)))
    assert traced == ["forward"] * 3 + ["inverse"] * 3
    traced.clear()
    y = jax.block_until_ready(c2c.forward(jnp.asarray(rhs, jnp.complex64)))
    assert traced == ["forward"] * 3
    traced.clear()
    jax.block_until_ready(c2c.inverse(y))
    assert traced == ["inverse"] * 3

    def lowered(plan, inverse):
        struct = plan.inv_in_struct if inverse else plan.in_struct
        fn = build_pipeline(cpu_mesh, plan.pipeline_spec(inverse=inverse))
        return jax.jit(fn).lower(struct).as_text(debug_info=True)

    # The DCT axis is transformed last forward (stage 2), first inverse.
    assert "/stage2/r2r_complex/" in lowered(ppb.plan, False)
    assert "/stage0/r2r_complex/" in lowered(ppb.plan, True)
    assert str(dct.in_struct.dtype) == "float32"
    for plan in (c2c, dct):
        for inverse in (False, True):
            assert "r2r_complex" not in lowered(plan, inverse)
