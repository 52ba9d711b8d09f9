"""Mixed-radix lines (factors 2, 3 and 5, as in the paper's 480^3 grid)
through the normal path on every local backend, against float64 NumPy.

``plan_fft`` forward, inverse and round trip run on a (1, 1) mesh here and
on a 2x2 mesh of fake host devices in a subprocess (the device count is
fixed when jax starts); single lines of 480 go through
``transforms.apply_1d``.  Pallas runs interpreted on the CPU.
"""
import numpy as np
import pytest

from conftest import run_subprocess

BACKENDS = ("xla", "matmul", "pallas")
#: max|y - ref| / max|ref| against float64.  complex64 reads 1e-7 to 4e-7
#: here on every backend, grid and line below; 1e-5 leaves 25x of room for
#: the rounding of longer or denser contractions, and storing the output in
#: bfloat16 (8 bits of mantissa) reads about 2e-3, which fails it
#: (``test_bfloat16_storage_fails_the_tolerance``).
TOL = 1e-5
#: Every axis has a factor of 3 or 5; 240 = 2 * 120 runs the kernel's
#: four-step split (not only its dense one) on a plan's axis.
GRIDS = [(30, 24, 20), (15, 12, 60), (240, 6, 10)]


def rel_err(y, ref) -> float:
    return float(np.max(np.abs(np.asarray(y) - ref)) / np.max(np.abs(ref)))


def field(shape, seed=0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(shape)
            + 1j * rng.standard_normal(shape)).astype(np.complex64)


@pytest.mark.parametrize("n,matmul_split,kernel_split", [
    (30, (5, 6), (1, 30)),
    (60, (6, 10), (1, 60)),
    (120, (10, 12), (1, 120)),
    (480, (20, 24), (4, 120)),
])
def test_splits_of_mixed_radix_lengths(n, matmul_split, kernel_split):
    """The matmul backend splits near sqrt(n); the kernel takes the widest
    contraction up to 128 and runs shorter lines as one dense matmul."""
    from repro.core.transforms import factorize
    from repro.kernels.fft_matmul import factors, supports_length

    assert factorize(n) == matmul_split
    assert factors(n) == kernel_split
    assert supports_length(n)


@pytest.mark.parametrize("grid", GRIDS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_plan_on_one_device(cpu_mesh, backend, grid):
    from repro.core.api import plan_fft

    x = field(grid)
    plan = plan_fft(cpu_mesh, grid, backend=backend, tuning="off")
    y = plan.forward(x)
    x2 = plan.inverse(y)
    yh = np.asarray(y)
    assert rel_err(yh, np.fft.fftn(x.astype(np.complex128))) < TOL
    assert rel_err(x2, np.fft.ifftn(yh.astype(np.complex128))) < TOL
    assert rel_err(x2, x) < TOL


@pytest.mark.parametrize("backend", BACKENDS)
def test_pencil_plan_on_2x2_mesh(backend):
    """Pencil over a 2x2 mesh: every axis divides by 2 at every stage."""
    out = run_subprocess(f"""
import numpy as np
from repro.compat import AxisType, make_mesh
from repro.core.api import plan_fft
mesh = make_mesh((2, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
rng = np.random.default_rng(1)
grid = (30, 12, 20)
x = (rng.standard_normal(grid) + 1j * rng.standard_normal(grid)).astype(np.complex64)
plan = plan_fft(mesh, grid, decomp="pencil", backend={backend!r}, tuning="off")
y = plan.forward(x)
x2 = plan.inverse(y)
yh = np.asarray(y)
def rel_err(a, b):
    return float(np.max(np.abs(np.asarray(a) - b)) / np.max(np.abs(b)))
print("devices", len(plan.out_sharding.device_set))
print("fwd", rel_err(yh, np.fft.fftn(x.astype(np.complex128))))
print("inv", rel_err(x2, np.fft.ifftn(yh.astype(np.complex128))))
print("rt", rel_err(x2, x))
""", devices=4)
    vals = dict(line.split() for line in out.strip().splitlines())
    assert vals["devices"] == "4"
    for key in ("fwd", "inv", "rt"):
        assert float(vals[key]) < TOL, (key, vals[key])


@pytest.mark.parametrize("axis", [0, -1])
@pytest.mark.parametrize("kind", ["fft", "ifft"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_line_of_480(backend, kind, axis):
    import jax.numpy as jnp

    from repro.core.transforms import apply_1d

    x = field((480, 6) if axis == 0 else (6, 480), seed=2)
    y = apply_1d(jnp.asarray(x), axis, kind, backend=backend)
    ref = (np.fft.ifft if kind == "ifft" else np.fft.fft)(
        x.astype(np.complex128), axis=axis)
    assert y.dtype == np.complex64
    assert rel_err(y, ref) < TOL


def test_bfloat16_storage_fails_the_tolerance(cpu_mesh):
    """The control for ``TOL``: the same comparison of a sound forward whose
    planes are stored in bfloat16 fails it."""
    import jax.numpy as jnp

    from repro.core.api import plan_fft

    grid = GRIDS[0]
    x = field(grid)
    y = plan_fft(cpu_mesh, grid, tuning="off").forward(x)
    stored = (jnp.real(y).astype(jnp.bfloat16).astype(jnp.float32)
              + 1j * jnp.imag(y).astype(jnp.bfloat16).astype(jnp.float32))
    ref = np.fft.fftn(x.astype(np.complex128))
    assert rel_err(y, ref) < TOL
    assert rel_err(stored, ref) > 100 * TOL
