"""Local transform correctness: C2C/R2C/R2R vs naive O(N^2) oracles,
plus hypothesis property tests (linearity, Parseval, roundtrips)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # hermetic container: fixed-seed shim
    from _propcheck import given, settings, strategies as st

from repro.core.transforms import ALL_KINDS, apply_1d, factorize, \
    fourstep_fft_planes

rng = np.random.default_rng(42)


def naive_dft(x, axis, inverse=False):
    x = np.moveaxis(np.asarray(x, np.complex128), axis, -1)
    n = x.shape[-1]
    k = np.arange(n)
    sign = 1 if inverse else -1
    w = np.exp(sign * 2j * np.pi * np.outer(k, k) / n)
    out = x @ w.T
    if inverse:
        out = out / n
    return np.moveaxis(out, -1, axis)


def naive_dct2(x, axis):
    x = np.moveaxis(np.asarray(x, np.float64), axis, -1)
    n = x.shape[-1]
    k, m = np.arange(n), np.arange(n)
    mat = 2 * np.cos(np.pi * np.outer(k, 2 * m + 1) / (2 * n))
    return np.moveaxis(x @ mat.T, -1, axis)


def naive_dst2(x, axis):
    x = np.moveaxis(np.asarray(x, np.float64), axis, -1)
    n = x.shape[-1]
    k, m = np.arange(n), np.arange(n)
    mat = 2 * np.sin(np.pi * np.outer(k + 1, 2 * m + 1) / (2 * n))
    return np.moveaxis(x @ mat.T, -1, axis)


def naive_dct3(x, axis):
    x = np.moveaxis(np.asarray(x, np.float64), axis, -1)
    n = x.shape[-1]
    k, m = np.arange(n), np.arange(n)
    mat = 2 * np.cos(np.pi * np.outer(2 * k + 1, m) / (2 * n))
    mat[:, 0] = 1.0
    return np.moveaxis(x @ mat.T, -1, axis)


def naive_dst3(x, axis):
    x = np.moveaxis(np.asarray(x, np.float64), axis, -1)
    n = x.shape[-1]
    k, m = np.arange(n), np.arange(n)
    mat = 2 * np.sin(np.pi * np.outer(2 * k + 1, m + 1) / (2 * n))
    mat[:, -1] = (-1.0) ** k
    return np.moveaxis(x @ mat.T, -1, axis)


NAIVE_R2R = {"dct2": naive_dct2, "dct3": naive_dct3,
             "dst2": naive_dst2, "dst3": naive_dst3}


@pytest.mark.parametrize("n", [4, 8, 12, 16, 30, 64, 128])
@pytest.mark.parametrize("backend", ["xla", "matmul"])
def test_c2c_matches_naive(n, backend):
    x = (rng.standard_normal((3, n)) + 1j * rng.standard_normal((3, n))
         ).astype(np.complex64)
    got = np.asarray(apply_1d(jnp.asarray(x), 1, "fft", backend=backend))
    ref = naive_dft(x, 1)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4 * n)


@pytest.mark.parametrize("backend", ["xla", "matmul"])
def test_ifft_roundtrip(backend):
    x = (rng.standard_normal((2, 32)) + 1j * rng.standard_normal((2, 32))
         ).astype(np.complex64)
    y = apply_1d(jnp.asarray(x), -1, "fft", backend=backend)
    xb = np.asarray(apply_1d(y, -1, "ifft", backend=backend))
    np.testing.assert_allclose(xb, x, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n", [8, 16, 17, 32])
@pytest.mark.parametrize("backend", ["xla", "matmul"])
def test_rfft_irfft(n, backend):
    x = rng.standard_normal((4, n)).astype(np.float32)
    y = apply_1d(jnp.asarray(x), -1, "rfft", backend=backend)
    assert y.shape[-1] == n // 2 + 1
    ref = np.fft.rfft(x, axis=-1)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=2e-4, atol=2e-4 * n)
    xb = apply_1d(y, -1, "irfft", backend=backend, irfft_n=n)
    np.testing.assert_allclose(np.asarray(xb), x, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("kind,ref_fn", [("dct2", naive_dct2),
                                         ("dst2", naive_dst2)])
@pytest.mark.parametrize("n", [4, 8, 16, 32])
def test_r2r_matches_naive(kind, ref_fn, n):
    x = rng.standard_normal((3, n)).astype(np.float32)
    got = np.asarray(apply_1d(jnp.asarray(x), 1, kind))
    np.testing.assert_allclose(got, ref_fn(x, 1), rtol=2e-4, atol=2e-4 * n)


@pytest.mark.parametrize("fwd,inv", [("dct2", "dct3"), ("dst2", "dst3")])
def test_r2r_roundtrip(fwd, inv):
    n = 16
    x = rng.standard_normal((2, n)).astype(np.float32)
    y = apply_1d(jnp.asarray(x), -1, fwd)
    xb = np.asarray(apply_1d(y, -1, inv)) / (2 * n)
    np.testing.assert_allclose(xb, x, rtol=1e-4, atol=1e-4)


#: (shape, axis): the unbatched last-axis line of the PPB path's first
#: form, and a batched operand transformed along a middle axis.
COMPLEX_LAYOUTS = {"last": ((2, None), -1), "middle": ((3, None, 5), 1)}


def _complex_operand(layout, n):
    shape, axis = COMPLEX_LAYOUTS[layout]
    shape = tuple(n if d is None else d for d in shape)
    x = (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
         ).astype(np.complex64)
    return x, axis


@pytest.mark.parametrize("layout", sorted(COMPLEX_LAYOUTS))
@pytest.mark.parametrize("n", [7, 8, 12])
@pytest.mark.parametrize("backend", ["xla", "matmul", "pallas"])
@pytest.mark.parametrize("kind", sorted(NAIVE_R2R))
def test_r2r_complex_input_planes(kind, backend, n, layout):
    """R2R of a complex operand = R2R(re) + i R2R(im), from one complex FFT
    per line (the PPB path: a DCT stage after periodic C2C stages)."""
    x, axis = _complex_operand(layout, n)
    got = apply_1d(jnp.asarray(x), axis, kind, backend=backend)
    assert got.dtype == jnp.complex64 and got.shape == x.shape
    ref = NAIVE_R2R[kind](x.real, axis) + 1j * NAIVE_R2R[kind](x.imag, axis)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-4, atol=1e-3)


@pytest.mark.parametrize("layout", sorted(COMPLEX_LAYOUTS))
@pytest.mark.parametrize("backend", ["xla", "matmul", "pallas"])
@pytest.mark.parametrize("fwd,inv", [("dct2", "dct3"), ("dst2", "dst3")])
def test_r2r_complex_roundtrip(fwd, inv, backend, layout):
    n = 12
    x, axis = _complex_operand(layout, n)
    y = apply_1d(jnp.asarray(x), axis, fwd, backend=backend)
    xb = np.asarray(apply_1d(y, axis, inv, backend=backend)) / (2 * n)
    np.testing.assert_allclose(xb, x, rtol=1e-4, atol=1e-4)


def test_factorize():
    for n in [1, 2, 4, 30, 64, 512, 1021]:
        a, b = factorize(n)
        assert a * b == n and a <= b


# ---------------------------------------------------------------------------
# hypothesis property tests
# ---------------------------------------------------------------------------

shapes = st.tuples(st.integers(1, 4), st.sampled_from([4, 8, 12, 16, 32]))


@settings(max_examples=20, deadline=None)
@given(shape=shapes, backend=st.sampled_from(["xla", "matmul"]),
       seed=st.integers(0, 2**31 - 1))
def test_fft_linearity(shape, backend, seed):
    r = np.random.default_rng(seed)
    x = r.standard_normal(shape).astype(np.float32).astype(np.complex64)
    y = r.standard_normal(shape).astype(np.float32).astype(np.complex64)
    a = 2.5
    lhs = apply_1d(jnp.asarray(a * x + y), -1, "fft", backend=backend)
    rhs = a * apply_1d(jnp.asarray(x), -1, "fft", backend=backend) \
        + apply_1d(jnp.asarray(y), -1, "fft", backend=backend)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs),
                               rtol=1e-3, atol=1e-3)


@settings(max_examples=20, deadline=None)
@given(shape=shapes, seed=st.integers(0, 2**31 - 1))
def test_fft_parseval(shape, seed):
    r = np.random.default_rng(seed)
    x = (r.standard_normal(shape) + 1j * r.standard_normal(shape)
         ).astype(np.complex64)
    y = np.asarray(apply_1d(jnp.asarray(x), -1, "fft"))
    n = shape[-1]
    np.testing.assert_allclose(np.sum(np.abs(y) ** 2) / n,
                               np.sum(np.abs(x) ** 2), rtol=1e-3)


@settings(max_examples=15, deadline=None)
@given(n=st.sampled_from([4, 8, 16, 32]), seed=st.integers(0, 2**31 - 1))
def test_fourstep_planes_match_jnp(n, seed):
    r = np.random.default_rng(seed)
    xr = r.standard_normal((2, n)).astype(np.float32)
    xi = r.standard_normal((2, n)).astype(np.float32)
    outr, outi = fourstep_fft_planes(jnp.asarray(xr), jnp.asarray(xi))
    ref = np.fft.fft(xr + 1j * xi, axis=-1)
    np.testing.assert_allclose(np.asarray(outr), ref.real, rtol=2e-3,
                               atol=2e-3 * n)
    np.testing.assert_allclose(np.asarray(outi), ref.imag, rtol=2e-3,
                               atol=2e-3 * n)
