"""Pallas kernel validation: shape/dtype sweep vs the pure-jnp oracle,
executed with interpret=True (the CPU platform's mode)."""
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # hermetic container: fixed-seed shim
    from _propcheck import given, settings, strategies as st

from conftest import run_subprocess
from repro.kernels.fft_matmul import (VMEM_LIMIT, fft_cols, line_flops,
                                      supports_length, vmem_bytes)
from repro.kernels.ops import fft1d, ifft1d
from repro.kernels.ref import fft1d_planes_ref, fft1d_ref, ifft1d_ref

rng = np.random.default_rng(7)


@pytest.mark.parametrize("b,n", [(1, 16), (4, 64), (8, 128), (3, 96),
                                 (130, 512), (2, 33), (5, 1024)])
def test_kernel_forward_sweep(b, n):
    x = (rng.standard_normal((b, n)) + 1j * rng.standard_normal((b, n))
         ).astype(np.complex64)
    got = np.asarray(fft1d(jnp.asarray(x)))
    ref = np.asarray(fft1d_ref(jnp.asarray(x)))
    scale = max(np.max(np.abs(ref)), 1e-9)
    np.testing.assert_allclose(got / scale, ref / scale, atol=5e-6)


@pytest.mark.parametrize("b,n", [(4, 64), (2, 256)])
def test_kernel_inverse_sweep(b, n):
    x = (rng.standard_normal((b, n)) + 1j * rng.standard_normal((b, n))
         ).astype(np.complex64)
    got = np.asarray(ifft1d(jnp.asarray(x)))
    ref = np.asarray(ifft1d_ref(jnp.asarray(x)))
    scale = max(np.max(np.abs(ref)), 1e-9)
    np.testing.assert_allclose(got / scale, ref / scale, atol=5e-6)


@pytest.mark.parametrize("axis", [0, 1, 2, -1])
def test_kernel_axis_handling(axis):
    x = (rng.standard_normal((4, 6, 8)) + 1j * rng.standard_normal((4, 6, 8))
         ).astype(np.complex64)
    got = np.asarray(fft1d(jnp.asarray(x), axis))
    ref = np.fft.fft(x, axis=axis)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_kernel_plane_dtypes(dtype):
    xr = rng.standard_normal((4, 32)).astype(dtype)
    xi = rng.standard_normal((4, 32)).astype(dtype)
    # the kernel transforms axis 1 of (G, N, M): lines on lanes
    outr, outi = fft_cols(jnp.asarray(xr).T[None], jnp.asarray(xi).T[None],
                          interpret=True)
    refr, refi = fft1d_planes_ref(jnp.asarray(xr), jnp.asarray(xi))
    np.testing.assert_allclose(np.asarray(outr[0]).T, np.asarray(refr),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(outi[0]).T, np.asarray(refi),
                               rtol=1e-3, atol=1e-3)


def test_kernel_batch_tiling():
    """Batch not a multiple of the tile must pad+trim correctly."""
    for b in (1, 127, 129, 300):
        x = (rng.standard_normal((b, 64))
             + 1j * rng.standard_normal((b, 64))).astype(np.complex64)
        got = np.asarray(fft1d(jnp.asarray(x)))
        np.testing.assert_allclose(got, np.fft.fft(x, axis=-1),
                                   rtol=1e-4, atol=1e-3)


@settings(max_examples=15, deadline=None)
@given(b=st.integers(1, 9), n=st.sampled_from([8, 16, 32, 48, 64, 128]),
       inverse=st.booleans(), seed=st.integers(0, 2**31 - 1))
def test_kernel_property_roundtrip(b, n, inverse, seed):
    r = np.random.default_rng(seed)
    x = (r.standard_normal((b, n)) + 1j * r.standard_normal((b, n))
         ).astype(np.complex64)
    fwd = fft1d(jnp.asarray(x)) if not inverse else ifft1d(jnp.asarray(x))
    back = ifft1d(fwd) if not inverse else fft1d(fwd)
    np.testing.assert_allclose(np.asarray(back), x, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# Parity sweep for the routed backend: prime N, every axis, both directions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [13, 17, 31])     # factorize -> (1, n) degenerate
@pytest.mark.parametrize("inverse", [False, True])
def test_kernel_prime_n_degenerate(n, inverse):
    """A prime N factorizes as (1, n): a single dense DFT matmul, still
    exact vs jnp.fft."""
    x = (rng.standard_normal((4, n)) + 1j * rng.standard_normal((4, n))
         ).astype(np.complex64)
    fn, ref = (ifft1d, np.fft.ifft) if inverse else (fft1d, np.fft.fft)
    got = np.asarray(fn(jnp.asarray(x)))
    np.testing.assert_allclose(got, ref(x, axis=-1), rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("axis", [0, 1, 2, -1, -2])
@pytest.mark.parametrize("inverse", [False, True])
def test_kernel_every_axis_both_directions(axis, inverse):
    x = (rng.standard_normal((3, 5, 8)) + 1j * rng.standard_normal((3, 5, 8))
         ).astype(np.complex64)
    fn, ref = (ifft1d, np.fft.ifft) if inverse else (fft1d, np.fft.fft)
    got = np.asarray(fn(jnp.asarray(x), axis))
    np.testing.assert_allclose(got, ref(x, axis=axis), rtol=1e-4, atol=1e-4)


def test_kernel_complex128_parity_under_x64():
    """complex128 input stays complex128 end-to-end and matches np.fft at
    double precision (the f64 plane path, interpret mode)."""
    out = run_subprocess("""
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from repro.kernels.ops import fft1d, ifft1d
r = np.random.default_rng(3)
x = r.standard_normal((5, 48)) + 1j * r.standard_normal((5, 48))
y = fft1d(jnp.asarray(x))
print("dtype", y.dtype)
print("fwd_ok", int(np.allclose(np.asarray(y), np.fft.fft(x, axis=-1),
                                rtol=1e-10, atol=1e-9)))
yi = ifft1d(jnp.asarray(x), 0)
print("inv_ok", int(np.allclose(np.asarray(yi), np.fft.ifft(x, axis=0),
                                rtol=1e-10, atol=1e-9)))
""", devices=1)
    vals = dict(l.split() for l in out.strip().splitlines())
    assert vals["dtype"] == "complex128"
    assert vals["fwd_ok"] == "1" and vals["inv_ok"] == "1"


def test_kernel_empty_batch_regression():
    """Regression: b == 0 used to build a zero grid / divide by zero in the
    pad computation.  Must return an empty result of the right shape/dtype."""
    outr, outi = fft_cols(jnp.zeros((1, 16, 0), jnp.float32),
                          jnp.zeros((1, 16, 0), jnp.float32), interpret=True)
    assert outr.shape == (1, 16, 0) and outi.shape == (1, 16, 0)
    assert outr.dtype == jnp.float32
    # an empty leading batch as well
    er, _ = fft_cols(jnp.zeros((0, 16, 4), jnp.float32),
                     jnp.zeros((0, 16, 4), jnp.float32), interpret=True)
    assert er.shape == (0, 16, 4)
    # the ops wrapper guards the same way (any-rank empty input)
    y = fft1d(jnp.zeros((0, 8, 16), jnp.complex64), -1)
    assert y.shape == (0, 8, 16) and y.dtype == jnp.complex64
    y2 = ifft1d(jnp.zeros((4, 0, 16), jnp.complex64), 1)
    assert y2.shape == (4, 0, 16)


def test_kernel_fused_twiddle_epilogue():
    """twiddle=(er, ei) must equal an elementwise post-multiply."""
    x = (rng.standard_normal((6, 24)) + 1j * rng.standard_normal((6, 24))
         ).astype(np.complex64)
    t = np.exp(-1j * np.pi * np.arange(24) / 48).astype(np.complex64)
    got = np.asarray(fft1d(jnp.asarray(x), twiddle=jnp.asarray(t)))
    ref = t * np.fft.fft(x, axis=-1)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-3)
    # and composes with the inverse direction
    got_i = np.asarray(ifft1d(jnp.asarray(x), twiddle=jnp.asarray(t)))
    np.testing.assert_allclose(got_i, t * np.fft.ifft(x, axis=-1),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,takes", [
    (2048, True),     # the largest power of two that fits VMEM
    (2560, True),     # (20, 128): 15 MiB
    (4096, False),    # 24 MiB: Mosaic runs out of VMEM compiling it
    (904, True),      # (8, 113)
    (905, False),     # 5 * 181: the dense (1, 905) split is over the limit
    (32768, False),   # would be a dense N x N operand
])
def test_kernel_line_length_limit(n, takes):
    """Lines whose kernel program does not fit VMEM are refused before any
    operand is built, in interpret mode as on the chip; the kernel's flop
    count splits into MXU and VPU work."""
    assert supports_length(n) is takes
    assert (vmem_bytes(n) <= VMEM_LIMIT) is takes
    mxu, vpu = line_flops(n)
    assert mxu > 0 and vpu > 0
    if not takes:
        planes = jnp.zeros((1, n, 1), jnp.float32)
        with pytest.raises(ValueError, match=f"lines of length {n}"):
            fft_cols(planes, planes, interpret=True)
