"""Local (on-device) 1D/2D transforms: C2C, R2C and R2R (DCT/DST).

Three interchangeable backends (``LOCAL_BACKENDS``):

* ``"xla"``    — ``jnp.fft.*``.  On TPU this lowers to the XLA Fft HLO; on the
  CPU test runtime it is the numerically-trusted path.
* ``"matmul"`` — the four-step factorization N = N1*N2 executed as two small
  DFT-matrix matmuls plus a fused twiddle, with complex numbers carried as
  separate real/imag planes.  This is the TPU-native formulation (MXU work
  instead of VPU butterflies) expressed as pure jnp ops.
* ``"pallas"`` — the same four-step algorithm as an explicit Pallas kernel
  (``kernels/fft_matmul.py``, wrapped by ``kernels/ops.py``), with a real
  operand's DCT phase twiddle fused into its matmul operand.  On the CPU
  platform it runs in interpret mode so tests stay hermetic.

A plan whose caller names no backend takes :func:`default_backend`'s:
the kernel on TPU devices wherever it takes the lines, else xla.

The matmul and pallas backends contract at ``MATMUL_PRECISION``
(``kernels/precision.py``).

R2C/R2R transforms are composed from the complex FFT with the standard
even/odd reordering identities, so they inherit whichever backend is
selected.  Complex working dtypes are derived from the input
(``jnp.result_type(x.dtype, complex64)``), so float64 inputs under
``jax.enable_x64`` stay in double precision on every backend.  All
transforms operate along ``axis`` of an arbitrarily-batched array.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.precision import MATMUL_PRECISION

C2C_KINDS = ("fft", "ifft")
R2C_KINDS = ("rfft", "irfft")
R2R_KINDS = ("dct2", "dct3", "dst2", "dst3")
ALL_KINDS = C2C_KINDS + R2C_KINDS + R2R_KINDS

#: Every local-FFT backend ``apply_1d`` (and hence the tuner) accepts.
LOCAL_BACKENDS = ("xla", "matmul", "pallas")


def default_backend(platform: str, lengths: Tuple[int, ...], dtype,
                    kinds: Tuple[str, ...]) -> Tuple[str, str]:
    """The local backend of a plan whose caller names none, and why.

    ``"pallas"`` where the kernel both applies and helps: the plan's
    devices are TPUs (on a v5e the kernel runs a 512^3 or 480^3 C2C in
    half of XLA's time), the kernel takes every line length in ``dtype``
    (``kernels.ops.unsupported``), and no kind is R2C, which the kernel
    computes as the full C2C and trims.  ``"xla"`` otherwise.
    """
    if platform != "tpu":
        return "xla", f"xla: {platform} devices"
    r2c = sorted({k for k in kinds if k in R2C_KINDS})
    if r2c:
        return "xla", f"xla: {'/'.join(r2c)} runs a full C2C on the kernel"
    from repro.kernels.ops import unsupported  # deferred: loads Pallas
    why = unsupported(lengths, dtype, platform)
    if why:
        return "xla", f"xla: the kernel {why}"
    fit = ", ".join(str(n) for n in sorted(set(lengths)))
    return "pallas", f"pallas: {platform}, lines {fit} fit"


def factorize(n: int) -> Tuple[int, int]:
    """Split n = n1*n2 with n1 <= n2, n1 as close to sqrt(n) as possible.

    Balanced factors minimize the four-step flop count n*(n1+n2) and keep
    both matmul operands MXU-shaped.  A prime n degrades to (1, n) — a single
    dense DFT matmul, still correct.
    """
    best = (1, n)
    for n1 in range(int(math.isqrt(n)), 0, -1):
        if n % n1 == 0:
            best = (n1, n // n1)
            break
    return best


@functools.lru_cache(maxsize=None)
def _dft_planes(n: int, sign: float, dtype: str) -> Tuple[np.ndarray, np.ndarray]:
    """(cos, sin) planes of the DFT matrix W[j,k] = exp(sign*2pi*i*j*k/n).

    Built in float64 and cast down so that bf16/f32 runs see a well-rounded
    operand rather than accumulated single-precision phase error.
    """
    k = np.arange(n, dtype=np.float64)
    theta = (sign * 2.0 * np.pi / n) * np.outer(k, k)
    return (np.cos(theta).astype(dtype), np.sin(theta).astype(dtype))


@functools.lru_cache(maxsize=None)
def _twiddle_planes(n1: int, n2: int, sign: float, dtype: str):
    """T[k1, m2] = exp(sign*2pi*i*k1*m2/(n1*n2)) — the four-step twiddle."""
    n = n1 * n2
    k1 = np.arange(n1, dtype=np.float64)
    m2 = np.arange(n2, dtype=np.float64)
    theta = (sign * 2.0 * np.pi / n) * np.outer(k1, m2)
    return (np.cos(theta).astype(dtype), np.sin(theta).astype(dtype))


def _cmatmul(ar, ai, br, bi, *, side: str, precision=MATMUL_PRECISION):
    """Complex matmul via 4 real matmuls on (..., rows, cols) planes.

    side="left":  result = B @ A   (contract A's rows with B's cols)
    side="right": result = A @ B
    """
    def mm(spec, u, v):
        return jnp.einsum(spec, u, v, precision=precision)

    if side == "left":
        rr = mm("kn,...nm->...km", br, ar)
        ri = mm("kn,...nm->...km", br, ai)
        ir = mm("kn,...nm->...km", bi, ar)
        ii = mm("kn,...nm->...km", bi, ai)
    else:
        rr = mm("...kn,nm->...km", ar, br)
        ri = mm("...kn,nm->...km", ar, bi)
        ir = mm("...kn,nm->...km", ai, br)
        ii = mm("...kn,nm->...km", ai, bi)
    return rr - ii, ri + ir


def fourstep_fft_planes(xr, xi, *, inverse: bool = False,
                        precision=MATMUL_PRECISION):
    """Four-step FFT along the last axis of real/imag planes (..., N).

    X[k1 + N1*k2] = sum_{m2} W_N2^{m2 k2} [ W_N^{m2 k1}
                        sum_{m1} x[m1*N2 + m2] W_N1^{m1 k1} ]

    ``precision`` is the contractions' ``jax.lax.Precision``.
    """
    n = xr.shape[-1]
    n1, n2 = factorize(n)
    sign = 1.0 if inverse else -1.0
    dt = str(xr.dtype)

    w1r, w1i = _dft_planes(n1, sign, dt)
    w2r, w2i = _dft_planes(n2, sign, dt)
    tr, ti = _twiddle_planes(n1, n2, sign, dt)

    # (..., N) -> (..., N1, N2): row m1, col m2  (n = m1*N2 + m2)
    xr = xr.reshape(xr.shape[:-1] + (n1, n2))
    xi = xi.reshape(xi.shape[:-1] + (n1, n2))

    # step 1: DFT_N1 over m1 (left-multiply) -> F1[k1, m2]
    f1r, f1i = _cmatmul(xr, xi, jnp.asarray(w1r), jnp.asarray(w1i),
                        side="left", precision=precision)
    # step 2: fused twiddle W_N^{k1 m2}
    g_r = f1r * tr - f1i * ti
    g_i = f1r * ti + f1i * tr
    # step 3: DFT_N2 over m2 (right-multiply, W2 symmetric) -> F2[k1, k2]
    f2r, f2i = _cmatmul(g_r, g_i, jnp.asarray(w2r), jnp.asarray(w2i),
                        side="right", precision=precision)
    # step 4: X[k1 + N1*k2]  ->  layout [k2, k1], then flatten
    outr = jnp.swapaxes(f2r, -1, -2).reshape(xr.shape[:-2] + (n,))
    outi = jnp.swapaxes(f2i, -1, -2).reshape(xi.shape[:-2] + (n,))
    if inverse:
        outr = outr / n
        outi = outi / n
    return outr, outi


def _matmul_fft(x: jax.Array, *, inverse: bool) -> jax.Array:
    """Complex-in/complex-out last-axis FFT via the four-step matmul path."""
    real_dt = jnp.finfo(x.dtype).dtype if jnp.iscomplexobj(x) else x.dtype
    xr = jnp.real(x).astype(real_dt)
    xi = jnp.imag(x).astype(real_dt) if jnp.iscomplexobj(x) else jnp.zeros_like(xr)
    outr, outi = fourstep_fft_planes(xr, xi, inverse=inverse)
    return jax.lax.complex(outr, outi)


def _move_last(x: jax.Array, axis: int):
    axis = axis % x.ndim
    return jnp.moveaxis(x, axis, -1), axis


def _c2c(x: jax.Array, axis: int, *, inverse: bool, backend: str) -> jax.Array:
    if backend == "xla":
        return (jnp.fft.ifft if inverse else jnp.fft.fft)(x, axis=axis)
    if backend == "pallas":
        # Deferred import: the kernel module loads Pallas.
        from repro.kernels import ops
        return (ops.ifft1d if inverse else ops.fft1d)(x, axis)
    if backend != "matmul":
        raise ValueError(f"unknown backend {backend!r}; supported local-FFT "
                         f"backends: {LOCAL_BACKENDS}")
    xm, axis = _move_last(x, axis)
    if not jnp.iscomplexobj(xm):
        # Promote to the complex dtype matching the input precision — a bare
        # complex64 cast here silently dropped float64 inputs under x64.
        xm = xm.astype(jnp.result_type(xm.dtype, jnp.complex64))
    out = _matmul_fft(xm, inverse=inverse)
    return jnp.moveaxis(out, -1, axis)


def _rfft(x: jax.Array, axis: int, backend: str) -> jax.Array:
    if backend == "xla":
        return jnp.fft.rfft(x, axis=axis)
    # Hermitian trim of the full C2C result (flop-wasteful but TPU-simple;
    # the distributed pipeline pads the frequency dim anyway).  ``_c2c``
    # promotes real inputs to the precision-matching complex dtype.
    full = _c2c(x, axis, inverse=False, backend=backend)
    n = x.shape[axis]
    return jax.lax.slice_in_dim(full, 0, n // 2 + 1, axis=axis)


def _irfft(x: jax.Array, axis: int, n: int, backend: str) -> jax.Array:
    if backend == "xla":
        return jnp.fft.irfft(x, n=n, axis=axis)
    # rebuild Hermitian spectrum then full inverse C2C, take real part
    xm, ax = _move_last(x, axis)
    body = jnp.conj(xm[..., 1:n - n // 2])[..., ::-1]
    full = jnp.concatenate([xm, body], axis=-1)
    out = _c2c(full, -1, inverse=True, backend=backend)
    return jnp.moveaxis(jnp.real(out), -1, ax)


# ---------------------------------------------------------------------------
# R2R: DCT-II/III and DST-II/III via the even/odd FFT reordering identities.
# Unnormalized ("scipy norm=None") conventions:
#   dct2(x)[k] = 2 sum_n x[n] cos(pi k (2n+1) / (2N))
#   dct3(x)[k] = x[0] + 2 sum_{n>=1} x[n] cos(pi n (2k+1) / (2N))
#   dct3(dct2(x)) = 2N x
#
# A complex operand (a bounded dim transformed after a periodic C2C stage,
# e.g. the (Periodic, Periodic, Bounded) Poisson topology) means
# R2R(Re x) + i R2R(Im x).  Both identities are C-linear, so one complex
# FFT per line serves both planes:
#   DCT-II:  V = FFT(shuffle(x)) and, with p_k = exp(-i pi k / 2N),
#            dct2(Re x) + i dct2(Im x) = p_k V_k + conj(p_k) V_{(N-k) mod N};
#            for real x, V is Hermitian and this is the real path's
#            2 Re(p_k V_k).
#   DCT-III: spec(x) = (x - i shifted(x)) exp(i pi k / 2N) is C-linear and
#            Hermitian for real x, so IFFT(spec(x)) is dct3(Re x) +
#            i dct3(Im x) before the unshuffle: the real path's real part,
#            kept complex.
# DST-II/III wrap these with sign flips and reversals, also C-linear.
# ---------------------------------------------------------------------------

def _dct2(x: jax.Array, axis: int, backend: str) -> jax.Array:
    xm, ax = _move_last(x, axis)
    n = xm.shape[-1]
    v = jnp.concatenate([xm[..., 0::2], xm[..., 1::2][..., ::-1]], axis=-1)
    # Promote to the complex dtype MATCHING the input precision: float64
    # pipelines (x64) must not round-trip through complex64.
    cdt = jnp.result_type(v.dtype, jnp.complex64)
    k = jnp.arange(n)
    phase = jnp.exp(-1j * jnp.pi * k / (2.0 * n)).astype(cdt)
    if jnp.iscomplexobj(xm):
        # One FFT for both planes; the epilogue pairs V_k with V_{(N-k) mod N}
        # so it is no per-element twiddle and stays outside the kernel.
        vf = _c2c(v, -1, inverse=False, backend=backend)
        mirror = jnp.roll(vf[..., ::-1], 1, axis=-1)
        out = phase * vf + jnp.conj(phase) * mirror
    elif backend == "pallas":
        # Fused epilogue: the kernel applies the DCT phase in-register
        # instead of a separate elementwise pass over the FFT output.
        from repro.kernels import ops
        pv = ops.fft1d(v.astype(cdt), -1, twiddle=phase)
        out = 2.0 * jnp.real(pv)
    else:
        pv = phase * _c2c(v.astype(cdt), -1, inverse=False, backend=backend)
        out = 2.0 * jnp.real(pv)
    return jnp.moveaxis(out.astype(x.dtype), -1, ax)


def _dct3(x: jax.Array, axis: int, backend: str) -> jax.Array:
    """Unnormalized DCT-III (the unscaled inverse of _dct2)."""
    xm, ax = _move_last(x, axis)
    n = xm.shape[-1]
    k = jnp.arange(n)
    phase = jnp.exp(1j * jnp.pi * k / (2.0 * n))
    # Build the complex spectrum whose IFFT reproduces the even/odd shuffle.
    shifted = jnp.concatenate([xm[..., :1] * 0, xm[..., :0:-1]], axis=-1)
    spec = (xm - 1j * shifted) * phase
    v = _c2c(spec, -1, inverse=True, backend=backend) * n
    if not jnp.iscomplexobj(xm):
        v = jnp.real(v)
    out = jnp.zeros_like(v)
    half = (n + 1) // 2
    out = out.at[..., 0::2].set(v[..., :half])
    out = out.at[..., 1::2].set(v[..., half:][..., ::-1])
    return jnp.moveaxis(out.astype(x.dtype), -1, ax)


def _alt_signs(x: jax.Array) -> jax.Array:
    n = x.shape[-1]
    return x * jnp.where(jnp.arange(n) % 2 == 0, 1.0, -1.0).astype(x.dtype)


def _dst2(x: jax.Array, axis: int, backend: str) -> jax.Array:
    # DST-II(x)[k] = DCT-II(alt_signs(x))[N-1-k]
    xm, ax = _move_last(x, axis)
    out = _dct2(_alt_signs(xm), -1, backend)[..., ::-1]
    return jnp.moveaxis(out, -1, ax)


def _dst3(x: jax.Array, axis: int, backend: str) -> jax.Array:
    # Inverse pairing of _dst2: dst3(dst2(x)) = 2N x
    xm, ax = _move_last(x, axis)
    out = _alt_signs(_dct3(xm[..., ::-1], -1, backend))
    return jnp.moveaxis(out, -1, ax)


def apply_1d(x: jax.Array, axis: int, kind: str, *, backend: str = "xla",
             irfft_n: int | None = None) -> jax.Array:
    """Apply one transform along ``axis``.  ``kind`` in ALL_KINDS."""
    if backend not in LOCAL_BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; supported local-FFT "
                         f"backends: {LOCAL_BACKENDS}")
    if kind == "fft":
        return _c2c(x, axis, inverse=False, backend=backend)
    if kind == "ifft":
        return _c2c(x, axis, inverse=True, backend=backend)
    if kind == "rfft":
        return _rfft(x, axis, backend)
    if kind == "irfft":
        if irfft_n is None:
            raise ValueError("irfft needs irfft_n (original real length)")
        return _irfft(x, axis, irfft_n, backend)
    if kind in R2R_KINDS:
        fn = {"dct2": _dct2, "dct3": _dct3,
              "dst2": _dst2, "dst3": _dst3}[kind]
        if jnp.iscomplexobj(x):
            # One complex FFT per line for both planes (see the R2R
            # identities above), under the device scope ``r2r_complex``.
            with jax.named_scope("r2r_complex"):
                return fn(x, axis, backend)
        return fn(x, axis, backend)
    raise ValueError(f"unknown transform kind {kind!r}")


def apply_nd(x: jax.Array, axes: Tuple[int, ...], kind: str, *,
             backend: str = "xla") -> jax.Array:
    """Apply the same 1D transform along several axes (slab stages)."""
    for ax in axes:
        x = apply_1d(x, ax, kind, backend=backend)
    return x
