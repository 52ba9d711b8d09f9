"""Pallas TPU kernel: batched 1D FFT as a four-step VPU/MXU transform.

The paper's local transforms call FFTW/cuFFT; TPU has neither, and a
butterfly network is VPU-bound.  The TPU-native formulation factorizes
N = N1*N2 and evaluates

    X[k1 + N1*k2] = sum_{m2} W_N2^{m2 k2} * W_N^{m2 k1}
                        * sum_{m1} x[m1*N2 + m2] * W_N1^{m1 k1}

on separate real/imag planes (no complex datapath on the MXU).

**Layout.**  The kernel transforms axis 1 of ``(G, N, M)`` planes: the
transformed index runs down the sublanes and the ``M`` independent lines
across the lanes, so every view the algorithm needs is a row slice:

* the input rows of one ``m1`` are the contiguous block ``[m1*N2, (m1+1)*N2)``;
* the output rows of one ``k1`` are ``k1, k1 + N1, ...`` — one strided store.

No array changes shape inside the kernel (Mosaic rejects lane/sublane shape
casts), and any axis of a row-major array is such a ``(G, N, M)`` view for
free: only a last-axis transform needs a transpose (``kernels/ops.py``).

**Steps.**  N2 is the MXU contraction (the largest divisor of N up to 128,
``factors``); N1 = N / N2 is small.

1. ``F_k1 = sum_m1 W1[k1, m1] * x_m1`` — N1 x N1 scalar-times-tile
   accumulations on the VPU (the W1 entries are compile-time constants).
2. ``X_k1 = M_k1 @ F_k1`` — one complex (N2 x N2) @ (N2 x lanes) matmul per
   ``k1`` on the MXU, stored to the strided output rows of ``k1``.

``M_k1[k2, m2] = e[k1 + N1*k2] * W_N2^{m2 k2} * W_N^{m2 k1} * s`` folds the
four-step twiddle, the optional output twiddle ``e`` (the DCT-II phase of
``transforms._dct2`` on a real operand) and the inverse's 1/N into the
matmul operand, so neither epilogue costs a pass over the data.

**Precision.**  f32 contractions use ``MATMUL_PRECISION``
(``kernels/precision.py``, ``HIGHEST``: the MXU's f32 path); the default
single bf16 pass misses the transform's 1e-4 tolerance on TPU.  f64 planes
(x64 pipelines) run only in interpret mode — Mosaic has no f64, and the
compiled path refuses them with a ``ValueError``.

**Limits.**  One program holds four ``(N, 128)`` line blocks and the
``(N1, N2, N2)`` operand, each double-buffered, in VMEM (``vmem_bytes``).
Lines whose program does not fit Mosaic's default scoped VMEM
(``VMEM_LIMIT``) are refused on every platform (``supports_length``):
powers of two up to N = 2048, no N above 4096, and prime N only up to
about 900 (the dense ``(1, N)`` split).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .precision import MATMUL_PRECISION

#: The MXU contraction the kernel aims for (one 128 x 128 systolic pass),
#: and its lane tile: Mosaic's strided store needs a 128-lane block.
MXU_DIM = 128
#: Mosaic's default scoped-VMEM limit on TPU v5e: one program's blocks must
#: fit it (compiled for v5e, N = 4096 runs out of VMEM; N = 2048 fits).
VMEM_LIMIT = 16 * 2**20


def factors(n: int) -> Tuple[int, int]:
    """The kernel's split N = N1*N2, N2 the MXU contraction.

    N2 is the largest divisor of N not above ``MXU_DIM``.  Lines that have
    no such divisor at least as large as N1 (e.g. a prime N) run as one
    dense DFT matmul, ``(1, N)``; ``supports_length`` bounds how large.
    """
    if n <= MXU_DIM:
        return 1, n
    n2 = max(d for d in range(1, MXU_DIM + 1) if n % d == 0)
    if n // n2 > n2:
        return 1, n
    return n // n2, n2


def vmem_bytes(n: int) -> int:
    """VMEM one kernel program holds for lines of length ``n`` (f32): the
    four ``(N, 128)`` line blocks and the real/imag ``(N1, N2, N2)``
    operand, each double-buffered."""
    n1, n2 = factors(n)
    return 2 * 4 * (4 * n * MXU_DIM + 2 * n1 * n2 * n2)


def supports_length(n: int) -> bool:
    """Whether the kernel takes lines of length ``n``: its program fits
    ``VMEM_LIMIT``.  Checked on every platform, so interpret mode refuses
    what the compiled kernel would (and never builds an N x N operand)."""
    return n >= 1 and vmem_bytes(n) <= VMEM_LIMIT


def line_flops(n: int) -> Tuple[float, float]:
    """Real FLOPs per line of length ``n``, as ``(mxu, vpu)``.

    The MXU runs N1 complex ``(N2 x N2) @ F`` products (four real matmuls
    each); the VPU runs step 1's scalar-times-tile updates (zero constants
    skipped) and the real/imag combines of step 2.
    """
    n1, n2 = factors(n)
    terms = sum(bool(c) + bool(s) for row in _w1_constants(n1, -1.0)
                for c, s in row)
    return 8.0 * n * n2, 4.0 * n2 * terms + 2.0 * n


def _phase(sign: float, num: np.ndarray, n: int) -> np.ndarray:
    """exp(sign * 2 pi i * num / n) in complex128."""
    return np.exp((sign * 2j * np.pi / n) * num)


def _w1_constants(n1: int, sign: float):
    """W_N1^{k1 m1} as python (cos, sin) pairs; exact zeros are kept exact
    so that step 1 skips them (N1 = 2, 4 are pure sign flips)."""
    w = np.round(_phase(sign, np.outer(np.arange(n1), np.arange(n1)), n1),
                 15)
    return tuple(tuple((float(v.real), float(v.imag)) for v in row)
                 for row in w)


def _operand(n1: int, n2: int, inverse: bool) -> np.ndarray:
    """The static part of ``M`` (see the module docstring), (N1, N2, N2)."""
    n = n1 * n2
    sign = 1.0 if inverse else -1.0
    k1 = np.arange(n1)[:, None, None]
    k2 = np.arange(n2)[None, :, None]
    m2 = np.arange(n2)[None, None, :]
    m = _phase(sign, k2 * m2, n2) * _phase(sign, k1 * m2, n)
    return m / n if inverse else m


def _fft_kernel(xr_ref, xi_ref, mr_ref, mi_ref, outr_ref, outi_ref, *,
                n1: int, n2: int, w1, precision):
    acc = xr_ref.dtype  # f32 planes accumulate in f32, f64 (x64) in f64

    def dot(a, b):
        return jax.lax.dot(a, b, precision=precision,
                           preferred_element_type=acc)

    for k1 in range(n1):
        # step 1 (VPU): F = sum_m1 W1[k1, m1] x_m1, constants inlined
        fr = fi = 0.0
        for m1, (c, s) in enumerate(w1[k1]):
            rows_in = pl.ds(m1 * n2, n2)
            ar, ai = xr_ref[rows_in, :], xi_ref[rows_in, :]
            if c:
                fr, fi = fr + c * ar, fi + c * ai
            if s:
                fr, fi = fr - s * ai, fi + s * ar
        # step 2 (MXU): X rows k1, k1 + N1, ... = M_k1 @ F
        mr, mi = mr_ref[k1], mi_ref[k1]
        rows = pl.ds(k1, n2, stride=n1) if n1 > 1 else pl.ds(0, n2)
        outr_ref[rows, :] = dot(mr, fr) - dot(mi, fi)
        outi_ref[rows, :] = dot(mr, fi) + dot(mi, fr)


@functools.partial(jax.jit,
                   static_argnames=("inverse", "interpret", "precision"))
def fft_cols(xr: jax.Array, xi: jax.Array, *, interpret: bool,
             inverse: bool = False,
             twiddle: Optional[Tuple[jax.Array, jax.Array]] = None,
             precision=MATMUL_PRECISION) -> Tuple[jax.Array, jax.Array]:
    """FFT along axis 1 of ``(G, N, M)`` real/imag planes.

    ``twiddle=(er, ei)`` (cos/sin planes of shape ``(N,)``) multiplies the
    result elementwise along the transformed axis, folded into the matmul
    operand.  ``interpret`` is required: ``True`` runs the kernel body as
    traced jax ops (the CPU path, chosen by ``kernels/ops.py``), ``False``
    compiles it with Mosaic.  ``precision`` is the MXU contractions'
    ``jax.lax.Precision``.
    """
    g, n, m = xr.shape
    dt = jnp.result_type(xr.dtype, jnp.float32)
    if not interpret and jnp.dtype(dt).itemsize == 8:
        raise ValueError("the compiled Pallas FFT kernel has no float64 "
                         "path (Mosaic has no f64); use backend='xla' or "
                         "'matmul' for double precision")
    if xr.size == 0:
        empty = jnp.zeros(xr.shape, dt)
        return empty, empty
    if not supports_length(n):
        raise ValueError(
            f"the Pallas FFT kernel does not take lines of length {n}: one "
            f"program needs {vmem_bytes(n)} bytes of VMEM, over its "
            f"{VMEM_LIMIT} (use backend='xla' or 'matmul')")
    n1, n2 = factors(n)
    sign = 1.0 if inverse else -1.0
    op = jnp.asarray(_operand(n1, n2, inverse).astype(
        np.complex128 if jnp.dtype(dt).itemsize == 8 else np.complex64))
    if twiddle is not None:
        er, ei = twiddle
        e = jax.lax.complex(jnp.asarray(er).astype(dt),
                            jnp.asarray(ei).astype(dt))
        # e[k1 + N1*k2] -> [k1, k2], scaling row k2 of M_k1
        op = op * e.reshape(n2, n1).T[:, :, None]
    # One (N, 128) block of lines per program; the edge block of a ragged
    # M is masked.
    block = pl.BlockSpec((None, n, MXU_DIM), lambda i, j: (i, 0, j))
    const = pl.BlockSpec((n1, n2, n2), lambda i, j: (0, 0, 0))
    out_struct = jax.ShapeDtypeStruct((g, n, m), dt)
    return pl.pallas_call(
        functools.partial(_fft_kernel, n1=n1, n2=n2,
                          w1=_w1_constants(n1, sign), precision=precision),
        grid=(g, pl.cdiv(m, MXU_DIM)),
        in_specs=[block, block, const, const],
        out_specs=[block, block],
        out_shape=[out_struct, out_struct],
        interpret=interpret,
    )(xr.astype(dt), xi.astype(dt), jnp.real(op), jnp.imag(op))

