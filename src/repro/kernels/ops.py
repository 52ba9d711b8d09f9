"""jit'd public wrappers around the Pallas kernels.

``fft1d`` / ``ifft1d`` take complex (or real) arrays of any rank and
transform along ``axis`` using the MXU four-step kernel.  They are the
routing target of ``backend="pallas"``: ``core/transforms.apply_1d``
dispatches every C2C line of the pallas backend here, with the DCT-II/DST-II
phase of a real operand fused into the kernel (``twiddle=``).

``interpret`` defaults to ``None`` = "interpret on the CPU platform only":
there (the test suite, CI) the kernel body executes as traced jax ops; on
every other platform the same call sites compile the Mosaic kernel, and a
kernel the compiler refuses is an error, never a silent interpreter run.
The platform is the one the program is built for: a plan sets it to its
mesh's devices (:func:`for_platform`), so a plan for TPU devices compiles
the Mosaic kernel even when the process's default backend is the CPU.
Output dtype follows the input — complex64 in/out for single precision,
complex128 end-to-end under ``jax.enable_x64`` (interpret mode only).
:func:`unsupported` says, before anything is traced, which plans the kernel
cannot run; the planner and the tuner ask it.
"""
from __future__ import annotations

import contextlib
import contextvars
import math
from typing import Iterator, Optional, Sequence

import jax
import jax.numpy as jnp

from .fft_matmul import fft_cols, line_flops, supports_length, vmem_bytes
from .precision import MATMUL_PRECISION

__all__ = ["fft1d", "for_platform", "ifft1d", "interpret_mode",
           "line_flops", "platform", "supports_dtype", "supports_length",
           "unsupported"]

_PLATFORM: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "repro_kernel_platform", default=None)


@contextlib.contextmanager
def for_platform(name: str) -> Iterator[None]:
    """Plan and trace the kernel for devices of platform ``name`` (``"tpu"``,
    ``"cpu"``, ...) inside the block, whatever the default backend."""
    token = _PLATFORM.set(name)
    try:
        yield
    finally:
        _PLATFORM.reset(token)


def platform() -> str:
    """The platform the kernel is built for: the innermost
    :func:`for_platform`, else the process's default backend."""
    return _PLATFORM.get() or jax.default_backend()


def interpret_mode() -> bool:
    """Whether the kernel runs interpreted: on the CPU platform only."""
    return platform() == "cpu"


def supports_dtype(dtype) -> bool:
    """Whether the kernel can run ``dtype`` on this platform: the compiled
    kernel has no float64 path (Mosaic has no f64)."""
    return interpret_mode() or jnp.finfo(dtype).bits < 64


def unsupported(lengths: Sequence[int], dtype,
                on: Optional[str] = None) -> Optional[str]:
    """Why the kernel cannot run lines of these ``lengths`` in ``dtype``
    on platform ``on`` (default :func:`platform`), or ``None`` when it
    can."""
    with for_platform(on or platform()):
        if not supports_dtype(dtype):
            return (f"has no {jnp.dtype(dtype)} path on {platform()} "
                    f"(Mosaic has no f64)")
    too_long = sorted({n for n in lengths if not supports_length(n)})
    if too_long:
        return (f"does not take lines of length {too_long}: one kernel "
                f"program would need {vmem_bytes(too_long[0])} bytes of VMEM")
    return None


def _apply(x: jax.Array, axis: int, *, inverse: bool,
           interpret: Optional[bool] = None,
           twiddle: Optional[jax.Array] = None,
           precision=MATMUL_PRECISION) -> jax.Array:
    interpret = interpret_mode() if interpret is None else interpret
    axis = axis % x.ndim
    n = x.shape[axis]
    cdt = jnp.result_type(x.dtype, jnp.complex64)
    rdt = jnp.finfo(cdt).dtype
    if x.size == 0:
        # Empty batch (or empty line): nothing to transform.
        return jnp.zeros(x.shape, cdt)
    # The kernel transforms axis 1 of (G, N, M): a free reshape for any
    # axis but the last, which is transposed so the lines run on lanes.
    g = math.prod(x.shape[:axis])
    last = axis == x.ndim - 1
    xc = x.reshape(g, n).T[None] if last else x.reshape(g, n, -1)
    planes_r = jnp.real(xc).astype(rdt)
    planes_i = (jnp.imag(xc).astype(rdt) if jnp.iscomplexobj(xc)
                else jnp.zeros_like(planes_r))
    tw = None
    if twiddle is not None:
        t = jnp.asarray(twiddle).reshape(-1)
        tw = (jnp.real(t).astype(rdt), jnp.imag(t).astype(rdt))
    outr, outi = fft_cols(planes_r, planes_i, inverse=inverse,
                          interpret=interpret, twiddle=tw,
                          precision=precision)
    out = jax.lax.complex(outr, outi)
    return (out[0].T if last else out).reshape(x.shape)


def fft1d(x: jax.Array, axis: int = -1, *,
          interpret: Optional[bool] = None,
          twiddle: Optional[jax.Array] = None,
          precision=MATMUL_PRECISION) -> jax.Array:
    """Forward FFT along ``axis`` via the Pallas MXU kernel.

    ``twiddle`` — optional complex ``(n,)`` phase fused into the kernel's
    matmul operand (the result is ``twiddle * fft(x)`` elementwise along
    ``axis``).  ``precision`` — the MXU contractions' ``jax.lax.Precision``
    (``chip_smoke.py --precision-probe`` compares them).
    """
    return _apply(x, axis, inverse=False, interpret=interpret,
                  twiddle=twiddle, precision=precision)


def ifft1d(x: jax.Array, axis: int = -1, *,
           interpret: Optional[bool] = None,
           twiddle: Optional[jax.Array] = None) -> jax.Array:
    """Inverse FFT along ``axis`` via the Pallas MXU kernel.

    ``twiddle`` as for :func:`fft1d`.
    """
    return _apply(x, axis, inverse=True, interpret=interpret,
                  twiddle=twiddle)
