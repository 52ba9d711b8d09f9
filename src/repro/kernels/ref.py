"""Pure-jnp oracles for the Pallas kernels (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def fft1d_ref(x: jax.Array, axis: int = -1) -> jax.Array:
    return jnp.fft.fft(x, axis=axis)


def ifft1d_ref(x: jax.Array, axis: int = -1) -> jax.Array:
    return jnp.fft.ifft(x, axis=axis)


def fft1d_planes_ref(xr: jax.Array, xi: jax.Array, *, inverse: bool = False):
    """Last-axis planes-in/planes-out oracle for the kernel's real/imag
    plane interface (``kernels.fft_matmul.fft_cols``)."""
    x = jax.lax.complex(xr.astype(jnp.float32), xi.astype(jnp.float32))
    out = jnp.fft.ifft(x, axis=-1) if inverse else jnp.fft.fft(x, axis=-1)
    return jnp.real(out), jnp.imag(out)
