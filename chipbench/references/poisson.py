"""float64 reference of ``solve``: the second-order finite-difference Poisson
solve lap(phi) = rhs with periodic (FFT) and bounded (Neumann, DCT-II) axes,
divided by the stencil's eigenvalues with the null mode pinned to zero
(Oceananigans' FFTBasedPoissonSolver)."""
import numpy as np

from chipbench.references.common import rel_err


def eigenvalues(n: int, length: float, topology: str) -> np.ndarray:
    dx = length / n
    arg = (2.0 if topology == "periodic" else 1.0) * np.pi * np.arange(n) / n
    return 2.0 * (np.cos(arg) - 1.0) / dx**2


def solve(rhs: np.ndarray, lengths, topology) -> np.ndarray:
    import scipy.fft
    periodic = tuple(a for a, t in enumerate(topology) if t == "periodic")
    bounded = tuple(a for a, t in enumerate(topology) if t == "bounded")
    spec = rhs.astype(np.float64)
    for a in bounded:
        spec = scipy.fft.dct(spec, type=2, axis=a, workers=-1)
    if periodic:
        spec = scipy.fft.fftn(spec, axes=periodic, workers=-1,
                              overwrite_x=True)
    lam = sum(eigenvalues(n, length, t).reshape(
        [-1 if b == a else 1 for b in range(rhs.ndim)])
        for a, (n, length, t) in enumerate(zip(rhs.shape, lengths, topology)))
    lam[(0,) * rhs.ndim] = 1.0
    spec /= lam
    spec[(0,) * rhs.ndim] = 0.0
    if periodic:
        spec = scipy.fft.ifftn(spec, axes=periodic, workers=-1,
                               overwrite_x=True).real
    for a in bounded:
        spec = scipy.fft.idct(spec, type=2, axis=a, workers=-1)
    return spec


def error(op: str, inp, out, cfg: dict, rng) -> float:
    if op != "solve":
        raise KeyError(f"poisson reference has no op {op!r}")
    ref = solve(np.asarray(inp), cfg["lengths"], cfg["topology"])
    return rel_err(np.asarray(out), ref)
