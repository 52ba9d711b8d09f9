"""float64 host arithmetic shared by the references (after ``chip_smoke.py``).

Nothing here imports the program or takes anything it made but the arrays
under test.
"""
import numpy as np


def rel_err(y, ref) -> float:
    """max|y - ref| / max|ref| in float64, one leading slab at a time."""
    y, ref = np.asarray(y), np.asarray(ref)
    num = den = 0.0
    for s in range(0, y.shape[0], 64):
        r = ref[s:s + 64].astype(np.complex128)
        num = max(num, float(np.max(np.abs(y[s:s + 64] - r))))
        den = max(den, float(np.max(np.abs(r))))
    return num / max(den, 1e-300)


def dft_points(x: np.ndarray, ks, inverse: bool = False) -> np.ndarray:
    """The 3-D DFT of ``x`` at the index sets ``ks`` (one per axis), by a
    direct separable sum in float64: the last axis a slab at a time, then the
    rest.  ``inverse`` takes the conjugate kernel and the 1/N scale."""
    sign = 1.0 if inverse else -1.0
    e = [np.exp(sign * 2j * np.pi * np.outer(np.arange(n), k) / n)
         for n, k in zip(x.shape, ks)]
    t = np.empty(x.shape[:2] + (len(ks[2]),), np.complex128)
    for s in range(0, x.shape[0], 64):
        t[s:s + 64] = x[s:s + 64].astype(np.complex128) @ e[2]
    t = np.einsum("ijc,jb->ibc", t, e[1])
    t = np.einsum("ibc,ia->abc", t, e[0])
    return t / x.size if inverse else t
