"""float64 reference of ``forward`` / ``inverse`` (unnormalised forward,
1/N inverse, over every axis).

With ``reference_points`` in the configuration the output is compared at
that many indices per axis, drawn from the run's seed, against a direct DFT
(the whole input comes to the host, the output only at those points);
otherwise the whole output is compared with ``scipy.fft``.
"""
import numpy as np

from chipbench.references.common import dft_points, rel_err


def error(op: str, inp, out, cfg: dict, rng) -> float:
    inverse = {"forward": False, "inverse": True}[op]
    points = cfg.get("reference_points")
    if points:
        ks = [np.sort(rng.choice(n, points, replace=False))
              for n in out.shape]
        got = np.asarray(out[np.ix_(*ks)])
        return rel_err(got, dft_points(np.asarray(inp), ks, inverse))
    import scipy.fft
    x = np.asarray(inp).astype(np.complex128)
    fn = scipy.fft.ifftn if inverse else scipy.fft.fftn
    ref = fn(x, workers=-1, overwrite_x=True)
    del x
    return rel_err(np.asarray(out), ref)
