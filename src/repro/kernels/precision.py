"""Precision of the f32 contractions of the matmul and pallas backends.

Shared by ``core/transforms.py`` (the matmul backend) and the Pallas kernel
(``kernels/fft_matmul.py``) without either importing the other.  On TPU an
f32 matmul at ``Precision.DEFAULT`` is one bf16 MXU pass, which misses the
transforms' 1e-4 tolerance; ``HIGHEST`` is the MXU's f32 path.
``python3 chip_smoke.py --precision-probe`` reports the error of each
precision on the chip.
"""
import jax

MATMUL_PRECISION = jax.lax.Precision.HIGHEST
