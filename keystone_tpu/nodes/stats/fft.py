"""Zero-padded FFT featurizer.

Ref: src/main/scala/nodes/stats/PaddedFFT.scala (used by MnistRandomFFT,
BASELINE.json) [unverified: recalled, no source at hand]: zero-pad each row
to the next power of two n, take its discrete Fourier transform and keep
the real parts of the first n/2 bins, unscaled. MNIST's 784 pixels pad to
1,024 and give 512 features ("Because the mnistImageSize is 784, we get 512
PaddedFFT features per FFT"). Those bins' real parts are a product: the
row's d live entries times a (d, n/2) table of cos(2 pi t k / n), the zero
padding being the table's missing rows. On the TPU the product at HIGHEST
runs on the MXU, faster than XLA's FFT, which the TPU's compiler expands
into convolutions over the whole complex transform (PERF.md section 5).
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.workflow import Transformer


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


@lru_cache(maxsize=None)
def _cosines(d: int, n: int) -> np.ndarray:
    """Re(DFT_n) on a row's d live entries: (d, n/2), float32."""
    t, k = np.arange(d)[:, None], np.arange(n // 2)[None, :]
    return np.cos(2.0 * np.pi * ((t * k) % n) / n).astype(np.float32)


class PaddedFFT(Transformer):
    def signature(self):
        return self.stable_signature()

    def apply_batch(self, X):
        d = X.shape[-1]
        return jnp.matmul(X, _cosines(d, _next_pow2(d)),
                          precision=jax.lax.Precision.HIGHEST).astype(X.dtype)
