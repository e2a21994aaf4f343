"""Dense patch convolution.

Ref: src/main/scala/nodes/images/Convolver.scala — convolves images with a
filter bank via explicit im2col + BLAS gemm, optionally folding a ZCA
whitener into the filters (the RandomPatchCifar featurizer; SURVEY.md §2.5,
§3.1) [unverified].

TPU lowering: as upstream, explicit patches (fh·fw strided slices side by
side, `patches.windows`) times the flattened bank: one product whose
contraction is fh·fw·c deep. `lax.conv_general_dilated` contracts c values a
tap, which at three channels ran the same products at a quarter of the rate
(PERF.md, PR 32); the patches of all rows are fh·fw times the images, so a
chain that holds many rows runs in row tiles (`FusedTransformer.row_tiling`).
A fitted whitener (x − μ)V is folded in algebraically: p·(Vᵀf) − (μVᵀf) per
filter, keeping everything a single fused computation. Float32 runs at
`Precision.HIGHEST`, as the solver's products do.

Where a fused chain puts a `SymmetricRectifier` and a sum or mean `Pooler`
right behind the convolution, the three run as one Pallas kernel
(`Convolver.takes`, `ops/conv_pool_pallas.py`): the responses are rectified
and pooled in VMEM and never written (58 MB a row at 10,000 filters, for
320 KB of pooled features). What such a kernel wants laid out in front of
it, the explicit patches, is 750 KB a row whatever the filter count
(`scratch_with`): the chain's row-tile rule prices it with the outputs.
Alone, or in front of anything else, the convolution is the product above.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from keystone_tpu.nodes.images.patches import windows
from keystone_tpu.nodes.images.pooling import Pooler, SymmetricRectifier
from keystone_tpu.ops.conv_pool_pallas import conv_rectify_pool, patch_arrays
from keystone_tpu.workflow import Transformer

_HIGHEST = lax.Precision.HIGHEST


class Convolver(Transformer):
    """filters: (num_filters, fh, fw, c) NHWC batch convolution, VALID.

    ``normalize_patches`` is upstream's ``normalizePatches = true``: each
    fh x fw x c patch p becomes (p - mean(p)) / sqrt(var(p) + alpha) before
    it meets the whitener and the filters, ``alpha`` the value given
    (upstream's 10.0; the variance divides by the patch's size less one).
    It is folded too: a normalised patch's response to g is
    p . (g - mean(g)) / sd(p), so the filters are centred once, here, and
    ``apply_batch`` divides the convolution by the patch's deviation, read
    off two box filters. Off (None) by default.

    The folded filters and the bias are the program's arguments
    (``array_fields``), not constants in it: a new filter bank of the same
    shape runs the program the last one compiled.
    """

    array_fields = ("filters", "bias")

    def __init__(
        self,
        filters: jax.Array,
        stride: int = 1,
        whitener=None,
        compute_dtype: Optional[str] = None,
        normalize_patches: Optional[float] = None,
    ):
        filters = jnp.asarray(filters)
        self.num_filters, self.fh, self.fw, self.c = filters.shape
        flat = filters.reshape(self.num_filters, -1)  # (nf, fh·fw·c)
        self.bias = None
        if whitener is not None:
            # Fold ZCA: patch featurization is ((p − μ) M) fᵀ = p (M f) − μ M f.
            M = jnp.asarray(whitener.whitener)
            mu = jnp.asarray(whitener.mean)
            # M is symmetric for ZCA; keep .T for clarity
            flat = jnp.matmul(flat, M.T, precision=_HIGHEST)
            self.bias = -jnp.matmul(flat, mu, precision=_HIGHEST)  # (nf,)
        if normalize_patches is not None:
            flat = flat - flat.mean(axis=1, keepdims=True)
        self.filters = flat.reshape(filters.shape)
        self.stride = stride
        self.normalize_patches = (
            None if normalize_patches is None else float(normalize_patches)
        )
        # "bfloat16": feed images + filters to the MXU in bf16 with f32
        # accumulation — the conv throughput mode (outputs stay f32, so
        # rectify/pool downstream are untouched). Normalized + validated
        # here so "float32" means off everywhere and a bad dtype fails at
        # the constructor, not deep inside a fused trace.
        if compute_dtype is not None:
            dt = jnp.dtype(compute_dtype)
            compute_dtype = None if dt == jnp.float32 else str(dt)
        self.compute_dtype = compute_dtype

    def _patch_deviation(self, X):
        """sqrt(var + alpha) of every patch, (n, oh, ow, 1): the sum and
        the sum of squares over each window and the channels."""
        size = self.fh * self.fw * self.c
        dims = (1, self.fh, self.fw, 1)
        strides = (1, self.stride, self.stride, 1)

        def box(A):
            return lax.reduce_window(
                A.sum(axis=-1, keepdims=True), 0.0, lax.add, dims, strides,
                "VALID",
            )

        var = (box(X * X) - box(X) ** 2 / size) / (size - 1)
        return jnp.sqrt(jnp.maximum(var, 0.0) + self.normalize_patches)

    def _centred(self, X):
        """``(X, deviation)`` as the product wants them: with patch
        normalisation the images less their own mean (a patch less its mean
        is the same whatever constant the image is moved by: centred, the
        sums of squares lose fewer digits where they cancel) and every
        patch's deviation; else ``X`` as it came and None."""
        if self.normalize_patches is None:
            return X, None
        X = X - X.mean(axis=(1, 2, 3), keepdims=True)
        return X, self._patch_deviation(X)

    def apply_batch(self, X):
        kwargs = {}
        filters = self.filters
        X, deviation = self._centred(X)
        if self.compute_dtype is not None:
            dt = jnp.dtype(self.compute_dtype)
            X = X.astype(dt)
            filters = filters.astype(dt)
            kwargs["preferred_element_type"] = jnp.float32
        else:
            kwargs["precision"] = _HIGHEST
        # (n, oh, ow, fh·fw·c) × (fh·fw·c, nf) → NHWO
        out = jnp.matmul(
            windows(X, self.fh, self.fw, self.stride),
            filters.reshape(self.num_filters, -1).T,
            **kwargs,
        )
        if deviation is not None:
            out = out * (1.0 / deviation)  # the reciprocal of the small one
        if self.bias is not None:
            out = out + self.bias
        return out

    def takes(self, following) -> int:
        """A symmetric rectifier and a sum or mean pooler right behind the
        convolution run with it as one kernel (``apply_with``): the
        responses and their rectified form, the largest arrays of such a
        chain by two orders, then never leave VMEM."""
        if (len(following) >= 2
                and type(following[0]) is SymmetricRectifier
                and type(following[1]) is Pooler
                and following[1].mode in ("sum", "mean")):
            return 2
        return 0

    def scratch_with(self, taken, x):
        _rectifier, pooler = taken
        return patch_arrays(
            x, self.fh * self.fw * self.c, window=(self.fh, self.fw),
            stride=self.stride, pool_size=pooler.pool_size,
            pool_stride=pooler.stride, compute_dtype=self.compute_dtype,
        )

    def apply_with(self, taken, X):
        rectifier, pooler = taken
        X, deviation = self._centred(X)
        out = conv_rectify_pool(
            X,
            self.filters.reshape(self.num_filters, -1).T,
            None if deviation is None else 1.0 / deviation,
            self.bias,
            window=(self.fh, self.fw), stride=self.stride,
            alpha=rectifier.alpha, max_val=rectifier.max_val,
            pool_size=pooler.pool_size, pool_stride=pooler.stride,
            compute_dtype=self.compute_dtype,
        )
        if pooler.mode == "mean":
            out = out / (pooler.pool_size * pooler.pool_size)
        return out
