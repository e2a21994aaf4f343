"""Patch extraction nodes.

Ref: src/main/scala/nodes/images/{RandomPatcher,Windower,
CenterCornerPatcher}.scala (SURVEY.md §2.5) [unverified].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from keystone_tpu.utils.metrics import device_scope
from keystone_tpu.workflow import Transformer


def windows(X, fh: int, fw: int, stride: int = 1):
    """(n, oh, ow, fh·fw·c): every fh x fw window of ``X`` (n, h, w, c) at
    ``stride``, flattened (row, column, channel): fh·fw strided slices side
    by side (im2col with no gather)."""
    _n, h, w, _c = X.shape
    oh, ow = (h - fh) // stride + 1, (w - fw) // stride + 1
    return jnp.concatenate([
        X[:, i:i + (oh - 1) * stride + 1:stride,
          j:j + (ow - 1) * stride + 1:stride, :]
        for i in range(fh) for j in range(fw)
    ], axis=-1)


@functools.partial(jax.jit, static_argnames="size")
def _take_patches(X, image, top, left, size: int):
    """The (size, size, c) windows of ``X`` (n, h, w, c) at ``top``,
    ``left`` of the images ``image``: (patches, size, size, c). Each patch's
    ``size`` whole rows are one slice of the (n, h, w·c) view; a slice as
    wide as the row lowers to one gather, where a narrower one ran as a
    loop of 100,000 slices (0.30 s of a ``cifar-fit`` fit: PERF.md, PR 39)
    and a gather of c-wide rows took the TPU's compiler eight minutes (PR
    32). The patch's size·c columns are then picked from each row by a
    select and a sum over the row's lanes, on the values' bits: one term of
    each sum is not zero, so the patches are the images' values exactly.
    The indices are arguments: one program for every draw."""
    n, h, w, c = X.shape
    with device_scope("filters.rows"):
        bits = lax.bitcast_convert_type(X, jnp.dtype(f"uint{8 * X.dtype.itemsize}"))
        rows = bits.reshape(n, h, w * c)
        slabs = jax.vmap(  # (patches, size, w·c)
            lambda i, t: lax.dynamic_slice(rows, (i, t, 0), (1, size, w * c))[0]
        )(image, top)
    with device_scope("filters.cols"):
        cols = left[:, None] * c + jnp.arange(size * c)  # (patches, size·c)
        pick = jnp.arange(w * c) == cols[:, None, :, None]
        picked = jnp.where(pick, slabs[:, :, None, :], 0).sum(-1, dtype=rows.dtype)
    return lax.bitcast_convert_type(picked, X.dtype).reshape(-1, size, size, c)


class RandomPatcher(Transformer):
    """Extract `num_patches` random (size × size) patches from the batch —
    the filter-learning sampler of RandomPatchCifar. Deterministic by seed.

    The indices are drawn on the host (numpy, from the seed); the patches
    are cut on the device by one gather of their rows (``_take_patches``).
    """

    jittable = False  # output count depends on num_patches, not batch size
    row_independent = False  # output rows are drawn across the whole batch

    def __init__(self, num_patches: int, patch_size: int, seed: int = 0):
        self.num_patches = num_patches
        self.patch_size = patch_size
        self.seed = seed

    def apply_batch(self, X):
        X = jnp.asarray(X)
        n, h, w, _c = X.shape
        p = self.patch_size
        rng = np.random.default_rng(self.seed)
        img_idx = rng.integers(0, n, size=self.num_patches)
        tops = rng.integers(0, h - p + 1, size=self.num_patches)
        lefts = rng.integers(0, w - p + 1, size=self.num_patches)
        return _take_patches(
            X, *(a.astype(np.int32) for a in (img_idx, tops, lefts)), size=p
        )


class Windower(Transformer):
    """All (size × size) windows at `stride` — the im2col view, exposed as a
    node for featurizers that want explicit patches."""

    # n images fan out to n·windows rows: slicing a padded batch's output
    # [:n] would return the wrong rows, so bucketed serving refuses it.
    row_independent = False

    def __init__(self, stride: int, window_size: int):
        self.stride = stride
        self.window_size = window_size

    def apply_batch(self, X):
        n, h, w, c = X.shape
        p, s = self.window_size, self.stride
        out_h = (h - p) // s + 1
        out_w = (w - p) // s + 1
        i0 = (jnp.arange(out_h) * s)[:, None] + jnp.arange(p)[None, :]
        j0 = (jnp.arange(out_w) * s)[:, None] + jnp.arange(p)[None, :]
        # (n, out_h, p, w, c) → (n, out_h, out_w, p, p, c)
        rows = X[:, i0, :, :]
        wins = rows[:, :, :, j0, :]
        wins = jnp.moveaxis(wins, 3, 2)  # windows before in-patch rows? see below
        # resulting layout: (n, out_h, out_w, p, p, c)
        return wins.reshape(n * out_h * out_w, p, p, c)


class Cropper(Transformer):
    """Fixed crop (Ref: nodes/images/Cropper.scala [unverified])."""

    def __init__(self, top: int, left: int, height: int, width: int):
        if min(top, left) < 0 or min(height, width) <= 0:
            raise ValueError(
                f"invalid crop (top={top}, left={left}, h={height}, w={width})"
            )
        self.top = top
        self.left = left
        self.height = height
        self.width = width

    def apply_batch(self, X):
        if self.top + self.height > X.shape[1] or self.left + self.width > X.shape[2]:
            raise ValueError(
                f"crop {self.top}+{self.height} x {self.left}+{self.width} "
                f"exceeds image {X.shape[1]}x{X.shape[2]}"
            )
        return X[
            :,
            self.top : self.top + self.height,
            self.left : self.left + self.width,
            :,
        ]


class CenterCornerPatcher(Transformer):
    """Center + four corner crops, optionally horizontally flipped — the
    test-time augmentation of the ImageNet pipeline. Emits (n·views, s, s, c)
    with views grouped per image."""

    row_independent = False  # n images emit n·views rows

    def __init__(self, crop_size: int, with_flips: bool = True):
        self.crop_size = crop_size
        self.with_flips = with_flips

    @property
    def num_views(self) -> int:
        return 10 if self.with_flips else 5

    def apply_batch(self, X):
        n, h, w, _c = X.shape
        s = self.crop_size
        if s > h or s > w:
            raise ValueError(f"crop {s} exceeds image {h}x{w}")
        ct, cl = (h - s) // 2, (w - s) // 2
        crops = [
            X[:, :s, :s, :],
            X[:, :s, w - s :, :],
            X[:, h - s :, :s, :],
            X[:, h - s :, w - s :, :],
            X[:, ct : ct + s, cl : cl + s, :],
        ]
        if self.with_flips:
            crops += [c[:, :, ::-1, :] for c in crops]
        stacked = jnp.stack(crops, axis=1)  # (n, views, s, s, c)
        return stacked.reshape(-1, s, s, X.shape[-1])
