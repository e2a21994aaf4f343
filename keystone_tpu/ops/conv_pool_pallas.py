"""Patch convolution, symmetric rectifier and sum pooling as one Pallas TPU
kernel.

The stage walk (`Convolver`, `SymmetricRectifier`, `Pooler` one by one)
writes the (n, oh, ow, F) responses' rectified form to HBM and reads it back
to sum a handful of windows: at the CIFAR random-patch widths (27 x 27
positions, 10,000 filters) 58 MB a row each way for 320 KB of pooled
features. Here one program takes a tile of images' explicit patches,
multiplies them with a tile of the bank on the MXU, adds the bias, forms both
rectified halves and sums them into the pooling windows, all in VMEM: what
leaves the kernel is the pooled array. On a v5e it runs at the product's
six-pass MXU time (0.38 s for 6,250 images against 0.37: PERF.md, PR 33).

Math identical to the stage walk (cross-checked in tests):

  z      = (p / sd(p)) . g + bias          p a patch, g a folded filter
  pooled = [ sum_win max(z - alpha, max_val) | sum_win max(-z - alpha, max_val) ]

The deviation's reciprocal scales the patches (K values a position), not
the responses (F values a position): the same product, a hundredth of the
multiplies at these widths. Float32 operands run at `Precision.HIGHEST`
(six bf16 passes; Mosaic's default for float32 is one), bfloat16 operands
accumulate in float32; the window sums are float32 adds on the VPU.

Layout. XLA writes the patches once in front of the kernel, lane-dense
(K padded to 128), with the positions ordered so that those owned by the
same set of windows lie side by side (for 14 / 13 on 27: nine runs), those no
window owns dropped, padded to whole sublane groups: the kernel then sums
most 8-position groups into their window with one add a vreg, and only a
group that straddles two runs needs a mask. Positions are on sublanes and
filters on lanes, so the pooled (1, filters) rows are written lane-dense.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from keystone_tpu.utils.metrics import device_scope

_LANES = 128
_SUBLANES = 8
# What the kernel asks of the compiler (Mosaic's own default is 16 MiB on a
# v5e, of 128 MiB): room for a response tile and its rectified halves beside
# the double-buffered patches. The tile sizes below are cut to it.
_VMEM_LIMIT = 32 * 2**20
_ALL = 2**_SUBLANES - 1


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _axis_runs(length: int, size: int, stride: int):
    """``(windows, runs)`` along one axis: the window count, and the maximal
    runs ``(start, stop, owners)`` of positions owned by the same non-empty
    set of windows."""
    count = (length - size) // stride + 1
    owners = [
        tuple(a for a in range(count) if a * stride <= i < a * stride + size)
        for i in range(length)
    ]
    runs, start = [], 0
    for i in range(1, length + 1):
        if i == length or owners[i] != owners[start]:
            if owners[start]:
                runs.append((start, i, owners[start]))
            start = i
    return count, runs


@functools.lru_cache(maxsize=None)
def _pool_layout(oh: int, ow: int, size: int, stride: int):
    """``(ph, pw, regions, groups)`` for ``size`` / ``stride`` windows on
    ``oh x ow`` positions. ``regions``: the ``(r0, r1, c0, c1)`` rectangles
    in the order the kernel wants the positions (row-major inside each).
    ``groups``: for each group of eight positions in that order, the
    ``(window, bits)`` pairs of the windows that own some of its positions,
    ``bits`` the owned ones (bit i: position i of the group)."""
    ph, row_runs = _axis_runs(oh, size, stride)
    pw, col_runs = _axis_runs(ow, size, stride)
    regions, owners = [], []
    for r0, r1, rows in row_runs:
        for c0, c1, cols in col_runs:
            regions.append((r0, r1, c0, c1))
            owned = tuple(a * pw + b for a in rows for b in cols)
            owners.extend([owned] * ((r1 - r0) * (c1 - c0)))
    groups = []
    for start in range(0, len(owners), _SUBLANES):
        bits = {}
        for i, owned in enumerate(owners[start:start + _SUBLANES]):
            for window in owned:
                bits[window] = bits.get(window, 0) | (1 << i)
        groups.append(tuple(sorted(bits.items())))
    return ph, pw, tuple(regions), tuple(groups)


def _kernel(p_ref, b_ref, bias_ref, o_ref, *, groups, windows, alpha,
            max_val, precision):
    """p_ref (tn, P, K) patches; b_ref (K, fc) bank tile; bias_ref (1, fc);
    o_ref (tn, 2 windows, fc): row 2 v + h is window v of half h."""
    fc = b_ref.shape[1]
    sublane = lax.broadcasted_iota(jnp.int32, (_SUBLANES, fc), 0)
    masks = {
        bits: ((bits >> sublane) & 1) == 1
        for bits in sorted({b for g in groups for _w, b in g if b != _ALL})
    }
    bank = b_ref[...]
    bias = bias_ref[...]

    def image(t, carry):
        z = jnp.dot(
            p_ref[t], bank,
            preferred_element_type=jnp.float32, precision=precision,
        ) + bias
        halves = (
            jnp.maximum(z - alpha, max_val),
            jnp.maximum(-z - alpha, max_val),
        )
        for h, rectified in enumerate(halves):
            sums = [None] * windows
            for k, owned in enumerate(groups):
                group = rectified[k * _SUBLANES:(k + 1) * _SUBLANES]
                for window, bits in owned:
                    part = (
                        group if bits == _ALL
                        else jnp.where(masks[bits], group, 0.0)
                    )
                    sums[window] = (
                        part if sums[window] is None else sums[window] + part
                    )
            for window, total in enumerate(sums):
                o_ref[t, pl.ds(2 * window + h, 1), :] = jnp.sum(
                    total, axis=0, keepdims=True
                )
        return carry

    lax.fori_loop(0, p_ref.shape[0], image, None)


def _tiles(n: int, positions: int, k: int, filters: int, itemsize: int):
    """``(tn, fc)``: images and filters a grid step, from the shapes and
    ``_VMEM_LIMIT``: three eighths of it for a response tile and its two
    rectified halves (float32), a quarter for the patches (two buffers).
    The filters go in the fewest steps that fit, evenly (a ragged last step
    computes its whole tile: 23 lane tiles with room for ten go as three
    steps of eight; 10,000 filters are 79, eight steps of ten)."""
    most = max(1, 3 * _VMEM_LIMIT // 8 // (3 * 4 * positions * _LANES))
    lanes = filters // _LANES
    fc = -(-lanes // -(-lanes // most))
    tn = max(1, _VMEM_LIMIT // 4 // (2 * positions * k * itemsize))
    return min(tn, _SUBLANES, n), fc * _LANES


def _patch_layout(side, k, window, stride, pool_size, pool_stride, dtype):
    """``(oh, ow, positions, kp, pool layout)``: the patch positions of an
    (h, w) image, how many the kernel is handed a row (those a window owns,
    in whole sublane tiles: 8 rows of float32, 16 of bfloat16) and the
    patch's K values padded to whole lanes."""
    (h, w), (fh, fw) = side, window
    oh, ow = (h - fh) // stride + 1, (w - fw) // stride + 1
    layout = _pool_layout(oh, ow, pool_size, pool_stride)
    rows = _SUBLANES * 4 // jnp.dtype(dtype).itemsize
    positions = _round_up(len(layout[3]) * _SUBLANES, rows)
    return oh, ow, positions, _round_up(k, _LANES), layout


def patch_arrays(images, k: int, *, window, stride: int, pool_size: int,
                 pool_stride: int, compute_dtype=None) -> tuple:
    """What XLA writes to the device's memory in front of the kernel for
    ``images`` (a shape), as shapes: the explicit patches (n, oh, ow, kp)
    and their copy in the kernel's order (n, positions, kp). 750 KB a row
    at CIFAR's 27 x 27 positions of 108 values, whatever the filter count:
    the arrays a caller that holds many rows has to cut along them for."""
    n, h, w, _c = images.shape
    dtype = jnp.dtype(compute_dtype or jnp.float32)
    oh, ow, positions, kp, _layout = _patch_layout(
        (h, w), k, tuple(window), stride, pool_size, pool_stride, dtype)
    return (jax.ShapeDtypeStruct((n, oh, ow, kp), jnp.float32),
            jax.ShapeDtypeStruct((n, positions, kp), dtype))


@functools.partial(jax.jit, static_argnames=(
    "window", "stride", "alpha", "max_val", "pool_size", "pool_stride",
    "dtype", "interpret"))
def _conv_rectify_pool(images, bank, scale, bias, *, window, stride, alpha,
                       max_val, pool_size, pool_stride, dtype, interpret):
    n, h, w, c = images.shape
    fh, fw = window
    k, filters = bank.shape
    dtype = jnp.dtype(dtype)
    oh, ow, positions, kp, (ph, pw, regions, groups) = _patch_layout(
        (h, w), k, window, stride, pool_size, pool_stride, dtype)
    fp = _round_up(filters, _LANES)
    # The explicit patches, (n, oh, ow, kp): `patches.windows`' values and
    # order (row, column, channel), cut by a convolution with one-hot
    # filters (at HIGHEST the three bf16 parts of a float32 add up to it
    # again). Slices of the c-channel image, c values a lane tile, cost XLA
    # 50 times the patches' memory at 6,250 rows; this writes whole lanes.
    with device_scope("conv.patches"):
        patches = lax.conv_general_dilated(
            images, jnp.eye(k, kp, dtype=images.dtype).reshape(fh, fw, c, kp),
            (stride, stride), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=lax.Precision.HIGHEST,
        )
        if scale is not None:
            patches = patches * scale
        ordered = jnp.concatenate([
            patches[:, r0:r1, c0:c1, :].reshape(n, -1, kp)
            for r0, r1, c0, c1 in regions
        ], axis=1).astype(dtype)
        ordered = jnp.pad(
            ordered, ((0, 0), (0, positions - ordered.shape[1]), (0, 0))
        )
        bank = jnp.pad(bank.astype(dtype), ((0, kp - k), (0, fp - filters)))
        bias = jnp.pad(bias.astype(jnp.float32), (0, fp - filters))[None, :]
    groups = groups + ((),) * (positions // _SUBLANES - len(groups))
    tn, fc = _tiles(n, positions, kp, fp, dtype.itemsize)
    kernel = pl.pallas_call(
        functools.partial(
            _kernel, groups=groups, windows=ph * pw, alpha=alpha,
            max_val=max_val,
            precision=lax.Precision.HIGHEST if dtype == jnp.float32 else None,
        ),
        # Filter tiles innermost: a tile of patches is fetched once and
        # meets every tile of the bank. Ragged last tiles on both axes:
        # rows and lanes are independent, and what lies beyond is dropped.
        grid=(pl.cdiv(n, tn), pl.cdiv(fp, fc)),
        in_specs=[
            pl.BlockSpec((tn, positions, kp), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((kp, fc), lambda i, j: (0, j)),
            pl.BlockSpec((1, fc), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tn, 2 * ph * pw, fc), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, 2 * ph * pw, fp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )
    with device_scope("conv.kernel"):
        out = kernel(ordered, bank, bias)
    # (n, windows, half, filter) -> the rectifier's channel order [pos | neg].
    with device_scope("conv.relayout"):
        return out[..., :filters].reshape(n, ph, pw, 2 * filters)


def conv_rectify_pool(
    images,
    bank,
    scale=None,
    bias=None,
    *,
    window: tuple,
    stride: int = 1,
    alpha: float,
    max_val: float,
    pool_size: int,
    pool_stride: int,
    compute_dtype=None,
    interpret: bool | None = None,
) -> jax.Array:
    """(n, h, w, c) images -> (n, ph, pw, 2 F) float32: the responses
    ``patches . bank * scale + bias`` of every ``window`` (fh, fw) patch at
    ``stride`` (``patches.windows``: K = fh fw c values a position) to the
    (K, F) ``bank``, rectified (``max(z - alpha, max_val)`` beside
    ``max(-z - alpha, max_val)``) and summed over ``pool_size`` windows at
    ``pool_stride``, VALID.

    ``scale`` (n, oh, ow, 1) or None; ``bias`` (F,) or None. The product's
    operands are ``compute_dtype`` (None: float32 at ``Precision.HIGHEST``).
    ``interpret`` and the counters as ``fisher_vectors_pallas`` has them:
    Mosaic everywhere but on the ``cpu`` backend, each call counted once a
    trace as ``pallas_mosaic_calls`` or ``pallas_interpret_calls``.
    """
    from keystone_tpu.utils.metrics import sharding_counters

    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    sharding_counters.bump(
        "pallas_interpret_calls" if interpret else "pallas_mosaic_calls"
    )
    bank = jnp.asarray(bank)
    if bias is None:
        bias = jnp.zeros((bank.shape[1],), jnp.float32)
    return _conv_rectify_pool(
        jnp.asarray(images, jnp.float32), bank, scale, bias,
        window=tuple(window), stride=int(stride), alpha=float(alpha),
        max_val=float(max_val), pool_size=int(pool_size),
        pool_stride=int(pool_stride),
        dtype=str(jnp.dtype(compute_dtype or jnp.float32)),
        interpret=bool(interpret),
    )
