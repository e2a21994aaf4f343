"""cifar-random-patch-kernel: images -> the random-patch featurizer of
cifar-random-patch-10k at 512 filters -> standard scaling -> Gaussian kernel
ridge regression by block Gauss-Seidel, every kernel block generated when
it is visited -> argmax
(keystone_tpu/pipelines/images/random_patch_cifar_kernel.py), and its plain
reference.

The harness loads this file by the name in the configuration's JSON.
``fit`` and ``answers`` are the only functions that touch the program; the
reference imports nothing of it.

The images, the draw of patches and filters and the featurizer's reference
(explicit patches, normalised, whitened, times the unit filters, rectified,
summed over window slices) are cifar-random-patch-10k's own, loaded from its
adapter beside this one: one rule, one copy. What is this configuration's
is the head: the reference's block Gauss-Seidel (``solve``), the equations
of the configuration in a Python loop over blocks, the ragged last block at
its true width, nothing padded, nothing scanned.
"""

from __future__ import annotations

import functools

import numpy as np

from program import stages

PIPELINE = "keystone_tpu.pipelines.images.random_patch_cifar_kernel"


@functools.lru_cache(maxsize=None)
def _base():
    """cifar-random-patch-10k's adapter: ``make_data``, ``geometry``,
    ``draw_indices`` and the featurizer's reference programs. By its path,
    not through ``harness.load_adapter``, which a test puts a planted
    adapter in the place of."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "cifar-random-patch-10k.py")
    spec = importlib.util.spec_from_file_location("benchmark_adapter_cifar_random_patch_10k", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def geometry(sizes: dict) -> dict:
    return _base().geometry(sizes)


def make_data(seed: int, sizes: dict) -> dict:
    """cifar-random-patch-10k's class-textured images, made on the device
    from the seed, where they stay: the whole train set and the held-out
    rows."""
    import importlib.util

    import harness

    # A program without the kernel pipeline cannot run the cell: say so
    # before anything is made.
    if importlib.util.find_spec(PIPELINE) is None:
        raise harness.Refused(f"the program has no {PIPELINE}")
    return _base().make_data(seed, sizes)


def blocks_of(sizes: dict) -> list:
    n, b = sizes["rows"], min(sizes["block_size"], sizes["rows"])
    return [(s, min(s + b, n)) for s in range(0, n, b)]


# ---------------------------------------------------------------- program


def fit(data: dict, sizes: dict, **changes):
    """One whole fit through ``random_patch_cifar_kernel.fit``, the
    construction the CLI shares; returns when the dual weights are on the
    device and one element is on the host. ``changes`` are for the planted
    faults."""
    import importlib

    kernel_fit = importlib.import_module(PIPELINE)
    conf = dict(
        num_filters=sizes["num_filters"], patch_size=sizes["patch_size"],
        patch_sample=sizes["patch_sample"], patch_norm=sizes["patch_norm"],
        pool_size=sizes["pool_size"], pool_stride=sizes["pool_stride"],
        alpha=sizes["alpha"], zca_eps=sizes["zca_eps"],
        num_classes=sizes["num_classes"], gamma=sizes["gamma"], lam=sizes["lam"],
        block_size=sizes["block_size"], num_epochs=sizes["num_epochs"],
        seed=data["weights_seed"])
    conf.update(changes)
    fitted = kernel_fit.fit(
        kernel_fit.RandomPatchCifarKernelConfig(**conf), data["x"], data["y"])
    mapper = parts_of(fitted)[2]
    mapper.alpha.block_until_ready()
    np.asarray(mapper.alpha[-1, -1])
    return fitted


def parts_of(fitted):
    """(the featurizing chain conv | rectify | pool | vectorize as one fused
    transformer, the scaler, the kernel map) of a fitted pipeline."""
    from keystone_tpu.workflow import FusedTransformer

    found = stages(fitted)
    kinds = [type(s).__name__ for s in found]
    want = ["Convolver", "SymmetricRectifier", "Pooler", "ImageVectorizer",
            "StandardScalerModel", "KernelBlockLinearMapper", "MaxClassifier"]
    if kinds != want:
        raise AssertionError(f"the fitted pipeline's stages are {kinds}, not {want}")
    return FusedTransformer(found[:4]), found[4], found[5]


def answers(fitted, data: dict, sizes: dict) -> dict:
    """What the timed fit produced: the dual weights, and the features and
    class scores of the held-out rows. The held-out rows go through the
    chain's program at the timed shape (repeated up to the train rows'
    count), so nothing new compiles; scaler and kernel map are applied by
    themselves. ``train_features`` is what the fit's solve read (the scaled
    train rows the map keeps, still on the device): the reference solves
    on them once, so that ``alpha_gap`` reads the solve and nothing in
    front of it."""
    import jax.numpy as jnp

    chain, scaler, mapper = parts_of(fitted)
    held, rows = data["x_held_out"], len(data["x"])
    tiled = jnp.tile(held, (-(-rows // len(held)), 1, 1, 1))[:rows]
    features = chain.batch_call(tiled)[:len(held)]
    del tiled
    scores = mapper.batch_call(scaler.apply_batch(features))
    n, block = mapper.X_train.shape[0], mapper.block_size
    return {
        "features": np.asarray(features),
        "scores": np.asarray(scores),
        "alpha": np.asarray(mapper.alpha),
        "train_features": mapper.X_train,
        "facts": {
            "feature_dim": int(mapper.X_train.shape[1]), "rows": int(n),
            "block_size": int(block), "blocks": -(-n // block),
            "classes": int(mapper.alpha.shape[1]),
            "filters": int(chain.stages[0].num_filters),
        },
    }


def expected_facts(sizes: dict) -> dict:
    blocks = blocks_of(sizes)
    return {"feature_dim": geometry(sizes)["feature_dim"], "rows": sizes["rows"],
            "block_size": blocks[0][1], "blocks": len(blocks),
            "classes": sizes["num_classes"], "filters": sizes["num_filters"]}


# --------------------------------------------------------------- counting


def flops(sizes: dict, work) -> dict:
    """Canonical FLOPs of one fit: the products of every patch with every
    filter, by ``conv_roofline``'s rule, and the kernel solve: an epoch
    generates all of K once (2 n² d) and multiplies it once (2 n² k), a
    visit factorises its b x b block (b³ / 3) and solves against it
    (2 b² k), the ragged block at its true width. A fixed accounting of the
    least work, whatever implements it."""
    g = geometry(sizes)
    n, k, d = sizes["rows"], sizes["num_classes"], g["feature_dim"]
    visits = sum((e - s) ** 3 / 3.0 + 2.0 * (e - s) ** 2 * k for s, e in blocks_of(sizes))
    return {"convolution": 2.0 * n * g["positions"] ** 2 * g["patch_dim"] * sizes["num_filters"],
            "solver": sizes["num_epochs"] * (2.0 * n * n * d + 2.0 * n * n * k + visits)}


def bytes_moved(sizes: dict, work, itemsize: int = 4) -> dict:
    """Least HBM traffic: the chain reads the images, the filters and the
    bias and writes the pooled features, each once; a visit reads X once,
    the block's alpha and Y, and writes alpha_B (K never reaches HBM in
    the least count)."""
    g = geometry(sizes)
    n, filters, k, d = sizes["rows"], sizes["num_filters"], sizes["num_classes"], g["feature_dim"]
    image = sizes["image_side"] ** 2 * sizes["channels"]
    visits = sum(n * d + 3 * (e - s) * k for s, e in blocks_of(sizes))
    return {"convolution": float(itemsize * (
                n * image + g["patch_dim"] * filters + filters + n * g["feature_dim"])),
            "solver": float(itemsize * sizes["num_epochs"] * visits)}


# -------------------------------------------------------------- reference
#
# The reference is given the images, the labels, the seed and, for one of
# its two solves, the scaled train features the fit's own solve read. It
# draws patches and filters by the configuration's rule, fits its own
# whitener and filters, featurizes train and held-out rows a block of rows
# at a time (cifar-random-patch-10k's reference programs), scales by its
# own moments and solves by block Gauss-Seidel:
#   features   the held-out rows' pooled features (its own featurizer);
#   alpha      its solve on the FIT's scaled train features: the solve
#              alone, whatever the featurizers' scatter;
#   scores     the held-out rows' class scores under its own features, its
#              own scaler and its own solve on them: the whole chain.


@functools.lru_cache(maxsize=None)
def _kernel_programs():
    """The head's two jitted steps, traced once a process and a precision."""
    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import cho_factor, cho_solve

    @jax.jit
    def kernel_block(x, z, gamma):
        d2 = (x * x).sum(axis=1)[:, None] + (z * z).sum(axis=1)[None, :] - 2.0 * (x @ z.T)
        return jnp.exp(-gamma * jnp.maximum(d2, 0.0))

    @jax.jit
    def solve_block(k_b, k_bb, y_b, alpha, alpha_b, lam):
        r = y_b - k_b.T @ alpha + k_bb @ alpha_b
        chol = cho_factor(k_bb + lam * jnp.eye(k_bb.shape[0], dtype=k_bb.dtype))[0]
        return cho_solve((chol, False), r)

    return kernel_block, solve_block


def solve(x, y, sizes: dict):
    """alpha (n, k) after ``num_epochs`` sweeps of block Gauss-Seidel from
    0 over the blocks of ``block_size`` rows in natural order: the
    configuration's equations, one visit after another."""
    import jax.numpy as jnp
    from jax.lax import dynamic_slice_in_dim as rows_of
    from jax.lax import dynamic_update_slice_in_dim

    kernel_block, solve_block = _kernel_programs()
    gamma, lam = jnp.float32(sizes["gamma"]), jnp.float32(sizes["lam"])
    alpha = jnp.zeros_like(y)
    for _ in range(sizes["num_epochs"]):
        for s, e in blocks_of(sizes):
            k_b = kernel_block(x, rows_of(x, s, e - s), gamma)
            new = solve_block(k_b, rows_of(k_b, s, e - s), rows_of(y, s, e - s), alpha,
                              rows_of(alpha, s, e - s), lam)
            alpha = dynamic_update_slice_in_dim(alpha, new, s, 0)
    return alpha


def scores_of(held, x, alpha, sizes: dict):
    """k(held, x) alpha, a block of train rows at a time."""
    import jax.numpy as jnp
    from jax.lax import dynamic_slice_in_dim as rows_of

    kernel_block, _ = _kernel_programs()
    gamma = jnp.float32(sizes["gamma"])
    return sum(kernel_block(held, rows_of(x, s, e - s), gamma) @ rows_of(alpha, s, e - s)
               for s, e in blocks_of(sizes))


def reference(data: dict, sizes: dict, answers: dict, precision: str = "highest") -> dict:
    """Plain float32 ``jax.numpy``. Of ``answers`` it reads
    ``train_features`` alone, and only for ``alpha``. ``precision`` below
    ``highest`` is the control, never the reference."""
    import time

    import jax
    import jax.numpy as jnp

    t0 = time.time()
    base = _base()
    whitener, unit_filters, _folded, features, _visit = base._programs(
        sizes["patch_size"], sizes["pool_size"], sizes["pool_stride"],
        min(base.FILTER_BLOCK, sizes["num_filters"]))
    f32 = jnp.float32
    offset, eps, rectifier = f32(sizes["patch_norm"]), f32(sizes["zca_eps"]), f32(sizes["alpha"])

    with jax.default_matmul_precision(precision):
        x, held = jnp.asarray(data["x"]), jnp.asarray(data["x_held_out"])
        image, top, left, chosen = base.draw_indices(data["weights_seed"], len(x), sizes)
        patches, mu, m = whitener(x, image, top, left, offset, eps)
        f = unit_filters(patches, chosen, mu, m)
        del patches
        block = min(base.ROW_BLOCK, len(x))

        def featurize(rows):
            return base._in_row_blocks(
                lambda r: features(r, mu, m, f, offset, rectifier), rows, block)

        train, held_features = featurize(x), featurize(held)
        t1 = time.time()

        n = train.shape[0]
        mean = train.mean(axis=0)
        std = jnp.maximum(jnp.sqrt(((train - mean) ** 2).sum(axis=0) / (n - 1)), 1e-8)
        scaled = (train - mean) / std
        del train
        y = 2.0 * jax.nn.one_hot(jnp.asarray(data["y"]), sizes["num_classes"], dtype=f32) - 1.0
        own = solve(scaled, y, sizes)
        scores = scores_of((held_features - mean) / std, scaled, own, sizes)
        out = {"features": np.asarray(held_features), "scores": np.asarray(scores)}
        del scaled, own
        if "train_features" in answers:
            out["alpha"] = np.asarray(solve(jnp.asarray(answers["train_features"]), y, sizes))
        out["seconds"] = {"features": t1 - t0, "solve": time.time() - t1}
        return out
