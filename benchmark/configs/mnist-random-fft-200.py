"""mnist-random-fft-200: images -> 200 branches of random signs, a padded
FFT's real half and a rectifier, gathered -> block least squares at block
2048 -> argmax (keystone_tpu/pipelines/images/mnist_random_fft.py), and its
plain reference.

The harness loads this file by the name in the configuration's JSON.
``fit`` and ``answers`` are the only functions that touch the program; the
reference imports nothing of it.

The 102,400 features of 60,000 rows (24.6 GB) are never held by either
side. The program makes each block of 2,048 columns from the images inside
its solve; the reference makes each block again at every visit, as the
real DFT's product. The signs are the model's random weights: the program
draws them inside the timed fit, and the reference draws them again by the
rule the configuration states (``draw_signs``): it computes nothing from an
array the program made. ``answers`` reads the program's signs off the
fitted stack only to have them compared with the reference's, entry by
entry (``signs_gap``).
"""

from __future__ import annotations

import functools

import numpy as np

from program import facts, linear_map, program_seed, wait_for

PIPELINE = "keystone_tpu.pipelines.images.mnist_random_fft"

#: The blocks whose held-out features are compared: the first, one in the
#: middle and the last.
FEATURE_BLOCKS = (0, 0.5, 1)


def _pad_to(sizes: dict) -> int:
    return sizes["fft_size"]


def make_data(seed: int, sizes: dict) -> dict:
    """``MnistLoader.synthetic``'s class-prototype digits from the seed, at
    MNIST's pixel range (clipped to [0, 1], times 255), put on the device
    once, where they stay: the train rows and the held-out rows."""
    import importlib

    import jax.numpy as jnp

    import harness

    mnist = importlib.import_module(PIPELINE)
    if not hasattr(mnist, "fit"):
        # A program without the generated-block fit cannot run the cell:
        # say so before anything is made.
        raise harness.Refused(f"{PIPELINE} has no fit()")
    from keystone_tpu.loaders import MnistLoader

    n, nh = sizes["rows"], sizes["held_out_rows"]
    train, test = MnistLoader.synthetic(
        n=n, num_classes=sizes["num_classes"], dim=sizes["image_dim"],
        seed=program_seed(seed))
    pixels = lambda x: jnp.asarray(255.0 * np.clip(x, 0.0, 1.0), jnp.float32)  # noqa: E731
    return {"seed": int(seed), "weights_seed": program_seed(seed),
            "x": pixels(train.data), "y": np.asarray(train.labels, np.int32),
            "x_held_out": pixels(test.data[:nh])}


def blocks_of(sizes: dict) -> list:
    d, b = sizes["feature_dim"], sizes["block_size"]
    return [(s, min(s + b, d)) for s in range(0, d, b)]


def feature_blocks(sizes: dict) -> list:
    last = len(blocks_of(sizes)) - 1
    return sorted({int(round(f * last)) for f in FEATURE_BLOCKS})


# ---------------------------------------------------------------- program


def fit(data: dict, sizes: dict, **changes):
    """One whole fit through ``mnist_random_fft.fit``, the construction the
    CLI shares; returns when every weight block is on the device and one
    element is on the host. ``changes`` are for the planted faults."""
    import importlib

    mnist = importlib.import_module(PIPELINE)
    conf = dict(num_ffts=sizes["num_ffts"], block_size=sizes["block_size"],
                num_epochs=sizes["num_epochs"], lam=sizes["lam"],
                num_classes=sizes["num_classes"], seed=data["weights_seed"])
    conf.update(changes)
    fitted = mnist.fit(mnist.MnistRandomFFTConfig(**conf), data["x"], data["y"])
    wait_for(linear_map(fitted))
    return fitted


def answers(fitted, data: dict, sizes: dict) -> dict:
    """What the timed fit produced: the weights, the held-out rows'
    features of ``feature_blocks`` as the fitted map makes them, their
    class scores, and the signs the fit drew, for ``signs_gap``."""
    import jax.numpy as jnp

    mapper = linear_map(fitted)
    stack = mapper.generator
    if stack is None:
        raise AssertionError("the fitted map makes no features: the gather was not lowered")
    held = jnp.asarray(data["x_held_out"])
    features = np.concatenate(
        [np.asarray(stack.block(held, b)) for b in feature_blocks(sizes)], axis=1)
    signs = np.asarray(stack.chain.stages[0].signs)
    return {
        "features": features,
        "scores": np.asarray(mapper.apply_batch(held)),
        "weights": np.asarray(mapper.W),
        "signs": signs,
        "facts": dict(facts(mapper), branches=int(stack.branches),
                      per_block=int(stack.per_block)),
    }


def expected_facts(sizes: dict) -> dict:
    blocks = blocks_of(sizes)
    return {"feature_dim": sizes["feature_dim"], "block_size": sizes["block_size"],
            "blocks": len(blocks), "classes": sizes["num_classes"],
            "branches": sizes["num_ffts"],
            "per_block": sizes["block_size"] // sizes["features_per_fft"]}


# --------------------------------------------------------------- counting


def _fft_flops(sizes: dict) -> float:
    """A real FFT's least work, ~2.5 N log2 N, a branch and a row."""
    n = sizes["fft_size"]
    return 2.5 * n * np.log2(n)


def flops(sizes: dict, work) -> dict:
    """Canonical FLOPs of one fit: ``bcd_flops`` of the solve on the
    102,400 columns, and each block made once by the least work of its
    FFTs (the transform as its least work, whatever implements it, so the
    share reads the same work for an FFT and for a DFT product)."""
    n, d, k = sizes["rows"], sizes["feature_dim"], sizes["num_classes"]
    make = sizes["num_ffts"] * n * _fft_flops(sizes)
    return {"solver": work.bcd_flops(n, d, k, sizes["block_size"], sizes["num_epochs"])
            + make}


def bytes_moved(sizes: dict, work, itemsize: int = 4) -> dict:
    """Least HBM traffic: ``bcd_bytes`` of the solve, and each block made
    once: the images read and the block written."""
    n, d, k, b = sizes["rows"], sizes["feature_dim"], sizes["num_classes"], sizes["block_size"]
    make = len(blocks_of(sizes)) * n * (sizes["image_dim"] + b) * itemsize
    return {"solver": work.bcd_bytes(n, d, k, b, sizes["num_epochs"]) + make}


# -------------------------------------------------------------- reference
#
# Plain float32 ``jax.numpy``, a block of columns at a time (the features do
# not fit either), on signs it draws itself (``draw_signs``). The transform
# is the real DFT as a product: bins 0 .. n/2 - 1 of the padded row are its
# 784 live entries times a (784, 512) cosine table, so the zero padding is
# the table's missing rows. The train rows' blocks are that product at the
# reference's precision on the device (a block is made three times a fit:
# too many for the host); the held-out rows' features are NumPy's FFT in
# float64 on the host at ``highest``, and the product at the step's
# precision below it. Then ``max(0, .)``; the block's columns centred by
# their means over the rows; block Gauss-Seidel from W = 0 over the blocks
# in natural order for the configuration's epochs, each block's ridge gram
# (A_b^T A_b + lam I) LU-factored at its first visit and solved against
# A_b^T R at every visit; the intercept ybar - xbar W.
#   signs     the (branches, 784) draw;
#   features  the held-out rows' features of ``feature_blocks``;
#   weights   W;
#   scores    the held-out rows' features, block by block, times W, plus
#             the intercept.


def draw_signs(seed: int, sizes: dict):
    """The signs as the configuration states the draw: branch ``i``'s
    ``image_dim`` signs are ``jax.random.rademacher`` of ``PRNGKey(seed +
    i)``, in float32, one row a branch in branch order."""
    import jax
    import jax.numpy as jnp

    return jnp.stack([
        jax.random.rademacher(jax.random.PRNGKey(seed + i), (sizes["image_dim"],),
                              dtype=jnp.float32)
        for i in range(sizes["num_ffts"])])


def _cosines(sizes: dict) -> np.ndarray:
    """Re(DFT_n) on the live entries: cos(2 pi t k / n) for t < image_dim
    and k < features_per_fft, in float64."""
    t = np.arange(sizes["image_dim"])[:, None]
    k = np.arange(sizes["features_per_fft"])[None, :]
    return np.cos(2.0 * np.pi * t * k / _pad_to(sizes))


def _host_features(rows: np.ndarray, signs: np.ndarray, sizes: dict) -> np.ndarray:
    """Every branch's features of ``rows`` by NumPy's FFT in float64."""
    n, width = _pad_to(sizes), sizes["features_per_fft"]
    rows = np.asarray(rows, np.float64)
    return np.concatenate([
        np.maximum(np.fft.rfft(np.pad(rows * s, ((0, 0), (0, n - rows.shape[1]))),
                               axis=1)[:, :width].real, 0.0)
        for s in np.asarray(signs, np.float64)], axis=1)


@functools.lru_cache(maxsize=None)
def _programs(branches: int):
    """The reference's jitted steps, traced once a process and a
    precision."""
    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import lu_factor, lu_solve

    @jax.jit
    def block(x, signs, cosines):
        """(rows, branches x width): one branch after another."""
        return jnp.concatenate(
            [jnp.maximum((x * signs[i]) @ cosines, 0.0) for i in range(branches)], axis=1)

    @jax.jit
    def factor(a, lam):
        return lu_factor(a.T @ a + lam * jnp.eye(a.shape[1], dtype=a.dtype))

    @jax.jit
    def visit(a, lu, r, w_b):
        r = r + a @ w_b
        w_b = lu_solve(lu, a.T @ r)
        return r - a @ w_b, w_b

    return block, factor, visit


def reference(data: dict, sizes: dict, answers: dict, precision: str = "highest") -> dict:
    """Plain float32 ``jax.numpy``. ``answers`` is not read: the harness
    hands it to every reference, and this one takes nothing from the
    program. ``precision`` below ``highest`` is the control, never the
    reference."""
    import time

    import jax
    import jax.numpy as jnp

    t0 = time.time()
    per = sizes["block_size"] // sizes["features_per_fft"]
    block, factor, visit = _programs(per)
    signs = draw_signs(data["weights_seed"], sizes)
    cosines = jnp.asarray(_cosines(sizes), jnp.float32)
    blocks = blocks_of(sizes)
    with jax.default_matmul_precision(precision):
        x, held = jnp.asarray(data["x"]), np.asarray(data["x_held_out"])

        def make(rows, i):
            return block(rows, signs[i * per:(i + 1) * per], cosines)

        if precision == "highest":
            held_features = _host_features(held, np.asarray(signs), sizes).astype(np.float32)
        else:
            held_features = np.concatenate(
                [np.asarray(make(jnp.asarray(held), i)) for i in range(len(blocks))], axis=1)
        y = 2.0 * jax.nn.one_hot(jnp.asarray(data["y"]), sizes["num_classes"],
                                 dtype=jnp.float32) - 1.0
        y_mean = y.mean(axis=0)
        r = y - y_mean
        lam = jnp.float32(sizes["lam"])
        means, lus, W = [], [], []
        for i in range(len(blocks)):
            a = make(x, i)
            means.append(a.mean(axis=0))
            a = a - means[-1]
            lus.append(factor(a, lam))
            W.append(jnp.zeros((a.shape[1], sizes["num_classes"]), jnp.float32))
            r, W[i] = visit(a, lus[i], r, W[i])
        for _ in range(sizes["num_epochs"] - 1):
            for i in range(len(blocks)):
                r, W[i] = visit(make(x, i) - means[i], lus[i], r, W[i])
        t1 = time.time()
        weights = np.asarray(jnp.concatenate(W, axis=0))
        xbar = np.asarray(jnp.concatenate(means))
        scores = held_features @ weights + (np.asarray(y_mean) - xbar @ weights)
        cols = np.concatenate([np.arange(*blocks[i]) for i in feature_blocks(sizes)])
        out = {"signs": np.asarray(signs), "features": held_features[:, cols],
               "scores": scores, "weights": weights}
    out["seconds"] = {"solve": t1 - t0, "scores": time.time() - t1}
    return out
