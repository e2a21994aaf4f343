"""cifar-random-patch-10k: images -> convolution with whitened random-patch
filters (patches normalised, whitener folded) -> symmetric rectifier -> sum
pooling -> standard scaling -> block least squares -> argmax
(keystone_tpu/pipelines/images/random_patch_cifar.py), and its plain
reference.

The harness loads this file by the name in the configuration's JSON.
``fit`` and ``answers`` are the only functions that touch the program; the
reference imports nothing of it.

The filter bank is the model's weights. The program fits it inside the
timed fit, from patches and filters drawn by the rule the configuration
states (``draw_indices``); the reference draws them again by that rule
from the same images and fits its own whitener and its own filters. It
never folds anything: it normalises and whitens explicit patches and takes
their products with the unit filters, which is what the program's one
convolution has to equal. ``answers`` reads the program's folded bank and
bias off the fitted convolver only to have them compared with what the
reference's own whitener and filters fold to (``filters_gap``).
"""

from __future__ import annotations

import functools

import numpy as np

from program import facts, linear_map, program_seed, stages, wait_for


def geometry(sizes: dict) -> dict:
    """Patch positions a side, pooled windows a side, features an image."""
    positions = sizes["image_side"] - sizes["patch_size"] + 1
    pooled = (positions - sizes["pool_size"]) // sizes["pool_stride"] + 1
    return {"positions": positions, "pooled": pooled,
            "patch_dim": sizes["patch_size"] ** 2 * sizes["channels"],
            "feature_dim": pooled * pooled * 2 * sizes["num_filters"]}


def make_data(seed: int, sizes: dict) -> dict:
    """Class-textured images in [0, 255]: two plane waves a class, each
    with the class's frequencies and colour, a phase of the image's own,
    over Gaussian noise. Train and held-out rows in one jitted call on the
    device, where they stay: a chip's share of the corpus, loaded."""
    import jax
    import jax.numpy as jnp

    side, channels, classes = sizes["image_side"], sizes["channels"], sizes["num_classes"]
    n, nh = sizes["rows"], sizes["held_out_rows"]

    @jax.jit
    def make(key):
        ky, kf, kc, kp, kn = jax.random.split(key, 5)
        y = jax.random.randint(ky, (n + nh,), 0, classes)
        freq = jax.random.uniform(kf, (classes, 2, 2), minval=0.3, maxval=2.5)
        colour = jax.random.uniform(kc, (classes, 2, channels), minval=0.3, maxval=1.0)
        phase = jax.random.uniform(kp, (n + nh, 2), maxval=2 * np.pi)
        u = jnp.arange(side, dtype=jnp.float32)
        f = freq[y]  # (rows, wave, axis)
        waves = jnp.sin(f[:, :, 0, None, None] * u[:, None] + f[:, :, 1, None, None] * u
                        + phase[:, :, None, None])  # (rows, wave, side, side)
        x = 127.5 + 45.0 * jnp.einsum("nwab,nwc->nabc", waves, colour[y])
        x = x + 20.0 * jax.random.normal(kn, (n + nh, side, side, channels))
        return jnp.clip(x, 0.0, 255.0), y

    key = jax.random.PRNGKey(program_seed(seed))
    x, y = make(jax.random.fold_in(key, int(seed) // (2**31 - 1)))
    return {"seed": int(seed), "weights_seed": program_seed(seed),
            "x": x[:n], "y": np.asarray(y[:n]).astype(np.int32), "x_held_out": x[n:]}


# ---------------------------------------------------------------- program


def fit(data: dict, sizes: dict, **changes):
    """One whole fit through ``random_patch_cifar.fit``, the construction
    the CLI shares; returns when every weight block is on the device and
    one element is on the host. ``changes`` are for the planted faults."""
    from keystone_tpu.pipelines.images import random_patch_cifar as cifar

    conf = dict(
        num_filters=sizes["num_filters"], patch_size=sizes["patch_size"],
        patch_sample=sizes["patch_sample"], patch_norm=sizes["patch_norm"],
        pool_size=sizes["pool_size"], pool_stride=sizes["pool_stride"],
        alpha=sizes["alpha"], lam=sizes["lam"], block_size=sizes["block_size"],
        num_iters=sizes["num_iters"], zca_eps=sizes["zca_eps"],
        num_classes=sizes["num_classes"], seed=data["weights_seed"])
    conf.update(changes)
    fitted = cifar.fit(cifar.RandomPatchCifarConfig(**conf), data["x"], data["y"])
    wait_for(linear_map(fitted))
    return fitted


def parts_of(fitted):
    """(the featurizing chain conv | rectify | pool | vectorize as one fused
    transformer, the scaler, the linear map) of a fitted pipeline."""
    from keystone_tpu.workflow import FusedTransformer

    found = stages(fitted)
    kinds = [type(s).__name__ for s in found]
    want = ["Convolver", "SymmetricRectifier", "Pooler", "ImageVectorizer",
            "StandardScalerModel", "BlockLinearMapper", "MaxClassifier"]
    if kinds != want:
        raise AssertionError(f"the fitted pipeline's stages are {kinds}, not {want}")
    return FusedTransformer(found[:4]), found[4], found[5]


def answers(fitted, data: dict, sizes: dict) -> dict:
    """What the timed fit produced: its folded filter bank with the bias,
    and the features and class scores of the held-out rows under it. The
    held-out rows go through the chain's program at the timed shape
    (repeated up to the train rows' count), so nothing new compiles at
    10,000 filters; scaler and linear map are applied by themselves."""
    import jax.numpy as jnp

    chain, scaler, mapper = parts_of(fitted)
    conv = chain.stages[0]
    held, rows = data["x_held_out"], len(data["x"])
    tiled = jnp.tile(held, (-(-rows // len(held)), 1, 1, 1))[:rows]
    features = chain.batch_call(tiled)[:len(held)]
    del tiled
    scores = mapper.apply_batch(scaler.apply_batch(features))
    bank = np.asarray(conv.filters).reshape(conv.num_filters, -1)
    return {
        "features": np.asarray(features),
        "scores": np.asarray(scores),
        "filters": np.concatenate([bank, np.asarray(conv.bias)[:, None]], axis=1),
        "facts": dict(facts(mapper), filters=int(conv.num_filters)),
    }


def expected_facts(sizes: dict) -> dict:
    d, b = geometry(sizes)["feature_dim"], sizes["block_size"]
    return {"feature_dim": d, "block_size": min(b, d), "blocks": -(-d // b),
            "classes": sizes["num_classes"], "filters": sizes["num_filters"]}


# --------------------------------------------------------------- counting


def _solver_parts(sizes: dict):
    """The solve as ``work.bcd_*`` count it (whole blocks only): the whole
    blocks, then the ragged last block at its true width. One epoch with
    nothing cached is a gram and a factorisation a visit, which is what
    the cached count's once-a-block terms are at one epoch."""
    f = expected_facts(sizes)
    n, k, b, iters = sizes["rows"], f["classes"], f["block_size"], sizes["num_iters"]
    whole, tail = divmod(f["feature_dim"], b)
    parts = [dict(n=n, d=whole * b, k=k, block=b, iters=iters)]
    if tail:
        parts.append(dict(n=n, d=tail, k=k, block=tail, iters=iters))
    return parts


def flops(sizes: dict, work) -> dict:
    """Canonical FLOPs of one fit: the products of every patch with every
    filter (normalisation, rectifier, pooling and the filter fit are not
    counted), and the block solve."""
    g = geometry(sizes)
    n = sizes["rows"]
    return {"convolution": 2.0 * n * g["positions"] ** 2 * g["patch_dim"] * sizes["num_filters"],
            "solver": sum(work.bcd_flops(**p) for p in _solver_parts(sizes))}


def bytes_moved(sizes: dict, work, itemsize: int = 4) -> dict:
    """Least HBM traffic: the chain reads the images, the filters and the
    bias and writes the pooled features, each once, whatever implements
    it; a response that goes through HBM on its way to the pool is more."""
    g = geometry(sizes)
    n, filters = sizes["rows"], sizes["num_filters"]
    image = sizes["image_side"] ** 2 * sizes["channels"]
    return {"convolution": float(itemsize * (
                n * image + g["patch_dim"] * filters + filters + n * g["feature_dim"])),
            "solver": sum(work.bcd_bytes(**p) for p in _solver_parts(sizes))}


# -------------------------------------------------------------- reference
#
# The reference is given the images, the labels and the seed, and nothing
# the program made. It draws the patch and filter indices by the
# configuration's rule, normalises the patches, fits its own ZCA map,
# whitens the drawn patches into unit filters; then, a block of rows and a
# block of filters at a time: every patch of every image, explicit,
# normalised, less the whitener's mean, times the map, times the filters;
# rectified both ways, summed over explicit window slices. It scales by its
# own moments and runs block coordinate descent in block order with a
# Cholesky solve a visit, the ragged last block at its true width.
#   filters    what its own map, mean and filters fold to: (F, patch_dim + 1),
#              the centred bank with the bias as the last column;
#   features   the held-out rows' pooled features;
#   scores     their class scores under the reference's own solve.

ROW_BLOCK, FILTER_BLOCK = 125, 2500


def draw_indices(seed: int, rows: int, sizes: dict):
    """(image, top, left) of every whitening patch and the filters' rows
    among them, as the configuration states the draw."""
    positions = geometry(sizes)["positions"]
    count = sizes["patch_sample"]
    rng = np.random.default_rng(seed)
    image = rng.integers(0, rows, size=count)
    top = rng.integers(0, positions, size=count)
    left = rng.integers(0, positions, size=count)
    chosen = np.random.default_rng(seed + 1).choice(
        count, size=sizes["num_filters"], replace=False)
    return image, top, left, chosen


@functools.lru_cache(maxsize=None)
def _programs(patch: int, pool: int, stride: int, filter_block: int):
    """The reference's jitted programs, traced once a process and a
    precision. Everything a run brings enters them as an argument."""
    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import cho_factor, cho_solve

    def normalise(p, offset):
        p = p - p.mean(axis=-1, keepdims=True)
        var = (p * p).sum(axis=-1, keepdims=True) / (p.shape[-1] - 1)
        return p / jnp.sqrt(var + offset)

    @jax.jit
    def whitener(x, image, top, left, offset, eps):
        """(normalised patches, their mean, the ZCA map)."""
        n, side, _, c = x.shape
        rows = x.reshape(n, side, side * c)  # a patch: `patch` rows of patch·c values

        def cut(i, t, l):
            return jax.lax.dynamic_slice(rows, (i, t, l * c), (1, patch, patch * c))

        p = normalise(jax.vmap(cut)(image, top, left).reshape(len(image), -1), offset)
        mu = p.mean(axis=0)
        cov = (p - mu).T @ (p - mu) / p.shape[0]
        lam, v = jnp.linalg.eigh(cov)
        return p, mu, (v / jnp.sqrt(jnp.maximum(lam, 0.0) + eps)) @ v.T

    @jax.jit
    def unit_filters(p, chosen, mu, m):
        f = (p[chosen] - mu) @ m
        return f / jnp.linalg.norm(f, axis=1, keepdims=True)

    @jax.jit
    def folded(f, mu, m):
        g = f @ m.T
        return jnp.concatenate([g - g.mean(axis=1, keepdims=True), -(g @ mu)[:, None]], axis=1)

    @jax.jit
    def features(x, mu, m, f, offset, rectifier):
        """Pooled features of the images ``x``: (rows, py, px, 2 F) flattened."""
        n, side = x.shape[0], x.shape[1]
        positions = side - patch + 1
        cols = [x[:, i:i + positions, j:j + positions, :]
                for i in range(patch) for j in range(patch)]
        p = jnp.stack(cols, axis=3).reshape(n, positions, positions, -1)
        white = (normalise(p, offset) - mu) @ m
        starts = range(0, positions - pool + 1, stride)

        def pooled(r):  # explicit window slices
            return jnp.stack([jnp.stack([r[:, a:a + pool, b:b + pool].sum(axis=(1, 2))
                                         for b in starts], axis=1) for a in starts], axis=1)

        halves = ([], [])
        for s in range(0, f.shape[0], filter_block):
            z = white @ f[s:s + filter_block].T
            halves[0].append(pooled(jnp.maximum(z - rectifier, 0.0)))
            halves[1].append(pooled(jnp.maximum(-z - rectifier, 0.0)))
        return jnp.concatenate(halves[0] + halves[1], axis=-1).reshape(n, -1)

    @jax.jit
    def visit(a, r, w, lam):
        r_plus = r + a @ w
        chol = cho_factor(a.T @ a + lam * jnp.eye(a.shape[1], dtype=a.dtype))[0]
        w_new = cho_solve((chol, False), a.T @ r_plus)
        return r_plus - a @ w_new, w_new

    return whitener, unit_filters, folded, features, visit


def _in_row_blocks(fn, x, block):
    """``fn`` over ``x`` a block of rows at a time (the last one padded with
    copies of the first rows and trimmed), so that one shape compiles."""
    import jax.numpy as jnp

    out = []
    for s in range(0, len(x), block):
        rows = x[s:s + block]
        short = block - len(rows)
        if short:
            rows = jnp.concatenate([rows, x[:short]])
        out.append(fn(rows)[:block - short])
    return jnp.concatenate(out)


def reference(data: dict, sizes: dict, answers: dict, precision: str = "highest") -> dict:
    """Plain float32 ``jax.numpy``, rows and filters in blocks. ``answers``
    is not read: the harness hands it to every reference, and this one
    takes nothing from the program. ``precision`` below ``highest`` is the
    control, never the reference."""
    import time

    import jax
    import jax.numpy as jnp

    t0 = time.time()
    whitener, unit_filters, folded, features, visit = _programs(
        sizes["patch_size"], sizes["pool_size"], sizes["pool_stride"],
        min(FILTER_BLOCK, sizes["num_filters"]))
    f32 = jnp.float32
    offset, eps = f32(sizes["patch_norm"]), f32(sizes["zca_eps"])
    rectifier, lam = f32(sizes["alpha"]), f32(sizes["lam"])
    fx = expected_facts(sizes)
    d, b, k = fx["feature_dim"], fx["block_size"], fx["classes"]
    blocks = [(s, min(s + b, d)) for s in range(0, d, b)]

    with jax.default_matmul_precision(precision):
        x, held = jnp.asarray(data["x"]), jnp.asarray(data["x_held_out"])
        image, top, left, chosen = draw_indices(data["weights_seed"], len(x), sizes)
        patches, mu, m = whitener(x, image, top, left, offset, eps)
        f = unit_filters(patches, chosen, mu, m)
        del patches
        bank = folded(f, mu, m)
        block = min(ROW_BLOCK, len(x))

        def featurize(rows):
            return _in_row_blocks(
                lambda r: features(r, mu, m, f, offset, rectifier), rows, block)

        train, held_features = featurize(x), featurize(held)
        t1 = time.time()

        n = train.shape[0]
        mean = train.mean(axis=0)
        std = jnp.maximum(jnp.sqrt(((train - mean) ** 2).sum(axis=0) / (n - 1)), 1e-8)
        scaled = (train - mean) / std
        del train
        x_mean = scaled.mean(axis=0)
        scaled = scaled - x_mean
        y = 2.0 * jax.nn.one_hot(jnp.asarray(data["y"]), k, dtype=f32) - 1.0
        y_mean = y.mean(axis=0)
        r = y - y_mean
        w = [jnp.zeros((e - s, k), f32) for s, e in blocks]
        for _ in range(sizes["num_iters"]):
            for i, (s, e) in enumerate(blocks):
                r, w[i] = visit(scaled[:, s:e], r, w[i], lam)
        held_scaled = (held_features - mean) / std - x_mean
        scores = y_mean + sum(held_scaled[:, s:e] @ wi for (s, e), wi in zip(blocks, w))
        return {
            "features": np.asarray(held_features),
            "scores": np.asarray(scores),
            "filters": np.asarray(bank),
            "seconds": {"features": t1 - t0, "solve": time.time() - t1},
        }
