"""timit-cosine-rf: frames -> standard scaling -> cosine random features in
blocks -> block least squares -> argmax
(keystone_tpu/pipelines/speech/timit.py), and its plain reference.

The harness loads this file by the name in the configuration's JSON.
``fit`` and ``answers`` are the only functions that touch the program; the
reference imports nothing of it.

The projection ``W`` and the phases ``b`` are the model's weights. The
program draws them from ``TimitConfig.seed`` (the run's seed) inside the
timed fit, and the reference draws them again in plain ``jax.random`` by
the rule the configuration states (``draw_weights``): it computes nothing
from an array the program made. ``answers`` reads the program's ``W`` and
``b`` off the fitted stage only to have them compared with the
reference's, entry by entry (``W_gap``, ``b_gap``).
"""

from __future__ import annotations

import functools

import numpy as np

from program import facts, linear_map, program_seed, wait_for


def make_data(seed: int, sizes: dict) -> dict:
    """Class-structured frames: one mean a class, unit noise, then each
    coordinate given an offset and a scale of its own, so that the
    standard scaling has work to do. Train and held-out rows in one jitted
    call on the device, handed over as host arrays, as a loader would."""
    import jax
    import jax.numpy as jnp

    dim, classes = sizes["input_dim"], sizes["num_classes"]
    n, nh = sizes["rows"], sizes["held_out_rows"]

    @jax.jit
    def make(key):
        ky, km, kn, ko, ks = jax.random.split(key, 5)
        y = jax.random.randint(ky, (n + nh,), 0, classes)
        means = jax.random.normal(km, (classes, dim))
        frames = means[y] + jax.random.normal(kn, (n + nh, dim))
        offset = 3.0 * jax.random.normal(ko, (dim,))
        scale = jnp.exp(0.5 * jax.random.normal(ks, (dim,)))
        return offset + scale * frames, y

    key = jax.random.PRNGKey(program_seed(seed))
    x, y = make(jax.random.fold_in(key, int(seed) // (2**31 - 1)))
    x, y = np.asarray(x), np.asarray(y).astype(np.int32)
    return {"seed": int(seed), "weights_seed": program_seed(seed),
            "x": x[:n], "y": y[:n], "x_held_out": x[n:]}


# ---------------------------------------------------------------- program


def fit(data: dict, sizes: dict):
    """One whole fit through ``timit.fit``, the construction the CLI
    shares; returns when every weight block is on the device and one
    element is on the host."""
    from keystone_tpu.loaders.labeled_data import LabeledData
    from keystone_tpu.pipelines.speech import timit

    fitted = timit.fit(
        timit.TimitConfig(
            num_features=sizes["block_features"], num_cosines=sizes["cosine_blocks"],
            gamma=sizes["gamma"], distribution=sizes["distribution"],
            lam=sizes["lam"], block_size=sizes["block_size"],
            num_iters=sizes["num_iters"], num_phones=sizes["num_classes"],
            seed=data["weights_seed"],
        ),
        LabeledData(data["x"], data["y"]),
    )
    wait_for(linear_map(fitted))
    return fitted


def featurizer_of(fitted):
    """(the fitted pipeline's first transformer, the fused chain scaler |
    cosines; its cosine stage)."""
    from keystone_tpu.nodes.stats import CosineRandomFeatures
    from keystone_tpu.nodes.stats.scalers import StandardScalerModel

    chain = fitted.transformers()[0]
    kinds = (StandardScalerModel, CosineRandomFeatures)
    found = getattr(chain, "stages", [])
    if len(found) != 2 or not all(isinstance(s, k) for s, k in zip(found, kinds)):
        raise AssertionError(f"the pipeline does not start scaler | cosines: {chain!r}")
    return chain, found[1]


def answers(fitted, data: dict, sizes: dict) -> dict:
    """What the timed fit produced: the weights it drew, and the features
    and the class scores of the held-out rows under them. The held-out rows go
    through the chain's program at the timed shape (tiled to the train
    rows' count), so nothing new compiles at 204,800 columns; the linear
    map is applied by itself, not through the executor, whose fused chain
    would end in the argmax."""
    chain, cosines = featurizer_of(fitted)
    mapper = linear_map(fitted)
    held = data["x_held_out"]
    rows = len(data["x"])
    tiled = np.tile(held, (-(-rows // len(held)), 1))[:rows]
    features = chain.batch_call(tiled)[:len(held)]
    return {
        "features": np.asarray(features),
        "scores": np.asarray(mapper.apply_batch(features)),
        "W": np.asarray(cosines.W),
        "b": np.asarray(cosines.b),
        "facts": facts(mapper),
    }


def expected_facts(sizes: dict) -> dict:
    d = sizes["cosine_blocks"] * sizes["block_features"]
    return {"feature_dim": d, "block_size": sizes["block_size"],
            "blocks": d // sizes["block_size"], "classes": sizes["num_classes"]}


# --------------------------------------------------------------- counting


def solver_shape(sizes: dict) -> dict:
    f = expected_facts(sizes)
    return dict(n=sizes["rows"], d=f["feature_dim"], k=f["classes"],
                block=f["block_size"], iters=sizes["num_iters"])


def flops(sizes: dict, work) -> dict:
    """Canonical FLOPs of one fit: the projection X W of the train rows
    (the scaling, the phases and the cosines are not counted), and the
    block solve."""
    n, m = sizes["rows"], sizes["input_dim"]
    d = expected_facts(sizes)["feature_dim"]
    return {"random_features": 2.0 * n * m * d,
            "solver": work.bcd_flops(**solver_shape(sizes))}


def bytes_moved(sizes: dict, work, itemsize: int = 4) -> dict:
    """Least HBM traffic: the random-features program reads the frames,
    the projection and the phases and writes the features, each once."""
    n, m = sizes["rows"], sizes["input_dim"]
    d = expected_facts(sizes)["feature_dim"]
    return {"random_features": float(itemsize * (n * m + m * d + d + n * d)),
            "solver": work.bcd_bytes(**solver_shape(sizes))}


# -------------------------------------------------------------- reference
#
# The reference is given the frames, the labels and the seed, and nothing
# the program made: it draws the weights by the configuration's rule, takes
# the scaling from its own moments, computes cos(X W + b) a block of
# columns at a time, and runs block coordinate descent on the centred
# features with a Cholesky solve a visit, in block order.
#   W, b       the weights as the rule draws them;
#   features   the held-out rows' cosine features under those weights;
#   scores     their class scores under the reference's own solve.


def draw_weights(seed: int, sizes: dict):
    """(W, b) as the configuration states the draw: the key of ``seed``;
    cosine block ``i`` from that key folded with ``i`` (a lone block from
    the key itself), split in two; W's block from the first half (Gaussian
    or Cauchy, times gamma), b's block uniform on [0, 2 pi) from the
    second; the blocks side by side in block order. float32."""
    import jax
    import jax.numpy as jnp

    m, width, blocks = sizes["input_dim"], sizes["block_features"], sizes["cosine_blocks"]
    sample = {"gaussian": jax.random.normal, "cauchy": jax.random.cauchy}[sizes["distribution"]]
    key = jax.random.PRNGKey(seed)
    W, b = [], []
    for i in range(blocks):
        kw, kb = jax.random.split(key if blocks == 1 else jax.random.fold_in(key, i))
        W.append(sample(kw, (m, width), dtype=jnp.float32) * sizes["gamma"])
        b.append(jax.random.uniform(kb, (width,), minval=0.0, maxval=2 * np.pi,
                                    dtype=jnp.float32))
    return jnp.concatenate(W, axis=1), jnp.concatenate(b)


@functools.lru_cache(maxsize=None)
def _programs():
    """The reference's jitted programs, traced once a process (and once a
    precision: the trace context is part of ``jax.jit``'s key). Everything
    a run brings enters them as an argument, so the compiled reference is
    the same for every seed and comes from the compile cache in every run
    after a checkout's first."""
    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import cho_factor, cho_solve

    @jax.jit
    def cosines(rows, mean, std, w, p):
        return jnp.cos(((rows - mean) / std) @ w + p)

    @jax.jit
    def factor(a, lam):
        return cho_factor(a.T @ a + lam * jnp.eye(a.shape[1], dtype=a.dtype))[0]

    @jax.jit
    def visit(a, chol, r, w):
        r_plus = r + a @ w
        w_new = cho_solve((chol, False), a.T @ r_plus)
        return r_plus - a @ w_new, w_new

    return cosines, factor, visit


def reference(data: dict, sizes: dict, answers: dict, precision: str = "highest") -> dict:
    """Plain float32 ``jax.numpy``, a block of columns at a time.
    ``answers`` is not read: the harness hands it to every reference, and
    this one takes nothing from the program. ``precision`` below
    ``highest`` is the control, never the reference."""
    import time

    import jax
    import jax.numpy as jnp

    t0 = time.time()
    cosines, factor, visit = _programs()
    f = expected_facts(sizes)
    d, b, k = f["feature_dim"], f["block_size"], f["classes"]
    blocks = [(s, s + b) for s in range(0, d, b)]
    W, phases = draw_weights(data["weights_seed"], sizes)
    lam = jnp.float32(sizes["lam"])

    with jax.default_matmul_precision(precision):
        x = jnp.asarray(data["x"])
        n = x.shape[0]
        mean = x.mean(axis=0)
        std = jnp.maximum(jnp.sqrt(((x - mean) ** 2).sum(axis=0) / (n - 1)), 1e-8)
        held = jnp.asarray(data["x_held_out"])
        a_blocks, x_means, held_blocks = [], [], []
        for s, e in blocks:
            z = cosines(x, mean, std, W[:, s:e], phases[s:e])
            x_means.append(z.mean(axis=0))
            a_blocks.append(z - x_means[-1])
            held_blocks.append(cosines(held, mean, std, W[:, s:e], phases[s:e]))
        t1 = time.time()

        y = 2.0 * jax.nn.one_hot(jnp.asarray(data["y"]), k, dtype=jnp.float32) - 1.0
        y_mean = y.mean(axis=0)
        chols = [factor(a, lam) for a in a_blocks]
        r = y - y_mean
        w = [jnp.zeros((b, k), jnp.float32) for _ in blocks]
        for _ in range(sizes["num_iters"]):
            for i, a in enumerate(a_blocks):
                r, w[i] = visit(a, chols[i], r, w[i])
        scores = y_mean + sum(
            (zh - m) @ wi for zh, m, wi in zip(held_blocks, x_means, w))
        return {
            "features": np.asarray(jnp.concatenate(held_blocks, axis=1)),
            "scores": np.asarray(scores),
            "W": np.asarray(W),
            "b": np.asarray(phases),
            "seconds": {"features": t1 - t0, "solve": time.time() - t1},
        }
