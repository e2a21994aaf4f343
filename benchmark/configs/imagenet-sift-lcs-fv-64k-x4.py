"""imagenet-sift-lcs-fv-64k-x4: the ImageNet SIFT/LCS Fisher-vector fit of
``imagenet-sift-lcs-fv-64k`` on one four-chip host, rows sharded over the
chips, every chip the whole model, and its plain reference on one device.

The program's side is the one-chip configuration's, through the same entry
point (``imagenet.fit``): the mesh is what ``jax.devices()`` gives. The
reference takes from the one-chip configuration's adapter every stage whose
mathematics does not depend on the layout (descriptors, samples, PCA,
mixture, Fisher encoding: ``base.reference(..., solve=False)`` and its
programs) and brings its own solve, because 32,768 rows of 65,536 features
(8.6 GB) do not fit one chip beside the factors: the features lie on the
host in column blocks and one block is on the device a visit. One device,
no mesh, no collective, nothing of the program's.
"""

from __future__ import annotations

import numpy as np

import harness
from program import facts, linear_map

base = harness.load_adapter("imagenet-sift-lcs-fv-64k.py")

fit = base.fit
flops = base.flops

# GB/s a chip, by jax's device_kind. Google Cloud documentation, "TPU v5e":
# 1,600 Gbit/s of chip-to-chip interconnect a chip (the on-chip-measurement
# guide's table of published peaks quotes it). A kind that is not here has
# no ``collective_roofline``.
ICI_GBPS = {"TPU v5 lite": 200.0}


def make_data(seed: int, sizes: dict) -> dict:
    """The one-chip configuration's class-textured images, a shard's rows
    a call (the whole 1.6 GB and what makes it would be one chip's), with
    the held-out rows from the first call; handed over as host arrays."""
    shards, per = sizes["shards"], sizes["rows_per_shard"]
    if shards * per != sizes["rows"]:
        raise AssertionError(f"{shards} shards of {per} rows are not {sizes['rows']}")
    parts = [
        base.make_data(int(seed) + i * (2**31 - 1),
                       dict(sizes, rows=per, held_out_rows=0 if i else sizes["held_out_rows"]))
        for i in range(shards)
    ]
    return {"seed": int(seed),
            "x": np.concatenate([p["x"] for p in parts]),
            "y": np.concatenate([p["y"] for p in parts]),
            "x_held_out": parts[0]["x_held_out"]}


def answers(fitted, data: dict, sizes: dict) -> dict:
    """The one-chip configuration's answers, and over how many devices the
    fit's rows lay: the devices that hold the solved weights (replicated
    over the mesh that reduced them) and the rows' share of each."""
    out = base.answers(fitted, data, sizes)
    mapper = linear_map(fitted[1])
    shards = len(mapper.W_blocks[0].sharding.device_set)
    out["facts"] = dict(facts(mapper), shards=shards,
                        rows_per_shard=-(-len(data["x"]) // shards))
    return out


def expected_facts(sizes: dict) -> dict:
    return dict(base.expected_facts(sizes), shards=sizes["shards"],
                rows_per_shard=sizes["rows_per_shard"])


# --------------------------------------------------------------- counting


def collective_bytes(sizes: dict) -> float:
    """What one fit's reductions across the mesh are handed, in bytes, from
    the sizes (what the program's ``collective_bytes`` counter sums from its
    shapes): each block's gram once; Aᵀ R a block visit; the classes'
    counts, the weights' sum and the weighted column sums of the features
    and the labels; each branch's descriptor sample; each branch's mass and
    two moments an EM sweep. float32."""
    f = base.expected_facts(sizes)
    d, b, k = f["feature_dim"], f["block_size"], f["classes"]
    nb = -(-d // b)
    sample = min(sizes["descriptor_sample"], sizes["rows"] * base.keypoints(sizes))
    grams = nb * b * b
    atr = sizes["num_iters"] * nb * b * k
    moments = k + 1 + d + k
    samples = sample * (128 + 96)
    em = 2 * sizes["gmm_iters"] * sizes["gmm_k"] * (1 + 2 * sizes["pca_dims"])
    return 4.0 * (grams + atr + moments + samples + em)


def bytes_moved(sizes: dict, work) -> dict:
    """The solver's HBM traffic, as the one-chip configuration counts it,
    and the collectives': the bytes above, and the least seconds the ICI
    could take for them, an all-reduce of S bytes over n chips sending at
    least 2 (n - 1) / n S from every chip at the published rate."""
    import jax

    out = dict(base.bytes_moved(sizes, work), collective=collective_bytes(sizes))
    gbps = ICI_GBPS.get(jax.devices()[0].device_kind)
    n = sizes["shards"]
    if gbps and n > 1:
        out["collective_least_s"] = 2.0 * (n - 1) / n * out["collective"] / (gbps * 1e9)
    return out


# -------------------------------------------------------------- reference


def reference(data: dict, sizes: dict, answers: dict, precision: str = "highest", *,
              fault: dict | None = None, solve: bool = True,
              rows_at_once: int = 256) -> dict:
    """The one-chip configuration's reference up to the mixtures and the
    held-out features, then the train rows' features again a block of rows
    at a time onto the host, and a weighted block solve with one column
    block on the device a visit (a Cholesky solve a visit, no cached
    inverse, block order, ``num_iters`` epochs)."""
    import time

    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import cho_factor, cho_solve

    out = base.reference(data, sizes, answers, precision, fault=fault, solve=False,
                         rows_at_once=rows_at_once)
    if not solve:
        return out
    t0 = time.time()
    describe, encode = base._programs(
        sizes["sift_step"], sizes["sift_bin"], sizes["lcs_step"], sizes["lcs_bin"])[:2]
    theta = [{k: jnp.asarray(v) for k, v in f.items()} for f in answers["fitted"]]
    x, n = data["x"], len(data["x"])
    k, mw = sizes["num_classes"], sizes["mixture_weight"]
    f = base.expected_facts(sizes)
    d, b = f["feature_dim"], f["block_size"]
    blocks = [(s, min(s + b, d)) for s in range(0, d, b)]

    with jax.default_matmul_precision(precision):
        labels = jnp.asarray(data["y"])
        y = 2.0 * jax.nn.one_hot(labels, k, dtype=jnp.float32) - 1.0
        counts = jnp.maximum(jnp.bincount(labels, length=k).astype(jnp.float32), 1.0)
        wts = ((1.0 - mw) + mw * n / (k * counts))[labels]

        cols = [np.empty((n, e - s), np.float32) for s, e in blocks]
        x_sum = jnp.zeros((d,), jnp.float32)
        for s in range(0, n, rows_at_once):
            e = min(s + rows_at_once, n)
            feats = encode(*describe(jnp.asarray(x[s:e])), theta)
            x_sum = x_sum + wts[s:e] @ feats
            feats = np.asarray(feats)
            for col, (cs, ce) in zip(cols, blocks):
                col[s:e] = feats[:, cs:ce]
        x_mean = x_sum / wts.sum()
        y_mean = (wts @ y) / wts.sum()
        for col, (cs, ce) in zip(cols, blocks):
            col -= np.asarray(x_mean[cs:ce])
        t1 = time.time()
        lam = jnp.float32(sizes["lam"])

        @jax.jit
        def factor(a, wts, lam):
            gram = (a * wts[:, None]).T @ a
            return cho_factor(gram + lam * jnp.eye(a.shape[1], dtype=a.dtype))[0]

        @jax.jit
        def visit(a, chol, r, w, wts):
            r_plus = r + a @ w
            w_new = cho_solve((chol, False), (a * wts[:, None]).T @ r_plus)
            return r_plus - a @ w_new, w_new

        r = y - y_mean
        w = [jnp.zeros((e - s, k), jnp.float32) for s, e in blocks]
        chols = [None] * len(blocks)
        for epoch in range(sizes["num_iters"]):
            for i, col in enumerate(cols):
                a = jnp.asarray(col)
                if epoch == 0:
                    chols[i] = factor(a, wts, lam)
                r, w[i] = visit(a, chols[i], r, w[i], wts)
                del a
        held = jnp.asarray(out["features"])
        scores = y_mean + sum(
            (held[:, s:e] - x_mean[s:e]) @ wi for (s, e), wi in zip(blocks, w))
        out["scores"] = np.asarray(scores)
    out["seconds"].update(train_features=t1 - t0, solve=time.time() - t1)
    return out
