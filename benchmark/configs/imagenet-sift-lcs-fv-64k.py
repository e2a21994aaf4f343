"""imagenet-sift-lcs-fv-64k: images -> (SIFT | LCS) -> PCA -> Fisher
vectors -> signed sqrt -> L2 -> gather -> weighted block least squares
(keystone_tpu/pipelines/images/imagenet_sift_lcs_fv.py), and its plain
reference.

The harness loads this file by the name in the configuration's JSON.
``fit`` and ``answers`` are the only functions that touch the program; the
reference imports nothing of it.
"""

from __future__ import annotations

import functools

import numpy as np

from program import facts, linear_map, program_seed, wait_for


def make_data(seed: int, sizes: dict) -> dict:
    """Class-textured images: a grating per class, a random tint, noise,
    clipped to [0, 1]. Train and held-out rows in one jitted call on the
    device, then handed over as host arrays, as a decoder would."""
    import jax
    import jax.numpy as jnp

    side, classes = sizes["image_side"], sizes["num_classes"]
    n, nh = sizes["rows"], sizes["held_out_rows"]

    @jax.jit
    def make(key):
        ky, kt, kn = jax.random.split(key, 3)
        y = jax.random.randint(ky, (n + nh,), 0, classes)
        yy, xx = jnp.mgrid[0:side, 0:side].astype(jnp.float32)
        angle = jnp.pi * y / classes
        freq = 2.0 + (y % 8)
        wave = (xx[None] * jnp.cos(angle)[:, None, None]
                + yy[None] * jnp.sin(angle)[:, None, None])
        base = 0.5 + 0.5 * jnp.sin(2 * jnp.pi * freq[:, None, None] / side * wave)
        tint = 0.5 + 0.5 * jax.random.uniform(kt, (n + nh, 1, 1, 3))
        noise = 0.15 * jax.random.normal(kn, (n + nh, side, side, 3))
        return jnp.clip(base[..., None] * tint + noise, 0.0, 1.0), y

    key = jax.random.PRNGKey(program_seed(seed))
    x, y = make(jax.random.fold_in(key, int(seed) // (2**31 - 1)))
    x, y = np.asarray(x), np.asarray(y).astype(np.int32)
    return {"seed": int(seed), "x": x[:n], "y": y[:n], "x_held_out": x[n:]}


# ---------------------------------------------------------------- program


def _pconf(data: dict, sizes: dict):
    from keystone_tpu.pipelines.images import imagenet_sift_lcs_fv as imagenet

    return imagenet.resolve_scale(imagenet.ImageNetSiftLcsFVConfig(
        sift_step=sizes["sift_step"], sift_bin=sizes["sift_bin"],
        lcs_step=sizes["lcs_step"], lcs_bin=sizes["lcs_bin"],
        pca_dims=sizes["pca_dims"], gmm_k=sizes["gmm_k"],
        gmm_iters=sizes["gmm_iters"],
        descriptor_sample=sizes["descriptor_sample"],
        lam=sizes["lam"], mixture_weight=sizes["mixture_weight"],
        num_iters=sizes["num_iters"], block_size=sizes["block_size"],
        sift_backend=sizes["sift_backend"], fv_backend=sizes["fv_backend"],
        seed=program_seed(data["seed"]),
    ))


def fit(data: dict, sizes: dict):
    """One whole fit through ``imagenet.fit``, the construction the CLI and
    the smoke share; returns when every weight block is on the device and
    one element is on the host."""
    from keystone_tpu.loaders.imagenet import LabeledData
    from keystone_tpu.pipelines.images import imagenet_sift_lcs_fv as imagenet

    featurizer, scored = imagenet.fit(
        _pconf(data, sizes), LabeledData(data["x"], data["y"]),
        sizes["num_classes"],
    )
    wait_for(linear_map(scored))
    return featurizer, scored


def _branches(featurizer):
    """[(PCATransformer, FisherVector)] of the fitted featurizer, in the
    order its gather concatenates them: the SIFT branch, then the LCS."""
    from keystone_tpu.nodes.images.external.fisher_vector import FisherVector
    from keystone_tpu.nodes.learning.pca import PCATransformer
    from keystone_tpu.workflow.operators import GatherOperator, TransformerOperator

    g = featurizer.graph
    gathers = [n for n in g.reachable([featurizer.sink])
               if isinstance(g.operators[n], GatherOperator)]
    if len(gathers) != 1:
        raise AssertionError(f"expected one gather, found {len(gathers)}")
    out = []
    for node in g.dependencies[gathers[0]]:
        chain = []
        while node in g.operators and isinstance(g.operators[node], TransformerOperator):
            t = g.operators[node].transformer
            chain = list(getattr(t, "stages", [t])) + chain
            node = g.dependencies[node][0]
        pca = [s for s in chain if isinstance(s, PCATransformer)]
        fv = [s for s in chain if isinstance(s, FisherVector)]
        if len(pca) != 1 or len(fv) != 1:
            raise AssertionError("a branch without exactly one PCA and one FV stage")
        out.append((pca[0], fv[0]))
    raw = [int(p.mean.shape[0]) for p, _ in out]
    if raw != [128, 96]:
        raise AssertionError(f"branches are not (SIFT, LCS): raw widths {raw}")
    return out


def answers(fitted, data: dict, sizes: dict) -> dict:
    """What the timed fit produced: each branch's fitted PCA and mixture,
    and the gathered Fisher vectors and the class scores of the held-out
    rows."""
    featurizer, scored = fitted
    branches = _branches(featurizer)
    mapper = linear_map(scored)
    # The linear map is applied stage by stage, not through the executor:
    # its jitted chain would carry the 262 MB of weights as a constant and
    # take half a minute to compile in every run.
    features = np.asarray(featurizer(data["x_held_out"]).get())
    return {
        "features": features,
        "scores": np.asarray(mapper.apply_batch(features)),
        "fitted": [
            {"pca_mean": np.asarray(p.mean), "pca_components": np.asarray(p.components),
             "gmm_weights": np.asarray(f.weights), "gmm_means": np.asarray(f.means),
             "gmm_variances": np.asarray(f.variances)}
            for p, f in branches
        ],
        "facts": facts(mapper),
    }


def expected_facts(sizes: dict) -> dict:
    d = 2 * (2 * sizes["gmm_k"] * sizes["pca_dims"])
    b = sizes["block_size"]
    b = sizes["block_size_resolved"] if b == "auto" else b
    return {"feature_dim": d, "block_size": b, "blocks": d // b,
            "classes": sizes["num_classes"]}


# --------------------------------------------------------------- counting


def solver_shape(sizes: dict) -> dict:
    f = expected_facts(sizes)
    return dict(n=sizes["rows"], d=f["feature_dim"], k=f["classes"],
                block=f["block_size"], iters=sizes["num_iters"])


def keypoints(sizes: dict) -> int:
    per_axis = (sizes["image_side"] - 4 * sizes["sift_bin"]) // sizes["sift_step"] + 1
    return per_axis * per_axis


def flops(sizes: dict, work) -> dict:
    """Canonical FLOPs of one fit. Featurizer: the matmul work of both
    branches, i.e. the PCA projection of every train descriptor, the EM
    sweeps over the descriptor sample (four n x k x d products a sweep) and
    the Fisher-vector encoding (four m x k x d products an image); SIFT's
    convolutions, LCS's box sums and the SVDs are not counted."""
    n, m = sizes["rows"], keypoints(sizes)
    k, d = sizes["gmm_k"], sizes["pca_dims"]
    raw = {"sift": 128, "lcs": 96}
    project = sum(2.0 * n * m * r * d for r in raw.values())
    em = 2 * sizes["gmm_iters"] * 4 * 2.0 * sizes["descriptor_sample"] * k * d
    encode = 2 * 4 * 2.0 * n * m * k * d
    return {"featurize": project + em + encode,
            "solver": work.bcd_flops(**solver_shape(sizes))}


def bytes_moved(sizes: dict, work) -> dict:
    return {"solver": work.bcd_bytes(**solver_shape(sizes))}


# -------------------------------------------------------------- reference
#
# The reference starts again from the images: its own descriptors, its own
# sample of them, its own PCA, its own k-means++ and EM, its own solve.
# One thing cannot be compared value by value. k-means++ draws its seeds
# through a float32 cumulative sum over 200,000 descriptors, a last-digit
# difference moves a draw, and two correct fits reach different mixtures
# whose Fisher vectors differ by order 1. So the fit's PCA and mixture are
# held to the reference's own as what they are for, a subspace and a
# density, and are then handed to the reference as a served model's
# reference is handed the served tokens:
#   <branch>_pca_residual  the share of the descriptors' variance that the
#                 branch's fitted PCA leaves outside its subspace, against
#                 the reference's own PCA, both read on the reference's
#                 sample;
#   <branch>_density  the held-out descriptors' mean log-likelihood a
#                 dimension under the branch's fitted PCA and mixture, as
#                 a density, against the reference's own (its own
#                 k-means++, Lloyd and EM sweeps);
#   <branch>_em_step  how far one more EM sweep, on a second sample of the
#                 reference's, still raises that sample's density a dimension
#                 under the branch's fitted PCA and mixture, against the same
#                 reading of the reference's own mixture. Neither was fitted
#                 on that sample, so both move by what a new sample moves a
#                 converged mixture, and a mixture whose sweeps were cut short
#                 moves further;
#   features      the held-out rows' gathered Fisher vectors, against the
#                 published formulas on the reference's own descriptors,
#                 given the fitted PCA and mixture;
#   scores        the fitted model's scores of those rows, against a plain
#                 weighted block solve on the reference's own features of
#                 the train rows (its descriptors, the fitted tables, its
#                 Fisher encoding).

_LUMA = (0.299, 0.587, 0.114)


def _ref_sift(gray, step: int, bin_size: int):
    """Dense SIFT, written from its definition: per pixel, gradient
    magnitude spread linearly over the two nearest of 8 orientations; per
    keypoint and 4x4 spatial cell, the sum over the 16x16 support of that
    times a centred Gaussian times the bilinear cell weights; then L2,
    clamp at 0.2, L2 again."""
    import jax.numpy as jnp

    n, h, w = gray.shape
    span = 4 * bin_size
    px = jnp.pad(gray, ((0, 0), (0, 0), (1, 1)), mode="edge")
    py = jnp.pad(gray, ((0, 0), (1, 1), (0, 0)), mode="edge")
    gx = 0.5 * (px[:, :, 2:] - px[:, :, :-2])
    gy = 0.5 * (py[:, 2:, :] - py[:, :-2, :])
    mag = jnp.sqrt(gx * gx + gy * gy)
    theta = jnp.arctan2(gy, gx)
    theta = jnp.where(theta < 0, theta + 2 * jnp.pi, theta)
    fbin = theta * (8 / (2 * jnp.pi))
    dist = jnp.abs(fbin[..., None] - jnp.arange(8, dtype=jnp.float32))
    ori = mag[..., None] * jnp.maximum(0.0, 1.0 - jnp.minimum(dist, 8 - dist))

    ny, nx = (h - span) // step + 1, (w - span) // step + 1
    off = jnp.arange(span)
    centre, sigma = 0.5 * (span - 1), 0.5 * span
    gauss = jnp.exp(-((off - centre) ** 2) / (2 * sigma * sigma))
    pos = (off + 0.5) / bin_size - 0.5
    cell = jnp.maximum(0.0, 1.0 - jnp.abs(pos[:, None] - jnp.arange(4)[None]))
    wy = (gauss[:, None] * cell).astype(jnp.float32)  # (span, 4)
    # The weights are a product of one in y and one in x: rows first
    # (every keypoint row's 16-pixel band against the 4 cell rows), then
    # columns the same way.
    bands = jnp.stack([ori[:, a * step:a * step + span] for a in range(ny)], axis=1)
    rows = jnp.einsum("nayxo,yc->nacxo", bands, wy)  # (n, ny, 4, w, 8)
    bands = jnp.stack([rows[:, :, :, b * step:b * step + span] for b in range(nx)], axis=2)
    desc = jnp.einsum("nabcxo,xd->nabcdo", bands, wy)  # (n, ny, nx, 4, 4, 8)
    desc = desc.reshape(n, ny * nx, 128)
    norm = jnp.linalg.norm(desc, axis=-1, keepdims=True)
    desc = jnp.minimum(desc / jnp.maximum(norm, 1e-12), 0.2)
    norm2 = jnp.linalg.norm(desc, axis=-1, keepdims=True)
    return jnp.where(norm > 1e-12, desc / jnp.maximum(norm2, 1e-12), 0.0)


def _ref_lcs(x, step: int, bin_size: int, eps: float = 1e-8):
    """Local colour statistics: per keypoint, per 4x4 cell of bin x bin
    pixels, each channel's mean and standard deviation."""
    import jax.numpy as jnp

    n, h, w, c = x.shape
    span = 4 * bin_size
    ny, nx = (h - span) // step + 1, (w - span) // step + 1

    def cell_means(v):
        """(n, ny, nx, 4, 4, c): the mean of ``v`` over each cell."""
        bands = jnp.stack([v[:, a * step:a * step + span] for a in range(ny)], axis=1)
        rows = bands.reshape(n, ny, 4, bin_size, w, c).mean(axis=3)
        bands = jnp.stack([rows[:, :, :, b * step:b * step + span] for b in range(nx)], axis=2)
        return bands.reshape(n, ny, nx, 4, 4, bin_size, c).mean(axis=5)

    mean = cell_means(x)
    var = jnp.maximum(cell_means(x * x) - mean * mean, 0.0)
    stats = jnp.concatenate([mean, jnp.sqrt(var + eps)], axis=-1)
    return stats.reshape(n, ny * nx, 16 * 2 * c)


def _ref_fisher(desc, fitted: dict):
    """(n, m, raw) descriptors -> (n, 2kd) improved Fisher vectors with the
    signed square root and the L2 norm applied. Responsibilities and both
    gradient blocks are written as the matrix products of the published
    encoding, so that the control, one precision step down, lowers the
    products a later PR would be tempted to lower."""
    import jax
    import jax.numpy as jnp

    x = (desc - fitted["pca_mean"]) @ fitted["pca_components"]  # (n, m, d)
    w = jnp.maximum(fitted["gmm_weights"], 1e-12)
    mu, var = fitted["gmm_means"], fitted["gmm_variances"]
    n, m, d = x.shape
    inv = 1.0 / var
    dist = (jnp.einsum("nmd,kd->nmk", x * x, inv)
            - 2.0 * jnp.einsum("nmd,kd->nmk", x, mu * inv)
            + (mu * mu * inv).sum(axis=1))
    log_norm = -0.5 * (d * jnp.log(2 * jnp.pi) + jnp.log(var).sum(axis=1))
    r = jax.nn.softmax(jnp.log(w) + log_norm - 0.5 * dist, axis=-1)  # (n, m, k)
    s0 = r.sum(axis=1)[..., None]  # (n, k, 1)
    s1 = jnp.einsum("nmk,nmd->nkd", r, x)
    s2 = jnp.einsum("nmk,nmd->nkd", r, x * x)
    gmu = (s1 - s0 * mu) / jnp.sqrt(var) / (m * jnp.sqrt(w))[:, None]
    gvar = ((s2 - 2.0 * mu * s1 + s0 * mu * mu) * inv - s0) / (m * jnp.sqrt(2 * w))[:, None]
    fv = jnp.concatenate([gmu.reshape(n, -1), gvar.reshape(n, -1)], axis=-1)
    fv = jnp.sign(fv) * jnp.sqrt(jnp.abs(fv))
    return fv / jnp.maximum(jnp.linalg.norm(fv, axis=-1, keepdims=True), 1e-12)


def _ref_describe(x, sizes: dict):
    """Images -> (dense SIFT of the luma, local colour statistics)."""
    import jax.numpy as jnp

    gray = jnp.tensordot(x, jnp.asarray(_LUMA, x.dtype), axes=[[-1], [0]])
    return (_ref_sift(gray, sizes["sift_step"], sizes["sift_bin"]),
            _ref_lcs(x, sizes["lcs_step"], sizes["lcs_bin"]))


def _ref_pca(sample, dims: int) -> dict:
    """Top ``dims`` principal directions of ``sample`` (rows), from the
    eigenvectors of its covariance; the covariance itself is kept, since
    every residual below is read from it."""
    import jax.numpy as jnp

    mean = sample.mean(axis=0)
    xc = sample - mean
    cov = np.asarray(xc.T @ xc, np.float64) / len(sample)
    _values, vectors = np.linalg.eigh(cov)
    return {"mean": np.asarray(mean, np.float64), "cov": cov,
            "components": vectors[:, ::-1][:, :dims]}


def _pca_residual(mean, components, own: dict) -> float:
    """The share of the own sample's variance that the projection onto
    ``components`` about ``mean`` leaves out: E|(I - CC')(x - mean)|^2 over
    E|x - own mean|^2, from the own sample's mean and covariance."""
    mean, c = np.asarray(mean, np.float64), np.asarray(components, np.float64)
    shift = own["mean_of_sample"] - mean
    second = own["cov_of_sample"] + np.outer(shift, shift)
    out = np.eye(len(mean)) - c @ c.T
    return float(np.trace(out @ second @ out.T) / np.trace(own["cov_of_sample"]))


def _ref_gmm(x, key, k: int, lloyd: int, sweeps: int, min_var: float = 1e-4):
    """A diagonal mixture of ``k`` Gaussians by EM: k-means++ seeds, a few
    Lloyd sweeps, the hard assignment's moments, then ``sweeps`` EM sweeps."""
    import jax
    import jax.numpy as jnp

    n, d = x.shape

    def sq_dists(centres):
        return jnp.maximum((x * x).sum(axis=1, keepdims=True) - 2.0 * x @ centres.T
                           + (centres * centres).sum(axis=1), 0.0)

    def seed(i, carry):
        centres, d2, key = carry
        key, sub = jax.random.split(key)
        pick = x[jax.random.choice(sub, n, p=d2 / jnp.maximum(d2.sum(), 1e-12))]
        return centres.at[i].set(pick), jnp.minimum(d2, ((x - pick) ** 2).sum(axis=1)), key

    key, sub = jax.random.split(key)
    first = x[jax.random.randint(sub, (), 0, n)]
    centres, _, _ = jax.lax.fori_loop(
        1, k, seed, (jnp.zeros((k, d), x.dtype).at[0].set(first),
                     ((x - first) ** 2).sum(axis=1), key))

    def hard(centres):
        return jax.nn.one_hot(jnp.argmin(sq_dists(centres), axis=1), k, dtype=x.dtype)

    def sweep_lloyd(_i, centres):
        member = hard(centres)
        counts = member.sum(axis=0)
        moved = (member.T @ x) / jnp.maximum(counts, 1.0)[:, None]
        return jnp.where((counts > 0)[:, None], moved, centres)

    member = hard(jax.lax.fori_loop(0, lloyd, sweep_lloyd, centres))
    counts = jnp.maximum(member.sum(axis=0), 1.0)
    means = (member.T @ x) / counts[:, None]
    start = (counts / n, means,
             jnp.maximum((member.T @ (x * x)) / counts[:, None] - means**2, min_var))

    return jax.lax.fori_loop(0, sweeps, lambda _i, theta: _em_sweep(x, theta, min_var), start)


def _em_sweep(x, theta, min_var: float = 1e-4):
    """One EM sweep of a diagonal mixture ``theta`` = (weights, means,
    variances) over the rows of ``x``."""
    import jax
    import jax.numpy as jnp

    resp = jax.nn.softmax(_component_logs(x, *theta), axis=-1)
    mass = jnp.maximum(resp.sum(axis=0), 1e-6)
    means = (resp.T @ x) / mass[:, None]
    second = (resp.T @ (x * x)) / mass[:, None]
    return mass / len(x), means, jnp.maximum(second - means**2, min_var)


def _em_gain(x, weights, means, variances):
    """By how much one more EM sweep over ``x`` raises its mean
    log-likelihood: nought at a fixed point, never below it."""
    import jax

    def mean_log(theta):
        return jax.nn.logsumexp(_component_logs(x, *theta), axis=-1).mean()

    theta = (weights, means, variances)
    return mean_log(_em_sweep(x, theta)) - mean_log(theta)


def _component_logs(x, weights, means, variances):
    """(n, k): log w_j + log N(x | mean_j, diag var_j)."""
    import jax.numpy as jnp

    inv = 1.0 / variances
    quad = (x * x) @ inv.T - 2.0 * x @ (means * inv).T + (means * means * inv).sum(axis=1)
    log_norm = -0.5 * (x.shape[1] * jnp.log(2 * jnp.pi) + jnp.log(variances).sum(axis=1))
    return jnp.log(jnp.maximum(weights, 1e-37)) + log_norm - 0.5 * quad


def _density(desc, mean, components, weights, means, variances) -> float:
    """How well a PCA and a mixture model raw descriptors: the geometric
    mean, over descriptors and dimensions, of the density of the projected
    descriptors, exp(mean log-likelihood / dimensions). A share more or
    less of it means the same in either branch, whatever its scale."""
    import jax
    import jax.numpy as jnp

    desc = desc.reshape(-1, desc.shape[-1])
    x = (desc - jnp.asarray(mean, jnp.float32)) @ jnp.asarray(components, jnp.float32)
    logs = _component_logs(x, jnp.asarray(weights), jnp.asarray(means), jnp.asarray(variances))
    return float(np.exp(float(jax.nn.logsumexp(logs, axis=-1).mean()) / x.shape[1]))


@functools.lru_cache(maxsize=None)
def _programs(sift_step: int, sift_bin: int, lcs_step: int, lcs_bin: int):
    """The reference's jitted programs, traced once a process. What a fit
    produced enters them as arguments, never as constants: the compiled
    reference is then the same for every seed and comes from the compile
    cache in every run after a checkout's first."""
    import jax
    import jax.numpy as jnp

    sizes = {"sift_step": sift_step, "sift_bin": sift_bin,
             "lcs_step": lcs_step, "lcs_bin": lcs_bin}
    describe = jax.jit(lambda x: _ref_describe(x, sizes))
    encode = jax.jit(lambda sift, lcs, theta: jnp.concatenate(
        [_ref_fisher(sift, theta[0]), _ref_fisher(lcs, theta[1])], axis=-1))
    rows_of = jax.jit(lambda desc, order: desc.reshape(-1, desc.shape[-1])[order])
    return (describe, encode, rows_of, jax.jit(_ref_gmm, static_argnums=(2, 3, 4)),
            jax.jit(_em_gain))


def reference(data: dict, sizes: dict, answers: dict, precision: str = "highest", *,
              fault: dict | None = None, solve: bool = True,
              rows_at_once: int = 256) -> dict:
    """Plain float32 ``jax.numpy`` from the images on, a block of rows at a
    time so that the descriptor patches fit. ``precision`` below ``highest``
    is the control, never the reference. ``fault`` (for tools/control.py)
    breaks the reference's own fit, to be read in the program's place:
    {"em_sweeps": n}, {"sample_share": s};
    ``solve=False`` stops before the features and the solve.

    Besides its own numbers it returns ``measured``: what the tables that
    the fit produced (``answers["fitted"]``) read on the reference's own
    descriptors, which no one else has."""
    import time

    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import cho_factor, cho_solve

    fault = dict(fault or {})
    t0 = time.time()
    theta = [{k: jnp.asarray(v) for k, v in f.items()} for f in answers["fitted"]]
    describe, encode, rows_of, fit_mixture, em_gain = _programs(
        sizes["sift_step"], sizes["sift_bin"], sizes["lcs_step"], sizes["lcs_bin"])

    with jax.default_matmul_precision(precision):
        # Its own draws, by its own generator: a stratified sample of the
        # train rows' descriptors, the same share of every block of rows;
        # and a second one that shares with the first what two independent
        # draws would, as the sample the program drew does.
        x, n = data["x"], len(data["x"])
        per_image = keypoints(sizes)
        want = min(sizes["descriptor_sample"], n * per_image)
        key = jax.random.PRNGKey(int(data["seed"]) % (2**31 - 1))
        samples, seconds, feats = ([], []), ([], []), []
        for s in range(0, n, rows_at_once):
            e = min(s + rows_at_once, n)
            sift, lcs = describe(jnp.asarray(x[s:e]))
            take = (want * e) // n - (want * s) // n
            fresh = take - (take * take) // ((e - s) * per_image)
            for branch, desc in enumerate((sift, lcs)):
                order = jax.random.permutation(
                    jax.random.fold_in(key, 2 * s + branch), (e - s) * per_image)
                samples[branch].append(rows_of(desc, order[:take]))
                seconds[branch].append(rows_of(desc, order[fresh:fresh + take]))
            if solve:
                feats.append(encode(sift, lcs, theta))
        held_desc = describe(jnp.asarray(data["x_held_out"]))
        held = encode(*held_desc, theta)
        t1 = time.time()

        out, measured = {"features": np.asarray(held)}, {}
        dims = sizes["pca_dims"]

        def project(rows, mean, components):
            return (rows - jnp.asarray(mean, jnp.float32)) @ jnp.asarray(components, jnp.float32)

        def em_step(rows, mixture) -> float:
            return float(np.exp(float(em_gain(rows, *mixture)) / dims))

        for branch, (name, fitted) in enumerate(zip(("sift", "lcs"), answers["fitted"])):
            sample = jnp.concatenate(samples[branch], axis=0)
            sound = _ref_pca(sample, dims)
            own = {"mean_of_sample": sound["mean"], "cov_of_sample": sound["cov"]}
            fit_on = max(int(fault.get("sample_share", 1.0) * len(sample)), sizes["gmm_k"])
            pca = sound if fit_on == len(sample) else _ref_pca(sample[:fit_on], dims)
            projected = project(sample, pca["mean"], pca["components"])
            mixture = fit_mixture(
                projected[:fit_on], jax.random.fold_in(key, 1_000_003 + branch),
                sizes["gmm_k"], 5, fault.get("em_sweeps", sizes["gmm_iters"]))
            theirs = tuple(jnp.asarray(fitted[k]) for k in
                           ("gmm_weights", "gmm_means", "gmm_variances"))
            out[name + "_pca_residual"] = _pca_residual(pca["mean"], pca["components"], own)
            out[name + "_density"] = _density(
                held_desc[branch], pca["mean"], pca["components"], *mixture)
            measured[name + "_pca_residual"] = _pca_residual(
                fitted["pca_mean"], fitted["pca_components"], own)
            measured[name + "_density"] = _density(
                held_desc[branch], fitted["pca_mean"], fitted["pca_components"], *theirs)
            # Each mixture on the sample it was not fitted on.
            second = jnp.concatenate(seconds[branch], axis=0)
            out[name + "_em_step"] = em_step(
                project(second, pca["mean"], pca["components"]), mixture)
            measured[name + "_em_step"] = em_step(
                project(second, fitted["pca_mean"], fitted["pca_components"]), theirs)
            del sample, projected, second
        del samples, seconds
        t2 = time.time()
        out["measured"] = measured
        out["seconds"] = {"describe_and_encode": t1 - t0, "pca_and_mixture": t2 - t1}
        if not solve:
            return out

        feats = jnp.concatenate(feats, axis=0)
        d = feats.shape[1]
        k, mw = sizes["num_classes"], sizes["mixture_weight"]
        labels = jnp.asarray(data["y"])
        y = 2.0 * jax.nn.one_hot(labels, k, dtype=jnp.float32) - 1.0
        counts = jnp.maximum(jnp.bincount(labels, length=k).astype(jnp.float32), 1.0)
        wts = ((1.0 - mw) + mw * n / (k * counts))[labels]
        x_mean = (wts @ feats) / wts.sum()
        y_mean = (wts @ y) / wts.sum()
        b = expected_facts(sizes)["block_size"]
        blocks = [(s, min(s + b, d)) for s in range(0, d, b)]
        a_blocks = [feats[:, s:e] - x_mean[s:e] for s, e in blocks]
        del feats
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

        chols = [factor(a, wts, lam) for a in a_blocks]
        r = y - y_mean
        w = [jnp.zeros((e - s, k), jnp.float32) for s, e in blocks]
        for _ in range(sizes["num_iters"]):
            for i, a in enumerate(a_blocks):
                r, w[i] = visit(a, chols[i], r, w[i], wts)
        scores = y_mean + sum(
            (held[:, s:e] - x_mean[s:e]) @ wi for (s, e), wi in zip(blocks, w))
        out["scores"] = np.asarray(scores)
        out["seconds"]["solve"] = time.time() - t2
        return out
