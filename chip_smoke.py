"""The quickest proof that the system still starts on the chip.

One process fits ImageNetSiftLcsFV at the widths the repo documents as its
full-scale configuration (PCA 64, GMM k=256 → 65,536-d features, 1000
classes, 3 epochs, block "auto") on synthetic images, serves the fitted
pipeline (image in, top-5 out) from a ``ServingDaemon`` over HTTP and the
framed socket, compiles the two Pallas kernels (Fisher vectors; convolution,
rectifier and pooling) with Mosaic, and compares features and class scores
with the same ``jnp`` code on the CPU device. Every phase goes through the
entry points a user calls; the widths are fixed, depth (rows, image side) is
cut. Any phase that fails raises.

    python chip_smoke.py

Exits non-zero, printing no result, unless JAX's default backend is a TPU.
The last two lines of standard output are one JSON object each: the report
(widths and block size run, counters, peak HBM, compile cache), then the
verdict, which holds exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The report's ``setup_seconds`` are wall clock with compilation included:
set-up cost, not a metric.
"""

from __future__ import annotations

import json
import os
import pickle
import shutil
import sys
import tempfile
import time
from dataclasses import asdict, dataclass

import numpy as np

REPO_DIR = os.path.dirname(os.path.abspath(__file__))


@dataclass(frozen=True)
class SmokeConfig:
    """Widths and depth of one smoke run. The defaults are the published
    widths; tier-1 drives the same phases with small ones."""

    pca_dims: int = 64
    gmm_k: int = 256
    classes: int = 1000
    epochs: int = 3
    # Depth. 4096 synthetic 64×64 images: every class has rows, the
    # descriptor pool (4096·169) exceeds the 200k descriptor sample, and the
    # feature matrix is 4096 × 65,536 × 4 B = 1 GiB.
    images: int = 4096
    # The second descriptor count the Pallas kernel compiles at (the first
    # is what the pipeline's SIFT grid produces: 169 for 64×64, so
    # tile_m = 169, not a multiple of 8).
    kernel_m: int = 2048
    # The fused convolution kernel's check: CIFAR's 32 x 32 x 3 images, the
    # random-patch pipeline's 6 x 6 filters at its published count and its
    # 14 / 13 pooling, on a few rows (the kernel's grid walks rows).
    conv_filters: int = 10000
    conv_rows: int = 37
    # Rows per request, sent once over each wire.
    request_rows: tuple = (1, 3, 37)
    # Chip-vs-CPU comparison: images, and the largest |chip − cpu| allowed
    # as a share of the largest |cpu| value, for features and for class
    # scores. float32 has 24 significand bits (6e-8); the chain's log-
    # likelihoods are sums of order 1e2..1e3 under an exp, and the head sums
    # 65,536 products, so 1e-3 is what a float32 chain can hold and a
    # one-pass bfloat16 matmul (8 bits, 4e-3 per product) cannot.
    reference_rows: int = 8
    tolerance: float = 1e-3


def _rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape:
        raise AssertionError(f"shape {got.shape} != reference {want.shape}")
    if not np.isfinite(got).all():
        raise AssertionError("non-finite values")
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _span(array) -> int:
    return len(array.sharding.device_set)


def _stage(pipeline, kind):
    """The first fitted stage of type ``kind``, fused chains opened."""
    for t in pipeline.transformers():
        for stage in getattr(t, "stages", [t]):
            if isinstance(stage, kind):
                return stage
    raise AssertionError(f"no {kind.__name__} in the fitted pipeline")


def phase_fit(conf: SmokeConfig) -> dict:
    """Fit through the pipeline module ``bin/run-pipeline.sh
    ImageNetSiftLcsFV`` execs; returns the fitted pieces and what ran."""
    import jax

    from keystone_tpu.nodes.learning.block_least_squares import (
        BlockLinearMapper,
    )
    from keystone_tpu.pipelines.images import imagenet_sift_lcs_fv as imagenet

    pconf = imagenet.resolve_scale(imagenet.ImageNetSiftLcsFVConfig(
        pca_dims=conf.pca_dims,
        gmm_k=conf.gmm_k,
        num_iters=conf.epochs,
        block_size="auto",
        sift_backend="xla",
        fv_backend="tpu",
        synthetic_n=conf.images,
        synthetic_classes=conf.classes,
    ))
    train, test, num_classes = imagenet.load_data(pconf)
    featurizer, scored = imagenet.fit(pconf, train, num_classes)
    pipeline = imagenet.top_k_pipeline(pconf, scored)

    mapper = _stage(scored, BlockLinearMapper)
    feature_dim = 2 * (2 * conf.gmm_k * conf.pca_dims)
    if mapper.blocks[-1][1] != feature_dim:
        raise AssertionError(
            f"solver saw {mapper.blocks[-1][1]} features, not {feature_dim}"
        )
    for w in mapper.W_blocks:
        if w.shape[1] != conf.classes or not bool(np.isfinite(w).all()):
            raise AssertionError("fitted weights: wrong width or non-finite")
    devices = len(jax.devices())
    if _span(mapper.W_blocks[0]) != devices:
        raise AssertionError(
            f"solved weights live on {_span(mapper.W_blocks[0])} of "
            f"{devices} devices: the solve did not span the mesh"
        )
    # The in-process answer the served responses must equal. The whole
    # held-out set in one call: its rows divide the mesh, so the executor
    # places it row-sharded like a training batch.
    applied = pipeline.apply(test.data).get()
    if devices > 1 and _span(applied) != devices:
        raise AssertionError(
            f"pipeline.apply output spans {_span(applied)} of {devices} "
            "devices"
        )
    top_k = np.asarray(applied)
    if top_k.shape != (len(test.data), pconf.top_k) or not (
        (top_k >= 0) & (top_k < conf.classes)
    ).all():
        raise AssertionError(f"top-{pconf.top_k} output malformed: {top_k.shape}")
    return {
        "pconf": pconf,
        "featurizer": featurizer,
        "scored": scored,
        "pipeline": pipeline,
        "images": test.data,
        "top_k": top_k,
        "widths": {
            "pca_dims": conf.pca_dims,
            "gmm_k": conf.gmm_k,
            "feature_dim": feature_dim,
            "classes": conf.classes,
            "epochs": conf.epochs,
            "images": conf.images,
            "image_side": int(test.data.shape[1]),
            "block_size": mapper.blocks[0][1] - mapper.blocks[0][0],
        },
        "operand_devices": devices,
    }


def phase_serve(conf: SmokeConfig, fitted: dict, compiles) -> dict:
    """save_artifact → ServingDaemon on ephemeral ports (default ladder,
    every local device a replica) → requests over both wires, each answer
    equal to the in-process ``pipeline.apply`` on the same rows."""
    sys.path.insert(0, os.path.join(REPO_DIR, "tools"))
    from serve_daemon import SocketClient, http_get, http_post

    from keystone_tpu.workflow.daemon import ServingDaemon
    from keystone_tpu.workflow.serialization import save_artifact

    images, want = fitted["images"], fitted["top_k"]
    # ~300 MB at published width: outside the checkout, removed below.
    art_dir = tempfile.mkdtemp(prefix="keystone_chip_smoke_")
    daemon = None
    try:
        path = os.path.join(art_dir, "imagenet.kart")
        save_artifact(
            fitted["pipeline"], path,
            feature_shape=images.shape[1:], dtype=str(images.dtype),
        )
        daemon = ServingDaemon(
            artifact=path, http_port=0, socket_port=0, name="chip-smoke"
        )
        port = daemon.http_port

        def stats():
            status, body = http_get(port, "/stats")
            if status != 200:
                raise AssertionError(f"/stats answered {status}")
            return json.loads(body)

        warm = stats()["service"]["compiled"]
        replicas = len(warm["devices"])
        compiles_warm = compiles.count

        # Every size once per wire; sequential requests rotate through the
        # pool, so a pool wider than that gets single rows until each
        # replica has had one.
        sizes = list(conf.request_rows) * 2
        sizes += [1] * max(0, replicas - len(sizes))
        sock = SocketClient(daemon.socket_port)
        try:
            start = 0
            for i, rows in enumerate(sizes):
                x = images[start:start + rows].tolist()
                if i % 2 == 0:
                    status, doc = http_post(port, "/predict", {"x": x})
                else:
                    doc = sock.request({"x": x})
                    status = doc["status"]
                if status != 200:
                    raise AssertionError(f"request {i} ({rows} rows): {doc}")
                if not np.array_equal(
                    np.asarray(doc["y"]), want[start:start + rows]
                ):
                    raise AssertionError(
                        f"request {i} ({rows} rows, "
                        f"{'http' if i % 2 == 0 else 'socket'}): response "
                        "differs from in-process pipeline.apply"
                    )
                start += rows
        finally:
            sock.close()

        status, body = http_get(port, "/healthz")
        if status != 200:
            raise AssertionError(f"/healthz answered {status}: {body!r}")
        after = stats()
        compiled = after["service"]["compiled"]
        post_warmup = (
            compiles.count - compiles_warm,
            compiled["compile_count"] - warm["compile_count"],
        )
        if any(post_warmup):
            raise AssertionError(
                f"compiles after warmup (jax, engine): {post_warmup}"
            )
        idle = [d for d, n in compiled["replica_dispatches"].items() if not n]
        if replicas > 1 and idle:
            raise AssertionError(f"replicas that served nothing: {idle}")
        return {
            "ladder": compiled["ladder"],
            "replicas": replicas,
            "warmup_compiles": compiled["compile_count"],
            "post_warmup_compiles": sum(post_warmup),
            "replica_dispatches": compiled["replica_dispatches"],
            "requests": after["service"]["requests"],
            "warmup_seconds": round(compiled["warmup_seconds"], 1),
        }
    finally:
        if daemon is not None:
            daemon.close()
        shutil.rmtree(art_dir, ignore_errors=True)


def _conv_kernel_error(conf: SmokeConfig, rng) -> float:
    """The convolver's kernel (product, rectifier and pooling in one,
    ``ops/conv_pool_pallas.py``) against its stage walk, the three nodes
    applied one by one, at the random-patch pipeline's shapes."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.nodes.images import (
        Convolver,
        Pooler,
        SymmetricRectifier,
    )

    filters = rng.standard_normal((conf.conv_filters, 6, 6, 3))
    filters /= np.linalg.norm(filters.reshape(len(filters), -1), axis=1)[
        :, None, None, None]
    conv = Convolver(filters.astype(np.float32), normalize_patches=10.0)
    conv.bias = jnp.asarray(
        0.1 * rng.standard_normal(conf.conv_filters).astype(np.float32))
    taken = [SymmetricRectifier(alpha=0.25), Pooler(13, 14, mode="sum")]
    X = jnp.asarray(rng.integers(
        0, 256, size=(conf.conv_rows, 32, 32, 3)).astype(np.float32))
    if conv.takes(taken) != len(taken):
        raise AssertionError("the convolver did not take its two stages")

    @jax.jit
    def walk(X):
        for stage in [conv] + taken:
            X = stage.apply_batch(X)
        return X

    return _rel_err(conv.apply_with(taken, X), walk(X))


def phase_kernel(conf: SmokeConfig, fitted: dict) -> dict:
    """The Pallas Fisher-vector kernel against the XLA einsum path, at the
    descriptor count the pipeline produces and at ``kernel_m``, then once
    through ``apply_sharded`` so its ``shard_map`` branch runs; then the
    convolver's kernel against its stage walk."""
    import functools

    import jax
    import jax.numpy as jnp

    from keystone_tpu.nodes.images.external.fisher_vector import (
        FisherVector,
        _fv_tpu,
    )
    from keystone_tpu.ops import fisher_vectors_pallas
    from keystone_tpu.ops.sift_xla import dense_sift_xla
    from keystone_tpu.utils.mesh import SpecLayout

    pconf, side = fitted["pconf"], fitted["widths"]["image_side"]
    fv = _stage(fitted["scored"], FisherVector)
    w, mu, var = fv.weights, fv.means, fv.variances
    m_pipeline = jax.eval_shape(
        functools.partial(
            dense_sift_xla, step=pconf.sift_step, bin_size=pconf.sift_bin
        ),
        jax.ShapeDtypeStruct((1, side, side), jnp.float32),
    ).shape[1]
    layout = SpecLayout.for_mesh()
    batch = 2 * layout.num_shards
    rng = np.random.default_rng(0)

    p = w.astype(np.float64) / w.sum(dtype=np.float64)

    def descriptors(m):
        """Draws from the fitted mixture itself: in-distribution input."""
        z = rng.choice(len(w), size=(batch, m), p=p)
        eps = rng.standard_normal((batch, m, mu.shape[1]))
        return (mu[z] + np.sqrt(var[z]) * eps).astype(np.float32)

    errors = {}
    for m in (m_pipeline, conf.kernel_m):
        X = descriptors(m)
        errors[f"m={m}"] = _rel_err(
            fisher_vectors_pallas(X, w, mu, var), _fv_tpu(X, w, mu, var)
        )
    X = descriptors(m_pipeline)
    node = FisherVector(w, mu, var, backend="pallas")
    sharded = layout.jit(lambda x: node.apply_sharded(x, layout))(
        layout.put(X)
    )
    if _span(sharded) != layout.num_shards:
        raise AssertionError("apply_sharded output does not span the mesh")
    errors["apply_sharded"] = _rel_err(sharded, _fv_tpu(X, w, mu, var))
    errors["conv_rectify_pool"] = _conv_kernel_error(conf, rng)
    bad = {k: v for k, v in errors.items() if not v <= conf.tolerance}
    if bad:
        raise AssertionError(
            f"Pallas kernel vs XLA path beyond {conf.tolerance}: {bad}"
        )
    return {"descriptors": m_pipeline, "rel_err_vs_xla": errors}


def phase_numerics(conf: SmokeConfig, fitted: dict) -> dict:
    """Features and class scores for a few images: this backend against
    the same ``jnp`` code on the CPU device, in this process."""
    import jax

    from keystone_tpu.workflow import fitted_forward

    X = np.asarray(fitted["images"][:conf.reference_rows])
    stages = {"features": fitted["featurizer"], "scores": fitted["scored"]}
    got = {
        name: np.asarray(jax.jit(fitted_forward(p, X))(X))
        for name, p in stages.items()
    }
    # The fitted parameters move through a pickle, as save_artifact moves
    # them, and land on the CPU device; the replay is the same function.
    with jax.default_device(jax.devices("cpu")[0]):
        reference = pickle.loads(pickle.dumps(stages))
        want = {
            name: np.asarray(fitted_forward(p, X)(X))
            for name, p in reference.items()
        }
    errors = {name: _rel_err(got[name], want[name]) for name in stages}
    bad = {k: v for k, v in errors.items() if not v <= conf.tolerance}
    if bad:
        raise AssertionError(
            f"chip vs float32 CPU reference beyond {conf.tolerance}: {bad} "
            f"(all: {errors})"
        )
    return {"tolerance": conf.tolerance, "rel_err_vs_cpu": errors}


def smoke(conf: SmokeConfig) -> dict:
    """All four phases on whatever ``jax.devices()`` offers; returns the
    report. Raises on the first failure: nothing is caught into a field."""
    from keystone_tpu.utils.metrics import (
        CompileEventCounter,
        reliability_counters,
        sharding_counters,
    )

    compiles = CompileEventCounter()
    before = {
        "sharding": sharding_counters.snapshot(),
        "reliability": reliability_counters.snapshot(),
    }
    seconds = {}

    def timed(name, fn, *args):
        t0 = time.perf_counter()
        out = fn(*args)
        seconds[name] = round(time.perf_counter() - t0, 1)
        print(f"chip_smoke: {name} ok, {seconds[name]} s of set-up",
              flush=True)
        return out

    # Serving last: its warmup compiles rungs x replicas one after another
    # and is most of a cold run, so the quick phases fail first.
    fitted = timed("fit", phase_fit, conf)
    kernel = timed("kernel", phase_kernel, conf, fitted)
    numerics = timed("numerics", phase_numerics, conf, fitted)
    serving = timed("serve", phase_serve, conf, fitted, compiles)

    def moved(name, counters):
        return {
            k: v - before[name].get(k, 0)
            for k, v in counters.snapshot().items()
            if v - before[name].get(k, 0)
        }

    sharding = moved("sharding", sharding_counters)
    reliability = moved("reliability", reliability_counters)
    tripped = {
        k: v for k, v in {**sharding, **reliability}.items()
        if k in ("fallback_small_batch", "fallback_row_coupled",
                 "oom_downshifts") or k.endswith("_retries")
    }
    if tripped:
        raise AssertionError(f"fallback/downshift/retry counters: {tripped}")
    if fitted["operand_devices"] > 1 and not (
        sharding.get("batches_sharded") and sharding.get("sharded_chain_calls")
    ):
        raise AssertionError(
            f"no batch was placed row-sharded on the mesh: {sharding}"
        )
    return {
        "widths": fitted["widths"],
        "operand_devices": fitted["operand_devices"],
        "setup_seconds": seconds,
        "serving": serving,
        "kernel": kernel,
        "numerics": numerics,
        "sharding_counters": sharding,
        "reliability_counters": reliability,
        "compile_cache": {"requests": compiles.count, "hits": compiles.hits},
    }


def result_lines(device: dict, conf: SmokeConfig, report: dict) -> list:
    """What a run that passed prints last: the report, then the verdict.
    The verdict is what a caller parses, so it holds ``ok`` and the device
    as JAX reports it and nothing else; the rest is the line before it."""
    return [
        json.dumps({"device": device, "config": asdict(conf), **report}),
        json.dumps({"ok": True, "device": device}),
    ]


def main() -> int:
    # A warm .keystone_cache/ must not serve the fit from disk (empty =
    # disabled, the __graft_entry__ dry-run precedent).
    os.environ["KEYSTONE_CACHE_DIR"] = ""
    import jax

    from keystone_tpu.utils.metrics import peak_hbm_bytes
    from keystone_tpu.utils.platform import device_info, setup_compile_cache

    cache_dir = setup_compile_cache()
    device = device_info(need_tpu=True)  # raises: exit code 1, no result
    print(f"chip_smoke: {device['count']} x {device['kind']} "
          f"({device['platform']}), jax {jax.__version__}", flush=True)

    conf = SmokeConfig()
    report = smoke(conf)
    peak = peak_hbm_bytes()
    if peak is None:
        raise AssertionError("the TPU runtime reported no peak_bytes_in_use")
    counters = report["sharding_counters"]
    if not counters.get("pallas_mosaic_calls") or counters.get(
        "pallas_interpret_calls"
    ):
        raise AssertionError(
            f"the Pallas kernel did not compile through Mosaic: {counters}"
        )
    report["compile_cache"]["dir"] = cache_dir
    report.update(jax=jax.__version__, peak_hbm_bytes=peak)
    for line in result_lines(device, conf, report):
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
