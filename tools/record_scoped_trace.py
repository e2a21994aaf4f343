"""Record the small trace that ``tests/test_device_trace.py`` reads: two
traced "fits" at toy widths, each running every family of device scopes
(``keystone_tpu.utils.metrics.DEVICE_SCOPES`` and a fused chain's stages)
once: a cached and an uncached block least-squares solve with a ragged last
block, the kernel solver, a chain whose convolver takes its rectifier and
pooler, the filter fit's patch cut, a mixture fit, and a block solve whose
blocks are made from the narrow rows (random signs, FFT, rectifier). On the chip, through
the chip tool:

    python3 tools/record_scoped_trace.py chiprun_out/scoped-fit.xplane.pb

then ``gzip -9`` it to ``tests/data/scoped-fit.xplane.pb.gz``. Each fit lies
under a host span ``bench.fit``, as the benchmark's fits do. The last line
of output is the reader's table over the trace, as JSON.
"""

import glob
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def one_fit(rng_seed: int = 0):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from keystone_tpu.nodes.images import (
        Convolver,
        ImageVectorizer,
        Pooler,
        RandomPatcher,
        SymmetricRectifier,
    )
    from keystone_tpu.nodes.learning import GaussianKernelGenerator
    from keystone_tpu.nodes.learning.block_least_squares import (
        BlockLeastSquaresEstimator,
    )
    from keystone_tpu.nodes.learning.gmm import GaussianMixtureModelEstimator
    from keystone_tpu.nodes.learning.kernel_ridge import KernelRidgeRegression
    from keystone_tpu.linalg import BlockSource
    from keystone_tpu.nodes.stats import LinearRectifier, PaddedFFT, RandomSignNode
    from keystone_tpu.workflow import FusedTransformer
    from keystone_tpu.workflow.pipeline import BranchStack

    rng = np.random.default_rng(rng_seed)
    x = rng.normal(size=(256, 160)).astype(np.float32)
    y = rng.normal(size=(256, 6)).astype(np.float32)
    images = rng.uniform(0, 255, size=(16, 13, 13, 3)).astype(np.float32)
    bank = rng.normal(size=(32, 6, 6, 3)).astype(np.float32)
    stack = BranchStack.of(
        [[RandomSignNode.create(12, seed=i), PaddedFFT(), LinearRectifier()]
         for i in range(6)], 16, jax.ShapeDtypeStruct((1, 12), jnp.float32))
    chain = FusedTransformer([
        Convolver(bank, normalize_patches=10.0), SymmetricRectifier(alpha=0.25),
        Pooler(4, 4, mode="sum"), ImageVectorizer()])
    out = [
        # 160 columns in blocks of 64: the last one padded in the stacked copy.
        BlockLeastSquaresEstimator(block_size=64, num_iters=3, lam=0.1).fit(x, y).W,
        BlockLeastSquaresEstimator(block_size=64, num_iters=1, lam=0.1).fit(x, y).W,
        KernelRidgeRegression(GaussianKernelGenerator(0.01), lam=1.0, block_size=64,
                              num_epochs=2).fit(x[:, :32], y).alpha,
        chain.batch_call(jnp.asarray(images)),
        RandomPatcher(num_patches=64, patch_size=6).apply_batch(jnp.asarray(images)),
        GaussianMixtureModelEstimator(k=4, max_iters=3).fit(x[:, :8]).means,
        # Six branches of 12 values (8 features each) in blocks of two.
        BlockLeastSquaresEstimator(block_size=16, num_iters=3, lam=0.1).fit(
            BlockSource(x[:, :12], stack, 16, 48), y).W,
    ]
    jax.block_until_ready(out)


def main(argv) -> int:
    import jax

    from keystone_tpu.utils import device_trace
    from keystone_tpu.utils.platform import device_info

    out = argv[0]
    print(device_info(need_tpu=True), file=sys.stderr)
    one_fit()  # compiles every shape
    trace_dir = tempfile.mkdtemp(prefix="scoped-trace-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        for fit in range(2):
            with jax.profiler.TraceAnnotation("bench.fit", fit=fit):
                one_fit()
    finally:
        jax.profiler.stop_trace()
    (found,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    shutil.copy(found, out)
    shutil.rmtree(trace_dir, ignore_errors=True)
    table = device_trace.by_scope(device_trace.read(out), fit_span="bench.fit")
    print(device_trace.render(table), file=sys.stderr)
    print(json.dumps(device_trace.by_module(table)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
