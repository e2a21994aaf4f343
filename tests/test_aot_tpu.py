"""Deviceless AOT compilation of the device programs for a REAL v5e target.

JAX's topology API (`jax.experimental.topologies.get_topology_desc`) builds
compile-only v5e devices from libtpu with zero live hardware, so every hot
program — the BCD updates, the ring step, TSQR, normal-equations reductions,
and the Pallas Fisher-vector kernel (through Mosaic, at the real ImageNet
configuration) — gets XLA:TPU-compiled, and sized against v5e HBM by buffer
assignment, as a CI property before any chip time is spent.

These tests compile only (no execution — there is no device to run on);
numerics are covered by the CPU-mesh tests elsewhere in the suite.
"""

from __future__ import annotations

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS = "data"
# What one v5e chip reports as ``bytes_limit`` (PERF.md section 5).
V5E_HBM_BYTES = 16_909_336_064


def _v5e_mesh(n: int = 8):
    """An n-device compile-only v5e mesh, or a skip if the installed
    libtpu/PJRT can't build deviceless topologies (the exact failure is the
    skip reason, per the VERDICT's record-the-failure instruction)."""
    import subprocess
    import sys

    from jax.experimental import topologies

    # Probe in a KILLABLE subprocess first: a wedged libtpu (dead chip,
    # stale /tmp/libtpu_lockfile) HANGS topology construction instead of
    # erroring, and an in-process hang would eat the whole suite budget.
    # Only a probe that succeeds promotes to the in-process construction.
    probe_src = (
        "from jax.experimental import topologies;"
        "topologies.get_topology_desc('v5e:2x4', platform='tpu')"
    )
    try:
        probe = subprocess.run(
            [sys.executable, "-c", probe_src],
            capture_output=True, text=True, timeout=60,
        )
    except subprocess.TimeoutExpired:  # pragma: no cover - env-dependent
        pytest.skip("deviceless TPU topology unavailable: libtpu hung (>60s)")
    if probe.returncode != 0:  # pragma: no cover - environment-dependent
        tail = (probe.stderr or probe.stdout or "").strip().splitlines()
        pytest.skip(
            "deviceless TPU topology unavailable: "
            + (tail[-1] if tail else f"probe exit {probe.returncode}")
        )
    try:
        topo = topologies.get_topology_desc("v5e:2x4", platform="tpu")
    except Exception as e:  # pragma: no cover - environment-dependent
        pytest.skip(f"deviceless TPU topology unavailable: {type(e).__name__}: {e}")
    devs = topo.devices
    assert len(devs) >= n
    return Mesh(np.array(devs[:n]), (AXIS,))


@pytest.fixture(scope="module")
def mesh():
    return _v5e_mesh()


def _fold(mesh) -> int:
    """The canonical-fold key the solver builders take (what production
    passes: ``fold_blocks`` of the mesh width)."""
    from keystone_tpu.utils.mesh import fold_blocks

    return fold_blocks(mesh.shape[AXIS])


def _reduces_across_devices(text: str) -> bool:
    """The row reduction as a TPU collective: the canonical fold's
    butterfly is collective-permutes; a width it cannot serve psums."""
    return "collective-permute" in text or "all-reduce" in text


def _sds(shape, mesh, spec, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, spec)
    )


def _compiled_ok(compiled) -> bool:
    text = compiled.as_text()
    assert "HloModule" in text
    return True


@pytest.mark.parametrize("pad", [0, 40], ids=["tiled", "padded-tail"])
def test_uncached_epochs_program_compiles_for_v5e(mesh, pad):
    """The scan body that forms gram and inverse at every block visit (the
    single-epoch solve), with and without a padded last block."""
    from keystone_tpu.linalg.bcd import _fused_epochs_fn
    from keystone_tpu.linalg.row_matrix import _precision

    fn = _fused_epochs_fn(
        mesh, AXIS, _precision(), False, 1, False, _fold(mesh), pad
    )
    n, b, k, nb = 1024, 128, 16, 4
    args = (
        _sds((nb, n, b), mesh, P(None, AXIS)),  # a3
        _sds((nb, 1, 1), mesh, P()),  # the uncached body's placeholder
        _sds((n, k), mesh, P(AXIS)),  # r
        _sds((nb, b, k), mesh, P()),  # w3
        _sds((), mesh, P()),  # lam
        _sds((n,), mesh, P(AXIS)),  # w_rows
    )
    compiled = fn.lower(*args).compile()
    assert _compiled_ok(compiled)
    text = compiled.as_text()
    assert "Cholesky" in text and "while" in text
    # The gram reduction must be present as a TPU collective.
    assert _reduces_across_devices(text)


@pytest.mark.parametrize(
    "n,b,k,whole_mesh",
    [
        (1024, 128, 16, True),
        # The ImageNet block size on one device — the host-streamed
        # path's production shape (slow: real v5e buffer assignment).
        pytest.param(8192, 8192, 1000, False, marks=pytest.mark.slow),
    ],
)
def test_bcd_streamed_first_and_cached_updates_compile_for_v5e(
    mesh, n, b, k, whole_mesh
):
    from keystone_tpu.linalg.bcd import (
        _cached_block_update_fn,
        _first_epoch_update_fn,
    )
    from keystone_tpu.linalg.row_matrix import _precision

    if not whole_mesh:
        mesh = Mesh(np.array(mesh.devices.flat[:1]), (AXIS,))
    first = _first_epoch_update_fn(
        mesh, AXIS, _precision(), True, _fold(mesh)
    )
    c1 = first.lower(
        _sds((n, b), mesh, P(AXIS)),
        _sds((n, k), mesh, P(AXIS)),
        _sds((b, k), mesh, P()),
        _sds((), mesh, P()),
        _sds((n,), mesh, P(AXIS)),
    ).compile()
    assert _compiled_ok(c1)
    cached = _cached_block_update_fn(
        mesh, AXIS, _precision(), True, _fold(mesh)
    )
    c2 = cached.lower(
        _sds((n, b), mesh, P(AXIS)),
        _sds((b, b), mesh, P()),  # cached ridge inverse
        _sds((n, k), mesh, P(AXIS)),
        _sds((b, k), mesh, P()),
        _sds((n,), mesh, P(AXIS)),
    ).compile()
    assert _compiled_ok(c2)


def test_padded_tail_stack_and_factor_compile_for_v5e(mesh):
    """A ragged last block: the stacking program pads it with zero columns
    and the factor program of the chunk that holds it puts 1 on the pad's
    diagonal; both must XLA:TPU-compile."""
    from keystone_tpu.linalg.bcd import _fused_factor_fn, _stack_blocks_fn
    from keystone_tpu.linalg.row_matrix import _precision

    n, b, nb, pad = 1024, 128, 16, 40
    stack = _stack_blocks_fn(mesh, AXIS, nb, pad)
    c0 = stack.lower(_sds((n, nb * b - pad), mesh, P(AXIS))).compile()
    assert _compiled_ok(c0)
    factor = _fused_factor_fn(
        mesh, AXIS, _precision(), False, _fold(mesh), pad
    )
    c1 = factor.lower(
        _sds((nb, n, b), mesh, P(None, AXIS)),
        _sds((), mesh, P()),
        _sds((n,), mesh, P(AXIS)),
    ).compile()
    assert _reduces_across_devices(c1.as_text())


def test_padded_factor_compiles_above_the_leaf_for_v5e(mesh):
    """Above the leaf the inverse is blocked: 1088 = 512 + 576, halves that
    differ and of which one is no power of two. A last chunk that holds the
    padded block alone."""
    from keystone_tpu.linalg.bcd import _fused_factor_fn, _inv_levels
    from keystone_tpu.linalg.row_matrix import _precision

    n, b, pad = 1024, 1088, 40
    assert _inv_levels(b) == 1
    factor = _fused_factor_fn(
        mesh, AXIS, _precision(), False, _fold(mesh), pad
    )
    compiled = factor.lower(
        _sds((1, n, b), mesh, P(None, AXIS)),
        _sds((), mesh, P()),
        _sds((n,), mesh, P(AXIS)),
    ).compile()
    assert _compiled_ok(compiled)
    assert "Cholesky" in compiled.as_text()  # factor_ms tells the phase by it


def test_ring_bcd_step_compiles_for_v5e(mesh):
    """The mp ring: ppermute over the model axis must lower to a TPU
    collective-permute inside a while loop."""
    from keystone_tpu.linalg.ring_bcd import _ring_solve_fn
    from keystone_tpu.linalg.row_matrix import _precision

    fn = _ring_solve_fn(mesh, AXIS, None, _precision())
    n, d, k = 512, 256, 16
    kc = k // 8 if k >= 8 else k
    compiled = fn.lower(
        _sds((n, d), mesh, P(None, AXIS)),
        _sds((n, 8 * kc), mesh, P(None, AXIS)),
        _sds((), mesh, P()),
        _sds((), mesh, P(), dtype=jnp.int32),  # num_steps (dynamic bound)
    ).compile()
    text = compiled.as_text()
    assert "collective-permute" in text
    assert "while" in text


def test_tsqr_compiles_for_v5e(mesh):
    from keystone_tpu.linalg.tsqr import _tsqr_r_fn

    fn = _tsqr_r_fn(mesh, AXIS)
    compiled = fn.lower(_sds((2048, 64), mesh, P(AXIS))).compile()
    text = compiled.as_text()
    assert "all-gather" in text


def test_normal_equations_reductions_compile_for_v5e(mesh):
    from keystone_tpu.linalg.row_matrix import _gram_and_atb_fn, _precision

    fn = _gram_and_atb_fn(mesh, AXIS, _precision(), _fold(mesh))
    compiled = fn.lower(
        _sds((2048, 256), mesh, P(AXIS)), _sds((2048, 16), mesh, P(AXIS))
    ).compile()
    assert _reduces_across_devices(compiled.as_text())


def test_pallas_fv_mosaic_compiles_for_v5e(mesh):
    """The Pallas kernel through the REAL Mosaic lowering (interpret=False)
    — the compile chip_smoke.py otherwise meets first on the chip."""
    from keystone_tpu.ops.fisher_vector_pallas import fisher_vectors_pallas

    one = Mesh(np.array(mesh.devices.flat[:1]), ("d",))
    repl = NamedSharding(one, P())

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    fv = functools.partial(fisher_vectors_pallas, interpret=False)
    bsz, m, d, k = 2, 256, 64, 16
    compiled = (
        jax.jit(fv)
        .lower(sds((bsz, m, d)), sds((k,)), sds((k, d)), sds((k, d)))
        .compile()
    )
    assert _compiled_ok(compiled)
    assert "custom-call" in compiled.as_text()  # the Mosaic kernel call


@pytest.mark.slow
def test_pallas_fv_mosaic_compiles_at_imagenet_config(mesh):
    """k=256, m≈2000, d=64 — the ImageNet configuration's VMEM/tiling
    limits. Compiling it for v5e settles them without a chip."""
    from keystone_tpu.ops.fisher_vector_pallas import fisher_vectors_pallas

    one = Mesh(np.array(mesh.devices.flat[:1]), ("d",))
    repl = NamedSharding(one, P())

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    fv = functools.partial(fisher_vectors_pallas, interpret=False)
    bsz, m, d, k = 8, 2048, 64, 256
    compiled = (
        jax.jit(fv)
        .lower(sds((bsz, m, d)), sds((k,)), sds((k, d)), sds((k, d)))
        .compile()
    )
    assert _compiled_ok(compiled)


def test_conv_rectify_pool_mosaic_compiles_at_cifar_fit_config(mesh):
    """The convolver's kernel at ``cifar-fit``'s sizes (6,250 images, 729
    positions, 10,000 filters, 14 / 13 pooling) through the real Mosaic
    lowering: its VMEM tiles (eight images by 1,280 filters a step) and
    the program's HBM (the one-hot patches, their reordering, the pooled
    sums) are settled for v5e here. As 36 slices of the 3-channel image
    the patches alone asked for 20 GB."""
    from keystone_tpu.ops.conv_pool_pallas import conv_rectify_pool

    one = Mesh(np.array(mesh.devices.flat[:1]), ("d",))
    repl = NamedSharding(one, P())

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=repl)

    kernel = functools.partial(
        conv_rectify_pool, window=(6, 6), alpha=0.25, max_val=0.0,
        pool_size=14, pool_stride=13, interpret=False)
    compiled = jax.jit(kernel).lower(
        sds((6250, 32, 32, 3)), sds((108, 10000)), sds((6250, 27, 27, 1)),
        sds((10000,)),
    ).compile()
    assert "custom-call" in compiled.as_text()  # the Mosaic kernel call
    memory = compiled.memory_analysis()
    # 2.0 GB of pooled sums (tiled layout: a little over 6250 x 80000 x 4).
    assert 6250 * 80000 * 4 <= memory.output_size_in_bytes < 2.05e9
    assert memory.temp_size_in_bytes < 7 * 2**30


def test_patch_cut_is_one_gather_at_cifar_fit_shape(mesh):
    """The filter fit's patch cut at ``cifar-fit``'s sizes (100,000 patches
    of 6 x 6 x 3 from 6,250 images) for one v5e chip: no loop (a slice
    narrower than the image row ran as 100,000 of them, 0.30 s a fit) and
    no more scratch than the row gather's 0.54 GB (a gather of single
    values would hold 3.3 GB, more than ``cifar-kernel-fit``'s chip has
    free beside its solver)."""
    from keystone_tpu.nodes.images.patches import _take_patches

    one = Mesh(np.array(mesh.devices.flat[:1]), ("d",))
    idx = _sds((100_000,), one, P(), jnp.int32)
    compiled = _take_patches.lower(
        _sds((6250, 32, 32, 3), one, P()), idx, idx, idx, size=6).compile()
    text = compiled.as_text()
    assert "while" not in text and "gather" in text
    assert "filters.rows" in text and "filters.cols" in text
    assert compiled.memory_analysis().temp_size_in_bytes <= 0.7e9


@pytest.mark.parametrize("keep", [0, 13])
def test_kernel_block_solve_compiles_at_cifar_kernel_fit_shape(mesh, keep):
    """The kernel solver's one program at ``cifar-kernel-fit``'s sizes
    (50,000 x 4,096 features, k = 10, blocks of 4,096 rows, 39 visits) for
    one v5e chip: the loop over the visits is in it. Without a store it
    holds, beside its arguments, one kernel block (0.82 GB) and little
    else: never the 10 GB kernel. With the cell's 13 slots it holds the
    store once (10.65 GB, updated in place: a copy would be a second
    store) and the working block of each of its two loops, and fits the
    chip beside its arguments."""
    from keystone_tpu.linalg.row_matrix import _precision
    from keystone_tpu.nodes.learning import GaussianKernelGenerator
    from keystone_tpu.nodes.learning.kernel_ridge import _block_solve_fn

    one = Mesh(np.array(mesh.devices.flat[:1]), (AXIS,))
    n, d, k, b = 50000, 4096, 10, 4096
    kernel = GaussianKernelGenerator(2e-4)
    kernel.gamma = _sds((), one, P())
    compiled = _block_solve_fn(
        one, AXIS, _precision(), _fold(one), b, keep, 13).lower(
        _sds((n, d), one, P(AXIS)), _sds((n, k), one, P(AXIS)), _sds((), one, P()),
        _sds((), one, P(), jnp.int32), _sds((39,), one, P(), jnp.int32), kernel,
    ).compile()
    text = compiled.as_text()
    assert "Cholesky" in text and "while" in text
    memory = compiled.memory_analysis()
    block = n * b * 4
    assert memory.argument_size_in_bytes < 1.01 * (n * d * 4)
    if keep:
        assert keep * block <= memory.temp_size_in_bytes < (keep + 2.5) * block
        assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
                + memory.output_size_in_bytes) < V5E_HBM_BYTES
    else:
        assert block <= memory.temp_size_in_bytes < 1.5 * block


def test_generated_block_solve_compiles_at_mnist_fft_fit_shape(mesh):
    """The generated-block dense solve at ``mnist-fft-fit``'s sizes (60,000
    images of 784, 200 branches of random signs, padded FFT and rectifier,
    blocks of 2,048 columns, k = 10, 3 epochs) for one v5e chip, with the
    slots the shared rule keeps there when it is given the cached inverses
    and what making a block holds (the generator compiled alone for the
    chip, ``bcd._making_bytes``): the store is the argument the program writes in place and hands
    back (aliased, not a second store), and the program's arguments and
    temporaries fit in what the rule leaves of the chip, an eighth kept for
    what is not this program's. Never the 24.6 GB of features."""
    from keystone_tpu.linalg import bcd
    from keystone_tpu.linalg.row_matrix import _blocks_kept, _precision
    from keystone_tpu.nodes.stats import LinearRectifier, PaddedFFT, RandomSignNode
    from keystone_tpu.workflow.pipeline import BranchStack

    one = Mesh(np.array(mesh.devices.flat[:1]), (AXIS,))
    n, d_in, k, b = 60000, 784, 10, 2048
    stack = BranchStack.of(
        [[RandomSignNode.create(d_in, seed=i), PaddedFFT(), LinearRectifier()]
         for i in range(200)], b, jax.ShapeDtypeStruct((1, d_in), jnp.float32))
    generator = jax.tree_util.tree_map(lambda a: _sds(a.shape, one, P(), a.dtype), stack)
    x = _sds((n, d_in), one, P(AXIS))
    making = jax.jit(lambda g, x, i: g.block(x, i)).lower(
        generator, x, _sds((), one, P(), jnp.int32)).compile().memory_analysis()
    block = n * b * 4
    keep = _blocks_kept(n, b, 50, 3, n * (d_in + k) * 4, 4, V5E_HBM_BYTES,
                        factor_bytes=50 * b * b * 4,
                        making_bytes=(making.argument_size_in_bytes + making.temp_size_in_bytes
                                      + making.output_size_in_bytes))
    assert 0 < keep < 50
    compiled = bcd._generated_solve_fn(one, AXIS, _precision(), _fold(one), b, 50, 3,
                                       keep, 0, True).lower(
        x, generator, _sds((n, k), one, P(AXIS)),
        _sds((), one, P()), _sds((), one, P(), jnp.int32),
        _sds((keep, n, b), one, P(None, AXIS)),
    ).compile()
    assert "Cholesky" in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes < keep * block + 0.2e9
    assert memory.alias_size_in_bytes >= keep * block
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            <= V5E_HBM_BYTES - V5E_HBM_BYTES // 8)


def test_kernel_block_solve_compiles_sharded_for_v5e(mesh):
    """The same program over the mesh: X_B and K_BB reach every chip by a
    collective, K_B^T alpha reduces over the sharded rows, and the dual
    weights come back replicated."""
    from keystone_tpu.linalg.row_matrix import _precision
    from keystone_tpu.nodes.learning import GaussianKernelGenerator
    from keystone_tpu.nodes.learning.kernel_ridge import _block_solve_fn

    n, d, k, b = 2048, 256, 10, 512
    kernel = GaussianKernelGenerator(2e-4)
    kernel.gamma = _sds((), mesh, P())
    compiled = _block_solve_fn(mesh, AXIS, _precision(), _fold(mesh), b).lower(
        _sds((n, d), mesh, P(AXIS)), _sds((n, k), mesh, P(AXIS)), _sds((), mesh, P()),
        _sds((), mesh, P(), jnp.int32), _sds((8,), mesh, P(), jnp.int32), kernel,
    ).compile()
    text = compiled.as_text()
    assert "Cholesky" in text and "while" in text
    assert "all-reduce" in text and "all-gather" in text
    assert _reduces_across_devices(text)


def test_dense_sift_xla_compiles_for_v5e(mesh):
    """The on-chip dense SIFT (grouped 1-D convs) must XLA:TPU-compile —
    it is the --sift-backend xla path that moves the last host-side
    featurization stage onto the chips."""
    import functools

    from keystone_tpu.ops.sift_xla import dense_sift_xla

    fn = functools.partial(dense_sift_xla, step=4, bin_size=4)
    # The batch-sharded input carries the v5e topology — without a
    # sharding the lowering would silently target the default (CPU)
    # backend and prove nothing.
    c = jax.jit(fn).lower(
        _sds((8, 256, 256), mesh, P(AXIS))
    ).compile()
    assert _compiled_ok(c)


def test_convolver_compiles_for_v5e(mesh):
    """The image-pipeline hot op (explicit patches times the flattened
    bank, which XLA:TPU lowers to its convolution op) on the v5e target."""
    from keystone_tpu.nodes.images.convolver import Convolver

    conv = Convolver(np.zeros((64, 6, 6, 3), dtype=np.float32))
    one = Mesh(np.array(mesh.devices.flat[:1]), ("d",))
    x = jax.ShapeDtypeStruct(
        (32, 32, 32, 3), jnp.float32, sharding=NamedSharding(one, P())
    )
    compiled = jax.jit(conv.apply_batch).lower(x).compile()
    assert "convolution" in compiled.as_text()


def test_fused_solver_programs_compile_for_v5e(mesh):
    """The in-HBM solve (stack → batched factor → scanned epochs): its
    three programs must XLA:TPU-compile."""
    from keystone_tpu.linalg.bcd import (
        _fused_epochs_fn,
        _fused_factor_fn,
        _stack_blocks_fn,
    )
    from keystone_tpu.linalg.row_matrix import _precision

    n, d, b, k, nb = 1024, 512, 128, 16, 4
    stack = _stack_blocks_fn(mesh, AXIS, nb)
    c0 = stack.lower(_sds((n, d), mesh, P(AXIS))).compile()
    assert _compiled_ok(c0)
    factor = _fused_factor_fn(mesh, AXIS, _precision(), False, _fold(mesh))
    c1 = factor.lower(
        _sds((nb, n, b), mesh, P(None, AXIS)),
        _sds((), mesh, P()),
        _sds((n,), mesh, P(AXIS)),
    ).compile()
    assert _reduces_across_devices(c1.as_text())
    epochs = _fused_epochs_fn(
        mesh, AXIS, _precision(), False, 3, True, _fold(mesh)
    )
    c2 = epochs.lower(
        _sds((nb, n, b), mesh, P(None, AXIS)),
        _sds((nb, b, b), mesh, P()),
        _sds((n, k), mesh, P(AXIS)),
        _sds((nb, b, k), mesh, P()),
        _sds((), mesh, P()),
        _sds((n,), mesh, P(AXIS)),
    ).compile()
    text = c2.as_text()
    assert "while" in text  # the scanned epoch/block loops
    assert _reduces_across_devices(text)


@pytest.mark.slow
def test_two_branch_imagenet_featurizer_compiles_for_v5e(mesh):
    """The FULL gathered featurizer graph at the headline 64k-dim config
    (SIFT-XLA and LCS branches, each PCA→FV(k=256)→signed-sqrt→L2, fused
    and concatenated) XLA:TPU-compiles as ONE program inside a stated
    wall-time budget — SURVEY.md §7 hard part 6 ("two deep branches fused
    without blowing compile time"), previously covered only per-program."""
    import time

    from keystone_tpu.loaders.imagenet import ImageNetLoader
    from keystone_tpu.pipelines.images.imagenet_sift_lcs_fv import (
        ImageNetSiftLcsFVConfig,
        build_featurizer,
    )
    from keystone_tpu.workflow import PipelineEnv, fitted_forward

    conf = ImageNetSiftLcsFVConfig(
        sift_backend="xla",  # the jittable on-chip branch (native = ctypes)
        fv_backend="tpu",
        pca_dims=64,
        gmm_k=256,  # 2·(2·256·64) = 65,536-dim gathered features
        gmm_iters=2,
        descriptor_sample=20_000,
    )
    train, _ = ImageNetLoader.synthetic(n=16, num_classes=4, size=64)
    PipelineEnv.reset()
    try:
        featurizer = build_featurizer(conf, train.data)
        fn = fitted_forward(featurizer, train.data[:2])
        out = jax.eval_shape(
            fn, jax.ShapeDtypeStruct((8, 64, 64, 3), jnp.float32)
        )
        assert out.shape[-1] == 2 * (2 * conf.gmm_k * conf.pca_dims) == 65_536
        t0 = time.time()
        compiled = (
            jax.jit(fn)
            .lower(_sds((8, 64, 64, 3), mesh, P(AXIS)))
            .compile()
        )
        wall = time.time() - t0
    finally:
        PipelineEnv.reset()
    assert _compiled_ok(compiled)
    # Budget: generous for the 1-core host, but low enough that a
    # combinatorial blowup (e.g. per-descriptor unrolling) fails loudly.
    assert wall < 600.0, f"featurizer compile took {wall:.0f}s"


def _compile_fused_factor(mesh, n, b, expected_chunk):
    """The fused factor program at the chunk the TPU policy gives block b,
    compiled for one v5e chip; returns (one-chip mesh, chunk). Its
    temporaries must stay inside what the chunk policy budgets (1.76 GiB
    at chunk 2 of b 8192 and 1.57 at chunk 8 of b 4096 with the blocked
    inverse; 2.79 and 2.60 with the dense solve and product it replaced)."""
    from unittest import mock

    from keystone_tpu.linalg.bcd import _factor_chunk, _fused_factor_fn
    from keystone_tpu.linalg.row_matrix import _precision

    with mock.patch("jax.default_backend", return_value="tpu"):
        chunk = _factor_chunk(b)  # the TPU policy, not this CPU host's
    assert chunk == expected_chunk
    one = Mesh(np.array(mesh.devices.flat[:1]), (AXIS,))
    factor = _fused_factor_fn(one, AXIS, _precision(), False, _fold(one))
    compiled = factor.lower(
        _sds((chunk, n, b), one, P(None, AXIS)),
        _sds((), one, P()),
        _sds((n,), one, P(AXIS)),
    ).compile()
    assert _compiled_ok(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30
    return one, chunk


@pytest.mark.slow
def test_fused_factor_compiles_at_timit_fit_shape(mesh):
    """The benchmark's ``timit-fit`` factor program: 8 blocks of 4096 a
    program, 4096 rows."""
    _compile_fused_factor(mesh, n=4096, b=4096, expected_chunk=8)


@pytest.mark.slow
@pytest.mark.parametrize(
    "scale_key,expected_chunk",
    [
        ("tpu-imagenet", 2),  # memory cap binds: 128M // 8192² = 2
        ("tpu-xl", 16),  # batch default binds (cap would allow 32)
    ],
)
def test_fused_solver_compiles_at_bench_shapes(mesh, scale_key, expected_chunk):
    """The full-scale bench shapes ('tpu-imagenet' n=8192/d=65536/k=1000/
    b=8192; 'tpu-xl' d=262144, 128 blocks of 2048) must not meet their
    first XLA:TPU compile on the chip, and must fit v5e buffer
    assignment."""
    import bench as bench_mod
    from keystone_tpu.linalg.bcd import _fused_epochs_fn
    from keystone_tpu.linalg.row_matrix import _precision

    p = bench_mod.SCALE[scale_key]
    n, d, k, b = p["n"], p["d"], p["k"], p["block"]
    nb = d // b
    # The production factor phase chunks the stack (_solve_fused): the
    # UNCHUNKED (nb, n, b) factor program at this shape demands ~5 stacked
    # (nb, b, b) temps ≈ 10+ GB of HLO temp and fails v5e buffer
    # assignment — which is exactly why the chunk policy exists. Compile
    # the shape production actually runs. The policy output is pinned per
    # scale so cap rot is detected where the cap binds (imagenet) and
    # batch-default drift where it doesn't (xl).
    one, chunk = _compile_fused_factor(mesh, n, b, expected_chunk)
    assert chunk < nb
    epochs = _fused_epochs_fn(
        one, AXIS, _precision(), False, p["iters"], True, _fold(one)
    )
    c2 = epochs.lower(
        _sds((nb, n, b), one, P(None, AXIS)),
        _sds((nb, b, b), one, P()),
        _sds((n, k), one, P(AXIS)),
        _sds((nb, b, k), one, P()),
        _sds((), one, P()),
        _sds((n,), one, P(AXIS)),
    ).compile()
    assert _compiled_ok(c2)
    if scale_key == "tpu-imagenet":
        # The UNCACHED body (single-epoch solves, cache_grams auto=False
        # at num_iters=1) re-derives each block's inverse INSIDE the scan
        # — the chunked-trsm machinery must fit there too. The dummy invs
        # operand mirrors _solve_fused's (nb, 1, 1) placeholder.
        unc = _fused_epochs_fn(
            one, AXIS, _precision(), False, 1, False, _fold(one)
        )
        c3 = unc.lower(
            _sds((nb, n, b), one, P(None, AXIS)),
            _sds((nb, 1, 1), one, P()),
            _sds((n, k), one, P(AXIS)),
            _sds((nb, b, k), one, P()),
            _sds((), one, P()),
            _sds((n,), one, P(AXIS)),
        ).compile()
        assert _compiled_ok(c3)
