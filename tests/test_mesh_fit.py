"""Mesh-native data-parallel fit (the ISSUE-13 tentpole).

Covers the four contracts of the spec-threaded path:

1. **Spec threading** — fused chains lower ONCE with the ``SpecLayout``
   convention's explicit ``in_shardings``/``out_shardings`` instead of
   inheriting input placement, and ``fitted_forward(layout=...)`` does
   the same for the functional replay.
2. **No silent cliff** — non-divisible batches mask-pad onto the mesh
   and trim (row counts downstream unchanged); the single-device
   fallback survives only below ``shard_min_rows`` and every decision
   is registry-counted.
3. **Bit-identity** — sharded vs unsharded fit/apply is byte-equal on
   the canonical pipeline shapes (MNIST FFT, the two-branch
   featurize→solve shape, newsgroups text), including under the
   standard chaos plan.
4. **Sharding-safe state** — checkpoints carry the mesh manifest and a
   mesh-width change is REFUSED with the typed ``MeshMismatchError``
   (both solvers, pinned both ways); profile-store entries from a
   different device_count are refused at load; profile rows carry the
   shard count; the resource planner prices chunks per shard.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_same_to_rounding

from keystone_tpu.config import config
from keystone_tpu.utils import mesh as mesh_util
from keystone_tpu.utils.mesh import (
    MeshMismatchError,
    SpecLayout,
    batch_layout,
    layout_of_array,
    num_data_shards,
    reset_default_mesh,
    set_default_mesh,
    value_data_shards,
)
from keystone_tpu.utils.metrics import sharding_counters
from keystone_tpu.workflow.executor import PipelineEnv
from keystone_tpu.workflow.operators import DatasetOperator
from keystone_tpu.workflow.pipeline import Pipeline, Transformer


@pytest.fixture(autouse=True)
def _fresh_sharding_state():
    """Counters and the shard toggle restored around every test."""
    prior = config.shard_data_batches
    sharding_counters.reset()
    yield
    config.shard_data_batches = prior
    sharding_counters.reset()


class MatmulChain(Transformer):
    """A deterministic jittable featurize chain (matmul + elementwise)."""

    def __init__(self, seed: int, d_in: int = 32, d_out: int = 48):
        self.seed, self.d_in, self.d_out = int(seed), int(d_in), int(d_out)
        rng = np.random.default_rng(self.seed)
        self._W = jnp.asarray(
            rng.normal(size=(d_in, d_out)).astype(np.float32)
        )

    def signature(self):
        return self.stable_signature(self.seed, self.d_in, self.d_out)

    def apply_batch(self, X):
        Y = jnp.tanh(X @ self._W)
        return Y / (1.0 + jnp.abs(Y))


def _two_branch_pipeline(X, y):
    """The two-branch ImageNet-featurizer shape at test scale: two
    jittable branches gathered into one block least squares."""
    from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator

    feat = Pipeline.gather(
        [MatmulChain(1).to_pipeline(), MatmulChain(2).to_pipeline()]
    )
    return feat.and_then(
        BlockLeastSquaresEstimator(block_size=96, num_iters=1, lam=1e-3),
        X, y,
    )


def _fit_apply(build, X_test, shard: bool) -> np.ndarray:
    PipelineEnv.reset()
    prior = config.shard_data_batches
    config.shard_data_batches = shard
    try:
        fitted = build().fit()
        return np.asarray(fitted.apply(X_test).get())
    finally:
        config.shard_data_batches = prior
        PipelineEnv.reset()


# ---------------------------------------------------------------------------
# Mesh helpers: reset + SpecLayout
# ---------------------------------------------------------------------------


def test_reset_default_mesh_drops_memoized_narrow_mesh():
    set_default_mesh(
        mesh_util.default_mesh(devices=jax.devices()[:1])
    )
    assert num_data_shards() == 1
    reset_default_mesh()
    assert num_data_shards() == len(jax.devices()) == 8


def test_spec_layout_convention_and_pad_put():
    layout = SpecLayout.for_mesh()
    assert layout.num_shards == 8
    assert layout.data().spec == jax.sharding.PartitionSpec(
        config.data_axis
    )
    assert layout.replicated().spec == jax.sharding.PartitionSpec()
    x = np.arange(70 * 4, dtype=np.float32).reshape(70, 4)
    padded, n = layout.pad_put(x)
    assert n == 70 and padded.shape == (72, 4)
    assert layout_of_array(padded) == layout
    assert value_data_shards(padded) == 8
    # The explicit lowering is bit-identical to the plain jit.
    chain = lambda a: jnp.tanh(a) * 2.0  # noqa: E731
    got = np.asarray(layout.jit(chain)(padded))[:70]
    np.testing.assert_array_equal(got, np.asarray(jax.jit(chain)(x)))


def test_batch_layout_decisions():
    layout = SpecLayout.for_mesh()
    big_div = np.zeros((128, 4), dtype=np.float32)
    big_odd = np.zeros((130, 4), dtype=np.float32)
    small = np.zeros((8, 4), dtype=np.float32)
    text = np.array(["a", "b"], dtype=object)
    # Divisible host batches stage (and donate) through the chain call
    # when they arrive host-side (e.g. from a host stage mid-chain).
    assert batch_layout(big_div) == layout
    # Non-divisible >= min rows: the mask-pad path.
    assert batch_layout(big_odd) == layout
    assert batch_layout(small) is None
    assert batch_layout(text) is None
    # An already-sharded array re-lowers with its own layout.
    assert batch_layout(layout.put(big_div)) == layout


# ---------------------------------------------------------------------------
# No silent cliff: DatasetOperator + fused-chain pad path
# ---------------------------------------------------------------------------


def test_dataset_operator_places_and_counts():
    div = DatasetOperator(np.zeros((128, 4), dtype=np.float32)).execute([])
    assert isinstance(div, jax.Array)
    assert value_data_shards(div) == 8
    odd = DatasetOperator(np.zeros((130, 4), dtype=np.float32)).execute([])
    assert isinstance(odd, np.ndarray)  # deferred to the chain's pad path
    small = DatasetOperator(np.zeros((16, 4), dtype=np.float32)).execute([])
    assert isinstance(small, np.ndarray)
    snap = sharding_counters.snapshot()
    assert snap.get("batches_sharded") == 1
    assert snap.get("batches_deferred_pad") == 1
    assert snap.get("fallback_small_batch") == 1


def test_fused_chain_pads_trims_and_counts():
    """A non-divisible batch through a jittable chain: output rows are
    unchanged, values are the unsharded walk's to rounding (9 rows a shard
    against 70 in one product), and the pad traffic is registry-counted:
    zero silent fallbacks."""
    t = MatmulChain(3)
    X = np.random.default_rng(0).normal(size=(70, 32)).astype(np.float32)
    config.shard_data_batches = False
    ref = np.asarray(t.batch_call(X))
    config.shard_data_batches = True
    sharding_counters.reset()
    out = t.batch_call(X)
    assert out.shape[0] == 70
    assert_same_to_rounding(out, ref)
    snap = sharding_counters.snapshot()
    assert snap.get("batches_padded") == 1
    assert snap.get("pad_rows_added") == 2
    assert snap.get("sharded_chain_calls") == 1
    assert "fallback_small_batch" not in snap


def test_row_coupled_chain_refuses_padding():
    class RowCoupled(MatmulChain):
        row_independent = False

    t = RowCoupled(4)
    X = np.random.default_rng(0).normal(size=(70, 32)).astype(np.float32)
    config.shard_data_batches = False
    ref = np.asarray(t.batch_call(X))
    config.shard_data_batches = True
    sharding_counters.reset()
    out = np.asarray(t.batch_call(X))
    np.testing.assert_array_equal(out, ref)
    snap = sharding_counters.snapshot()
    assert snap.get("fallback_row_coupled") == 1
    assert "batches_padded" not in snap


def test_sharded_input_uses_explicit_specs():
    """An already-sharded batch re-lowers with the explicit SpecLayout
    shardings (counted), and the output keeps the row-sharded layout."""
    t = MatmulChain(5)
    layout = SpecLayout.for_mesh()
    X = np.random.default_rng(0).normal(size=(64, 32)).astype(np.float32)
    config.shard_data_batches = True
    sharding_counters.reset()
    out = t.batch_call(layout.put(X))
    assert sharding_counters.get("sharded_chain_calls") == 1
    assert layout_of_array(out) == layout
    config.shard_data_batches = False
    assert_same_to_rounding(out, t.batch_call(X))


# ---------------------------------------------------------------------------
# Bit-identity on the canonical pipeline shapes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows", [512, 518])
def test_two_branch_fit_apply_bit_identical(rows):
    """The two-branch featurize→solve shape, divisible and mask-padded:
    the sharded walk's held-out predictions are the single-device
    walk's to rounding (its products and grams are summed a shard at a
    time), through a ridge solve at lambda 1e-3."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(rows, 32)).astype(np.float32)
    y = rng.normal(size=(rows, 4)).astype(np.float32)
    X_test = rng.normal(size=(70, 32)).astype(np.float32)
    build = lambda: _two_branch_pipeline(X, y)  # noqa: E731
    ref = _fit_apply(build, X_test, shard=False)
    sharding_counters.reset()
    got = _fit_apply(build, X_test, shard=True)
    assert_same_to_rounding(got, ref)
    snap = sharding_counters.snapshot()
    assert snap.get("sharded_chain_calls", 0) > 0
    assert "fallback_small_batch" not in snap


def test_mnist_fft_fit_apply_bit_identical():
    """The canonical MNIST random-FFT pipeline (gathered FFT branches →
    LinearMapEstimator → MaxClassifier), sharded vs unsharded."""
    from keystone_tpu.loaders import MnistLoader
    from keystone_tpu.pipelines.images.mnist_random_fft import (
        MnistRandomFFTConfig,
        build_pipeline,
    )

    conf = MnistRandomFFTConfig(num_ffts=2, synthetic_n=512, seed=0)
    train, test = MnistLoader.synthetic(n=conf.synthetic_n, seed=conf.seed)
    build = lambda: build_pipeline(  # noqa: E731
        conf, train.data, train.labels
    )
    ref = _fit_apply(build, test.data, shard=False)
    got = _fit_apply(build, test.data, shard=True)
    np.testing.assert_array_equal(ref, got)


def test_newsgroups_fit_apply_bit_identical():
    """The canonical newsgroups text shape (host tokenize → n-grams →
    term frequency → sparse features → naive bayes): the sharded walk
    must leave the host/text path byte-identical."""
    from keystone_tpu.loaders.newsgroups import NewsgroupsDataLoader
    from keystone_tpu.nodes.learning import NaiveBayesEstimator
    from keystone_tpu.nodes.nlp import (
        CommonSparseFeatures,
        LowerCase,
        NGramsFeaturizer,
        TermFrequency,
        Tokenizer,
        Trim,
    )
    from keystone_tpu.nodes.util import MaxClassifier

    train, test, classes = NewsgroupsDataLoader.synthetic(
        n=240, num_classes=3
    )

    def build():
        featurizer = (
            Trim()
            .and_then(LowerCase())
            .and_then(Tokenizer())
            .and_then(NGramsFeaturizer(1, 2))
            .and_then(TermFrequency("log"))
            .and_then(CommonSparseFeatures(512), train.data)
        )
        return featurizer.and_then(
            NaiveBayesEstimator(len(classes)), train.data, train.labels
        ).and_then(MaxClassifier())

    ref = _fit_apply(build, test.data, shard=False)
    got = _fit_apply(build, test.data, shard=True)
    np.testing.assert_array_equal(ref, got)


def test_chaos_parity_sharded_fit():
    """The standard chaos plan (io:0.05,oom:1) injected under the SHARDED
    walk: every fault recovers invisibly and the fit/apply stays
    bit-identical to the fault-free sharded run."""
    rng = np.random.default_rng(1)
    X = rng.normal(size=(518, 32)).astype(np.float32)
    y = rng.normal(size=(518, 4)).astype(np.float32)
    X_test = rng.normal(size=(70, 32)).astype(np.float32)
    build = lambda: _two_branch_pipeline(X, y)  # noqa: E731
    baseline = _fit_apply(build, X_test, shard=True)
    prior = (config.faults, config.faults_seed)
    try:
        config.faults, config.faults_seed = "io:0.05,oom:1", 0
        chaos = _fit_apply(build, X_test, shard=True)
    finally:
        config.faults, config.faults_seed = prior
    np.testing.assert_array_equal(baseline, chaos)


def test_fitted_forward_with_layout():
    """The functional replay lowered once with explicit shardings is the
    un-jitted replay's to rounding and row-sharded on output."""
    from keystone_tpu.workflow.functional import fitted_forward

    rng = np.random.default_rng(0)
    X = rng.normal(size=(512, 32)).astype(np.float32)
    y = rng.normal(size=(512, 4)).astype(np.float32)
    fitted = _two_branch_pipeline(X, y).fit()
    layout = SpecLayout.for_mesh()
    fn_plain = fitted_forward(fitted, X[:8])
    fn_sharded = fitted_forward(fitted, X[:8], layout=layout)
    Xb = rng.normal(size=(64, 32)).astype(np.float32)
    ref = np.asarray(jax.jit(fn_plain)(Xb))
    out = fn_sharded(layout.put(Xb))
    assert layout_of_array(out) == layout
    assert_same_to_rounding(out, ref)


# ---------------------------------------------------------------------------
# Sharding-safe state: checkpoints, profile store, planner
# ---------------------------------------------------------------------------


def test_stream_checkpoint_mesh_width_refusal_both_ways(tmp_path, monkeypatch):
    # The refuse-only contract under KEYSTONE_ELASTIC_MESH=0 — the
    # default-on elastic migration path is pinned in test_elastic_mesh.py.
    monkeypatch.setattr(config, "elastic_mesh", False)
    from keystone_tpu.linalg.normal_equations import (
        _STREAM_CKPT_KEY,
        _StreamCheckpointer,
        _stream_fingerprint,
    )

    rng = np.random.default_rng(0)
    chunk = (
        rng.normal(size=(64, 8)).astype(np.float32),
        rng.normal(size=(64, 2)).astype(np.float32),
    )
    fp = _stream_fingerprint(chunk)
    assert fp["device_count"] == 8
    assert fp["data_axis"] == config.data_axis

    # Same problem recorded under a DIFFERENT mesh width: typed refusal.
    ck = _StreamCheckpointer(str(tmp_path), checkpoint_every=1)
    narrow = dict(fp, device_count=1)
    ck.store.put(
        _STREAM_CKPT_KEY,
        {"fingerprint": narrow, "chunks_done": 2,
         "gram": np.eye(8), "atb": np.zeros((8, 2))},
        overwrite=True,
    )
    with pytest.raises(MeshMismatchError):
        ck.resume(chunk)

    # Same mesh width: resumes (the refusal never blocks a legal resume).
    ck2 = _StreamCheckpointer(str(tmp_path), checkpoint_every=1)
    ck2.store.put(
        _STREAM_CKPT_KEY,
        {"fingerprint": dict(fp), "chunks_done": 2,
         "gram": np.eye(8), "atb": np.zeros((8, 2))},
        overwrite=True,
    )
    ck2.resume(chunk)
    assert ck2.skip == 2

    # A genuinely different PROBLEM on a different width stays on the
    # warn-and-start-fresh path (no typed refusal).
    ck3 = _StreamCheckpointer(str(tmp_path), checkpoint_every=1)
    other = dict(fp, device_count=1, d=99)
    ck3.store.put(
        _STREAM_CKPT_KEY,
        {"fingerprint": other, "chunks_done": 2,
         "gram": np.eye(8), "atb": np.zeros((8, 2))},
        overwrite=True,
    )
    ck3.resume(chunk)
    assert ck3.skip == 0  # fresh start

    # A PRE-MANIFEST snapshot (no mesh keys) of the same problem still
    # RESUMES after the manifest upgrade: absent keys are wildcards, so
    # the upgrade never silently throws away accumulated progress.
    ck4 = _StreamCheckpointer(str(tmp_path), checkpoint_every=1)
    legacy = {k: v for k, v in fp.items()
              if k not in ("device_count", "data_axis")}
    ck4.store.put(
        _STREAM_CKPT_KEY,
        {"fingerprint": legacy, "chunks_done": 3,
         "gram": np.eye(8), "atb": np.zeros((8, 2))},
        overwrite=True,
    )
    ck4.resume(chunk)
    assert ck4.skip == 3  # legacy resume preserved


def test_bcd_checkpoint_mesh_width_refusal_both_ways(monkeypatch):
    monkeypatch.setattr(config, "elastic_mesh", False)
    from keystone_tpu.linalg.bcd import _refuse_bcd_mesh_mismatch

    fp = {
        "rows": 520, "n": 518, "d": 64, "k": 4, "block_size": 64,
        "lam": 0.001, "weighted": False, "a_dtype": "float32",
        "a_probe": 1.5, "b_probe": 2.5,
        "device_count": 8, "data_axis": "data",
    }
    narrow = dict(fp, device_count=1, rows=518)
    with pytest.raises(MeshMismatchError):
        _refuse_bcd_mesh_mismatch(narrow, fp, "/tmp/ck")
    # Same width: no refusal. Different problem: no refusal (fresh path).
    _refuse_bcd_mesh_mismatch(dict(fp), fp, "/tmp/ck")
    _refuse_bcd_mesh_mismatch(
        dict(narrow, d=128), fp, "/tmp/ck"
    )
    # Pre-manifest snapshots (no mesh claim) never refuse.
    legacy = {k: v for k, v in narrow.items()
              if k not in ("device_count", "data_axis")}
    _refuse_bcd_mesh_mismatch(legacy, fp, "/tmp/ck")


def test_bcd_legacy_fingerprint_still_matches():
    """A pre-manifest BCD fingerprint of the same problem (no mesh keys)
    must still MATCH after the upgrade — mesh_fp_compat backfills the
    absent keys as wildcards, so an epoch checkpoint recorded by the
    previous release resumes instead of silently restarting."""
    from keystone_tpu.linalg.bcd import _fingerprint_matches
    from keystone_tpu.utils.mesh import mesh_fp_compat

    fp = {
        "rows": 520, "n": 518, "d": 64, "k": 4, "block_size": 64,
        "lam": 0.001, "weighted": False, "a_dtype": "float32",
        "a_probe": 1.5, "b_probe": 2.5,
        "device_count": 8, "data_axis": "data",
    }
    legacy = {k: v for k, v in fp.items()
              if k not in ("device_count", "data_axis")}
    assert not _fingerprint_matches(legacy, fp)  # raw: key-set mismatch
    assert _fingerprint_matches(mesh_fp_compat(legacy, fp), fp)
    # Present keys keep their saved values: a REAL width mismatch stays
    # a mismatch after compat.
    narrow = dict(fp, device_count=1)
    assert not _fingerprint_matches(mesh_fp_compat(narrow, fp), fp)


def test_profile_store_device_count_refused_both_ways(tmp_path, monkeypatch):
    monkeypatch.setattr(config, "elastic_mesh", False)
    from keystone_tpu.workflow.profile_store import (
        ProfileFingerprintError,
        load_profile,
        save_profile,
    )

    digest = "d" * 40
    digests = {"abc": {"label": "X", "calls": 1, "wall_ns": 10,
                       "out_bytes": 4, "out_rows": 1,
                       "queue_wait_ns": 0, "out_shape": [1, 1],
                       "data_shards": 1}}
    save_profile(
        digest, digests, [], store_dir=str(tmp_path),
        fingerprint={"backend": "cpu", "device_kind": "cpu",
                     "device_count": 1},
    )
    # A 1-device profile must never size an 8-device plan: refused.
    with pytest.raises(ProfileFingerprintError):
        load_profile(
            digest, store_dir=str(tmp_path),
            fingerprint={"backend": "cpu", "device_kind": "cpu",
                         "device_count": 8},
        )
    # The matching width loads (and carries the shard count per row).
    entry = load_profile(
        digest, store_dir=str(tmp_path),
        fingerprint={"backend": "cpu", "device_kind": "cpu",
                     "device_count": 1},
    )
    assert entry is not None
    assert entry.node("abc")["data_shards"] == 1


def test_profile_rows_carry_data_shards():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(512, 32)).astype(np.float32)
    y = rng.normal(size=(512, 4)).astype(np.float32)
    config.shard_data_batches = True
    PipelineEnv.reset()
    fitted = _two_branch_pipeline(X, y).fit(profile=True)
    rows = fitted.fit_profile.rows
    sharded = [r for r in rows if r.get("data_shards") == 8]
    assert sharded, f"no 8-shard rows in {[r['node'] for r in rows]}"


def test_plan_chunk_rows_prices_per_shard():
    """The planner sizes solver chunks against per-device HBM ÷ shard
    count: on the 8-shard mesh the planned rows are 8x the 1-shard
    sizing for the same measured bytes/row."""
    from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator
    from keystone_tpu.utils.metrics import device_hbm_bytes
    from keystone_tpu.workflow.rules import PlanResourcesRule

    rng = np.random.default_rng(0)
    X = rng.normal(size=(64, 8)).astype(np.float32)
    y = rng.normal(size=(64, 2)).astype(np.float32)
    pipe = BlockLeastSquaresEstimator(block_size=8).with_data(X, y)
    graph, sink = pipe.graph, pipe.sink

    budget = device_hbm_bytes() // PlanResourcesRule.CHUNK_BUDGET_FRAC
    bytes_per_row = float(budget)  # 1 row/shard-free budget: forces a plan
    measured_rows = 10**9

    class FakeMeasured:
        def node(self, digest):
            return {"out_rows": measured_rows,
                    "out_bytes": int(bytes_per_row * measured_rows)}

    plan: dict = {}
    PlanResourcesRule()._plan_chunk_rows(
        graph, [sink], FakeMeasured(), plan
    )
    shards = num_data_shards()
    assert shards == 8
    expected = int(budget // max(1.0, bytes_per_row / shards))
    assert plan["solve_chunk_rows"] == expected
    assert expected == shards  # budget == bytes_per_row → shards rows


# ---------------------------------------------------------------------------
# KG103: the silent-cliff class at lint time
# ---------------------------------------------------------------------------


def test_kg103_flags_never_divisible_batch():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(130, 32)).astype(np.float32)
    y = rng.normal(size=(130, 4)).astype(np.float32)
    pipe = _two_branch_pipeline(X, y)
    report = pipe.lint()
    hits = report.by_rule("KG103")
    assert hits and all(d.severity == "warning" for d in hits)
    assert "130 rows" in hits[0].message


def test_kg103_ignores_estimator_only_datasets():
    """Labels/side inputs consumed solely by estimators never go through
    the fused-chain pad path (RowMatrix re-pads them once regardless), so
    KG103 must not fire on them — only the feature batch warns."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(128, 32)).astype(np.float32)  # divisible
    y = rng.normal(size=(130, 4)).astype(np.float32)   # never divides
    hits = _two_branch_pipeline(X, y[:128]).lint().by_rule("KG103")
    assert not hits  # divisible X, aligned labels: clean
    X_odd = rng.normal(size=(130, 32)).astype(np.float32)
    hits = _two_branch_pipeline(X_odd, y).lint().by_rule("KG103")
    # Only the FEATURE dataset (feeding the jittable branches) fires;
    # the labels dataset (estimator-only consumer) stays silent.
    assert len(hits) == 1


def test_kg103_sees_through_host_stages():
    """A non-divisible batch whose jittable chain sits BEHIND a
    row-preserving host stage still pays the pad on every chain call —
    the traversal must reach through the host node and flag it."""

    class HostPass(Transformer):
        jittable = False

        def signature(self):
            return self.stable_signature()

        def apply_batch(self, X):
            return np.asarray(X) * 1.0

    rng = np.random.default_rng(0)
    X = rng.normal(size=(130, 32)).astype(np.float32)
    y = rng.normal(size=(130, 4)).astype(np.float32)
    from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator

    pipe = HostPass().and_then(MatmulChain(9)).and_then(
        BlockLeastSquaresEstimator(block_size=48, num_iters=1, lam=1e-3),
        X, y,
    )
    assert pipe.lint().by_rule("KG103")


def test_kg103_silent_on_divisible_and_small_batches():
    rng = np.random.default_rng(0)
    for rows in (128, 16):  # divisible; below shard_min_rows
        X = rng.normal(size=(rows, 32)).astype(np.float32)
        y = rng.normal(size=(rows, 4)).astype(np.float32)
        assert not _two_branch_pipeline(X, y).lint().by_rule("KG103")


def test_kg103_in_catalog():
    from keystone_tpu.workflow.analysis import GRAPH_RULES

    assert "KG103" in GRAPH_RULES


# ---------------------------------------------------------------------------
# bench_watch: the fit_multichip family
# ---------------------------------------------------------------------------


def _multichip_row(value, bit_identical=True, rows_per_s=4000.0):
    return {
        "metric": "fit_multichip",
        "value": value,
        "unit": "x rows_per_s scaling (8-device sharded fit / "
                "1-device sharded fit)",
        "backend": "cpu",
        "host_cores": 1,
        "n_devices": 8,
        "detail": {
            "rows_per_s_ndev": rows_per_s,
            "bit_identical": bit_identical,
            "shard_fallbacks": 0,
        },
        "ok": True,
    }


def _bench_watch_run(tmp_path, rows):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_watch_under_test",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "bench_watch.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(tmp_path / "BENCH_fit.json", "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    return mod.run(str(tmp_path))


def test_bench_watch_judges_fit_multichip(tmp_path):
    # Healthy history then a collapse: scaling (value) and rows/s down,
    # bit_identical flipped — all three must be flagged.
    rows = [
        _multichip_row(4.0), _multichip_row(4.2), _multichip_row(3.9),
        _multichip_row(1.0, bit_identical=False, rows_per_s=900.0),
    ]
    result = _bench_watch_run(tmp_path, rows)
    bad = {v["series"] for v in result["regressions"]}
    assert "fit:fit_multichip:value" in bad
    assert "fit:fit_multichip:detail.rows_per_s_ndev" in bad
    assert "fit:fit_multichip:detail.bit_identical" in bad
    assert not result["ok"]


def test_bench_watch_passes_healthy_fit_multichip(tmp_path):
    rows = [_multichip_row(4.0), _multichip_row(4.2), _multichip_row(4.1)]
    result = _bench_watch_run(tmp_path, rows)
    assert result["ok"], result["regressions"]


@pytest.mark.slow
def test_bench_multichip_quick_green():
    """The bench harness end-to-end (two subprocesses, quick scale)."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join("tools", "bench_multichip.py"),
         "--quick"],
        cwd=repo, capture_output=True, text=True, timeout=560,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    assert row["ok"] and row["detail"]["bit_identical"]
    assert row["detail"]["shard_fallbacks"] == 0
    assert row["detail"]["batches_padded"] > 0  # the pad path exercised
