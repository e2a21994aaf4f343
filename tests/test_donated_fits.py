"""Fused & donated fits (the ISSUE-16 tentpole).

Covers the donation contract end to end:

1. **Invisibility** — a donated fused fit is bit-identical to the same
   fit with ``KEYSTONE_DONATE_BUFFERS=0`` (donation changes WHERE the
   output lives, never what it is), including through the Pallas
   Fisher-vector sharded path.
2. **The buffers** — only staging copies ``_sharded_call`` itself
   creates are donated: the staged buffer is provably dead afterwards
   (deleted-buffer error pinned), while caller-owned arrays — host
   batches and mesh-placed ``jax.Array`` inputs — stay readable.
3. **Refusal is counted, never silent** — XLA aliases donated buffers
   to outputs by exact aval, so shrinking/growing chains refuse up
   front and bump ``donation_refused``.
4. **The memory win** — per-device working set (argument + output +
   temp − alias, the PR-8 ``memory_analysis`` attribution) of the
   donated lowering sits strictly below the undonated one. This is the
   CPU-portable form of the peak-HBM gate ``bench_imagenet`` enforces
   on real hardware.
5. **KG106** — a fused sharded fit whose accumulator-carrying chain
   lowers WITHOUT donation (mesh-placed caller-owned input) warns while
   ``config.donate_buffers`` promises one live copy; pinned both ways.
"""

import numpy as np
import pytest
from conftest import assert_same_to_rounding

import jax
import jax.numpy as jnp

from keystone_tpu.config import Config, config
from keystone_tpu.utils.mesh import SpecLayout, batch_layout
from keystone_tpu.utils.metrics import _memory_analysis, sharding_counters
from keystone_tpu.workflow.executor import PipelineEnv
from keystone_tpu.workflow.pipeline import Transformer


@pytest.fixture(autouse=True)
def _fresh_donation_state():
    """Counters and the shard/donate toggles restored around every test."""
    prior = (config.shard_data_batches, config.donate_buffers)
    sharding_counters.reset()
    PipelineEnv.reset()
    yield
    config.shard_data_batches, config.donate_buffers = prior
    sharding_counters.reset()
    PipelineEnv.reset()


class SquareChain(Transformer):
    """Shape-preserving jittable chain: its output aval matches its
    input aval, so the staged buffer can alias into the output."""

    def __init__(self, seed: int = 0, d: int = 32):
        self.seed, self.d = int(seed), int(d)
        rng = np.random.default_rng(self.seed)
        self._W = jnp.asarray(rng.normal(size=(d, d)).astype(np.float32))

    def signature(self):
        return self.stable_signature(self.seed, self.d)

    def apply_batch(self, X):
        return jnp.tanh(X @ self._W) + 0.25 * X


class ShrinkChain(Transformer):
    """32 → 16 columns: no output aval can alias the donated input."""

    def __init__(self, seed: int = 0, d_in: int = 32, d_out: int = 16):
        self.seed = int(seed)
        rng = np.random.default_rng(self.seed)
        self._W = jnp.asarray(
            rng.normal(size=(d_in, d_out)).astype(np.float32)
        )

    def signature(self):
        return self.stable_signature(self.seed)

    def apply_batch(self, X):
        return jnp.tanh(X @ self._W)


class HostPass(Transformer):
    """Row-preserving host stage: whatever follows it receives a HOST
    batch and must stage (and may donate) its own copy."""

    jittable = False

    def signature(self):
        return self.stable_signature()

    def apply_batch(self, X):
        return np.asarray(X) * 1.0


def _host_staged_fit(donate: bool, rows: int = 128):
    """Fit the host-arrival chain (HostPass → SquareChain → BlockLS):
    the jittable stage's input arrives host-side, so every chain call
    stages its own copy — the flagship ImageNet shape, where SIFT/LCS
    run on the host and the fused jittable tail stages."""
    from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator

    rng = np.random.default_rng(7)
    X = rng.normal(size=(rows, 32)).astype(np.float32)
    y = rng.normal(size=(rows, 4)).astype(np.float32)
    X_test = rng.normal(size=(rows, 32)).astype(np.float32)
    PipelineEnv.reset()
    config.shard_data_batches = True
    config.donate_buffers = donate
    pipe = HostPass().and_then(SquareChain(3)).and_then(
        BlockLeastSquaresEstimator(block_size=64, num_iters=1, lam=1e-3),
        X, y,
    )
    fitted = pipe.fit()
    preds = np.asarray(fitted.apply(X_test).get())
    return preds, sharding_counters.snapshot()


# ---------------------------------------------------------------------------
# Donation is invisible: bit-identical fits either way
# ---------------------------------------------------------------------------


def test_donated_fit_bit_identical_to_undonated_walk():
    donated, c_on = _host_staged_fit(donate=True)
    sharding_counters.reset()
    undonated, c_off = _host_staged_fit(donate=False)
    assert donated.tobytes() == undonated.tobytes()
    # The donate-on fit actually donated (shape-preserving chain over a
    # staged host arrival), and the knob fully disarms the path.
    assert c_on.get("buffers_donated", 0) > 0
    assert c_off.get("buffers_donated", 0) == 0
    assert c_off.get("donation_refused", 0) == 0


def test_donated_fit_bit_identical_to_single_device_walk():
    """Sharded + donated is the plain unsharded jitted walk to rounding
    (``assert_same_to_rounding``: a shard's rows a product where the walk
    has all of them); donated against undonated on the same mesh stays
    byte for byte, above."""
    donated, _ = _host_staged_fit(donate=True)
    sharding_counters.reset()
    PipelineEnv.reset()
    from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator

    rng = np.random.default_rng(7)
    X = rng.normal(size=(128, 32)).astype(np.float32)
    y = rng.normal(size=(128, 4)).astype(np.float32)
    X_test = rng.normal(size=(128, 32)).astype(np.float32)
    config.shard_data_batches = False
    pipe = HostPass().and_then(SquareChain(3)).and_then(
        BlockLeastSquaresEstimator(block_size=64, num_iters=1, lam=1e-3),
        X, y,
    )
    plain = np.asarray(pipe.fit().apply(X_test).get())
    assert_same_to_rounding(donated, plain)


# ---------------------------------------------------------------------------
# The donated buffer: staged copies die, caller-owned arrays survive
# ---------------------------------------------------------------------------


def test_donated_staging_buffer_is_deleted_after_call():
    """The staged copy is consumed by the donated lowering — XLA reuses
    its memory for the output, and any later read is the canonical
    deleted-buffer RuntimeError. This pins the failure mode the README
    documents (and proves donation really happened: an undonated call
    leaves the buffer readable)."""
    config.shard_data_batches = True
    config.donate_buffers = True
    chain = SquareChain(1)
    X = np.random.default_rng(0).normal(size=(128, 32)).astype(np.float32)
    layout = batch_layout(X)
    assert layout is not None
    staged = layout.put(X)
    chain._staged_call(staged, layout)
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(staged)
    # Control: with the knob off the same staged call leaves it live.
    config.donate_buffers = False
    chain2 = SquareChain(2)
    staged2 = layout.put(X)
    chain2._staged_call(staged2, layout)
    np.testing.assert_array_equal(np.asarray(staged2), X)


def test_caller_owned_buffers_never_donated():
    """Host batches and mesh-placed jax.Arrays are caller-owned (either
    can be multi-consumer via gather / the by-hash memo): the chain must
    leave both readable after the call."""
    config.shard_data_batches = True
    config.donate_buffers = True
    chain = SquareChain(1)
    X = np.random.default_rng(0).normal(size=(128, 32)).astype(np.float32)
    out_host = np.asarray(chain.batch_call(X))
    np.testing.assert_array_equal(X, X)  # host input untouched
    layout = batch_layout(X)
    placed = layout.put(X)
    before = sharding_counters.snapshot().get("buffers_donated", 0)
    out_dev = np.asarray(chain.batch_call(placed))
    # The placed input went through the caller-owned branch: readable
    # afterwards, and no donation was even decided for it.
    np.testing.assert_array_equal(np.asarray(placed), X)
    after = sharding_counters.snapshot().get("buffers_donated", 0)
    assert after == before
    assert out_host.tobytes() == out_dev.tobytes()


def test_shrinking_chain_refuses_donation_counted():
    """No output aval matches the staged input → donation is refused up
    front (XLA would warn and no-op), counted, and the result is still
    bit-identical to the plain walk."""
    config.shard_data_batches = True
    config.donate_buffers = True
    chain = ShrinkChain(5)
    X = np.random.default_rng(1).normal(size=(128, 32)).astype(np.float32)
    layout = batch_layout(X)
    assert not chain._donation_eligible(layout.put(X), layout)
    out = np.asarray(chain.batch_call(X))
    c = sharding_counters.snapshot()
    assert c.get("donation_refused", 0) >= 1
    assert c.get("buffers_donated", 0) == 0
    ref = np.asarray(jax.jit(chain.apply_batch)(X))
    assert out.tobytes() == ref.tobytes()


# ---------------------------------------------------------------------------
# The memory win: donated working set strictly below undonated
# ---------------------------------------------------------------------------


def test_donated_working_set_strictly_below_undonated():
    """Per-node resource attribution (PR-8 ``memory_analysis``): the
    donated lowering aliases the staged argument into the output, so
    its working set (argument + output + temp − alias) sits strictly
    below the undonated lowering's. The proof chain is elementwise so
    the alias is the whole story on every backend (a matmul chain needs
    the same scratch either way on CPU and the two working sets tie).
    On real hardware `make bench-imagenet` additionally gates live peak
    HBM; this is the backend-portable form of the same evidence."""

    class ElemChain(Transformer):
        def signature(self):
            return self.stable_signature()

        def apply_batch(self, X):
            return jnp.tanh(X) * 2.0 + 0.5

    config.shard_data_batches = True
    config.donate_buffers = True
    chain = ElemChain()
    X = np.random.default_rng(2).normal(size=(128, 32)).astype(np.float32)
    layout = batch_layout(X)
    staged = layout.put(X)

    def working_set(donate: bool) -> float:
        fn = chain._jitted_sharded(layout, donate=donate)
        mem = _memory_analysis(fn.lower(staged).compile())
        alias = mem.get("alias_bytes", 0.0)
        if donate:
            assert alias > 0.0  # the argument really aliases
        else:
            assert alias == 0.0
        return (
            mem.get("argument_bytes", 0.0)
            + mem.get("output_bytes", 0.0)
            + mem.get("temp_bytes", 0.0)
            - alias
        )

    assert working_set(True) < working_set(False)


# ---------------------------------------------------------------------------
# The knob
# ---------------------------------------------------------------------------


def test_donate_buffers_env_knob_resolution(monkeypatch):
    for raw, expect in (
        ("0", False), ("false", False), ("no", False), ("FALSE", False),
        ("", True), ("1", True), ("yes", True), ("on", True),
    ):
        if raw:
            monkeypatch.setenv("KEYSTONE_DONATE_BUFFERS", raw)
        else:
            monkeypatch.delenv("KEYSTONE_DONATE_BUFFERS", raising=False)
        assert Config().donate_buffers is expect, raw


# ---------------------------------------------------------------------------
# Pallas Fisher vectors on the sharded path
# ---------------------------------------------------------------------------


def _gmm(k: int = 4, d: int = 8):
    rng = np.random.default_rng(9)
    w = rng.uniform(0.5, 1.5, size=k).astype(np.float32)
    w /= w.sum()
    mu = rng.normal(size=(k, d)).astype(np.float32)
    var = (0.5 + rng.uniform(size=(k, d))).astype(np.float32)
    return w, mu, var


def test_pallas_fv_sharded_bit_identical_and_counted():
    """The Pallas Fisher-vector chain on the sharded path matches the
    single-device jitted walk byte for byte, and its activity is
    counter-verified (``pallas_sharded_calls``) — the bench's
    zero-silent-fallback evidence at test scale."""
    from keystone_tpu.nodes.images.external.fisher_vector import FisherVector

    config.shard_data_batches = True
    config.donate_buffers = True
    w, mu, var = _gmm()
    fv = FisherVector(w, mu, var, backend="pallas")
    assert fv.uses_pallas
    rng = np.random.default_rng(3)
    X = rng.normal(size=(128, 16, 8)).astype(np.float32)
    sharded = np.asarray(fv.batch_call(X))
    c = sharding_counters.snapshot()
    assert c.get("pallas_sharded_calls", 0) >= 1
    assert c.get("sharded_chain_calls", 0) >= 1
    # The single-device walk is the same program unsharded, the mixture
    # its argument (a closure that folds it in as constants rounds the
    # last bit otherwise).
    config.shard_data_batches = False
    plain = np.asarray(fv.batch_call(X))
    assert sharded.tobytes() == plain.tobytes()
    np.testing.assert_allclose(
        plain, np.asarray(jax.jit(fv.apply_batch)(X)), rtol=1e-6, atol=1e-6)
    # FV widens (B, m, d) → (B, 2kd): its donation is refused, counted.
    assert c.get("donation_refused", 0) >= 1


# ---------------------------------------------------------------------------
# KG106: fused sharded fit lowering without donation
# ---------------------------------------------------------------------------


def _placed_fit_pipeline(rows: int = 128):
    """Divisible dataset (the "shard" class: DatasetOperator places it
    onto the mesh) feeding an estimator through a jittable chain — the
    fused fit's input arrives caller-owned, so its lowering cannot
    donate."""
    from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator

    rng = np.random.default_rng(11)
    X = rng.normal(size=(rows, 32)).astype(np.float32)
    y = rng.normal(size=(rows, 4)).astype(np.float32)
    return SquareChain(6).and_then(
        BlockLeastSquaresEstimator(block_size=64, num_iters=1, lam=1e-3),
        X, y,
    )


def test_kg106_flags_undonated_placed_fit_chain():
    config.shard_data_batches = True
    config.donate_buffers = True
    hits = _placed_fit_pipeline().lint().by_rule("KG106")
    assert hits and all(d.severity == "warning" for d in hits)
    assert "WITHOUT donation" in hits[0].message
    assert "KEYSTONE_DONATE_BUFFERS=0" in hits[0].hint


def test_kg106_silent_when_donation_off_or_chain_not_jittable():
    config.shard_data_batches = True
    config.donate_buffers = False
    assert not _placed_fit_pipeline().lint().by_rule("KG106")

    config.donate_buffers = True
    from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator

    rng = np.random.default_rng(11)
    X = rng.normal(size=(128, 32)).astype(np.float32)
    y = rng.normal(size=(128, 4)).astype(np.float32)
    # No jittable stage between dataset and estimator: RowMatrix stages
    # the solve itself, nothing lowers a fused undonated chain.
    host_only = HostPass().and_then(
        BlockLeastSquaresEstimator(block_size=64, num_iters=1, lam=1e-3),
        X, y,
    )
    assert not host_only.lint().by_rule("KG106")


def test_kg106_silent_on_pad_class_rows():
    """Non-divisible rows are the "pad" class: the chain call stages its
    own mask-padded copy and donates it — KG103's territory, not
    KG106's."""
    config.shard_data_batches = True
    config.donate_buffers = True
    report = _placed_fit_pipeline(rows=130).lint()
    assert not report.by_rule("KG106")
    assert report.by_rule("KG103")  # still flagged, as the pad cliff


def test_kg106_in_catalog():
    from keystone_tpu.workflow.analysis import GRAPH_RULES

    assert "KG106" in GRAPH_RULES
