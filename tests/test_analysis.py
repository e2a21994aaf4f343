"""Graph-linter tests (workflow/analysis.py, Layer 1 of keystone-lint).

Every shipped KG rule is pinned both ways: one fixture that must flag it
and one that must stay clean. The canonical fused serving chains (the
test_serving.py head) must lint clean; a RandomPatcher chain must flag
serveability; and the KEYSTONE_LINT gate must refuse at compiled() in
error mode, log-only in warn mode, and stay silent when off.
"""

import logging

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.config import config
from keystone_tpu.nodes.images.patches import RandomPatcher
from keystone_tpu.nodes.learning.linear_mapper import LinearMapper
from keystone_tpu.nodes.stats.hellinger import SignedHellingerMapper
from keystone_tpu.nodes.stats.normalizer import L2Normalizer
from keystone_tpu.nodes.stats.random_features import CosineRandomFeatures
from keystone_tpu.nodes.stats.scalers import StandardScalerModel
from keystone_tpu.workflow import LintError, Pipeline, Transformer
from keystone_tpu.workflow.analysis import GRAPH_RULES, lint_graph
from keystone_tpu.workflow.graph import Graph, fresh_source_id
from keystone_tpu.workflow.operators import GatherOperator, TransformerOperator


@pytest.fixture(autouse=True)
def lint_off():
    """Isolate the process-wide lint/serve knobs per test."""
    prior = (config.lint, config.serve_buckets)
    config.lint = "off"
    yield
    config.lint, config.serve_buckets = prior


def _fused_head(d=8, D=16, k=3, seed=0):
    """The canonical fused serving head from tests/test_serving.py, built
    as a pipeline — the chain the serving engine actually compiles."""
    rng = np.random.default_rng(seed)
    return (
        StandardScalerModel(
            rng.normal(size=d).astype(np.float32),
            (1.0 + rng.uniform(size=d)).astype(np.float32),
        ).to_pipeline()
        .and_then(CosineRandomFeatures.create(d, D, seed=seed))
        .and_then(SignedHellingerMapper())
        .and_then(L2Normalizer())
        .and_then(LinearMapper(rng.normal(size=(D, k)).astype(np.float32)))
    )


class Identity(Transformer):
    def apply_batch(self, X):
        return X


class CastF32(Transformer):
    def apply_batch(self, X):
        return X.astype(jnp.float32)


class HostOnly(Transformer):
    jittable = False

    def apply_batch(self, X):
        return X


# ---------------------------------------------------------------------------
# Serveability rules: KG001 / KG002
# ---------------------------------------------------------------------------


def test_canonical_fused_serving_chain_lints_clean():
    report = _fused_head().lint(example=(8,), serve=True, have_ladder=True)
    assert not report.errors()
    for rule in ("KG001", "KG002"):
        assert not report.by_rule(rule), report.render()


def test_random_patcher_chain_flags_serveability_as_errors():
    bad = RandomPatcher(4, 3).and_then(L2Normalizer())
    report = bad.lint(serve=True, have_ladder=True)
    rules = {d.rule for d in report.errors()}
    assert "KG001" in rules  # not jittable
    assert "KG002" in rules  # row-coupled
    # every serveability diagnostic names the offending node
    assert all("RandomPatcher" in d.node for d in report.errors())


def test_serveability_is_warning_without_serve_intent():
    bad = RandomPatcher(4, 3).and_then(L2Normalizer())
    report = bad.lint(serve=False, have_ladder=True)
    assert not report.errors()
    assert {d.rule for d in report.warnings()} >= {"KG001", "KG002"}


def test_host_transformer_flags_kg001_only():
    report = HostOnly().and_then(L2Normalizer()).lint(
        serve=True, have_ladder=True
    )
    rules = {d.rule for d in report.errors()}
    assert rules == {"KG001"}


def test_gather_join_lints_every_branch():
    """A gather join is serveable (it lowers to one GatherTransformer), so
    the serveability rules walk into each of its branches."""
    gathered = Pipeline.gather([L2Normalizer(), Identity()])
    assert not gathered.lint(serve=True, have_ladder=True).errors()
    host_branch = Pipeline.gather([L2Normalizer(), HostOnly()])
    report = host_branch.lint(serve=True, have_ladder=True)
    assert {d.rule for d in report.errors()} == {"KG001"}
    assert all("HostOnly" in d.node for d in report.errors())


# ---------------------------------------------------------------------------
# KG101 recompile hazard
# ---------------------------------------------------------------------------


def test_kg101_polymorphic_without_ladder_flags():
    p = L2Normalizer().and_then(Identity())
    report = p.lint()  # no example: polymorphic traffic, no ladder
    assert report.by_rule("KG101")
    assert report.by_rule("KG101")[0].severity == "warning"


def test_kg101_suppressed_by_ladder_or_concrete_batch():
    p = L2Normalizer().and_then(Identity())
    assert not p.lint(have_ladder=True).by_rule("KG101")
    # a concrete sample batch is not polymorphic traffic
    assert not p.lint(
        example=np.zeros((4, 8), np.float32)
    ).by_rule("KG101")
    # config.serve_buckets counts as a ladder
    config.serve_buckets = (8, 64)
    assert not p.lint().by_rule("KG101")


# ---------------------------------------------------------------------------
# KG102 dtype seams (abstract shape/dtype propagation)
# ---------------------------------------------------------------------------


def test_kg102_silent_upcast_flagged_with_node_and_dtypes():
    p = CastF32().and_then(L2Normalizer())
    report = p.lint(example=np.zeros((4, 8), np.float16), have_ladder=True)
    seams = report.by_rule("KG102")
    assert len(seams) == 1
    assert "float16" in seams[0].message and "float32" in seams[0].message
    assert "CastF32" in seams[0].node


def test_kg102_clean_on_dtype_preserving_chain():
    report = _fused_head().lint(
        example=np.zeros((4, 8), np.float32), have_ladder=True
    )
    assert not report.by_rule("KG102"), report.render()


def test_kg102_mixed_dtype_gather():
    gathered = Pipeline.gather([Identity(), CastF32()])
    report = gathered.lint(example=np.zeros((4, 8), np.float16))
    seams = report.by_rule("KG102")
    # the branch upcast itself is one seam; the mixed-dtype join another
    assert any("gather" in d.message.lower() for d in seams), report.render()


# ---------------------------------------------------------------------------
# KG201 dead nodes / KG202 cache advice
# ---------------------------------------------------------------------------


def _shared_prefix_graph(cache_after_prefix=False):
    src = fresh_source_id()
    g, prefix = Graph().add(TransformerOperator(L2Normalizer()), [src])
    tail_src = prefix
    if cache_after_prefix:
        from keystone_tpu.workflow.cache import CacheOperator

        g, tail_src = g.add(CacheOperator(), [prefix])
    g, b1 = g.add(TransformerOperator(SignedHellingerMapper()), [tail_src])
    g, b2 = g.add(TransformerOperator(Identity()), [tail_src])
    g, out = g.add(GatherOperator(), [b1, b2])
    return Pipeline(g, src, out)


def test_kg201_dead_node_flagged_and_pruned_graph_clean():
    src = fresh_source_id()
    g, live = Graph().add(TransformerOperator(L2Normalizer()), [src])
    g, _orphan = g.add(TransformerOperator(Identity()), [src])
    report = Pipeline(g, src, live).lint(example=(8,), have_ladder=True)
    assert report.by_rule("KG201")
    pruned = Pipeline(g.pruned([live]), src, live)
    assert not pruned.lint(example=(8,), have_ladder=True).by_rule("KG201")


def test_kg202_shared_subchain_advice_and_cache_satisfies_it():
    report = _shared_prefix_graph().lint(example=(8,), have_ladder=True)
    advice = report.by_rule("KG202")
    assert advice and advice[0].severity == "info"
    assert "L2Normalizer" in advice[0].node
    cached = _shared_prefix_graph(cache_after_prefix=True)
    assert not cached.lint(
        example=(8,), have_ladder=True
    ).by_rule("KG202")


# ---------------------------------------------------------------------------
# API robustness + catalog
# ---------------------------------------------------------------------------


def test_lint_never_executes_and_survives_unfitted_estimators():
    from keystone_tpu.workflow import LabelEstimator

    class Boom(LabelEstimator):
        def fit(self, X, y):  # would explode if lint executed the graph
            raise AssertionError("lint must not fit")

    X = np.zeros((4, 8), np.float32)
    y = np.zeros((4, 3), np.float32)
    p = L2Normalizer().and_then(Boom(), X, y)
    report = p.lint(example=(8,), have_ladder=True)
    assert isinstance(report.render(), str)  # completed without executing


def test_rule_catalog_covers_every_emitted_rule():
    fixtures = [
        RandomPatcher(4, 3).and_then(L2Normalizer()).lint(serve=True),
        Pipeline.gather([Identity(), CastF32()]).lint(
            example=np.zeros((4, 8), np.float16)
        ),
        L2Normalizer().and_then(Identity()).lint(),
        _shared_prefix_graph().lint(example=(8,), have_ladder=True),
    ]
    emitted = {d.rule for rep in fixtures for d in rep}
    assert emitted <= set(GRAPH_RULES)
    assert {"KG001", "KG002", "KG101", "KG102", "KG202"} <= emitted


def test_lint_graph_matches_pipeline_lint():
    p = _fused_head()
    direct = lint_graph(p.graph, p.source, p.sink, example=(8,),
                        serve=True, have_ladder=True)
    assert direct.as_dicts() == p.lint(
        example=(8,), serve=True, have_ladder=True
    ).as_dicts()


# ---------------------------------------------------------------------------
# The KEYSTONE_LINT gate
# ---------------------------------------------------------------------------


def test_gate_error_mode_refuses_unserveable_compiled():
    config.lint = "error"
    bad = RandomPatcher(4, 3).and_then(L2Normalizer())
    with pytest.raises(LintError, match="KG00"):
        bad.compiled()


def test_gate_error_mode_passes_clean_chain():
    config.lint = "error"
    cp = _fused_head().compiled(buckets=(4, 8), devices=1)
    assert cp.ladder == (4, 8)


def test_gate_warn_mode_logs_but_never_blocks(caplog):
    config.lint = "warn"
    bad = RandomPatcher(4, 3).and_then(L2Normalizer())
    with caplog.at_level(logging.ERROR, logger="keystone_tpu"):
        with pytest.raises(Exception) as ei:
            bad.compiled()  # the RUNTIME refusal still fires downstream
    assert not isinstance(ei.value, LintError)
    assert any("KG00" in r.message for r in caplog.records)


def test_gate_off_is_silent(caplog):
    config.lint = "off"
    with caplog.at_level(logging.INFO, logger="keystone_tpu"):
        _fused_head().fit()
    assert not any("lint[" in r.message for r in caplog.records)


def test_gate_fit_runs_lint_in_warn_mode(caplog):
    config.lint = "warn"
    with caplog.at_level(logging.WARNING, logger="keystone_tpu"):
        # polymorphic + no ladder: the fit gate logs KG101 as a warning
        L2Normalizer().and_then(Identity()).fit()
    assert any("KG101" in r.message for r in caplog.records)


# ---------------------------------------------------------------------------
# The in-process demo (the `make lint` graph half, like make trace-demo)
# ---------------------------------------------------------------------------


def test_lint_report_demo_in_process():
    import importlib
    import os
    import sys

    tools = os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools")
    sys.path.insert(0, tools)
    try:
        lint_report = importlib.import_module("lint_report")
        verdict = lint_report.run_graph_demo()
    finally:
        sys.path.remove(tools)
    assert verdict["canonical_clean"], verdict
    assert verdict["control_refused"], verdict
    assert "KG002" in verdict["control_rules"]


# ---------------------------------------------------------------------------
# KG104: pinned memory plan priced beyond the HBM budget (shape-only)
# ---------------------------------------------------------------------------


def test_kg104_flags_over_budget_pinned_ladder(monkeypatch):
    """A pinned serve ladder whose priced residency (ladder x replicas x
    dtype) exceeds the ladder budget share is flagged statically — no
    execution, no compile, no device work (the Boom-estimator test above
    already pins that lint never executes)."""
    config.serve_buckets = (1024,)
    monkeypatch.setattr(config, "hbm_budget_bytes", 50_000)
    hits = _fused_head().lint(example=(8,), have_ladder=True).by_rule(
        "KG104"
    )
    assert hits and hits[0].severity == "warning"
    assert "serve ladder" in hits[0].message
    assert "1024" in hits[0].message
    assert "KEYSTONE_SERVE_BUCKETS" in hits[0].hint


def test_kg104_silent_on_in_budget_plans():
    """The other way: an in-budget pinned ladder — and the unpinned
    default (no ladder configured at all) — stay silent."""
    p = _fused_head()
    assert not p.lint(example=(8,), have_ladder=True).by_rule("KG104")
    config.serve_buckets = (8, 64)  # tiny ladder, default 12 GiB budget
    assert not p.lint(example=(8,), have_ladder=True).by_rule("KG104")


def test_kg104_flags_over_budget_pinned_solve_chunk(monkeypatch):
    from keystone_tpu.nodes.learning import LinearMapEstimator

    X = np.zeros((4, 8), np.float32)
    y = np.zeros((4, 3), np.float32)
    p = L2Normalizer().and_then(LinearMapEstimator(lam=1e-3), X, y)
    monkeypatch.setattr(config, "solve_chunk_rows", 1 << 22)
    monkeypatch.setattr(config, "hbm_budget_bytes", 1 << 20)
    hits = p.lint(example=(8,), have_ladder=True).by_rule("KG104")
    assert hits and hits[0].severity == "warning"
    assert "solve chunk" in hits[0].message
    assert "OOM-halving" in hits[0].message
    # Unpinned chunk (the planner's to size): silent under any budget.
    monkeypatch.setattr(config, "solve_chunk_rows", 0)
    assert not p.lint(example=(8,), have_ladder=True).by_rule("KG104")


def test_kg104_env_pin_reads_live(monkeypatch):
    """The env-pins live-read convention: an exported
    KEYSTONE_SOLVE_CHUNK_ROWS=0 retires a programmatic pin, and an
    exported value prices instead of the config snapshot."""
    from keystone_tpu.nodes.learning import LinearMapEstimator

    X = np.zeros((4, 8), np.float32)
    y = np.zeros((4, 3), np.float32)
    p = L2Normalizer().and_then(LinearMapEstimator(lam=1e-3), X, y)
    monkeypatch.setattr(config, "solve_chunk_rows", 1 << 22)
    monkeypatch.setattr(config, "hbm_budget_bytes", 1 << 20)
    monkeypatch.setenv("KEYSTONE_SOLVE_CHUNK_ROWS", "0")
    assert not p.lint(example=(8,), have_ladder=True).by_rule("KG104")
    monkeypatch.setenv("KEYSTONE_SOLVE_CHUNK_ROWS", str(1 << 22))
    monkeypatch.setattr(config, "solve_chunk_rows", 0)
    assert p.lint(example=(8,), have_ladder=True).by_rule("KG104")


# ---------------------------------------------------------------------------
# KG105 — refit_stream head without partial_fit (ISSUE-15)
# ---------------------------------------------------------------------------


def _refit_pipeline(head):
    X = np.zeros((8, 8), np.float32)
    y = np.zeros((8, 3), np.float32)
    return L2Normalizer().and_then(head, X, y)


def test_kg105_flags_batch_only_head_under_refit():
    from keystone_tpu.workflow import LabelEstimator

    class BatchOnlyHead(LabelEstimator):
        def fit(self, X, y):
            return LinearMapper(np.zeros((8, 3), np.float32))

    hits = _refit_pipeline(BatchOnlyHead()).lint(
        example=(8,), have_ladder=True, refit=True
    ).by_rule("KG105")
    assert hits and hits[0].severity == "warning"
    assert "partial_fit" in hits[0].message
    assert "FULL head refit" in hits[0].message
    assert "BatchOnlyHead" in hits[0].node


def test_kg105_silent_on_online_head_and_without_refit():
    from keystone_tpu.nodes.learning import LinearMapEstimator
    from keystone_tpu.workflow import LabelEstimator

    # The whole normal-equation family implements the contract.
    p = _refit_pipeline(LinearMapEstimator(lam=1e-3))
    assert not p.lint(example=(8,), have_ladder=True,
                      refit=True).by_rule("KG105")

    class BatchOnlyHead(LabelEstimator):
        def fit(self, X, y):
            return LinearMapper(np.zeros((8, 3), np.float32))

    # A batch-only head is a fine BATCH pipeline: silent unless the
    # refit contract is requested.
    assert not _refit_pipeline(BatchOnlyHead()).lint(
        example=(8,), have_ladder=True
    ).by_rule("KG105")


def test_kg105_weighted_block_head_flags():
    """BlockWeighted nulls the online contract (per-batch folds cannot
    know the full class counts) — the lint must see that, not just a
    missing attribute."""
    from keystone_tpu.nodes.learning.block_least_squares import (
        BlockWeightedLeastSquaresEstimator,
    )

    hits = _refit_pipeline(
        BlockWeightedLeastSquaresEstimator(lam=1e-3)
    ).lint(example=(8,), have_ladder=True, refit=True).by_rule("KG105")
    assert hits and "BlockWeightedLeastSquaresEstimator" in hits[0].node
