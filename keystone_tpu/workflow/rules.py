"""Whole-pipeline optimization rules: auto-caching, node-level solver
selection, and profile-guided resource planning.

Ref: src/main/scala/workflow/{AutoCacheRule,NodeOptimizationRule}.scala
(SURVEY.md §2.1, §3.5) [unverified].

Cost provenance, in preference order (the closed cost-model loop):

1. **measured** — per-node wall/bytes/shape rows recorded by a prior
   ``Pipeline.fit(profile=True)`` and persisted in the profile store
   (workflow/profile_store.py), matched back to graph nodes by
   content-stable prefix digest. On a store hit the rules run ZERO
   sample executions.
2. **sampled** — the 64-row sample-run ``Profiler`` extrapolation
   (with the compiled-FLOPs non-linearity correction).
3. **model** — the abstract ``node_cost_analysis`` AOT estimate, where
   neither of the above exists.

Every choice is appended to the process-wide decision log
(``optimizer_decisions()``), which ``tools/profile_report.py
--decisions`` renders — the optimizer explains itself.
"""

from __future__ import annotations

import logging
import threading
import weakref
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from keystone_tpu.config import config
from keystone_tpu.workflow.cache import CacheOperator, NodeProfile, Profiler
from keystone_tpu.workflow.graph import Graph, GraphId, NodeId
from keystone_tpu.workflow.operators import (
    DatasetOperator,
    DelegatingOperator,
    EstimatorOperator,
    GatherOperator,
    TransformerOperator,
)
from keystone_tpu.workflow.optimizer import Rule, active_profile_key

logger = logging.getLogger(__name__)

# ---------------------------------------------------------------------------
# Decision log — how the optimizer explains itself
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class OptimizerDecision:
    """One recorded optimizer choice: which rule, on which node, what it
    did, from which cost provenance, and why."""

    rule: str
    node: str
    action: str        # e.g. "cache-insert", "cache-skip", "solver=...",
                       # "exec_workers=4", "solve_chunk_rows=8192"
    provenance: str    # "measured" | "sampled" | "model"
    reason: str
    cost: Dict[str, Any] = field(default_factory=dict)

    def as_dict(self) -> dict:
        return {
            "rule": self.rule,
            "node": self.node,
            "action": self.action,
            "provenance": self.provenance,
            "reason": self.reason,
            "cost": dict(self.cost),
        }


#: Bounded process-wide decision ring (newest kept): repeated optimizer
#: passes over hot pipelines must not grow memory.
_DECISIONS_CAP = 256
_decisions_lock = threading.Lock()
_decisions: List[OptimizerDecision] = []


def record_decision(
    rule: str, node: str, action: str, provenance: str, reason: str,
    cost: Optional[Dict[str, Any]] = None,
) -> None:
    d = OptimizerDecision(rule, node, action, provenance, reason, cost or {})
    with _decisions_lock:
        _decisions.append(d)
        if len(_decisions) > _DECISIONS_CAP:
            del _decisions[: len(_decisions) - _DECISIONS_CAP]


def optimizer_decisions() -> List[OptimizerDecision]:
    """The recorded decisions, oldest first (bounded ring)."""
    with _decisions_lock:
        return list(_decisions)


def clear_decisions() -> None:
    with _decisions_lock:
        _decisions.clear()


def _measured_profile():
    """The stored measured profile for the pipeline currently being
    optimized, or None (no store / no key / no entry / incompatible
    fingerprint — the latter logged by lookup_measured)."""
    key = active_profile_key()
    if key is None:
        return None
    from keystone_tpu.workflow.profile_store import lookup_measured

    return lookup_measured(key)


def _scaled_shape(value, scale: float):
    """Full-size shape estimate from a row-sampled value: axis 0 scales by
    the sample's row ratio, trailing dims are exact."""
    shape = getattr(value, "shape", None)
    if shape is None or len(shape) == 0:
        return None
    if scale == 1.0:
        return tuple(shape)
    return (int(round(shape[0] * scale)),) + tuple(shape[1:])


def _gathered_branches(graph: Graph, gid, consumers):
    """(the one input, each branch's transformers in order) of a gather
    node that nothing else reads and whose branches are chains of
    transformer nodes over one input, each read by the next alone; None
    for anything else."""
    if len(consumers.get(gid, [])) != 1:
        return None
    if not isinstance(graph.operators.get(gid), GatherOperator):
        return None
    source, chains = None, []
    for tail in graph.dependencies[gid]:
        chain, nid = [], tail
        while isinstance(graph.operators.get(nid), TransformerOperator):
            if len(consumers.get(nid, [])) != 1:
                return None
            chain.append(graph.operators[nid].transformer)
            (nid,) = graph.dependencies[nid]
        if not chain or source not in (None, nid):
            return None
        source = nid
        chains.append(chain[::-1])
    return source, chains


class GeneratedBlocksRule(Rule):
    """``gather(branches) → BlockLeastSquaresEstimator`` lowered to the
    generated-block solve, where every branch is one chain of the same
    transformers that differ only in the arrays they name, the estimator's
    block is a whole number of branches, and the fit's input is a bound
    batch: the branches stacked into one ``BranchStack``, the estimator fit
    on the branches' input as a ``BlockSource`` (each block made from it
    inside the solve), and every application of the fitted map handed the
    input too, where it applies the same branches. The gathered features
    never exist. Any other gather keeps its path."""

    def apply(self, graph: Graph, targets: Sequence[GraphId]) -> Graph:
        import jax

        from keystone_tpu.nodes.learning.block_least_squares import (
            BlockLeastSquaresEstimator,
        )
        from keystone_tpu.workflow.pipeline import BranchStack

        consumers = graph.consumers(targets)
        ops, dps = dict(graph.operators), dict(graph.dependencies)
        for nid in graph.reachable(targets):
            op = graph.operators[nid]
            if not (isinstance(op, EstimatorOperator)
                    and isinstance(op.estimator, BlockLeastSquaresEstimator)
                    and op.estimator.generates_blocks()
                    and len(graph.dependencies[nid]) == 2):
                continue
            features, labels = graph.dependencies[nid]
            found = _gathered_branches(graph, features, consumers)
            data = graph.operators.get(found[0]) if found else None
            if not isinstance(data, DatasetOperator) or np.ndim(data.data) != 2:
                continue
            applied = []
            for user in consumers.get(nid, []):
                if not isinstance(graph.operators[user], DelegatingOperator):
                    break
                est_dep, x_dep = graph.dependencies[user]
                there = _gathered_branches(graph, x_dep, consumers)
                if (est_dep != nid or there is None
                        or not _same_objects(there[1], found[1])):
                    break
                applied.append((user, there[0]))
            else:
                x = jax.ShapeDtypeStruct((1, np.shape(data.data)[1]),
                                         np.asarray(data.data[:1]).dtype)
                stack = BranchStack.of(found[1], op.estimator.block_size, x)
                if stack is None:
                    continue
                ops[nid] = EstimatorOperator(op.estimator.generating(
                    stack, stack.branches * stack.width))
                dps[nid] = (found[0], labels)
                for user, x_dep in applied:
                    dps[user] = (nid, x_dep)
        if ops == graph.operators and dps == graph.dependencies:
            return graph
        return Graph(ops, dps).pruned(list(targets))


def _same_objects(a, b) -> bool:
    return len(a) == len(b) and all(
        len(x) == len(y) and all(s is t for s, t in zip(x, y))
        for x, y in zip(a, b))


class NodeOptimizationRule(Rule):
    """Swap optimizable estimators for concrete implementations chosen from
    data statistics at optimization time.

    An estimator opts in by defining ``optimize_node(self, data_shape) ->
    estimator``. Shapes are read from directly-attached dataset nodes when
    available (the simple with_data case); estimators fed by deeper
    transformer subgraphs get their (n, d) from the MEASURED output shapes
    of a stored profile when one matches (exact full-size shapes, zero
    executions), else from ONE sampled prefix run per apply (the
    reference's optimizer profiles sampled prefixes for stats anywhere in
    the DAG — SURVEY.md §3.5), so cost-model dispatch happens at
    optimization time, not fit time.

    The concrete replacement is memoized per (estimator, shapes): every
    optimizer pass over any copy of the graph swaps in the SAME concrete
    instance, so the replaced node's structural hash — and therefore its fit
    cache entry — is stable across executions.
    """

    def __init__(self, sample_rows: int = 64):
        self._memo: Dict[tuple, tuple] = {}
        # Deep-graph shapes memoized by the deps' CONTENT-STABLE prefix
        # digests: repeated optimizer passes over graph copies hit this
        # instead of re-executing the sampled prefix. id-based prefixes
        # digest to None and are never memoized — a recycled id must not
        # serve stale shapes (same rule as the executor's fit cache).
        self._shape_memo: Dict[tuple, List] = {}
        self.sample_rows = sample_rows

    def clear_cache(self) -> None:
        self._memo.clear()
        self._shape_memo.clear()

    @staticmethod
    def _dep_prefix_key(graph: Graph, deps: Sequence[GraphId]):
        """(memo key, sampleable): the key is a tuple of content-stable
        prefix digests (None when any prefix lacks content identity — then
        shapes are recomputed each pass rather than risking a stale hit);
        sampleable=False when a prefix reaches an unbound source, where a
        sample run could never resolve the shapes."""
        from keystone_tpu.workflow.graph import structural_digest

        digests = []
        for d in deps:
            if not isinstance(d, NodeId):
                return None, False
            if graph.sources_of([d]):
                return None, False
            digests.append(structural_digest(graph, d))
        if any(x is None for x in digests):
            return None, True
        return tuple(digests), True

    @staticmethod
    def _measured_shapes(graph: Graph, deps, shapes, measured, dmemo):
        """Resolve the still-missing dep shapes from a stored measured
        profile's recorded output shapes (exact full-size values — better
        than a scaled sample). None when any gap stays unresolved."""
        from keystone_tpu.workflow.graph import structural_digest

        out = []
        for s, dep in zip(shapes, deps):
            if s is not None:
                out.append(tuple(s))
                continue
            if not isinstance(dep, NodeId):
                return None
            entry = measured.node(structural_digest(graph, dep, dmemo))
            shp = (entry or {}).get("out_shape")
            if not shp:
                return None
            out.append(tuple(int(x) for x in shp))
        return out

    def _sample_prefixes(self, graph: Graph, targets: Sequence[GraphId]):
        """One row-sampled execution of the input prefixes of every
        optimizable estimator that still NEEDS sampling — deep-graph deps
        not already served by the shape memo or by direct dataset shapes.
        All such estimators in the DAG share the run."""
        needed = []
        for nid in graph.reachable(targets):
            op = graph.operators[nid]
            if not isinstance(op, EstimatorOperator) or (
                getattr(op.estimator, "optimize_node", None) is None
            ):
                continue
            deps = graph.dependencies[nid]
            if all(
                isinstance(d, NodeId)
                and isinstance(graph.operators.get(d), DatasetOperator)
                for d in deps
            ):
                continue  # direct with_data case: shapes read off datasets
            pkey, sampleable = self._dep_prefix_key(graph, deps)
            if not sampleable:
                continue  # unbound prefix: sampling can't resolve it
            if pkey is not None and pkey in self._shape_memo:
                continue  # already served without execution
            needed.extend(d for d in deps if isinstance(d, NodeId))
        return Profiler(self.sample_rows).sample_values(graph, needed)

    def apply(self, graph: Graph, targets: Sequence[GraphId]) -> Graph:
        out = graph
        sampled = None  # lazy: only deep-graph estimators pay for the run
        sample_ok = True
        measured = _measured_profile()
        dmemo: Dict[GraphId, Any] = {}
        for nid in graph.reachable(targets):
            op = graph.operators[nid]
            if not isinstance(op, EstimatorOperator):
                continue
            optimize = getattr(op.estimator, "optimize_node", None)
            if optimize is None:
                continue
            deps = graph.dependencies[nid]
            shapes = []
            for dep in deps:
                shape = None
                if isinstance(dep, NodeId):
                    dep_op = graph.operators.get(dep)
                    if isinstance(dep_op, DatasetOperator):
                        shape = getattr(dep_op.data, "shape", None)
                shapes.append(shape)
            provenance = "model"
            if shapes and any(s is None for s in shapes):
                pkey, sampleable = self._dep_prefix_key(graph, deps)
                if not sampleable:
                    continue  # unbound prefix: nothing to sample or dispatch
                resolved = None
                if measured is not None:
                    resolved = self._measured_shapes(
                        graph, deps, shapes, measured, dmemo
                    )
                if resolved is not None:
                    shapes = resolved
                    provenance = "measured"
                else:
                    memo_shapes = (
                        self._shape_memo.get(pkey)
                        if pkey is not None else None
                    )
                    if memo_shapes is not None:
                        shapes = memo_shapes
                        provenance = "sampled"
                    else:
                        provenance = "sampled"
                        if sampled is None:
                            try:
                                sampled = self._sample_prefixes(graph, targets)
                                sample_ok = True
                            except Exception:  # lint: broad-ok sample-run probe over arbitrary user operators
                                # A prefix that can't run on a 64-row sample
                                # must not crash optimization: affected
                                # estimators keep their fit-time dispatch.
                                logger.warning(
                                    "sampled prefix run failed; deep-graph "
                                    "estimators keep fit-time dispatch",
                                    exc_info=True,
                                )
                                sampled = ({}, {}, {})
                                sample_ok = False
                        values, scales, rows_ok = sampled
                        shapes = [
                            s
                            if s is not None
                            else (
                                _scaled_shape(
                                    values.get(dep), scales.get(dep, 1.0)
                                )
                                # A row-changing prefix (sampler/aggregator)
                                # makes scaled-n a lie; defer to fit-time.
                                if rows_ok.get(dep, False)
                                else None
                            )
                            for s, dep in zip(shapes, deps)
                        ]
                        # Legitimate deferrals memoize; a FAILED run must not —
                        # a transient error would otherwise disable
                        # optimize-time dispatch for this prefix forever.
                        # Bounded by refusing inserts when full, NOT by
                        # clearing: a mid-apply clear would strand estimators
                        # that _sample_prefixes skipped on a memo hit, letting
                        # them memoize all-None shapes from a run that never
                        # sampled their deps.
                        if (
                            pkey is not None
                            and sample_ok
                            and len(self._shape_memo) < 1024
                        ):
                            self._shape_memo[pkey] = shapes
            if not shapes or shapes[0] is None:
                continue
            key = (id(op.estimator), tuple(shapes))
            memoized = self._memo.get(key)
            if memoized is not None and memoized[0]() is op.estimator:
                concrete = memoized[1]
            else:
                concrete = optimize(*shapes)
                # The original is held weakly with eviction: when the user
                # drops their pipeline the memo entry (and the concrete
                # estimator it pins, and in turn that estimator's fit-cache
                # entry with its pinned training data) is freed. A dead or
                # recycled id can never serve a stale concrete because the
                # weakref identity check above fails first.
                try:
                    ref = weakref.ref(
                        op.estimator,
                        lambda _r, key=key: self._memo.pop(key, None),
                    )
                except TypeError:  # not weak-referenceable: don't memoize
                    ref = None
                if ref is not None:
                    self._memo[key] = (ref, concrete)
            if concrete is not None and concrete is not op.estimator:
                choice = getattr(op.estimator, "last_choice", None)
                record_decision(
                    rule="NodeOptimizationRule",
                    node=op.label(),
                    action=f"solver={type(concrete).__name__}",
                    provenance=provenance,
                    reason=(
                        getattr(choice, "reason", None)
                        or "optimize_node replacement from data shapes"
                    ),
                    cost={"shapes": [list(map(int, s)) for s in shapes
                                     if s is not None]},
                )
                out = out.replace_node(
                    nid, EstimatorOperator(concrete), graph.dependencies[nid]
                )
        return out


#: Assumed host/HBM materialization bandwidth used to price PERSISTING a
#: cached value (bytes / this = seconds of materialization cost). A
#: deliberately conservative 2 GB/s: only nodes whose recompute clearly
#: dominates a memory write get cached on the measured path.
_MATERIALIZE_BYTES_PER_S = 2e9

#: Absolute floor on a measured node's per-call wall before it can earn a
#: cache slot: sub-millisecond "costs" are dispatch overhead, and caching
#: them trades a fusion boundary (and a recompile) for nothing.
_MIN_CACHE_WALL_S = 1e-3


class AutoCacheRule(Rule):
    """Insert cache nodes where a subchain's recompute cost exceeds its
    materialization cost, best time-saved-per-byte first, under a memory
    budget.

    Costs come measured-first: a stored profile for this pipeline
    (matched by prefix digest) supplies per-node wall/bytes with ZERO
    sample executions; otherwise one 64-row sample run extrapolates
    (``Profiler``, with the compiled-FLOPs non-linearity correction).

    The session cache persists values across executions (fit → later
    applies, repeated gets over graph copies); within one execution the
    structural-hash memo already dedups, so the win is cross-execution
    recompute avoidance — the reference's cached-RDD reuse, with HBM/host
    RAM as the budget.
    """

    def __init__(
        self,
        budget_bytes: int | None = None,
        sample_rows: int = 64,
        min_consumers: int = 1,
        only_if_enabled: bool = False,
    ):
        self.budget_bytes = budget_bytes
        self.sample_rows = sample_rows
        self.min_consumers = min_consumers
        # The default optimizer installs the rule unconditionally and gates
        # each apply on config.auto_cache, so toggling the flag mid-session
        # takes effect instead of silently depending on when PipelineEnv
        # was constructed. Directly-constructed rules stay unconditional.
        self.only_if_enabled = only_if_enabled

    def _skippable(self, graph: Graph, nid: NodeId, targets_set, cons) -> bool:
        """Nodes no candidate path considers (shared by both provenances)."""
        op = graph.operators[nid]
        if isinstance(op, (DatasetOperator, CacheOperator)):
            return True  # data already lives in host memory; cache is cache
        if isinstance(op, EstimatorOperator):
            # Fits persist in the fit cache already, and a cache node
            # between an estimator and its delegating consumer would
            # hide the fitted transformer from Pipeline.fit's rewrite.
            return True
        if nid in targets_set or len(cons.get(nid, ())) < self.min_consumers:
            return True
        return False

    def _measured_candidates(
        self, graph: Graph, targets, measured, targets_set, cons
    ) -> List[tuple]:
        """(ratio, bytes, nid, decision-meta) candidates priced from the
        stored profile — no execution of any kind. A candidate survives
        only when its measured per-call recompute cost exceeds the cost
        of materializing its measured output bytes. The saving a cache
        buys is ONE avoided re-execution per later walk — the executor's
        structural-hash memo already runs a multi-consumer node once per
        walk, so consumer count is reported as context, never multiplied
        into the saving (the sampled path prices identically)."""
        from keystone_tpu.workflow.graph import structural_digest

        dmemo: Dict[GraphId, Any] = {}
        out: List[tuple] = []
        for nid in graph.reachable(targets):
            if self._skippable(graph, nid, targets_set, cons):
                continue
            label = graph.operators[nid].label()
            entry = measured.node(structural_digest(graph, nid, dmemo))
            if entry is None:
                # No measured row for this prefix (e.g. it only executed
                # fused into a larger program in the recorded run): leave
                # it uncached rather than guessing.
                continue
            calls = max(1, int(entry.get("calls") or 0))
            wall_s = (int(entry.get("wall_ns") or 0) / 1e9) / calls
            nbytes = int(entry.get("out_bytes") or 0)
            if nbytes <= 0 or wall_s <= 0:
                continue
            reuse = max(1, len(
                [u for u in cons.get(nid, ()) if isinstance(u, NodeId)]
            ))
            materialize_s = nbytes / _MATERIALIZE_BYTES_PER_S
            if wall_s < _MIN_CACHE_WALL_S:
                record_decision(
                    rule="AutoCacheRule", node=label, action="cache-skip",
                    provenance="measured",
                    reason=(
                        "measured wall below the cache floor "
                        "(dispatch overhead, not recompute)"
                    ),
                    cost={"recompute_s": round(wall_s, 6),
                          "floor_s": _MIN_CACHE_WALL_S,
                          "bytes": nbytes},
                )
                continue
            if wall_s <= materialize_s:
                record_decision(
                    rule="AutoCacheRule", node=label, action="cache-skip",
                    provenance="measured",
                    reason="measured recompute cheaper than materialization",
                    cost={"recompute_s": round(wall_s, 6),
                          "materialize_s": round(materialize_s, 6),
                          "bytes": nbytes},
                )
                continue
            out.append((
                wall_s / nbytes, nbytes, nid,
                ("measured", {
                    "recompute_s": round(wall_s, 6),
                    "materialize_s": round(materialize_s, 6),
                    "bytes": nbytes, "consumers": reuse,
                }),
            ))
        return out

    def _sampled_candidates(
        self, graph: Graph, targets, targets_set, cons
    ) -> List[tuple]:
        """The original sample-run path: profile a 64-row execution and
        extrapolate (rows scale bytes; compiled FLOPs scale time)."""
        profiles = Profiler(self.sample_rows).profile(graph, targets)
        out: List[tuple] = []
        for nid, prof in profiles.items():
            if self._skippable(graph, nid, targets_set, cons):
                continue
            # Output bytes scale with rows; time scales with compiled FLOPs
            # when XLA counted them (the non-linear-stage correction).
            est_bytes = int(prof.bytes * prof.scale)
            est_seconds = prof.seconds * prof.time_scale
            if est_bytes <= 0 or est_seconds <= 0:
                continue
            out.append((
                est_seconds / est_bytes, est_bytes, nid,
                ("sampled", {
                    "recompute_s": round(est_seconds, 6),
                    "bytes": est_bytes,
                }),
            ))
        return out

    def apply(self, graph: Graph, targets: Sequence[GraphId]) -> Graph:
        if self.only_if_enabled and not config.auto_cache:
            return graph
        # `is not None`: an explicit 0 means "no cache budget", not "unset".
        if self.budget_bytes is not None:
            budget = self.budget_bytes
        else:
            # Real device budget when the runtime reports one (TPU
            # bytes_limit), config fallback otherwise.
            from keystone_tpu.utils.metrics import device_hbm_bytes

            budget = device_hbm_bytes() // 4
        cons = graph.consumers(targets)
        targets_set = set(targets)
        measured = _measured_profile()
        if measured is not None:
            # Profile hit: measured costs, ZERO sample-run executions.
            candidates = self._measured_candidates(
                graph, targets, measured, targets_set, cons
            )
        else:
            candidates = self._sampled_candidates(
                graph, targets, targets_set, cons
            )
        if not candidates:
            return graph
        candidates.sort(key=lambda c: (c[0], c[1]), reverse=True)

        ops = dict(graph.operators)
        dps = dict(graph.dependencies)
        spent = 0
        changed = False
        for _ratio, nbytes, nid, (provenance, cost) in candidates:
            label = graph.operators[nid].label()
            if spent + nbytes > budget:
                record_decision(
                    rule="AutoCacheRule", node=label, action="cache-skip",
                    provenance=provenance,
                    reason=(
                        f"budget exhausted ({spent + nbytes} of {budget} "
                        "bytes would be pinned)"
                    ),
                    cost=cost,
                )
                continue
            spent += nbytes
            changed = True
            from keystone_tpu.workflow.graph import fresh_node_id

            cache_id = fresh_node_id()
            ops[cache_id] = CacheOperator()
            dps[cache_id] = (nid,)
            for consumer in cons.get(nid, ()):
                dps[consumer] = tuple(
                    cache_id if d == nid else d for d in dps[consumer]
                )
            record_decision(
                rule="AutoCacheRule", node=label, action="cache-insert",
                provenance=provenance,
                reason=(
                    "measured recompute cost exceeds materialization cost"
                    if provenance == "measured"
                    else "best sampled time-saved-per-byte under budget"
                ),
                cost=dict(cost, budget_spent=spent, budget=budget),
            )
        return Graph(ops, dps) if changed else graph


# ---------------------------------------------------------------------------
# Serve-ladder planning — the memory-bounded serving half of the planner
# ---------------------------------------------------------------------------

#: Fraction of the device budget the AOT-warmed serve ladder may pin:
#: request buffers, the in-flight window, and XLA scratch live alongside
#: the resident executables.
SERVE_LADDER_BUDGET_FRAC = 2


def plan_serve_ladder(
    ladder: Sequence[int],
    bytes_per_row: float,
    replicas: int,
    budget_bytes: Optional[int] = None,
    provenance: str = "model",
    node: str = "-",
) -> tuple:
    """Trim a candidate bucket ladder against an HBM budget BEFORE any
    rung compiles ("Memory Safe Computations with XLA", arXiv:2206.14148
    — plan memory, don't react to OOM).

    Every rung of the ladder AOT-warms into a resident executable on
    every replica, so the whole set coexists: a rung's priced residency
    is ``bytes_per_row × rung × replicas`` (conservative — on a real
    multi-HBM pool each replica's ladder lives on its own device; on the
    CPU/forced-host pools the replicas genuinely share one memory).
    Rungs are kept smallest-first while the cumulative priced bytes fit
    ``budget_bytes`` (default ``device_hbm_bytes() //
    SERVE_LADDER_BUDGET_FRAC``); the rungs that don't fit are trimmed
    top-down — capping the top bucket, so oversize batches chunk through
    a smaller rung instead of OOMing a bigger one. The smallest rung is
    always kept (serving must stay possible; a plan still over budget at
    one rung is counted and left for KG104 to flag).

    Never silent: every trim is a counted registry decision
    (``serve_plan`` counters + the optimizer decision ring).

    Returns ``(kept_ladder, trimmed_buckets, plan_info)``.
    """
    from keystone_tpu.utils.metrics import (
        device_hbm_bytes,
        serve_plan_counters,
    )

    if budget_bytes is None:
        budget_bytes = device_hbm_bytes() // SERVE_LADDER_BUDGET_FRAC
    replicas = max(1, int(replicas))
    per_bucket = {
        int(b): int(bytes_per_row * int(b)) * replicas for b in ladder
    }
    kept: List[int] = []
    trimmed: List[int] = []
    spent = 0
    for b in sorted(per_bucket):
        cost = per_bucket[b]
        if kept and spent + cost > budget_bytes:
            trimmed.append(b)
            continue
        kept.append(b)
        spent += cost
    serve_plan_counters.bump("ladders_planned")
    over_budget = spent > budget_bytes
    if over_budget:
        serve_plan_counters.bump("plans_over_budget")
    for b in trimmed:
        serve_plan_counters.bump("buckets_trimmed")
        record_decision(
            rule="PlanServeLadder", node=node,
            action=f"trim-bucket={b}",
            provenance=provenance,
            reason=(
                f"bucket {b}'s AOT-warmed executables cannot coexist "
                f"with the smaller rungs under the HBM headroom "
                f"({spent + per_bucket[b]} of {budget_bytes} bytes "
                "would be resident)"
            ),
            cost={"bucket_bytes": per_bucket[b],
                  "ladder_bytes_kept": spent,
                  "budget_bytes": budget_bytes,
                  "replicas": replicas},
        )
    if trimmed:
        # Trims are always a top segment of the sorted ladder (per-rung
        # cost is monotone in rung size and the spent total only grows),
        # so any trim caps the top bucket.
        serve_plan_counters.bump("top_bucket_capped")
    record_decision(
        rule="PlanServeLadder", node=node,
        action=f"serve_buckets={','.join(str(b) for b in kept)}",
        provenance=provenance,
        reason=(
            f"{len(kept)} rung(s) priced at {round(bytes_per_row, 1)} "
            f"B/row x {replicas} replica(s) fit the "
            f"{budget_bytes}-byte ladder budget"
            + (f"; {len(trimmed)} rung(s) trimmed" if trimmed else "")
            + ("; STILL over budget at one rung" if over_budget else "")
        ),
        cost={"bytes_per_row": round(float(bytes_per_row), 1),
              "ladder_bytes": spent, "budget_bytes": budget_bytes,
              "replicas": replicas, "trimmed": list(trimmed)},
    )
    plan_info = {
        "bytes_per_row": round(float(bytes_per_row), 1),
        "provenance": provenance,
        "replicas": replicas,
        "budget_bytes": int(budget_bytes),
        "planned_bytes": int(spent),
        "headroom_bytes": int(budget_bytes - spent),
        "per_bucket_bytes": {b: per_bucket[b] for b in kept},
        "trimmed": list(trimmed),
        "over_budget": over_budget,
    }
    return tuple(kept), list(trimmed), plan_info


class PlanResourcesRule(Rule):
    """Profile-guided resource planning: on a measured-profile hit, pick
    the executor worker count and the solver chunk rows BEFORE any device
    work, writing a session-scoped plan (``PipelineEnv.resource_plan``)
    that the executor and the chunked solvers consult wherever the
    explicit knobs (KEYSTONE_EXEC_WORKERS / KEYSTONE_SOLVE_CHUNK_ROWS)
    are unset.

    - ``exec_workers``: the graph's independent-branch width (max fan-in
      over gather-style joins), clamped to host cores — measured
      queue-wait attribution from a previous parallel run widens nothing
      (the pool was already saturated) but is surfaced in the decision.
    - ``solve_chunk_rows``: measured bytes-per-row of each estimator's
      input against the HBM budget, so PR-3's reactive OOM-halving
      becomes a planned size ("Memory Safe Computations with XLA",
      arXiv:2206.14148).

    The graph is never rewritten — this rule only plans.
    """

    #: Fraction of the device budget one solver chunk may occupy: the
    #: accumulators, the previous in-flight chunk, and XLA scratch all
    #: live alongside it.
    CHUNK_BUDGET_FRAC = 8

    #: Fraction of the device budget the host prefetch queue may hold in
    #: flight (depth × per-batch bytes): the queued batches are the next
    #: H2D transfers, and a hand-picked depth over multi-GB batches would
    #: stage more than the device can ever accept.
    PREFETCH_BUDGET_FRAC = 8

    def __init__(self, only_if_enabled: bool = False):
        self.only_if_enabled = only_if_enabled

    #: The plan keys this rule owns (and therefore clears every pass).
    PLAN_KEYS = ("exec_workers", "solve_chunk_rows", "prefetch_depth")

    def apply(self, graph: Graph, targets: Sequence[GraphId]) -> Graph:
        if not targets:
            return graph
        from keystone_tpu.workflow.executor import PipelineEnv

        plan = PipelineEnv.get().resource_plan
        # The plan describes the pipeline being optimized NOW. Clearing
        # at pass entry — BEFORE the enable gate, so disabling the
        # planner mid-session also retires its last plan — keeps a plan
        # derived from one profiled pipeline from leaking into an
        # unrelated pipeline's walk/solve in the same session (a planned
        # chunk split regroups the gram accumulation — numerics the
        # other pipeline never opted into).
        for key in self.PLAN_KEYS:
            plan.pop(key, None)
        if self.only_if_enabled and not config.plan_resources:
            return graph
        measured = _measured_profile()
        if measured is None:
            return graph
        self._plan_workers(graph, targets, measured, plan)
        self._plan_chunk_rows(graph, targets, measured, plan)
        self._plan_prefetch_depth(graph, targets, measured, plan)
        return graph

    @staticmethod
    def _branch_width(graph: Graph, targets) -> int:
        """Independent-branch width: the widest fan-in any reachable node
        joins (a gather of B branches can run B-wide)."""
        width = 1
        for nid in graph.reachable(targets):
            deps = [d for d in graph.dependencies[nid]
                    if isinstance(d, NodeId)
                    and not isinstance(graph.operators.get(d),
                                       DatasetOperator)]
            width = max(width, len(set(deps)))
        return width

    def _plan_workers(self, graph, targets, measured, plan) -> None:
        import os

        width = self._branch_width(graph, targets)
        cores = os.cpu_count() or 1
        workers = min(width, cores)
        queue_wait_ms = round(sum(
            int(e.get("queue_wait_ns") or 0)
            for e in measured.digests.values()
        ) / 1e6, 3)
        if workers >= 2:
            plan["exec_workers"] = workers
            record_decision(
                rule="PlanResourcesRule", node="-",
                action=f"exec_workers={workers}",
                provenance="measured",
                reason=(
                    f"graph has {width} independent branch(es) on a "
                    f"{cores}-core host"
                ),
                cost={"branch_width": width, "host_cores": cores,
                      "measured_queue_wait_ms": queue_wait_ms},
            )
        else:
            record_decision(
                rule="PlanResourcesRule", node="-", action="exec_workers=0",
                provenance="measured",
                reason=(
                    "serial walk kept: "
                    + (f"only {cores} host core(s)" if cores < 2
                       else "no independent branches to overlap")
                ),
                cost={"branch_width": width, "host_cores": cores},
            )

    def _plan_chunk_rows(self, graph, targets, measured, plan) -> None:
        from keystone_tpu.workflow.graph import structural_digest
        from keystone_tpu.utils.mesh import num_data_shards
        from keystone_tpu.utils.metrics import device_hbm_bytes

        dmemo: Dict[GraphId, Any] = {}
        budget = device_hbm_bytes() // self.CHUNK_BUDGET_FRAC
        # A solver chunk is row-sharded over the mesh, so each device
        # holds rows/shards of it: the per-device HBM budget prices
        # bytes_per_row ÷ shard_count, not the whole chunk. The stored
        # profile's fingerprint already pins device_count at load
        # (ProfileFingerprintError), so a 1-device profile can never
        # reach this sizing under an 8-device mesh.
        try:
            shards = max(1, int(num_data_shards()))
        except RuntimeError:  # deviceless backend: plan as one shard
            shards = 1
        for nid in graph.reachable(targets):
            op = graph.operators[nid]
            if not isinstance(op, EstimatorOperator):
                continue
            deps = graph.dependencies[nid]
            if not deps or not isinstance(deps[0], NodeId):
                continue
            entry = measured.node(structural_digest(graph, deps[0], dmemo))
            if entry is None:
                continue
            rows = int(entry.get("out_rows") or 0)
            nbytes = int(entry.get("out_bytes") or 0)
            if rows <= 0 or nbytes <= 0:
                continue
            bytes_per_row = nbytes / rows
            planned = int(budget // max(1.0, bytes_per_row / shards))
            if planned >= rows or planned < 1:
                # The whole measured input fits the chunk budget: nothing
                # to plan (streams smaller than the budget never split).
                continue
            prior = int(plan.get("solve_chunk_rows", 0) or 0)
            plan["solve_chunk_rows"] = (
                min(prior, planned) if prior else planned
            )
            record_decision(
                rule="PlanResourcesRule", node=op.label(),
                action=f"solve_chunk_rows={planned}",
                provenance="measured",
                reason=(
                    f"measured {bytes_per_row:.0f} B/row over "
                    f"{shards} shard(s) vs {budget} B per-device chunk "
                    "budget — planned split replaces reactive OOM-halving"
                ),
                cost={"bytes_per_row": round(bytes_per_row, 1),
                      "chunk_budget_bytes": budget,
                      "data_shards": shards,
                      "measured_rows": rows},
            )

    def _plan_prefetch_depth(self, graph, targets, measured, plan) -> None:
        """Clamp the hand-picked prefetch depth against the budget share:
        depth × measured per-batch bytes staged in the host queue must
        not overrun ``device_hbm_bytes() // PREFETCH_BUDGET_FRAC`` —
        those batches are the next H2D transfers. Only ever clamps DOWN
        (the hand-picked ``config.prefetch_depth`` stays the ceiling);
        an exported KEYSTONE_PREFETCH_DEPTH wins outright at the consume
        site (loaders/stream.py)."""
        from keystone_tpu.workflow.graph import structural_digest
        from keystone_tpu.utils.metrics import (
            device_hbm_bytes,
            serve_plan_counters,
        )

        hand_picked = int(config.prefetch_depth)
        if hand_picked <= 1:
            return  # depth 0/1 is already minimal: nothing to clamp
        budget = device_hbm_bytes() // self.PREFETCH_BUDGET_FRAC
        dmemo: Dict[GraphId, Any] = {}
        worst = None  # (per-batch bytes, node label, rows per batch)
        for nid in graph.reachable(targets):
            op = graph.operators[nid]
            if not isinstance(op, EstimatorOperator):
                continue
            deps = graph.dependencies[nid]
            if not deps or not isinstance(deps[0], NodeId):
                continue
            entry = measured.node(structural_digest(graph, deps[0], dmemo))
            if entry is None:
                continue
            # out_rows/out_bytes are LAST-WRITE per-call sizes (the store
            # contract, utils/metrics._DIGEST_DELTA_FIELDS), so `rows`
            # already IS the measured per-batch row count — never divide
            # by the accumulated call count.
            rows = int(entry.get("out_rows") or 0)
            nbytes = int(entry.get("out_bytes") or 0)
            if rows <= 0 or nbytes <= 0:
                continue
            bytes_per_row = nbytes / rows
            # The prefetcher stages whatever the producer yields: the
            # planned solver chunk when one exists, else the measured
            # per-call batch.
            chunk_rows = int(plan.get("solve_chunk_rows", 0) or 0)
            batch_rows = chunk_rows if chunk_rows else rows
            batch_bytes = int(bytes_per_row * batch_rows)
            if worst is None or batch_bytes > worst[0]:
                worst = (batch_bytes, op.label(), batch_rows)
        if worst is None:
            return  # no measured estimator input: nothing to price
        batch_bytes, label, batch_rows = worst
        fit = max(1, int(budget // max(1, batch_bytes)))
        if fit >= hand_picked:
            record_decision(
                rule="PlanResourcesRule", node=label,
                action=f"prefetch_depth={hand_picked}",
                provenance="measured",
                reason=(
                    f"hand-picked depth {hand_picked} x {batch_bytes} "
                    f"B/batch fits the {budget} B prefetch budget share"
                ),
                cost={"batch_bytes": batch_bytes,
                      "batch_rows": batch_rows,
                      "prefetch_budget_bytes": budget},
            )
            return
        plan["prefetch_depth"] = fit
        serve_plan_counters.bump("prefetch_clamped")
        record_decision(
            rule="PlanResourcesRule", node=label,
            action=f"prefetch_depth={fit}",
            provenance="measured",
            reason=(
                f"hand-picked depth {hand_picked} x {batch_bytes} B/batch "
                f"overruns the {budget} B prefetch budget share — clamped "
                f"to {fit}"
            ),
            cost={"batch_bytes": batch_bytes, "batch_rows": batch_rows,
                  "prefetch_budget_bytes": budget,
                  "hand_picked_depth": hand_picked},
        )
