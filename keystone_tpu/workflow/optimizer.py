"""Rule-based graph optimizer.

Ref: src/main/scala/workflow/Optimizer.scala — Catalyst-style batches of
rewrite rules run to fixed point [unverified]. The default pipeline here:

1. ``EquivalentNodeMergeRule`` — dedups structurally identical nodes (restores
   sharing lost to copy-on-instantiate composition).
2. ``ChainFusionRule`` — the TPU-specific lowering: maximal chains of jittable
   transformers become ONE ``FusedTransformer`` whose batch function is a
   single XLA computation. This replaces the reference's per-stage RDD
   execution with whole-chain compilation, letting XLA fuse elementwise work
   into the matmuls/convs around it.

Node-level solver selection and the auto-caching rule plug in as additional
rules (see workflow/rules.py as they land).
"""

from __future__ import annotations

import contextvars
from typing import Dict, List, Optional, Sequence, Tuple

from keystone_tpu.config import config
from keystone_tpu.workflow.graph import Graph, GraphId, NodeId, SourceId
from keystone_tpu.workflow.operators import TransformerOperator
from keystone_tpu.workflow.pipeline import FusedTransformer

#: The content digest of the pipeline AS THE USER WROTE IT, captured at
#: optimizer entry BEFORE any rule rewrites the graph (node-level solver
#: swaps change node digests, so a rule computing the key mid-pass would
#: never match what Pipeline.fit(profile=True) stored). Rules read it via
#: ``active_profile_key()``.
_profile_key: "contextvars.ContextVar[Optional[str]]" = contextvars.ContextVar(
    "keystone_profile_key", default=None
)


def active_profile_key() -> Optional[str]:
    """The measured-profile store key of the pipeline currently being
    optimized (None outside an optimizer pass, when no store is
    configured, or when the pipeline has no content identity)."""
    return _profile_key.get()


class Rule:
    def apply(self, graph: Graph, targets: Sequence[GraphId]) -> Graph:
        raise NotImplementedError


class EquivalentNodeMergeRule(Rule):
    """Merge nodes with identical (operator signature, dependencies).

    Ref: workflow/EquivalentNodeMergeRule.scala [unverified].
    """

    def apply(self, graph: Graph, targets: Sequence[GraphId]) -> Graph:
        order = graph.reachable(targets)
        canon: Dict[Tuple, NodeId] = {}
        remap: Dict[GraphId, GraphId] = {}
        ops = {}
        dps = {}
        targets_set = set(targets)
        for nid in order:
            op = graph.operators[nid]
            deps = tuple(remap.get(d, d) for d in graph.dependencies[nid])
            key = (op.signature(), deps)
            if key in canon and nid not in targets_set:
                remap[nid] = canon[key]
            else:
                canon.setdefault(key, nid)
                ops[nid] = op
                dps[nid] = deps
        # Always rebuild: this also prunes nodes unreachable from the targets
        # (orphans left by composition's copy-on-instantiate).
        return Graph(ops, dps)


class ChainFusionRule(Rule):
    """Fuse maximal single-consumer chains of jittable transformers.

    Fused transformers are memoized on the identity of their stage tuple so
    re-optimizing a copy of the same logical chain (every ``apply`` creates a
    fresh graph copy) reuses the same FusedTransformer object — and therefore
    its already-compiled jit cache. Without this, every ``get()`` would
    re-trace and re-compile the chain.
    """

    def __init__(self):
        # stage-id tuple -> FusedTransformer; values hold the stages strongly,
        # so the id keys can never alias recycled objects.
        self._fuse_cache: Dict[Tuple[int, ...], FusedTransformer] = {}

    def clear_cache(self) -> None:
        self._fuse_cache.clear()

    def _fused(self, stages: List) -> FusedTransformer:
        key = tuple(id(s) for s in stages)
        fused = self._fuse_cache.get(key)
        if fused is None:
            while len(self._fuse_cache) > 1024:
                # Bound the memo by evicting the OLDEST entry (dict keeps
                # insertion order): wholesale clearing would force live hot
                # pipelines to re-fuse (new identity, recompile, cache-miss
                # cascade); dropping one cold entry degrades gracefully.
                self._fuse_cache.pop(next(iter(self._fuse_cache)))
            fused = FusedTransformer(stages)
            # The memo holds closure chains only: one whose program is found
            # by its structure (``Transformer.shares_program``) needs no
            # memo to meet its executable again, and the memo would pin its
            # arrays (0.36 GB a TIMIT fit) for the life of the process.
            if not fused.shares_program():
                self._fuse_cache[key] = fused
        return fused

    def apply(self, graph: Graph, targets: Sequence[GraphId]) -> Graph:
        if not config.fuse_chains:
            return graph
        targets_set = set(targets)
        cons = graph.consumers(targets)
        order = graph.reachable(targets)

        def fusable(gid: GraphId) -> bool:
            if not isinstance(gid, NodeId):
                return False
            op = graph.operators.get(gid)
            return (
                isinstance(op, TransformerOperator) and op.transformer.jittable
            )

        chain_of: Dict[NodeId, List[NodeId]] = {}
        for nid in order:
            if not fusable(nid):
                continue
            dep = graph.dependencies[nid][0]
            if (
                fusable(dep)
                and len(cons.get(dep, ())) == 1
                and dep not in targets_set
                and dep in chain_of
            ):
                chain_of[nid] = chain_of.pop(dep) + [nid]
            else:
                chain_of[nid] = [nid]

        changed = False
        ops = dict(graph.operators)
        dps = dict(graph.dependencies)
        for tail, chain in chain_of.items():
            if len(chain) < 2:
                continue
            changed = True
            stages = [graph.operators[c].transformer for c in chain]
            ops[tail] = TransformerOperator(self._fused(stages))
            dps[tail] = graph.dependencies[chain[0]]
            for c in chain[:-1]:
                ops.pop(c, None)
                dps.pop(c, None)
        return Graph(ops, dps) if changed else graph


class Optimizer:
    """Batches of rules, each run to fixed point (bounded)."""

    def __init__(self, batches: Sequence[Tuple[str, Sequence[Rule], int]]):
        self.batches = list(batches)

    def execute(self, graph: Graph, targets: Sequence[GraphId]) -> Graph:
        token = _profile_key.set(self._profile_key_of(graph, targets))
        try:
            for _name, rules, max_iters in self.batches:
                for _ in range(max_iters):
                    before = (graph.operators, graph.dependencies)
                    for rule in rules:
                        graph = rule.apply(graph, targets)
                    if (graph.operators, graph.dependencies) == before:
                        break
            return graph
        finally:
            _profile_key.reset(token)

    @staticmethod
    def _profile_key_of(
        graph: Graph, targets: Sequence[GraphId]
    ) -> Optional[str]:
        """The store key for this pass — computed only when a profile
        store is configured AND a consuming rule is enabled (the digest
        walks the whole graph, fingerprinting bound data; a per-batch
        apply pass with auto-cache and the planner both off must not
        pay it)."""
        from keystone_tpu.config import config, resolved_profile_store

        if not targets or not resolved_profile_store():
            return None
        if not (config.auto_cache or config.plan_resources):
            return None
        from keystone_tpu.workflow.profile_store import (
            pipeline_profile_digest,
        )

        return pipeline_profile_digest(graph, targets[0])


def default_optimizer() -> Optimizer:
    from keystone_tpu.workflow.rules import (
        AutoCacheRule,
        NodeOptimizationRule,
        PlanResourcesRule,
    )

    batches: List[Tuple[str, List[Rule], int]] = [
        ("dedup", [EquivalentNodeMergeRule()], 3),
        ("node-level", [NodeOptimizationRule()], 1),
        # Profile-guided resource planning (exec workers / solve chunk
        # rows): acts only on a measured-profile hit; gated per-apply on
        # config.plan_resources like auto-cache below.
        ("plan", [PlanResourcesRule(only_if_enabled=True)], 1),
        # Gated per-apply on config.auto_cache (see AutoCacheRule), so the
        # flag works whenever it's flipped, not only before env creation.
        ("auto-cache", [AutoCacheRule(only_if_enabled=True)], 1),
        ("fusion", [ChainFusionRule()], 1),
    ]
    return Optimizer(batches)
