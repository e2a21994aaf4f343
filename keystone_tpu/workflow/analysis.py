"""Static pipeline-graph linter (Layer 1 of keystone-lint).

KeystoneML's core move is whole-pipeline optimization over a statically
analyzable operator DAG; this module adds the *checking* half of that
bargain. An abstract-interpretation pass propagates symbolic shape/dtype
specs through the graph (``jax.eval_shape`` on the transformers' batch
functions — no device compute, no data) and a small rule catalog turns
what the pass sees into structured diagnostics, so serveability, shape,
and recompile hazards surface BEFORE a trace ever reaches a device
(arXiv:2206.14148's pre-execution resource checking; arXiv:2008.01040's
pre-execution graph analysis).

Rule catalog (KG = Keystone Graph):

- ``KG001 serve-unjittable`` — a non-jittable (host) transformer on the
  would-be serving chain. ``compiled()`` would refuse it at call time;
  the linter says so up front.
- ``KG002 serve-row-coupled`` — a ``row_independent=False`` stage on the
  chain: bucket padding would change real outputs
  (``RowDependenceError`` at serve time).
- ``KG101 recompile-hazard`` — a shape-polymorphic input feeding jit
  consumers with no bucket ladder configured: every distinct row count
  recompiles the whole fused chain.
- ``KG102 dtype-seam`` — a silent upcast across a node boundary (output
  dtype wider than input), or mixed dtypes meeting at a gather join:
  the upcast doubles bytes/HBM mid-chain without anyone asking for it.
- ``KG103 shard-pad`` — fitting under ``config.shard_data_batches`` with
  a dataset whose batch rows can never divide the active data mesh: every
  fused-chain call over it mask-pads onto the mesh (extra pad rows per
  call) — the old silent single-device cliff, now caught statically (a
  pure shape check, no execution) so the operator can pick a divisible
  batch size instead of paying the padding.
- ``KG104 plan-over-budget`` — a memory plan whose priced HBM exceeds
  the budget, caught at lint time instead of at warmup/trace time: a
  pinned serve bucket ladder (ladder × replicas × storage dtype — the
  AOT-warmed executables all coexist) beyond the ladder budget share,
  or a pinned solve chunk (rows × bytes/row from the propagated spec)
  beyond the chunk budget share. Shape-only pricing off the propagated
  specs — no execution, no compile; the un-pinned defaults stay silent
  because the warmup/plan path auto-sizes those.
- ``KG105 refit-full-head`` — linting with ``refit=True`` (the
  ``Pipeline.refit_stream`` contract) against a pipeline whose head
  estimator does not implement ``partial_fit``: every cadence tick then
  silently costs a FULL head refit over the buffered stream instead of
  a cheap accumulator re-solve.
- ``KG106 undonated-fit-chain`` — with ``config.donate_buffers`` on, an
  estimator's jittable feature chain takes its input from a dataset the
  runtime places directly onto the mesh (the divisible "shard" class):
  the placed array is caller-owned, so the fused lowering runs WITHOUT
  donating its input and the fit holds the batch live twice (input +
  chain output). Host-staged arrivals (streamed batches, the pad class)
  donate their staging copy instead. Shape-only, no execution.
- ``KG107 checkpoint-mesh-drift`` — an estimator configured with a
  ``checkpoint_dir`` whose on-disk mesh manifest was recorded under a
  DIFFERENT mesh width than the active data mesh: the fit will hit the
  elastic migration (counted) — or the typed ``MeshMismatchError`` with
  ``KEYSTONE_ELASTIC_MESH=0`` — at resume time. Flagged up front from
  the directory's JSON sidecar (a static dict read: no unpickling, no
  orbax restore, no execution).
- ``KG108 autoscale-pinned`` — a capacity model is enabled (telemetry
  dir configured / ``KEYSTONE_CAPACITY_MODEL``) while the replica count
  and/or the serve bucket ladder are hand-pinned
  (``KEYSTONE_SERVE_DEVICES`` != 0 / ``KEYSTONE_SERVE_BUCKETS``): the
  capacity re-plan loop refuses to touch pinned resources (pins win, by
  contract), so the pin silently defeats the traffic-aware autoscaling
  the model was enabled for. Same classifier discipline as KG104:
  static config reads only, pinned configurations only — the un-pinned
  defaults are exactly what the re-plan loop is allowed to size.
- ``KG201 dead-node`` — a node in the graph unreachable from the sink
  (composition orphans the pruner should have dropped).
- ``KG202 cache-advice`` — a non-trivial subchain re-used by >= 2
  consumers with no cache node: each consumer recomputes the prefix.
- ``KG203 profile-unused`` — a measured profile for this pipeline exists
  in the profile store, but the auto-cache rule would run model-only
  (``config.auto_cache`` is off, so the measured costs are never used
  for cache placement; the resource planner may still consume them).

Severity model: serveability rules (KG00x) are *errors* when linting
with ``serve=True`` (the pre-``compiled()`` gate) and *warnings*
otherwise; KG101/KG102/KG103/KG104/KG105/KG106/KG107/KG108 are
warnings; KG201/KG202/KG203 are info.

Wire-up: ``Pipeline.lint()`` runs this directly; the opt-in env gate
``KEYSTONE_LINT=warn|error|off`` (default off) runs it before every
``fit()`` / ``compiled()`` via ``enforce_lint`` — ``warn`` logs,
``error`` raises ``LintError`` on error-severity findings. CLI/CI
rendering goes through ``tools/lint_report.py.format_findings`` over
``LintReport.as_dicts()`` — the same table the AST layer prints;
``LintReport.render()`` is only the inline (no-tools-import)
convenience for interactive use.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from keystone_tpu.workflow.graph import Graph, GraphId, NodeId, SourceId
from keystone_tpu.workflow.operators import (
    DatasetOperator,
    DatumOperator,
    DelegatingOperator,
    EstimatorOperator,
    GatherOperator,
    TransformerOperator,
)

logger = logging.getLogger("keystone_tpu")

#: Rule ids -> one-line descriptions (the catalog tools/lint_report.py
#: and the README render; tests assert it stays in sync with the rules).
GRAPH_RULES: Dict[str, str] = {
    "KG001": "non-jittable (host) transformer on the serving chain",
    "KG002": "row-coupled stage on the serving chain (padding unsound)",
    "KG101": "shape-polymorphic input feeds jit consumers without buckets",
    "KG102": "silent dtype upcast / mixed-dtype seam across nodes",
    "KG103": "dataset batch rows never divide the active data mesh",
    "KG104": "pinned serve ladder / solve chunk priced beyond the HBM budget",
    "KG105": "refit_stream head estimator lacks partial_fit (full refit "
             "per cadence tick)",
    "KG106": "estimator's fit chain lowers without donation (mesh-placed "
             "caller-owned input)",
    "KG107": "checkpoint_dir holds state recorded under a different mesh "
             "width",
    "KG108": "capacity model enabled but replica count / serve ladder "
             "hand-pinned (pin defeats autoscaling)",
    "KG201": "dead node unreachable from the pipeline sink",
    "KG202": "re-used subchain with no cache node",
    "KG203": "stored measured profile exists but auto-cache is model-only",
}


class LintError(ValueError):
    """Raised by the KEYSTONE_LINT=error gate on error-severity findings."""


@dataclass(frozen=True)
class Diagnostic:
    """One structured finding: rule id, severity, where, what, and how to
    fix it — the graph-layer analog of a compiler diagnostic."""

    rule: str
    severity: str  # "error" | "warning" | "info"
    node: str      # "n12:RandomPatcher" or "-" for graph-wide findings
    message: str
    hint: str = ""

    def as_dict(self) -> dict:
        return {
            "rule": self.rule,
            "severity": self.severity,
            "node": self.node,
            "message": self.message,
            "hint": self.hint,
        }


@dataclass
class LintReport:
    """The diagnostics of one lint pass, with severity accessors.
    ``as_dicts()`` is the interchange shape ``tools/lint_report.py``'s
    shared formatter consumes; ``render()`` is a dependency-free inline
    rendering for interactive use."""

    diagnostics: List[Diagnostic] = field(default_factory=list)

    def __iter__(self):
        return iter(self.diagnostics)

    def __len__(self) -> int:
        return len(self.diagnostics)

    def errors(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.severity == "error"]

    def warnings(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.severity == "warning"]

    def by_rule(self, rule: str) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.rule == rule]

    def as_dicts(self) -> List[dict]:
        return [d.as_dict() for d in self.diagnostics]

    def render(self) -> str:
        """Human-readable table (one line per finding)."""
        if not self.diagnostics:
            return "pipeline lint: clean"
        lines = []
        for d in sorted(
            self.diagnostics,
            key=lambda d: ({"error": 0, "warning": 1, "info": 2}[d.severity],
                           d.rule),
        ):
            loc = f" @ {d.node}" if d.node != "-" else ""
            hint = f" [{d.hint}]" if d.hint else ""
            lines.append(f"{d.severity:<7} {d.rule}{loc}: {d.message}{hint}")
        return "\n".join(lines)


def _node_label(graph: Graph, nid: NodeId) -> str:
    return f"{nid!r}:{graph.operators[nid].label()}"


# ---------------------------------------------------------------------------
# Abstract shape/dtype propagation
# ---------------------------------------------------------------------------


def _spec_of_value(data: Any):
    """A ShapeDtypeStruct for a concrete batch, or None for host objects
    without array shape/dtype (token lists, strings)."""
    import numpy as np

    import jax

    shape = getattr(data, "shape", None)
    dtype = getattr(data, "dtype", None)
    if shape is None or dtype is None:
        return None
    try:
        return jax.ShapeDtypeStruct(tuple(int(s) for s in shape),
                                    np.dtype(dtype))
    except (TypeError, ValueError):
        return None


def _input_spec(example: Any) -> Tuple[Any, bool]:
    """Resolve the lint input: (spec-or-None, polymorphic_batch).

    ``example`` may be a sample batch (array), a ``jax.ShapeDtypeStruct``,
    a per-row feature-shape tuple (batch dim unknown -> polymorphic, a
    nominal batch stands in for propagation), or None (no input spec —
    dataset-rooted subgraphs still propagate; the batch is treated as
    polymorphic, which is what serving traffic is).
    """
    import numpy as np

    import jax

    if example is None:
        return None, True
    if isinstance(example, jax.ShapeDtypeStruct):
        return example, False
    if isinstance(example, tuple) and all(isinstance(d, int) for d in example):
        from keystone_tpu.config import config

        return (
            jax.ShapeDtypeStruct((8,) + example, np.dtype(config.default_dtype)),
            True,
        )
    spec = _spec_of_value(np.asarray(example))
    return spec, False


def propagate_specs(
    graph: Graph, sink: GraphId, source_spec: Any = None
) -> Dict[GraphId, Any]:
    """Abstract interpretation of the DAG: walk in topological order and
    compute each node's output ``ShapeDtypeStruct`` via ``jax.eval_shape``
    on the transformer's batch function — symbolic execution, no device
    work, no data. Unknown stays unknown (None) and poisons downstream
    specs rather than guessing: estimator fits (the fitted transformer is
    a runtime value), host transformers, and anything eval_shape refuses.
    """
    import jax
    import jax.numpy as jnp

    specs: Dict[GraphId, Any] = {}
    for nid in graph.reachable([sink]):
        op = graph.operators[nid]
        deps = graph.dependencies[nid]
        dep_specs = [
            specs.get(d) if isinstance(d, NodeId) else source_spec
            for d in deps
        ]
        out = None
        if isinstance(op, DatasetOperator):
            out = _spec_of_value(op.data)
        elif isinstance(op, DatumOperator):
            out = None
        elif isinstance(op, TransformerOperator):
            t = op.transformer
            if getattr(t, "jittable", False) and dep_specs and dep_specs[0] is not None:
                try:
                    out = jax.eval_shape(t.apply_batch, dep_specs[0])
                except Exception:  # lint: broad-ok abstract eval is best-effort; unknown, not fatal
                    out = None
        elif isinstance(op, GatherOperator):
            if dep_specs and all(s is not None for s in dep_specs):
                try:
                    out = jax.eval_shape(
                        lambda *xs: jnp.concatenate(
                            [jnp.asarray(x) for x in xs], axis=-1
                        ),
                        *dep_specs,
                    )
                except Exception:  # lint: broad-ok mismatched branches reported by KG102, not a crash
                    out = None
        elif getattr(op, "persist", False):  # identity cache node
            out = dep_specs[0] if dep_specs else None
        # Estimator / Delegating / unknown operators: runtime values.
        specs[nid] = out
    return specs


# ---------------------------------------------------------------------------
# The serve-chain walk (the non-throwing twin of executor.serving_chain)
# ---------------------------------------------------------------------------


def _walk_serve_chain(graph: Graph, source: SourceId, sink: GraphId):
    """Walk sink -> source the way ``GraphExecutor.serving_chain`` would
    after fit: see through cache nodes, follow a DelegatingOperator's
    input edge (its estimator resolves at fit time), and walk every
    branch of a gather join (it lowers to one ``GatherTransformer``).
    Returns the serve-path nodes, sink-first per branch."""
    chain: List[NodeId] = []
    pending: List[GraphId] = [sink]
    while pending:
        gid = pending.pop()
        while gid != source:
            if isinstance(gid, SourceId):
                break  # foreign source; composition artifact
            op = graph.operators[gid]
            deps = graph.dependencies[gid]
            if getattr(op, "persist", False):
                gid = deps[0]
            elif isinstance(op, DelegatingOperator):
                chain.append(gid)
                gid = deps[1]  # [estimator, input]
            elif isinstance(op, GatherOperator):
                pending.extend(deps)
                break
            elif len(deps) != 1:
                break  # a dataset-rooted graph: no serve input to reach
            else:
                chain.append(gid)
                gid = deps[0]
    return chain


# ---------------------------------------------------------------------------
# The lint pass
# ---------------------------------------------------------------------------


def lint_graph(
    graph: Graph,
    source: SourceId,
    sink: GraphId,
    example: Any = None,
    serve: bool = False,
    have_ladder: Optional[bool] = None,
    refit: bool = False,
) -> LintReport:
    """Run every graph rule over ``graph`` and return a ``LintReport``.

    ``serve=True`` escalates the serveability rules (KG00x) to errors —
    the pre-``compiled()`` contract. ``example`` feeds the shape/dtype
    propagation (see ``_input_spec``); ``have_ladder`` overrides the
    bucket-ladder detection for KG101 (None = read
    ``config.serve_buckets``); ``refit=True`` additionally checks the
    ``Pipeline.refit_stream`` contract (KG105: head estimator without
    ``partial_fit`` — every cadence tick is a full head refit).
    """
    from keystone_tpu.config import config

    report = LintReport()
    emit = report.diagnostics.append
    serve_sev = "error" if serve else "warning"

    order = graph.reachable([sink])
    live = set(order)

    # -- KG201: dead nodes -------------------------------------------------
    for nid in graph.operators:
        if nid not in live:
            emit(Diagnostic(
                "KG201", "info", _node_label(graph, nid),
                "node is unreachable from the pipeline sink",
                hint="prune with graph.pruned([sink])",
            ))

    # -- serveability: KG001 / KG002 ---------------------------------------
    for nid in _walk_serve_chain(graph, source, sink):
        op = graph.operators[nid]
        if not isinstance(op, TransformerOperator):
            continue
        t = op.transformer
        if not getattr(t, "jittable", True):
            emit(Diagnostic(
                "KG001", serve_sev, _node_label(graph, nid),
                f"{type(t).__name__} is not jittable; the AOT serving path "
                "compiles the whole chain as one XLA program",
                hint="keep host transformers off the serve path, or serve "
                     "per-shape via Pipeline.apply",
            ))
        if not getattr(t, "row_independent", True):
            emit(Diagnostic(
                "KG002", serve_sev, _node_label(graph, nid),
                f"{type(t).__name__} couples output rows to other input "
                "rows (row_independent=False); bucket padding would change "
                "real outputs",
                hint="serve it per-shape (unset KEYSTONE_SERVE_BUCKETS) or "
                     "keep the row-coupled stage off the bucketed path",
            ))

    # -- shape/dtype propagation: KG101 / KG102 ----------------------------
    source_spec, polymorphic = _input_spec(example)
    specs = propagate_specs(graph, sink, source_spec)

    if have_ladder is None:
        have_ladder = bool(config.serve_buckets)
    jit_consumers = [
        nid for nid in order
        if isinstance(graph.operators[nid], TransformerOperator)
        and getattr(graph.operators[nid].transformer, "jittable", False)
    ]
    if polymorphic and jit_consumers and not have_ladder:
        emit(Diagnostic(
            "KG101", "warning", _node_label(graph, jit_consumers[0]),
            f"shape-polymorphic input feeds {len(jit_consumers)} jit "
            "node(s) with no bucket ladder: every distinct batch size "
            "recompiles the fused chain",
            hint="set KEYSTONE_SERVE_BUCKETS (or serve via "
                 "Pipeline.compiled(), which pads onto a pow-2 ladder)",
        ))

    for nid in order:
        op = graph.operators[nid]
        out = specs.get(nid)
        if out is None:
            continue
        deps = graph.dependencies[nid]
        dep_specs = [
            specs.get(d) if isinstance(d, NodeId) else source_spec
            for d in deps
        ]
        if isinstance(op, GatherOperator):
            dts = {str(s.dtype) for s in dep_specs if s is not None}
            if len(dts) > 1:
                emit(Diagnostic(
                    "KG102", "warning", _node_label(graph, nid),
                    f"gather joins mixed dtypes {sorted(dts)}; XLA silently "
                    f"upcasts the concatenation to {out.dtype}",
                    hint="cast the narrower branch explicitly where the "
                         "width is intended",
                ))
            continue
        if isinstance(op, TransformerOperator):
            d0 = dep_specs[0] if dep_specs else None
            if (
                d0 is not None
                and out.dtype != d0.dtype
                and out.dtype.itemsize > d0.dtype.itemsize
            ):
                emit(Diagnostic(
                    "KG102", "warning", _node_label(graph, nid),
                    f"silent upcast {d0.dtype} -> {out.dtype} across "
                    f"{op.label()}: doubles bytes/HBM for everything "
                    "downstream",
                    hint="cast explicitly if intended, or compute at the "
                         "input dtype",
                ))

    # ONE consumer map shared by KG103 and KG202: the full-graph
    # traversal is paid once per lint pass, not per rule.
    consumers = graph.consumers([sink])

    # -- KG103: shard-pad (batch rows never divide the data mesh) ----------
    # A pure static shape check — no execution, no placement: the device
    # list is only consulted for the mesh width, and failures to resolve
    # one (deviceless backends) simply skip the rule (the classifier
    # answers "inert" there). One classifier shared with the runtime
    # placement (DatasetOperator) and the chain lowering (batch_layout),
    # so the lint can never drift from what execution actually does.
    if config.shard_data_batches:
        from keystone_tpu.utils.mesh import (
            host_batch_shard_class,
            num_data_shards,
        )

        try:
            shards = int(num_data_shards())
        except RuntimeError:  # deviceless backend: no mesh to divide
            shards = 0

        def _feeds_jittable_chain(start: NodeId) -> bool:
            """Does the dataset's row count reach a jittable chain? Walk
            downstream through row-preserving transformer stages (host
            normalizers etc. keep the batch's row count, so the pad cost
            still lands on the first jittable stage after them); stop at
            estimators/gathers-of-other-rows — labels/side inputs
            consumed solely by estimators are re-padded once inside
            RowMatrix regardless, and warning on them would train
            operators to ignore the rule."""
            seen, stack = set(), [start]
            while stack:
                nid = stack.pop()
                for u in consumers.get(nid, ()):
                    if not isinstance(u, NodeId) or u in seen:
                        continue
                    seen.add(u)
                    u_op = graph.operators.get(u)
                    if isinstance(u_op, TransformerOperator):
                        if getattr(u_op.transformer, "jittable", False):
                            return True
                        if getattr(u_op.transformer, "row_independent",
                                   True):
                            stack.append(u)  # rows survive the host stage
                    elif getattr(u_op, "persist", False):
                        stack.append(u)  # identity cache node
            return False

        for nid in (order if shards > 1 else ()):
            op = graph.operators[nid]
            if not isinstance(op, DatasetOperator):
                continue
            if host_batch_shard_class(op.data, shards) != "pad":
                continue
            if not _feeds_jittable_chain(nid):
                continue
            rows = int(op.data.shape[0])
            pad = (-rows) % shards
            emit(Diagnostic(
                "KG103", "warning", _node_label(graph, nid),
                f"batch of {rows} rows can never divide the "
                f"{shards}-shard data mesh: every fused-chain call "
                f"over it mask-pads {pad} row(s) onto the mesh "
                "(the old silent single-device cliff, now padded)",
                hint="size batches to a multiple of the mesh "
                     f"width ({shards}) to shard without padding",
            ))

        # -- KG106: estimator fit chain lowers without donation --------
        # Same classifier, same shape-only discipline as KG103. The
        # "shard" class is placed onto the mesh by DatasetOperator, so
        # the fused chain's input arrives caller-owned: the lowering
        # cannot donate it (placed values can be multi-consumer via
        # gather / the by-hash memo), and an accumulator-carrying fit
        # over it holds batch + chain output live at once while
        # ``config.donate_buffers`` promises one live copy. Host-staged
        # arrivals (streamed batches, the pad class) donate the staging
        # copy the chain call itself creates.
        if config.donate_buffers:

            def _feeds_estimator_via_jittable(start: NodeId) -> bool:
                """Does a jittable chain stand between this dataset and
                an estimator's fit? Walk downstream like KG103's helper,
                but keep going past the first jittable stage until an
                ``EstimatorOperator`` consumes the chain's output."""
                seen = set()
                stack = [(start, False)]
                while stack:
                    nid_, jit_seen = stack.pop()
                    for u in consumers.get(nid_, ()):
                        if not isinstance(u, NodeId) or (u, jit_seen) in seen:
                            continue
                        seen.add((u, jit_seen))
                        u_op = graph.operators.get(u)
                        if isinstance(u_op, EstimatorOperator):
                            if jit_seen:
                                return True
                        elif isinstance(u_op, TransformerOperator):
                            stack.append((
                                u,
                                jit_seen or getattr(
                                    u_op.transformer, "jittable", False
                                ),
                            ))
                        elif getattr(u_op, "persist", False):
                            stack.append((u, jit_seen))
                return False

            for nid in (order if shards > 1 else ()):
                op = graph.operators[nid]
                if not isinstance(op, DatasetOperator):
                    continue
                if host_batch_shard_class(op.data, shards) != "shard":
                    continue
                if not _feeds_estimator_via_jittable(nid):
                    continue
                rows = int(op.data.shape[0])
                emit(Diagnostic(
                    "KG106", "warning", _node_label(graph, nid),
                    f"fit chain over this {rows}-row mesh-placed batch "
                    "lowers WITHOUT donation (the placed input is "
                    "caller-owned), so the fit holds batch + chain "
                    "output live at once while config.donate_buffers "
                    "promises in-place updates",
                    hint="stream the batches (host arrivals stage-and-"
                         "donate their copy), or pin "
                         "KEYSTONE_DONATE_BUFFERS=0 if two live copies "
                         "are intended",
                ))

    # -- KG104: pinned memory plan priced beyond the HBM budget ------------
    # Shape-only pricing off the propagated specs — no execution, no
    # compile, no device work. Only PINNED plans are priced (an explicit
    # serve bucket ladder / an explicit solve chunk size): the un-pinned
    # defaults go through the warmup/optimizer planners, which auto-size
    # them under the same budget fractions, so flagging those would warn
    # about a plan that will never run as written.
    from keystone_tpu.config import (
        resolved_serve_buckets,
        resolved_solve_chunk_rows,
    )
    from keystone_tpu.utils.metrics import device_hbm_bytes

    def _row_bytes(spec, itemsize=None) -> int:
        import numpy as np

        shape = tuple(spec.shape[1:])
        size = itemsize if itemsize is not None else spec.dtype.itemsize
        return int(np.prod(shape, dtype=np.int64)) * int(size)

    budget = device_hbm_bytes()
    ladder = resolved_serve_buckets() or config.serve_buckets
    if ladder and source_spec is not None:
        from keystone_tpu.workflow.rules import SERVE_LADDER_BUDGET_FRAC

        # The storage dtype the ladder warms at: bf16 serving stores the
        # request batch at half the bytes (the precision-ladder boundary
        # cast); f32/f32h keep the spec's dtype.
        in_itemsize = (
            2 if config.serve_precision == "bf16"
            else source_spec.dtype.itemsize
        )
        replicas = config.serve_devices
        if replicas == 0:
            import jax

            try:
                replicas = len(jax.local_devices())
            except Exception:  # lint: broad-ok deviceless backend: price a one-replica pool
                replicas = 1
        # Per-row price = input + EVERY known node output (the runtime
        # planner's conservative all-activations-resident price — the
        # 512-feature intermediate of a featurize chain dominates, and
        # pricing only the in/out boundary would systematically miss
        # genuinely over-budget ladders).
        bpr = _row_bytes(source_spec, in_itemsize) + sum(
            _row_bytes(s) for s in (specs.get(nid) for nid in order)
            if s is not None
        )
        ladder_bytes = (
            sum(int(b) * bpr for b in ladder) * max(1, int(replicas))
        )
        ladder_budget = budget // SERVE_LADDER_BUDGET_FRAC
        if ladder_bytes > ladder_budget:
            emit(Diagnostic(
                "KG104", "warning", "-",
                f"pinned serve ladder {tuple(int(b) for b in ladder)} x "
                f"{replicas} replica(s) at serve_precision="
                f"{config.serve_precision} prices {ladder_bytes} resident "
                f"bytes — beyond the {ladder_budget}-byte ladder budget "
                f"(device HBM {budget} // {SERVE_LADDER_BUDGET_FRAC}); "
                "warmup would pin more executables than the device holds",
                hint="drop rungs from KEYSTONE_SERVE_BUCKETS, serve fewer "
                     "replicas, or unset the ladder so the HBM planner "
                     "sizes it",
            ))
    chunk_rows = resolved_solve_chunk_rows()
    if chunk_rows is None:
        chunk_rows = config.solve_chunk_rows
    if chunk_rows and chunk_rows > 0:
        from keystone_tpu.workflow.rules import PlanResourcesRule

        chunk_budget = budget // PlanResourcesRule.CHUNK_BUDGET_FRAC
        for nid in order:
            if not isinstance(graph.operators[nid], EstimatorOperator):
                continue
            deps = graph.dependencies[nid]
            d0 = deps[0] if deps else None
            spec = (
                specs.get(d0) if isinstance(d0, NodeId) else source_spec
            )
            if spec is None:
                continue
            chunk_bytes = int(chunk_rows) * _row_bytes(spec)
            if chunk_bytes > chunk_budget:
                emit(Diagnostic(
                    "KG104", "warning", _node_label(graph, nid),
                    f"pinned solve chunk of {int(chunk_rows)} rows x "
                    f"{_row_bytes(spec)} B/row prices {chunk_bytes} bytes "
                    f"per H2D transfer — beyond the {chunk_budget}-byte "
                    f"chunk budget (device HBM {budget} // "
                    f"{PlanResourcesRule.CHUNK_BUDGET_FRAC}); the solve "
                    "would fall back to reactive OOM-halving",
                    hint="lower KEYSTONE_SOLVE_CHUNK_ROWS, or unset it so "
                         "the profile-guided planner sizes the chunk",
                ))

    # -- KG108: capacity model enabled under hand-pinned resources ---------
    # Static config reads only (the KG104 discipline): the pin/enable
    # state is entirely resolvable without execution, and only PINNED
    # configurations are flagged — the un-pinned defaults are exactly
    # what the capacity re-plan loop is allowed to size, so they are the
    # healthy configuration, not a finding.
    from keystone_tpu.config import resolved_capacity_model

    if resolved_capacity_model():
        pins = []
        if ladder:
            pins.append(
                f"serve bucket ladder {tuple(int(b) for b in ladder)} "
                "(KEYSTONE_SERVE_BUCKETS / config.serve_buckets)"
            )
        if config.serve_devices != 0:
            pins.append(
                f"replica count {int(config.serve_devices)} "
                "(KEYSTONE_SERVE_DEVICES)"
            )
        if pins:
            emit(Diagnostic(
                "KG108", "warning", "-",
                "the learned capacity model is enabled "
                "(KEYSTONE_CAPACITY_MODEL / telemetry dir configured) but "
                f"{' and '.join(pins)} are hand-pinned: the capacity "
                "re-plan loop refuses pinned resources by contract, so "
                "traffic-aware autoscaling is silently defeated — the "
                "model observes mix shifts it is never allowed to act on",
                hint="unset the pin(s) so the re-plan loop can size the "
                     "replica pool / re-price the ladder from the observed "
                     "traffic mix, or disable the model "
                     "(KEYSTONE_CAPACITY_MODEL=0) if the pins are "
                     "intentional",
            ))

    # -- KG105: refit-stream head without partial_fit ----------------------
    # Only under the refit contract (refit=True): a batch-only head is a
    # perfectly fine BATCH pipeline — the hazard exists solely when the
    # operator intends to stream refits through it. One head definition
    # shared with refit_stream/OnlineTrainer (workflow.online), so the
    # lint can never disagree with what the runtime would do.
    if refit:
        from keystone_tpu.workflow.online import (
            refit_head_estimator,
            supports_partial_fit,
        )

        head_est = refit_head_estimator(graph, sink)
        if head_est is not None and not supports_partial_fit(head_est):
            emit(Diagnostic(
                "KG105", "warning", type(head_est).__name__,
                f"{type(head_est).__name__} does not implement "
                "partial_fit: refit_stream will fall back to a FULL head "
                "refit (over the whole buffered stream) on every cadence "
                "tick instead of a cheap accumulator re-solve",
                hint="use a normal-equation head (LinearMapEstimator / "
                     "BlockLeastSquaresEstimator / LeastSquaresEstimator) "
                     "or accept the counted online.full_refits cost",
            ))

    # -- KG107: checkpoint_dir state recorded under a different mesh -------
    # Pure static read: the checkpoint writers drop a JSON mesh sidecar
    # (utils.mesh.write_mesh_manifest) next to their payloads, so the
    # width comparison is one dict read per checkpointed estimator — no
    # unpickling, no orbax restore, no execution. Absent sidecars
    # (pre-elastic directories, no checkpoint yet) stay silent: the
    # resume-time triage is authoritative; this is the early warning.
    for nid, op in graph.operators.items():
        if not isinstance(op, EstimatorOperator):
            continue
        ckpt_dir = getattr(
            getattr(op, "estimator", None), "checkpoint_dir", None
        )
        if not ckpt_dir:
            continue
        from keystone_tpu.utils.mesh import (
            num_data_shards,
            read_mesh_manifest,
        )

        manifest = read_mesh_manifest(ckpt_dir)
        if manifest is None:
            continue
        recorded = manifest.get("device_count")
        if recorded is None:
            continue
        try:
            active = int(num_data_shards())
        except RuntimeError:  # deviceless backend: no mesh to drift from
            continue
        if int(recorded) == active:
            continue
        emit(Diagnostic(
            "KG107", "warning", _node_label(graph, nid),
            f"checkpoint_dir {ckpt_dir} holds solver state recorded "
            f"under a {int(recorded)}-shard mesh, but the active data "
            f"mesh has {active} shards: the fit will migrate the state "
            "at resume (elastic mesh, counted in the 'elastic' metrics "
            "family) — or refuse with MeshMismatchError under "
            "KEYSTONE_ELASTIC_MESH=0",
            hint="expected with an intentional width change (the elastic "
                 "migration is bit-identical); otherwise point "
                 "checkpoint_dir at state recorded on this mesh, or "
                 "migrate it explicitly with utils.mesh.reshard_state",
        ))

    # -- KG202: cache placement advice (consumer map shared with KG103) ----
    for gid, users in consumers.items():
        if not isinstance(gid, NodeId):
            continue
        op = graph.operators[gid]
        if isinstance(op, (DatasetOperator, DatumOperator)):
            continue  # constants are free to "recompute"
        if getattr(op, "persist", False):
            continue
        node_users = [u for u in users if isinstance(u, NodeId)]
        if len(node_users) < 2:
            continue
        if any(
            getattr(graph.operators[u], "persist", False) for u in node_users
        ):
            continue  # one consumer is already a cache node
        emit(Diagnostic(
            "KG202", "info", _node_label(graph, gid),
            f"subchain output is consumed by {len(node_users)} nodes with "
            "no cache node; each consumer recomputes the prefix",
            hint="insert .cache() after the shared prefix (or enable "
                 "config.auto_cache)",
        ))

    # -- KG203: stored measured profile not consumed -----------------------
    # Only when a store is configured: the existence probe is one stat(),
    # and the digest walk is skipped entirely for unstored sessions.
    from keystone_tpu.config import resolved_profile_store

    if resolved_profile_store() and not config.auto_cache:
        from keystone_tpu.workflow.profile_store import (
            has_profile,
            pipeline_profile_digest,
        )

        if has_profile(pipeline_profile_digest(graph, sink)):
            emit(Diagnostic(
                "KG203", "info", "-",
                "a measured profile for this pipeline exists in the "
                "profile store, but config.auto_cache is off — the "
                "cache rule will run model-only and the measured costs "
                "go unused for cache placement (the resource planner "
                "may still consume them)",
                hint="enable config.auto_cache to consume the stored "
                     "profile for cache placement with zero sample runs",
            ))

    return report


# ---------------------------------------------------------------------------
# The opt-in pre-fit / pre-compiled gate
# ---------------------------------------------------------------------------


def enforce_lint(pipeline, stage: str, serve: bool = False,
                 have_ladder: Optional[bool] = None,
                 refit: bool = False) -> Optional[LintReport]:
    """Run the graph lint as a gate when ``KEYSTONE_LINT`` asks for it.

    ``off`` (default): no-op, zero cost beyond one config read.
    ``warn``: log each finding at its severity, never block.
    ``error``: additionally raise ``LintError`` when any error-severity
    finding exists — the pre-execution refusal the rule catalog promises.
    """
    from keystone_tpu.config import config

    mode = config.lint
    if mode == "off":
        return None
    report = lint_graph(
        pipeline.graph, pipeline.source, pipeline.sink,
        serve=serve, have_ladder=have_ladder, refit=refit,
    )
    for d in report:
        log = logger.error if d.severity == "error" else (
            logger.warning if d.severity == "warning" else logger.info
        )
        log("lint[%s] %s %s: %s", stage, d.rule, d.node, d.message)
    errors = report.errors()
    if mode == "error" and errors:
        raise LintError(
            f"KEYSTONE_LINT=error: {len(errors)} error-severity finding(s) "
            f"before {stage}:\n" + "\n".join(
                f"  {d.rule} {d.node}: {d.message}" for d in errors
            )
        )
    return report
