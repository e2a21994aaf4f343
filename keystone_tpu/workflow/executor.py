"""Graph executor + session environment.

Ref: src/main/scala/workflow/{GraphExecutor,PipelineEnv,Prefix}.scala
[unverified]. The executor walks the DAG in topological order, memoizing
values by *structural prefix hash* so that:

- duplicated subgraphs (created by composition's copy-on-instantiate) are
  computed once per execution;
- estimator fits are memoized across executions in ``PipelineEnv.fit_cache``
  (the reference's fitted-prefix state reuse);
- values marked by the auto-caching rule persist in ``node_cache``.

Where the reference's executor schedules Spark jobs per stage, ours executes
operators whose jittable chains were pre-fused into single XLA computations by
the optimizer.
"""

from __future__ import annotations

import contextvars
import threading
from typing import Any, Dict, Iterable, List, Optional, Sequence

from keystone_tpu.workflow.graph import (
    Graph,
    GraphId,
    NodeId,
    SourceId,
    structural_digest,
    structural_hash,
)
from keystone_tpu.workflow.operators import (
    DelegatingOperator,
    EstimatorOperator,
    GatherOperator,
    Operator,
    TransformerOperator,
)


class UnboundSourceError(RuntimeError):
    pass


def _span_shape(value) -> Any:
    """A JSON-able shape for a node-span attr: array shape, list length,
    or None — best-effort, never a failure."""
    shape = getattr(value, "shape", None)
    if shape is not None:
        try:
            return [int(s) for s in shape]
        except (TypeError, ValueError):
            return None
    if isinstance(value, (list, tuple)):
        return [len(value)]
    return None


def _no_sources(sid: SourceId):
    raise UnboundSourceError(
        f"graph has unbound source {sid!r}; apply the pipeline to data first"
    )


def _out_rows(value) -> Optional[int]:
    """Leading-axis row count of a node output (None when rowless) — the
    per-row-bytes denominator the resource planner sizes chunks with."""
    shape = getattr(value, "shape", None)
    if shape is not None and len(shape) >= 1:
        try:
            return int(shape[0])
        except (TypeError, ValueError):
            return None
    if isinstance(value, (list, tuple)):
        return len(value)
    return None


def _observed_execute(op, deps, tracer, profile, worker=None,
                      queue_wait_ns=None, digest=None):
    """Execute one node under the tracer and/or the resource profile.

    The profiled path blocks on array outputs so wall time covers device
    completion (dispatch vs wait attributed separately) and attributes
    cost-model FLOPs/bytes via the memoized abstract AOT compile — the
    node's VALUES are untouched, which is what keeps KEYSTONE_PROFILE=0
    and =1 fits bit-identical.

    ``worker`` / ``queue_wait_ns`` come from the parallel walk: which pool
    thread ran the node and how long it sat ready before a worker picked
    it up. The serial walk passes neither, so its spans and profile rows
    are unchanged. ``digest`` (the node's content-stable prefix digest,
    precomputed by the walk) additionally files the measurement under the
    profile's digest-keyed aggregates — the rows the profile store
    persists and the optimizer rules re-match."""
    import time

    label = op.label()
    extra = {}
    if worker is not None:
        extra["worker"] = worker
    if queue_wait_ns is not None:
        extra["queue_wait_ms"] = round(queue_wait_ns / 1e6, 4)
    if profile is None:
        with tracer.span(
            "node:" + label, "executor", cache="miss", **extra
        ) as attrs:
            out = op.execute(deps)
            attrs["shape"] = _span_shape(out)
            chain = getattr(op, "transformer", None)
            tiling = getattr(chain, "row_tiling", None)
            tiled = tiling(deps[0]) if tiling is not None and deps else None
            if tiled is not None:  # a fused chain that ran in row tiles
                attrs["tile_rows"], attrs["tiles"] = tiled
            fused = getattr(chain, "fused_stages", 0)
            if fused:  # a stage of the chain took the ones behind it
                attrs["fused_stages"] = fused
        return out

    import jax

    from keystone_tpu.utils.mesh import value_data_shards
    from keystone_tpu.utils.metrics import (
        node_cost_analysis,
        peak_hbm_bytes,
        profile_forced,
    )

    hbm0 = peak_hbm_bytes()
    t0 = time.perf_counter_ns()
    out = op.execute(deps)
    t_disp = time.perf_counter_ns()
    if isinstance(out, jax.Array):
        out.block_until_ready()
    end = time.perf_counter_ns()
    hbm1 = peak_hbm_bytes()
    if profile_forced() and not isinstance(op, EstimatorOperator):
        # Explicit profiling sessions (fit(profile=True) — the rows the
        # profile store persists for the optimizer) re-time on the warmed
        # path so recorded wall excludes one-time jit compile/tracing —
        # compile cost attributed as recompute cost would make every
        # trivial jittable node look cache-worthy (the sampled Profiler's
        # warmed re-time, applied to the measured walk). Non-estimator
        # operators are pure, so the extra execution cannot change state;
        # the FIRST output is still the one returned. Ambient
        # KEYSTONE_PROFILE=1 observation never pays the double execution.
        t0 = time.perf_counter_ns()
        warm = op.execute(deps)
        t_disp = time.perf_counter_ns()
        if isinstance(warm, jax.Array):
            warm.block_until_ready()
        end = time.perf_counter_ns()
    cost = None
    if (
        isinstance(op, TransformerOperator)
        and deps
        and hasattr(deps[0], "shape")
        and hasattr(deps[0], "dtype")
    ):
        cost = node_cost_analysis(op.transformer, deps[0])
    profile.record_node(
        label,
        wall_ns=end - t0,
        dispatch_ns=t_disp - t0,
        flops=(cost or {}).get("flops"),
        bytes_accessed=(cost or {}).get("bytes_accessed"),
        out_nbytes=getattr(out, "nbytes", None),
        hbm_delta=(
            hbm1 - hbm0 if hbm0 is not None and hbm1 is not None else None
        ),
        cache="miss",
        queue_wait_ns=queue_wait_ns,
        worker=worker,
        digest=digest,
        out_rows=_out_rows(out),
        out_shape=_span_shape(out),
        # Mesh-width provenance on every measured row: a 1-shard profile
        # is visibly 1-shard, and (with the store fingerprint's
        # device_count) can never size a wider mesh's plan.
        data_shards=value_data_shards(out),
    )
    if tracer is not None:
        # The profiled walk keeps explicit endpoints (a forced profile
        # re-times the warm run, and trace_report --fit must agree with the
        # profile's rows): ring only, no mirror in a profiler trace.
        tracer.record(
            "node:" + label, "executor", t0, end,
            cache="miss", shape=_span_shape(out), profiled=True, **extra,
        )
    return out


#: Thread-local flag marking "this thread is a parallel-walk worker": an
#: estimator fit that internally applies pipelines (fisher featurizers,
#: auto-cache profiling) re-enters ``execute_many`` on a pool thread, and
#: a nested walk must take the serial path instead of spawning a second
#: pool under the first (bounded concurrency stays bounded).
_walk_tls = threading.local()

_pool_lock = threading.Lock()
_shared_pool = None
_shared_pool_workers = 0


def _exec_pool(workers: int):
    """The process-wide executor worker pool, built lazily and reused
    across walks (the ``active_tracer()`` memo idiom): a streamed
    per-batch apply loop must not pay thread spawn/join on every walk.
    Rebuilt when the requested width changes; the old pool's threads
    drain without blocking the caller."""
    global _shared_pool, _shared_pool_workers
    from concurrent.futures import ThreadPoolExecutor

    with _pool_lock:
        if _shared_pool is None or _shared_pool_workers != workers:
            if _shared_pool is not None:
                _shared_pool.shutdown(wait=False)
            _shared_pool = ThreadPoolExecutor(
                max_workers=workers, thread_name_prefix="keystone-exec"
            )
            _shared_pool_workers = workers
        return _shared_pool


class _ParallelWalk:
    """Dependency-counting ready-set scheduler over one executor walk.

    The serial walk's execution loop, parallelized: every node of the
    (already cache-cut) ``order`` becomes a task; a node dispatches onto
    a bounded ``ThreadPoolExecutor`` the moment its inputs are resolved,
    so independent branches — the ImageNet SIFT|LCS featurizer's two
    fisher fronts, parallel text encoders — run concurrently, and a
    host-bound node (native SIFT, JPEG decode, tokenize) stops blocking
    sibling-branch device work. Jittable device nodes stay non-blocking:
    ``op.execute`` rides JAX async dispatch, returning array futures the
    workers never materialize — a value is only consumed host-side at
    estimator fits and host transformers, exactly where the serial walk
    would block too.

    Semantics preserved bit-identically (the scheduler reorders only
    provably independent nodes; per-node math is untouched):

    - cache cuts: persistent-cache hits were already resolved as leaves
      by the discovery pass — this walk never sees their subgraphs;
    - structural dedup: the FIRST node (in topological order) with a
      given prefix hash is the hash's owner and executes; same-hash
      duplicates become memo tasks that wait for the owner and copy its
      value — two duplicates can never compute concurrently;
    - fit/persist cache writes happen under the walk lock, on the same
      paths the serial loop uses;
    - a fault on a worker thread cancels the remaining schedule and
      re-raises on the calling thread (chaos parity with serial).

    Shared state (``values``/``by_hash``/``pend`` and the session cache
    writes) is guarded by ``self._lock``; mutation outside it lives only
    in ``*_locked`` methods, and ``_run_node_worker`` is registered in
    keystone-lint's ``KNOWN_THREAD_TARGETS`` so KL001 covers the pool
    threads.
    """

    def __init__(self, executor, graph, order, values, by_hash, hmemo,
                 d_of, tracer, profile, workers, node_digests=None):
        self.ex = executor
        self.graph = graph
        self.values = values
        self.by_hash = by_hash
        self.hashes = hmemo
        self.tracer = tracer
        self.profile = profile
        self.workers = workers
        # Precomputed in the single-threaded build phase (like dks): the
        # shared digest memo is never touched from a worker thread.
        self.node_digests: Dict[NodeId, Any] = node_digests or {}
        # The build thread's context, copied into every pool task: the
        # profile_scope() contextvar (and anything else context-scoped)
        # must follow the walk onto its workers — without this, a
        # fit(profile=True) parallel walk would lose the forced scope on
        # pool threads while keeping it on the serial path.
        self._ctx = contextvars.copy_context()
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._pool = None
        self._error: Optional[BaseException] = None
        self._stop = False
        self._inflight = 0
        self._remaining = len(order)
        self._ready_ns: Dict[NodeId, int] = {}
        # Build phase (single-threaded): hash ownership, per-node pending
        # counts, and the dependent edges the completions will decrement.
        # Estimator disk-cache digests are precomputed HERE so the shared
        # digest memo is never touched from a worker thread.
        self.is_memo: set = set()
        self.dks: Dict[NodeId, Any] = {}
        self.pend: Dict[NodeId, int] = {}
        self.dependents: Dict[NodeId, List[NodeId]] = {}
        self.initial: List[NodeId] = []
        owner_of_hash: Dict[int, NodeId] = {}
        for nid in order:
            h = hmemo[nid]
            if h in by_hash:
                # Produced by a discovery-phase cache hit: a memo task
                # with no prerequisites (the value already exists).
                self.is_memo.add(nid)
                deps = set()
            elif h in owner_of_hash:
                # Duplicate: wait for the hash owner, then copy its
                # value off by_hash (the dependency edge IS the link —
                # no separate owner lookup exists at execute time).
                self.is_memo.add(nid)
                deps = {owner_of_hash[h]}
            else:
                owner_of_hash[h] = nid
                deps = {
                    d for d in graph.dependencies[nid]
                    if isinstance(d, NodeId) and d not in values
                }
                op = graph.operators[nid]
                if (
                    isinstance(op, EstimatorOperator)
                    and executor.env.disk_cache is not None
                ):
                    self.dks[nid] = d_of(nid)
            self.pend[nid] = len(deps)
            for d in deps:
                self.dependents.setdefault(d, []).append(nid)
            if not deps:
                self.initial.append(nid)

    def run(self) -> None:
        """Drive the schedule to completion on the shared bounded pool;
        block the caller until every node resolved (or re-raise the
        first worker fault once in-flight tasks drained). The exit wait
        covers BOTH completion shapes — every submitted task retires
        through ``_finish_locked`` before the loop can exit, so no task
        of this walk can still be running when run() returns."""
        pool = _exec_pool(self.workers)
        with self._lock:
            self._pool = pool
            for nid in self.initial:
                self._submit_locked(nid)
            while self._remaining and not (
                self._stop and self._inflight == 0
            ):
                self._cv.wait()
            self._pool = None
        if self._error is not None:
            raise self._error

    def _submit_locked(self, nid: NodeId) -> None:
        """Hand one ready node to the pool (caller holds the lock)."""
        import time

        if self._stop or self._pool is None:
            return
        self._ready_ns[nid] = time.perf_counter_ns()
        # submit BEFORE the in-flight increment: if the shared pool was
        # rebuilt under this walk (a width change from another thread),
        # submit raises without leaking a phantom in-flight count — the
        # raise surfaces as the walk's error instead of wedging run()'s
        # drain wait forever. The spawned task cannot observe the
        # bookkeeping early: its first action takes this same lock.
        # Each task runs under its own COPY of the walk's build-thread
        # context (a Context cannot be entered concurrently).
        self._pool.submit(
            self._ctx.copy().run, self._run_node_worker, nid
        )
        self._inflight += 1

    def _run_node_worker(self, nid: NodeId) -> None:
        """One pool task (a keystone-lint KNOWN_THREAD_TARGETS entry):
        execute one ready node outside the lock, publish its value, and
        schedule dependents that became ready. Any exception cancels the
        remaining schedule and surfaces on the calling thread."""
        import time

        with self._lock:
            if self._stop:
                # A sibling already faulted: tasks queued behind it must
                # not burn work (estimator fits, disk writes) on a walk
                # that is already doomed — the serial loop stops at the
                # first fault, so the parallel walk does too.
                self._finish_locked()
                return
        _walk_tls.active = True
        try:
            queue_wait_ns = time.perf_counter_ns() - self._ready_ns[nid]
            out = self._execute(nid, queue_wait_ns)
            with self._lock:
                self._publish_locked(nid, out)
                self._finish_locked()
        except BaseException as e:  # lint: broad-ok re-raised on the caller by run()
            with self._lock:
                if self._error is None:
                    self._error = e
                self._stop = True
                self._finish_locked()
        finally:
            _walk_tls.active = False

    def _finish_locked(self) -> None:
        """Retire this task from the in-flight count and wake the caller
        (caller holds the lock). ONE place decrements, so the
        publish-succeeded and fault paths can never double-count."""
        self._inflight -= 1
        self._cv.notify_all()

    def _execute(self, nid: NodeId, queue_wait_ns: int):
        """The per-node body of the serial loop, minus the shared-state
        writes (those happen in ``_publish_locked``). Runs on a pool
        thread with every dependency value already published."""
        graph = self.graph
        op = graph.operators[nid]
        h = self.hashes[nid]
        if nid in self.is_memo:
            out = self.by_hash[h]
            if self.tracer is not None:
                self.tracer.instant(
                    "node:" + op.label(), "executor", cache="memo"
                )
            if self.profile is not None:
                self.profile.record_node(op.label(), cache="memo")
            return out
        deps = [self.values[d] for d in graph.dependencies[nid]]
        if self.tracer is None and self.profile is None:
            out = op.execute(deps)
        else:
            out = _observed_execute(
                op, deps, self.tracer, self.profile,
                worker=threading.current_thread().name,
                queue_wait_ns=queue_wait_ns,
                digest=self.node_digests.get(nid),
            )
        if isinstance(op, EstimatorOperator):
            # Cross-process store: content-addressed, atomic put — safe
            # off the lock (hash ownership makes the key unique per walk).
            dk = self.dks.get(nid)
            if dk is not None:
                self.ex.env.disk_cache.put(dk, out)
        return out

    def _publish_locked(self, nid: NodeId, out) -> None:
        """Store one node's value, run the session-cache writes the
        serial loop does at this point, and wake newly-ready dependents
        (caller holds the lock)."""
        graph = self.graph
        op = graph.operators[nid]
        h = self.hashes[nid]
        self.values[nid] = out
        env = self.ex.env
        if nid not in self.is_memo:
            self.by_hash[h] = out
            if isinstance(op, EstimatorOperator):
                self.ex._cache_fit(graph, nid, h, op, out)
            if getattr(op, "persist", False):
                env.node_cache[h] = (out, self.ex._prefix_pins(graph, nid))
        elif getattr(op, "persist", False) and h not in env.node_cache:
            # A cache node hashes identically to its dependency (it's an
            # identity), so it lands on the memo path — still persist.
            env.node_cache[h] = (out, self.ex._prefix_pins(graph, nid))
        self._remaining -= 1
        for dep in self.dependents.get(nid, ()):
            self.pend[dep] -= 1
            if self.pend[dep] == 0:
                self._submit_locked(dep)


class GraphExecutor:
    def __init__(self, env: "PipelineEnv"):
        self.env = env

    def execute_many(
        self, graph: Graph, targets: Sequence[GraphId]
    ) -> Dict[GraphId, Any]:
        """Evaluate all targets in one pass with shared memoization.

        The walk CUTS at persistent-cache hits: a node whose structural hash
        is already in the fit/node cache becomes a leaf and its upstream
        subgraph is never visited — cached values short-circuit
        recomputation, not just value storage.
        """
        from keystone_tpu.utils.metrics import active_profile, active_tracer

        # Resolved once per execution walk (the active_plan discipline):
        # the untraced/unprofiled walk pays one None check per node,
        # nothing more.
        tracer = active_tracer()
        profile = active_profile()
        for t in targets:
            if isinstance(t, SourceId):
                _no_sources(t)
        hmemo: Dict[GraphId, int] = {}
        dmemo: Dict[GraphId, Any] = {}

        def h_of(nid: GraphId) -> int:
            return structural_hash(graph, nid, _no_sources, hmemo)

        def d_of(nid: GraphId):
            if self.env.disk_cache is None:
                return None
            dk = structural_digest(graph, nid, dmemo)
            if dk is None:
                return None
            # Salt with the numeric regime: a fit computed under different
            # dtype/precision settings is a different artifact. Platform is
            # deliberately NOT included — CPU/TPU runs are treated as
            # numerically equivalent the way the reference treats local[n]
            # vs cluster (SURVEY.md §4 [unverified]).
            from keystone_tpu.config import config
            from keystone_tpu.workflow.fingerprint import digest_tree

            return digest_tree(
                (
                    "v1",
                    dk,
                    config.default_dtype,
                    config.accum_dtype,
                    config.solver_precision,
                    config.solver_storage_dtype,
                )
            )

        values: Dict[GraphId, Any] = {}
        by_hash: Dict[int, Any] = {}
        order: List[GraphId] = []
        seen = set()
        stack: List[tuple] = [(t, False) for t in targets]
        while stack:
            gid, processed = stack.pop()
            if processed:
                order.append(gid)
                continue
            if gid in seen or isinstance(gid, SourceId):
                continue
            seen.add(gid)
            op = graph.operators[gid]
            h = h_of(gid)
            hit = None
            if isinstance(op, EstimatorOperator) and h in self.env.fit_cache:
                hit = self.env.fit_cache[h][0]
            elif isinstance(op, EstimatorOperator):
                dk = d_of(gid)
                if dk is not None:
                    hit = self.env.disk_cache.get(dk)
                    if hit is not None:  # promote to the session cache too
                        self._cache_fit(graph, gid, h, op, hit)
            elif h in self.env.node_cache:
                hit = self.env.node_cache[h][0]
            if hit is not None:
                values[gid] = by_hash[h] = hit
                if tracer is not None:
                    tracer.instant(
                        "node:" + op.label(), "executor", cache="hit"
                    )
                if profile is not None:
                    profile.record_node(op.label(), cache="hit")
                continue  # leaf: do not descend into its dependencies
            stack.append((gid, True))
            for dep in graph.dependencies[gid]:
                if dep not in seen and isinstance(dep, NodeId):
                    stack.append((dep, False))

        # Stage-parallel walk (KEYSTONE_EXEC_WORKERS / config.exec_workers,
        # resolved once per walk like the tracer): > 0 dispatches the
        # execution loop below onto a bounded worker pool instead —
        # identical per-node work, identical cache writes, bit-identical
        # values; only provably independent nodes reorder. 0 (default)
        # falls through to the legacy serial loop, byte for byte. A walk
        # re-entered from a pool thread (an estimator fitting sub-pipelines)
        # always runs serial so concurrency stays bounded by ONE pool.
        # Digest every node the walk will execute under a FORCED profile
        # scope (fit(profile=True) / profile_scope() — the rows the
        # profile store persists): the measured row's content-stable
        # key, shared with the disk cache's memo so dataset fingerprints
        # hash once. Ambient KEYSTONE_PROFILE=1 observation never pays
        # the digest walk — only forced sessions can save store entries,
        # so hashing each per-batch dataset there would buy nothing.
        node_digests: Dict[GraphId, Any] = {}
        if profile is not None:
            from keystone_tpu.utils.metrics import profile_forced

            if profile_forced():
                for nid in order:
                    node_digests[nid] = structural_digest(graph, nid, dmemo)

        if len(order) > 1 and not getattr(_walk_tls, "active", False):
            from keystone_tpu.config import config

            # Explicit setting wins — including an explicitly exported
            # KEYSTONE_EXEC_WORKERS=0 (the byte-identical serial pin);
            # only the UNSET default falls back to the profile-guided
            # session plan (PlanResourcesRule), which only exists after
            # a measured-profile hit. The env is read live so a late
            # export is honored, not the config-instantiation snapshot.
            from keystone_tpu.config import resolved_exec_workers

            env_workers = resolved_exec_workers()
            if env_workers is not None:
                workers = env_workers
            else:
                workers = config.exec_workers
                if not workers:
                    workers = int(
                        self.env.resource_plan.get("exec_workers", 0) or 0
                    )
            if workers and workers > 0:
                _ParallelWalk(
                    self, graph, order, values, by_hash, hmemo, d_of,
                    tracer, profile, workers, node_digests=node_digests,
                ).run()
                return values

        for nid in order:
            h = h_of(nid)
            op = graph.operators[nid]
            if h in by_hash:
                values[nid] = by_hash[h]
                if tracer is not None:
                    tracer.instant(
                        "node:" + op.label(), "executor", cache="memo"
                    )
                if profile is not None:
                    profile.record_node(op.label(), cache="memo")
                # A cache node hashes identically to its dependency (it's an
                # identity), so it lands here — still persist its value.
                if getattr(op, "persist", False) and h not in self.env.node_cache:
                    self.env.node_cache[h] = (
                        values[nid],
                        self._prefix_pins(graph, nid),
                    )
                continue
            deps = [values[d] for d in graph.dependencies[nid]]
            if tracer is None and profile is None:
                out = op.execute(deps)
            else:
                out = _observed_execute(
                    op, deps, tracer, profile,
                    digest=node_digests.get(nid),
                )
            values[nid] = by_hash[h] = out
            if isinstance(op, EstimatorOperator):
                self._cache_fit(graph, nid, h, op, out)
                dk = d_of(nid)
                if dk is not None:
                    self.env.disk_cache.put(dk, out)
            if getattr(op, "persist", False):
                self.env.node_cache[h] = (out, self._prefix_pins(graph, nid))
        return values

    def _cache_fit(self, graph: Graph, nid: NodeId, h: int, op, out) -> None:
        """Cache a fitted transformer, scoped to the estimator's lifetime.

        The entry pins every prefix object except the estimator itself, which
        is held weakly with an eviction callback: when the user drops the
        estimator (and its pipelines), the entry — and the training data it
        pins — is freed, and the now-recyclable ids can never produce a stale
        hash hit because eviction precedes reuse.
        """
        import weakref

        estimator = op.estimator
        pins = tuple(
            p for p in self._prefix_pins(graph, nid) if p is not estimator
        )
        fit_cache = self.env.fit_cache
        try:
            keeper: Any = weakref.ref(
                estimator, lambda _ref, h=h: fit_cache.pop(h, None)
            )
        except TypeError:  # not weak-referenceable: pin strongly
            keeper = estimator
        fit_cache[h] = (out, pins, keeper)

    @staticmethod
    def _prefix_pins(graph: Graph, nid: NodeId) -> tuple:
        """Strong references to every object whose id() feeds the prefix hash
        of ``nid``. While a cache entry holds its pins, CPython cannot recycle
        those ids, so a hash hit always means the same live objects."""
        pins = []
        for n in graph.reachable([nid]):
            pins.extend(graph.operators[n].pinned_objects())
        return tuple(pins)

    def execute(self, graph: Graph, target: GraphId) -> Any:
        return self.execute_many(graph, [target])[target]

    def fit_estimators(self, graph: Graph, sink: GraphId) -> Graph:
        """Force every estimator reachable from ``sink`` and rewrite the graph
        so each DelegatingOperator becomes a concrete TransformerOperator.

        This is the `Pipeline.fit` lowering: the result graph is
        transformer-only on the inference path.
        """
        # The resource plan the optimizer pass writes is scoped to THIS
        # fit's walk: a nested optimization (an estimator fitting a
        # sub-pipeline, an interleaved apply) saves the outer plan at
        # its own entry and restores it here on exit, so the outer
        # solve keeps reading the plan computed FOR it.
        prior_plan = dict(self.env.resource_plan)
        graph = self.env.optimizer.execute(graph, [sink])
        order = graph.reachable([sink])
        est_nodes = [
            n for n in order if isinstance(graph.operators[n], EstimatorOperator)
        ]
        try:
            if est_nodes:
                fitted = self.execute_many(graph, est_nodes)
            else:
                fitted = {}
        finally:
            self.env.resource_plan.clear()
            self.env.resource_plan.update(prior_plan)
        ops = dict(graph.operators)
        dps = dict(graph.dependencies)
        for nid in order:
            op = graph.operators[nid]
            if isinstance(op, DelegatingOperator):
                est_dep, input_dep = graph.dependencies[nid]
                # See through identity cache nodes between estimator and
                # delegating consumer.
                while (
                    est_dep in graph.operators
                    and getattr(graph.operators[est_dep], "persist", False)
                ):
                    est_dep = graph.dependencies[est_dep][0]
                if est_dep in fitted:
                    ops[nid] = TransformerOperator(fitted[est_dep])
                    dps[nid] = (input_dep,)
        # Prune: drops the now-unreferenced estimator nodes and their training
        # DatasetOperator subtrees so a fitted pipeline doesn't pin the
        # training set in memory.
        return Graph(ops, dps).pruned([sink])

    def serving_chain(self, graph: Graph, source: SourceId, sink: GraphId):
        """Lower a FITTED pipeline graph to the one transformer the serving
        layer AOT-compiles: optimize (fusing jittable chains), then walk
        sink→source requiring jittable TransformerOperators. A gather
        join lowers to a ``GatherTransformer`` over its lowered branches,
        so the two-branch featurizers stay one program. Identity cache
        nodes are seen through; anything else (unfitted estimators, host
        nodes) is refused with an error naming the offender — the serving
        engine compiles ONE program per bucket and cannot host-hop
        mid-chain.
        """
        from keystone_tpu.workflow.pipeline import (
            FusedTransformer,
            GatherTransformer,
        )

        g = self.env.optimizer.execute(graph, [sink])

        def fuse(stages):
            return stages[0] if len(stages) == 1 else FusedTransformer(stages)

        def lower(gid) -> List[Any]:
            """The stages computing node ``gid`` from the pipeline input."""
            chain: List[Any] = []
            while gid != source:
                if isinstance(gid, SourceId):
                    raise ValueError(
                        f"serve path ends at foreign source {gid!r}, not "
                        "the pipeline's own input"
                    )
                op = g.operators[gid]
                deps = g.dependencies[gid]
                if getattr(op, "persist", False):  # identity Cache node
                    gid = deps[0]
                    continue
                if isinstance(op, GatherOperator):
                    # Every branch runs back to the input on its own.
                    chain.append(
                        GatherTransformer([fuse(lower(d)) for d in deps])
                    )
                    break
                if not isinstance(op, TransformerOperator):
                    raise TypeError(
                        f"cannot compile {op.label()} for serving: the "
                        "serve path must be fitted transformers (fit the "
                        "pipeline first; estimator/host nodes cannot join "
                        "the single-program bucketed executable)"
                    )
                if not op.transformer.jittable:
                    raise TypeError(
                        f"{type(op.transformer).__name__} is not jittable; "
                        "the AOT serving path compiles the whole chain as "
                        "one XLA program"
                    )
                chain.append(op.transformer)
                gid = deps[0]
            chain.reverse()
            return chain

        chain = lower(sink)
        if not chain:
            raise ValueError("pipeline has no transformers on the serve path")
        return fuse(chain)


class PipelineEnv:
    """Session state: optimizer, executor, and persistent caches.

    Ref: workflow/PipelineEnv.scala [unverified].
    """

    _instance: Optional["PipelineEnv"] = None

    def __init__(self):
        from keystone_tpu.config import resolved_cache_dir
        from keystone_tpu.workflow.optimizer import default_optimizer

        self.optimizer = default_optimizer()
        self.executor = GraphExecutor(self)
        # structural hash of estimator node -> fitted Transformer
        self.fit_cache: Dict[int, Any] = {}
        # structural hash -> persisted value (auto-cache rule / Cacher nodes)
        self.node_cache: Dict[int, Any] = {}
        # Session-scoped profile-guided plan (workflow/rules.py
        # PlanResourcesRule): e.g. {"exec_workers": 4,
        # "solve_chunk_rows": 8192}. Consulted only where the explicit
        # config knob is unset, so a user setting always wins.
        self.resource_plan: Dict[str, Any] = {}
        # Cross-process fitted-prefix store, keyed by content digest; the
        # env-presence-over-config precedence lives in config.py so the
        # os.environ read stays out of this module (keystone-lint KL003).
        cache_dir = resolved_cache_dir()
        self.disk_cache = None
        if cache_dir:
            from keystone_tpu.workflow.disk_cache import DiskFitCache

            try:
                self.disk_cache: Optional["DiskFitCache"] = DiskFitCache(
                    cache_dir
                )
            except OSError as e:  # uncreatable dir: degrade, never abort
                import logging

                logging.getLogger("keystone_tpu").warning(
                    "disk fit cache disabled: cannot create %s (%s)",
                    cache_dir,
                    e,
                )

    @classmethod
    def get(cls) -> "PipelineEnv":
        if cls._instance is None:
            cls._instance = PipelineEnv()
        return cls._instance

    @classmethod
    def reset(cls) -> None:
        cls._instance = None

    def clear_caches(self) -> None:
        """Drop all memoized fits, persisted values, and optimizer-held state
        (frees pinned data)."""
        self.fit_cache.clear()
        self.node_cache.clear()
        self.resource_plan.clear()
        for _name, rules, _iters in getattr(self.optimizer, "batches", []):
            for rule in rules:
                clear = getattr(rule, "clear_cache", None)
                if clear is not None:
                    clear()

    def optimize_and_execute(self, graph: Graph, sink: GraphId) -> Any:
        save = self._profile_save_ctx(graph, sink)
        # Scope this pass's resource plan to this execution (see
        # fit_estimators): the pass clears-then-writes the plan, the
        # walk consumes it, and the OUTER pass's plan is restored on
        # exit so a nested optimization never retires a plan some
        # enclosing solve is still reading.
        prior_plan = dict(self.resource_plan)
        g = self.optimizer.execute(graph, [sink])
        try:
            out = self.executor.execute(g, sink)
        finally:
            self.resource_plan.clear()
            self.resource_plan.update(prior_plan)
        if save is not None:
            save()
        return out

    @staticmethod
    def _profile_save_ctx(graph: Graph, sink: GraphId):
        """When this execution is under a FORCED profile scope (an
        explicit ``profile_scope()`` / ``fit(profile=True)`` session —
        ambient KEYSTONE_PROFILE=1 deliberately does not write store
        entries per apply) and a profile store is configured, return a
        closure that persists the walk's measured delta under THIS
        graph's digest — so a profiled apply makes later applies of the
        same pipeline-over-data a measured-store hit too, completing the
        profile-once-optimize-forever workflow on the apply side."""
        from keystone_tpu.config import resolved_profile_store
        from keystone_tpu.utils.metrics import profile_forced

        if not profile_forced() or not resolved_profile_store():
            return None
        from keystone_tpu.utils.metrics import (
            resource_profile,
            runtime_fingerprint,
        )
        from keystone_tpu.workflow.profile_store import (
            ProfileStoreError,
            pipeline_profile_digest,
            save_profile,
        )

        digest = pipeline_profile_digest(graph, sink)
        if digest is None:
            return None
        mark = resource_profile.mark()
        dmark = resource_profile.mark_digests()

        def save():
            digests = resource_profile.digest_rows(since=dmark)
            if not digests:
                return  # nothing executed (full cache hit): keep the old entry
            try:
                save_profile(
                    digest, digests, resource_profile.rows(since=mark),
                    fingerprint=runtime_fingerprint(),
                )
            except ProfileStoreError as e:
                import logging

                logging.getLogger("keystone_tpu").warning(
                    "apply profile not saved: %s", e
                )

        return save

    def execute(self, graph: Graph, sink: GraphId) -> Any:
        return self.executor.execute(graph, sink)
