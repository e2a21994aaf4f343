"""Least squares via normal equations.

Ref: ml-matrix `NormalEquations.solveLeastSquares` — AᵀA and AᵀB accumulated
with `treeAggregate`, Cholesky solve on the driver (SURVEY.md §2.2, §3.2)
[unverified]. Here: per-shard grams + `psum` over ICI, replicated on-device
Cholesky (every chip solves the small (d, d) system redundantly — cheaper
than shipping it anywhere).
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.scipy.linalg import cho_factor, cho_solve
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from keystone_tpu.config import config
from keystone_tpu.utils.mesh import register_reshard_adapter
from keystone_tpu.linalg.row_matrix import (
    RowMatrix,
    _precision,
    donate_argnums,
    sharded_rowsum,
    solver_matmul,
    storage_dtype,
)


@partial(jax.jit, static_argnames=("refine_steps",))
def _chol_solve(gram, atb, lam, refine_steps: int = 1):
    d = gram.shape[0]
    reg = gram + lam * jnp.eye(d, dtype=gram.dtype)
    c, low = cho_factor(reg)
    W = cho_solve((c, low), atb)
    # Iterative refinement: each step removes most of the factorization
    # rounding error, pushing the f32 solve toward the f64 oracle the
    # reference's Breeze/LAPACK path produces (SURVEY.md §7 hard part 2).
    for _ in range(refine_steps):
        resid = atb - jnp.matmul(reg, W, precision=lax.Precision.HIGHEST)
        W = W + cho_solve((c, low), resid)
    return W


def solve_least_squares_normal(
    A: RowMatrix, B: RowMatrix, lam: float = 0.0, refine_steps: int = 1
) -> jax.Array:
    """argmin_W ||A W - B||² + lam ||W||²  →  (d, k) replicated array."""
    gram = A.gram()
    atb = A.atb(B)
    return _chol_solve(
        gram, atb, jnp.asarray(lam, dtype=gram.dtype), refine_steps
    )


@lru_cache(maxsize=None)
def _accum_gram_atb_fn(mesh: Mesh, axis: str, precision):
    """One fused program per chunk: (AᵀA, AᵀB) — reduced over rows in the
    canonical width-independent fold (``sharded_rowsum``, so a stream
    checkpointed on one mesh width resumes on another bit-identically) —
    added into the running accumulators. Everything is donated — the
    accumulators because the previous values are dead once the sums
    exist, and the CHUNK buffers because the overlapped loop never
    touches a chunk after its accumulation step, so XLA recycles their
    HBM for the next transfer and device residency stays at two in-flight
    chunk buffers regardless of stream length."""
    width = mesh.shape[axis]

    def local(gram, atb, a, b):
        g, t = sharded_rowsum(
            lambda ab, bb: (
                solver_matmul(ab.T, ab, precision),
                solver_matmul(ab.T, bb, precision),
            ),
            axis, width, (a, b), scope="coll.gram",
        )
        return gram + g, atb + t

    sm = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis)),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(sm, donate_argnums=donate_argnums(mesh, 0, 1, 2, 3))


def _put_labeled_chunk(chunk):
    X_chunk, Y_chunk = chunk
    if Y_chunk is None:
        raise ValueError("chunked solve needs labeled batches")
    A = RowMatrix.from_array(X_chunk, dtype=storage_dtype())
    B = RowMatrix.from_array(Y_chunk)
    return A, B


def planned_chunk_rows() -> int:
    """The PLANNED per-transfer row bound: ``config.solve_chunk_rows``
    (env KEYSTONE_SOLVE_CHUNK_ROWS) when set, else the session plan the
    profile-guided ``PlanResourcesRule`` wrote from measured
    bytes-per-row vs the HBM budget (``PipelineEnv.resource_plan``).
    An explicitly exported KEYSTONE_SOLVE_CHUNK_ROWS wins outright —
    including an explicit 0, which pins reactive-halving-only (the
    planner never overrides an explicit setting; the env is read live,
    not the config-instantiation snapshot). The unset default 0 falls
    through to the plan."""
    from keystone_tpu.config import resolved_solve_chunk_rows

    env_rows = resolved_solve_chunk_rows()
    if env_rows is not None:
        return env_rows
    rows = int(config.solve_chunk_rows or 0)
    if rows > 0:
        return rows
    from keystone_tpu.workflow.executor import PipelineEnv

    env = PipelineEnv._instance  # never CREATE an env from a solver
    if env is not None:
        return int(env.resource_plan.get("solve_chunk_rows", 0) or 0)
    return 0


def _put_chunks_resilient(chunk, plan, retry):
    """H2D one labeled chunk with OOM recovery; returns the (A, B) pairs
    to accumulate, in row order.

    A chunk larger than the PLANNED row bound (``planned_chunk_rows``:
    the profile-guided HBM-budget plan, or the explicit knob) is split to
    plan size BEFORE any transfer is attempted — the memory-safe-by-
    construction path (arXiv:2206.14148) that makes the reactive halving
    below a fallback instead of the mechanism.

    RESOURCE_EXHAUSTED at the transfer (real, or the harness's ``oom``
    site) is retried with backoff — transient allocation pressure clears,
    and a successful retry transfers the SAME host bytes, so the solve
    stays bit-identical. OOM that survives the whole retry budget is
    structural (the chunk itself doesn't fit): halve its rows and recurse,
    recording the downshift in ``reliability_counters``. Sub-chunks
    accumulate in row order, so the split solve is the same least-squares
    sum at a different flop grouping — numerically equivalent, though not
    bit-identical to the unsplit run.
    """
    import numpy as np

    X_chunk, Y_chunk = chunk
    if Y_chunk is None:
        raise ValueError("chunked solve needs labeled batches")

    planned = planned_chunk_rows()
    if planned > 0:
        n_rows = int(np.asarray(X_chunk).shape[0])
        if n_rows > planned:
            from keystone_tpu.utils.metrics import reliability_counters

            reliability_counters.bump("planned_chunk_splits")
            out = []
            for s in range(0, n_rows, planned):
                out.extend(_put_chunks_resilient(
                    (X_chunk[s:s + planned], Y_chunk[s:s + planned]),
                    plan, retry,
                ))
            return out

    def attempt():
        if plan is not None:
            plan.maybe_raise("oom")
        return _put_labeled_chunk(chunk)

    from keystone_tpu.utils.reliability import is_oom

    try:
        if retry is None:
            return [attempt()]
        return [retry.call(attempt, site="h2d", counter="h2d_retries")]
    except Exception as exc:
        if not is_oom(exc):
            raise
        n = int(np.asarray(X_chunk).shape[0])
        if n <= 1:
            raise  # can't split a single row: genuinely out of memory
        import logging

        from keystone_tpu.utils.metrics import reliability_counters

        reliability_counters.bump("oom_downshifts")
        logging.getLogger("keystone_tpu").warning(
            "chunked solve: device OOM persisted across retries on a "
            "%d-row chunk; halving and re-transferring", n,
        )
        mid = n // 2
        lo = (X_chunk[:mid], Y_chunk[:mid])
        hi = (X_chunk[mid:], Y_chunk[mid:])
        return _put_chunks_resilient(lo, plan, retry) + _put_chunks_resilient(
            hi, plan, retry
        )


def _chol_solve_maybe_traced(tracer, gram, atb, lam, refine_steps):
    """The final replicated Cholesky, spanned when tracing is live."""
    lam_arr = jnp.asarray(lam, dtype=gram.dtype)
    if tracer is None:
        return _chol_solve(gram, atb, lam_arr, refine_steps)
    t0 = tracer.now()
    out = _chol_solve(gram, atb, lam_arr, refine_steps)
    tracer.record("solve.cholesky", "solver", t0, d=int(gram.shape[0]))
    return out


def _put_chunks_traced(chunk, plan, retry, tracer, idx: int):
    """``_put_chunks_resilient`` wrapped in a per-chunk H2D span (chunk
    index, rows, and how many OOM-downshift splits it took). The untraced
    path calls ``_put_chunks_resilient`` directly — zero added work."""
    import numpy as np

    t0 = tracer.now()
    out = _put_chunks_resilient(chunk, plan, retry)
    tracer.record(
        "solve.h2d", "solver", t0, chunk=idx,
        rows=int(np.asarray(chunk[0]).shape[0]), splits=len(out),
    )
    return out


_STREAM_CKPT_KEY = "stream_solve"


def _stream_ckpt_store(checkpoint_dir: str):
    from keystone_tpu.workflow.disk_cache import DiskCache

    return DiskCache(checkpoint_dir, suffix=".ckpt.pkl")


def _stream_fingerprint(first_chunk) -> dict:
    """Solve identity for checkpoint binding: shapes, dtypes, a probe of
    the stream's first record — enough to refuse resuming a different
    problem into these accumulators — plus the per-shard manifest (mesh
    width and data axis), so a snapshot folded under one mesh can never
    SILENTLY continue under another: a width change either migrates the
    snapshot through ``utils.mesh.reshard_state`` (elastic mesh, default
    on, counted) or refuses typed."""
    import numpy as np

    from keystone_tpu.utils.mesh import num_data_shards

    X, Y = first_chunk
    X = np.asarray(X)
    return {
        "d": int(X.shape[1]),
        "b_tail": tuple(int(t) for t in np.asarray(Y).shape[1:]),
        "accum_dtype": str(config.accum_dtype),
        "storage_dtype": str(jnp.dtype(storage_dtype())),
        "chunk_rows": int(X.shape[0]),
        "x0_probe": float(np.asarray(X[0], dtype=np.float64).sum()),
        "device_count": int(num_data_shards()),
        "data_axis": str(config.data_axis),
    }


def _reshard_stream_state(state, layout):
    """Elastic-mesh adapter for chunked-solve snapshots: the retained
    gram/AᵀB are full (d, d)/(d, b) f64 sums — placement-free, nothing
    per-shard to re-fold — so migration rewrites the fingerprint's mesh
    manifest onto ``layout`` and passes every accumulator byte through
    untouched. Torn payloads (accumulator shapes contradicting the
    fingerprint) refuse typed instead."""
    import numpy as np

    from keystone_tpu.utils.mesh import reshard_refused

    fp = dict(state.get("fingerprint") or {})
    gram, atb = state.get("gram"), state.get("atb")
    d = int(fp.get("d", -1))
    gram = np.asarray(gram) if gram is not None else None
    atb = np.asarray(atb) if atb is not None else None
    if (
        gram is None
        or atb is None
        or gram.shape != (d, d)
        or atb.shape[:1] != (d,)
        or int(state.get("chunks_done", -1)) < 0
    ):
        raise reshard_refused(
            "stream solve",
            "snapshot accumulators do not match their fingerprint "
            "(torn or partially written checkpoint)",
        )
    fp["device_count"] = int(layout.num_shards)
    fp["data_axis"] = str(layout.axis)
    return dict(state, fingerprint=fp)


register_reshard_adapter("stream_solve", _reshard_stream_state)


class _StreamCheckpointer:
    """THE checkpoint/resume protocol of the chunked solve — one
    implementation driven by both the overlapped and sync paths, so the
    fingerprint binding, skip accounting, every-K save cadence, and
    consume-on-success can never drift between them. Inert (every call a
    no-op) when constructed without a ``checkpoint_dir``."""

    def __init__(self, checkpoint_dir: str | None, checkpoint_every: int | None):
        self.store = (
            _stream_ckpt_store(checkpoint_dir)
            if checkpoint_dir is not None
            else None
        )
        every = (
            config.checkpoint_every
            if checkpoint_every is None
            else int(checkpoint_every)
        )
        #: Snapshot cadence K; 0 = resume-only (no mid-stream saves).
        self.every = max(0, every)
        self.fingerprint = None
        self.done = 0
        self.skip = 0
        self.gram_np = None
        self.atb_np = None

    def resume(self, first_chunk) -> None:
        """Bind to the stream's identity (call once, with the first host
        chunk) and load a matching snapshot if one exists."""
        if self.store is None:
            return
        import logging

        from keystone_tpu.utils.metrics import reliability_counters

        from keystone_tpu.utils.mesh import (
            mesh_resume_decision,
            reshard_state,
        )

        self.fingerprint = _stream_fingerprint(first_chunk)
        state = self.store.get(_STREAM_CKPT_KEY)
        if state is None:
            return
        # Pre-manifest snapshots (no device_count/data_axis keys) compare
        # with the absent keys backfilled as wildcards (the shared
        # mesh_resume_decision triage), so a legacy checkpoint of the
        # SAME problem still resumes after the manifest upgrade instead
        # of silently recomputing hours of accumulation. The same problem
        # on a different mesh width MIGRATES (elastic mesh, counted) or
        # refuses typed — never a wrong-answer resume, never a silent
        # restart.
        decision, saved_fp = mesh_resume_decision(
            state.get("fingerprint"), self.fingerprint, "stream solve"
        )
        if decision == "fresh":
            logging.getLogger("keystone_tpu").warning(
                "stream-solve checkpoint holds a different solve "
                "(fingerprint mismatch); starting fresh"
            )
            return
        if decision == "migrate":
            state = reshard_state(
                dict(state, fingerprint=saved_fp), family="stream_solve"
            )
        reliability_counters.bump("checkpoints_resumed")
        self.skip = int(state["chunks_done"])
        self.gram_np, self.atb_np = state["gram"], state["atb"]

    def skipping(self) -> bool:
        """True while fast-forwarding past already-accumulated chunks —
        the caller drops the chunk unread (no transfer, no gram)."""
        if self.done < self.skip:
            self.done += 1
            from keystone_tpu.utils.metrics import reliability_counters

            reliability_counters.bump("chunks_skipped_on_resume")
            return True
        return False

    def restored(self, cdtype):
        """(gram, atb) from the snapshot in the accumulation dtype, or
        (None, None) on a fresh start. The numpy round-trip is bit-exact,
        which is what makes resumed solves bit-identical."""
        if self.gram_np is None:
            return None, None
        return (
            jnp.asarray(self.gram_np, dtype=cdtype),
            jnp.asarray(self.atb_np, dtype=cdtype),
        )

    def chunk_done(self, gram, atb) -> bool:
        """Count one accumulated chunk; snapshot at the cadence. The D2H
        fetch is the only sync this adds, once per K chunks; the atomic
        DiskCache rewrite means a kill mid-save leaves the previous
        complete snapshot. Returns True when a snapshot was written (the
        progress journey stamps its checkpoint age from this)."""
        self.done += 1
        if (
            self.store is None
            or self.every <= 0
            or self.done % self.every != 0
        ):
            return False
        import numpy as np

        from keystone_tpu.utils.metrics import reliability_counters

        self.store.put(
            _STREAM_CKPT_KEY,
            {
                "fingerprint": dict(self.fingerprint),
                "chunks_done": int(self.done),
                "gram": np.asarray(gram),
                "atb": np.asarray(atb),
            },
            overwrite=True,
        )
        from keystone_tpu.utils.mesh import write_mesh_manifest

        write_mesh_manifest(self.store.root, self.fingerprint)
        reliability_counters.bump("checkpoints_written")
        return True

    def consume(self) -> None:
        """Delete the snapshot: it belongs to the solve that just
        completed over it, and a later solve over changed data must never
        silently resume stale accumulators."""
        if self.store is not None:
            self.store.delete(_STREAM_CKPT_KEY)


def solve_least_squares_chunked(
    batches, lam: float = 0.0, refine_steps: int = 1,
    prefetch_depth: int | None = None,
    checkpoint_dir: str | None = None,
    checkpoint_every: int | None = None,
) -> jax.Array:
    """Normal-equation solve over an out-of-core row stream.

    ``batches`` yields (X_chunk, Y_chunk) row batches (see
    loaders.stream.BatchIterator); AᵀA and AᵀB accumulate chunk by chunk —
    the same additive decomposition the reference exploits with
    ``treeAggregate`` over RDD partitions, so n is bounded only by the
    source, not by host or device memory. Each chunk's gram rides the
    mesh's psum; the accumulator stays replicated on-device.

    ``prefetch_depth`` (default ``config.prefetch_depth``) > 0 takes the
    overlapped path: the producer runs ``depth`` batches ahead on a
    background thread (unless ``batches`` is already a PrefetchIterator),
    the next chunk's host→device transfer is issued while the current
    chunk's accumulation is in flight, and the accumulation step donates
    both accumulators and the consumed chunk buffers. 0 restores the
    fully synchronous loop.

    Reliability: the H2D step retries transient RESOURCE_EXHAUSTED with
    backoff and halves chunks that structurally don't fit (see
    ``_put_chunks_resilient``). With ``checkpoint_dir``, the AᵀA/AᵀB
    accumulators plus the stream cursor snapshot every
    ``checkpoint_every`` chunks (default ``config.checkpoint_every``,
    env ``KEYSTONE_CHECKPOINT_EVERY``; 0 = resume-only) through the atomic
    ``DiskCache``: a killed fit re-run with the same stream resumes at
    the last snapshot, recomputes at most K chunks, and — because the
    restored accumulators round-trip bit-exactly and the remaining
    chunks accumulate through the same program in the same order —
    yields a bit-identical solution. A snapshot is CONSUMED by the
    successful solve that completes over it (deleted on return), so a
    later solve over changed data can never silently resume stale
    accumulators.
    """
    depth = config.prefetch_depth if prefetch_depth is None else int(prefetch_depth)
    from contextlib import nullcontext

    from keystone_tpu.config import env_flag
    from keystone_tpu.loaders.stream import PrefetchIterator, prefetched
    from keystone_tpu.utils.reliability import RetryPolicy, active_plan

    # The measurement knob wins over any depth (matching the streamed BCD
    # path): serialized means serialized, even at the default prefetch
    # depth or for a caller-built PrefetchIterator.
    if env_flag("KEYSTONE_STREAM_NO_OVERLAP") or (
        depth <= 0 and not isinstance(batches, PrefetchIterator)
    ):
        return _solve_chunked_sync(
            batches, lam, refine_steps, checkpoint_dir, checkpoint_every
        )

    from keystone_tpu.utils.flight_recorder import ProgressReporter
    from keystone_tpu.utils.metrics import active_tracer

    plan = active_plan()
    retry = RetryPolicy()
    tracer = active_tracer()  # resolved once per solve, like the plan
    ckpt = _StreamCheckpointer(checkpoint_dir, checkpoint_every)

    # Respect an upstream-constructed prefetcher (the bench hands one in to
    # read its queue high-water afterwards) instead of double-wrapping —
    # and leave closing it to its owner.
    own = not isinstance(batches, PrefetchIterator)
    ctx = prefetched(iter(batches), depth) if own else nullcontext(batches)
    # Always-on solve journey (utils/flight_recorder.ProgressReporter):
    # chunk progress, rows/s, checkpoint age, stall watchdog; an
    # exception anywhere in the solve force-dumps the solver recorder
    # naming the last completed chunk.
    progress = ProgressReporter("lsq_chunked")
    with progress, ctx as src:
        it = iter(src)
        first = next(it, None)
        if first is None:
            raise ValueError("empty batch stream")
        if first[1] is None:
            raise ValueError("chunked solve needs labeled batches")
        ckpt.resume(first)
        # Fast-forward past checkpointed chunks: the producer re-reads
        # them (row streams don't seek) but no transfer or gram runs.
        cur_host = first
        while cur_host is not None and ckpt.skipping():
            cur_host = next(it, None)
        cdtype = jnp.dtype(config.accum_dtype)
        if cur_host is None:
            # The whole stream was already accumulated before the kill:
            # nothing left to recompute, solve straight off the snapshot.
            gram, atb = ckpt.restored(cdtype)
            if gram is None:
                raise ValueError("empty batch stream")
            ckpt.consume()
            return _chol_solve_maybe_traced(
                tracer, gram, atb, lam, refine_steps
            )
        if tracer is None:
            cur = _put_chunks_resilient(cur_host, plan, retry)
        else:
            cur = _put_chunks_traced(cur_host, plan, retry, tracer, ckpt.done)
        mesh = cur[0][0].mesh
        accum = _accum_gram_atb_fn(mesh, config.data_axis, _precision())
        d = cur[0][0].data.shape[1]
        # Labels may be 1-D (a single regression/class column — the CSV
        # label_col shape); AᵀB is then (d,) and the Cholesky solve
        # accepts the vector rhs directly, same as the sync path.
        b_tail = cur[0][1].data.shape[1:]
        replicated = NamedSharding(mesh, P())
        gram, atb = ckpt.restored(cdtype)
        if gram is not None:
            gram = jax.device_put(gram, replicated)
            atb = jax.device_put(atb, replicated)
        else:
            gram = jax.device_put(jnp.zeros((d, d), dtype=cdtype), replicated)
            atb = jax.device_put(
                jnp.zeros((d,) + b_tail, dtype=cdtype), replicated
            )
        while cur is not None:
            # Dispatch is async: the gemms run while the host fetches (the
            # producer thread parses/featurizes ahead) and stages the next
            # chunk's transfer. An OOM-downshifted chunk accumulates its
            # halves in row order.
            rows = sum(int(A.data.shape[0]) for A, _B in cur)
            if tracer is None:
                for A, B in cur:
                    gram, atb = accum(gram, atb, A.data, B.data)
            else:
                t0 = tracer.now()
                for A, B in cur:
                    gram, atb = accum(gram, atb, A.data, B.data)
                # The span measures DISPATCH, not device completion — the
                # gemms drain asynchronously (flagged so the trace reads
                # honestly next to the blocking H2D spans).
                tracer.record(
                    "solve.accum", "solver", t0,
                    chunk=ckpt.done, async_dispatch=True,
                )
            wrote = ckpt.chunk_done(gram, atb)
            progress.unit_done(rows=rows, chunk=ckpt.done)
            if wrote:
                progress.checkpoint(ckpt.done)
            nxt = next(it, None)
            if nxt is None:
                cur = None
            elif tracer is None:
                cur = _put_chunks_resilient(nxt, plan, retry)
            else:
                cur = _put_chunks_traced(nxt, plan, retry, tracer, ckpt.done)
        ckpt.consume()
        return _chol_solve_maybe_traced(tracer, gram, atb, lam, refine_steps)


def _solve_chunked_sync(
    batches, lam: float, refine_steps: int,
    checkpoint_dir: str | None = None,
    checkpoint_every: int | None = None,
) -> jax.Array:
    """The prefetch_depth=0 path: one thread, one chunk in flight — the
    pre-overlap behavior, preserved exactly for A/B measurement and as the
    fallback where background threads are unwelcome. Shares the overlapped
    path's OOM recovery and checkpoint/resume.

    KEYSTONE_STREAM_NO_OVERLAP=1 additionally blocks on each chunk's
    reduction, serializing ingest and compute outright — the same
    measurement knob the streamed BCD path honors, so benches can price
    what overlap (including plain async dispatch) buys. Never the right
    setting for real runs."""
    from keystone_tpu.config import env_flag
    from keystone_tpu.utils.flight_recorder import ProgressReporter
    from keystone_tpu.utils.metrics import active_tracer
    from keystone_tpu.utils.reliability import RetryPolicy, active_plan

    serialize = env_flag("KEYSTONE_STREAM_NO_OVERLAP")
    plan = active_plan()
    retry = RetryPolicy()
    tracer = active_tracer()
    ckpt = _StreamCheckpointer(checkpoint_dir, checkpoint_every)
    bound = False
    gram = None
    atb = None
    # Same always-on journey as the overlapped path: a death mid-stream
    # dumps the solver recorder naming the last completed chunk.
    progress = ProgressReporter("lsq_chunked")
    with progress:
        for chunk in batches:
            if not bound:
                bound = True
                if ckpt.store is not None:
                    if chunk[1] is None:
                        raise ValueError(
                            "chunked solve needs labeled batches"
                        )
                    ckpt.resume(chunk)
                    gram, atb = ckpt.restored(jnp.dtype(config.accum_dtype))
            if ckpt.skipping():
                continue
            if tracer is None:
                pairs = _put_chunks_resilient(chunk, plan, retry)
            else:
                pairs = _put_chunks_traced(
                    chunk, plan, retry, tracer, ckpt.done
                )
                t0 = tracer.now()
            rows = 0
            for A, B in pairs:
                rows += int(A.data.shape[0])
                g, ab = A.gram_and_atb(B)  # fused: one read of the chunk
                if serialize:
                    jax.block_until_ready((g, ab))
                gram = g if gram is None else gram + g
                atb = ab if atb is None else atb + ab
            if tracer is not None:
                tracer.record(
                    "solve.accum", "solver", t0,
                    chunk=ckpt.done, async_dispatch=not serialize,
                )
            wrote = ckpt.chunk_done(gram, atb)
            progress.unit_done(rows=rows, chunk=ckpt.done)
            if wrote:
                progress.checkpoint(ckpt.done)
        if gram is None:
            raise ValueError("empty batch stream")
        ckpt.consume()
        return _chol_solve_maybe_traced(tracer, gram, atb, lam, refine_steps)
