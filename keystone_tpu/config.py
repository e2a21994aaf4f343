"""Global configuration for keystone_tpu.

The reference computes in float64 via Breeze/netlib BLAS. On TPU, float64 is
emulated and slow; the MXU wants float32 (with bfloat16 inputs where quality
permits). We default to float32 end-to-end and expose a switch for tests that
compare against float64 NumPy oracles on CPU.

Ref: build.sbt (Breeze/netlib deps) [unverified].
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field


def env_flag(name: str) -> bool:
    """True unless the var is unset or a falsy spelling ('', '0', 'false',
    'no') — the one env-knob convention used across the framework."""
    return os.environ.get(name, "").lower() not in ("", "0", "false", "no")


def _env_int(name: str, default: int) -> int:
    """Validated integer env knob: a bad value fails AT IMPORT naming the
    variable — the same diagnostic contract as _env_choice."""
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        return int(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r}: expected an integer") from None


def _env_float(name: str, default: float) -> float:
    """Validated float env knob: a bad value fails AT IMPORT naming the
    variable — the same diagnostic contract as _env_int."""
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        return float(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r}: expected a number") from None


def pow2_ladder(max_batch: int) -> tuple:
    """Power-of-two bucket ladder up to (and always including) max_batch —
    the default shape set the serving layer pads batches onto."""
    if max_batch <= 0:
        raise ValueError(f"max_batch must be positive, got {max_batch}")
    ladder = []
    b = 1
    while b < max_batch:
        ladder.append(b)
        b *= 2
    ladder.append(max_batch)
    return tuple(ladder)


def _env_buckets() -> tuple:
    """Parse KEYSTONE_SERVE_BUCKETS: empty/unset = () (today's per-shape
    jit), 'pow2' = power-of-two ladder up to serve_max_batch, else a
    comma-separated ascending bucket list. Bad values fail AT IMPORT naming
    the variable (same contract as _env_choice)."""
    raw = os.environ.get("KEYSTONE_SERVE_BUCKETS")
    if raw is None or not raw.strip():
        return ()
    if raw.strip().lower() == "pow2":
        return pow2_ladder(_env_int("KEYSTONE_SERVE_MAX_BATCH", 1024))
    try:
        vals = tuple(
            sorted({int(tok) for tok in raw.split(",") if tok.strip()})
        )
    except ValueError:
        raise ValueError(
            f"KEYSTONE_SERVE_BUCKETS={raw!r}: expected 'pow2' or "
            "comma-separated integers"
        ) from None
    if not vals or vals[0] <= 0:
        raise ValueError(
            f"KEYSTONE_SERVE_BUCKETS={raw!r}: buckets must be positive"
        )
    return vals


def _env_choice(name: str, choices: tuple, default: str) -> str:
    """Validated enum env knob: case-insensitive, and a bad value fails AT
    IMPORT naming the variable — not as a bare KeyError deep in a solve."""
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    val = raw.strip().lower()
    if val not in choices:
        raise ValueError(f"{name}={raw!r}: expected one of {choices}")
    return val


@dataclass
class Config:
    # Default dtype for dense compute (solvers, featurization).
    default_dtype: str = "float32"
    # dtype used for matmul accumulation-sensitive reductions (grams). XLA on
    # TPU accumulates fp32; this is the storage dtype of gram matrices.
    accum_dtype: str = "float32"
    # Matmul precision for solver-path compute (grams, QR, residuals). TPU
    # default matmul precision is bf16-class and loses ~3 decimal digits;
    # solvers default to full fp32 ("highest" = 6-pass bf16 emulation,
    # ~1/6 MXU peak). "high" (3-pass) doubles gemm throughput at ~f32-ish
    # accuracy — BCD's per-epoch residual re-solve self-corrects, so the
    # bench measures it as the f32h mode; flip the default only on
    # silicon evidence. Env: KEYSTONE_SOLVER_PRECISION.
    solver_precision: str = field(
        default_factory=lambda: _env_choice(
            "KEYSTONE_SOLVER_PRECISION", ("highest", "high", "default"),
            "highest",
        )
    )
    # Storage dtype for the solver's BIG operands (the feature matrix A and
    # streamed blocks). None = default_dtype. "bfloat16" is the v5e
    # throughput mode: A is stored (and streamed) at half the bytes and
    # every matmul touching it takes the MXU's native bf16-multiply /
    # f32-accumulate path; grams, Cholesky factors, weights, and residuals
    # stay in accum_dtype. Set via KEYSTONE_SOLVER_DTYPE or per-run config.
    solver_storage_dtype: str | None = field(
        default_factory=lambda: os.environ.get("KEYSTONE_SOLVER_DTYPE") or None
    )
    # Canonical block count for the width-independent solver row fold
    # (utils.mesh.fold_blocks). Row reductions (grams, AᵀB, column sums)
    # are summed over this many fixed row blocks in a balanced-tree order
    # regardless of mesh width, so a solve accumulated on W devices is
    # BIT-identical to the same solve on W' devices — the property the
    # elastic mesh migration's resume gate relies on. Must be a power of
    # two; meshes whose width does not divide it fall back to the plain
    # psum fold (order differs per width). Rows pad to a multiple of this
    # count instead of the mesh width. 0 pins the legacy psum fold
    # everywhere. Env: KEYSTONE_GRAM_FOLD_BLOCKS.
    gram_fold_blocks: int = field(
        default_factory=lambda: int(
            os.environ.get("KEYSTONE_GRAM_FOLD_BLOCKS", "16")
        )
    )
    # Mesh axis name used for data (row) parallelism throughout.
    data_axis: str = "data"
    # Mesh axis name used for model (feature-block) parallelism.
    model_axis: str = "model"
    # HBM budget (bytes) assumed by the auto-caching rule when no device is
    # queried. v5e = 16 GiB; leave headroom for XLA scratch.
    hbm_budget_bytes: int = 12 * (1 << 30)
    # Row-shard array batches over the mesh when they enter the graph (the
    # RDD-partitioning analog): divisible batches are placed with the
    # explicit data sharding, and fused jittable chains lower ONCE with
    # the SpecLayout convention's in_shardings/out_shardings
    # (utils/mesh.py) — not just the solvers. Batches whose row count
    # doesn't divide the mesh are mask-padded onto it by the chain call
    # and trimmed (bit-identical, counted in the "sharding" registry);
    # only sub-shard_min_rows batches fall back to single-device, and
    # that fallback is counted too. KEYSTONE_SHARD_DATA=0 pins the
    # single-device walk (the bench's A/B control and the escape hatch).
    shard_data_batches: bool = field(
        default_factory=lambda: os.environ.get(
            "KEYSTONE_SHARD_DATA", ""
        ).lower() not in ("0", "false", "no")
    )
    # Minimum rows before sharding is worth the placement overhead — the
    # ONLY batch class still allowed to run single-device (visible via
    # sharding.fallback_small_batch). Env: KEYSTONE_SHARD_MIN_ROWS.
    shard_min_rows: int = field(
        default_factory=lambda: _env_int("KEYSTONE_SHARD_MIN_ROWS", 64)
    )
    # Buffer donation across the fused-fit plumbing: the sharded chain
    # call donates the staging copy it creates for a host batch
    # (utils/mesh.py SpecLayout.jit) when an output can alias it, and the
    # solver hot loops donate their dead accumulator/residual buffers
    # (linalg/row_matrix.py donate_argnums) — each update then holds ONE
    # live copy instead of two, capping the fit's HBM high-water.
    # Donation never touches caller-owned arrays (anything placed
    # upstream can be multi-consumer via gather/memo), and is refused —
    # counted, never silent — when no output matches the buffer's
    # shape/dtype (XLA aliasing is aval-matched, so donating there would
    # be a warning and a no-op). KEYSTONE_DONATE_BUFFERS=0 pins donation
    # off everywhere: the bench's non-donated A/B control and the
    # debugging escape hatch when a deleted-buffer error needs isolating.
    donate_buffers: bool = field(
        default_factory=lambda: os.environ.get(
            "KEYSTONE_DONATE_BUFFERS", ""
        ).lower() not in ("0", "false", "no")
    )
    # Elastic mesh: durable solver/profile state recorded under one mesh
    # width migrates onto the current width at resume time
    # (utils/mesh.reshard_state — the accumulators are placement-free
    # sums, so a migrated resume is bit-identical to an uninterrupted fit
    # at the target width) instead of refusing with MeshMismatchError.
    # Every migration is counted in the "elastic" metrics family — never
    # silent — and truly non-migratable state (torn/partial per-shard
    # payloads) still refuses typed. KEYSTONE_ELASTIC_MESH=0 pins the
    # refuse-only contract everywhere (the pre-elastic behavior and the
    # escape hatch when a migration needs isolating).
    elastic_mesh: bool = field(
        default_factory=lambda: os.environ.get(
            "KEYSTONE_ELASTIC_MESH", ""
        ).lower() not in ("0", "false", "no")
    )
    # Depth of the bounded host-side prefetch queue in front of the chunked
    # solvers and streamed pipeline application (loaders/stream.py
    # PrefetchIterator): the upstream producer — CSV parse, JPEG decode,
    # map_batches featurization — runs on a background thread up to this
    # many batches ahead, so host ingest leaves the device's critical path
    # while peak host residency stays bounded by depth × batch bytes.
    # 0 restores fully synchronous single-thread ingestion. This is the
    # hand-picked ceiling: on a measured-profile hit PlanResourcesRule
    # CLAMPS the effective depth down when depth × measured per-batch
    # bytes would overrun its budget share (the session plan; an
    # exported KEYSTONE_PREFETCH_DEPTH — including 0 — always wins, see
    # resolved_prefetch_depth). Env: KEYSTONE_PREFETCH_DEPTH.
    prefetch_depth: int = field(
        default_factory=lambda: _env_int("KEYSTONE_PREFETCH_DEPTH", 2)
    )
    # Serving bucket ladder: when non-empty, Transformer.batch_call rounds
    # array batches up to the next bucket (padding with the last real row)
    # so the per-shape jit cache only ever sees ladder shapes — a serving
    # workload with variable request sizes stops recompiling once the
    # ladder is warm. Empty = today's per-shape jit. The AOT serving engine
    # (workflow/serving.py CompiledPipeline) uses this ladder too, falling
    # back to pow-2 up to serve_max_batch when empty. Padding is refused
    # (RowDependenceError) for transformers with row_independent=False.
    # Env: KEYSTONE_SERVE_BUCKETS ('pow2' or comma-separated ints).
    serve_buckets: tuple = field(default_factory=_env_buckets)
    # Top of the default serving ladder: the largest batch a single bucketed
    # device call serves (bigger requests chunk through this bucket).
    # Env: KEYSTONE_SERVE_MAX_BATCH.
    serve_max_batch: int = field(
        default_factory=lambda: _env_int("KEYSTONE_SERVE_MAX_BATCH", 1024)
    )
    # Serving precision ladder (workflow/serving.py CompiledPipeline):
    # the storage/accumulate mode every serve bucket AOT-warms at.
    # "f32" (default) is byte-for-byte today's path — the engine's jit
    # wrapper is constructed exactly as before, so outputs stay
    # bit-identical when the knob is off. "f32h" traces the chain under
    # matmul precision HIGH (3-pass bf16 emulation — ~2x MXU throughput
    # at ~f32-ish accuracy; a no-op on CPU). "bf16" is the MXU-native
    # throughput mode: the request batch is cast to bfloat16 at the
    # chain boundary (bf16 storage) and every matmul traces at DEFAULT
    # precision (one bf16 pass, f32 accumulation — the
    # tests/test_bf16_mode.py storage/accumulate contract); fitted
    # weights stay f32 and any bf16 leaf is cast back to the request
    # dtype at the boundary. Non-f32 modes should be gated per pipeline
    # with CompiledPipeline.qualify() — evaluation/ metrics within a
    # declared tolerance of the f32 oracle, or the knob refuses with a
    # typed PrecisionQualityError. Env: KEYSTONE_SERVE_PRECISION.
    serve_precision: str = field(
        default_factory=lambda: _env_choice(
            "KEYSTONE_SERVE_PRECISION", ("f32", "f32h", "bf16"), "f32"
        )
    )
    # Serving replica pool width: how many local devices CompiledPipeline
    # AOT-warms its bucket ladder onto (one replica per device, each owning
    # its own compiled executables). 0 = all local devices — the training
    # side already spans the whole mesh; serving should too. 1 pins the
    # pre-replica single-device behavior exactly.
    # Env: KEYSTONE_SERVE_DEVICES.
    serve_devices: int = field(
        default_factory=lambda: _env_int("KEYSTONE_SERVE_DEVICES", 0)
    )
    # Per-replica in-flight window for pipelined serving dispatch: the
    # micro-batcher launches up to this many flush groups per replica
    # before waiting on a completion, riding JAX async dispatch so replica
    # B computes while replica A's results materialize. 1 serializes
    # launch->materialize per replica (with one replica, exactly the
    # pre-pipelining flush loop). Env: KEYSTONE_SERVE_INFLIGHT.
    serve_inflight: int = field(
        default_factory=lambda: _env_int("KEYSTONE_SERVE_INFLIGHT", 2)
    )
    # Host worker threads for the executor's stage-parallel DAG walk
    # (workflow/executor.py): when > 0, nodes whose inputs are resolved
    # dispatch concurrently onto a bounded pool — independent branches
    # (the two-branch ImageNet featurizer, parallel text encoders) run
    # side by side, and a host-bound node (native SIFT, JPEG decode,
    # tokenize) no longer blocks device work on a sibling branch.
    # Jittable device nodes keep riding JAX async dispatch (launch
    # without materializing); only estimator fits and host consumers
    # block. 0 (default) = the byte-identical legacy serial topological
    # walk — nothing changes until opted in. Outputs are bit-identical
    # at any worker count: the scheduler reorders only provably
    # independent nodes. Env: KEYSTONE_EXEC_WORKERS.
    exec_workers: int = field(
        default_factory=lambda: _env_int("KEYSTONE_EXEC_WORKERS", 0)
    )
    # Whole-pipeline auto-caching (profile a sample run, persist the best
    # time-saved-per-byte intermediates under a budget). Opt-in: profiling
    # costs a sample execution per optimization — unless a MEASURED
    # profile for the pipeline exists in the profile store, in which case
    # the rule consumes that and skips the sample run entirely.
    auto_cache: bool = False
    # Directory of the measured-profile store (workflow/profile_store.py):
    # `Pipeline.fit(profile=True)` persists per-node wall/bytes rows keyed
    # by the pipeline's structural digest + runtime fingerprint; the
    # optimizer rules consume matching entries instead of sample-run
    # extrapolation. None = disabled; the KEYSTONE_PROFILE_STORE env var
    # takes precedence (presence, not truthiness — an exported empty var
    # disables, the resolved_cache_dir convention).
    profile_store: str | None = None
    # Profile-guided resource planning (workflow/rules.py
    # PlanResourcesRule): on a measured-profile hit, pick the executor
    # worker count from the graph's branch width + measured queue-wait
    # attribution and plan solver chunk rows against the HBM budget
    # (PR-3's reactive OOM-halving becomes a planned size). The plan is
    # scoped to the optimized pipeline's own walk
    # (PipelineEnv.resource_plan, saved/restored around nested passes)
    # and never overrides an explicitly EXPORTED KEYSTONE_EXEC_WORKERS /
    # KEYSTONE_SOLVE_CHUNK_ROWS (presence wins, including an explicit
    # 0). A programmatic pin (config.exec_workers = 0 in code, no env)
    # cannot be told apart from the unset default — to pin
    # programmatically, disable the planner: config.plan_resources =
    # False. Env: KEYSTONE_PLAN_RESOURCES=0 disables.
    plan_resources: bool = field(
        default_factory=lambda: os.environ.get(
            "KEYSTONE_PLAN_RESOURCES", ""
        ).lower() not in ("0", "false", "no")
    )
    # Planned row count per solver chunk H2D transfer: chunks larger than
    # this are split BEFORE the transfer (linalg/normal_equations.py), so
    # a chunk that could not fit HBM never triggers the reactive
    # OOM-halving path. 0 = unplanned (reactive halving only, or the
    # session plan from PlanResourcesRule). Env: KEYSTONE_SOLVE_CHUNK_ROWS.
    solve_chunk_rows: int = field(
        default_factory=lambda: _env_int("KEYSTONE_SOLVE_CHUNK_ROWS", 0)
    )
    # Raise on NaNs inside jitted computations (jax debug_nans; the
    # sanitizer analog — SURVEY.md §5 race-detection row).
    debug_nans: bool = False
    # Arrays above this size are fingerprinted from a deterministic chunk
    # sample instead of a full scan: multi-GB fit inputs stay
    # content-addressed (the cross-process cache keeps working at real
    # scale) without paying full-buffer hashing per streamed batch.
    fingerprint_max_bytes: int = 128 << 20
    # Vocabulary size at which text vectorizers switch from dense (batch, K)
    # output to a host-side CSR SparseBatch (consumers densify per column
    # block). Below this, dense batches feed the MXU classifiers directly.
    text_sparse_threshold: int = 16384
    # Directory for the cross-process fitted-prefix store (None = disabled;
    # the KEYSTONE_CACHE_DIR env var takes precedence). Content-addressed, so
    # it never serves stale fits — see workflow/disk_cache.py.
    cache_dir: str | None = None
    # Fault-injection plan (utils/reliability.py FaultPlan): a
    # 'site:value,...' spec, e.g. 'io:0.05,oom:1,producer_death:1'. Integer
    # values fire on the first N checks of the site; fractions are per-check
    # probabilities drawn from a stream seeded by faults_seed, so a fixed
    # seed reproduces the exact fault sequence. Empty = injection disabled,
    # zero overhead. Env: KEYSTONE_FAULTS / KEYSTONE_FAULTS_SEED.
    faults: str = field(
        default_factory=lambda: os.environ.get("KEYSTONE_FAULTS", "")
    )
    faults_seed: int = field(
        default_factory=lambda: _env_int("KEYSTONE_FAULTS_SEED", 0)
    )
    # Transient-failure retry budget (utils/reliability.py RetryPolicy):
    # total attempts per operation, and the exponential-backoff base/cap in
    # milliseconds (full jitter: each pause is uniform over [0, cap]).
    # Used by the prefetch producer (flaky record reads) and the chunked
    # solvers (device RESOURCE_EXHAUSTED at the H2D step).
    # Env: KEYSTONE_RETRY_ATTEMPTS / KEYSTONE_RETRY_BASE_MS /
    # KEYSTONE_RETRY_MAX_MS.
    retry_attempts: int = field(
        default_factory=lambda: _env_int("KEYSTONE_RETRY_ATTEMPTS", 4)
    )
    retry_base_ms: float = field(
        default_factory=lambda: _env_float("KEYSTONE_RETRY_BASE_MS", 5.0)
    )
    retry_max_ms: float = field(
        default_factory=lambda: _env_float("KEYSTONE_RETRY_MAX_MS", 1000.0)
    )
    # Checkpoint cadence for the streaming solvers: snapshot accumulator
    # state (gram/AᵀB, resp. W/R blocks) every K chunks/blocks into the
    # solve's checkpoint_dir, so a killed fit recomputes at most K chunks on
    # resume. 0 disables mid-stream snapshots in BOTH solvers (resume from
    # an existing snapshot still works; the streamed BCD epoch-boundary
    # orbax saves are independent and keep happening).
    # Env: KEYSTONE_CHECKPOINT_EVERY.
    checkpoint_every: int = field(
        default_factory=lambda: _env_int("KEYSTONE_CHECKPOINT_EVERY", 8)
    )
    # Serving backpressure: the most requests PipelineService holds pending
    # before submit() fast-fails with QueueFullError — bounded queues turn
    # overload into fast rejections instead of unbounded latency cliffs.
    # Env: KEYSTONE_SERVE_MAX_PENDING.
    serve_max_pending: int = field(
        default_factory=lambda: _env_int("KEYSTONE_SERVE_MAX_PENDING", 1024)
    )
    # Default per-request deadline for PipelineService submits, in
    # milliseconds: a request still queued past its deadline fails its
    # future with DeadlineExceeded BEFORE wasting a device call. 0 = no
    # deadline. Env: KEYSTONE_SERVE_DEADLINE_MS.
    serve_deadline_ms: float = field(
        default_factory=lambda: _env_float("KEYSTONE_SERVE_DEADLINE_MS", 0.0)
    )
    # Whether executor fuses jittable transformer chains into one XLA program.
    # Disabled by KEYSTONE_NO_FUSE set to a truthy value (anything except
    # "", "0", "false", "no").
    fuse_chains: bool = field(
        default_factory=lambda: not env_flag("KEYSTONE_NO_FUSE")
    )
    # Process-wide span tracing (utils/metrics.py Tracer): executor nodes,
    # solver chunks, prefetch queue residency, and serving request
    # lifecycle record into a bounded ring buffer, exportable as
    # Chrome-trace JSON (Perfetto-viewable; tools/trace_report.py). Off by
    # default: call sites resolve ``active_tracer()`` ONCE per
    # stream/solve/service — like ``active_plan()`` — so the disabled
    # tracer is a None check, never a per-record cost. Env: KEYSTONE_TRACE.
    trace: bool = field(default_factory=lambda: env_flag("KEYSTONE_TRACE"))
    # Span ring-buffer capacity: the tracer keeps the most recent N spans,
    # so a long-running traced process holds bounded memory instead of an
    # unbounded event log. Env: KEYSTONE_TRACE_BUFFER.
    trace_buffer: int = field(
        default_factory=lambda: _env_int("KEYSTONE_TRACE_BUFFER", 65536)
    )
    # Tail-sampling threshold for request-scoped tracing, in milliseconds:
    # when tracing is on, a request whose end-to-end latency breaches this
    # keeps its FULL span tree in the tracer's retained store (survives
    # ring churn; exported under "tailSampled"). 0 = auto: the running p99
    # of the service's always-on e2e histogram (so ~the slowest 1% are
    # retained once enough samples exist); negative disables tail
    # sampling entirely. Env: KEYSTONE_TRACE_TAIL_MS.
    trace_tail_ms: float = field(
        default_factory=lambda: _env_float("KEYSTONE_TRACE_TAIL_MS", 0.0)
    )
    # Serving stall watchdog (workflow/serving.py): a background thread
    # per service that fires when the pending queue is non-empty but no
    # dispatch progress (group pop / completion) has happened for this
    # many milliseconds — bumping the serve.stalls counter and dumping the
    # flight recorder instead of hanging silently. 0 disables the thread.
    # Env: KEYSTONE_WATCHDOG_MS.
    serve_watchdog_ms: float = field(
        default_factory=lambda: _env_float("KEYSTONE_WATCHDOG_MS", 10000.0)
    )
    # Deadline-storm dump trigger: this many DeadlineExceeded failures
    # inside one second auto-dumps the flight recorder (the post-mortem
    # for "why did everything suddenly expire"). 0 disables the trigger.
    # Env: KEYSTONE_STORM_EXPIRED.
    serve_storm_expired: int = field(
        default_factory=lambda: _env_int("KEYSTONE_STORM_EXPIRED", 8)
    )
    # Flight-recorder ring capacity: the most recent N per-request journey
    # records each PipelineService keeps for post-mortem dumps (always on;
    # one record per accepted request). 0 disables the journey ring
    # (error events and dump triggers keep working).
    # Env: KEYSTONE_FLIGHT_RECORDS.
    flight_records: int = field(
        default_factory=lambda: _env_int("KEYSTONE_FLIGHT_RECORDS", 2048)
    )
    # Where flight-recorder dumps land ('' = the platform tempdir). Each
    # dump is one JSON file named for the service, trigger reason, pid,
    # and sequence number. Env: KEYSTONE_FLIGHT_DIR.
    flight_dir: str = field(
        default_factory=lambda: os.environ.get("KEYSTONE_FLIGHT_DIR", "")
    )
    # Durable telemetry export (utils/telemetry.py TelemetryLog): where
    # resolved request journeys + tail-retained span trees append as
    # JSONL, written by a dedicated writer thread off the serving hot
    # path. '' (default) = telemetry export off — the daemon keeps only
    # its in-memory rings. Env: KEYSTONE_TELEMETRY_DIR.
    telemetry_dir: str = field(
        default_factory=lambda: os.environ.get("KEYSTONE_TELEMETRY_DIR", "")
    )
    # Telemetry segment rotation threshold (MB): when the active JSONL
    # segment grows past this, the writer rotates to a new sequence-
    # numbered segment file. Env: KEYSTONE_TELEMETRY_ROTATE_MB.
    telemetry_rotate_mb: float = field(
        default_factory=lambda: _env_float("KEYSTONE_TELEMETRY_ROTATE_MB",
                                           64.0)
    )
    # Bounded telemetry retention: keep the newest N rotated segments per
    # process, delete the rest (the keep_artifacts precedent — a steady
    # flood must not fill the volume). Env: KEYSTONE_TELEMETRY_KEEP.
    telemetry_keep: int = field(
        default_factory=lambda: _env_int("KEYSTONE_TELEMETRY_KEEP", 8)
    )
    # Telemetry writer-queue capacity: journeys enqueue to the writer
    # thread through a bounded queue; a full queue DROPS the record and
    # counts it (telemetry family, records_dropped) — export never
    # blocks admission. Env: KEYSTONE_TELEMETRY_QUEUE.
    telemetry_queue: int = field(
        default_factory=lambda: _env_int("KEYSTONE_TELEMETRY_QUEUE", 4096)
    )
    # Per-tenant SLO accounting (workflow/daemon.py): rolling-window
    # length in seconds over which deadline-hit rate and error-budget
    # burn are computed for /stats + /metrics. Env: KEYSTONE_SLO_WINDOW_S.
    slo_window_s: float = field(
        default_factory=lambda: _env_float("KEYSTONE_SLO_WINDOW_S", 300.0)
    )
    # SLO objective: the target fraction of in-deadline, non-error
    # responses per tenant/tier. Error-budget burn is the ratio of the
    # observed failure rate to the budget this objective leaves
    # (burn > 1.0 = burning budget faster than sustainable).
    # Env: KEYSTONE_SLO_TARGET.
    slo_target: float = field(
        default_factory=lambda: _env_float("KEYSTONE_SLO_TARGET", 0.99)
    )
    # TCP port for tools/metrics_server.py (the /metrics + /healthz pull
    # surface). 0 = bind an ephemeral port (the smoke-test default; the
    # chosen port is printed/returned). Env: KEYSTONE_METRICS_PORT.
    metrics_port: int = field(
        default_factory=lambda: _env_int("KEYSTONE_METRICS_PORT", 0)
    )
    # Per-node resource attribution (utils/metrics.py ResourceProfile):
    # when on, every executor walk records wall time, device wait,
    # cost-model FLOPs/bytes (one AOT lower+compile per executable,
    # memoized), output nbytes, and the HBM high-water delta per pipeline
    # node into the process-wide profile (registry name "profile",
    # exported over /metrics). Off by default: call sites resolve
    # ``active_profile()`` ONCE per execution walk — the
    # ``active_plan()`` discipline — so the disabled profiler is a None
    # check. ``Pipeline.fit(profile=True)`` forces it for one fit.
    # Env: KEYSTONE_PROFILE.
    profile: bool = field(default_factory=lambda: env_flag("KEYSTONE_PROFILE"))
    # Streaming-solve stall watchdog (utils/flight_recorder.py
    # ProgressReporter): each streaming solve gets a watchdog thread that
    # fires when no chunk/block completes for this many milliseconds —
    # bumping the solver stall counters and dumping the solver flight
    # recorder, so a dead producer mid-fit leaves forensics exactly like
    # a dead serving worker. 0 disables the per-solve thread.
    # Env: KEYSTONE_SOLVE_WATCHDOG_MS.
    solve_watchdog_ms: float = field(
        default_factory=lambda: _env_float("KEYSTONE_SOLVE_WATCHDOG_MS",
                                           30000.0)
    )
    # Progress-event cadence for streaming solves: every K completed
    # chunks/blocks appends one structured event (unit, rows/s, ETA,
    # residual when cheap) to the solve's journey record. 1 = every
    # unit; higher thins the bounded event ring for hour-scale solves.
    # Env: KEYSTONE_SOLVE_PROGRESS_EVERY.
    solve_progress_every: int = field(
        default_factory=lambda: _env_int("KEYSTONE_SOLVE_PROGRESS_EVERY", 1)
    )
    # Network serving daemon (workflow/daemon.py) — bind address for
    # BOTH ingresses. Default loopback (safe: nothing is exposed until
    # the operator says so); set 0.0.0.0 to serve real external traffic
    # behind a load balancer. Env: KEYSTONE_SERVE_HOST.
    serve_host: str = field(
        default_factory=lambda: os.environ.get("KEYSTONE_SERVE_HOST",
                                               "127.0.0.1")
    )
    # HTTP/JSON ingress port. 0 = bind an ephemeral port (tests/smokes;
    # the chosen port is reported on the daemon object).
    # Env: KEYSTONE_SERVE_PORT.
    serve_port: int = field(
        default_factory=lambda: _env_int("KEYSTONE_SERVE_PORT", 0)
    )
    # Length-prefixed socket ingress port for the daemon (the low-overhead
    # wire: 4-byte big-endian frame length + JSON payload, persistent
    # connections). 0 = ephemeral. Env: KEYSTONE_SERVE_SOCKET_PORT.
    serve_socket_port: int = field(
        default_factory=lambda: _env_int("KEYSTONE_SERVE_SOCKET_PORT", 0)
    )
    # Tenant/quota/SLA table for daemon admission control:
    # 'name:api_key:qps:tier,...' entries — qps is the token-bucket refill
    # rate (0 = unlimited), tier is 'gold' or 'best_effort'. Empty = open
    # mode (no API keys; every request is an anonymous best-effort
    # tenant). Env: KEYSTONE_TENANTS.
    tenants: str = field(
        default_factory=lambda: os.environ.get("KEYSTONE_TENANTS", "")
    )
    # Global admission budget: the most requests the daemon holds admitted
    # (accepted but not yet responded) across every tenant before
    # fast-failing with 429. Best-effort tenants are refused earlier (at
    # BE_BUDGET_FRAC of this) so gold always has reserved headroom — the
    # queue-priority half of the SLA tiers.
    # Env: KEYSTONE_SERVE_PENDING_BUDGET.
    serve_pending_budget: int = field(
        default_factory=lambda: _env_int("KEYSTONE_SERVE_PENDING_BUDGET", 256)
    )
    # Per-tier default deadlines (ms) the daemon stamps on each admitted
    # request: gold = the latency SLA (0 = none); best_effort usually
    # runs without one. An explicit per-request deadline overrides.
    # Env: KEYSTONE_SERVE_GOLD_DEADLINE_MS / KEYSTONE_SERVE_BE_DEADLINE_MS.
    serve_gold_deadline_ms: float = field(
        default_factory=lambda: _env_float(
            "KEYSTONE_SERVE_GOLD_DEADLINE_MS", 500.0
        )
    )
    serve_be_deadline_ms: float = field(
        default_factory=lambda: _env_float("KEYSTONE_SERVE_BE_DEADLINE_MS",
                                           0.0)
    )
    # Hot-swap drain bound (ms): how long the generation flip waits for
    # the OLD generation's service to drain its queued + in-flight
    # requests before failing the stragglers with ServiceClosed (the
    # daemon then transparently re-submits them on the new generation).
    # Env: KEYSTONE_SWAP_DRAIN_MS.
    swap_drain_ms: float = field(
        default_factory=lambda: _env_float("KEYSTONE_SWAP_DRAIN_MS", 30000.0)
    )
    # Upper bound (ms) a synchronous /swap request waits for the swap
    # worker before reporting 504 (the swap itself keeps running).
    # Env: KEYSTONE_SWAP_TIMEOUT_MS.
    swap_timeout_ms: float = field(
        default_factory=lambda: _env_float("KEYSTONE_SWAP_TIMEOUT_MS",
                                           120000.0)
    )
    # Control-plane credential: when set, POST /swap requires a matching
    # X-Swap-Token header and /stats serves its full (tenant-naming)
    # payload only to token holders. When UNSET while KEYSTONE_TENANTS
    # is configured, /swap over HTTP is refused outright (403) — a
    # data-plane key must never be able to replace the model, and an
    # admission-controlled daemon must not ship with an open control
    # plane. Open dev mode (no tenants, no token) leaves /swap open.
    # Env: KEYSTONE_SWAP_TOKEN.
    swap_token: str = field(
        default_factory=lambda: os.environ.get("KEYSTONE_SWAP_TOKEN", "")
    )
    # Online learning (workflow/online.py OnlineTrainer) — refresh
    # cadence in milliseconds: the trainer's _refresh_loop thread
    # re-solves the retained accumulators, writes a versioned artifact,
    # and hot-swaps it into the wired daemon whenever new batches were
    # folded since the last tick. 0 = no background thread (manual
    # refresh() only — the bench/test mode).
    # Env: KEYSTONE_ONLINE_REFRESH_MS.
    online_refresh_ms: float = field(
        default_factory=lambda: _env_float("KEYSTONE_ONLINE_REFRESH_MS",
                                           5000.0)
    )
    # Online time-decay γ ∈ (0, 1]: each partial_fit call scales the
    # retained sums by γ first, so a batch folded a calls ago carries
    # weight γ^a (exponentially-weighted ridge — the drift-tracking
    # mode). 1.0 = no forgetting. Exclusive with online_window.
    # Env: KEYSTONE_ONLINE_DECAY.
    online_decay: float = field(
        default_factory=lambda: _env_float("KEYSTONE_ONLINE_DECAY", 1.0)
    )
    # Online sliding window (batches): keep a per-window accumulator
    # ring of the most recent k partial_fit calls, subtracting the
    # oldest window's sums on evict (counted as windows_evicted).
    # 0 = unbounded horizon. Exclusive with online_decay.
    # Env: KEYSTONE_ONLINE_WINDOW.
    online_window: int = field(
        default_factory=lambda: _env_int("KEYSTONE_ONLINE_WINDOW", 0)
    )
    # Pipeline-graph lint gate (workflow/analysis.py): run the static
    # graph linter before every fit()/compiled(). "off" (default) = never;
    # "warn" = log findings at their severity; "error" = additionally
    # raise LintError on error-severity findings (serveability violations
    # on the pre-compiled() path), so a pipeline the serving engine would
    # refuse at trace time is refused BEFORE any device work.
    # Env: KEYSTONE_LINT.
    lint: str = field(
        default_factory=lambda: _env_choice(
            "KEYSTONE_LINT", ("warn", "error", "off"), "off"
        )
    )
    # Learned serving-capacity model (workflow/capacity.py) — re-plan
    # cadence of the daemon's traffic-aware autoscaling loop, seconds.
    # The loop wakes on this period, compares the observed bucket mix
    # with the mix at the last re-plan, and re-sizes replicas /
    # re-prices the ladder when the shift crosses its threshold. The
    # same window backs the no-flap guard (a second re-plan inside one
    # window is refused, counted). Env: KEYSTONE_CAPACITY_REPLAN_S.
    capacity_replan_s: float = field(
        default_factory=lambda: _env_float("KEYSTONE_CAPACITY_REPLAN_S", 5.0)
    )
    # Journeys the capacity model must observe before ANY consumer
    # (predicted admission, autoscaling, micro-batching) acts on it;
    # below this the model is "cold" and every consumer no-ops
    # bit-identically to KEYSTONE_CAPACITY_MODEL=0 (counted as
    # capacity.model_cold_skips). Env: KEYSTONE_CAPACITY_MIN_SAMPLES.
    capacity_min_samples: int = field(
        default_factory=lambda: _env_int("KEYSTONE_CAPACITY_MIN_SAMPLES", 64)
    )


config = Config()


def resolved_cache_dir() -> str | None:
    """The cross-process fit-cache directory: env presence (not
    truthiness) takes precedence over ``config.cache_dir``, so an
    exported empty KEYSTONE_CACHE_DIR explicitly disables the store.
    Lives here so the env read stays inside config.py (keystone-lint
    KL003: hot paths must not consult os.environ directly)."""
    if "KEYSTONE_CACHE_DIR" in os.environ:
        return os.environ["KEYSTONE_CACHE_DIR"]
    return config.cache_dir


def resolved_exec_workers() -> int | None:
    """The LIVE env value of KEYSTONE_EXEC_WORKERS when it is exported,
    else None. Presence, not truthiness: an explicitly exported 0 pins
    the byte-identical legacy serial walk against the profile-guided
    session plan (PlanResourcesRule); only the unset default falls
    through to the plan. Read live (not the config-instantiation
    snapshot) so a late export behaves like the resolved_cache_dir
    convention. Lives here so the env read stays inside config.py
    (keystone-lint KL003)."""
    if "KEYSTONE_EXEC_WORKERS" in os.environ:
        return _env_int("KEYSTONE_EXEC_WORKERS", 0)
    return None


def resolved_solve_chunk_rows() -> int | None:
    """The LIVE env value of KEYSTONE_SOLVE_CHUNK_ROWS when exported,
    else None — same presence-over-truthiness contract as
    ``resolved_exec_workers``: an explicit 0 pins reactive-halving-only
    against the planner's session plan."""
    if "KEYSTONE_SOLVE_CHUNK_ROWS" in os.environ:
        return _env_int("KEYSTONE_SOLVE_CHUNK_ROWS", 0)
    return None


def resolved_serve_buckets() -> tuple | None:
    """The LIVE env value of KEYSTONE_SERVE_BUCKETS when it is exported
    non-empty, else None — the serve-ladder planner's env pin: an
    explicitly exported bucket list always wins over the HBM-planned
    ladder (the resolved_exec_workers convention). An exported EMPTY
    value reads as unset here (it spells "no in-graph bucketing", not a
    ladder pin). Lives here so the env read stays inside config.py
    (keystone-lint KL003)."""
    if "KEYSTONE_SERVE_BUCKETS" in os.environ:
        return _env_buckets() or None
    return None


def resolved_prefetch_depth() -> int | None:
    """The LIVE env value of KEYSTONE_PREFETCH_DEPTH when exported, else
    None — presence over truthiness: an explicitly exported 0 pins the
    synchronous ingest path against the planner's session clamp
    (PlanResourcesRule); only the unset default falls through to the
    plan, then to ``config.prefetch_depth``."""
    if "KEYSTONE_PREFETCH_DEPTH" in os.environ:
        return _env_int("KEYSTONE_PREFETCH_DEPTH", 2)
    return None


def resolved_telemetry_dir() -> str | None:
    """The durable telemetry export directory: env presence (not
    truthiness) takes precedence over ``config.telemetry_dir``, so an
    exported empty KEYSTONE_TELEMETRY_DIR explicitly disables the
    export (the ``resolved_cache_dir`` convention). Returns None when
    telemetry export is off. Lives here so the env read stays inside
    config.py (keystone-lint KL003)."""
    if "KEYSTONE_TELEMETRY_DIR" in os.environ:
        return os.environ["KEYSTONE_TELEMETRY_DIR"] or None
    return config.telemetry_dir or None


def resolved_capacity_model() -> bool:
    """Whether the learned serving-capacity model is enabled. Resolution
    order (documented contract): an exported KEYSTONE_CAPACITY_MODEL
    wins outright (env_flag spelling — '', '0', 'false', 'no' disable,
    anything else enables); unset, the model defaults ON exactly when a
    telemetry directory is configured (the model trains on and persists
    through those segments — without them it would relearn from zero
    every restart) and OFF otherwise. Lives here so the env read stays
    inside config.py (keystone-lint KL003)."""
    if "KEYSTONE_CAPACITY_MODEL" in os.environ:
        return env_flag("KEYSTONE_CAPACITY_MODEL")
    return resolved_telemetry_dir() is not None


def resolved_profile_store() -> str | None:
    """The measured-profile store directory: env presence (not
    truthiness) takes precedence over ``config.profile_store``, exactly
    like ``resolved_cache_dir`` — an exported empty KEYSTONE_PROFILE_STORE
    explicitly disables the store. Lives here so the env read stays
    inside config.py (keystone-lint KL003)."""
    if "KEYSTONE_PROFILE_STORE" in os.environ:
        return os.environ["KEYSTONE_PROFILE_STORE"] or None
    return config.profile_store
