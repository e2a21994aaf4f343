"""Shape-stable serving benchmark: per-shape jit vs bucketed + AOT-warmed.

Serves a mixed-size request trace (row counts drawn uniformly from
[1, max_batch]) through a representative fused serving head
(standardize -> cosine random features -> signed-Hellinger -> L2
normalize -> linear scores) two ways:

1. naive — today's ``Transformer.batch_call`` per-shape ``jax.jit``:
   every distinct row count recompiles the whole fused chain;
2. bucketed — ``workflow.serving.CompiledPipeline``: the pow-2 bucket
   ladder is AOT-compiled BEFORE traffic (``warmup``), every request is
   padded onto a bucket and served by a pre-compiled executable.

Reports steady-state p50/p99/mean request latency, throughput, and
compile counts for both paths (compiles are counted two ways: the
serving layer's own counter and a jax monitoring listener on XLA
compile-cache requests). The acceptance gate: ZERO compiles after
warmup on the bucketed path, and bucketed p99 at least 2x better than
naive. A third phase drives the ``PipelineService`` micro-batcher with
concurrent single-row clients and reports the coalescing ratio.

Usage: python tools/bench_serve.py [--requests 160] [--max-batch 256]
           [--out BENCH_serve.json]
Prints one JSON line and (with --out) writes the machine-readable
result for future PRs to regress against.

``--overload`` runs the hardening bench instead: calibrate the
micro-batcher's closed-loop capacity, then drive it OPEN-loop at 2x
sustained over-capacity against a bounded pending queue and per-request
deadlines. Reports the fast-fail rate (QueueFullError + DeadlineExceeded
— rejections that cost no device time), accepted-request p99, and the
no-stranded-future invariant. The gate: excess load turns into fast
failures while accepted p99 stays bounded by the deadline — degradation,
not a cliff.

``--precision`` runs the memory-bounded precision A/B instead: the f32
HAND-PICKED ladder (one bucket at the provisioned maximum — the
pad-everything-to-max config) vs the HBM-PLANNED ladder served at bf16
through the same trained canonical head. Gates hard on any backend:
planned+bf16 beats the baseline on wall AND p99 (pad-overhead structure,
not core count), the default-built engine serves bit-identically to the
explicit-f32 engine on the same ladder (the knob-off contract), the
ladder change itself moves answers at most float noise, the multiclass
quality gate stays within its declared tolerance of the f32 oracle
(``CompiledPipeline.qualify`` refuses otherwise), zero post-warmup
compiles; the appended ``serve_precision`` row carries the planner's
per-bucket bytes + provenance under bench_watch.

``--devices N`` runs the replica-scaling bench instead: the same uniform
mixed-size trace is served at devices=1 and devices=N through the
pipelined micro-batcher (``make bench-serve-replicas`` forces the
8-host-device CPU mesh via --xla_force_host_platform_device_count=8).
Reports per-pool-width throughput, the dispatch-balance counters
(max/min ≤ 3x gate), and a bit-identity check of replica outputs against
the single-device engine; the row APPENDS to --out so the scaling
evidence accumulates next to the main serving anchor. The hard ≥1.3x
throughput gate only applies when the fingerprint shows ≥2 host cores —
on a 1-core container N replicas time-slice one core, so the gate there
is merely "no worse".
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_chain(d: int, features: int, classes: int, seed: int):
    """A fresh serving-head instance (fresh jit caches) over shared
    deterministic weights."""
    from keystone_tpu.nodes.learning.linear_mapper import LinearMapper
    from keystone_tpu.nodes.stats.hellinger import SignedHellingerMapper
    from keystone_tpu.nodes.stats.normalizer import L2Normalizer
    from keystone_tpu.nodes.stats.random_features import CosineRandomFeatures
    from keystone_tpu.nodes.stats.scalers import StandardScalerModel
    from keystone_tpu.workflow.pipeline import FusedTransformer

    rng = np.random.default_rng(seed)
    return FusedTransformer(
        [
            StandardScalerModel(
                rng.normal(size=d).astype(np.float32),
                (1.0 + rng.uniform(size=d)).astype(np.float32),
            ),
            CosineRandomFeatures.create(d, features, seed=seed),
            SignedHellingerMapper(),
            L2Normalizer(),
            LinearMapper(
                (rng.normal(size=(features, classes)) / np.sqrt(features))
                .astype(np.float32)
            ),
        ]
    )


def write_result(path: str, line: str, metric: str) -> None:
    """One latest row per metric in the JSONL evidence file: rewrite
    keeping other metrics' rows, so the main anchor, the overload row,
    and the replica-scaling row coexist in --out without any mode's
    writer wiping another's evidence."""
    rows = []
    if os.path.exists(path):
        with open(path) as f:
            for raw in f:
                raw = raw.strip()
                if not raw:
                    continue
                try:
                    if json.loads(raw).get("metric") == metric:
                        continue  # superseded by this run
                except ValueError:
                    pass
                rows.append(raw)
    rows.append(line)
    # Atomic rewrite (the disk_cache.py idiom): an interrupt mid-write
    # must not destroy the OTHER modes' accumulated evidence rows.
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write("\n".join(rows) + "\n")
    os.replace(tmp, path)


def lat_stats(lats_s) -> dict:
    ms = np.asarray(lats_s) * 1e3
    return {
        "p50_ms": round(float(np.percentile(ms, 50)), 3),
        "p99_ms": round(float(np.percentile(ms, 99)), 3),
        "mean_ms": round(float(ms.mean()), 3),
        "total_s": round(float(ms.sum() / 1e3), 3),
    }


def nearest_rank_ms(lats_s, p: float) -> float:
    """Nearest-rank percentile in ms — the estimator the registry's
    log-bucket histogram implements, used for the agreement cross-check so
    both sides measure the SAME order statistic (numpy's default linear
    interpolation can smooth across a tail jump that nearest-rank, by
    design, reports)."""
    import math

    s = sorted(lats_s)
    return s[max(0, math.ceil(len(s) * p / 100.0) - 1)] * 1e3


def run_overload(cp, args) -> dict:
    """2x-capacity open-loop hammering of the bounded-queue service."""
    from keystone_tpu.utils.reliability import (
        DeadlineExceeded,
        QueueFullError,
        ServiceClosed,
    )
    from keystone_tpu.workflow.serving import PipelineService

    x = np.zeros((args.d,), dtype=np.float32)
    clients = max(1, args.service_clients)

    # -- calibration. The service's capacity is flushes/s x rows/flush.
    # An unbounded row budget makes a coalescing service effectively
    # saturation-proof from a handful of host threads (one flush absorbs
    # hundreds of rows), so the overload scenario pins max_rows — the
    # stand-in for a device already at its batch budget — and capacity
    # follows from the measured per-flush latency at that budget.
    xb = np.zeros((args.overload_max_rows, args.d), dtype=np.float32)
    for _ in range(5):
        cp(xb)
    n_cal = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < args.calibrate_seconds or n_cal < 10:
        cp(xb)
        n_cal += 1
    t_flush = (time.perf_counter() - t0) / n_cal
    capacity_rps = args.overload_max_rows / t_flush

    # -- open loop at 2x: clients submit on a fixed clock, never waiting
    # for results, so the offered rate really is 2x what the service can
    # sustain — the queue must absorb or reject the difference.
    offered_rps = 2.0 * capacity_rps
    interval = clients / offered_rps
    lock = threading.Lock()
    accepted_lat, outcomes = [], {
        "ok": 0, "rejected": 0, "expired": 0, "closed": 0, "error": 0,
    }
    futures = []

    svc = PipelineService(
        cp,
        max_delay_ms=0.5,
        max_rows=args.overload_max_rows,
        max_pending=args.overload_max_pending,
        deadline_ms=args.overload_deadline_ms,
    )

    def on_done(fut, t_submit):
        lat = time.perf_counter() - t_submit
        exc = fut.exception()
        with lock:
            if exc is None:
                outcomes["ok"] += 1
                accepted_lat.append(lat)
            elif isinstance(exc, DeadlineExceeded):
                outcomes["expired"] += 1
            elif isinstance(exc, ServiceClosed):
                outcomes["closed"] += 1
            else:
                outcomes["error"] += 1

    def open_loop(cid):
        end = time.perf_counter() + args.overload_seconds
        next_t = time.perf_counter() + (cid / clients) * interval
        while time.perf_counter() < end:
            pause = next_t - time.perf_counter()
            if pause > 0:
                time.sleep(pause)
            next_t += interval
            t1 = time.perf_counter()
            try:
                fut = svc.submit(x)
            except QueueFullError:
                with lock:
                    outcomes["rejected"] += 1
                continue
            with lock:
                futures.append(fut)
            fut.add_done_callback(lambda f, t1=t1: on_done(f, t1))

    threads = [
        threading.Thread(target=open_loop, args=(c,)) for c in range(clients)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    svc.close()  # drains; MUST leave no future unresolved
    unresolved = sum(not f.done() for f in futures)
    total = sum(outcomes.values())
    fast_fails = outcomes["rejected"] + outcomes["expired"]
    acc = lat_stats(accepted_lat) if accepted_lat else None
    # The deadline bounds time-in-queue; execution adds at most a batch.
    p99_bound_ms = 2.0 * args.overload_deadline_ms
    return {
        "clients": clients,
        "flush_ms": round(t_flush * 1e3, 3),
        "max_rows_per_flush": args.overload_max_rows,
        "capacity_rps": round(capacity_rps, 1),
        "offered_rps": round(offered_rps, 1),
        "offered_requests": total,
        "max_pending": args.overload_max_pending,
        "deadline_ms": args.overload_deadline_ms,
        "outcomes": outcomes,
        "fast_fail_rate": round(fast_fails / total, 4) if total else None,
        "accepted": acc,
        "unresolved_futures": unresolved,
        "service": svc.stats(),
        "pass": {
            "no_stranded_futures": unresolved == 0,
            "backpressure_engaged": fast_fails > 0,
            "accepted_p99_bounded": bool(
                acc and acc["p99_ms"] <= p99_bound_ms
            ),
        },
    }


def run_daemon_bench(args) -> dict:
    """Open-loop load at 2x measured capacity through the REAL socket
    ingress of the serving daemon, with two hot-swaps performed under
    the sustained flood.

    Tenants: one gold (protected: reserved budget headroom + deadline)
    probed closed-loop for its p99, one best-effort flood driven
    open-loop at 2x the capacity measured closed-loop through the same
    wire. Gates: backpressure engages (fast-fail 429/504 on the excess
    instead of a latency cliff), gold p99 stays within 2x its deadline,
    both swaps succeed with responses spanning >= 2 generations, and
    every request issued gets exactly one response (zero
    dropped/unresolved)."""
    import tempfile

    import serve_daemon as sd  # tools/ is on sys.path when run as a script

    from keystone_tpu.workflow.daemon import ServingDaemon, Tenant
    from keystone_tpu.workflow.serialization import save_artifact

    d = args.d
    out_dir = tempfile.mkdtemp(prefix="keystone_daemon_bench_")
    arts = []
    for seed in (args.seed, args.seed + 1):
        chain = build_chain(d, args.features, args.classes, seed)
        pipe = chain.to_pipeline().fit()
        path = os.path.join(out_dir, f"model_s{seed}.kart")
        save_artifact(pipe, path, feature_shape=(d,), dtype="float32")
        arts.append(path)

    # Admission capacity is the daemon's pending budget: best-effort is
    # refused past BE_BUDGET_FRAC of it. The flood offers 2x that
    # concurrency through the real socket, so the excess MUST fast-fail
    # at admission (429 before any device work) while gold rides its
    # reserved headroom.
    pending_budget = max(4, args.service_clients)
    from keystone_tpu.workflow.daemon import BE_BUDGET_FRAC

    be_limit = max(1, int(pending_budget * BE_BUDGET_FRAC))
    clients = 2 * be_limit
    tenants = {
        "bk-gold": Tenant("gold", "bk-gold", qps=0, tier="gold"),
        "bk-be": Tenant("flood", "bk-be", qps=0, tier="best_effort"),
    }
    daemon = ServingDaemon(
        artifact=arts[0], tenants=tenants, devices=1,
        max_batch=args.overload_max_rows * 2,
        max_rows=args.overload_max_rows,
        max_delay_ms=0.5,
        max_pending=args.overload_max_pending,
        pending_budget=pending_budget,
        gold_deadline_ms=args.overload_deadline_ms,
        be_deadline_ms=args.overload_deadline_ms,
        name="bench-daemon",
        swap_token="bench-swap-token",
    )
    x_row = np.zeros((d,), dtype=np.float32).tolist()
    lock = threading.Lock()

    try:
        # -- calibrate: sustained within-budget closed-loop capacity
        # through the wire (be_limit concurrent connections = exactly
        # the admitted best-effort concurrency).
        def closed_loop(stop_t, counter):
            sc = sd.SocketClient(daemon.socket_port)
            n = 0
            try:
                while time.perf_counter() < stop_t:
                    resp = sc.request({"x": x_row, "key": "bk-be"})
                    if resp.get("status") == 200:
                        n += 1
            finally:
                sc.close()
                with lock:
                    counter.append(n)

        cal_counts: list = []
        t_end = time.perf_counter() + args.calibrate_seconds
        cal_threads = [
            threading.Thread(target=closed_loop, args=(t_end, cal_counts))
            for _ in range(be_limit)
        ]
        t0 = time.perf_counter()
        for t in cal_threads:
            t.start()
        for t in cal_threads:
            t.join()
        cal_wall = time.perf_counter() - t0
        capacity_rps = sum(cal_counts) / cal_wall

        # -- flood: 2x the admitted concurrency hammering the socket;
        # gold probes closed-loop via HTTP; two hot-swaps land mid-flood.
        outcomes = {"ok": 0, "rejected": 0, "expired": 0, "closed": 0,
                    "error": 0, "conn": 0}
        gens_seen = set()
        gold_lats: list = []
        gold_errors: list = []
        swap_results: list = []
        stop = threading.Event()

        def flood(cid):
            sc = sd.SocketClient(daemon.socket_port)
            end = time.perf_counter() + args.overload_seconds
            try:
                while time.perf_counter() < end:
                    try:
                        resp = sc.request({"x": x_row, "key": "bk-be"})
                    except (ConnectionError, OSError):
                        with lock:
                            outcomes["conn"] += 1
                        sc.close()
                        sc = sd.SocketClient(daemon.socket_port)
                        continue
                    status = resp.get("status")
                    with lock:
                        if status == 200:
                            outcomes["ok"] += 1
                            gens_seen.add(resp.get("generation"))
                        elif status == 429:
                            outcomes["rejected"] += 1
                        elif status == 504:
                            outcomes["expired"] += 1
                        elif status == 503:
                            outcomes["closed"] += 1
                        else:
                            outcomes["error"] += 1
            finally:
                sc.close()

        def gold_probe():
            while not stop.is_set():
                t1 = time.perf_counter()
                st, doc = sd.http_post(
                    daemon.http_port, "/predict", {"x": x_row},
                    {"X-API-Key": "bk-gold"},
                )
                if st == 200:
                    gold_lats.append(time.perf_counter() - t1)
                    gens_seen.add(doc.get("generation"))
                else:
                    gold_errors.append((st, doc.get("error")))
                time.sleep(0.01)

        def swapper():
            # Two swaps spread across the flood window. retries=1: /swap
            # is not idempotent — a retried ack-lost swap would run twice.
            for i, path in enumerate((arts[1], arts[0])):
                time.sleep(args.overload_seconds / 3.0)
                st, doc = sd.http_post(
                    daemon.http_port, "/swap", {"artifact": path},
                    {"X-Swap-Token": "bench-swap-token"},
                    timeout=120, retries=1,
                )
                swap_results.append((st, doc))

        flood_threads = [
            threading.Thread(target=flood, args=(c,)) for c in range(clients)
        ]
        gold_t = threading.Thread(target=gold_probe, daemon=True)
        swap_t = threading.Thread(target=swapper)
        for t in flood_threads:
            t.start()
        gold_t.start()
        swap_t.start()
        for t in flood_threads:
            t.join()
        swap_t.join()
        stop.set()
        gold_t.join(timeout=30)

        stats = daemon.stats()
        total = sum(outcomes.values())
        fast_fails = outcomes["rejected"] + outcomes["expired"]
        gold = lat_stats(gold_lats) if gold_lats else None
        p99_bound_ms = 2.0 * args.overload_deadline_ms
        swaps_ok = (
            len(swap_results) == 2
            and all(st == 200 for st, _ in swap_results)
        )
        gold_total = len(gold_lats) + len(gold_errors)
        gold_ok_frac = len(gold_lats) / gold_total if gold_total else None
        offered_rps = total / max(args.overload_seconds, 1e-9)
        result = {
            "metric": "serve_daemon",
            "unit": "ms",
            "clients": clients,
            "pending_budget_admission": pending_budget,
            "be_admission_limit": be_limit,
            "capacity_rps": round(capacity_rps, 1),
            "offered_rps": round(offered_rps, 1),
            "offered_requests": total,
            "deadline_ms": args.overload_deadline_ms,
            "service_max_pending": args.overload_max_pending,
            "outcomes": outcomes,
            "fast_fail_rate": round(fast_fails / total, 4) if total else None,
            "gold": gold,
            "gold_ok_frac": (
                round(gold_ok_frac, 4) if gold_ok_frac is not None else None
            ),
            "gold_errors": gold_errors[:10],
            "generations_seen": sorted(
                g for g in gens_seen if g is not None
            ),
            "swaps": stats["swaps"],
            "active_leftover": stats["active_requests"],
            "pass": {
                "backpressure_engaged": fast_fails > 0,
                "gold_p99_bounded": bool(
                    gold and gold["p99_ms"] <= p99_bound_ms
                ),
                # A lone gold 504 riding a swap-compile stall on a 1-core
                # host is noise; sustained gold rejection is the failure.
                "gold_mostly_served": bool(
                    gold_ok_frac is not None and gold_ok_frac >= 0.95
                ),
                "swap_under_load_ok": swaps_ok,
                "two_generations_served": len(gens_seen) >= 2,
                "zero_unresolved": (
                    stats["active_requests"] == 0 and outcomes["conn"] == 0
                    and outcomes["error"] == 0
                ),
            },
        }
        result["ok"] = all(result["pass"].values())
        return result
    finally:
        daemon.close()


def run_telemetry_bench(args) -> dict:
    """Telemetry-on vs telemetry-off A/B flood through the daemon's
    socket ingress: the same closed-loop load served twice, once with
    durable journey export off (KEYSTONE_TELEMETRY_DIR unset) and once
    with it writing to a scratch directory.

    Gates: the telemetry-on phase stays within a bounded throughput
    overhead of the off phase (the writer thread + queue handoff is the
    ONLY added hot-path work, so a large gap means the export leaked
    into admission), every enqueued record is accounted for as either
    durably written or counted-dropped after the close-time drain (the
    drops-counted-never-blocks contract), and the on-phase journeys are
    actually recoverable from disk."""
    import glob as _glob
    import tempfile

    import serve_daemon as sd  # tools/ is on sys.path when run as a script

    from keystone_tpu.workflow.daemon import ServingDaemon
    from keystone_tpu.workflow.serialization import save_artifact
    from keystone_tpu.utils.telemetry import active_telemetry, reset_telemetry

    d = args.d
    out_dir = tempfile.mkdtemp(prefix="keystone_telemetry_bench_")
    chain = build_chain(d, args.features, args.classes, args.seed)
    pipe = chain.to_pipeline().fit()
    art = os.path.join(out_dir, "model.kart")
    save_artifact(pipe, art, feature_shape=(d,), dtype="float32")

    x_row = np.zeros((d,), dtype=np.float32).tolist()
    clients = max(2, args.service_clients)
    seconds = args.telemetry_seconds
    lock = threading.Lock()

    def run_phase(tag: str, telemetry_dir: str | None) -> dict:
        if telemetry_dir is None:
            os.environ.pop("KEYSTONE_TELEMETRY_DIR", None)
        else:
            os.environ["KEYSTONE_TELEMETRY_DIR"] = telemetry_dir
        reset_telemetry()
        daemon = ServingDaemon(
            artifact=art, devices=1, max_delay_ms=0.5,
            name=f"telemetry-bench-{tag}",
        )
        counts: list = []
        lats: list = []
        try:
            def closed_loop():
                sc = sd.SocketClient(daemon.socket_port)
                n = 0
                mine: list = []
                end = time.perf_counter() + seconds
                try:
                    while time.perf_counter() < end:
                        t1 = time.perf_counter()
                        resp = sc.request({"x": x_row})
                        if resp.get("status") == 200:
                            n += 1
                            mine.append(time.perf_counter() - t1)
                finally:
                    sc.close()
                    with lock:
                        counts.append(n)
                        lats.extend(mine)

            threads = [threading.Thread(target=closed_loop)
                       for _ in range(clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
        finally:
            daemon.close()  # drains the telemetry queue before return
        tel = active_telemetry()
        tstats = tel.stats() if tel is not None else None
        reset_telemetry()
        served = sum(counts)
        return {
            "served": served,
            "req_per_s": served / max(wall, 1e-9),
            "lat": lat_stats(lats) if lats else None,
            "telemetry": tstats,
        }

    prior_env = os.environ.get("KEYSTONE_TELEMETRY_DIR")
    try:
        off = run_phase("off", None)
        tel_dir = os.path.join(out_dir, "telemetry")
        on = run_phase("on", tel_dir)
    finally:
        if prior_env is None:
            os.environ.pop("KEYSTONE_TELEMETRY_DIR", None)
        else:
            os.environ["KEYSTONE_TELEMETRY_DIR"] = prior_env
        reset_telemetry()

    overhead = max(0.0, 1.0 - on["req_per_s"] / max(off["req_per_s"], 1e-9))
    ts = on["telemetry"] or {}
    enqueued = int(ts.get("enqueued", 0))
    written = int(ts.get("written", 0))
    dropped = int(ts.get("dropped", 0))
    journeys_on_disk = 0
    for seg in _glob.glob(os.path.join(tel_dir, "keystone_telemetry_*.jsonl")):
        with open(seg, "r", encoding="utf-8") as fh:
            for raw in fh:
                try:
                    rec = json.loads(raw)
                except ValueError:
                    continue
                if rec.get("kind") == "journey":
                    journeys_on_disk += 1

    result = {
        "metric": "serve_telemetry",
        "unit": "req/s",
        "clients": clients,
        "seconds": seconds,
        "off": {"req_per_s": round(off["req_per_s"], 1),
                "served": off["served"], "lat": off["lat"]},
        "on": {"req_per_s": round(on["req_per_s"], 1),
               "served": on["served"], "lat": on["lat"]},
        "overhead_frac": round(overhead, 4),
        "overhead_bound": args.telemetry_overhead_bound,
        "records_enqueued": enqueued,
        "records_written": written,
        "records_dropped": dropped,
        "journeys_on_disk": journeys_on_disk,
        "pass": {
            "overhead_bounded": overhead <= args.telemetry_overhead_bound,
            "telemetry_engaged": enqueued > 0 and written > 0,
            # The never-blocks contract: after the close-time drain every
            # enqueued record is durably written or counted as dropped —
            # nothing stalls in the queue, nothing vanishes uncounted.
            "nonblocking_accounted": enqueued == written + dropped,
            # Every journey that was not a counted drop is on disk.
            "journeys_recoverable": (
                journeys_on_disk >= on["served"] - dropped
            ),
        },
    }
    result["ok"] = all(result["pass"].values())
    return result


def build_trained_chain(d: int, features: int, classes: int, seed: int,
                        n_train: int = 2048, n_eval: int = 512):
    """The quality-gated serving head: the canonical featurize chain with
    its linear map TRAINED (least squares on margin-separated synthetic
    classes) instead of random — random weights leave argmax margins at
    quantization scale, which is not the scenario a precision ladder
    serves. Returns ``(chain, X_eval, y_eval)``."""
    from keystone_tpu.nodes.learning.linear_mapper import LinearMapper
    from keystone_tpu.workflow.pipeline import FusedTransformer

    base = build_chain(d, features, classes, seed)
    prefix = FusedTransformer(base.stages[:-1])
    rng = np.random.default_rng(seed + 1)
    centroids = rng.normal(size=(classes, d)).astype(np.float32) * 2.0
    y = rng.integers(0, classes, n_train)
    X = (centroids[y] + 0.3 * rng.normal(size=(n_train, d))).astype(
        np.float32
    )
    F = np.asarray(prefix.batch_call(X))
    Y = np.eye(classes, dtype=np.float32)[y]
    W, *_ = np.linalg.lstsq(F, Y, rcond=None)
    chain = FusedTransformer(
        base.stages[:-1] + [LinearMapper(W.astype(np.float32))]
    )
    ye = rng.integers(0, classes, n_eval)
    Xe = (centroids[ye] + 0.3 * rng.normal(size=(n_eval, d))).astype(
        np.float32
    )
    return chain, Xe, ye


def run_precision_bench(args) -> dict:
    """Memory-bounded serving A/B: the f32 HAND-PICKED ladder (one bucket
    at the provisioned maximum — the classic pad-everything-to-max AOT
    config, config.serve_buckets-style) vs the HBM-PLANNED ladder served
    at bf16 precision, on the same mixed-size trace through the same
    trained canonical head.

    Gates (hard on any backend — the win is pad-overhead structure, not
    core count): planned+bf16 beats the hand-picked f32 baseline on wall
    AND p99; the default-built engine is BIT-identical to the explicit
    f32 engine on the same ladder (the knob-off contract — the default
    path is today's construction, untouched) while the ladder change
    itself moves answers at most float noise (bit-identity across
    DIFFERENT bucket shapes is a backend property; shared-rung chunks
    stay bit-identical, pinned in tests); the bf16 quality gate
    (multiclass accuracy vs the f32 oracle, evaluation/ metrics) stays
    within its declared tolerance or ``qualify()`` refuses; zero
    post-warmup compiles on every engine; and the planner's evidence
    (per-bucket planned bytes, provenance, trims) rides the row."""
    from keystone_tpu.utils.metrics import CompileEventCounter
    from keystone_tpu.workflow.serving import CompiledPipeline

    d, features, classes = args.d, args.features, args.classes
    provisioned = args.provisioned_max or 4 * args.max_batch
    rng = np.random.default_rng(args.seed)
    sizes = rng.integers(1, args.max_batch + 1, size=args.requests)
    trace = [
        rng.normal(size=(int(n), d)).astype(np.float32) for n in sizes
    ]
    rows = int(sizes.sum())
    chain, X_eval, y_eval = build_trained_chain(
        d, features, classes, args.seed
    )
    compile_events = CompileEventCounter()

    def serve_phase(cp):
        cp.warmup((d,))
        ev0 = compile_events.count
        lats, outs = [], []
        t0 = time.perf_counter()
        for x in trace:
            t1 = time.perf_counter()
            outs.append(cp(x))
            lats.append(time.perf_counter() - t1)
        wall = time.perf_counter() - t0
        return {
            "lats": lats,
            "wall": wall,
            "outs": outs,
            "post_warmup_compiles": compile_events.count - ev0,
            "stats": cp.stats(),
        }

    # -- baseline: f32, hand-picked single provisioned-max bucket (every
    # request pads to the bucket someone sized for the biggest batch
    # they could imagine).
    base = serve_phase(CompiledPipeline(
        chain, buckets=[provisioned], devices=1, precision="f32",
        name="prec-handpicked-f32",
    ))
    # -- planned ladder, knob off: must be bit-identical to the baseline.
    planned = serve_phase(CompiledPipeline(
        chain, max_batch=provisioned, devices=1, precision="f32",
        name="prec-planned-f32",
    ))
    # -- knob-off contract: the engine built WITHOUT the precision knob
    # (today's construction) must serve bit-identically to the explicit
    # f32 engine on the same ladder — the default path is untouched.
    cp_default = CompiledPipeline(
        chain, max_batch=provisioned, devices=1,
        name="prec-planned-default",
    ).warmup((d,))
    bit_identical = all(
        np.array_equal(cp_default(x), out)
        for x, out in zip(trace, planned["outs"])
    )
    # Cross-ladder agreement is NUMERIC, not bit-level: a different
    # bucket shape legitimately changes gemm tiling (reduction order)
    # on some backends, so the evidence is the max relative error.
    ladder_rel_err = max(
        float(np.abs(a - b).max() / max(np.abs(a).max(), 1e-12))
        for a, b in zip(base["outs"], planned["outs"])
    )
    # -- planned ladder + bf16: the throughput mode under quality gates.
    cp_bf16 = CompiledPipeline(
        chain, max_batch=provisioned, devices=1, precision="bf16",
        name="prec-planned-bf16",
    )
    quality = cp_bf16.qualify(
        X_eval, y=y_eval, metric="multiclass",
        tolerance=args.quality_tolerance,
    )
    bf16 = serve_phase(cp_bf16)
    base_p99 = nearest_rank_ms(base["lats"], 99)
    bf16_p99 = nearest_rank_ms(bf16["lats"], 99)
    plan = planned["stats"]["plan"]
    result = {
        "metric": "serve_precision",
        "unit": "ms",
        "requests": args.requests,
        "rows": rows,
        "d": d,
        "features": features,
        "classes": classes,
        "provisioned_max": provisioned,
        "handpicked_ladder": base["stats"]["ladder"],
        "planned_ladder": planned["stats"]["ladder"],
        "plan": plan,
        "precision": "bf16",
        "quality": quality,
        "handpicked_f32": {
            **lat_stats(base["lats"]),
            "rows_per_s": round(rows / base["wall"], 1),
            "pad_rows_per_request": round(
                sum(provisioned - s for s in sizes) / len(sizes), 1
            ),
            "post_warmup_compiles": base["post_warmup_compiles"],
        },
        "planned_f32": {
            **lat_stats(planned["lats"]),
            "rows_per_s": round(rows / planned["wall"], 1),
            "post_warmup_compiles": planned["post_warmup_compiles"],
        },
        "planned_bf16": {
            **lat_stats(bf16["lats"]),
            "rows_per_s": round(rows / bf16["wall"], 1),
            "post_warmup_compiles": bf16["post_warmup_compiles"],
        },
        "speedup": {
            # "throughput" (wall ratio), matching the main serve row's
            # leaf naming — "wall" is a lower-better fragment in
            # bench_watch, and a speedup must judge higher-better.
            "throughput": round(base["wall"] / bf16["wall"], 2),
            "p99": round(base_p99 / bf16_p99, 2),
            "throughput_planned_f32": round(
                base["wall"] / planned["wall"], 2
            ),
        },
        "bit_identical_f32": bit_identical,
        "ladder_change_max_rel_err": ladder_rel_err,
        "pass": {
            # Structural pad-overhead win: hard on every backend.
            "wall_speedup_ge_1p5": base["wall"] / bf16["wall"] >= 1.5,
            "p99_speedup_ge_1p5": base_p99 / bf16_p99 >= 1.5,
            "bit_identical_when_knob_off": bit_identical,
            # A ladder change must not move answers beyond float noise
            # (bit-identity across DIFFERENT bucket shapes is a backend
            # property — gemm tiling follows the batch dim; shared-rung
            # chunks stay bit-identical, pinned in tests).
            "ladder_change_within_noise": ladder_rel_err <= 1e-5,
            "quality_within_tolerance": quality["within_tolerance"],
            "planner_ran": bool(plan and plan.get("enabled")),
            "zero_post_warmup_compiles": (
                base["post_warmup_compiles"] == 0
                and planned["post_warmup_compiles"] == 0
                and bf16["post_warmup_compiles"] == 0
            ),
        },
    }
    result["ok"] = all(result["pass"].values())
    return result


def run_replica_bench(args) -> dict:
    """Replica-pool scaling: serve the same uniform mixed-size trace at
    devices=1 and devices=N through the pipelined micro-batcher, with
    concurrent closed-loop clients keeping the dispatcher fed."""
    import jax

    from keystone_tpu.utils.metrics import environment_fingerprint
    from keystone_tpu.workflow.serving import CompiledPipeline, PipelineService

    n_local = len(jax.local_devices())
    if args.devices > n_local:
        raise SystemExit(
            f"--devices {args.devices} exceeds the {n_local} local devices "
            "(force more with --xla_force_host_platform_device_count)"
        )
    counts = sorted({1, args.devices})
    rng = np.random.default_rng(args.seed)
    sizes = rng.integers(1, args.max_batch + 1, size=args.requests)
    trace = [
        rng.normal(size=(int(n), args.d)).astype(np.float32) for n in sizes
    ]
    rows = int(sizes.sum())
    clients = max(1, args.service_clients)

    per_devices = {}
    single_outputs = None
    for c in counts:
        cp = CompiledPipeline(
            build_chain(args.d, args.features, args.classes, args.seed),
            max_batch=args.max_batch,
            devices=c,
            inflight=args.inflight,
        )
        cp.warmup((args.d,))
        # Bit-identity evidence: every request's output from the pool must
        # equal the single-device engine's, bit for bit (same XLA program,
        # same device kind — padding and replica choice must not matter).
        outputs = [cp(x) for x in trace]
        if single_outputs is None:
            single_outputs = outputs
            outputs_match = True
        else:
            outputs_match = all(
                np.array_equal(a, b)
                for a, b in zip(single_outputs, outputs)
            )
        # Balance is gated on the SERVICE phase alone: snapshot the
        # cumulative dispatch counters so the (uniformly round-robined)
        # bit-identity pass above can't mask a skewed dispatcher.
        pre_dispatch = dict(cp.stats()["replica_dispatches"])
        # Throughput: closed-loop clients × the shared trace through the
        # service — ~`clients` groups outstanding keeps >1 replica busy.
        errs: list = []

        def client(cid: int, svc):
            try:
                for i in range(cid, len(trace), clients):
                    svc.submit(trace[i]).result(timeout=120)
            except Exception as e:  # pragma: no cover - surfaced below
                errs.append(e)

        with PipelineService(
            cp, max_delay_ms=0.5, inflight=args.inflight
        ) as svc:
            threads = [
                threading.Thread(target=client, args=(k, svc))
                for k in range(clients)
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            stats = svc.stats()
        if errs:
            raise errs[0]
        dispatches = {
            k: v - pre_dispatch.get(k, 0)
            for k, v in stats["compiled"]["replica_dispatches"].items()
        }
        served = {k: v for k, v in dispatches.items() if v > 0}
        balance = (
            max(dispatches.values()) / max(1, min(dispatches.values()))
            if dispatches else None
        )
        per_devices[str(c)] = {
            "devices": c,
            "wall_s": round(wall, 3),
            "rows_per_s": round(rows / wall, 1),
            "dispatch_balance": dispatches,
            "balance_max_over_min": (
                round(balance, 2) if balance is not None else None
            ),
            "replicas_serving": len(served),
            "outputs_match_single_device": outputs_match,
            "batches_run": stats["batches_run"],
            "replica_deaths": stats["replicas"]["deaths"],
            "latency": stats["latency"],
        }

    lo, hi = str(counts[0]), str(counts[-1])
    compared = counts[0] != counts[-1]
    speedup = (
        per_devices[hi]["rows_per_s"] / per_devices[lo]["rows_per_s"]
        if compared else 1.0
    )
    cores = os.cpu_count() or 1
    # One core can't run two replicas at once: the hard scaling gate only
    # binds on multi-core hosts; single-core merely must not regress. A
    # --devices 1 run compares nothing, so no gate applies at all.
    threshold = (1.3 if cores >= 2 else 0.75) if compared else None
    top = per_devices[hi]
    return {
        "metric": "serve_replica_scaling",
        "host_cores": cores,
        "env": environment_fingerprint(),
        "requests": args.requests,
        "rows": rows,
        "d": args.d,
        "features": args.features,
        "classes": args.classes,
        "clients": clients,
        "inflight": args.inflight,
        "devices_swept": counts,
        "per_devices": per_devices,
        "speedup_vs_single": round(speedup, 2),
        "speedup_threshold": threshold,
        "pass": {
            "outputs_bit_identical": all(
                e["outputs_match_single_device"]
                for e in per_devices.values()
            ),
            "every_replica_served": (
                top["replicas_serving"] == counts[-1]
            ),
            "balance_max_min_le_3x": (
                top["balance_max_over_min"] is not None
                and top["balance_max_over_min"] <= 3.0
            ),
            "throughput_gate": (
                speedup >= threshold if compared else None
            ),
            "throughput_gate_is_hard": compared and cores >= 2,
        },
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=160,
                    help="requests in the mixed-size trace")
    ap.add_argument("--max-batch", type=int, default=256,
                    help="largest request row count / top serving bucket")
    ap.add_argument("--d", type=int, default=64, help="input feature dim")
    ap.add_argument("--features", type=int, default=512,
                    help="random-feature width of the serving head")
    ap.add_argument("--classes", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--service-clients", type=int, default=4,
                    help="concurrent single-row clients for the "
                    "micro-batcher phase (0 skips it)")
    ap.add_argument("--service-requests", type=int, default=200,
                    help="total single-row requests across clients")
    ap.add_argument("--out", type=str, default=None,
                    help="also write the JSON result to this path")
    ap.add_argument("--overload", action="store_true",
                    help="run the hardening bench instead: 2x sustained "
                    "over-capacity against a bounded queue + deadlines")
    ap.add_argument("--overload-seconds", type=float, default=3.0)
    ap.add_argument("--calibrate-seconds", type=float, default=1.5)
    ap.add_argument("--overload-max-pending", type=int, default=32)
    ap.add_argument("--overload-deadline-ms", type=float, default=100.0)
    ap.add_argument("--overload-max-rows", type=int, default=4,
                    help="rows per service flush in the overload phase — "
                    "the capacity-limited-device stand-in")
    ap.add_argument("--precision", action="store_true",
                    help="run the memory-bounded precision bench instead: "
                    "f32 hand-picked single-bucket ladder vs HBM-planned "
                    "ladder + bf16 under the evaluation/ quality gate")
    ap.add_argument("--provisioned-max", type=int, default=0,
                    help="the hand-picked baseline's provisioned bucket "
                    "(0 = 4x --max-batch): the pad-everything-to-max "
                    "config the planner replaces")
    ap.add_argument("--quality-tolerance", type=float, default=None,
                    help="override the declared quality-gate tolerance "
                    "(default: serving.PRECISION_QUALITY_TOLERANCES)")
    ap.add_argument("--daemon", action="store_true",
                    help="run the networked-daemon bench instead: open-loop "
                    "load at 2x capacity through the REAL socket ingress, "
                    "gold-tier p99 under deadline, two hot-swaps under load")
    ap.add_argument("--telemetry", action="store_true",
                    help="run the telemetry-overhead bench instead: the "
                    "same closed-loop socket flood with durable journey "
                    "export off vs on, gated on bounded throughput "
                    "overhead + the drops-counted-never-blocks contract")
    ap.add_argument("--telemetry-seconds", type=float, default=2.0)
    ap.add_argument("--telemetry-overhead-bound", type=float, default=0.30,
                    help="max allowed fractional req/s loss with durable "
                    "export on (the writer thread is off the hot path, "
                    "but 1-core CI hosts pay real scheduler tax)")
    ap.add_argument("--devices", type=int, default=0,
                    help="run the replica-scaling bench instead: serve the "
                    "trace at devices=1 and devices=N, report throughput + "
                    "dispatch balance (0 = off)")
    ap.add_argument("--inflight", type=int, default=2,
                    help="per-replica in-flight window for the replica "
                    "bench's pipelined dispatch")
    args = ap.parse_args()

    from keystone_tpu.utils.platform import device_info

    backend = device_info()["platform"]
    import jax

    from keystone_tpu.config import config
    from keystone_tpu.utils.metrics import (
        CompileEventCounter,
        environment_fingerprint,
        metrics_registry,
    )
    from keystone_tpu.workflow.serving import (
        CompiledPipeline,
        PipelineService,
        _jit_cache_size,
    )

    # The baseline phase must measure TRUE per-shape jit: an inherited
    # KEYSTONE_SERVE_BUCKETS would silently route batch_call through
    # bucketing and collapse the comparison to bucketed-vs-bucketed.
    # The env var must go too, not just the config snapshot: the ladder
    # resolution reads it LIVE (env-pins-win), so an exported value
    # would pin every engine's ladder — hard-failing the --precision
    # mode's planner_ran gate and turning its "planned ladder" column
    # into the operator's env ladder (the KEYSTONE_PROFILE_STORE bench
    # isolation precedent). Same for an ambient serving precision: the
    # A/B names its precision per engine explicitly, and the knob-off
    # phase must really be the default f32 path.
    os.environ.pop("KEYSTONE_SERVE_BUCKETS", None)
    os.environ.pop("KEYSTONE_SERVE_PRECISION", None)
    config.serve_buckets = ()
    config.serve_precision = "f32"
    # Same class: an ambient KEYSTONE_PLAN_RESOURCES=0 (the documented
    # programmatic-pin workaround) snapshots config.plan_resources False
    # at import and would hard-fail the --precision planner_ran gate.
    config.plan_resources = True

    if args.precision:
        result = run_precision_bench(args)
        result["backend"] = backend
        result["host_cores"] = os.cpu_count()
        result["env"] = environment_fingerprint()
        line = json.dumps(result)
        print(line)
        if args.out:
            write_result(args.out, line, result["metric"])
        sys.exit(0 if result["ok"] else 1)

    if args.daemon:
        result = run_daemon_bench(args)
        result["backend"] = backend
        result["host_cores"] = os.cpu_count()
        result["env"] = environment_fingerprint()
        line = json.dumps(result)
        print(line)
        if args.out:
            write_result(args.out, line, result["metric"])
        sys.exit(0 if result["ok"] else 1)

    if args.telemetry:
        result = run_telemetry_bench(args)
        result["backend"] = backend
        result["host_cores"] = os.cpu_count()
        result["env"] = environment_fingerprint()
        line = json.dumps(result)
        print(line)
        if args.out:
            write_result(args.out, line, result["metric"])
        sys.exit(0 if result["ok"] else 1)

    if args.devices > 0:
        result = run_replica_bench(args)
        result["backend"] = backend
        line = json.dumps(result)
        print(line)
        if args.out:
            # The scaling row lives next to the main serving anchor;
            # reruns replace only their own metric's row.
            write_result(args.out, line, result["metric"])
        return

    if args.overload:
        cp = CompiledPipeline(
            build_chain(args.d, args.features, args.classes, args.seed),
            max_batch=args.max_batch,
        )
        cp.warmup((args.d,))
        overload = run_overload(cp, args)
        result = {
            "metric": "serve_overload",
            "backend": backend,
            "host_cores": os.cpu_count(),
            "env": environment_fingerprint(),
            "d": args.d,
            "features": args.features,
            "classes": args.classes,
            "ladder": list(cp.ladder),
            "overload": overload,
        }
        line = json.dumps(result)
        print(line)
        if args.out:
            write_result(args.out, line, result["metric"])
        return

    compile_events = CompileEventCounter()
    rng = np.random.default_rng(args.seed)
    sizes = rng.integers(1, args.max_batch + 1, size=args.requests)
    trace = [
        rng.normal(size=(int(n), args.d)).astype(np.float32) for n in sizes
    ]

    # -- naive: per-shape jit ---------------------------------------------
    naive = build_chain(args.d, args.features, args.classes, args.seed)
    # One warm call at the top size — the naive server has seen SOME
    # traffic; every new row count in the trace still recompiles.
    jax.block_until_ready(naive.batch_call(trace[0][: args.max_batch]))
    ev0 = compile_events.count
    naive_lats = []
    t0 = time.perf_counter()
    for x in trace:
        t1 = time.perf_counter()
        jax.block_until_ready(naive.batch_call(x))
        naive_lats.append(time.perf_counter() - t1)
    naive_wall = time.perf_counter() - t0
    naive_compiles = compile_events.count - ev0

    # -- bucketed + AOT warmup --------------------------------------------
    # One registry reset covers the serving counters AND the
    # request-latency histogram the bucketed phase is about to fill.
    metrics_registry.reset()
    cp = CompiledPipeline(
        build_chain(args.d, args.features, args.classes, args.seed),
        max_batch=args.max_batch,
    )
    ev0 = compile_events.count
    cp.warmup((args.d,))
    warmup_compiles = compile_events.count - ev0
    ev0 = compile_events.count
    bucketed_lats = []
    t0 = time.perf_counter()
    for x in trace:
        t1 = time.perf_counter()
        cp(x)  # host-out: the np result is already synchronized
        bucketed_lats.append(time.perf_counter() - t1)
    bucketed_wall = time.perf_counter() - t0
    post_warmup_compiles = compile_events.count - ev0

    rows = int(sizes.sum())
    naive_p99 = float(np.percentile(np.asarray(naive_lats) * 1e3, 99))
    bucketed_p99 = float(np.percentile(np.asarray(bucketed_lats) * 1e3, 99))
    # The unified registry is THE counter source — one snapshot feeds the
    # serving counters and the internal latency histogram (which must
    # agree with this bench's own external timing within 10%).
    registry_snap = metrics_registry.snapshot()
    counters = registry_snap["serving"]
    reg_lat = registry_snap["serve.request_latency"]

    result = {
        "metric": "serve_bucketed_vs_pershape",
        "backend": backend,
        "host_cores": os.cpu_count(),
        "env": environment_fingerprint(),
        "requests": args.requests,
        "rows": rows,
        "d": args.d,
        "features": args.features,
        "classes": args.classes,
        "ladder": list(cp.ladder),
        "naive": {
            **lat_stats(naive_lats),
            "rows_per_s": round(rows / naive_wall, 1),
            "compiles": naive_compiles,
            "jit_cache_entries": _jit_cache_size(naive._jitted()),
        },
        "bucketed": {
            **lat_stats(bucketed_lats),
            "rows_per_s": round(rows / bucketed_wall, 1),
            "warmup_seconds": round(cp.warmup_seconds, 3),
            "warmup_compiles": warmup_compiles,
            "post_warmup_compiles": post_warmup_compiles,
            "serving_counter_compiles_post_warmup": (
                counters["compiles"] - len(cp.ladder)
            ),
            "compiles_by_bucket": counters["compiles_by_bucket"],
            "pad_overhead": round(counters["pad_overhead"], 4),
            "bucket_hits": counters["bucket_hits"],
        },
        "registry_latency": {
            # MetricsRegistry's internal histogram vs this bench's own
            # external stopwatch over the same requests: the acceptance
            # contract is agreement within 10%, nearest-rank on both sides
            # (see nearest_rank_ms).
            **reg_lat,
            "p50_vs_external": round(
                reg_lat["p50_ms"] / nearest_rank_ms(bucketed_lats, 50), 3
            ),
            "p99_vs_external": round(
                reg_lat["p99_ms"] / nearest_rank_ms(bucketed_lats, 99), 3
            ),
        },
        "speedup": {
            "p50": round(
                float(np.percentile(np.asarray(naive_lats) * 1e3, 50))
                / float(np.percentile(np.asarray(bucketed_lats) * 1e3, 50)),
                2,
            ),
            "p99": round(naive_p99 / bucketed_p99, 2),
            "throughput": round(naive_wall / bucketed_wall, 2),
        },
        "pass": {
            "zero_post_warmup_compiles": post_warmup_compiles == 0,
            "p99_speedup_ge_2x": naive_p99 / bucketed_p99 >= 2.0,
            "registry_p99_within_10pct": (
                abs(reg_lat["p99_ms"] / nearest_rank_ms(bucketed_lats, 99)
                    - 1.0) <= 0.10
            ),
        },
    }

    # -- micro-batcher: concurrent single-row clients -------------------------
    if args.service_clients > 0:
        per_client = max(1, args.service_requests // args.service_clients)
        lats, lock = [], threading.Lock()

        def client(cid: int):
            crng = np.random.default_rng(1000 + cid)
            mine = []
            for _ in range(per_client):
                x = crng.normal(size=(args.d,)).astype(np.float32)
                t1 = time.perf_counter()
                svc.submit(x).result()
                mine.append(time.perf_counter() - t1)
            with lock:
                lats.extend(mine)

        with PipelineService(cp, max_delay_ms=2.0) as svc:
            threads = [
                threading.Thread(target=client, args=(c,))
                for c in range(args.service_clients)
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            svc_wall = time.perf_counter() - t0
            stats = svc.stats()
        result["service"] = {
            **lat_stats(lats),
            "clients": args.service_clients,
            "requests": stats["requests"],
            "device_batches": stats["batches_run"],
            "coalesce_ratio": round(stats["coalesce_ratio"], 2),
            "rows_per_s": round(stats["rows_served"] / svc_wall, 1),
            # The service's own registry-backed e2e histogram, next to the
            # client-side stopwatch numbers above.
            "internal_latency": stats["latency"],
        }

    line = json.dumps(result)
    print(line)
    if args.out:
        write_result(args.out, line, result["metric"])


if __name__ == "__main__":
    main()
