"""Capacity-loop A/B benchmark: learned latency model off vs on.

Drives the SAME shifting-mix flood twice through the real socket +
HTTP ingress of a fresh ``ServingDaemon`` per phase — once with the
learned capacity model disabled (``KEYSTONE_CAPACITY_MODEL=0``, the
PR-19 baseline) and once enabled — and hard-gates the closed loop the
model is supposed to close:

1. **goodput_improved** — model-on goodput beats model-off. Goodput
   counts DEADLINE-MET 200s only (a late 200 is a served SLA
   violation — the exact waste class the model exists to prevent, so
   crediting it would rig the baseline). The mechanism: under
   sustained best-effort overload with a deadline that is infeasible
   at full queue depth, the baseline admits everything — most of it
   expires in the queue (504) and over half of what IS dispatched
   completes after its deadline (wasted device work) — while the
   model fast-fails the knowably-infeasible excess (429
   ``predicted_infeasible`` before any device work: effective-bucket
   pricing at the observed rows-per-flush drain rate, flush cost at
   the model's ``ADMIT_Q`` quantile) so the queue stabilises at a
   depth the admitted requests can actually survive. Clients back off
   exponentially on consecutive non-200s (identical policy in both
   phases — the realistic retry loop is what turns a fast-fail 429
   into freed capacity instead of a hammering retry storm).
2. **gold_p99_ok** — the gold tier's closed-loop p99 with the model
   on stays equal-or-better (a small tolerance covers timer noise):
   shedding doomed best-effort work must not cost the protected tier.
3. **zero_knowing_violations** — no request is both predicted
   infeasible and admitted: every ``predicted_infeasible`` journey in
   the on-phase telemetry must have been refused BEFORE admission
   (no ``admitted``/``dispatched`` phase stamp).
4. **microbatches_formed** — the deadline-aware cross-tenant
   micro-batcher coalesced at least one best-effort request into a
   gold group's padding slack during the flood. A small dedicated
   pool of LOOSE-deadline riders (per-request ``deadline_ms`` wide
   enough to survive the combined batch's p99) supplies eligible
   passengers — the tight flood class is never coalescible, which is
   itself the deadline-awareness under test.
5. **model_reacted** — the traffic mix shifts halfway through the
   flood (best-effort rows 1 -> 2) and the re-plan loop must notice:
   at least one executed or suppressed re-plan decision.

The best-effort deadline is **self-calibrating**: a throwaway daemon
measures the shallow-queue p50 (feasible floor) and the full-depth
p50 (infeasible ceiling) through the same wire, and the deadline is
set to their geometric midpoint — infeasible at depth, comfortably
feasible shallow — so the A/B contrast survives host-speed variance.

The ``serve_capacity`` row appends to BENCH_serve.json (one latest
row per metric) and is judged by ``make bench-watch`` like every
other serving row: goodput/per_s leaves down or p99/_ms leaves up
across rounds is a regression; the ``pass`` gate flags flipping
true -> false is a regression.

Usage: JAX_PLATFORMS=cpu python tools/bench_capacity.py \
           [--flood-seconds 4.0] [--out BENCH_serve.json]
Prints one JSON line; exit 0 iff every gate passed.
"""

from __future__ import annotations

import argparse
import glob as _glob
import json
import math
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


GOLD_ROWS = 3       #: gold request rows: pads to bucket 4 with slack 1
BE_ROWS_A = 1       #: best-effort rows, first half of the flood
BE_ROWS_B = 2       #: best-effort rows, second half (the mix shift)
MAX_ROWS = 4        #: per-flush device rows — the capacity limiter
MAX_BATCH = 8       #: top bucket (pow-2 ladder (1, 2, 4, 8), unpinned)


def _closed_loop(port, sd, payload, stop_t, on_response, backoff_s=0.0):
    """One closed-loop client against the framed socket: send, classify
    (the callback gets the attempt's own deadline so a LATE 200 can be
    told apart from a deadline-met one), back off EXPONENTIALLY on
    consecutive non-200s (the realistic retry policy — identical in
    both phases; it is what turns a fast-fail 429 into freed capacity
    instead of a hammering retry storm), repeat until the stop time."""
    sc = sd.SocketClient(port)
    delay = backoff_s
    try:
        while time.perf_counter() < stop_t:
            doc = payload() if callable(payload) else payload
            t1 = time.perf_counter()
            try:
                resp = sc.request(doc)
            except (ConnectionError, OSError):
                on_response(None, None, time.perf_counter() - t1, doc)
                sc.close()
                sc = sd.SocketClient(port)
                continue
            status = resp.get("status")
            on_response(status, resp.get("error"),
                        time.perf_counter() - t1, doc)
            if status == 200:
                delay = backoff_s
            elif backoff_s:
                time.sleep(delay)
                delay = min(delay * 2.0, 64.0 * backoff_s)
    finally:
        sc.close()


def _scan_violations(tel_dir: str) -> dict:
    """Parse the phase's telemetry segments and count
    ``predicted_infeasible`` journeys that ever reached admission or a
    device — the knowingly-admitted-SLA-violation gate (must be 0)."""
    refused = 0
    violations = 0
    for path in sorted(_glob.glob(
            os.path.join(tel_dir, "keystone_telemetry_*.jsonl"))):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn tail of a live segment
                if rec.get("kind") != "journey":
                    continue
                j = rec.get("journey") or {}
                if j.get("outcome") != "predicted_infeasible":
                    continue
                refused += 1
                phases = {p.get("phase") for p in j.get("phases") or []}
                if phases & {"admitted", "dispatched", "delivered"}:
                    violations += 1
    return {"journeys_refused": refused, "violations": violations}


def run_capacity_bench(args) -> dict:
    import tempfile

    import serve_daemon as sd  # tools/ is on sys.path when run as a script

    from bench_serve import build_chain, lat_stats
    from keystone_tpu.utils.metrics import capacity_counters
    from keystone_tpu.utils.telemetry import reset_telemetry
    from keystone_tpu.workflow.daemon import ServingDaemon, Tenant
    from keystone_tpu.workflow.serialization import save_artifact

    d = args.d
    out_dir = tempfile.mkdtemp(prefix="keystone_capacity_bench_")
    chain = build_chain(d, args.features, args.classes, args.seed)
    pipe = chain.to_pipeline().fit()
    art = os.path.join(out_dir, "model.kart")
    save_artifact(pipe, art, feature_shape=(d,), dtype="float32")

    gold_x = np.zeros((GOLD_ROWS, d), dtype=np.float32).tolist()
    be_x = {
        BE_ROWS_A: np.zeros((BE_ROWS_A, d), dtype=np.float32).tolist(),
        BE_ROWS_B: np.zeros((BE_ROWS_B, d), dtype=np.float32).tolist(),
    }
    tenants = {
        "cap-gold": Tenant("gold", "cap-gold", qps=0, tier="gold"),
        "cap-be": Tenant("flood", "cap-be", qps=0, tier="best_effort"),
    }

    def make_daemon(tag, gold_deadline_ms, be_deadline_ms):
        return ServingDaemon(
            artifact=art, tenants=dict(tenants), devices=1,
            max_batch=MAX_BATCH, max_rows=MAX_ROWS, max_delay_ms=0.5,
            max_pending=args.max_pending, pending_budget=args.max_pending,
            gold_deadline_ms=gold_deadline_ms,
            be_deadline_ms=be_deadline_ms,
            name=f"capacity-bench-{tag}",
        )

    lock = threading.Lock()

    from keystone_tpu.config import config

    prior_env = {
        k: os.environ.get(k)
        for k in ("KEYSTONE_TELEMETRY_DIR", "KEYSTONE_CAPACITY_MODEL")
    }
    # The knobs are config snapshots (env read at import): mutate the
    # config object directly, the documented programmatic override.
    prior_cfg = (config.capacity_min_samples, config.capacity_replan_s)
    config.capacity_min_samples = args.min_samples
    config.capacity_replan_s = args.replan_s

    # ---- self-calibration: shallow vs full-depth best-effort p50
    # through the wire, model off, no deadline pressure. The geometric
    # midpoint becomes the flood's best-effort deadline: infeasible at
    # the flood's queue depth, comfortably feasible shallow.
    os.environ.pop("KEYSTONE_TELEMETRY_DIR", None)
    os.environ["KEYSTONE_CAPACITY_MODEL"] = "0"
    reset_telemetry()
    cal = make_daemon("cal", 60000.0, 60000.0)

    def measure(n_clients, seconds):
        lats: list = []

        def on_resp(status, _err, dt, _doc):
            if status == 200:
                with lock:
                    lats.append(dt)

        stop_t = time.perf_counter() + seconds
        ts = [
            threading.Thread(
                target=_closed_loop,
                args=(cal.socket_port, sd,
                      {"x": be_x[BE_ROWS_A], "key": "cap-be"},
                      stop_t, on_resp),
            )
            for _ in range(n_clients)
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return lats

    try:
        shallow = measure(2, args.calibrate_seconds)
        loaded = measure(args.be_clients, args.calibrate_seconds)
    finally:
        cal.close()
    if not shallow or not loaded:
        raise RuntimeError("calibration served no traffic")
    base_p50 = lat_stats(shallow)["p50_ms"]
    loaded_p50 = lat_stats(loaded)["p50_ms"]
    # Weighted geometric mean, biased toward the shallow floor: the
    # flood class must be infeasible at any meaningful depth (so the
    # A/B contrast doesn't depend on which queue-depth equilibrium the
    # learned drain rate settles into) yet comfortably feasible at an
    # empty queue (so refusing it all would trip the accuracy guard).
    be_deadline_ms = max(4.0, base_p50 ** 0.7
                         * max(loaded_p50, base_p50) ** 0.3)
    # The loose rider class clears the full-depth wait with headroom.
    loose_deadline_ms = max(35.0, 4.0 * loaded_p50)
    gold_deadline_ms = max(1000.0, 50.0 * loaded_p50)

    # ---- one flood phase: identical traffic, model off vs on --------------
    def run_phase(tag: str, model_on: bool) -> dict:
        tel_dir = os.path.join(out_dir, f"tel_{tag}")
        os.makedirs(tel_dir, exist_ok=True)
        os.environ["KEYSTONE_TELEMETRY_DIR"] = tel_dir
        os.environ["KEYSTONE_CAPACITY_MODEL"] = "1" if model_on else "0"
        reset_telemetry()
        cap0 = capacity_counters.snapshot()
        daemon = make_daemon(tag, gold_deadline_ms, be_deadline_ms)
        outcomes = {"ok_gold": 0, "ok_be": 0, "late_200": 0,
                    "predicted_refused": 0, "rejected": 0, "expired": 0,
                    "closed": 0, "error": 0, "conn": 0}
        gold_lats: list = []
        try:
            def warm_resp(*_a):
                return None

            # Warmup: shallow mixed traffic — compiles every bucket and
            # (model-on) feeds the model past KEYSTONE_CAPACITY_MIN_SAMPLES
            # before the measured window. Identical in both phases.
            warm_t = time.perf_counter() + args.warmup_seconds
            warm = [
                threading.Thread(
                    target=_closed_loop,
                    args=(daemon.socket_port, sd,
                          {"x": gold_x, "key": "cap-gold"},
                          warm_t, warm_resp),
                ),
            ] + [
                threading.Thread(
                    target=_closed_loop,
                    args=(daemon.socket_port, sd,
                          {"x": be_x[BE_ROWS_A], "key": "cap-be"},
                          warm_t, warm_resp),
                )
                for _ in range(2)
            ]
            for t in warm:
                t.start()
            for t in warm:
                t.join()

            # Flood: gold closed-loop probes + best-effort overload;
            # best-effort rows shift 1 -> 2 at the halfway mark (the
            # traffic-mix shift the re-plan loop must notice).
            t_start = time.perf_counter()
            t_half = t_start + args.flood_seconds / 2.0
            stop_t = t_start + args.flood_seconds

            def gold_resp(status, err, dt, doc):
                if status == 200:
                    with lock:
                        if dt * 1e3 <= gold_deadline_ms:
                            outcomes["ok_gold"] += 1
                        else:
                            outcomes["late_200"] += 1
                        gold_lats.append(dt)
                    return
                be_resp(status, err, dt, doc)  # same failure taxonomy

            def be_resp(status, err, dt, doc):
                with lock:
                    if status == 200:
                        # Goodput counts DEADLINE-MET responses only: a
                        # late 200 (dispatched before expiry, delivered
                        # after the deadline) is a served SLA violation,
                        # not goodput.
                        ddl = doc.get("deadline_ms") or be_deadline_ms
                        if dt * 1e3 <= ddl:
                            outcomes["ok_be"] += 1
                        else:
                            outcomes["late_200"] += 1
                    elif status == 429 and err == "predicted_infeasible":
                        outcomes["predicted_refused"] += 1
                    elif status == 429:
                        outcomes["rejected"] += 1
                    elif status == 504:
                        outcomes["expired"] += 1
                    elif status == 503:
                        outcomes["closed"] += 1
                    elif status is None:
                        outcomes["conn"] += 1
                    else:
                        outcomes["error"] += 1

            def be_payload():
                rows = (BE_ROWS_A if time.perf_counter() < t_half
                        else BE_ROWS_B)
                return {"x": be_x[rows], "key": "cap-be"}

            # A small DEDICATED pool of loose-deadline 1-row riders:
            # admissible under load (their deadline survives a full
            # queue) and the micro-batcher's eligible cargo. Closed-loop,
            # so at most --rider-clients of them ever occupy the queue —
            # they must not become queue mass the gold tier waits behind.
            rider_payload = {"x": be_x[BE_ROWS_A], "key": "cap-be",
                             "deadline_ms": loose_deadline_ms}

            floods = [
                threading.Thread(
                    target=_closed_loop,
                    args=(daemon.socket_port, sd, be_payload, stop_t,
                          be_resp),
                    kwargs={"backoff_s": args.backoff_ms / 1e3},
                )
                for _ in range(args.be_clients)
            ] + [
                threading.Thread(
                    target=_closed_loop,
                    args=(daemon.socket_port, sd, rider_payload, stop_t,
                          be_resp),
                    kwargs={"backoff_s": args.backoff_ms / 1e3},
                )
                for _ in range(args.rider_clients)
            ] + [
                threading.Thread(
                    target=_closed_loop,
                    args=(daemon.socket_port, sd,
                          {"x": gold_x, "key": "cap-gold"},
                          stop_t, gold_resp),
                    kwargs={"backoff_s": args.backoff_ms / 1e3},
                )
                for _ in range(args.gold_clients)
            ]
            for t in floods:
                t.start()
            for t in floods:
                t.join()
            wall = time.perf_counter() - t_start
            stats = daemon.stats()
        finally:
            daemon.close()

        cap1 = capacity_counters.snapshot()
        delta = {
            k: cap1.get(k, 0) - cap0.get(k, 0)
            for k in set(cap0) | set(cap1)
        }
        goodput = (outcomes["ok_gold"] + outcomes["ok_be"]) / max(wall, 1e-9)
        phase = {
            "model_on": model_on,
            "goodput_per_s": round(goodput, 1),
            "served": outcomes["ok_gold"] + outcomes["ok_be"],
            "outcomes": outcomes,
            "gold": lat_stats(gold_lats) if gold_lats else None,
            "capacity_counters": {k: v for k, v in delta.items() if v},
            "capacity_stats": stats["capacity"],
            "wall_s": round(wall, 3),
        }
        if model_on:
            phase["telemetry_scan"] = _scan_violations(tel_dir)
        return phase

    try:
        off = run_phase("off", model_on=False)
        on = run_phase("on", model_on=True)
    finally:
        for k, v in prior_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        config.capacity_min_samples, config.capacity_replan_s = prior_cfg
        reset_telemetry()

    scan = on["telemetry_scan"]
    counters = on["capacity_counters"]
    gold_off = (off["gold"] or {}).get("p99_ms")
    gold_on = (on["gold"] or {}).get("p99_ms")
    replans = counters.get("replans", 0) + counters.get(
        "replans_suppressed", 0)
    result = {
        "metric": "serve_capacity",
        "unit": "req/s",
        "be_clients": args.be_clients,
        "gold_clients": args.gold_clients,
        "flood_seconds": args.flood_seconds,
        "calibration": {
            "shallow_p50_ms": round(base_p50, 3),
            "loaded_p50_ms": round(loaded_p50, 3),
            "be_deadline_ms": round(be_deadline_ms, 3),
            "loose_deadline_ms": round(loose_deadline_ms, 1),
            "gold_deadline_ms": round(gold_deadline_ms, 1),
        },
        "off": off,
        "on": on,
        "goodput_off_per_s": off["goodput_per_s"],
        "goodput_on_per_s": on["goodput_per_s"],
        "gold_p99_off_ms": gold_off,
        "gold_p99_on_ms": gold_on,
        "predicted_refusals": counters.get("predicted_refusals", 0),
        "microbatches_formed": counters.get("microbatches_formed", 0),
        "microbatch_rows_filled": counters.get("microbatch_rows_filled", 0),
        "replans": counters.get("replans", 0),
        "replans_suppressed": counters.get("replans_suppressed", 0),
        "guard_checked": on["capacity_stats"].get("guard_checked", 0),
        "guard_violations": counters.get("guard_violations", 0),
        "knowing_violations": scan["violations"],
        "late_200_off": off["outcomes"]["late_200"],
        "late_200_on": on["outcomes"]["late_200"],
        "pass": {
            "goodput_improved": (
                on["goodput_per_s"] > off["goodput_per_s"]
            ),
            "gold_p99_ok": bool(
                gold_off is not None and gold_on is not None
                and gold_on <= gold_off * args.gold_p99_tolerance
            ),
            "zero_knowing_violations": scan["violations"] == 0,
            "refusals_engaged": counters.get("predicted_refusals", 0) > 0,
            "refusals_on_wire": (
                on["outcomes"]["predicted_refused"] > 0
            ),
            "microbatches_formed": (
                counters.get("microbatches_formed", 0) > 0
            ),
            "model_reacted": replans > 0,
            "off_phase_untouched": (
                off["capacity_stats"] == {"enabled": False}
                and not off["capacity_counters"].get("predicted_refusals")
                and not off["capacity_counters"].get("microbatches_formed")
            ),
            "zero_unresolved": (
                off["outcomes"]["conn"] + on["outcomes"]["conn"] == 0
                and off["outcomes"]["error"] + on["outcomes"]["error"] == 0
            ),
        },
    }
    result["ok"] = all(result["pass"].values())
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--d", type=int, default=128, help="input feature dim")
    ap.add_argument("--features", type=int, default=2048,
                    help="random-feature width of the serving head")
    ap.add_argument("--classes", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--be-clients", type=int, default=24,
                    help="closed-loop best-effort flood clients — the "
                    "overload depth the deadline is calibrated against")
    ap.add_argument("--gold-clients", type=int, default=2)
    ap.add_argument("--rider-clients", type=int, default=3,
                    help="dedicated loose-deadline 1-row best-effort "
                    "clients — the micro-batcher's eligible cargo, "
                    "closed-loop so they never become deep queue mass")
    ap.add_argument("--calibrate-seconds", type=float, default=1.0)
    ap.add_argument("--warmup-seconds", type=float, default=1.2,
                    help="shallow mixed traffic before each measured "
                    "flood: compiles every bucket and warms the model "
                    "past --min-samples")
    ap.add_argument("--flood-seconds", type=float, default=8.0)
    ap.add_argument("--backoff-ms", type=float, default=8.0,
                    help="client retry backoff after any non-200 — "
                    "identical in both phases")
    ap.add_argument("--max-pending", type=int, default=256,
                    help="service queue + admission budget, sized so "
                    "queue-full/budget 429s never mask the A/B: the "
                    "only refuser under flood is the model")
    ap.add_argument("--min-samples", type=int, default=48,
                    help="KEYSTONE_CAPACITY_MIN_SAMPLES for the phases "
                    "(warmup feeds well past this)")
    ap.add_argument("--replan-s", type=float, default=0.25,
                    help="KEYSTONE_CAPACITY_REPLAN_S for the phases")
    ap.add_argument("--gold-p99-tolerance", type=float, default=1.15,
                    help="model-on gold p99 must stay within this "
                    "factor of model-off (equal-or-better + timer "
                    "noise)")
    ap.add_argument("--out", type=str, default=None,
                    help="append/replace the serve_capacity row in this "
                    "BENCH_serve.json")
    args = ap.parse_args()

    from keystone_tpu.utils.platform import device_info

    backend = device_info()["platform"]

    from bench_serve import write_result
    from keystone_tpu.config import config
    from keystone_tpu.utils.metrics import environment_fingerprint

    # Bench isolation (the bench_serve precedent): an ambient ladder /
    # precision / plan pin would change what the phases measure.
    os.environ.pop("KEYSTONE_SERVE_BUCKETS", None)
    os.environ.pop("KEYSTONE_SERVE_PRECISION", None)
    config.serve_buckets = ()
    config.serve_precision = "f32"
    config.plan_resources = True

    result = run_capacity_bench(args)
    result["backend"] = backend
    result["host_cores"] = os.cpu_count()
    result["env"] = environment_fingerprint()
    line = json.dumps(result)
    print(line)
    if args.out:
        write_result(args.out, line, result["metric"])
    sys.exit(0 if result["ok"] else 1)


if __name__ == "__main__":
    main()
