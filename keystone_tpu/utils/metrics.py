"""Observability: tracing, latency histograms, the unified metrics
registry, and XLA cost introspection.

Ref: the reference's `Logging` trait with per-stage wall times in pipeline
mains + Spark metrics (SURVEY.md §5 metrics row) [unverified]. KeystoneML
attributed per-stage wall time to every pipeline node to drive its
optimizer; the analog here is three layers:

- ``Tracer`` — nested spans (name, start, duration, thread, attrs, and
  ``id`` / ``parent_id`` / ``root_id``) in a bounded ring buffer, exported
  as Chrome-trace JSON; ``span()`` also mirrors each span as a
  ``jax.profiler.TraceAnnotation`` named ``ks:<name>``, so a profiler
  session holds the program's spans on the device trace's own clock.
  Armed by ``KEYSTONE_TRACE`` or by a live profiler session and resolved
  ONCE per stream/solve/service via ``active_tracer()`` (the
  ``active_plan()`` discipline), so the disabled tracer costs a None
  check, never a per-record context manager.
- ``LatencyHistogram`` / ``Gauge`` — HdrHistogram-style fixed log buckets
  (p50/p95/p99 within one bucket's ~4% quantization) and point-in-time
  gauges with a high-water mark, both thread-safe.
- ``MetricsRegistry`` — every process-wide metric component (serving
  counters, reliability counters, histograms, gauges) under one
  ``snapshot()``/``reset()``; bench tools and the serving health surface
  read this instead of keeping private copies.

Plus the pre-existing FLOP/byte counts straight from the compiled HLO
(`cost_analysis`), which is what per-chip TFLOPS reporting uses.
"""

from __future__ import annotations

import contextvars
import itertools
import json
import logging
import math
import os
import re
import threading
import time
from collections import OrderedDict, deque
from contextlib import contextmanager, nullcontext
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

logger = logging.getLogger("keystone_tpu")


def compiled_cost(compiled) -> Dict[str, Any]:
    """FLOPs / bytes-accessed of an already-compiled executable."""
    cost = compiled.cost_analysis() or {}
    # Older jax returns a one-element list of dicts (per-executable);
    # newer returns the dict directly.
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "raw": dict(cost),
    }


def cost_analysis(fn: Callable, *args) -> Dict[str, Any]:
    """FLOPs / bytes-accessed of `fn` as XLA compiles it for these args."""
    return compiled_cost(jax.jit(fn).lower(*args).compile())


# ---------------------------------------------------------------------------
# Span tracing
# ---------------------------------------------------------------------------


class Tracer:
    """Process-wide span recorder: a bounded ring buffer of (name, cat,
    start, duration, thread, attrs) spans, exported as Chrome-trace JSON.

    Spans nest two ways: timestamps on one thread track contain each other
    (which is all Perfetto needs to draw the flame), and every record
    carries ``id``, ``parent_id`` (the span open around it on its thread,
    None for a root) and ``root_id`` (its root's ``id``: what ``req_id`` is
    to a request, all spans of one fit share it), so two spans of one name
    are told apart. ``span()`` also keeps the parent's *name* in
    ``args["parent"]``, and mirrors itself as a
    ``jax.profiler.TraceAnnotation("ks:" + name)`` with its scalar attrs:
    while a profiler session is live the span lies on the host plane of
    the same ``.xplane.pb`` as the device's lines. Never open one inside a
    function that ``jit`` traces: it would time the trace, once.
    ``record()`` takes externally-captured endpoints — the shape the hot
    paths use (one ``now()`` before, one ``record()`` after, no generator
    frame in the timed region) and the shape cross-thread spans need
    (queue residency starts on the producer, ends on the consumer).

    Thread-safe; the ring (``deque(maxlen=...)``) keeps the most recent
    ``capacity`` spans so a long traced run holds bounded memory.

    Request-scoped spans carry a ``req_id`` (single-request spans:
    ``serve.queued``, ``serve.request``) or ``req_ids`` (group spans:
    ``serve.flush``, ``serve.device``) attr — the serving layer mints one
    monotonic id per request and threads it across the dispatcher,
    replica, and completion threads, so ``spans_for_request()`` can
    reconstruct one request's full cross-thread journey from the ring.
    ``retain_request()`` is the tail-sampling hook: it copies a slow
    request's span tree into a small bounded store that survives ring
    churn (the ring keeps the most recent spans of ALL traffic; the
    retained store keeps the interesting outliers).
    """

    #: How many tail-sampled requests keep their full span trees.
    RETAIN_CAPACITY = 64

    #: The prefix of a span's mirror in a profiler trace.
    ANNOTATION_PREFIX = "ks:"

    def __init__(self, capacity: int = 65536):
        self.capacity = int(capacity)
        self.epoch_ns = time.perf_counter_ns()
        # time.time() endpoints (jax.monitoring's time spans) onto the
        # ring's clock: one offset, taken here.
        self.wall_offset_ns = time.time_ns() - self.epoch_ns
        self._lock = threading.Lock()
        self._spans: deque = deque(maxlen=self.capacity)
        self._retained: "OrderedDict[int, List[dict]]" = OrderedDict()
        self._tls = threading.local()
        self._ids = itertools.count(1)
        self.dropped = 0  # spans evicted by the ring bound

    @staticmethod
    def now() -> int:
        """Monotonic timestamp (ns) on the tracer's clock."""
        return time.perf_counter_ns()

    def _append(self, name, cat, start_ns, end_ns, sid, parent_id, root_id,
                attrs) -> None:
        t = threading.current_thread()
        with self._lock:
            if len(self._spans) == self.capacity:
                self.dropped += 1
            self._spans.append(
                {
                    "name": name,
                    "cat": cat,
                    "start_ns": start_ns,
                    "dur_ns": max(0, end_ns - start_ns),
                    "tid": t.ident,
                    "thread": t.name,
                    "id": sid,
                    "parent_id": parent_id,
                    "root_id": root_id,
                    "args": attrs,
                }
            )

    def record(
        self,
        name: str,
        cat: str,
        start_ns: int,
        end_ns: Optional[int] = None,
        **attrs,
    ) -> None:
        """Record one completed span from explicit endpoints (``end_ns``
        None = now). ``attrs`` must be JSON-representable. Its parent is
        the innermost ``span()`` open on this thread that began no later
        than it did. Ring only: nothing is mirrored to the profiler."""
        if end_ns is None:
            end_ns = time.perf_counter_ns()
        sid = next(self._ids)
        parent_id, root_id = None, sid
        for _name, pid, rid, began in reversed(getattr(self._tls, "stack", ())):
            if began <= start_ns:
                parent_id, root_id = pid, rid
                break
        self._append(name, cat, start_ns, end_ns, sid, parent_id, root_id, attrs)

    def instant(self, name: str, cat: str = "app", **attrs) -> None:
        """A zero-duration marker (cache hits, rejections)."""
        now = time.perf_counter_ns()
        self.record(name, cat, now, now, **attrs)

    @contextmanager
    def span(self, name: str, cat: str = "app", **attrs):
        """Context-managed span; yields the attrs dict so the body can add
        keys it only knows afterwards (e.g. an output shape: such keys
        reach the ring, not the profiler's mirror). Tracks the per-thread
        span stack and stamps the parent."""
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        mirror = jax.profiler.TraceAnnotation(
            self.ANNOTATION_PREFIX + name,
            **{k: v for k, v in attrs.items() if isinstance(v, (int, float, str))},
        )
        sid = next(self._ids)
        parent_id, root_id = None, sid
        if stack:
            parent_name, parent_id, root_id, _began = stack[-1]
            attrs.setdefault("parent", parent_name)
        mirror.__enter__()
        t0 = time.perf_counter_ns()
        stack.append((name, sid, root_id, t0))
        try:
            yield attrs
        finally:
            end = time.perf_counter_ns()
            stack.pop()
            mirror.__exit__(None, None, None)
            self._append(name, cat, t0, end, sid, parent_id, root_id, attrs)

    def spans(self) -> List[dict]:
        """Snapshot of the ring's current spans (oldest first)."""
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._retained.clear()
            self.dropped = 0

    @staticmethod
    def _mentions(span: dict, rid: int) -> bool:
        """Does this span belong to request ``rid`` (``req_id`` attr, or
        membership in a group span's ``req_ids`` list)?"""
        args = span["args"]
        return args.get("req_id") == rid or rid in args.get("req_ids", ())

    def spans_for_request(self, rid: int) -> List[dict]:
        """Every span in the ring OR the retained store that mentions
        request ``rid`` — the cross-thread journey of one request."""
        with self._lock:
            found = [s for s in self._spans if self._mentions(s, rid)]
            kept = self._retained.get(rid)
        if kept:
            seen = {(s["name"], s["start_ns"]) for s in found}
            found.extend(
                s for s in kept if (s["name"], s["start_ns"]) not in seen
            )
        found.sort(key=lambda s: s["start_ns"])
        return found

    #: Slack (ns) on the ``since_ns`` early-exit of ``retain_request``:
    #: the ring is ordered by record() call, which can trail a span's end
    #: timestamp by scheduler jitter across threads.
    RETAIN_SCAN_SLACK_NS = 5_000_000

    def retain_request(self, rid: int,
                       since_ns: Optional[int] = None) -> int:
        """Tail-sampling: copy request ``rid``'s spans from the ring into
        the bounded retained store (oldest retained request evicted past
        ``RETAIN_CAPACITY``), so a slow request's full span tree survives
        ring churn. Returns how many spans were retained.

        ``since_ns`` (the request's submit timestamp) bounds the scan: a
        span that ENDED before the request existed cannot mention it, and
        the ring is ordered by record time, so the newest-first walk
        stops at the first span ending more than a slack margin before
        ``since_ns`` — an expiry storm with tracing armed then scans one
        request's lifetime of spans, not the whole 65536-entry ring,
        while this may run under the serving lock. Without ``since_ns``
        the full ring is scanned (O(ring))."""
        cutoff = (
            since_ns - self.RETAIN_SCAN_SLACK_NS
            if since_ns is not None else None
        )
        with self._lock:
            matched = []
            for s in reversed(self._spans):
                if (
                    cutoff is not None
                    and s["start_ns"] + s["dur_ns"] < cutoff
                ):
                    break
                if self._mentions(s, rid):
                    matched.append(dict(s))
            matched.reverse()
            if not matched:
                return 0
            self._retained[rid] = matched
            self._retained.move_to_end(rid)
            while len(self._retained) > self.RETAIN_CAPACITY:
                self._retained.popitem(last=False)
            return len(matched)

    def retained(self) -> Dict[int, List[dict]]:
        """Snapshot of the tail-sampled store: req id -> its span tree."""
        with self._lock:
            return {rid: list(spans) for rid, spans in self._retained.items()}

    def _as_event(self, s: dict, pid: int) -> dict:
        """One ring span as a Chrome-trace X event (µs timestamps)."""
        return {
            "name": s["name"],
            "cat": s["cat"],
            "ph": "X",
            "ts": (s["start_ns"] - self.epoch_ns) / 1e3,
            "dur": s["dur_ns"] / 1e3,
            "pid": pid,
            "tid": s["tid"],
            "args": s["args"],
        }

    def export(self, path: Optional[str] = None) -> dict:
        """The ring as a Chrome-trace document (``{"traceEvents": [...]}``,
        timestamps/durations in microseconds) — loadable by Perfetto /
        chrome://tracing.
        Tail-sampled span trees ride along under a ``tailSampled`` key
        (req id -> events) so ``tools/trace_report.py --request`` can
        reconstruct a slow request even after the ring churned past it;
        Chrome-trace consumers ignore unknown top-level keys. With
        ``path``, also written as JSON to that file."""
        pid = os.getpid()
        events = []
        threads: Dict[int, str] = {}
        for s in self.spans():
            threads.setdefault(s["tid"], s["thread"])
            events.append(self._as_event(s, pid))
        for tid, tname in threads.items():
            events.append(
                {
                    "name": "thread_name",
                    "ph": "M",
                    "pid": pid,
                    "tid": tid,
                    "args": {"name": tname},
                }
            )
        doc = {"traceEvents": events, "displayTimeUnit": "ms"}
        tail = self.retained()
        if tail:
            doc["tailSampled"] = {
                str(rid): [self._as_event(s, pid) for s in spans]
                for rid, spans in tail.items()
            }
        if path is not None:
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, path)
            logger.info("chrome trace (%d events) written to %s",
                        len(events), path)
        return doc


#: The ``jax.named_scope`` names inside the device programs, written once:
#: the sites enter them through ``device_scope`` and
#: ``utils/device_trace.py`` puts a profiler trace's device seconds down to
#: them. A scope acts at trace time only (it extends the operations'
#: ``op_name``; the compile cache's key leaves metadata out), so it is
#: always on. Constant names: no shape, id or count. A device scope whose
#: work a host span dispatches takes that span's name.
DEVICE_SCOPES = (
    "solver.stack", "solver.gram", "solver.cholesky", "solver.inverse",
    "solver.update", "solver.generate", "solver.fetch",
    "krr.generate", "krr.fetch", "krr.reduce", "krr.factor", "krr.solve",
    "conv.patches", "conv.kernel", "conv.relayout",
    "filters.rows", "filters.cols",
    "gmm.estep", "gmm.mstep",
    # What crosses the mesh: ``row_matrix.sharded_rowsum``'s exchanges by
    # what is summed (grams; AᵀR and the like; column sums for the means;
    # the mixture's statistics of an EM sweep), and the descriptor sample's
    # gather across the shards.
    "coll.gram", "coll.atr", "coll.moments", "coll.em", "coll.sample",
)

#: A fused chain's step is scoped by this and its stage's class name, as the
#: walk's host spans are (``node:<label>``); a step in which a stage took the
#: ones behind it joins the names with ``+``.
STAGE_SCOPE_PREFIX = "node:"


def device_scope(name: str):
    """``jax.named_scope(name)`` for one of ``DEVICE_SCOPES``."""
    assert name in DEVICE_SCOPES, name
    return jax.named_scope(name)


def stage_scope(stage, taken=()):
    """The scope of one step of a fused chain: ``node:<Stage>``, or
    ``node:<Stage>+<Taken>+...`` where the stage runs the ones behind it."""
    return jax.named_scope(STAGE_SCOPE_PREFIX + "+".join(
        s._program_name() for s in (stage, *taken)))


_tracer_lock = threading.Lock()
_tracer: Optional[Tracer] = None
_tracer_key: Optional[int] = None
_compile_spans: Optional["CompileSpanListener"] = None  # one per process


def active_tracer() -> Optional[Tracer]:
    """The process-wide Tracer, or None when tracing is disabled.

    Armed by ``config.trace`` (env ``KEYSTONE_TRACE``) or by a live
    ``jax.profiler`` session: whoever profiles the device gets the
    program's spans in the same trace, with no second switch. Sized by
    ``config.trace_buffer`` and rebuilt when that changes. Call sites grab
    the tracer ONCE per stream/solve/service/execution — never per record
    — so the disabled tracer (None) adds nothing to hot loops (the
    ``active_plan()`` discipline). The ring outlives a profiler session:
    ``recorded_tracer()`` reads it afterwards."""
    global _tracer, _tracer_key, _compile_spans
    from keystone_tpu.config import config

    if not config.trace and not jax.profiler.TraceAnnotation.is_enabled():
        return None
    key = config.trace_buffer
    with _tracer_lock:
        if key != _tracer_key or _tracer is None:
            _tracer = Tracer(config.trace_buffer)
            _tracer_key = key
        if _compile_spans is None:
            _compile_spans = CompileSpanListener()
        return _tracer


def recorded_tracer() -> Optional[Tracer]:
    """The tracer ``active_tracer()`` last built, armed or not: how a
    reader gets at what was recorded under a profiler session that has
    since stopped. Never arms tracing."""
    with _tracer_lock:
        return _tracer


def span_of(tracer: Optional[Tracer], name: str, cat: str = "app", **attrs):
    """``tracer.span(...)``, or a null context (yielding None) where the
    tracer is None: one ``with`` at a cold site, whatever the switch."""
    if tracer is None:
        return nullcontext()
    return tracer.span(name, cat, **attrs)


def upload_nbytes(value) -> int:
    """The bytes a ``jnp.asarray(value)`` sends to the device: the size of
    a host array, 0 for what is already a ``jax.Array``."""
    if isinstance(value, jax.Array):
        return 0
    return int(getattr(value, "nbytes", 0))


def reset_tracer() -> None:
    """Drop the cached tracer (a fresh empty ring on next resolve)."""
    global _tracer, _tracer_key
    with _tracer_lock:
        _tracer = None
        _tracer_key = None


class _TracerLoss:
    """Registry adapter exporting the tracer's loss/occupancy numbers
    on /metrics (``keystone_tracer_*`` gauges): a ring that silently
    evicts spans is telemetry lying by omission, so ``Tracer.dropped``
    must be a scrape-able number, not a private attribute. Reads the
    CACHED tracer only — scraping /metrics never arms tracing."""

    def snapshot(self) -> Dict[str, int]:
        with _tracer_lock:
            t = _tracer
        if t is None:
            return {"enabled": 0, "dropped": 0, "spans_held": 0,
                    "retained_requests": 0}
        with t._lock:
            return {
                "enabled": 1,
                "dropped": t.dropped,
                "spans_held": len(t._spans),
                "retained_requests": len(t._retained),
            }

    def reset(self) -> None:
        pass  # stateless view; the tracer itself owns reset


def validate_chrome_trace(doc: Any) -> List[str]:
    """Schema check of a Chrome-trace document; returns the list of
    problems (empty = valid). Shared by ``tools/trace_report.py`` and the
    tier-1 trace-demo test so the exporter and its validator can't
    drift."""
    errors: List[str] = []
    if not isinstance(doc, dict) or not isinstance(
        doc.get("traceEvents"), list
    ):
        return ["document must be an object with a 'traceEvents' list"]
    for i, ev in enumerate(doc["traceEvents"]):
        where = f"traceEvents[{i}]"
        if not isinstance(ev, dict):
            errors.append(f"{where}: not an object")
            continue
        ph = ev.get("ph")
        if ph not in ("X", "M", "i", "B", "E"):
            errors.append(f"{where}: bad phase {ph!r}")
            continue
        if not isinstance(ev.get("name"), str) or "pid" not in ev:
            errors.append(f"{where}: missing name/pid")
        if ph == "X":
            ts, dur = ev.get("ts"), ev.get("dur")
            if not isinstance(ts, (int, float)) or not isinstance(
                dur, (int, float)
            ):
                errors.append(f"{where}: X event needs numeric ts/dur")
            elif dur < 0:
                errors.append(f"{where}: negative duration")
            if "args" in ev and not isinstance(ev["args"], dict):
                errors.append(f"{where}: args must be an object")
    return errors


# ---------------------------------------------------------------------------
# Latency histograms and gauges
# ---------------------------------------------------------------------------


class LatencyHistogram:
    """Fixed log-bucket latency histogram, HdrHistogram-style.

    Buckets grow geometrically by ``2**(1/sub)`` from ``min_s`` to
    ``max_s`` (defaults: 1 µs → 1000 s at sub=16 ≈ 480 buckets, ~4.4%
    quantization per bucket — well inside the 10% agreement budget the
    serving acceptance check demands). ``record()`` is one ``log2`` + a
    locked bucket increment; min/max/sum are tracked exactly, so mean and
    the extreme percentiles don't pay the quantization. Thread-safe:
    client threads and the serving worker record concurrently."""

    def __init__(self, min_s: float = 1e-6, max_s: float = 1e3, sub: int = 16):
        assert min_s > 0 and max_s > min_s and sub >= 1
        self._lo = float(min_s)
        self._sub = int(sub)
        self._nbuckets = int(math.ceil(math.log2(max_s / min_s) * sub)) + 2
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self._counts = [0] * self._nbuckets
            self._n = 0
            self._sum = 0.0
            self._min = math.inf
            self._max = 0.0
            self._nonpositive = 0

    def _index(self, seconds: float) -> int:
        if seconds <= self._lo:
            return 0
        i = int(math.log2(seconds / self._lo) * self._sub) + 1
        return min(i, self._nbuckets - 1)

    def _value(self, index: int) -> float:
        """Representative (geometric-midpoint) value of a bucket."""
        if index <= 0:
            return self._lo
        return self._lo * 2.0 ** ((index - 0.5) / self._sub)

    def record(self, seconds: float) -> None:
        # Non-positive samples (clock skew, double-resolution races) are
        # clamped to the minimum bucket AND counted separately: log-bucket
        # math must never see them, and the snapshot's
        # ``dropped_nonpositive`` names how often the clock misbehaved
        # instead of silently polluting the distribution's low tail.
        nonpos = seconds <= 0.0
        if nonpos:
            seconds = self._lo
        i = self._index(seconds)
        with self._lock:
            self._counts[i] += 1
            self._n += 1
            self._sum += seconds
            if nonpos:
                self._nonpositive += 1
            if seconds < self._min:
                self._min = seconds
            if seconds > self._max:
                self._max = seconds

    @property
    def count(self) -> int:
        with self._lock:
            return self._n

    def _percentile_locked(self, p: float) -> float:
        """Nearest-rank percentile (caller holds the lock, _n > 0)."""
        target = max(1, math.ceil(self._n * p / 100.0))
        acc = 0
        for i, c in enumerate(self._counts):
            acc += c
            if acc >= target:
                return min(max(self._value(i), self._min), self._max)
        return self._max  # unreachable

    def percentile(self, p: float) -> Optional[float]:
        """The p-th percentile in seconds (nearest-rank over buckets), or
        None when empty. Clamped to the exactly-tracked min/max so p0/p100
        don't carry bucket quantization."""
        with self._lock:
            if self._n == 0:
                return None
            return self._percentile_locked(p)

    def snapshot(self) -> Dict[str, Any]:
        # ONE lock acquisition for counts AND percentiles: a concurrent
        # reset() between them would hand a poller percentile()=None.
        with self._lock:
            if self._n == 0:
                return {"count": 0}
            to_ms = lambda s: round(s * 1e3, 4)  # noqa: E731
            snap = {
                "count": self._n,
                "mean_ms": to_ms(self._sum / self._n),
                "min_ms": to_ms(self._min),
                "p50_ms": to_ms(self._percentile_locked(50)),
                "p95_ms": to_ms(self._percentile_locked(95)),
                "p99_ms": to_ms(self._percentile_locked(99)),
                "max_ms": to_ms(self._max),
            }
            if self._nonpositive:
                snap["dropped_nonpositive"] = self._nonpositive
            return snap

    def buckets(self) -> Dict[str, Any]:
        """The raw distribution for exposition formats: occupied buckets
        as ``(upper_bound_seconds, cumulative_count)`` pairs (sparse —
        empty buckets are omitted; cumulative counts stay valid), plus
        the exact count/sum. This is what ``MetricsRegistry.prometheus()``
        renders as ``_bucket{le=...}`` lines."""
        with self._lock:
            pairs: List[Tuple[float, int]] = []
            cum = 0
            for i, c in enumerate(self._counts):
                cum += c
                if c:
                    le = self._lo * 2.0 ** (i / self._sub) if i else self._lo
                    pairs.append((le, cum))
            return {
                "buckets": pairs,
                "count": self._n,
                "sum": self._sum,
                "dropped_nonpositive": self._nonpositive,
            }


class Gauge:
    """A point-in-time value with a high-water mark (queue depth,
    in-flight requests). Thread-safe."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self._value = 0.0
            self._max = 0.0

    def set(self, value: float) -> None:
        with self._lock:
            self._value = value
            if value > self._max:
                self._max = value

    @property
    def value(self) -> float:
        with self._lock:
            return self._value

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return {"value": self._value, "max": self._max}


class CounterSet:
    """Thread-safe string-keyed monotonic counters — the registry's
    generic tally component (request outcomes, dispatch balance,
    reliability events). Keys are created on first ``bump``; ``snapshot``
    returns whatever was bumped, sorted."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}

    def bump(self, key: str, n: int = 1) -> None:
        with self._lock:
            self._counts[key] = self._counts.get(key, 0) + n

    def get(self, key: str) -> int:
        with self._lock:
            return self._counts.get(key, 0)

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(sorted(self._counts.items()))


class MetricsRegistry:
    """THE process-wide metrics surface: every counter set, histogram, and
    gauge registers here, and one ``snapshot()``/``reset()`` covers them
    all — bench tools and ``PipelineService.stats()`` read this instead of
    keeping private copies that drift. Components need only
    ``snapshot()``/``reset()`` methods."""

    def __init__(self):
        self._lock = threading.Lock()
        self._parts: Dict[str, Any] = {}

    def register(self, name: str, part: Any) -> Any:
        with self._lock:
            existing = self._parts.get(name)
            if existing is not None and existing is not part:
                raise ValueError(f"metric {name!r} already registered")
            self._parts[name] = part
        return part

    def _get_or_create(self, name: str, factory: Callable[[], Any]) -> Any:
        with self._lock:
            part = self._parts.get(name)
            if part is None:
                part = self._parts[name] = factory()
            return part

    def histogram(self, name: str, **kwargs) -> LatencyHistogram:
        """Get-or-create a named latency histogram."""
        part = self._get_or_create(name, lambda: LatencyHistogram(**kwargs))
        if not isinstance(part, LatencyHistogram):
            raise TypeError(f"metric {name!r} is a {type(part).__name__}")
        return part

    def gauge(self, name: str) -> Gauge:
        """Get-or-create a named gauge."""
        part = self._get_or_create(name, Gauge)
        if not isinstance(part, Gauge):
            raise TypeError(f"metric {name!r} is a {type(part).__name__}")
        return part

    def counters(self, name: str) -> CounterSet:
        """Get-or-create a named counter set (outcome tallies, dispatch
        balance). Per-instance serving metrics use ``base[instance]``
        names — ``serve.requests[svc0]`` — so two services in one process
        never overwrite each other's readings."""
        part = self._get_or_create(name, CounterSet)
        if not isinstance(part, CounterSet):
            raise TypeError(f"metric {name!r} is a {type(part).__name__}")
        return part

    def part(self, name: str, factory: Callable[[], Any]) -> Any:
        """Get-or-create an arbitrary ``snapshot()``/``reset()`` part —
        for adapter views (the daemon's SLO gauges) that need the same
        get-or-create semantics histograms and counter sets enjoy: two
        daemons reusing one name share the family instead of raising."""
        return self._get_or_create(name, factory)

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._parts)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            parts = dict(self._parts)
        return {name: part.snapshot() for name, part in sorted(parts.items())}

    def reset(self) -> None:
        with self._lock:
            parts = list(self._parts.values())
        for part in parts:
            part.reset()

    def prometheus(self) -> str:
        """The whole registry as Prometheus text exposition (format 0.0.4)
        — what ``tools/metrics_server.py`` serves at ``/metrics``.

        Naming: every family is prefixed ``keystone_``, dots become
        underscores, and the PR-5 per-instance namespacing
        (``serve.queue_depth[svc0]``) becomes an ``instance`` label
        instead of a distinct family, so one scrape config covers every
        engine/service in the process. Per component type:

        - ``LatencyHistogram`` -> a ``<name>_seconds`` histogram family
          (sparse ``_bucket{le=...}`` lines over the occupied log buckets,
          exact ``_sum``/``_count``), a ``<name>_quantile_seconds`` gauge
          family (p50/p95/p99, the same nearest-rank numbers
          ``snapshot()`` reports), and a ``_dropped_nonpositive_total``
          counter;
        - ``Gauge`` -> ``<name>`` and ``<name>_max`` gauges;
        - ``CounterSet`` -> ``<name>_total`` counters, keys as a ``key``
          label;
        - anything else (e.g. the serving compile counters) -> its
          ``snapshot()`` dict flattened to gauges, one level of nested
          dict becoming a ``key`` label.

        The output always parses under ``validate_prometheus_text`` and
        agrees with ``snapshot()`` — both are pinned by tier-1.
        """
        with self._lock:
            parts = dict(self._parts)
        fams: "OrderedDict[str, dict]" = OrderedDict()

        def fam(name: str, typ: str) -> List[tuple]:
            entry = fams.setdefault(name, {"type": typ, "samples": []})
            return entry["samples"]

        for name, part in sorted(parts.items()):
            base, instance = _split_instance(name)
            mname = _prom_name(base)
            labels = {"instance": instance} if instance else {}
            if isinstance(part, LatencyHistogram):
                dist = part.buckets()
                hname = f"{mname}_seconds"
                samples = fam(hname, "histogram")
                for le, cum in dist["buckets"]:
                    samples.append((
                        f"{hname}_bucket",
                        {**labels, "le": _format_value(le)},
                        cum,
                    ))
                samples.append((
                    f"{hname}_bucket", {**labels, "le": "+Inf"},
                    dist["count"],
                ))
                samples.append((f"{hname}_sum", labels, dist["sum"]))
                samples.append((f"{hname}_count", labels, dist["count"]))
                qsamples = fam(f"{mname}_quantile_seconds", "gauge")
                snap = part.snapshot()
                for q, key in ((0.5, "p50_ms"), (0.95, "p95_ms"),
                               (0.99, "p99_ms")):
                    if key in snap:
                        qsamples.append((
                            f"{mname}_quantile_seconds",
                            {**labels, "quantile": str(q)},
                            snap[key] / 1e3,
                        ))
                dname = f"{mname}_dropped_nonpositive_total"
                fam(dname, "counter").append(
                    (dname, labels, dist["dropped_nonpositive"])
                )
            elif isinstance(part, Gauge):
                snap = part.snapshot()
                fam(mname, "gauge").append((mname, labels, snap["value"]))
                fam(f"{mname}_max", "gauge").append(
                    (f"{mname}_max", labels, snap["max"])
                )
            elif isinstance(part, CounterSet):
                cname = f"{mname}_total"
                samples = fam(cname, "counter")
                for key, count in part.snapshot().items():
                    samples.append((cname, {**labels, "key": key}, count))
            else:
                for key, val in part.snapshot().items():
                    sub = f"{mname}_{_PROM_BAD.sub('_', str(key))}"
                    if isinstance(val, bool) or val is None:
                        continue
                    if isinstance(val, (int, float)):
                        fam(sub, "gauge").append((sub, labels, val))
                    elif isinstance(val, dict):
                        samples = fam(sub, "gauge")
                        for k2, v2 in val.items():
                            if isinstance(v2, (int, float)) and not isinstance(
                                v2, bool
                            ):
                                samples.append(
                                    (sub, {**labels, "key": str(k2)}, v2)
                                )
        lines: List[str] = []
        for fname, entry in fams.items():
            if not entry["samples"]:
                continue
            lines.append(f"# TYPE {fname} {entry['type']}")
            for sname, labels, value in entry["samples"]:
                lines.append(
                    f"{sname}{_prom_labels(labels)} {_format_value(value)}"
                )
        return "\n".join(lines) + "\n"


metrics_registry = MetricsRegistry()


# ---------------------------------------------------------------------------
# Prometheus text exposition helpers (stdlib only — the export surface)
# ---------------------------------------------------------------------------

_PROM_BAD = re.compile(r"[^a-zA-Z0-9_:]")
_INSTANCE_RE = re.compile(r"^(?P<base>.+?)\[(?P<instance>[^\]]+)\]$")
_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>.*)\})?"
    r" (?P<value>\S+)$"
)
_LABEL_RE = re.compile(
    r'(?P<key>[a-zA-Z_][a-zA-Z0-9_]*)="(?P<value>(?:[^"\\]|\\.)*)"'
)
_PROM_TYPES = ("counter", "gauge", "histogram", "summary", "untyped")


def _split_instance(name: str) -> Tuple[str, Optional[str]]:
    """Split the registry's ``base[instance]`` namespacing into a family
    base and an instance label value."""
    m = _INSTANCE_RE.match(name)
    if m:
        return m.group("base"), m.group("instance")
    return name, None


def _prom_name(base: str) -> str:
    name = _PROM_BAD.sub("_", base)
    if name and name[0].isdigit():
        name = "_" + name
    return f"keystone_{name}"


def _prom_labels(labels: Dict[str, str]) -> str:
    if not labels:
        return ""
    inner = ",".join(
        '{}="{}"'.format(
            k,
            str(v).replace("\\", r"\\").replace('"', r"\"").replace(
                "\n", r"\n"
            ),
        )
        for k, v in sorted(labels.items())
    )
    return "{" + inner + "}"


def _format_value(v) -> str:
    """A float/int as Prometheus spells it (no trailing .0 on ints, repr
    precision on floats so the scrape agrees with ``snapshot()``)."""
    if isinstance(v, bool):
        return "1" if v else "0"
    if isinstance(v, int):
        return str(v)
    f = float(v)
    if f == math.inf:
        return "+Inf"
    if f == -math.inf:
        return "-Inf"
    return repr(f)


_LABEL_ESCAPES = {"n": "\n", "\\": "\\", '"': '"'}


def _unescape_label(value: str) -> str:
    """Decode label-value escapes in ONE pass: sequential str.replace
    would let the tail of an escaped backslash re-match as the head of
    another escape (``dir\\\\name`` -> ``dir\\<newline>ame``)."""
    return re.sub(
        r"\\(.)", lambda m: _LABEL_ESCAPES.get(m.group(1), m.group(1)),
        value,
    )


def _parse_prom_value(raw: str) -> float:
    if raw == "+Inf":
        return math.inf
    if raw == "-Inf":
        return -math.inf
    return float(raw)  # raises ValueError on garbage; NaN parses


def parse_prometheus_text(text: str) -> List[Dict[str, Any]]:
    """Parse text exposition into sample dicts (``name``, ``labels``,
    ``value``). Raises ValueError naming the first malformed line —
    ``validate_prometheus_text`` is the error-list wrapper."""
    samples: List[Dict[str, Any]] = []
    for i, line in enumerate(text.splitlines(), 1):
        line = line.rstrip()
        if not line:
            continue
        if line.startswith("#"):
            parts = line.split(None, 3)
            if len(parts) >= 2 and parts[1] == "TYPE":
                if len(parts) != 4 or parts[3] not in _PROM_TYPES:
                    raise ValueError(f"line {i}: malformed TYPE: {line!r}")
            continue
        m = _SAMPLE_RE.match(line)
        if m is None:
            raise ValueError(f"line {i}: malformed sample: {line!r}")
        raw_labels = m.group("labels") or ""
        labels: Dict[str, str] = {}
        if raw_labels:
            for lm in _LABEL_RE.finditer(raw_labels):
                labels[lm.group("key")] = _unescape_label(lm.group("value"))
            leftovers = _LABEL_RE.sub("", raw_labels).strip(", \t")
            if leftovers:
                raise ValueError(
                    f"line {i}: malformed labels: {raw_labels!r}"
                )
        try:
            value = _parse_prom_value(m.group("value"))
        except ValueError:
            raise ValueError(
                f"line {i}: bad sample value {m.group('value')!r}"
            ) from None
        samples.append({"name": m.group("name"), "labels": labels,
                        "value": value})
    return samples


def validate_prometheus_text(text: str) -> List[str]:
    """Schema check of a Prometheus text exposition; returns the list of
    problems (empty = valid). Shared by ``tools/metrics_server.py``'s
    smoke mode and the tier-1 export tests so the renderer and its
    validator can't drift. Beyond line syntax, histogram families are
    checked for cumulative, ``+Inf``-terminated buckets that agree with
    ``_count``."""
    errors: List[str] = []
    try:
        samples = parse_prometheus_text(text)
    except ValueError as e:
        return [str(e)]
    types: Dict[str, str] = {}
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) == 4:
                if parts[2] in types:
                    errors.append(f"duplicate TYPE for {parts[2]}")
                types[parts[2]] = parts[3]
    by_name: Dict[str, List[dict]] = {}
    for s in samples:
        by_name.setdefault(s["name"], []).append(s)
    for fname, typ in types.items():
        if typ != "histogram":
            continue
        series: Dict[tuple, List[tuple]] = {}
        for s in by_name.get(f"{fname}_bucket", []):
            key = tuple(sorted(
                (k, v) for k, v in s["labels"].items() if k != "le"
            ))
            le = s["labels"].get("le")
            if le is None:
                errors.append(f"{fname}_bucket sample missing le label")
                continue
            try:
                le_val = _parse_prom_value(le)
            except ValueError:
                # A validator must report, never raise: that is its
                # whole contract against untrusted exposition text.
                errors.append(f"{fname}_bucket: non-numeric le {le!r}")
                continue
            series.setdefault(key, []).append((le_val, s["value"]))
        counts = {
            tuple(sorted(s["labels"].items())): s["value"]
            for s in by_name.get(f"{fname}_count", [])
        }
        for key, pairs in series.items():
            les = [p[0] for p in pairs]
            cums = [p[1] for p in pairs]
            if les != sorted(les):
                errors.append(f"{fname}{dict(key)}: le bounds not sorted")
            if any(b < a for a, b in zip(cums, cums[1:])):
                errors.append(
                    f"{fname}{dict(key)}: bucket counts not cumulative"
                )
            if not les or les[-1] != math.inf:
                errors.append(f"{fname}{dict(key)}: no le=\"+Inf\" bucket")
            elif counts and counts.get(key) != cums[-1]:
                errors.append(
                    f"{fname}{dict(key)}: _count disagrees with +Inf bucket"
                )
    return errors


def environment_fingerprint(devices: bool = True) -> Dict[str, Any]:
    """Provenance block for every bench JSON writer: the jax/runtime
    identity plus whichever ``KEYSTONE_*`` knobs were in effect, so
    cross-run comparisons (e.g. a p99 delta between rounds) are
    interpretable instead of mystery noise.

    ``devices=False`` skips the device probe — for a parent process
    (tools/bench_mfu.py) that must not initialize the backend: a chip
    belongs to one process at a time, and its children need it."""
    import platform as _platform

    def _redact(name: str, value: str) -> str:
        # Secrets must never ride the fingerprint into committed bench
        # JSON: KEYSTONE_SWAP_TOKEN is the control-plane credential
        # (fully masked), KEYSTONE_TENANTS carries tenant API KEYS
        # ('name:api_key:qps[:tier[:burst]]' — the key field is masked,
        # the name/qps/tier provenance survives).
        if name == "KEYSTONE_SWAP_TOKEN" and value:
            return "****"
        if name != "KEYSTONE_TENANTS" or not value.strip():
            return value
        masked = []
        for token in value.split(","):
            parts = token.split(":")
            if len(parts) >= 2:
                parts[1] = "****"
            masked.append(":".join(parts))
        return ",".join(masked)

    fp: Dict[str, Any] = {
        "jax": getattr(jax, "__version__", None),
        "python": _platform.python_version(),
        "cpu_count": os.cpu_count(),
        "keystone_env": {
            k: _redact(k, v) for k, v in sorted(os.environ.items())
            if k.startswith("KEYSTONE_")
        },
    }
    try:
        import numpy as _np

        fp["numpy"] = _np.__version__
    except ImportError:  # fingerprint stays useful without numpy
        pass
    if not devices:
        return fp
    try:
        devs = jax.local_devices()
        fp["backend"] = jax.default_backend()
        fp["device_kind"] = devs[0].device_kind if devs else None
        fp["device_count"] = jax.device_count()
    except Exception as e:  # lint: broad-ok deviceless/dead backend raises backend-specific types: record, don't die
        fp["backend_error"] = str(e)[:200]
    return fp


# Device memory probes, memoized per process: ``jax.local_devices()`` and
# an unsupported ``memory_stats()`` are host syncs, and once the profiler
# wires these onto the per-node hot path they must cost a dict read, not a
# runtime round-trip per node. ``False`` = probed and unavailable.
_memprobe_lock = threading.Lock()
_memprobe_device: Any = None
_hbm_limit_memo: Any = None  # None=unprobed, False=not reported, else int
_peak_supported: Optional[bool] = None


def reset_memory_probe() -> None:
    """Drop the memoized device/limit probes (tests, backend swaps)."""
    global _memprobe_device, _hbm_limit_memo, _peak_supported
    with _memprobe_lock:
        _memprobe_device = None
        _hbm_limit_memo = None
        _peak_supported = None


def _memory_stats_device():
    """Device 0 for ``memory_stats`` probes, resolved ONCE per process
    (None when the backend is dead or deviceless)."""
    global _memprobe_device
    dev = _memprobe_device
    if dev is None:
        with _memprobe_lock:
            if _memprobe_device is None:
                try:
                    devs = jax.local_devices()
                    _memprobe_device = devs[0] if devs else False
                except Exception:  # lint: broad-ok a dead/deviceless backend raises backend-specific types; all mean 'nothing to probe'
                    _memprobe_device = False
            dev = _memprobe_device
    return dev if dev is not False else None


def device_hbm_bytes(default: int | None = None) -> int:
    """Memory budget of device 0 as the runtime reports it (``bytes_limit``
    from ``memory_stats``). The CPU backend reports none and gets
    ``config.hbm_budget_bytes``; a TPU that reports none is an error —
    sizing a TPU program against a constant it never confirmed is how a
    block that does not fit gets chosen. The device probe AND the reported
    limit are memoized per process — the limit is static, and re-asking
    the runtime per call is a host sync. Always returns an int."""
    from keystone_tpu.config import config

    global _hbm_limit_memo
    limit = _hbm_limit_memo
    if limit is None:
        dev = _memory_stats_device()
        found: Any = False
        if dev is not None:
            try:
                stats = dev.memory_stats() or {}
                raw = stats.get("bytes_limit")
                if raw:
                    found = int(raw)
            except Exception:  # lint: broad-ok backend-specific probe failures all mean 'no reported limit'
                pass
            if found is False and dev.platform == "tpu":
                raise RuntimeError(
                    f"{dev} reports no bytes_limit in memory_stats(); "
                    "refusing to size TPU programs against the CPU default "
                    "config.hbm_budget_bytes"
                )
        with _memprobe_lock:
            _hbm_limit_memo = found
        limit = found
    if limit is not False:
        return int(limit)
    return int(default) if default is not None else config.hbm_budget_bytes


def peak_hbm_bytes() -> int | None:
    """HBM high-water of device 0 (``peak_bytes_in_use``), or None where
    the runtime doesn't report it (notably CPU). Shared by the
    single-number evidence rows (bench line, chip_smoke.py) and the
    profiler's per-node HBM deltas.

    The device handle and the does-this-runtime-report-a-peak verdict are
    memoized per process (the CPU backend answers None forever; asking it
    again per profiled node would put a host sync on the hot path). The
    peak VALUE itself is re-read on every call where supported."""
    global _peak_supported
    if _peak_supported is False:
        return None
    dev = _memory_stats_device()
    peak = None
    if dev is not None:
        try:
            stats = dev.memory_stats() or {}
            peak = stats.get("peak_bytes_in_use")
        except Exception:  # lint: broad-ok backend-specific probe failures all mean 'no reported peak'
            peak = None
    if peak is None:
        with _memprobe_lock:
            _peak_supported = False
        return None
    if _peak_supported is None:
        with _memprobe_lock:
            _peak_supported = True
    return int(peak)


_runtime_fp_lock = threading.Lock()
_runtime_fp: Optional[Dict[str, Any]] = None


def runtime_fingerprint() -> Dict[str, Any]:
    """The small memoized backend-identity subset of
    ``environment_fingerprint`` (jax version, backend, device kind/count)
    that profile snapshots and solver journey records carry, so
    ``tools/bench_watch.py`` can refuse to compare rows recorded under
    different backends or device counts. Memoized per process: the full
    fingerprint probes devices per call, which is a host sync once this
    rides every solve record."""
    global _runtime_fp
    fp = _runtime_fp
    if fp is None:
        fp = {
            "jax": getattr(jax, "__version__", None),
            "backend": None,
            "device_kind": None,
            "device_count": None,
        }
        try:
            fp["backend"] = jax.default_backend()
            fp["device_count"] = int(jax.device_count())
            devs = jax.local_devices()
            fp["device_kind"] = devs[0].device_kind if devs else None
        except Exception as e:  # lint: broad-ok deviceless/dead backend raises backend-specific types: record, don't die
            fp["backend_error"] = str(e)[:200]
        with _runtime_fp_lock:
            _runtime_fp = fp
    return dict(fp)


# ---------------------------------------------------------------------------
# Per-node resource attribution (the training-side profiler)
# ---------------------------------------------------------------------------

#: FIFO bound on the per-(transformer, shape, dtype) cost-model memo.
_NODE_COST_CAP = 256
_node_cost_lock = threading.Lock()
#: key -> (estimate dict | None, transformer pin). The pin keeps the
#: transformer alive while its id() keys the memo, so CPython id reuse
#: can never alias a stale entry (the _prefix_pins discipline).
_node_cost_memo: "OrderedDict[tuple, tuple]" = OrderedDict()


def _memory_analysis(compiled) -> Dict[str, float]:
    """Whatever ``memory_analysis`` the backend reports for a compiled
    executable, as plain floats (empty where unsupported)."""
    try:
        mem = compiled.memory_analysis()
    except Exception:  # lint: broad-ok memory_analysis is backend-optional; absence means 'no estimate'
        return {}
    out: Dict[str, float] = {}
    for attr, key in (
        ("temp_size_in_bytes", "temp_bytes"),
        ("argument_size_in_bytes", "argument_bytes"),
        ("output_size_in_bytes", "output_bytes"),
        ("generated_code_size_in_bytes", "code_bytes"),
        # Donated-and-aliased input bytes: a donated lowering's working
        # set is argument+output+temp MINUS alias (the aliased buffers
        # are the same memory counted twice) — the backend-portable
        # evidence that donation lowered the high-water, usable where
        # peak_bytes_in_use isn't reported (CPU fake-device runs).
        ("alias_size_in_bytes", "alias_bytes"),
    ):
        v = getattr(mem, attr, None)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[key] = float(v)
    return out


def node_cost_analysis(transformer, X) -> Optional[Dict[str, float]]:
    """Cost-model estimate (FLOPs, bytes accessed, memory analysis) of
    running ``transformer.apply_batch`` at ``X``'s shape — computed ONCE
    per (transformer, shape, dtype) via an abstract AOT lower+compile
    (``ShapeDtypeStruct``: no data touched, nothing executed) and
    memoized, so a profiled fit pays one extra compile per distinct
    executable, never one per node execution. Returns None where the
    transformer can't lower (host nodes, non-array inputs) — those rows
    stay measured-only."""
    shape = tuple(getattr(X, "shape", ()) or ())
    dtype = getattr(X, "dtype", None)
    if not shape or dtype is None or not getattr(transformer, "jittable", False):
        return None
    key = (id(transformer), shape, str(dtype))
    with _node_cost_lock:
        hit = _node_cost_memo.get(key)
    if hit is not None:
        est = hit[0]
        return dict(est) if est else None
    try:
        spec = jax.ShapeDtypeStruct(shape, dtype)
        # The transformer's own cached jit wrapper (built lazily by
        # batch_call) keeps this the SAME executable identity the traced
        # path runs where the runtime caches by avals.
        jitted = getattr(transformer, "_jitted", None)
        fn = jitted() if jitted is not None else jax.jit(transformer.apply_batch)
        compiled = fn.lower(spec).compile()
        cost = compiled_cost(compiled)
        est = {
            "flops": cost["flops"],
            "bytes_accessed": cost["bytes_accessed"],
        }
        est.update(_memory_analysis(compiled))
        # ``argument_bytes`` is the batch's: a shared program takes its
        # transformer's arrays as arguments (``Transformer.array_fields``)
        # and counts them too, and the planner that prices a row would
        # bill the weights to every row.
        if "argument_bytes" in est and getattr(transformer, "shares_program", bool)():
            own = sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(transformer))
            est["argument_bytes"] = max(est["argument_bytes"] - own, 0.0)
    except Exception:  # lint: broad-ok the cost model is best-effort; any lowering/compile failure means 'no estimate', never a failed fit
        est = None
    with _node_cost_lock:
        _node_cost_memo[key] = (est, transformer)
        while len(_node_cost_memo) > _NODE_COST_CAP:
            _node_cost_memo.popitem(last=False)
    return dict(est) if est else None


class ResourceProfile:
    """Per-node resource attribution for executor walks — the
    training-side answer to "what does each operator cost", the
    measurement substrate KeystoneML's cost-based optimization presumes.

    One process-wide instance aggregates rows keyed by node label:
    per-node call count, wall time (covering device completion — the
    profiled path blocks on array outputs), dispatch time, cost-model
    FLOPs / bytes accessed (from the memoized ``node_cost_analysis``
    AOT compile — estimates, not measurements), output nbytes, the HBM
    high-water delta where the runtime reports one, and cache-status
    tallies (hit / memo / miss). Registered in ``metrics_registry`` as
    ``"profile"`` so ``snapshot()`` and the Prometheus exposition carry
    the per-node families (``keystone_profile_node_*{key="<label>"}``).

    Thread-safe; populated only when ``active_profile()`` resolves
    non-None (KEYSTONE_PROFILE, or a ``profile_scope()`` forced by
    ``Pipeline.fit(profile=True)``)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._nodes: "OrderedDict[str, dict]" = OrderedDict()
        # Content-addressed measured aggregates: prefix digest ->
        # {label, calls, wall_ns, out_bytes, out_rows, queue_wait_ns}.
        # This is what the profile store persists and the optimizer rules
        # re-match to graph nodes — digests survive graph copies, fusion
        # (chain_digest folds stage-by-stage), and process restarts,
        # where labels collide and ids die.
        self._digests: "OrderedDict[str, dict]" = OrderedDict()

    def reset(self) -> None:
        with self._lock:
            self._nodes.clear()
            self._digests.clear()

    def record_node(
        self,
        label: str,
        wall_ns: int = 0,
        dispatch_ns: Optional[int] = None,
        flops: Optional[float] = None,
        bytes_accessed: Optional[float] = None,
        out_nbytes: Optional[int] = None,
        hbm_delta: Optional[int] = None,
        cache: str = "miss",
        queue_wait_ns: Optional[int] = None,
        worker: Optional[str] = None,
        digest: Optional[str] = None,
        out_rows: Optional[int] = None,
        out_shape: Optional[list] = None,
        data_shards: Optional[int] = None,
    ) -> None:
        """Fold one node execution into the label's aggregate row.

        Safe under concurrent callers: the parallel executor walk records
        from every pool thread, and each fold is one atomic
        read-modify-write under the profile lock — call counts and wall
        sums stay exact at any worker count. ``queue_wait_ns`` (ready →
        picked up by a worker) and ``worker`` (pool thread name) are the
        parallel walk's scheduling attribution; the serial walk passes
        neither."""
        with self._lock:
            agg = self._nodes.get(label)
            if agg is None:
                agg = self._nodes[label] = {
                    "calls": 0, "wall_ns": 0, "dispatch_ns": 0,
                    "flops": 0.0, "bytes_accessed": 0.0, "output_bytes": 0,
                    "hbm_delta_bytes": 0, "cost_modeled": 0,
                    "hbm_known": False, "queue_wait_ns": 0,
                    "workers": set(), "data_shards": None,
                    "cache": {"hit": 0, "memo": 0, "miss": 0},
                }
            agg["calls"] += 1
            agg["wall_ns"] += int(wall_ns)
            if dispatch_ns is not None:
                agg["dispatch_ns"] += int(dispatch_ns)
            if flops is not None:
                agg["flops"] += float(flops)
                agg["cost_modeled"] += 1
            if bytes_accessed is not None:
                agg["bytes_accessed"] += float(bytes_accessed)
            if out_nbytes is not None:
                agg["output_bytes"] += int(out_nbytes)
            if hbm_delta is not None:
                agg["hbm_delta_bytes"] += int(hbm_delta)
                agg["hbm_known"] = True
            if queue_wait_ns is not None:
                agg["queue_wait_ns"] += int(queue_wait_ns)
            if worker is not None:
                agg["workers"].add(str(worker))
            if data_shards is not None:
                # Last-write (like out_shape): how many data shards the
                # node's output spanned — the profile row's mesh-width
                # provenance, so a 1-shard row is visibly 1-shard.
                agg["data_shards"] = int(data_shards)
            agg["cache"][cache] = agg["cache"].get(cache, 0) + 1
            # Digest aggregation covers EXECUTED nodes only (cache
            # hits/memos carry no digest): the stored profile must
            # describe what computing the node costs, not what skipping
            # it cost.
            if digest is not None:
                dagg = self._digests.get(digest)
                if dagg is None:
                    dagg = self._digests[digest] = {
                        "label": label, "calls": 0, "wall_ns": 0,
                        "out_bytes": 0, "out_rows": 0, "queue_wait_ns": 0,
                        "out_shape": None, "data_shards": None,
                    }
                dagg["calls"] += 1
                dagg["wall_ns"] += int(wall_ns)
                if queue_wait_ns is not None:
                    dagg["queue_wait_ns"] += int(queue_wait_ns)
                if out_nbytes is not None:
                    dagg["out_bytes"] = int(out_nbytes)
                if out_rows is not None:
                    dagg["out_rows"] = int(out_rows)
                if out_shape is not None:
                    dagg["out_shape"] = list(out_shape)
                if data_shards is not None:
                    dagg["data_shards"] = int(data_shards)

    #: Numeric aggregate fields a ``mark()`` delta subtracts.
    _DELTA_FIELDS = ("calls", "wall_ns", "dispatch_ns", "flops",
                     "bytes_accessed", "output_bytes", "hbm_delta_bytes",
                     "cost_modeled", "queue_wait_ns")

    def mark(self) -> Dict[str, dict]:
        """Opaque snapshot of the per-label aggregates, for delta views:
        ``rows(since=mark)`` / ``table(since=mark)`` report only what was
        recorded AFTER the mark — how ``Pipeline.fit(profile=True)``
        logs one fit's attribution without resetting the process-wide
        profile other readers (Prometheus) are watching."""
        with self._lock:
            return {
                label: dict(agg, cache=dict(agg["cache"]),
                            workers=set(agg["workers"]))
                for label, agg in self._nodes.items()
            }

    def mark_digests(self) -> Dict[str, dict]:
        """``mark()`` for the digest-keyed aggregates: ``digest_rows``
        with this snapshot reports only executions recorded AFTER it —
        how one fit's measurements are carved out of the process-wide
        accumulation for the profile store."""
        with self._lock:
            return {d: dict(agg) for d, agg in self._digests.items()}

    #: Numeric digest-aggregate fields a ``mark_digests()`` delta
    #: subtracts (out_bytes / out_rows are last-write sizes, not sums).
    _DIGEST_DELTA_FIELDS = ("calls", "wall_ns", "queue_wait_ns")

    def digest_rows(
        self, since: Optional[Dict[str, dict]] = None
    ) -> Dict[str, Dict[str, Any]]:
        """The content-addressed measured aggregates ({prefix digest ->
        {label, calls, wall_ns, out_bytes, out_rows, queue_wait_ns}}) the
        profile store persists. ``since`` (a ``mark_digests()``) restricts
        to the delta; digests untouched after the mark are dropped."""
        with self._lock:
            items = {d: dict(agg) for d, agg in self._digests.items()}
        if since is None:
            return items
        out: Dict[str, Dict[str, Any]] = {}
        for d, agg in items.items():
            base = since.get(d)
            if base is not None:
                agg = dict(agg)
                for f in self._DIGEST_DELTA_FIELDS:
                    agg[f] = agg[f] - base[f]
            if agg["calls"] > 0:
                out[d] = agg
        return out

    def rows(
        self, since: Optional[Dict[str, dict]] = None
    ) -> List[Dict[str, Any]]:
        """Attribution rows (one per node label, heaviest wall first) in
        the shape ``render_attribution_table`` and
        ``tools/profile_report.py`` consume. FLOPs/bytes are cost-model
        ESTIMATES (provenance ``cost-model``); wall/dispatch/output are
        measured. ``since`` (a ``mark()``) restricts to the delta —
        labels untouched after the mark are dropped."""
        with self._lock:
            # workers is copied under the lock (like mark()): the live set
            # keeps mutating under concurrent record_node calls, and
            # sorting it outside the lock would iterate a changing set.
            items = [(label, dict(agg, workers=set(agg["workers"])),
                      dict(agg["cache"]))
                     for label, agg in self._nodes.items()]
        if since is not None:
            delta_items = []
            for label, agg, cache in items:
                base = since.get(label)
                if base is not None:
                    agg = dict(agg)
                    for f in self._DELTA_FIELDS:
                        agg[f] = agg[f] - base[f]
                    # workers is a set, not a counter: the delta view
                    # names only pool threads first seen AFTER the mark.
                    agg["workers"] = agg["workers"] - base.get(
                        "workers", set()
                    )
                    cache = {
                        k: v - base["cache"].get(k, 0)
                        for k, v in cache.items()
                    }
                if agg["calls"] > 0:
                    delta_items.append((label, agg, cache))
            items = delta_items
        rows = []
        for label, agg, cache in items:
            executed = cache.get("miss", 0)
            rows.append({
                "node": label,
                "calls": agg["calls"],
                "wall_ms": round(agg["wall_ns"] / 1e6, 4),
                "dispatch_ms": round(agg["dispatch_ns"] / 1e6, 4),
                "device_wait_ms": round(
                    max(0, agg["wall_ns"] - agg["dispatch_ns"]) / 1e6, 4
                ),
                "flops": agg["flops"] if agg["cost_modeled"] else None,
                "bytes_accessed": (
                    agg["bytes_accessed"] if agg["cost_modeled"] else None
                ),
                "output_bytes": agg["output_bytes"] or None,
                "hbm_delta_bytes": (
                    agg["hbm_delta_bytes"] if agg["hbm_known"] else None
                ),
                "cache_hits": cache.get("hit", 0) + cache.get("memo", 0),
                "executed": executed,
                # Parallel-walk scheduling attribution: time spent ready
                # but unclaimed, and which pool threads ran the label.
                # None/empty under the serial walk.
                "queue_wait_ms": (
                    round(agg["queue_wait_ns"] / 1e6, 4)
                    if agg["queue_wait_ns"] else None
                ),
                "workers": sorted(agg["workers"]) or None,
                # Mesh-width provenance: how many data shards the node's
                # output spanned (None where never observed/arrayless).
                "data_shards": agg.get("data_shards"),
                "provenance": (
                    "cost-model" if agg["cost_modeled"] else "measured"
                ),
            })
        rows.sort(key=lambda r: -r["wall_ms"])
        return rows

    def snapshot(self) -> Dict[str, Any]:
        """Registry-shape snapshot: per-label numeric families (flattened
        by the Prometheus exposition into ``key``-labelled gauges) plus
        the memoized runtime fingerprint for cross-run comparability."""
        with self._lock:
            items = [(label, dict(agg)) for label, agg in self._nodes.items()]
        snap: Dict[str, Any] = {
            "nodes": len(items),
            "node_calls": {}, "node_wall_seconds": {},
            "node_device_wait_seconds": {}, "node_flops": {},
            "node_bytes_accessed": {}, "node_output_bytes": {},
            "node_hbm_delta_bytes": {}, "node_queue_wait_seconds": {},
            "node_workers": {},
        }
        for label, agg in items:
            snap["node_calls"][label] = agg["calls"]
            snap["node_wall_seconds"][label] = agg["wall_ns"] / 1e9
            snap["node_device_wait_seconds"][label] = (
                max(0, agg["wall_ns"] - agg["dispatch_ns"]) / 1e9
            )
            if agg["queue_wait_ns"]:
                snap["node_queue_wait_seconds"][label] = (
                    agg["queue_wait_ns"] / 1e9
                )
            if agg["workers"]:
                snap["node_workers"][label] = len(agg["workers"])
            if agg["cost_modeled"]:
                snap["node_flops"][label] = agg["flops"]
                snap["node_bytes_accessed"][label] = agg["bytes_accessed"]
            if agg["output_bytes"]:
                snap["node_output_bytes"][label] = agg["output_bytes"]
            if agg["hbm_known"]:
                snap["node_hbm_delta_bytes"][label] = agg["hbm_delta_bytes"]
        snap["fingerprint"] = runtime_fingerprint()
        return snap

    def table(self, since: Optional[Dict[str, dict]] = None) -> str:
        """The attribution table, rendered (see
        ``render_attribution_table``); ``since`` as in ``rows``."""
        return render_attribution_table(self.rows(since=since))

    def export(self, path: str) -> dict:
        """Write rows + snapshot as JSON (atomic), for
        ``tools/profile_report.py`` to render offline."""
        doc = {
            "profile": self.snapshot(),
            "rows": self.rows(),
            "digests": self.digest_rows(),
        }
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1)
        os.replace(tmp, path)
        return doc


def render_attribution_table(rows: List[Dict[str, Any]]) -> str:
    """The trace_report-style attribution table over profile rows — ONE
    renderer shared by ``Pipeline.fit(profile=True)``'s log line,
    ``tools/profile_report.py``, and ``tools/trace_report.py --fit``, so
    a live profile and a Chrome trace of the same fit render identically.
    Missing columns (a trace has no cost model) print as ``-``."""

    def num(v, scale=1.0, fmt="{:.3f}"):
        if v is None:
            return "-"
        return fmt.format(v / scale)

    header = (
        f"{'node':<40} {'calls':>5} {'wall ms':>10} {'wait ms':>9} "
        f"{'MFLOP':>10} {'MB moved':>9} {'out MB':>8} {'hbm Δ MB':>9} "
        f"{'cache':>6}  src"
    )
    lines = [header, "-" * len(header)]
    for r in rows:
        lines.append(
            f"{r['node'][:40]:<40} {r['calls']:>5} "
            f"{num(r.get('wall_ms')):>10} {num(r.get('device_wait_ms')):>9} "
            f"{num(r.get('flops'), 1e6):>10} "
            f"{num(r.get('bytes_accessed'), 1e6):>9} "
            f"{num(r.get('output_bytes'), 1e6):>8} "
            f"{num(r.get('hbm_delta_bytes'), 1e6):>9} "
            f"{r.get('cache_hits', 0):>6}  {r.get('provenance', 'measured')}"
        )
    return "\n".join(lines)


resource_profile = ResourceProfile()
metrics_registry.register("profile", resource_profile)

#: profile_scope() nesting depth, CONTEXT-local (contextvar, not a
#: process global): one thread's fit(profile=True) must not flip every
#: concurrently executing walk in the process into forced-profiling mode
#: (double-executing their nodes for the warmed re-time and persisting
#: store entries for unrelated graphs). The parallel walk copies its
#: build-thread context into each pool task, so nested estimator
#: sub-fits inside a profiled walk stay inside the scope.
_profile_force: "contextvars.ContextVar[int]" = contextvars.ContextVar(
    "keystone_profile_force", default=0
)


@contextmanager
def profile_scope():
    """Force per-node profiling on for the dynamic extent of one fit /
    apply (``Pipeline.fit(profile=True)``) in THIS context, yielding the
    process-wide ``ResourceProfile``. Nests; restores on exit."""
    token = _profile_force.set(_profile_force.get() + 1)
    try:
        yield resource_profile
    finally:
        _profile_force.reset(token)


def profile_forced() -> bool:
    """True inside an explicit ``profile_scope()`` (fit(profile=True) or
    a user scope) — the opt-in the profile store's per-apply auto-save
    keys on, distinct from ambient KEYSTONE_PROFILE=1 observation."""
    return bool(_profile_force.get())


def active_profile() -> Optional[ResourceProfile]:
    """The process-wide ``ResourceProfile``, or None when profiling is
    disabled (``config.profile`` / KEYSTONE_PROFILE off and no
    ``profile_scope()`` active in this context). Resolve ONCE per
    executor walk — the ``active_plan()`` discipline — so the unprofiled
    walk pays one None check per node."""
    from keystone_tpu.config import config

    if config.profile or _profile_force.get():
        return resource_profile
    return None


def achieved_tflops(fn: Callable, *args, repeats: int = 3) -> Dict[str, float]:
    """Compile, time, and convert to achieved TFLOPS (per process).

    One lowered/compiled executable serves both the timing loop and the
    FLOP count — lowering the function a second time through
    ``cost_analysis`` would double compile cost for the same HLO.
    """
    compiled = jax.jit(fn).lower(*args).compile()
    out = compiled(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = compiled(*args)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / repeats
    flops = compiled_cost(compiled)["flops"]
    return {
        "seconds": dt,
        "flops": flops,
        "tflops": flops / dt / 1e12 if dt > 0 else 0.0,
    }


class CompileEventCounter:
    """Counts XLA backend compiles via ``jax.monitoring`` — each compile
    emits one compile-cache event. THE process's compile oracle, shared by
    the serving bench and the zero-post-warmup-compile tests so they can't
    drift apart if a jax upgrade renames the event. ``.hits`` counts the
    requests the persistent compilation cache answered from disk.
    Listener registration is global and permanent: create one per process
    and snapshot ``.count`` around phases."""

    EVENT = "/jax/compilation_cache/compile_requests_use_cache"
    HIT_EVENT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.count = 0
        self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name, **kwargs):
        if name == self.EVENT:
            self.count += 1
        elif name == self.HIT_EVENT:
            self.hits += 1


class CompileSpanListener:
    """Puts what ``jax.monitoring`` times of every program a process
    builds on the armed tracer's ring: ``jax.trace`` (a re-trace to a
    jaxpr), ``jax.lower`` (jaxpr to MLIR) and ``jax.compile`` (the backend
    compile *or* the load from the persistent cache), each with the
    program's ``fun_name``, as children of whatever span is open on their
    thread: that is how a re-trace is put down to a node. ``jax.compile``
    carries ``cache_hit`` where the compilation cache's own events on that
    thread since the last request tell it. Registration is global and
    permanent, like ``CompileEventCounter``'s: ``active_tracer()`` makes
    the one of the process, and with no tracer armed an event costs the
    None check."""

    NAMES = {
        "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
        "/jax/core/compile/backend_compile_duration": "jax.compile",
    }

    def __init__(self):
        self._tls = threading.local()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_time_span_listener(self._on_time_span)

    def _on_event(self, name, **kwargs):
        if name == CompileEventCounter.EVENT:
            self._tls.cache_hit = False
        elif name == CompileEventCounter.HIT_EVENT:
            self._tls.cache_hit = True

    def _on_time_span(self, event, start, end, **kwargs):
        name = self.NAMES.get(event)
        if name is None:
            return
        attrs = {"fun_name": str(kwargs.get("fun_name", ""))}
        if name == "jax.compile":
            hit = getattr(self._tls, "cache_hit", None)
            self._tls.cache_hit = None
            if hit is not None:
                attrs["cache_hit"] = hit
        tracer = active_tracer()
        if tracer is not None:
            offset = tracer.wall_offset_ns
            tracer.record(name, "jax", int(start * 1e9) - offset,
                          int(end * 1e9) - offset, **attrs)


class ServingCounters:
    """Process-wide serving observability: how many XLA compiles the
    bucketed apply path performed, and which buckets traffic actually
    lands in (the evidence behind 'zero steady-state recompiles' — after
    warmup the compile counter must not move). Thread-safe: the
    micro-batcher worker and client threads both record here."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.compiles = 0
            self.calls = 0
            self.rows_in = 0
            self.rows_padded = 0
            self.bucket_hits: Dict[int, int] = {}
            self.compiles_by_bucket: Dict[int, int] = {}

    def record_compile(self, bucket: int) -> None:
        with self._lock:
            self.compiles += 1
            # Per-bucket attribution: warmup evidence can then NAME which
            # bucket compiled instead of reporting an anonymous total.
            self.compiles_by_bucket[bucket] = (
                self.compiles_by_bucket.get(bucket, 0) + 1
            )

    def record_call(self, bucket: int, rows: int) -> None:
        with self._lock:
            self.calls += 1
            self.rows_in += rows
            self.rows_padded += bucket - rows
            self.bucket_hits[bucket] = self.bucket_hits.get(bucket, 0) + 1

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "compiles": self.compiles,
                "calls": self.calls,
                "rows_in": self.rows_in,
                "rows_padded": self.rows_padded,
                "pad_overhead": (
                    self.rows_padded / self.rows_in if self.rows_in else 0.0
                ),
                "bucket_hits": dict(sorted(self.bucket_hits.items())),
                "compiles_by_bucket": dict(
                    sorted(self.compiles_by_bucket.items())
                ),
            }


serving_counters = ServingCounters()
metrics_registry.register("serving", serving_counters)


class ReliabilityCounters(CounterSet):
    """Process-wide failure/recovery observability: every reliability event
    (utils/reliability.py and its call sites) lands here, so a chaos run
    can assert which recoveries fired and an operator can see whether a
    'healthy' fit was actually limping on retries. Thread-safe: producer
    threads, the serving worker, and client threads all record.

    Well-known keys (call sites may add more; snapshot returns whatever
    was bumped):

    - ``faults_injected_<site>`` — harness injections per FaultPlan site
    - ``io_retries`` / ``h2d_retries`` — transient-failure retries at the
      record boundary resp. the solvers' H2D step
    - ``records_quarantined`` — irrecoverably corrupt records skipped
    - ``producer_restarts`` / ``producer_leaks`` — prefetch producer
      threads restarted after silent death / still alive after the join
      timeout
    - ``oom_downshifts`` — chunks halved after repeated RESOURCE_EXHAUSTED
    - ``checkpoints_written`` / ``checkpoints_resumed`` /
      ``chunks_skipped_on_resume`` — streaming-solver snapshot traffic
    - ``requests_rejected`` / ``deadline_expired`` — serving fast-fail
      backpressure and expired-before-run requests
    - ``worker_restarts`` / ``futures_failed_on_close`` /
      ``futures_failed_on_worker_death`` — serving worker lifecycle
    - ``replica_deaths`` / ``replica_revivals`` /
      ``serve_groups_redispatched`` — serving replica-pool lifecycle (a
      dead replica's in-flight groups re-dispatch to survivors)
    """


reliability_counters = ReliabilityCounters()
metrics_registry.register("reliability", reliability_counters)


class ShardingCounters(CounterSet):
    """Process-wide data-parallel placement observability: every batch
    entering the graph (and every fused-chain lowering decision) lands
    here, so 'the fit ran data-parallel' is a counter assertion instead
    of a hope — the registry-verified 'no silent single-device cliff'
    gate of the multichip bench. Thread-safe (CounterSet).

    Well-known keys:

    - ``batches_sharded`` — divisible host batches row-sharded over the
      mesh at graph entry (DatasetOperator)
    - ``batches_deferred_pad`` — non-divisible host batches left to the
      fused chain's mask-pad path (placement deferred, NOT a fallback)
    - ``batches_padded`` / ``pad_rows_added`` — fused-chain calls that
      mask-padded a non-divisible batch onto the mesh, and how many
      zero rows the padding added in total
    - ``sharded_chain_calls`` — fused-chain executions lowered with the
      explicit SpecLayout shardings (vs inheriting input placement)
    - ``fallback_small_batch`` — batches below ``config.shard_min_rows``
      that genuinely ran single-device (the ONLY surviving fallback)
    - ``fallback_row_coupled`` — pad-unsound (row_independent=False)
      chains that kept the propagation path for a non-divisible batch
    - ``buffers_donated`` — staged chain inputs donated into the lowered
      chain (the buffer aliases an output; one live copy, not two)
    - ``donation_refused`` — staged calls under ``config.donate_buffers``
      where no output aval could alias the buffer (shrinking/growing
      chains): donation would be a warning and a no-op, so it is refused
      up front and counted instead of silently dropped
    - ``pallas_sharded_calls`` — sharded chain executions whose lowered
      body runs a Pallas kernel (``uses_pallas``) — the 'kernel actually
      active on the sharded path' evidence the ImageNet bench gates on
    - ``pallas_mosaic_calls`` / ``pallas_interpret_calls`` — calls of the
      Pallas Fisher-vector kernel lowered through Mosaic, or run by the
      Pallas interpreter (CPU only); chip_smoke.py asserts the former
    """


sharding_counters = ShardingCounters()
metrics_registry.register("sharding", sharding_counters)


class ProgramCounters(CounterSet):
    """How the jitted transformer programs were found and what they were
    handed as arguments (``workflow/pipeline.py``). Thread-safe
    (CounterSet).

    - ``argument_bytes``: the size of the arrays a call handed its
      program, summed over the calls; a chain whose arrays are constants
      of its program adds nothing, so a span that reads the counter around
      a call tells the two apart
    - ``shared_program_calls``: calls of a program found by the
      transformer's structure (``Transformer.shares_program``): a second
      fit meets the first fit's executable
    - ``closure_program_calls``: calls of a transformer's own jitted
      closure, traced anew for every new transformer; a fit that builds
      only transformers with value-hashed static parts reads 0 here
    - ``dataset_fingerprints``: calls of ``array_fingerprint`` on an array
      over 1 MiB (``workflow/fingerprint.py``): a dataset hashed for a
      walk's prefix hashes or a disk-cache key. A fit's root span carries
      this and the two call counts (``since``); a fit that places its
      host batch once (``placed_batch``) reads 1 here
    - ``collective_bytes``: the size of every array a reduction across the
      mesh was handed (``linalg/row_matrix.count_reduced``: grams, AᵀR,
      column sums, the mixture's statistics a sweep, the descriptor
      sample), from shapes on the dispatching host; 0 on one device. On
      the fit's root span with the others
    """

    _A_FIT = ("shared_program_calls", "closure_program_calls",
              "dataset_fingerprints", "collective_bytes")

    def calls(self) -> Dict[str, int]:
        """The counts a fit reports as they stand: a mark for ``since``."""
        return {key: self.get(key) for key in self._A_FIT}

    def since(self, mark: Dict[str, int]) -> Dict[str, int]:
        """Those counts since ``mark`` (an earlier ``calls()``)."""
        return {key: self.get(key) - mark[key] for key in self._A_FIT}


program_counters = ProgramCounters()
metrics_registry.register("programs", program_counters)


class ServePlanCounters(CounterSet):
    """Process-wide serve-planner observability: every memory-bounded
    serving decision lands here, so "the planner trimmed the ladder" is
    a counter assertion instead of a log line someone may have read —
    the no-silent-trim contract of the HBM-planned bucket ladder.
    Thread-safe (CounterSet).

    Well-known keys:

    - ``ladders_planned`` — ladder plans priced by the HBM planner at
      warmup: one per (engine, traffic signature) — a re-warm at a new
      feature shape/dtype re-prices and counts again
    - ``ladders_pinned`` — plans skipped because the ladder was explicit
      (buckets=, KEYSTONE_SERVE_BUCKETS, or config.serve_buckets — the
      env-pin-wins convention); per (engine, signature) like
      ``ladders_planned``
    - ``buckets_trimmed`` — ladder rungs dropped because their AOT-warmed
      executables could not coexist under the HBM headroom
    - ``top_bucket_capped`` — plans whose LARGEST rung was among the
      trims (oversize batches now chunk through a smaller top bucket)
    - ``plans_unpriced`` — plans skipped because no bytes-per-row could
      be priced (no measured profile and no abstract estimate)
    - ``plans_over_budget`` — plans still over budget after trimming to
      the minimum one-rung ladder (serving proceeds; KG104 flags it)
    - ``prefetch_clamped`` — session plans that clamped the hand-picked
      prefetch depth down against the budget share
    """


serve_plan_counters = ServePlanCounters()
metrics_registry.register("serve_plan", serve_plan_counters)


class OnlineCounters(CounterSet):
    """Process-wide online-learning observability: every incremental-fit
    decision (workflow/online.py) lands here, so "the model is current"
    is a counter assertion — folds happened, re-solves ran, refreshes
    reached the daemon — instead of a log line. Thread-safe (CounterSet);
    rides ``/metrics`` like every registry family.

    Well-known keys:

    - ``batches_folded`` — labeled batches folded into retained
      gram/AᵀB/mean accumulators (``OnlineState.fold``; both the trainer
      path and direct ``partial_fit`` calls)
    - ``resolves`` — cheap re-solves of the retained state through the
      Cholesky path (``OnlineState.solve``)
    - ``refreshes_pushed`` — completed trainer refreshes: re-solve +
      versioned artifact + (when wired) daemon hot-swap
    - ``refreshes_failed`` — refreshes that died anywhere (fault sites,
      failed swap, full disk): serving keeps the old generation, the
      accumulators are untouched, the next cadence tick retries
    - ``windows_evicted`` — sliding-window units whose sums were
      subtracted from the running totals (subtract-on-evict)
    - ``full_refits`` — ``Pipeline.refit_stream`` cadence ticks that
      fell back to a FULL head refit because the head estimator lacks
      ``partial_fit`` (the KG105 hazard, counted at runtime)
    - ``batches_buffered`` — batches a partial_fit-less
      ``refit_stream`` buffered for those full refits (distinct from
      ``batches_folded``: nothing reached retained accumulators)
    """


online_counters = OnlineCounters()
metrics_registry.register("online", online_counters)


class ElasticCounters(CounterSet):
    """Process-wide elastic-mesh observability: every durable-state
    migration across a mesh-width change (``utils.mesh.reshard_state``)
    lands here, so "the resume was migrated, not refused and not
    silently restarted" is a counter assertion — the never-silent half
    of the ``KEYSTONE_ELASTIC_MESH`` contract. Thread-safe (CounterSet);
    rides ``/metrics`` like every registry family.

    Well-known keys:

    - ``states_migrated`` — total successful ``reshard_state``
      migrations, any family
    - ``stream_solve_migrated`` — chunked-solve snapshots
      (``solve_least_squares_chunked`` checkpoints) re-manifested onto a
      new mesh width
    - ``bcd_epoch_migrated`` / ``bcd_stream_migrated`` — BCD epoch
      checkpoints (orbax) and mid-epoch block snapshots whose residual
      was re-padded and manifest rewritten
    - ``online_state_migrated`` — ``OnlineState`` snapshots resumed
      across a width change
    - ``profile_migrated`` — profile-store entries whose per-shard rows
      were re-scaled onto the new width
    - ``migrations_refused`` — same-problem/different-mesh state that
      could NOT be migrated (torn/partial per-shard payload, unknown
      family): kept the typed ``MeshMismatchError`` refusal
    """


elastic_counters = ElasticCounters()
metrics_registry.register("elastic", elastic_counters)


class TelemetryCounters(CounterSet):
    """Process-wide telemetry-pipeline observability: every durable-
    export decision (utils/telemetry.py TelemetryLog) and every loss
    the in-memory rings take lands here — telemetry that silently
    loses data is worse than none, so the losses themselves are
    first-class counters riding ``/metrics`` like every registry
    family. Thread-safe (CounterSet).

    Well-known keys:

    - ``records_enqueued`` — journeys/span-tree records accepted onto
      the writer queue
    - ``records_written`` — records the writer thread landed on disk
    - ``records_dropped`` — records lost WITHOUT blocking: queue full,
      log closed, or a write error (the never-blocks-admission
      contract, measured)
    - ``segments_rotated`` — size-triggered segment rotations
    - ``segments_pruned`` — rotated segments deleted by bounded
      retention (``KEYSTONE_TELEMETRY_KEEP``)
    - ``journeys_evicted`` — FlightRecorder journey-ring evictions: a
      resolved-but-unexported journey pushed out by ring capacity
      (the flight-recorder half of the no-silent-loss satellite;
      ``Tracer.dropped`` rides the ``tracer`` gauges)
    """


telemetry_counters = TelemetryCounters()
metrics_registry.register("telemetry", telemetry_counters)
metrics_registry.register("tracer", _TracerLoss())


class CapacityCounters(CounterSet):
    """Process-wide learned-capacity-model observability
    (workflow/capacity.py and its three consumers): every refusal,
    coalesce, and re-plan the model drives is a counted decision —
    nothing the model does to traffic is silent. Thread-safe
    (CounterSet).

    Well-known keys:

    - ``predicted_refusals`` — requests 429'd because the model
      predicted their completion past the deadline
      (``predicted_infeasible``), before any device work
    - ``microbatches_formed`` — gold-anchored flush groups that
      absorbed at least one best-effort request into padding slack
    - ``microbatch_rows_filled`` — best-effort rows served inside
      gold groups' pad slack (free device time, measured)
    - ``replans`` — autoscale re-plans executed (mix shift past the
      threshold; decision-logged in the optimizer ring)
    - ``replans_suppressed`` — re-plans refused by the no-flap guard
      (a second trigger inside the re-plan window)
    - ``replicas_resized`` — replica-pool grow/shrink operations the
      re-plan loop performed
    - ``model_cold_skips`` — consumer consultations that no-op'd
      because the model had fewer than ``KEYSTONE_CAPACITY_MIN_SAMPLES``
      journeys (the cold contract, measured)
    - ``guard_violations`` — strict-accuracy guard hits: a refusal the
      matured model would call feasible (a bug gate, not a tuning knob)
    """


capacity_counters = CapacityCounters()
metrics_registry.register("capacity", capacity_counters)
