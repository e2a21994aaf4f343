"""A profiler trace's device seconds put down to the program's own names.

``jax.profiler`` writes an ``XSpace`` protobuf (``*.xplane.pb``). On a TPU
every operation of the ``XLA Ops`` line points at an *event metadata*
entry whose stats hold ``tf_op`` (the jax ``op_name`` path, e.g.
``jit(local)/while/body/closed_call/solver.gram/dot_general:``),
``hlo_category``, ``flops``, ``bytes_accessed`` and ``program_id``.
``jax.profiler.ProfileData`` shows an event's own stats and not its
metadata's, so this module reads the wire format itself, with the standard
library alone.

A ``jax.named_scope`` extends the ``op_name`` path at trace time and costs
nothing after. The scopes the device programs carry are listed once, in
``keystone_tpu.utils.metrics.DEVICE_SCOPES``; a fused chain's stages carry
``STAGE_SCOPE_PREFIX`` and the stage's class name.

    python -m keystone_tpu.utils.device_trace <trace> [--fit-span NAME] [--json]

``<trace>`` is an ``.xplane.pb``, an ``.xplane.pb.gz``, or the directory
that ``jax.profiler.start_trace`` was given.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
import struct
import sys
from functools import lru_cache
from typing import Dict, Iterator, List, Optional, Tuple

from keystone_tpu.utils.metrics import DEVICE_SCOPES, STAGE_SCOPE_PREFIX

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
UNSCOPED, NO_OP_NAME = "(unscoped)", "(no op_name)"
#: The stats of an operation's metadata that ``read`` keeps.
OP_STATS = ("tf_op", "hlo_category", "flops", "bytes_accessed", "program_id")


# ------------------------------------------------------------ wire format


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message: a varint as an
    int, a length-delimited field as its bytes, fixed 64 and 32 as their
    raw bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an XSpace")
        yield field, wire, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(v: bytes) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf: bytes):
    """(metadata id, value, is a reference) of one ``XStat``."""
    key, value, ref = 0, None, False
    for field, _wire, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = struct.unpack("<d", v)[0]
        elif field == 3:
            value = v
        elif field == 4:
            value = _signed(v)
        elif field in (5, 6):
            value = _text(v)
        elif field == 7:
            value, ref = v, True
    return key, value, ref


def _map_entry(buf: bytes):
    key, value = 0, b""
    for field, _wire, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def _event_metadata(buf: bytes):
    name, stats = "", []
    for field, _wire, v in _fields(buf):
        if field == 2:
            name = _text(v)
        elif field == 5:
            stats.append(_stat(v))
    return name, stats


def _stat_name(buf: bytes) -> str:
    for field, _wire, v in _fields(buf):
        if field == 2:
            return _text(v)
    return ""


def _event(buf: bytes):
    meta = offset_ps = duration_ps = 0
    for field, _wire, v in _fields(buf):
        if field == 1:
            meta = v
        elif field == 2:
            offset_ps = _signed(v)
        elif field == 3:
            duration_ps = _signed(v)
    return meta, offset_ps, duration_ps


def _line(buf: bytes):
    name, timestamp_ns, events = "", 0, []
    for field, _wire, v in _fields(buf):
        if field == 2:
            name = _text(v)
        elif field == 3:
            timestamp_ns = _signed(v)
        elif field == 4:
            events.append(v)
    return name, timestamp_ns, events


def _plane(buf: bytes) -> Optional[dict]:
    """One ``XPlane`` decoded, or None for a plane that is neither a
    device's nor the host's (found out before anything in it is decoded)."""
    name, lines, raw_meta, raw_stats = "", [], [], []
    for field, _wire, v in _fields(buf):
        if field == 2:
            name = _text(v)
        elif field == 3:
            lines.append(v)
        elif field == 4:
            raw_meta.append(v)
        elif field == 5:
            raw_stats.append(v)
    device = bool(DEVICE_PLANE.match(name))
    if not (device or name.startswith("/host:")):
        return None
    stat_names = {key: _stat_name(value) for key, value in map(_map_entry, raw_stats)}
    event_meta = {key: _event_metadata(value) for key, value in map(_map_entry, raw_meta)}
    ops = {}
    for key, (op, stats) in event_meta.items():
        kept = {}
        for stat_id, value, ref in stats:
            stat = stat_names.get(stat_id)
            if stat in OP_STATS:
                kept[stat] = stat_names.get(value, "") if ref else value
        ops[key] = (op, kept)
    out_lines = []
    for raw in lines:
        line_name, timestamp_ns, raw_events = _line(raw)
        if device and line_name not in (MODULES_LINE, OPS_LINE):
            continue
        events = []
        for raw_event in raw_events:
            meta, offset_ps, duration_ps = _event(raw_event)
            start = timestamp_ns + offset_ps // 1000
            op, stats = ops.get(meta, ("", {}))
            events.append((op, start, start + duration_ps // 1000,
                           stats if device and line_name == OPS_LINE else None))
        out_lines.append((line_name, events))
    return {"name": name, "lines": out_lines}


def find_xplane(path: str) -> str:
    """``path`` itself, or the one ``.xplane.pb`` under the directory that
    ``jax.profiler.start_trace(path)`` wrote into."""
    if not os.path.isdir(path):
        return path
    found = sorted(
        glob.glob(os.path.join(path, "plugins", "profile", "*", "*.xplane.pb"))
        + glob.glob(os.path.join(path, "*.xplane.pb"))
    )
    if len(found) != 1:
        raise ValueError(f"expected one trace under {path}, found {len(found)}")
    return found[0]


def read(path: str) -> List[dict]:
    """The device and host planes of a trace: ``[{"name", "lines": [(line
    name, [(event name, start_ns, end_ns, stats)])]}]``, names and times as
    ``benchmark/tracereduce.read_xplane`` has them (a device plane's lines
    ``XLA Modules`` and ``XLA Ops``, every line of a host plane; one clock,
    nanoseconds). ``stats`` is None but for a device operation, where it is
    what its metadata holds of ``OP_STATS``: ``tf_op`` is the jax
    ``op_name`` path and is absent from copies and some loop fusions."""
    path = find_xplane(path)
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        buf = f.read()
    planes = (_plane(v) for field, _wire, v in _fields(buf) if field == 1)
    return [plane for plane in planes if plane is not None]


# -------------------------------------------------------------- reduction


def module_name(event_name: str) -> str:
    """``jit_local(1234)`` -> ``jit_local``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _self_times(events):
    """``[(event, self_ns, leaf)]`` of nested ``(name, start, end, stats)``
    events: each one's duration less what its children cover (a ``while``
    less its body: the rule of ``tracereduce._self_times``); ``leaf`` where
    it holds no other."""
    out, stack = [], []
    for ev in sorted(events, key=lambda t: (t[1], -t[2])):
        _name, s, e = ev[:3]
        while stack and stack[-1][0][2] <= s:
            stack.pop()
        if stack:
            stack[-1][1][0] -= min(e, stack[-1][0][2]) - s
            stack[-1][1][1] = False
        own = [e - s, True]
        stack.append((ev, own))
        out.append((ev, own))
    return [(ev, max(own[0], 0), own[1]) for ev, own in out]


@lru_cache(maxsize=None)
def scope_of(tf_op: Optional[str]) -> str:
    """The innermost segment of an ``op_name`` path that is one of
    ``DEVICE_SCOPES`` or names a chain's stage; ``(unscoped)`` for a path
    with neither, ``(no op_name)`` where the operation has none."""
    if not tf_op:
        return NO_OP_NAME
    for segment in reversed(tf_op.split("/")):
        if segment in DEVICE_SCOPES or segment.startswith(STAGE_SCOPE_PREFIX):
            return segment
    return UNSCOPED


@lru_cache(maxsize=None)
def stage_of(tf_op: Optional[str]) -> Optional[str]:
    """The chain's stage an ``op_name`` path lies under (the segment that
    carries ``STAGE_SCOPE_PREFIX``), whatever scope lies further in; None
    outside a stage."""
    for segment in (tf_op or "").split("/"):
        if segment.startswith(STAGE_SCOPE_PREFIX):
            return segment
    return None


def by_scope(trace: List[dict], fit_span: Optional[str] = None, chip: int = 0) -> dict:
    """Device self time of chip ``chip`` per (module, ``program_id``,
    scope): ``{"fits", "window_s", "rows": [{"module", "program_id",
    "stage", "scope", "seconds", "flops", "bytes_accessed", "events"}]}``,
    most seconds first; ``stage`` is the chain's stage the scope lies under
    (``stage_of``: the scope itself where no other lies further in). ``flops`` and ``bytes_accessed`` are XLA's own counts of
    the operations that hold no other (a ``while`` counts through its
    body), summed over their events, so a scope's FLOP/s and bytes/s come
    with its seconds. With ``fit_span`` (the name of a host span around
    each fit, the benchmark's ``bench.fit``) only the operations from the
    first such span's start to the last one's end count, and every number
    is a fit's: the sum over the number of spans. Without it, the whole
    trace, summed."""
    fits = []
    if fit_span is not None:
        for plane in trace:
            if plane["name"].startswith("/host:"):
                for _line_name, events in plane["lines"]:
                    fits += [(s, e) for name, s, e, _st in events if name == fit_span]
        if not fits:
            raise ValueError(f"no {fit_span!r} span in the trace")
        fits.sort()
    lines = None
    for plane in trace:
        m = DEVICE_PLANE.match(plane["name"])
        if m and int(m.group(1)) == chip:
            lines = dict(plane["lines"])
    if lines is None:
        raise ValueError(f"no plane of chip {chip} in the trace: nothing ran on a device")
    runs = sorted((s, e, module_name(n)) for n, s, e, _st in lines.get(MODULES_LINE, []))
    starts = [r[0] for r in runs]
    events = lines.get(OPS_LINE, [])
    if fits:
        lo, hi = fits[0][0], fits[-1][1]
        events = [ev for ev in events if ev[2] > lo and ev[1] < hi]
    rows: Dict[tuple, list] = {}
    for (_name, s, _e, stats), own, leaf in _self_times(events):
        i = bisect.bisect_right(starts, s) - 1
        module = runs[i][2] if i >= 0 and s < runs[i][1] else "other"
        tf_op = stats.get("tf_op")
        key = (module, stats.get("program_id"), stage_of(tf_op), scope_of(tf_op))
        row = rows.setdefault(key, [0, 0.0, 0.0, 0])
        row[0] += own
        if leaf:
            row[1] += float(stats.get("flops") or 0)
            row[2] += float(stats.get("bytes_accessed") or 0)
        row[3] += 1
    n = len(fits) or 1
    return {
        "fits": len(fits) or None,
        "window_s": (fits[-1][1] - fits[0][0]) / 1e9 if fits else None,
        "rows": sorted(
            ({"module": m, "program_id": p, "stage": st, "scope": sc,
              "seconds": ns / 1e9 / n, "flops": fl / n, "bytes_accessed": by / n,
              "events": ev / n}
             for (m, p, st, sc), (ns, fl, by, ev) in rows.items()),
            key=lambda r: -r["seconds"]),
    }


def by_module(table: dict) -> List[dict]:
    """``by_scope``'s rows folded over ``program_id``: ``[{"module",
    "seconds", "unscoped_share", "no_op_name_share", "stages": {stage:
    seconds}, "scopes": [{"scope", "seconds", "flops",
    "bytes_accessed"}]}]``, modules and scopes by seconds.
    ``unscoped_share`` is of the seconds that carry an ``op_name``;
    ``no_op_name_share`` of the module's. ``stages`` sums a chain's
    seconds by stage, the scopes further in included."""
    modules: Dict[str, Dict[str, list]] = {}
    stages: Dict[str, Dict[str, float]] = {}
    for row in table["rows"]:
        scope = modules.setdefault(row["module"], {}).setdefault(row["scope"], [0.0, 0.0, 0.0])
        scope[0] += row["seconds"]
        scope[1] += row["flops"]
        scope[2] += row["bytes_accessed"]
        if row["stage"] is not None:
            by_stage = stages.setdefault(row["module"], {})
            by_stage[row["stage"]] = by_stage.get(row["stage"], 0.0) + row["seconds"]
    out = []
    for module, scopes in modules.items():
        total = sum(s[0] for s in scopes.values())
        bare = scopes.get(NO_OP_NAME, [0.0])[0]
        named = total - bare
        out.append({
            "module": module,
            "seconds": total,
            "unscoped_share": scopes.get(UNSCOPED, [0.0])[0] / named if named else 0.0,
            "no_op_name_share": bare / total if total else 0.0,
            "stages": dict(sorted(stages.get(module, {}).items(), key=lambda r: -r[1])),
            "scopes": sorted(
                ({"scope": name, "seconds": s[0], "flops": s[1], "bytes_accessed": s[2]}
                 for name, s in scopes.items()), key=lambda r: -r["seconds"]),
        })
    return sorted(out, key=lambda r: -r["seconds"])


def render(table: dict, min_share: float = 0.001) -> str:
    """The table by module and scope as text, largest first; a module
    under ``min_share`` of all the device's seconds is left out."""
    per = " a fit" if table["fits"] else ""
    out = [f"device seconds{per} by module and scope"
           + (f" ({table['fits']} fits, window {table['window_s']:.3f} s)" if table["fits"] else "")]
    modules = by_module(table)
    least = min_share * sum(m["seconds"] for m in modules)
    for m in modules:
        if m["seconds"] < least:
            continue
        out.append(f"{m['seconds']:10.6f} s  {m['module']}  (unscoped "
                   f"{100 * m['unscoped_share']:.1f} % of what has an op_name; "
                   f"no op_name {100 * m['no_op_name_share']:.1f} %)")
        for s in m["scopes"]:
            rates = ""
            if s["seconds"] > 0 and (s["flops"] or s["bytes_accessed"]):
                rates = (f"  {s['flops'] / s['seconds'] / 1e12:8.2f} TFLOP/s"
                         f"  {s['bytes_accessed'] / s['seconds'] / 1e9:8.1f} GB/s")
            out.append(f"    {s['seconds']:10.6f} s  {s['scope']}{rates}")
        own = {s["scope"]: s["seconds"] for s in m["scopes"]}
        for stage, seconds in m["stages"].items():
            if seconds > own.get(stage, 0.0):  # scopes lie further in
                out.append(f"    {seconds:10.6f} s  under {stage}, scopes further in included")
    return "\n".join(out)


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m keystone_tpu.utils.device_trace",
        description="Device seconds of a jax.profiler trace by HLO module and named scope.")
    ap.add_argument("trace", help=".xplane.pb, .xplane.pb.gz, or a start_trace directory")
    ap.add_argument("--fit-span", default=None,
                    help="a host span around each fit (bench.fit): seconds a fit inside them")
    ap.add_argument("--chip", type=int, default=0)
    ap.add_argument("--json", action="store_true", help="one JSON object instead of the table")
    args = ap.parse_args(argv)
    table = by_scope(read(args.trace), fit_span=args.fit_span, chip=args.chip)
    if args.json:
        print(json.dumps(dict(table, modules=by_module(table))))
    else:
        print(render(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
