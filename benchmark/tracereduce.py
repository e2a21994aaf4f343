"""From a profiler trace (``*.xplane.pb``) to the numbers the per-layer
readers use. Reads the file with ``jax.profiler.ProfileData`` and nothing
else of the program.

What a TPU trace holds (looked at by hand, PR 25): one plane per chip,
``/device:TPU:<i>``, whose line ``XLA Modules`` has one event per run of a
compiled program (``jit_local(<fingerprint>)``) and whose line ``XLA Ops``
has one event per HLO operation, named by its whole HLO line and nested
where an operation (a ``while``) contains others; and the host's plane ``/host:CPU`` with one line per
thread, where ``jax.profiler.TraceAnnotation`` spans appear under their
own name. All on one clock, in nanoseconds.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
FIT_SPAN = "bench.fit"


def module_name(event_name: str) -> str:
    """``jit_local(1234)`` -> ``jit_local``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """[(name, start, self_ns)] of nested events: each one's duration less
    the part its children cover, so that a ``while`` and the operations in
    its body are not counted twice."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda t: (t[1], -t[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        if stack:
            stack[-1][3][0] -= min(e, stack[-1][2]) - s
        own = [e - s]
        stack.append((name, s, e, own))
        out.append((name, s, own))
    return [(name, s, max(own[0], 0)) for name, s, own in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def op_name(event_name: str) -> str:
    """An operation's event is named by its whole HLO line. Keep the
    result's name, and a custom call's target: ``%custom-call.9 = ...
    custom_call_target="Cholesky"`` -> ``custom-call.9[Cholesky]``."""
    name = event_name.split(" = ", 1)[0].lstrip("%")
    target = re.search(r'custom_call_target="([^"]+)"', event_name)
    return f"{name}[{target.group(1)}]" if target else name


def reduce_planes(planes, chips: int = 1, top: int = 10, fit_span: str = FIT_SPAN) -> dict:
    """``planes``: [(plane name, [(line name, [(event name, start_ns,
    end_ns)])])]. Returns the summary the readers and the result line use:

    - ``window_s``: first fit span's start to the last one's end;
    - ``busy_s``: the union of the device's operation intervals inside the
      window, averaged over the chips;
    - ``ops``: [(module, operation, seconds, tags)] by self time, summed
      over the window and averaged over the chips. ``tags`` names the
      custom-call targets that ran in the same run of the module (so
      ``Cholesky`` marks every operation of a program that factorises: XLA
      expands a large Cholesky or triangular solve into hundreds of
      anonymous fusions around small ``Cholesky`` custom calls);
    - ``fit_modules_s``: per fit, the device seconds of each module;
    - ``breakdown``: the ten operations with most time and the ten longest
      idle gaps, each named by the fit span it lies in and the modules
      that ran before and after it.
    """
    fits, device = [], {}
    for plane, lines in planes:
        m = DEVICE_PLANE.match(plane)
        if m:
            device[int(m.group(1))] = dict(lines)
        elif plane.startswith("/host:"):
            for _line, events in lines:
                fits += [(s, e) for name, s, e in events if name == fit_span]
    fits.sort()
    if not fits:
        raise ValueError(f"no {fit_span!r} span in the trace")
    lo, hi = fits[0][0], fits[-1][1]
    window_s = (hi - lo) / 1e9
    used = sorted(device)[:chips]
    empty = {"window_s": window_s, "busy_s": 0.0, "ops": [], "fit_modules_s": [],
             "breakdown": {"device_ops": [], "idle_gaps": []}, "notes": []}
    if not used:
        return dict(empty, notes=["no device plane in the trace"])

    def fit_at(t):
        i = bisect.bisect_right(fits, (t, float("inf"))) - 1
        return i if i >= 0 and t < fits[i][1] else None

    busy_s, ops, gaps, per_fit = 0.0, {}, {}, [dict() for _ in fits]
    for chip in used:
        lines = device[chip]
        runs = sorted((s, e, module_name(n)) for n, s, e in lines.get(MODULES_LINE, []))
        starts = [r[0] for r in runs]

        def run_at(t, _runs=runs, _starts=starts):
            i = bisect.bisect_right(_starts, t) - 1
            return i if i >= 0 and t < _runs[i][1] else None

        def module_at(t, _runs=runs):
            i = run_at(t)
            return _runs[i][2] if i is not None else "other"

        events = [(n, s, e) for n, s, e in lines.get(OPS_LINE, []) if e > lo and s < hi]
        busy = _union(_clip([(s, e) for _n, s, e in events], lo, hi))
        busy_s += sum(e - s for s, e in busy) / 1e9
        timed = _self_times(events)
        tags = {}
        for name, s, _own in timed:
            target = re.search(r'custom_call_target="([^"]+)"', name)
            if target:
                tags.setdefault(run_at(s), set()).add(target.group(1))
        for name, s, own in timed:
            run = run_at(s)
            module = runs[run][2] if run is not None else "other"
            key = (module, op_name(name), tuple(sorted(tags.get(run, ()))))
            ops[key] = ops.get(key, 0) + own
            fit = fit_at(s)
            if fit is not None:
                per_fit[fit][module] = per_fit[fit].get(module, 0) + own
        if chip == used[0]:
            edges = [(lo, lo)] + [tuple(b) for b in busy] + [(hi, hi)]
            for (_s0, e0), (s1, _e1) in zip(edges, edges[1:]):
                if s1 > e0:
                    fit = fit_at(e0)
                    where = f"{fit_span}[{fit}]" if fit is not None else "between fits"
                    name = f"{where}: after {module_at(e0 - 1)}, before {module_at(s1)}"
                    gaps[name] = gaps.get(name, 0.0) + (s1 - e0) / 1e9
    n = len(used)
    op_list = sorted(((m, o, ns / 1e9 / n, list(t)) for (m, o, t), ns in ops.items()),
                     key=lambda r: -r[2])
    return dict(
        empty,
        busy_s=busy_s / n,
        ops=op_list,
        # Every fit has to do the whole work: a later fit's solver time
        # agrees with the first's.
        fit_modules_s=[{m: ns / 1e9 / n for m, ns in f.items()} for f in per_fit],
        breakdown={
            "device_ops": [[f"{m}/{o}", s] for m, o, s, _t in op_list[:top]],
            "idle_gaps": [[k, v] for k, v in sorted(gaps.items(), key=lambda r: -r[1])[:top]],
        },
    )


def read_xplane(path: str, fit_span: str = FIT_SPAN):
    """The planes of one ``.xplane.pb`` in the shape ``reduce_planes`` takes."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not (DEVICE_PLANE.match(plane.name) or plane.name.startswith("/host:")):
            continue
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device and line.name not in (MODULES_LINE, OPS_LINE):
                continue
            events = []
            for ev in line.events:
                if device or ev.name == fit_span:
                    start = int(ev.start_ns)
                    events.append((ev.name, start, start + int(ev.duration_ns)))
            if events:
                lines.append((line.name, events))
        planes.append((plane.name, lines))
    return planes


def find_xplane(trace_dir: str) -> str:
    """The one trace that ``jax.profiler.start_trace(trace_dir)`` wrote."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(found) != 1:
        raise ValueError(f"expected one trace under {trace_dir}, found {len(found)}")
    return found[0]


def reduce_dir(trace_dir: str, chips: int = 1) -> dict:
    return reduce_planes(read_xplane(find_xplane(trace_dir)), chips=chips)
