"""What the host was doing while the chip waited: one traced run of a
cell at its own size, then, from the kept ``.xplane.pb``, every idle gap
of the device (the same union of ``XLA Ops`` intervals as ``tracereduce``)
put down to the program's spans on the host plane (``ks:<name>``, the
mirror of ``keystone_tpu.utils.metrics.Tracer.span``): at every idle
instant, the innermost span open on the host. Seconds a fit, largest first.

    python3 benchmark/tools/host_gaps.py imagenet-fit 2200000011

The ring's ``jax.trace`` / ``jax.lower`` / ``jax.compile`` records have no
mirror in the trace (they arrive with their endpoints, after the fact);
the run above lays them over it by the offset between the last ``fit``
root in the ring and its mirror. ``attribute(path)`` alone reads a trace
somebody else kept. The last line of output is the result as JSON.
"""

import bisect
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import tracereduce as tr

PREFIX = "ks:"
NO_SPAN = "(no span open)"


def read_host_spans(path: str, fit_span: str = tr.FIT_SPAN):
    """[(name, start_ns, end_ns)] of the host plane's ``ks:`` spans and the
    harness's fit spans, from every thread."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX) or ev.name == fit_span:
                    start = int(ev.start_ns)
                    spans.append((ev.name, start, start + int(ev.duration_ns)))
    return spans


def innermost(spans):
    """[(name, start, end)] -> sorted, disjoint [(start, end, name)] in
    which ``name`` is the innermost span open over that stretch: of those
    open, the shortest (of two as long, the one that began last). Spans
    that only nearly nest, as records laid over from another clock do,
    need no care."""
    points = sorted({t for _n, s, e in spans for t in (s, e)})
    out = []
    for a, b in zip(points, points[1:]):
        open_here = [(e - s, -s, n) for n, s, e in spans if s <= a and e >= b]
        if not open_here:
            continue
        name = min(open_here)[2]
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def _overlaps(segments, starts, lo, hi):
    """{name: ns} of the segments' overlap with [lo, hi)."""
    out = {}
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    covered = 0
    while i < len(segments) and segments[i][0] < hi:
        s, e, name = segments[i]
        ns = min(e, hi) - max(s, lo)
        if ns > 0:
            out[name] = out.get(name, 0) + ns
            covered += ns
        i += 1
    if hi - lo > covered:
        out[NO_SPAN] = hi - lo - covered
    return out


def attribute(path: str, host_spans=None, chip: int = 0, top: int = 12) -> dict:
    """Idle seconds a fit of chip ``chip`` by the innermost host span, and
    the longest gaps (named as ``tracereduce`` names them, by the programs
    around them) each with the spans that fill it. ``host_spans``: the
    trace's own (``read_host_spans``) where the caller has read them, with
    whatever it laid over them."""
    host = read_host_spans(path) if host_spans is None else host_spans
    fits = sorted((s, e) for name, s, e in host if name == tr.FIT_SPAN)
    if not fits:
        raise ValueError(f"no {tr.FIT_SPAN!r} span in {path}")
    lo, hi = fits[0][0], fits[-1][1]
    device = {}
    for plane, lines in tr.read_xplane(path):
        m = tr.DEVICE_PLANE.match(plane)
        if m:
            device[int(m.group(1))] = dict(lines)
    if chip not in device:
        raise ValueError(f"no plane of chip {chip} in {path}: nothing ran on a device")
    runs = sorted((s, e, tr.module_name(n)) for n, s, e in device[chip].get(tr.MODULES_LINE, []))
    run_starts = [r[0] for r in runs]

    def module_at(t):
        i = bisect.bisect_right(run_starts, t) - 1
        return runs[i][2] if i >= 0 and t < runs[i][1] else "other"

    busy = tr._union(tr._clip(
        [(s, e) for _n, s, e in device[chip].get(tr.OPS_LINE, [])], lo, hi))
    segments = innermost([(n, s, e) for n, s, e in host if n != tr.FIT_SPAN])
    starts = [s[0] for s in segments]
    by_span, gaps = {}, {}
    edges = [(lo, lo)] + [tuple(b) for b in busy] + [(hi, hi)]
    for (_s0, e0), (s1, _e1) in zip(edges, edges[1:]):
        if s1 <= e0:
            continue
        name = f"after {module_at(e0 - 1)}, before {module_at(s1)}"
        gap = gaps.setdefault(name, {})
        for span, ns in _overlaps(segments, starts, e0, s1).items():
            by_span[span] = by_span.get(span, 0) + ns
            gap[span] = gap.get(span, 0) + ns
    n = len(fits)

    def ranked(d, k):
        return [[name, ns / n / 1e9] for name, ns in sorted(d.items(), key=lambda r: -r[1])[:k]]

    return {
        "fits": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s_a_fit": sum(e - s for s, e in busy) / n / 1e9,
        "idle_s_a_fit": sum(by_span.values()) / n / 1e9,
        "idle_by_span": ranked(by_span, top),
        "gaps": [{"gap": name, "s_a_fit": sum(g.values()) / n / 1e9, "spans": ranked(g, 5)}
                 for name, g in sorted(gaps.items(), key=lambda r: -sum(r[1].values()))[:top]],
    }


def ring_records(host_spans, names=("jax.trace", "jax.lower", "jax.compile")):
    """The ring's records of ``names`` on the clock of the trace that
    ``host_spans`` came from, as ``ks:`` spans: laid over it by the last
    ``fit`` root and its mirror."""
    from keystone_tpu.utils.metrics import recorded_tracer

    tracer = recorded_tracer()
    ring = tracer.spans() if tracer is not None else []
    roots = [s for s in ring if s["name"] == "fit" and s["parent_id"] is None]
    mirrors = sorted(s for n, s, _e in host_spans if n == PREFIX + "fit")
    if not roots or not mirrors:
        return []
    # Only the traced window's roots have a mirror: align the last ones.
    offset = mirrors[-1] - roots[-1]["start_ns"]
    since = roots[-min(len(mirrors), len(roots))]["start_ns"]
    return [(PREFIX + s["name"], s["start_ns"] + offset, s["start_ns"] + s["dur_ns"] + offset)
            for s in ring if s["name"] in names and s["start_ns"] >= since]


def main(argv) -> int:
    import harness

    cell, seed = argv[0], int(argv[1])
    out_dir = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    kept = os.path.join(out_dir, f"host_gaps-{cell}-{seed}.xplane.pb")
    result = harness.run_cell(cell, seed, 0.0, True, keep_trace=kept)
    host = read_host_spans(kept)
    table = attribute(kept, host_spans=host + ring_records(host))
    table["metrics"] = {k: v["value"] for k, v in result["metrics"].items()}
    table["notes"], table["correct"] = result["notes"], result["correct"]
    for name, seconds in table["idle_by_span"]:
        print(f"{seconds:9.3f} s a fit idle under {name}")
    for gap in table["gaps"]:
        inside = ", ".join(f"{name} {s:.3f}" for name, s in gap["spans"])
        print(f"{gap['s_a_fit']:9.3f} s a fit {gap['gap']}: {inside}")
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
