"""Readers of the per-layer metrics that come from the program's own span
ring (``keystone_tpu.utils.metrics.Tracer``), beside ``reducers.py``. This
module imports the program: ``recorded_tracer()`` hands over the ring that
a profiler session armed, after the session has stopped. Against a program
that has no such ring, or no ``fit`` root span, every reader returns None
and says why in a note: never a guess, never 0.

The window's fits are the ring's last ``ctx["fits"]`` root spans named
``fit``; a span belongs to a fit by its ``root_id``. Times are *self*
times: a span's duration less what its children (by ``parent_id``) cover,
summed over the window and divided by its fits. The ``jax.*`` records of
one parent nest in each other (a re-trace traces the primitives inside
it), so they count by the union of their intervals.
"""

from __future__ import annotations

FIT_ROOT = "fit"
JAX_SPANS = ("jax.trace", "jax.lower", "jax.compile")
TABLE_ROWS = 14


def _covered(intervals, lo, hi) -> int:
    """Nanoseconds of [lo, hi) that the intervals cover, overlaps once."""
    from tracereduce import _clip, _union

    return sum(e - s for s, e in _union(_clip(intervals, lo, hi)))


def _ends(span):
    return span["start_ns"], span["start_ns"] + span["dur_ns"]


def _note(ctx, text):
    ctx.setdefault("notes", []).append(text)


def _ring():
    try:
        from keystone_tpu.utils import metrics
    except ImportError:
        return None
    reader = getattr(metrics, "recorded_tracer", None)
    tracer = reader() if reader is not None else None
    return tracer.spans() if tracer is not None else None


def window(ctx, ring=None):
    """What the readers share, worked out once a run and kept in ``ctx``:
    ``{"roots", "self_ns": {name: ns}, "spans": {name: [span]},
    "retrace_ns", "names": {id: name}}`` over the window's fits, or None (with a note) where
    the ring holds fewer ``fit`` roots than the window had fits."""
    if "span_window" in ctx:
        return ctx["span_window"]
    ctx["span_window"] = None
    ring = _ring() if ring is None else ring
    if ring is None:
        _note(ctx, "host spans: the program keeps no span ring to read")
        return None
    fits = int(ctx["fits"])
    roots = [s for s in ring if s["name"] == FIT_ROOT and s.get("parent_id") is None
             and "root_id" in s][-fits:]
    if len(roots) < fits:
        _note(ctx, f"host spans: {len(roots)} '{FIT_ROOT}' roots in the ring for "
                   f"{fits} fits: nothing read")
        return None
    root_ids = {r["id"] for r in roots}
    spans = [s for s in ring if s.get("root_id") in root_ids]
    children = {}
    for s in spans:
        children.setdefault(s["parent_id"], []).append(s)
    self_ns, by_name, retrace_ns = {}, {}, 0
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
        if s["name"] in JAX_SPANS:
            continue  # counted under their parent, below
        lo, hi = _ends(s)
        kids = children.get(s["id"], [])
        own = s["dur_ns"] - _covered([_ends(k) for k in kids], lo, hi)
        self_ns[s["name"]] = self_ns.get(s["name"], 0) + own
        jax_kids = [k for k in kids if k["name"] in JAX_SPANS]
        retrace_ns += _covered([_ends(k) for k in jax_kids], lo, hi)
        for name in JAX_SPANS:
            covered = _covered([_ends(k) for k in jax_kids if k["name"] == name], lo, hi)
            if covered:
                self_ns[name] = self_ns.get(name, 0) + covered
    out = {"roots": roots, "self_ns": self_ns, "spans": by_name, "retrace_ns": retrace_ns,
           "names": {s["id"]: s["name"] for s in spans}}
    ctx["span_window"] = out
    _table_notes(ctx, out)
    return out


def _table_notes(ctx, w):
    fits = len(w["roots"])
    rows = sorted(w["self_ns"].items(), key=lambda r: -r[1])
    shown = ", ".join(f"{name} {ns / fits / 1e6:.1f}" for name, ns in rows[:TABLE_ROWS])
    rest = sum(ns for _n, ns in rows[TABLE_ROWS:]) / fits / 1e6
    root_s = sum(r["dur_ns"] for r in w["roots"]) / fits / 1e9
    _note(ctx, f"host spans, self ms a fit ('{FIT_ROOT}' is the root's unnamed "
               f"remainder): {shown}; others {rest:.1f}")
    if ctx.get("fit_s") is not None:
        _note(ctx, f"wait for the device after the fit returned: "
                   f"{ctx['fit_s'] - root_s:.3f} s a fit (fit_s {ctx['fit_s']:.3f} "
                   f"less the mean '{FIT_ROOT}' span {root_s:.3f})")
    # A fit's programs share a fun_name (six are jit(apply_batch)): the
    # span a compile lies under tells them apart.
    programs = {}
    for s in w["spans"].get("jax.compile", []):
        under = w["names"].get(s["parent_id"], "no span")
        row = programs.setdefault(f"{s['args'].get('fun_name', '?')} under {under}", [0, 0, 0, 0])
        row[0] += 1
        row[1] += s["args"].get("cache_hit") is True
        row[2] += s["args"].get("cache_hit") is False
        row[3] += s["dur_ns"]
    if programs:
        _note(ctx, "jax.compile in the window, by program: " + "; ".join(
            f"{name} x{n}: {hits} from the cache, {misses} compiled, {ns / 1e6:.1f} ms"
            for name, (n, hits, misses, ns) in
            sorted(programs.items(), key=lambda r: -r[1][3])))


def span_self_ms(ctx, name):
    """Self time a fit of the spans called ``name``, in ms."""
    w = window(ctx)
    if w is None or name not in w["self_ns"]:
        return None
    return w["self_ns"][name] / len(w["roots"]) / 1e6


def span_attr_gib(ctx, name, attr="bytes"):
    """The sum a fit of ``attr`` over the spans called ``name``, in GiB: a
    count, the same in every run."""
    w = window(ctx)
    if w is None or name not in w["spans"]:
        return None
    total = sum(int(s["args"].get(attr, 0)) for s in w["spans"][name])
    return total / len(w["roots"]) / 2**30


def retrace_ms(ctx):
    """Host time a fit inside ``jax.trace``, ``jax.lower`` and
    ``jax.compile`` (a backend compile or a load from the cache), in ms.
    A program that has the ``fit`` root has the compile listener too, so
    a fit that builds no program reads a true 0."""
    w = window(ctx)
    return None if w is None else w["retrace_ns"] / len(w["roots"]) / 1e6


def span_coverage(ctx):
    """The share of the ``fit`` roots' time that a span below them names,
    in percent: 100 x (1 - the roots' own self time over their durations)."""
    w = window(ctx)
    if w is None:
        return None
    total = sum(r["dur_ns"] for r in w["roots"])
    return 100.0 * (1.0 - w["self_ns"].get(FIT_ROOT, 0) / total) if total else None
