"""Readers of the collectives' per-layer metrics, beside ``reducers.py``:
what a fit on a mesh spends in exchanges across its chips. Against a trace
of one chip, a program whose ``fit`` root counts no collective bytes, or an
adapter that counts none, each returns None: never 0, never a guess."""

from __future__ import annotations

import re

# An HLO collective by its result's name, sync or in its async halves
# (``all-reduce.3``, ``collective-permute-start.1``, ``all-gather-done``).
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?(\.\d+)*$")


def is_collective(op: str) -> bool:
    """Is ``op`` (``tracereduce.op_name`` of a traced operation) one of the
    collectives? A fusion that merely feeds one is not."""
    return bool(_COLLECTIVE.match(op.split("[", 1)[0]))


def _exposed_seconds(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["ops"]:
        return None
    found = [seconds for _module, op, seconds, _tags in trace["ops"] if is_collective(op)]
    return sum(found) / ctx["fits"] if found else None


def exposed_ms(ctx):
    """Device time a fit, averaged over the chips, inside collective
    operations: the exchange itself where it is synchronous, the waits of
    its ``-start`` and ``-done`` halves where it is not (what overlaps with
    compute lies in no operation of theirs)."""
    seconds = _exposed_seconds(ctx)
    return None if seconds is None else seconds * 1e3


def roofline(ctx):
    """The least time the ICI could take for the fit's reductions (the
    adapter's ``collective_least_s``) over the time the chips spent in
    collectives, in percent."""
    seconds = _exposed_seconds(ctx)
    least = (ctx.get("bytes") or {}).get("collective_least_s")
    if not seconds or not least:
        return None
    return 100.0 * least / seconds


def counted_gib(ctx):
    """The ``fit`` roots' ``collective_bytes`` a fit, in GiB: what the
    program's reductions across the mesh were handed, counted from shapes
    on the host."""
    from spanreaders import FIT_ROOT, window

    w = window(ctx)
    if w is None:
        return None
    counted = [r["args"]["collective_bytes"] for r in w["roots"]
               if "collective_bytes" in r.get("args", {})]
    if not counted:
        ctx.setdefault("notes", []).append(
            f"collective_gib: no '{FIT_ROOT}' root carries collective_bytes")
        return None
    # On one device nothing crosses: the count is 0 there, and no reading.
    return sum(counted) / len(w["roots"]) / 2**30 if sum(counted) else None
