"""A reader of counts that the program's spans carry, beside
``spanreaders.py`` (whose ``span_attr_gib`` reads bytes as GiB): the sum a
fit of one attribute, as it stands. Against a program without the span it
returns None, as every reader does."""

from __future__ import annotations

from spanreaders import window


def span_attr_sum(ctx, name, attr):
    """The sum a fit of ``attr`` over the spans called ``name``: a count,
    the same in every run."""
    w = window(ctx)
    if w is None or name not in w["spans"]:
        return None
    total = sum(s["args"].get(attr, 0) for s in w["spans"][name])
    return total / len(w["roots"])
