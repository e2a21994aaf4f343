"""Readers of the per-layer metrics. Each takes the run's context and
returns a number, or None where it finds nothing to read: the harness then
leaves the metric out of the line. None ever returns 0 for a share of a
roofline or of a peak. A metric's ``metrics/<name>.json`` names its reader
as ``module:function`` with the arguments it gets; a later PR that needs
another reader adds a module beside this one."""

from __future__ import annotations


def _device_seconds(ctx, modules=None, exclude_modules=None, run_has=None):
    """Device seconds a fit, averaged over the chips, of the traced
    operations that the filters keep: those of ``modules`` (HLO module
    names), not of ``exclude_modules``, and with ``run_has`` only those of
    program runs in which a custom call of that target ran."""
    trace = ctx.get("trace")
    if not trace or not trace["ops"]:
        return None
    total, seen = 0.0, False
    for module, _op, seconds, tags in trace["ops"]:
        if modules is not None and module not in modules:
            continue
        if exclude_modules is not None and module in exclude_modules:
            continue
        if run_has is not None and run_has not in tags:
            continue
        total, seen = total + seconds, True
    return total / ctx["fits"] if seen else None


def device_ms_per_fit(ctx, modules=None, exclude_modules=None, run_has=None):
    seconds = _device_seconds(ctx, modules, exclude_modules, run_has)
    return None if seconds is None else seconds * 1e3


def roofline(ctx, work, modules):
    """The least time the chips could take for ``work`` (the larger of
    FLOPs over peak FLOP/s and bytes over peak bytes/s) over the device
    time its modules took, in percent."""
    from work import roofline_seconds

    seconds = _device_seconds(ctx, modules=modules)
    if not seconds or ctx.get("peaks") is None:
        return None
    least, bound = roofline_seconds(
        ctx["flops"][work], ctx["bytes"][work], ctx["peaks"], ctx["chips"])
    ctx.setdefault("notes", []).append(f"{work}_roofline bound: {bound}")
    return 100.0 * least / seconds


def fit_mfu(ctx):
    """All of one fit's canonical FLOPs over the fit's wall time, against
    the bf16 peak whatever precision runs."""
    if ctx.get("peaks") is None:
        return None
    flops = sum(ctx["flops"].values())
    return 100.0 * flops / ctx["fit_s"] / (ctx["chips"] * ctx["peaks"]["bf16_tflops"] * 1e12)


def compiles_per_fit(ctx):
    return ctx["window_compiles"] / ctx["fits"]


def device_idle(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def hbm_peak_gib(ctx):
    peak = ctx.get("memory_peak_bytes")
    return peak / 2**30 if peak else None
