"""One run of one cell: set-up, the measured window, the comparison with
the plain reference, the result line.

Everything that belongs to one configuration, one cell or one per-layer
metric is a file of its own, found by the name in ``BENCHMARK.json``:
``configs/<configuration>.json`` with its adapter beside it,
``workloads/<cell>.json``, ``metrics/<metric>.json``. Adding one of them
adds files and an entry, and edits nothing here.
"""

from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(HERE, ".trace")


class Refused(Exception):
    """The run cannot be made here (no chip, too few chips, unknown cell).
    ``run.py`` exits non-zero on it and prints no result."""


def _load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_adapter(file_name: str):
    """The configuration's adapter module, loaded by its file name."""
    path = os.path.join(HERE, "configs", file_name)
    spec = importlib.util.spec_from_file_location(
        "benchmark_adapter_" + file_name.replace("-", "_").replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(cell: str, overrides: dict | None = None) -> dict:
    """Everything one cell's run needs, from the files named after it.
    ``overrides`` ({"sizes": ..., "limits": ...}) is for tests, which run
    tiny widths on the CPU through it."""
    bench = _load_json(ROOT, "BENCHMARK.json")
    if not os.path.exists(os.path.join(HERE, "workloads", cell + ".json")):
        raise Refused(f"no workload file for {cell!r}")
    workload = _load_json(HERE, "workloads", cell + ".json")
    listed = [w for w in bench["workloads"] if w["name"] == cell]
    if not listed:
        raise Refused(f"BENCHMARK.json lists no cell {cell!r}")
    entry = listed[0]
    config = _load_json(HERE, "configs", entry["config"] + ".json")
    sizes = dict(config["sizes"])
    sizes.update(workload.get("sizes", {}))
    sizes.update((overrides or {}).get("sizes", {}))
    limits = dict(config.get("limits") or {})
    limits.update((overrides or {}).get("limits", {}))

    def in_cell(metric):
        return cell in metric.get("workloads", [cell])

    return {
        "name": cell,
        "chips": int(entry["chips"]),
        "config": config,
        "workload": workload,
        "sizes": sizes,
        "limits": limits,
        "adapter": load_adapter(config["adapter"]),
        "end_to_end": [m for m in bench["end_to_end"] if in_cell(m)],
        "per_layer": [m for m in bench["per_layer"] if in_cell(m)],
    }


def read_metric(name: str, ctx: dict):
    """The value of one per-layer metric, by its own reader file, or None
    where the reader finds nothing to read."""
    spec = _load_json(HERE, "metrics", name + ".json")
    module, _, function = spec["reducer"].partition(":")
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    return getattr(importlib.import_module(module), function)(ctx, **spec.get("args", {}))


# ------------------------------------------------------------ comparison


def compare(answers: dict, reference: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}} for every number the configuration sets a
    limit on. ``<key>_gap`` is the norm of the difference between the timed
    path's ``key`` and the reference's over the norm of the reference's
    (root of the sum of squares over all entries): steady from seed to
    seed, where a widest gap swings."""
    import numpy as np

    out = {}
    for name, limit in limits.items():
        key, _, kind = name.rpartition("_")
        if kind != "gap":
            raise AssertionError(f"{name}: a limit is on <key>_gap")
        got = np.asarray(answers[key], np.float64)
        want = np.asarray(reference[key], np.float64)
        if got.shape != want.shape:
            raise AssertionError(f"{key}: shape {got.shape}, reference {want.shape}")
        gap = np.linalg.norm(got - want) / np.linalg.norm(want)
        # A non-finite answer compares as infinitely far off.
        out[name] = {"value": float(gap) if np.isfinite(got).all() else float("inf"),
                     "limit": float(limit)}
    return out


def facts_gap(facts: dict, expected: dict) -> int:
    """How many of the run's widths differ from what the configuration
    states (block size resolved, feature width, classes): limit 0."""
    return sum(1 for k, v in expected.items() if facts.get(k) != v)


# ------------------------------------------------------------------ run


def _log(*a):
    print(*a, file=sys.stderr, flush=True)


def prepare(cell: str, *, need_tpu: bool = True, overrides: dict | None = None, log=_log):
    """(cell's files, device, compile counter) with the process set up as
    every run has it: no stored fits, the compile cache inside the
    checkout. The program's precision is left as the program ships it."""
    spec = load_cell(cell, overrides)
    # No stored fit may be served from disk (chip_smoke.main does the same).
    os.environ["KEYSTONE_CACHE_DIR"] = ""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax

    from keystone_tpu.utils.metrics import CompileEventCounter
    from keystone_tpu.utils.platform import device_info, setup_compile_cache

    cache_dir = None
    if need_tpu:
        # The compile cache lies inside the checkout at a fixed path (the
        # path is part of every key), whatever JAX_COMPILATION_CACHE_DIR
        # said: two checkouts then share nothing. Only the directory is
        # forced. What is stored there and what is evicted stays as the
        # program's setup_compile_cache(), JAX and the environment have it,
        # so a fit's re-traced programs cost here what they cost a user.
        cache_dir = os.path.join(ROOT, ".xla_compile_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        setup_compile_cache()
    try:
        device = device_info(need_tpu=need_tpu)
    except RuntimeError as e:
        raise Refused(str(e)) from e
    if device["count"] < spec["chips"]:
        raise Refused(f"cell {cell} needs {spec['chips']} chips, JAX offers {device['count']}")
    log(f"bench: {device['count']} x {device['kind']}, compile cache {cache_dir}")
    return spec, device, CompileEventCounter()


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             need_tpu: bool = True, overrides: dict | None = None,
             t_start: float | None = None, log=None,
             keep_trace: str | None = None) -> dict:
    """Run one cell once and return the result object of its last line.

    ``need_tpu=False`` skips the look for a chip and nothing else: tests
    drive the rest of a run through it at tiny widths. ``keep_trace``
    copies the traced run's ``.xplane.pb`` there before it is deleted."""
    t_start = time.time() if t_start is None else t_start
    log = log or _log
    spec, device, compiles = prepare(cell, need_tpu=need_tpu, overrides=overrides, log=log)
    adapter, sizes = spec["adapter"], spec["sizes"]
    precision = spec["config"]["precision"]["reference"]
    import jax

    log(f"bench: {cell} seed {seed}")
    data = adapter.make_data(seed, sizes)
    fitted = adapter.fit(data, sizes)  # warm-up: compiles or loads every shape
    del fitted
    warm_compiles, warm_hits = compiles.count, compiles.hits
    setup_s = time.time() - t_start
    log(f"bench: set-up {setup_s:.1f} s, {warm_compiles} compile requests, "
        f"{warm_hits} from the cache")

    traced_fits = int(spec["workload"].get("traced_fits", 2))
    annotate = jax.profiler.TraceAnnotation
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    fits, fit_ends = 0, []
    t0 = time.perf_counter()
    try:
        # Whole fits back to back; the fit in flight at the end is finished.
        # A traced window is a fixed number of fits instead: a trace of the
        # whole window would be most of the run's time to read.
        while (fits < traced_fits) if trace else (time.perf_counter() - t0 < seconds):
            with annotate("bench.fit", fit=fits):
                fitted = adapter.fit(data, sizes)
            fits += 1
            fit_ends.append(time.perf_counter() - t0)
    finally:
        window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
    window_compiles = compiles.count - warm_compiles
    window_hits = compiles.hits - warm_hits

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    memory_peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    fit_s = window_s / fits
    log(f"bench: {fits} fits in {window_s:.3f} s, {window_compiles} compile requests in "
        f"the window, {window_hits} from the cache, peak {memory_peak} bytes")

    # What the last timed fit produced, then the program's state is freed
    # and the reference runs where the peak has already been read.
    t_ref = time.time()
    answers = adapter.answers(fitted, data, sizes)
    del fitted
    gc.collect()
    log(f"bench: the fit's answers {time.time() - t_ref:.1f} s")
    t_ref = time.time()
    reference = adapter.reference(data, sizes, answers, precision)
    # What only the reference's own data can read off the fitted tables.
    answers.update(reference.get("measured", {}))
    checks = compare(answers, reference, spec["limits"])
    checks["widths_off"] = {
        "value": facts_gap(answers["facts"], adapter.expected_facts(sizes)), "limit": 0}
    log(f"bench: reference and comparison {time.time() - t_ref:.1f} s")
    del reference, answers
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    # The device as JAX reports it: the program's mesh takes every chip JAX
    # offers, so that is what ran, whatever the cell asked for.
    device_out = dict(device, memory_peak_bytes=int(memory_peak))
    result = {"correct": bool(correct), "attempted": fits, "failed": 0}
    if not trace:
        values = {"setup_s": setup_s, "fit_s": fit_s}
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in spec["end_to_end"]}
    else:
        import tracereduce as reduction
        import work

        summary = reduction.reduce_dir(TRACE_DIR, chips=device["count"])
        if keep_trace:
            shutil.copy(reduction.find_xplane(TRACE_DIR), keep_trace)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = {
            "trace": summary, "fits": fits, "fit_s": fit_s, "chips": device["count"],
            "flops": adapter.flops(sizes, work), "bytes": adapter.bytes_moved(sizes, work),
            "peaks": work.chip_peaks(device["kind"]) if need_tpu else None,
            "window_compiles": window_compiles, "memory_peak_bytes": memory_peak,
            "notes": summary["notes"],
        }
        result["metrics"] = {}
        for m in spec["per_layer"]:
            value = read_metric(m["name"], ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        device_out["busy_s"] = summary["busy_s"]
        device_out["window_s"] = summary["window_s"]
        result["breakdown"] = summary["breakdown"]
        # Every fit has to do the whole work: read these side by side.
        result["device_s_by_fit"] = [sum(f.values()) for f in summary["fit_modules_s"]]
        result["notes"] = summary["notes"]
    result["device"] = device_out
    result["fits"] = {"count": fits, "ends_s": fit_ends, "setup_compiles": warm_compiles,
                      "setup_cache_hits": warm_hits, "window_compiles": window_compiles,
                      "window_cache_hits": window_hits}
    result["compared"] = checks  # comes last: each number beside its limit
    for name, c in checks.items():
        log(f"compared {name}: {c['value']:.6g} (limit {c['limit']:.6g})")
    log(f"correct: {correct}")
    return result
