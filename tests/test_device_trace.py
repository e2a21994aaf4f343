"""``keystone_tpu.utils.device_trace``: the wire-format reader of a profiler
trace and its reduction by named scope. On bytes built here by hand, where
every number can be checked, and on one small trace recorded on a v5e with
the scopes in it (``tools/record_scoped_trace.py``; two traced fits, each
under a ``bench.fit`` host span)."""

import gzip
import json
import os
import struct

import pytest

from keystone_tpu.utils import device_trace as dt
from keystone_tpu.utils.metrics import DEVICE_SCOPES, STAGE_SCOPE_PREFIX

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "scoped-fit.xplane.pb.gz")


# --------------------------------------------------- an XSpace built by hand


def _varint(n: int) -> bytes:
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _int(field: int, n: int) -> bytes:
    return _varint(field << 3) + _varint(n)


def _bytes(field: int, payload) -> bytes:
    payload = payload.encode() if isinstance(payload, str) else payload
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _double(field: int, x: float) -> bytes:
    return _varint(field << 3 | 1) + struct.pack("<d", x)


STATS = {1: "tf_op", 2: "flops", 3: "bytes_accessed", 4: "program_id", 5: "hlo_category",
         6: "fusion", 7: "an_event_stat"}  # 6: a name a reference points at


def _metadata(key, name, tf_op=None, flops=None, nbytes=None, program=None, category_ref=None):
    stats = b""
    if tf_op is not None:
        stats += _bytes(5, _int(1, 1) + _bytes(5, tf_op))
    if flops is not None:
        stats += _bytes(5, _int(1, 2) + _int(3, flops))
    if nbytes is not None:
        stats += _bytes(5, _int(1, 3) + _double(2, float(nbytes)))
    if program is not None:
        stats += _bytes(5, _int(1, 4) + _int(3, program))
    if category_ref is not None:
        stats += _bytes(5, _int(1, 5) + _int(7, category_ref))
    return _bytes(4, _int(1, key) + _bytes(2, _int(1, key) + _bytes(2, name) + stats))


def _line(name, timestamp_ns, events):
    """``events``: [(metadata id, offset_ns, duration_ns)]."""
    body = _bytes(2, name) + _int(3, timestamp_ns)
    for key, offset, duration in events:
        # An event's own stat (7) is not its metadata's: never read as one.
        body += _bytes(4, _int(1, key) + _int(2, offset * 1000) + _int(3, duration * 1000)
                       + _bytes(4, _int(1, 7) + _int(4, -1)))
    return _bytes(3, body)


def _space():
    """Two programs that share the module name ``jit_local`` (10 and 20),
    the first a ``while`` with two operations in its body, the second one
    operation with no ``tf_op``; a chain of two stages; two fits."""
    stat_names = b"".join(_bytes(5, _int(1, k) + _bytes(2, _int(1, k) + _bytes(2, v)))
                          for k, v in STATS.items())
    meta = (
        _metadata(1, "%while.2 = (s32[]) while(%t)", flops=999, program=10)
        + _metadata(2, "%fusion.7 = f32[8] fusion(%b)",
                    tf_op="jit(local)/while/body/closed_call/solver.gram/dot_general:",
                    flops=4000, nbytes=64, program=10, category_ref=6)
        + _metadata(3, "%custom-call.3 = f32[8,8] custom-call(%x)",
                    tf_op="jit(local)/while/body/closed_call/jit(cholesky)/solver.cholesky/cholesky:",
                    flops=300, nbytes=32, program=10)
        + _metadata(4, "%copy.9 = f32[8] copy(%c)", nbytes=16, program=20)
        + _metadata(5, "%fusion.1 = f32[8] fusion(%a)",
                    tf_op="jit(apply_A_B)/node:A+C/jit(inner)/conv.kernel/pallas_call:",
                    flops=80, nbytes=8, program=30)
        + _metadata(6, "%fusion.2 = f32[8] fusion(%a)",
                    tf_op="jit(apply_A_B)/node:B/mul:", flops=8, nbytes=8, program=30)
        + _metadata(7, "%fusion.3 = f32[8] fusion(%a)", tf_op="jit(apply_A_B)/reshape:",
                    program=30)
        + _metadata(11, "jit_apply_A_B(11)") + _metadata(12, "jit_local(1)")
        + _metadata(13, "jit_local(2)")
    )
    t0 = 1_000_000
    modules = _line(dt.MODULES_LINE, t0, [(11, 10, 30), (12, 40, 40), (13, 120, 30)])
    ops = _line(dt.OPS_LINE, t0, [
        (5, 10, 10), (6, 20, 12), (7, 32, 8),        # the chain
        (1, 40, 40), (2, 45, 10), (3, 60, 10),       # while: 40 long, children 10 + 10
        (4, 120, 30),                                # jit_local(2): no tf_op
    ])
    steps = _line("Steps", t0, [(11, 0, 1)])  # a device line the reader leaves out
    device = _bytes(1, _int(1, 1) + _bytes(2, "/device:TPU:0") + modules + ops + steps
                    + meta + stat_names)
    host_meta = _metadata(1, "bench.fit") + _metadata(2, "ks:fit")
    host = _bytes(1, _int(1, 2) + _bytes(2, "/host:CPU") + host_meta + _line(
        "python3", t0, [(1, 0, 100), (2, 5, 50), (1, 100, 100)]))
    other = _bytes(1, _int(1, 3) + _bytes(2, "/host:python-tracer") + host_meta)
    skipped = _bytes(1, _int(1, 4) + _bytes(2, "Task Environment"))
    return device + host + other + skipped + _bytes(4, "a-hostname")


@pytest.fixture()
def hand_built(tmp_path):
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_space())
    return str(path)


def test_the_wire_reader_on_hand_built_bytes(hand_built):
    planes = {p["name"]: dict(p["lines"]) for p in dt.read(hand_built)}
    assert set(planes) == {"/device:TPU:0", "/host:CPU", "/host:python-tracer"}
    device = planes["/device:TPU:0"]
    assert set(device) == {dt.MODULES_LINE, dt.OPS_LINE}
    assert device[dt.MODULES_LINE][1] == ("jit_local(1)", 1_000_040, 1_000_080, None)
    by_name = {e[0].split(" = ")[0]: e for e in device[dt.OPS_LINE]}
    name, start, end, stats = by_name["%fusion.7"]
    assert (start, end) == (1_000_045, 1_000_055)
    # A string, an unsigned, a double and a reference to a stat's name.
    assert stats == {"tf_op": "jit(local)/while/body/closed_call/solver.gram/dot_general:",
                     "flops": 4000, "bytes_accessed": 64.0, "program_id": 10,
                     "hlo_category": "fusion"}
    # No tf_op in the metadata: none in the stats, and the event's own
    # stats are not its metadata's.
    assert by_name["%copy.9"][3] == {"bytes_accessed": 16.0, "program_id": 20}
    assert planes["/host:CPU"]["python3"][1] == ("ks:fit", 1_000_005, 1_000_055, None)


def test_a_gzipped_file_and_a_profiler_directory_read_the_same(hand_built, tmp_path):
    want = dt.read(hand_built)
    zipped = str(tmp_path / "hand.xplane.pb.gz")
    with open(hand_built, "rb") as f, gzip.open(zipped, "wb") as g:
        g.write(f.read())
    assert dt.read(zipped) == want
    run = tmp_path / "dir" / "plugins" / "profile" / "2026_10_04"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(open(hand_built, "rb").read())
    assert dt.read(str(tmp_path / "dir")) == want
    (run / "second.xplane.pb").write_bytes(b"")
    with pytest.raises(ValueError, match="found 2"):
        dt.read(str(tmp_path / "dir"))


def test_a_while_keeps_what_its_body_does_not_cover(hand_built):
    table = dt.by_scope(dt.read(hand_built))
    assert table["fits"] is None and table["window_s"] is None
    rows = {(r["module"], r["program_id"], r["scope"]): r for r in table["rows"]}
    # The while has no tf_op: its own 20 ns are (no op_name) of program 10,
    # and its flops are its body's, never counted twice.
    loop = rows[("jit_local", 10, dt.NO_OP_NAME)]
    assert loop["seconds"] == pytest.approx(20e-9) and loop["flops"] == 0
    gram = rows[("jit_local", 10, "solver.gram")]
    assert (gram["seconds"], gram["flops"], gram["bytes_accessed"]) == (
        pytest.approx(10e-9), 4000, 64)
    assert rows[("jit_local", 10, "solver.cholesky")]["seconds"] == pytest.approx(10e-9)
    assert sum(r["seconds"] for r in table["rows"]) == pytest.approx(100e-9)


def test_two_programs_of_one_module_name_are_told_apart(hand_built):
    table = dt.by_scope(dt.read(hand_built))
    by_program = {}
    for r in table["rows"]:
        if r["module"] == "jit_local":
            by_program[r["program_id"]] = by_program.get(r["program_id"], 0) + r["seconds"]
    assert by_program == {10: pytest.approx(40e-9), 20: pytest.approx(30e-9)}
    (local,) = [m for m in dt.by_module(table) if m["module"] == "jit_local"]
    assert local["seconds"] == pytest.approx(70e-9)
    # 50 of 70 ns have no op_name; of the 20 that have one, none is unscoped.
    assert local["no_op_name_share"] == pytest.approx(50 / 70)
    assert local["unscoped_share"] == 0.0


def test_a_chains_seconds_by_stage_and_by_the_scope_further_in(hand_built):
    table = dt.by_scope(dt.read(hand_built))
    (chain,) = [m for m in dt.by_module(table) if m["module"] == "jit_apply_A_B"]
    scopes = {s["scope"]: s["seconds"] for s in chain["scopes"]}
    assert scopes == {"conv.kernel": pytest.approx(10e-9), "node:B": pytest.approx(12e-9),
                      dt.UNSCOPED: pytest.approx(8e-9)}
    assert chain["stages"] == {"node:B": pytest.approx(12e-9), "node:A+C": pytest.approx(10e-9)}
    assert chain["unscoped_share"] == pytest.approx(8 / 30)


def test_a_fit_span_gives_seconds_a_fit(hand_built):
    table = dt.by_scope(dt.read(hand_built), fit_span="bench.fit")
    assert table["fits"] == 2 and table["window_s"] == pytest.approx(200e-9)
    assert sum(r["seconds"] for r in table["rows"]) == pytest.approx(50e-9)
    # Only what runs between the first span's start and the last one's end.
    late = dt.by_scope(dt.read(hand_built), fit_span="ks:fit")
    assert late["fits"] == 1
    assert {r["scope"] for r in late["rows"]} == {
        "conv.kernel", "node:B", dt.UNSCOPED, "solver.gram", dt.NO_OP_NAME}
    with pytest.raises(ValueError, match="no 'absent' span"):
        dt.by_scope(dt.read(hand_built), fit_span="absent")
    with pytest.raises(ValueError, match="chip 3"):
        dt.by_scope(dt.read(hand_built), chip=3)


def test_the_innermost_listed_segment_is_the_scope():
    assert dt.scope_of(None) == dt.NO_OP_NAME and dt.scope_of("") == dt.NO_OP_NAME
    assert dt.scope_of("jit(local)/dot_general:") == dt.UNSCOPED
    assert dt.scope_of("a:") == dt.UNSCOPED  # a layout copy of the argument ``a``
    path = "jit(local)/solver.update/while/body/closed_call/solver.gram/dot_general:"
    assert dt.scope_of(path) == "solver.gram"
    # A segment that only looks like a scope is none.
    assert dt.scope_of("jit(local)/solver.gramophone/add:") == dt.UNSCOPED
    staged = f"jit(apply_X)/{STAGE_SCOPE_PREFIX}Convolver+Pooler/jit(f)/conv.patches/pad:"
    assert dt.scope_of(staged) == "conv.patches"
    assert dt.stage_of(staged) == STAGE_SCOPE_PREFIX + "Convolver+Pooler"
    assert dt.stage_of(path) is None
    assert len(set(DEVICE_SCOPES)) == len(DEVICE_SCOPES)
    assert not any(" " in s or "/" in s or ":" in s for s in DEVICE_SCOPES)


def test_the_command_prints_the_table_and_the_json(hand_built, capsys):
    assert dt.main([hand_built, "--fit-span", "bench.fit"]) == 0
    text = capsys.readouterr().out
    assert "device seconds a fit by module and scope (2 fits" in text
    lines = text.splitlines()
    first = next(i for i, line in enumerate(lines) if "jit_local" in line)
    assert "no op_name 71.4 %" in lines[first]
    assert "solver.gram" in text and "under node:A+C, scopes further in included" in text
    assert dt.main([hand_built, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert {m["module"] for m in doc["modules"]} == {"jit_local", "jit_apply_A_B"}
    assert doc["fits"] is None and len(doc["rows"]) == 7


# ------------------------------------------------- the trace recorded on a v5e


@pytest.fixture(scope="module")
def recorded():
    return dt.read(RECORDED)


def test_the_recorded_trace_holds_every_scope_with_time_of_its_own(recorded):
    table = dt.by_scope(recorded, fit_span="bench.fit")
    assert table["fits"] == 2
    seconds = {}
    for r in table["rows"]:
        seconds[r["scope"]] = seconds.get(r["scope"], 0.0) + r["seconds"]
    # At these widths XLA folds the pooled sums' cut and flattening
    # (``conv.relayout``) into a neighbour; every other scope runs alone.
    # And on the one chip that recorded it nothing crosses a mesh: the
    # ``coll.`` scopes name nothing there (``tests/test_imagenet_mesh.py``
    # holds them in the mesh's compiled programs). The recording predates
    # the patch cut's scopes (``filters.``) and the generated-block solve's
    # (``solver.generate``, ``solver.fetch``), which
    # ``tools/record_scoped_trace.py`` now runs: recorded again on a chip,
    # they join the rest; ``tests/test_aot_tpu.py`` and
    # ``tests/test_generated_blocks.py`` hold them meanwhile.
    newer = ("solver.generate", "solver.fetch")
    for scope in DEVICE_SCOPES:
        if (scope != "conv.relayout" and not scope.startswith("coll.")
                and not scope.startswith("filters.") and scope not in newer):
            assert seconds.get(scope, 0.0) > 0.0, scope
    assert not [scope for scope in seconds if scope.startswith("coll.")]
    stages = {r["stage"] for r in table["rows"] if r["stage"]}
    assert STAGE_SCOPE_PREFIX + "Convolver+SymmetricRectifier+Pooler" in stages
    # The solver's three programs and the kernel solver's share a module
    # name and differ in program_id.
    local = {r["program_id"] for r in table["rows"] if r["module"] == "jit_local"}
    assert len(local) >= 4
    for m in dt.by_module(table):
        if m["module"] == "jit_local":
            assert m["unscoped_share"] < 0.5


def test_the_scopes_of_a_module_sum_to_the_modules_seconds(recorded):
    """Against a sum made another way: the union of the operations'
    intervals inside the module's runs (operations nest and never overlap
    otherwise, so self times add up to it, to a nanosecond where the
    truncated endpoints of a parent and its child cross)."""
    table = dt.by_scope(recorded)
    (plane,) = [p for p in recorded if dt.DEVICE_PLANE.match(p["name"])]
    lines = dict(plane["lines"])
    want = {}
    for name, s, e, _stats in lines[dt.MODULES_LINE]:
        inside = sorted((a, b) for _n, a, b, _st in lines[dt.OPS_LINE] if s <= a < e)
        covered, upto = 0, s
        for a, b in inside:
            covered += max(0, b - max(a, upto))
            upto = max(upto, b)
        module = dt.module_name(name)
        want[module] = want.get(module, 0) + covered
    got = {m["module"]: m["seconds"] for m in dt.by_module(table)}
    assert set(got) - {"other"} <= set(want) and "jit_local" in got
    for module, ns in want.items():  # a run that holds no operation: 0 on both sides
        assert got.get(module, 0.0) == pytest.approx(ns / 1e9, rel=1e-4), module


def test_profile_data_sees_the_same_events_and_no_metadata_stats(recorded, tmp_path):
    """What ``jax.profiler.ProfileData`` shows of the same file: the same
    names and times, and of ``tf_op`` nothing, which is why this reader
    exists."""
    from jax.profiler import ProfileData

    raw = str(tmp_path / "scoped.xplane.pb")
    with gzip.open(RECORDED, "rb") as f, open(raw, "wb") as g:
        g.write(f.read())
    (plane,) = [p for p in ProfileData.from_file(raw).planes
                if dt.DEVICE_PLANE.match(p.name)]
    (ops,) = [line for line in plane.lines if line.name == dt.OPS_LINE]
    theirs, stats_seen = [], set()
    for ev in ops.events:
        start = int(ev.start_ns)
        theirs.append((ev.name, start, start + int(ev.duration_ns)))
        stats_seen.update(k for k, _v in ev.stats)  # the event's own
    (mine,) = [dict(p["lines"])[dt.OPS_LINE] for p in recorded
               if dt.DEVICE_PLANE.match(p["name"])]
    assert sorted(theirs) == sorted(e[:3] for e in mine)
    assert "tf_op" not in stats_seen
    assert sum(1 for e in mine if e[3].get("tf_op")) > len(mine) // 2
