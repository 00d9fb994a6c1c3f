"""The program's side of a trace: spans and counters in the benchmark's
window, device self time by named scope from the ops' ``tf_op`` metadata,
idle time inside a span by interval overlap (against ``bench/reduce.py``'s
midpoint rule), one parse per run, and readers that find nothing in a
trace without the program's spans."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

TESTS = Path(__file__).resolve().parent
sys.path.insert(0, str(TESTS))
sys.path.insert(0, str(TESTS.parent))

import benchcopy  # noqa: E402
import program_trace  # noqa: E402
import reduce  # noqa: E402
from harness import Spans  # noqa: E402

NEW_METRICS = {
    "replan.node-failure": ("solve_host_ms.replan", "solve_wait_ms.replan",
                            "solver_trips.replan", "solver_us_per_trip.replan",
                            "loop_ms.replan", "replan_idle_share.replan"),
    "codec.degraded-read": ("to_host_gb_per_s.codec", "decode_wait_ms.codec"),
    "fleet.nj-client": ("draw_ns_per_req.fleet", "sketch_ns_per_req.fleet"),
}


# --- an XSpace written by hand (protobuf wire format) ---------------------


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        low, n = n & 0x7F, n >> 7
        out.append(low | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(field: int, v: int) -> bytes:
    return _varint(field << 3) + _varint(v)


def _msg(field: int, body) -> bytes:
    body = body.encode() if isinstance(body, str) else body
    return _varint(field << 3 | 2) + _varint(len(body)) + body


def _stat(sid: int, v) -> bytes:
    return _int(1, sid) + (_int(4, v) if isinstance(v, int) else _msg(5, v))


def _plane(name, lines, metadata, stat_names=()):
    """``lines``: [(line name, [(metadata id, start ns, duration ns,
    [(stat id, value)])])]; ``metadata``: {id: (name, [(stat id, value)])}."""
    out = _int(1, 1) + _msg(2, name)
    for i, (lname, events) in enumerate(lines):
        body = _int(1, i) + _msg(2, lname) + _int(3, 0)
        for mid, start, dur, stats in events:
            ev = _int(1, mid) + _int(2, int(start * 1000)) + _int(3, int(dur * 1000))
            ev += b"".join(_msg(4, _stat(s, v)) for s, v in stats)
            body += _msg(4, ev)
        out += _msg(3, body)
    for mid, (mname, stats) in metadata.items():
        meta = _int(1, mid) + _msg(2, mname)
        meta += b"".join(_msg(5, _stat(s, v)) for s, v in stats)
        out += _msg(4, _int(1, mid) + _msg(2, meta))
    for sid, sname in dict(stat_names).items():
        out += _msg(5, _int(1, sid) + _msg(2, _int(1, sid) + _msg(2, sname)))
    return out


VALUE, BYTES, PID, TF_OP = 1, 2, 3, 4
WHILE = "%while.1 = (f32[]) while((f32[]) %t)"
ADD = "%add.2 = f32[] add(f32[] %a, f32[] %b)"
MUL = "%mul.3 = f32[] multiply(f32[] %a, f32[] %b)"
SUB = "%sub.4 = f32[] subtract(f32[] %a, f32[] %b)"


def xspace() -> bytes:
    """Window 0..1000 ns (benchmark span ``window``); device programs at
    0..100 and 300..400 and 600..1000, so idle 100..300 and 400..600.
    Program spans ``a.x`` 50..200 and ``b.y`` 200..350 split the first gap;
    ``late.z`` lies after the window. A while loop of the first program
    (0..100) holds two body ops under ``jlcm.iterate``, one of them inside
    a transform, and the same add runs in the second program's run of it;
    the third program's one op is under ``jlcm.finalize``."""
    host = _plane("/host:CPU", [("python", [
        (1, 0, 1000, []),
        (2, 50, 150, []),
        (3, 200, 150, []),
        (4, 250, 1, [(VALUE, 7)]),
        (4, 700, 1, [(VALUE, 9)]),
        (5, 420, 60, [(BYTES, 3_000)]),
        (6, 1200, 10, []),
        (7, 300, 10, []),
    ])], {1: ("window", []), 2: ("a.x", []), 3: ("b.y", []),
          4: ("solver.trips", []), 5: ("codec.to_host", []), 6: ("late.z", []),
          7: ("copy.215", [])},
        {VALUE: "value", BYTES: "bytes"})
    device = _plane("/device:TPU:0", [
        ("XLA Modules", [(1, 0, 100, []), (1, 300, 100, []), (2, 600, 400, [])]),
        ("XLA Ops", [(10, 0, 100, []), (11, 10, 30, []), (12, 50, 20, []),
                     (11, 300, 40, []), (13, 600, 400, [])]),
    ], {1: ("jit_solve(77)", []), 2: ("jit_final(88)", []),
        10: (WHILE, [(PID, 77), (TF_OP, "jit(solve)/while")]),
        11: (ADD, [(PID, 77), (TF_OP, "jit(solve)/while/body/jlcm.iterate/add:")]),
        12: (MUL, [(PID, 77), (TF_OP, "jit(solve)/while/body/transpose(jvp("
                               "jlcm.iterate))/mul:")]),
        13: (SUB, [(PID, 88), (TF_OP, "jit(final)/jlcm.finalize/sub:")])},
        {PID: "program_id", TF_OP: "tf_op"})
    return _msg(1, _plane("/host:metadata", [], {})) + _msg(1, host) + _msg(1, device)


@pytest.fixture
def trace_dir(tmp_path):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(xspace())
    return tmp_path


def _read(trace_dir):
    return program_trace.load_dir(trace_dir, {"window"})


def test_spans_and_counters_in_the_window(trace_dir):
    t = _read(trace_dir)
    assert t.count("a.x") == t.count("b.y") == 1
    assert t.count("late.z") == 0  # ends after the benchmark's last span
    assert set(t.spans) == {"a.x", "b.y", "solver.trips", "codec.to_host"}  # no HLO name
    assert t.mean_s("a.x") == pytest.approx(150e-9)
    assert t.mean_s("nothing") is None
    assert t.values("solver.trips") == [7.0, 9.0]
    assert sum(t.values("solver.trips")) / t.count("solver.trips") == 8.0
    assert t.values("codec.to_host", "bytes") == [3000.0]
    assert t.values("a.x") == []


def test_scopes_group_device_self_time(trace_dir):
    t = _read(trace_dir)
    # the body ops' self times (30 + 20 + 40), transform peeled; the while
    # op's own 50 ns lies under no scope
    assert t.scope_seconds("jlcm.iterate") == pytest.approx(90e-9)
    assert t.scope_seconds("jlcm.finalize") == pytest.approx(400e-9)
    assert t.scope_seconds("fleet.inputs") == 0.0
    assert program_trace.scopes_of(
        "jit(f)/while/body/transpose(jvp(jlcm.iterate))/vmap(fleet.stats)/mul:"
    ) == {"jlcm.iterate", "fleet.stats"}


def test_idle_is_split_by_overlap_not_by_midpoint(trace_dir):
    from jax.profiler import ProfileData

    t = _read(trace_dir)
    assert t.idle_s() == pytest.approx(400e-9)
    # the gap 100..300 straddles a.x (50..200) and b.y (200..350): half each
    assert t.idle_within_s("a.x") == pytest.approx(100e-9)
    assert t.idle_within_s("b.y") == pytest.approx(100e-9)
    assert t.idle_within_s("window") == 0.0  # not a program span
    # the midpoint rule gives the whole gap to one of them
    path = reduce.find_xplane(trace_dir)
    r = reduce.reduce_profile(ProfileData.from_file(str(path)),
                              host_names={"window", "a.x", "b.y"})
    assert sorted(v for k, v in r.idle_by_host.items() if k in ("a.x", "b.y")) \
        == pytest.approx([200e-9])


def test_a_device_trace_that_stops_early(tmp_path):
    """Programs recorded up to 400 ns of a 1000 ns window: the idle gaps
    are those of the covered stretch (the lost 400..1000 is not idle), an
    operation after the last recorded program counts under no scope, and
    a span whose program was lost is told from one whose was kept."""
    host = _plane("/host:CPU", [("python", [
        (1, 0, 1000, []), (2, 20, 60, []), (2, 700, 60, [])])],
        {1: ("window", []), 2: ("replan.solve", [])})
    device = _plane("/device:TPU:0", [
        ("XLA Modules", [(1, 0, 100, []), (1, 300, 100, [])]),
        ("XLA Ops", [(11, 10, 30, []), (11, 700, 30, [])]),
    ], {1: ("jit_solve(77)", []),
        11: (ADD, [(PID, 77), (TF_OP, "jit(solve)/while/body/jlcm.iterate/add:")])},
        {PID: "program_id", TF_OP: "tf_op"})
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "t.xplane.pb").write_bytes(_msg(1, host) + _msg(1, device))
    t = _read(tmp_path)
    assert t.idle_s() == pytest.approx(200e-9)
    assert t.scope_seconds("jlcm.iterate") == pytest.approx(30e-9)
    assert t.modules == {"jit_solve": [(0.0, 100.0), (300.0, 400.0)]}
    assert t.recorded("replan.solve", "jit_solve") == [True, False]
    assert t.recorded("replan.solve", "jit_other") == [False, False]


def test_merge_and_overlap():
    assert program_trace.merge([(5, 8), (0, 2), (1, 3), (8, 9)]) == [(0, 3), (5, 9)]
    assert program_trace.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10


def test_one_parse_per_run(trace_dir, monkeypatch):
    from jax.profiler import ProfileData

    calls = []
    real = ProfileData.from_serialized_xspace

    class Counting:
        @staticmethod
        def from_serialized_xspace(data):
            calls.append(1)
            return real(data)

    monkeypatch.setattr("jax.profiler.ProfileData", Counting)
    program_trace._CACHE.clear()
    first = _read(trace_dir)
    assert _read(trace_dir) is first
    assert len(calls) == 1


def _run(**kw):
    return NS(trace=True, reduced=object(), spans=Spans(), counters={}, **kw)


def _metrics():
    manifest = json.loads((benchcopy.ROOT / "BENCHMARK.json").read_text())
    return {m["name"]: m for m in manifest["per_layer"]}


def test_new_metrics_are_listed_for_their_cell():
    manifest = _metrics()
    for cell, names in NEW_METRICS.items():
        for name in names:
            m = manifest[name]
            assert m["workloads"] == [cell]
            assert m["source"] in ("program_span", "program_counter")
            assert (benchcopy.BENCH / "metrics" / f"{name}.py").is_file()


@pytest.mark.parametrize("name", [n for ns in NEW_METRICS.values() for n in ns])
def test_readers_find_nothing_without_the_programs_spans(name, monkeypatch):
    """A trace of a program that writes no spans (one from before they
    existed) gives no value and raises nothing."""
    import run as run_module

    empty = program_trace.ProgramTrace(spans={}, stats={}, scope_s={},
                                       idle=[[(0.0, 100.0)]])
    monkeypatch.setattr(program_trace, "load", lambda run: empty)
    run = _run()
    run.counters["attempted"] = 10
    run.spans.add("decode", 0.1)
    assert run_module.metric_reader(name).read(run) is None


def test_readers_read_the_programs_spans(monkeypatch):
    import run as run_module

    ms = 1e6  # ns
    t = program_trace.ProgramTrace(
        spans={"replan.step": [(0, 10 * ms), (20 * ms, 30 * ms)],
               "replan.solve": [(1 * ms, 6 * ms), (21 * ms, 26 * ms)],
               "replan.solve_wait": [(4 * ms, 6 * ms), (24 * ms, 26 * ms)],
               "solver.trips": [(5 * ms, 5 * ms), (25 * ms, 25 * ms)],
               "loop.simulate": [(10 * ms, 14 * ms)],
               "loop.observe": [(14 * ms, 15 * ms)],
               "codec.to_host": [(0, 500 * ms)],
               "codec.wait": [(0, 4 * ms), (10 * ms, 12 * ms)]},
        stats={"solver.trips": [{"value": 4}, {"value": 6}],
               "codec.to_host": [{"bytes": 10**9}]},
        scope_s={"jlcm.iterate": 1e-3, "fleet.inputs": 2e-6, "fleet.stats": 1e-6},
        idle=[[(2 * ms, 6 * ms), (12 * ms, 20 * ms)]],
        modules={"jit__solve_merged_device_batch": [(2 * ms, 5 * ms), (22 * ms, 25 * ms)]})
    monkeypatch.setattr(program_trace, "load", lambda run: t)
    run = _run()
    run.counters["attempted"] = 1000
    run.spans.add("decode", 0.1)
    run.spans.add("decode", 0.1)
    got = {name: run_module.metric_reader(name).read(run)
           for names in NEW_METRICS.values() for name in names}
    assert got == pytest.approx({
        "solve_host_ms.replan": 3.0, "solve_wait_ms.replan": 2.0,
        "solver_trips.replan": 5.0, "solver_us_per_trip.replan": 100.0,
        "loop_ms.replan": 5.0, "replan_idle_share.replan": 100.0 * 4 / 12,
        "to_host_gb_per_s.codec": 2.0, "decode_wait_ms.codec": 3.0,
        "draw_ns_per_req.fleet": 2.0, "sketch_ns_per_req.fleet": 1.0,
    })
    # the trace lost the second solve's program: its 6 trips do not count
    t.modules["jit__solve_merged_device_batch"] = [(2 * ms, 5 * ms)]
    got = run_module.metric_reader("solver_us_per_trip.replan").read(run)
    assert got == pytest.approx(1e-3 / 4 * 1e6)


def test_load_needs_a_traced_run():
    assert program_trace.load(NS(trace=False, reduced=None)) is None


@pytest.mark.parametrize("cell", ["replan.node-failure", "codec.degraded-read"])
def test_traced_run_reports_the_program_spans(cell, tmp_path, monkeypatch):
    """A ``--trace 1`` run of a CPU-sized copy prints every new metric
    that reads host spans (a CPU trace has no device plane, so the scope
    and idle readers give nothing), and the solve's two parts add up to
    the program's own solve wall."""
    root = benchcopy.small_copy(tmp_path)
    monkeypatch.setattr(program_trace, "TRACE_DIR", root / ".bench_trace")
    res, err = benchcopy.run_cell(root, cell, trace=1)
    assert res["correct"], err
    got = {k: v["value"] for k, v in res["metrics"].items()}
    host_side = {n for n in NEW_METRICS[cell]
                 if n not in ("solver_us_per_trip.replan", "replan_idle_share.replan")}
    assert host_side <= set(got)
    assert all(got[n] > 0 for n in host_side)
    if cell == "replan.node-failure":
        parts = got["solve_host_ms.replan"] + got["solve_wait_ms.replan"]
        assert parts == pytest.approx(got["solve_ms.replan"], rel=0.01)
        assert got["solver_trips.replan"] >= got["solver_iters.replan"]
