"""The rack cell (``replan.f4-rack-failure``) at CPU sizes, in a copy of the
benchmark with the look for a chip skipped: its check passes on what the
program's timed path produced and fails with each planted fault of
``rack_faults.py`` (two chunks of a stripe in one rack, a rack over its
cap, mass on the lost rack) and of the closed loop (a plan left unchanged
through the failure, the worse candidate deployed); its bfloat16 control
fails the comparison; the new readers stay silent on a trace without the
program's rack scope; and the benchmark's rack reference agrees with
brute force on tiny inputs."""
import itertools
import sys
from pathlib import Path

import numpy as np
import pytest

TESTS = Path(__file__).resolve().parent
sys.path.insert(0, str(TESTS))
sys.path.insert(0, str(TESTS.parent))

import benchcopy  # noqa: E402
import rack_faults  # noqa: E402
from reference import racks as rref  # noqa: E402

CELL = "replan.f4-rack-failure"
# 6 racks of 3 hosts, RS(3, .) volumes, rates scaled to a mean host
# utilization of about 0.5, as the cell's own catalog is
SMALL_CONFIG = {
    "cell": {"racks": 6, "hosts_per_rack": 3},
    "code": {"k": 3},
    "catalog": {"r": 30, "rate_by_tier": [3.65, 1.83, 0.913]},
    "planner": {"max_iters": 60},
}
SMALL_MIX = {"scenario": {"requests_per_segment": 200}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    dest = benchcopy.small_copy(tmp_path_factory.mktemp("bench"))
    benchcopy.edit_json(dest / "bench" / "configs" / "f4-cell-14x15-rs10-4.json",
                        SMALL_CONFIG)
    benchcopy.edit_json(dest / "bench" / "traffic" / "rack-failure-loop.json", SMALL_MIX)
    return dest


def test_check_passes_on_the_program(root):
    res, err = benchcopy.run_cell(root, CELL)
    assert res["correct"], err
    assert res["failed"] == 0 and res["attempted"] > 0
    assert "programs lowered 0, compiled 0" in err
    assert set(res["checks"]) == {"plan_err", "spread_err", "obj_err", "fw_gap",
                                  "score_err", "arb_regret"}
    assert res["checks"]["spread_err"]["value"] == 0
    assert set(res["metrics"]) == {"replan_p95_ms", "setup_s"}


# the check each planted fault has to trip
TRIPS = {
    "replan_two_hosts_in_a_rack": "spread_err",
    "replan_rack_over_cap": "plan_err",
    "replan_mass_on_down_rack": "plan_err",
    "replan_state_unchanged": "plan_err",
    "replan_other_candidate": "arb_regret",
    "replan_solver_truncated": "fw_gap",
}


@pytest.mark.parametrize("fault", rack_faults.FAULTS[CELL], ids=lambda f: f.__name__)
def test_check_fails_on_a_broken_timed_path(root, fault, monkeypatch):
    res, err = benchcopy.run_cell(root, CELL, patch=lambda mod: fault(monkeypatch.setattr))
    assert not res["correct"], err
    tripped = res["checks"][TRIPS[fault.__name__]]
    assert tripped["value"] > tripped["limit"], res["checks"]


def test_control_fails_the_comparison(root):
    checks, failed = benchcopy.run_control(root, CELL)
    assert failed > 0
    assert any(value > limit for _, value, limit, _ in checks), checks


# the rack cell's readers that read the program's trace
TRACE_READERS = ["solver_trips", "project_us_per_trip", "project_roofline", "solve_wait_ms",
                 "solve_host_ms", "solver_us_per_trip", "loop_ms", "replan_idle_share"]
# the rack cell's readers that are a ``.replan`` metric's reader
SAME_AS_REPLAN = ["solve_ms", "solver_trips", "arbitration_ms", "solve_wait_ms",
                  "solve_host_ms", "solver_us_per_trip", "solver_iters", "host_ms",
                  "loop_ms", "replan_idle_share"]


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_readers_are_silent_without_the_program(root, name, monkeypatch):
    """A trace without the program's rack scope or counters (the parent's
    program) gives no reading, and no error."""
    mod = benchcopy.load_run(root)
    reader = mod.metric_reader(f"{name}.rack")
    import program_trace

    empty = program_trace.ProgramTrace(spans={}, stats={}, scope_s={}, idle=[])
    monkeypatch.setattr(program_trace, "load", lambda run: empty)
    assert reader.read(object()) is None


def _traced_run():
    """A run whose spans, counters and trace hold two replans."""
    from types import SimpleNamespace as NS

    import program_trace
    from harness import Spans

    ms = 1e6  # ns
    t = program_trace.ProgramTrace(
        spans={"replan.step": [(0, 10 * ms), (20 * ms, 30 * ms)],
               "replan.solve": [(1 * ms, 6 * ms), (21 * ms, 26 * ms)],
               "replan.solve_wait": [(4 * ms, 6 * ms), (24 * ms, 26 * ms)],
               "solver.trips": [(5 * ms, 5 * ms), (25 * ms, 25 * ms)],
               "loop.simulate": [(10 * ms, 14 * ms)],
               "loop.observe": [(14 * ms, 15 * ms)]},
        stats={"solver.trips": [{"value": 30}, {"value": 50}]},
        scope_s={"jlcm.iterate": 4e-2, "jlcm.project": 3e-2},
        idle=[[(2 * ms, 6 * ms), (12 * ms, 20 * ms)]],
        modules={"jit__solve_merged_device_batch": [(2 * ms, 5 * ms), (22 * ms, 25 * ms)]})
    spans = Spans()
    for name, secs in (("solve", 0.005), ("arbitration", 0.002), ("replan_host", 0.001)):
        spans.add(name, secs)
        spans.add(name, 2 * secs)
    return t, NS(spans=spans, counters={"attempted": 2, "solver_iters": 70})


@pytest.mark.parametrize("name", SAME_AS_REPLAN)
def test_rack_readers_read_as_the_replan_ones(root, name, monkeypatch):
    """Each of these rack readers is its ``.replan`` metric's reader: the
    same reading of the same run, and a reading there is one."""
    import program_trace

    t, run = _traced_run()
    monkeypatch.setattr(program_trace, "load", lambda r: t)
    mod = benchcopy.load_run(root)
    got = mod.metric_reader(f"{name}.rack").read(run)
    assert got is not None and got > 0
    assert got == mod.metric_reader(f"{name}.replan").read(run)


def test_traced_run_reports_the_host_side_metrics(root, monkeypatch):
    """A ``--trace 1`` run of the cell at CPU sizes prints every metric that
    reads host spans and counters (a CPU trace has no device plane, so the
    scope and idle readers give nothing) and passes its check."""
    import program_trace

    monkeypatch.setattr(program_trace, "TRACE_DIR", root / ".bench_trace")
    res, err = benchcopy.run_cell(root, CELL, trace=1)
    assert res["correct"], err
    got = {k: v["value"] for k, v in res["metrics"].items()}
    host_side = {"solve_ms.rack", "solver_trips.rack", "arbitration_ms.rack",
                 "solve_wait_ms.rack", "solve_host_ms.rack", "solver_iters.rack",
                 "host_ms.rack", "loop_ms.rack"}
    assert host_side <= set(got), sorted(got)
    assert all(got[n] > 0 for n in host_side)
    parts = got["solve_host_ms.rack"] + got["solve_wait_ms.rack"]
    assert parts == pytest.approx(got["solve_ms.rack"], rel=0.01)


def _kkt_projection(v, k, mask, racks, iters=200):
    """Brute force: nested bisection on the KKT form x_j = clip(v_j - tau
    - mu_d, 0, 1), mu_d >= 0 the least that brings rack d to at most 1."""
    h = v.size // racks

    def place(tau):
        x = np.zeros_like(v)
        for d in range(racks):
            sl = slice(d * h, (d + 1) * h)
            seg = lambda mu: np.where(mask[sl], np.clip(v[sl] - tau - mu, 0, 1), 0)  # noqa: E731
            lo, hi = 0.0, float(np.abs(v).max()) + 2.0
            if seg(0.0).sum() > 1.0:
                for _ in range(iters):
                    mid = 0.5 * (lo + hi)
                    lo, hi = (mid, hi) if seg(mid).sum() > 1.0 else (lo, mid)
                x[sl] = seg(0.5 * (lo + hi))
            else:
                x[sl] = seg(0.0)
        return x

    lo, hi = float(v.min()) - 2.0, float(v.max()) + 1.0
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if place(mid).sum() > k else (lo, mid)
    return place(0.5 * (lo + hi))


@pytest.mark.parametrize("seed", range(4))
def test_rack_projection_matches_brute_force(seed):
    rng = np.random.default_rng(seed)
    racks, h = 3, 2
    v = rng.normal(0.4, 0.8, (5, racks * h))
    k = rng.choice([1.0, 2.0], 5)
    mask = np.ones(v.shape, bool)
    mask[::2, 1] = False
    got = rref.project(v, k, mask, racks)
    want = np.stack([_kkt_projection(v[i], k[i], mask[i], racks) for i in range(5)])
    np.testing.assert_allclose(got, want, atol=1e-8)
    assert rref.feasibility_error(got, k, racks) < 1e-9


def test_fw_oracle_matches_vertex_enumeration():
    """The gap's oracle against every vertex (k racks, one placed host in
    each) of the rack-capped set, on a tiny catalog."""
    rng = np.random.default_rng(7)
    racks, h, r = 3, 2, 4
    m = racks * h
    lam = rng.uniform(0.5, 1.5, r)
    k = np.array([2.0, 1.0, 2.0, 1.0])
    mu, m2, m3 = np.full(m, 3.0), np.full(m, 0.3), np.full(m, 0.05)
    cost = rng.uniform(0.5, 1.5, m)
    pi = rref.project(rng.uniform(0, 1, (r, m)), k, np.ones((r, m), bool), racks)
    pi[:, 1] = 0.0  # host 1 outside every placement
    allowed = np.ones(m, bool)
    allowed[4] = False
    gap = rref.fw_gap(pi, lam, k, mu, m2, m3, cost, 2.0, 1e3, allowed, racks, 1e-3)
    d_lat, latency = rref.latency_gradient(pi, lam, mu, m2, m3)
    grad = lam[:, None] * d_lat[None, :] + 2.0 * cost[None, :] * 1e3 / (
        (1e3 * pi + 1.0) * np.log(1e3))
    best = 0.0
    for i in range(r):
        cand = [j for j in range(m) if pi[i, j] > 1e-3 and allowed[j]]
        vals = [sum(grad[i, j] for j in s) for s in itertools.combinations(cand, int(k[i]))
                if len({j // h for j in s}) == int(k[i])]
        best += min(vals)
    assert gap == pytest.approx((np.sum(grad * pi) - best) / latency, rel=1e-9)


def test_spread_and_cap_readings():
    racks = 2
    pi = np.array([[0.6, 0.4, 1.0, 0.0], [1.0, 0.0, 0.0, 1.0]])
    assert rref.spread_count(pi, racks, 1e-3) == 1
    assert rref.feasibility_error(pi, [2, 2], racks) == 0.0
    pi[0, 3] = 0.5
    pi[0, 2] = 0.5
    pi[0, 1] = 0.9
    assert rref.feasibility_error(pi, [2.5, 2], racks) == pytest.approx(0.5)
    assert rref.feasibility_error(np.array([[0.0, 1.0, 1.0, 0.0]]), [2], racks,
                                  down=np.array([False, True, False, False])) == 1.0
