"""Runtime guard layer: numpy tripwire, CompileWatcher, and the
REPRO_DIAG=1 closed-loop contract (zero disallowed transfers inside
guarded hot paths, zero recompiles after warmup) over a 3-segment
steady-state replan loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import diag
from repro.core.jlcm import JLCMProblem, _solve_merged_device, solve
from repro.serving import AdaptiveReplanner, EwmaMomentEstimator
from repro.serving.router import _arbitrate_device
from repro.storage import init_carry, tahoe_testbed
from repro.storage.simulator import run_segment_raw

LAM = np.asarray([0.030, 0.020, 0.015, 0.012])
K4 = np.asarray([4.0, 4.0, 6.0, 6.0])
CHUNK_MB = 150.0 / 4


@pytest.fixture
def armed(monkeypatch):
    monkeypatch.setenv("REPRO_DIAG", "1")


@pytest.fixture
def disarmed(monkeypatch):
    monkeypatch.delenv("REPRO_DIAG", raising=False)


class TestTripwire:
    def test_materializing_a_device_array_raises(self, armed):
        x = jnp.arange(4.0)
        with diag.hot_path("t.materialize"):
            with pytest.raises(diag.HostSyncError, match="np.asarray"):
                np.asarray(x)

    def test_all_materializer_entry_points_guarded(self, armed):
        x = jnp.arange(4.0)
        # look the entry point up *inside* the guard — a reference taken
        # before __enter__ would bypass the patch
        for name in ("asarray", "array", "asanyarray", "ascontiguousarray"):
            with diag.hot_path("t.entry"):
                with pytest.raises(diag.HostSyncError):
                    getattr(np, name)(x)

    def test_numpy_inputs_pass_through(self, armed):
        with diag.hot_path("t.numpy_ok"):
            out = np.asarray([1.0, 2.0])
        np.testing.assert_array_equal(out, [1.0, 2.0])

    def test_disabled_by_default(self, disarmed):
        x = jnp.arange(4.0)
        with diag.hot_path("t.off"):
            host = np.asarray(x)  # inert without REPRO_DIAG=1
        assert host.shape == (4,)

    def test_numpy_is_restored_after_exception(self, armed):
        orig = np.asarray
        with pytest.raises(RuntimeError, match="boom"):
            with diag.hot_path("t.restore"):
                raise RuntimeError("boom")
        assert np.asarray is orig

    def test_nested_hot_paths_patch_once_and_restore(self, armed):
        orig = np.asarray
        with diag.hot_path("t.outer"):
            with diag.hot_path("t.inner"):
                with pytest.raises(diag.HostSyncError):
                    np.asarray(jnp.zeros(2))
            # still armed after the inner guard exits
            with pytest.raises(diag.HostSyncError):
                np.asarray(jnp.zeros(2))
        assert np.asarray is orig

    def test_decorator_form(self, armed):
        @diag.hot_path("t.decorated")
        def sync_inside(x):
            return np.asarray(x)

        with pytest.raises(diag.HostSyncError):
            sync_inside(jnp.arange(3.0))
        assert "t.decorated" in diag.hot_path_registry()


class TestCompileWatcher:
    def test_counts_and_reuse(self):
        @jax.jit
        def f(x):
            return x * 2

        f(jnp.zeros(3))  # pre-region warmup the watcher must ignore
        with diag.CompileWatcher(f) as w:
            f(jnp.zeros(3))  # cached
            assert w.new_compiles(f) == 0
            f(jnp.zeros(5))  # new shape -> one new program
            w.assert_compiles(f, exactly=1)
            with pytest.raises(diag.RecompileError):
                w.assert_no_recompiles()

    def test_requires_jitted_callable(self):
        # the entry snapshot already needs _cache_size(), so a plain
        # function is rejected at __enter__
        with pytest.raises(TypeError, match="_cache_size"):
            with diag.CompileWatcher(lambda x: x):
                pass

    def test_unwraps_hot_path_decorated_functions(self):
        @diag.hot_path("t.wrapped")
        @jax.jit
        def g(x):
            return x + 1

        g(jnp.zeros(2))
        with diag.CompileWatcher(g) as w:
            g(jnp.zeros(2))
        w.assert_no_recompiles(g)


def _problem(cluster):
    r = LAM.size
    return JLCMProblem(
        lam=jnp.asarray(LAM, jnp.float32),
        k=jnp.asarray(K4, jnp.float32),
        moments=cluster.moments(CHUNK_MB),
        cost=cluster.cost,
        theta=2.0,
    )


class TestSolverGuard:
    def test_merged_solve_passes_under_strict_diag(self, armed, monkeypatch):
        """Same-shape re-solves reuse ONE compiled program even with the
        strict recompile tripwire armed."""
        monkeypatch.setenv("REPRO_DIAG_STRICT", "1")
        cluster = tahoe_testbed()
        prob = _problem(cluster)
        solve(prob, max_iters=60)  # warmup compile
        with diag.CompileWatcher(_solve_merged_device) as w:
            solve(prob, max_iters=60)
            solve(prob, max_iters=60)
        w.assert_no_recompiles(_solve_merged_device)
        stats = diag.hot_path_registry()["core.solve_merged"]
        assert stats.guarded_calls >= 3


class TestClosedLoopContract:
    def test_three_segment_steady_state(self, armed):
        """3 replan->simulate segments under REPRO_DIAG=1: no guarded
        hot path materializes a device array, and segments after the
        first compile ZERO new arbitration programs (the ISSUE's
        acceptance criterion, asserted via CompileWatcher)."""
        cluster = tahoe_testbed()
        rp = AdaptiveReplanner(
            k=K4.copy(),
            cost=np.asarray(cluster.cost),
            theta=2.0,
            estimator=EwmaMomentEstimator(prior=cluster.moments(CHUNK_MB)),
            max_iters=60,
            rollout_requests=120,
        )
        avail = np.ones(cluster.m, bool)
        carry = init_carry(cluster.m)
        d, rates = cluster.service_params(CHUNK_MB)

        def segment(seg, carry):
            key = jax.random.key(40 + seg)
            pi = rp.replan(LAM, avail, carry=carry, key=key)
            carry, res = run_segment_raw(
                carry,
                jax.random.key(140 + seg),
                jnp.asarray(pi, jnp.float32),
                jnp.asarray(LAM, jnp.float32),
                jnp.asarray(d, jnp.float32),
                jnp.asarray(rates, jnp.float32),
                jnp.asarray(avail),
                120,
                jnp.zeros((1,), jnp.float32),
                0.0,
            )
            return pi, carry

        # segments 1-2 are warmup: the first replan has no incumbent plan
        # (N candidates); every later replan appends the incumbent start
        # (2N candidates) — so steady-state shape is only reached on the
        # SECOND replan. After that, zero new programs.
        _, carry = segment(0, carry)
        _, carry = segment(1, carry)
        with diag.CompileWatcher(_arbitrate_device, _solve_merged_device) as w:
            for seg in (2, 3):
                pi, carry = segment(seg, carry)
                assert np.all(np.isfinite(pi))
        w.assert_no_recompiles()

        stats = diag.hot_path_registry()["serving.batched_rollout_scores"]
        assert stats.guarded_calls >= 3
        assert stats.recompiles == 0


REPLAN_SPANS = (
    "replan.step", "replan.estimate", "replan.assemble", "replan.solve",
    "solve.stack", "solve.dispatch", "replan.solve_wait", "replan.arbitrate",
    "replan.rollout_fit", "serving.batched_rollout_scores", "replan.sync",
    "replan.deploy", "solver.trips", "solver.lanes", "loop.simulate",
    "loop.observe",
)


def _host_events(trace_dir):
    """(name, start_ns, end_ns, stats) of every /host:CPU event; stats
    are read for the program's counters only."""
    from jax.profiler import ProfileData

    path = sorted(trace_dir.glob("**/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats) if e.name.startswith("solver.") else {}
                out.append((e.name, e.start_ns, e.start_ns + e.duration_ns, stats))
    return out


def _inside(inner, outers):
    return any(s <= inner[1] and inner[2] <= e for _, s, e, _ in outers)


class TestSpansAndCounters:
    @pytest.fixture(scope="class")
    def traced_loop(self, tmp_path_factory):
        """A two-segment steady-state closed loop under a profiler trace,
        with each replan's batched solve captured."""
        import dataclasses

        from repro.scenarios import get_scenario, run_scenario
        from repro.serving import router

        spec = dataclasses.replace(
            get_scenario("steady-state"), n_segments=2, requests_per_segment=200
        )
        orig = router.solve_batch
        sols = []

        def capture(probs, **kw):
            out = orig(probs, **kw)
            sols.append(out)
            return out

        router.solve_batch = capture
        trace_dir = tmp_path_factory.mktemp("trace")
        try:
            with jax.profiler.trace(str(trace_dir)):
                out = run_scenario(spec, "adaptive", seed=3)
        finally:
            router.solve_batch = orig
        return out, sols, _host_events(trace_dir)

    def test_every_replan_and_loop_span_appears(self, traced_loop):
        _, _, events = traced_loop
        names = {e[0] for e in events}
        assert set(REPLAN_SPANS) <= names

    def test_solve_wait_nests_in_solve_in_step(self, traced_loop):
        _, _, events = traced_loop
        by = lambda n: [e for e in events if e[0] == n]  # noqa: E731
        waits, solves, steps = by("replan.solve_wait"), by("replan.solve"), by(
            "replan.step")
        assert waits
        for w in waits:
            assert _inside(w, solves)
        for s in solves:
            assert _inside(s, steps)

    def test_trips_counter_is_the_slowest_lane(self, traced_loop):
        _, sols, events = traced_loop
        trips = [e[3]["value"] for e in events if e[0] == "solver.trips"]
        lanes = [e[3]["value"] for e in events if e[0] == "solver.lanes"]
        assert trips == [int(np.max(np.asarray(s.iterations))) for s in sols]
        assert lanes == [int(np.asarray(s.iterations).size) for s in sols]

    def test_span_counts_match_the_replanner_walls(self, traced_loop):
        out, _, events = traced_loop
        names = [e[0] for e in events]
        assert names.count("replan.solve") == len(out.solve_walls) >= 1
        assert names.count("replan.arbitrate") == len(out.rollout_walls) >= 1
        assert names.count("loop.simulate") == names.count("loop.observe") == 2

    def test_walls_are_the_spans_seconds(self, traced_loop):
        out, _, events = traced_loop
        solve = [e[2] - e[1] for e in events if e[0] == "replan.solve"]
        for wall, ns in zip(out.solve_walls, solve):
            assert wall * 1e9 <= ns + 1e3  # perf_counter runs inside the span

    def test_geo_replan_records_step_and_trips(self, tmp_path, monkeypatch):
        """A (C, r) geo replan runs inside the same spans and counters as a
        plain one: the step, its phases and the solver's trip count."""
        from repro.serving import router
        from repro.storage import geo_testbed

        fabric = geo_testbed()
        rp = AdaptiveReplanner(
            k=K4.copy(),
            cost=np.asarray(fabric.cluster.cost),
            theta=2.0,
            estimator=EwmaMomentEstimator(prior=fabric.moments(12.5)),
            max_iters=60,
            rollout_requests=120,
        )
        orig = router.solve_batch
        sols = []

        def capture(probs, **kw):
            sols.append(orig(probs, **kw))
            return sols[-1]

        monkeypatch.setattr(router, "solve_batch", capture)
        lam_cs = np.asarray(fabric.uniform_mix(4)).T * LAM
        with jax.profiler.trace(str(tmp_path)):
            rp.replan(
                lam_cs, np.ones(fabric.m, bool),
                carry=init_carry(fabric.m), key=jax.random.key(5),
            )
        events = _host_events(tmp_path)
        names = {e[0] for e in events}
        assert {
            "replan.step", "replan.estimate", "replan.assemble",
            "replan.solve", "replan.arbitrate", "replan.sync",
            "replan.deploy", "solver.trips", "solver.lanes",
        } <= names
        trips = [e[3]["value"] for e in events if e[0] == "solver.trips"]
        assert trips == [int(np.max(np.asarray(sols[0].iterations)))]

    def test_no_trace_leaves_nothing_behind(self, tmp_path):
        before = set(diag.hot_path_registry())
        with diag.span("t.untraced", bytes=3) as sp:
            diag.count("t.untraced_count", 1)
        assert sp.seconds >= 0.0
        assert set(diag.hot_path_registry()) == before
        with jax.profiler.trace(str(tmp_path)):
            with diag.span("t.traced"):
                pass
        names = {e[0] for e in _host_events(tmp_path)}
        assert "t.traced" in names
        assert not {"t.untraced", "t.untraced_count"} & names

    def test_hot_path_opens_a_span(self, tmp_path, disarmed):
        with jax.profiler.trace(str(tmp_path)):
            with diag.hot_path("t.spanned"):
                pass
        assert "t.spanned" in {e[0] for e in _host_events(tmp_path)}

    def test_scope_names_ops_and_marks_the_program(self):
        @jax.jit
        def f(x):
            with diag.scope("t.scoped"):
                y = jnp.sin(x) * 2.0
            return y + 1.0

        lowered = f.lower(jnp.ones(3))
        # in the program itself (what the compilation cache keys on)...
        assert 'repro_scope = "t.scoped"' in lowered.as_text()
        # ...and in each operation's scope path
        hlo = lowered.compile().as_text()
        assert "/t.scoped/" in hlo
        np.testing.assert_array_equal(f(jnp.ones(3)), jnp.sin(jnp.ones(3)) * 2.0 + 1.0)
