"""Parity harness for the batched on-device rollout arbitration.

Four layers of trust, each asserted independently:

1. **Simulator batch parity** — the candidate-batched rollout entry
   points (``run_segment_batch`` / ``run_geo_segment_batch``) against
   per-candidate calls of the sequential kernels they vmap: identical
   trajectories, bitwise, including the cached (TTL) path.
2. **Device objective parity** — ``empirical_objective_device`` against
   the host numpy ``empirical_objective`` it mirrors, with and without a
   composed multi-tenant spec, including the repair-row validity mask.
3. **Arbitration parity** — ``batched_rollout_scores`` and the
   replanner against a sequential reference kept here: one
   ``run_segment_raw`` / ``run_geo_segment_raw`` rollout per candidate,
   scored by the host ``empirical_objective`` plus ``theta * cost`` on
   client rows. Same chosen plan (bitwise deployed pi and repair pi),
   matching per-candidate scores, across plain / cache-aware /
   repair-augmented / geo replans — plus one-compiled-program reuse
   across varying candidate counts (the power-of-two lane padding).
4. **Sharding parity** — vmapped vs ``shard_map``-over-8-forced-devices
   arbitration in a subprocess (device count must precede jax init).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import diag
from repro.core import (
    JLCMProblem,
    empirical_objective,
    empirical_objective_device,
    make_objective,
    solve_batch,
    stack_problems,
)
from repro.serving import (
    AdaptiveReplanner,
    EwmaMomentEstimator,
    batched_rollout_scores,
    router,
)
from repro.serving.router import _arbitrate_device, _pow2
from repro.storage import (
    CacheModel,
    build_repair_flow,
    geo_testbed,
    init_carry,
    run_geo_segment_batch,
    run_segment_batch,
    tahoe_testbed,
)
from repro.storage.simulator import run_geo_segment_raw, run_segment_raw

MB = 1024 * 1024
LAM = np.asarray([0.030, 0.020, 0.015, 0.012])
K4 = np.asarray([4.0, 4.0, 6.0, 6.0])
CHUNK_MB = 150.0 / 4
N_REQ = 200


@pytest.fixture(scope="module")
def cluster():
    return tahoe_testbed()


@pytest.fixture(scope="module")
def params(cluster):
    d, rates = cluster.service_params(CHUNK_MB)
    return (
        jnp.asarray(LAM, jnp.float32),
        jnp.asarray(d, jnp.float32),
        jnp.asarray(rates, jnp.float32),
        jnp.ones((cluster.m,), bool),
    )


def _pi_stack(cluster, n_cand, scales=None):
    """n_cand candidate plans from a fan of demand scales."""
    scales = np.linspace(0.8, 1.2, n_cand) if scales is None else scales
    probs = [
        JLCMProblem(
            lam=jnp.asarray(LAM * s, jnp.float32),
            k=jnp.asarray(K4, jnp.float32),
            moments=cluster.moments(CHUNK_MB),
            cost=cluster.cost,
            theta=2.0,
        )
        for s in scales
    ]
    return solve_batch(stack_problems(probs), max_iters=60)


class TestSimulatorBatchParity:
    def test_plain_bitwise(self, cluster, params):
        lam, d, rates, avail = params
        sols = _pi_stack(cluster, 3)
        key = jax.random.key(0)
        carry = init_carry(cluster.m)
        batch = run_segment_batch(
            carry, key[None], sols.pi, lam, d, rates, avail, N_REQ
        )
        assert batch.latency.shape == (3, 1, N_REQ)
        for i in range(3):
            _, one = run_segment_raw(
                carry, key, sols.pi[i], lam, d, rates, avail, N_REQ
            )
            np.testing.assert_array_equal(
                np.asarray(batch.latency[i, 0]), np.asarray(one.latency)
            )
            np.testing.assert_array_equal(
                np.asarray(batch.file_id[i, 0]), np.asarray(one.file_id)
            )

    def test_cached_bitwise(self, cluster, params):
        """TTL cache path: the scan carry (per-file expiries) vmaps too."""
        lam, d, rates, avail = params
        sols = _pi_stack(cluster, 2)
        key = jax.random.key(1)
        carry = init_carry(cluster.m, cache_files=LAM.size)
        ttl = jnp.asarray([8.0, 8.0, 0.0, 4.0], jnp.float32)
        batch = run_segment_batch(
            carry, key[None], sols.pi, lam, d, rates, avail, N_REQ,
            ttl, 0.5,
        )
        for i in range(2):
            _, one = run_segment_raw(
                carry, key, sols.pi[i], lam, d, rates, avail, N_REQ,
                ttl, 0.5,
            )
            np.testing.assert_array_equal(
                np.asarray(batch.latency[i, 0]), np.asarray(one.latency)
            )
            np.testing.assert_array_equal(
                np.asarray(batch.hit[i, 0]), np.asarray(one.hit)
            )

    def test_geo_bitwise(self):
        fabric = geo_testbed()
        sols = _pi_stack_geo(fabric, 3)
        lam_cs = jnp.asarray(
            np.asarray(fabric.uniform_mix(4)).T * LAM, jnp.float32
        )
        d, rates = fabric.service_params(12.5)
        key = jax.random.key(2)
        carry = init_carry(fabric.m)
        avail = jnp.ones((fabric.m,), bool)
        batch = run_geo_segment_batch(
            carry, key[None], sols, lam_cs, d, rates, avail, N_REQ
        )
        for i in range(3):
            _, one = run_geo_segment_raw(
                carry, key, sols[i], lam_cs, d, rates, avail, N_REQ
            )
            np.testing.assert_array_equal(
                np.asarray(batch.latency[i, 0]), np.asarray(one.latency)
            )
            np.testing.assert_array_equal(
                np.asarray(batch.site_id[i, 0]), np.asarray(one.site_id)
            )

    def test_seed_axis_matches_split_keys(self, cluster, params):
        """K>1: lane (i, j) replays candidate i under split key j."""
        lam, d, rates, avail = params
        sols = _pi_stack(cluster, 2)
        keys = jax.random.split(jax.random.key(3), 2)
        carry = init_carry(cluster.m)
        batch = run_segment_batch(
            carry, keys, sols.pi, lam, d, rates, avail, N_REQ
        )
        assert batch.latency.shape == (2, 2, N_REQ)
        _, one = run_segment_raw(
            carry, keys[1], sols.pi[0], lam, d, rates, avail, N_REQ
        )
        np.testing.assert_array_equal(
            np.asarray(batch.latency[0, 1]), np.asarray(one.latency)
        )


def _pi_stack_geo(fabric, n_cand):
    from repro.core import feasible_uniform

    pis = [
        feasible_uniform(jnp.ones((4, fabric.m), bool), jnp.asarray(K4))
    ]
    key = jax.random.key(7)
    for i in range(n_cand - 1):
        noise = jax.random.uniform(
            jax.random.fold_in(key, i), pis[0].shape, minval=0.5, maxval=1.5
        )
        pi = pis[0] * noise
        pi = pi / pi.sum(-1, keepdims=True) * jnp.asarray(K4)[:, None]
        pis.append(jnp.clip(pi, 0.0, 1.0))
    return jnp.stack(pis)


class TestDeviceObjective:
    def _stream(self, n=500, seed=4):
        rng = np.random.default_rng(seed)
        lat = rng.exponential(10.0, n)
        fid = rng.integers(0, 4, n)
        return lat, fid

    def test_matches_host_mean(self):
        lat, fid = self._stream()
        dev = float(empirical_objective_device(lat, fid, None))
        host = float(empirical_objective(lat, fid, None))
        np.testing.assert_allclose(dev, host, rtol=1e-5)

    def test_matches_host_composed_spec(self):
        lat, fid = self._stream()
        spec = make_objective(
            class_id=np.asarray([0, 0, 1, 1]),
            weight=np.asarray([3.0, 1.0]),
            deadline=np.asarray([15.0, np.inf]),
            tail_weight=np.asarray([5.0, 0.0]),
        )
        dev = float(empirical_objective_device(lat, fid, spec))
        host = float(empirical_objective(lat, fid, spec))
        np.testing.assert_allclose(dev, host, rtol=1e-5)

    def test_valid_mask_drops_repair_rows(self):
        """valid=fid < n_clients must equal host scoring on the filtered
        stream — and masked ±inf latencies must not poison the sums."""
        lat, fid = self._stream()
        fid = fid.copy()
        fid[::5] = 4  # repair pseudo-file rows
        lat = lat.copy()
        lat[::5] = np.inf  # would NaN the mean if not masked out
        client = fid < 4
        dev = float(
            empirical_objective_device(lat, fid, None, valid=client)
        )
        host = float(empirical_objective(lat[client], fid[client], None))
        np.testing.assert_allclose(dev, host, rtol=1e-5)
        assert np.isfinite(dev)


class TestBatchedScores:
    def _sequential(self, carry, key, sols, lam, d, rates, avail, cost):
        scores = []
        for i in range(cost.size):
            _, res = run_segment_raw(
                carry, key, sols.pi[i], lam, d, rates, avail, N_REQ
            )
            lat = np.asarray(res.latency)
            fid = np.asarray(res.file_id)
            ok = fid < LAM.size
            scores.append(
                empirical_objective(lat[ok], fid[ok], None) + float(cost[i])
            )
        return np.asarray(scores)

    def test_padding_scores_and_best(self, cluster, params):
        lam, d, rates, avail = params
        sols = _pi_stack(cluster, 3)
        cost = 2.0 * np.asarray(sols.cost)
        key = jax.random.key(5)
        carry = init_carry(cluster.m)
        # devices="never": the padded width must be the plain power of
        # two for the shape asserts below (under a forced multi-device
        # mesh "auto" grows the pad to divide the lane count; that path
        # is covered by the sharded subprocess test)
        scores, best = batched_rollout_scores(
            carry, key, sols.pi, lam, d, rates, avail,
            jnp.asarray(cost, jnp.float32), None,
            n_clients=LAM.size, n_requests=N_REQ, devices="never",
        )
        scores = np.asarray(scores)
        assert scores.shape == (4,)  # padded to the next power of two
        assert scores[3] == np.inf  # padded lane masked out
        ref = self._sequential(carry, key, sols, lam, d, rates, avail, cost)
        np.testing.assert_allclose(scores[:3], ref, rtol=1e-5, atol=1e-5)
        assert int(best) == int(np.argmin(ref))

    def test_seed_axis_reduces_to_mean(self, cluster, params):
        lam, d, rates, avail = params
        sols = _pi_stack(cluster, 2)
        cost = jnp.zeros((2,), jnp.float32)
        key = jax.random.key(6)
        carry = init_carry(cluster.m)
        scores, best = batched_rollout_scores(
            carry, key, sols.pi, lam, d, rates, avail, cost, None,
            n_clients=LAM.size, n_requests=N_REQ, rollout_seeds=3,
        )
        scores = np.asarray(scores)[:2]
        assert np.isfinite(scores).all() and 0 <= int(best) < 2
        # the K-seed mean equals scoring each split key and averaging
        keys = jax.random.split(key, 3)
        per_seed = np.zeros((2, 3))
        for i in range(2):
            for j, kk in enumerate(keys):
                _, res = run_segment_raw(
                    carry, kk, sols.pi[i], lam, d, rates, avail, N_REQ
                )
                lat = np.asarray(res.latency)
                fid = np.asarray(res.file_id)
                per_seed[i, j] = empirical_objective(lat, fid, None)
        np.testing.assert_allclose(
            scores, per_seed.mean(axis=1), rtol=1e-5, atol=1e-5
        )

    def test_one_program_across_candidate_counts(self, cluster, params):
        """3 and 4 candidates both pad to 4 lanes -> ONE compiled
        executable serves both replans (the dynamic lane_ok mask, not a
        fresh trace, handles the count change)."""
        lam, d, rates, avail = params
        key = jax.random.key(8)
        carry = init_carry(cluster.m)
        _arbitrate_device._clear_cache()
        with diag.CompileWatcher(_arbitrate_device) as watch:
            for n_cand in (3, 4, 2):
                sols = _pi_stack(cluster, n_cand)
                # devices="never" pins the pad to _pow2(n) so the
                # expected program count is device-count independent
                batched_rollout_scores(
                    carry, key, sols.pi, lam, d, rates, avail,
                    jnp.zeros((n_cand,), jnp.float32), None,
                    n_clients=LAM.size, n_requests=N_REQ, devices="never",
                )
        # 3 and 4 cands share the 4-lane program; 2 pads to 2 lanes
        watch.assert_compiles(_arbitrate_device, exactly=2)

    def test_pow2(self):
        assert [_pow2(n) for n in (1, 2, 3, 4, 5, 9)] == [1, 2, 4, 4, 8, 16]


def _estimator(cluster):
    return EwmaMomentEstimator(prior=cluster.moments(CHUNK_MB))


def _replanner(cost, estimator, **kw):
    return AdaptiveReplanner(
        k=K4.copy(),
        cost=np.asarray(cost),
        theta=2.0,
        estimator=estimator,
        max_iters=80,
        rollout_requests=N_REQ,
        **kw,
    )


@pytest.fixture
def sequential(monkeypatch):
    """Score every batched arbitration's candidates one at a time.

    Wraps ``router.batched_rollout_scores`` to capture its arguments and,
    per candidate, runs one sequential rollout (``run_segment_raw`` or
    ``run_geo_segment_raw``) from the same queue state and key, scores its
    client rows by the host ``empirical_objective`` and adds the
    candidate's ``theta * cost``. Each call appends ``(pi_stack, scores)``.
    """
    calls = []
    orig = router.batched_rollout_scores

    def spy(carry, key, pi_stack, lam, d, rates, avail, cost_term,
            objective=None, **kw):
        out = orig(carry, key, pi_stack, lam, d, rates, avail, cost_term,
                   objective, **kw)
        cost = np.asarray(cost_term)
        n_req, n_clients = kw["n_requests"], kw["n_clients"]
        scores = []
        for i in range(cost.size):
            if kw.get("geo", False):
                _, res = run_geo_segment_raw(
                    carry, key, pi_stack[i], lam, d, rates, avail, n_req
                )
            else:
                _, res = run_segment_raw(
                    carry, key, pi_stack[i], lam, d, rates, avail, n_req,
                    kw.get("ttl"), kw.get("hit_latency", 0.0),
                )
            lat = np.asarray(res.latency)
            fid = np.asarray(res.file_id)
            client = fid < n_clients
            scores.append(
                empirical_objective(lat[client], fid[client], objective)
                + float(cost[i])
            )
        calls.append((np.asarray(pi_stack), np.asarray(scores)))
        return out

    monkeypatch.setattr(router, "batched_rollout_scores", spy)
    return calls


def _assert_sequential_choice(rp, pi, calls):
    """The replanner deployed the sequential reference's argmin, bitwise,
    and its batched scores match the reference's."""
    assert len(calls) == 1
    pi_stack, ref = calls[0]
    np.testing.assert_allclose(
        np.asarray(rp.last_scores), ref, rtol=1e-5, atol=1e-5
    )
    best = pi_stack[int(np.argmin(ref))]
    r = K4.size
    np.testing.assert_array_equal(pi, best[:r])
    if rp.repair_pi is not None:
        np.testing.assert_array_equal(rp.repair_pi, best[r:])


class TestReplannerParity:
    """Batched arbitration picks the plan the sequential reference picks."""

    def test_plain(self, cluster, sequential):
        rp = _replanner(cluster.cost, _estimator(cluster))
        carry = init_carry(cluster.m)
        key = jax.random.key(9)
        avail = np.ones(cluster.m, bool)
        pi = rp.replan(LAM, avail, carry=carry, key=key)
        _assert_sequential_choice(rp, pi, sequential)
        assert len(rp.rollout_walls) == 1

    def test_warm_start_candidates(self, cluster, sequential):
        """pi0 doubles the candidate set (cold + warm per mask)."""
        rp = _replanner(cluster.cost, _estimator(cluster))
        carry = init_carry(cluster.m)
        key = jax.random.key(10)
        avail = np.ones(cluster.m, bool)
        pi0 = np.asarray(
            _pi_stack(cluster, 1).pi[0]
        )
        masks = [avail, np.concatenate([[False], avail[1:]])]
        pi = rp.replan(
            LAM, avail, carry=carry, key=key, pi0=pi0,
            candidate_masks=masks,
        )
        _assert_sequential_choice(rp, pi, sequential)
        assert np.asarray(rp.last_scores).shape == (4,)

    def test_repair_augmented(self, cluster, sequential):
        sols = _pi_stack(cluster, 1)
        placement = np.asarray(sols.pi[0]) > 1e-6
        avail = np.ones(cluster.m, bool)
        avail[0] = False
        flow = build_repair_flow(placement, K4, avail, 0.05)
        rp = _replanner(cluster.cost, _estimator(cluster))
        carry = init_carry(cluster.m)
        key = jax.random.key(11)
        pi = rp.replan(LAM, avail, carry=carry, key=key, repair=flow)
        assert rp.repair_pi is not None
        _assert_sequential_choice(rp, pi, sequential)

    def test_cache_aware(self, cluster, sequential):
        model = CacheModel(
            file_bytes=np.asarray([50.0, 50.0, 75.0, 75.0]) * MB,
            capacity_bytes=100.0 * MB,
            hit_latency=0.5,
            hot_price_per_mb=0.02,
        )
        rp = _replanner(cluster.cost, _estimator(cluster), cache=model)
        rp.last_ttl = model.ttl(LAM)
        rp.last_raw = LAM.copy()
        carry = init_carry(cluster.m, cache_files=LAM.size)
        key = jax.random.key(12)
        avail = np.ones(cluster.m, bool)
        miss = model.thin(LAM)
        pi = rp.replan(miss, avail, carry=carry, key=key)
        _assert_sequential_choice(rp, pi, sequential)

    def test_geo(self, sequential):
        fabric = geo_testbed()
        rp = _replanner(
            fabric.cluster.cost,
            EwmaMomentEstimator(prior=fabric.moments(12.5)),
        )
        lam_cs = np.asarray(fabric.uniform_mix(4)).T * LAM
        carry = init_carry(fabric.m)
        key = jax.random.key(13)
        avail = np.ones(fabric.m, bool)
        pi = rp.replan(lam_cs, avail, carry=carry, key=key)
        _assert_sequential_choice(rp, pi, sequential)
        assert len(rp.rollout_walls) == 1

    def test_scenario_outcome_reports_rollout_wall(self):
        from repro.scenarios.engine import ScenarioOutcome

        out = ScenarioOutcome(
            scenario="t", policy="adaptive",
            seg_mean=np.asarray([1.0]), seg_p99=np.asarray([2.0]),
            mean=1.0, p99=2.0, degraded_frac=0.0, replans=2,
            solve_walls=(0.01, 0.02), rollout_walls=(0.004, 0.005),
        )
        row = out.row()
        assert row["rollout_wall_ms"] == "4.0|5.0"
        # open-loop outcomes leave the column empty, not absent
        empty = ScenarioOutcome(
            scenario="t", policy="static",
            seg_mean=np.asarray([1.0]), seg_p99=np.asarray([2.0]),
            mean=1.0, p99=2.0, degraded_frac=0.0, replans=0,
        )
        assert empty.row()["rollout_wall_ms"] == ""


@pytest.mark.slow
def test_sharded_arbitration_parity_on_8_fake_devices():
    """vmap vs shard_map arbitration on a forced 8-device host mesh: same
    scores (fp32-tight) and the same chosen candidate, for both a
    mesh-divisible lane count (8) and one needing pad growth (3 -> pad 4
    -> grow 8). Subprocess: device count must be set before jax init."""
    prog = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.core import JLCMProblem, solve_batch, stack_problems
        from repro.serving import batched_rollout_scores
        from repro.storage import init_carry, tahoe_testbed

        assert len(jax.devices()) == 8
        cl = tahoe_testbed()
        LAM = np.asarray([0.030, 0.020, 0.015, 0.012])
        d, rates = cl.service_params(150.0 / 4)
        lam = jnp.asarray(LAM, jnp.float32)
        d = jnp.asarray(d, jnp.float32)
        rates = jnp.asarray(rates, jnp.float32)
        avail = jnp.ones((cl.m,), bool)
        carry = init_carry(cl.m)
        key = jax.random.key(20)

        for n_cand, n_seeds in ((8, 1), (3, 1), (4, 2)):
            probs = [
                JLCMProblem(
                    lam=jnp.asarray(LAM * s, jnp.float32),
                    k=jnp.asarray([4.0, 4.0, 6.0, 6.0], jnp.float32),
                    moments=cl.moments(150.0 / 4),
                    cost=cl.cost,
                    theta=2.0,
                )
                for s in np.linspace(0.8, 1.2, n_cand)
            ]
            sols = solve_batch(stack_problems(probs), max_iters=40)
            cost = jnp.asarray(2.0 * np.asarray(sols.cost), jnp.float32)
            sh, best_sh = batched_rollout_scores(
                carry, key, sols.pi, lam, d, rates, avail, cost, None,
                n_clients=4, n_requests=200, rollout_seeds=n_seeds,
                devices="auto",
            )
            vm, best_vm = batched_rollout_scores(
                carry, key, sols.pi, lam, d, rates, avail, cost, None,
                n_clients=4, n_requests=200, rollout_seeds=n_seeds,
                devices="never",
            )
            np.testing.assert_allclose(
                np.asarray(sh)[:n_cand], np.asarray(vm)[:n_cand],
                rtol=1e-6, atol=1e-6,
            )
            assert int(best_sh) == int(best_vm), (n_cand, n_seeds)
        print("REPLAN_SHARD_PARITY_OK")
        """
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", prog],
        capture_output=True,
        text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
        timeout=600,
    )
    assert "REPLAN_SHARD_PARITY_OK" in out.stdout, out.stderr[-3000:]
