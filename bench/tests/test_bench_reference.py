"""The plain references the checks compare against, on the CPU at small
sizes: the Frank-Wolfe gap of a plan reads 0 at a stationary plan and
more away from one, the rollout score rebuilds the program's rollout
from its key, and the fleet's sketch histogram bins as the program's."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from reference import fcfs, rollout  # noqa: E402
from reference import plan as ref  # noqa: E402

CONFIG = json.loads((BENCH / "configs" / "tahoe-3dc-r1000.json").read_text())
THETA = float(CONFIG["theta"])
BETA = float(CONFIG["planner"]["beta"])


def problem(r=40, seed=0):
    """A small catalog on the configuration's testbed, float64 moments."""
    rng = np.random.default_rng(seed)
    lam = rng.uniform(0.5, 1.5, r) * 1e-3
    k = rng.choice([4, 6], r).astype(np.float64)
    mu, m2, m3, cost = ref.testbed_moments(CONFIG, 25.0)
    return lam, k, mu, m2, m3, cost


def uniform_plan(k, m, allowed=None):
    allowed = np.ones(m, bool) if allowed is None else allowed
    return np.outer(k, allowed / allowed.sum())


def program_solve(lam, k, mu, m2, m3, cost, allowed, iters, pi0=None):
    import jax.numpy as jnp

    from repro.core import JLCMProblem, ServiceMoments, solve

    prob = JLCMProblem(
        lam=jnp.asarray(lam, jnp.float32), k=jnp.asarray(k, jnp.float32),
        moments=ServiceMoments(*(jnp.asarray(x, jnp.float32) for x in (mu, m2, m3))),
        cost=jnp.asarray(cost, jnp.float32), theta=THETA,
        mask=jnp.broadcast_to(jnp.asarray(allowed), (lam.size, mu.size)))
    kw = {} if pi0 is None else {"pi0": jnp.asarray(pi0, jnp.float32)}
    return np.asarray(solve(prob, beta=BETA, max_iters=iters, eps=0.0, **kw).pi, np.float64)


@pytest.mark.parametrize("down", [None, 0])
def test_fw_gap_falls_as_the_solve_goes_on(down):
    lam, k, mu, m2, m3, cost = problem()
    allowed = np.ones(mu.size, bool)
    if down is not None:
        allowed[down] = False
    gap = lambda pi: ref.fw_gap(pi, lam, k, mu, m2, m3, cost, THETA, BETA, allowed)  # noqa: E731
    start = uniform_plan(k, mu.size, allowed)
    gaps = [gap(program_solve(lam, k, mu, m2, m3, cost, allowed, n, start))
            for n in (2, 20, 2000)]
    assert gap(start) > gaps[0] > gaps[-1] >= -1e-9
    assert gaps[-1] < 1e-3 * gap(start)


def test_fw_gap_is_not_negative_on_feasible_plans():
    lam, k, mu, m2, m3, cost = problem(seed=3)
    rng = np.random.default_rng(1)
    for _ in range(5):
        pi = rng.dirichlet(np.ones(mu.size), size=lam.size) * k[:, None]
        pi = np.minimum(pi, 1.0)
        pi *= (k / pi.sum(1))[:, None]  # may leave an entry just above 1
        assert ref.fw_gap(np.minimum(pi, 1.0), lam, k, mu, m2, m3, cost, THETA, BETA,
                          np.ones(mu.size, bool)) >= -1e-9


def test_latency_gradient_is_the_envelope_of_z():
    """At the optimal z the bound's derivative in z is 0, so moving the
    node rates with z held is the derivative the gap takes."""
    lam, k, mu, m2, m3, cost = problem()
    rates = lam @ uniform_plan(k, mu.size)
    lat, z = ref.latency_at(rates, lam.sum(), mu, m2, m3)
    for dz in (-1e-3, 1e-3):
        assert ref.latency_at(rates, lam.sum(), mu, m2, m3, z + dz)[0] >= lat - 1e-12
    step = np.zeros_like(rates)
    step[2] = 1e-6
    free = ref.latency_at(rates + step, lam.sum(), mu, m2, m3)[0]
    held = ref.latency_at(rates + step, lam.sum(), mu, m2, m3, z)[0]
    assert abs(free - held) < 1e-4 * abs(free - lat)  # second order in the step


def test_rollout_score_rebuilds_the_programs_rollout():
    import jax
    import jax.numpy as jnp

    from repro.serving import batched_rollout_scores
    from repro.storage import SimCarry, run_segment_raw

    lam, k, mu, m2, m3, cost = problem(r=30)
    allowed = np.ones(mu.size, bool)
    allowed[4] = False
    plans = np.stack([uniform_plan(k, mu.size, allowed),
                      program_solve(lam, k, mu, m2, m3, cost, allowed, 50)])
    d = np.linspace(2.0, 4.0, mu.size)
    rates = np.linspace(0.1, 0.3, mu.size)
    carry = SimCarry(dep=jnp.linspace(900.0, 1100.0, mu.size), t0=jnp.asarray(1000.0))
    key = jax.random.key(7)
    args = (jnp.asarray(lam, jnp.float32), jnp.asarray(d, jnp.float32),
            jnp.asarray(rates, jnp.float32), jnp.asarray(allowed))
    got, _ = batched_rollout_scores(
        carry, key, jnp.asarray(plans, jnp.float32), args[0], args[1], args[2], args[3],
        THETA * jnp.asarray([ref.support_cost(p, cost) for p in plans], jnp.float32),
        n_clients=lam.size, n_requests=200)
    for i, plan in enumerate(plans):
        mean, score = rollout.score(carry, key, plan, lam, d, rates, allowed, 200, cost, THETA)
        _, seg = run_segment_raw(carry, key, jnp.asarray(plan, jnp.float32), *args, 200)
        assert abs(float(jnp.mean(seg.latency)) - mean) <= 1e-5 * mean
        assert abs(float(got[i]) - score) <= 1e-5 * mean
        low = rollout.score(carry, key, plan, lam, d, rates, allowed, 200, cost, THETA,
                            fcfs.BF16)
        assert abs(low[1] - score) > 1e-3 * mean  # the control's walk reads off


def test_sketch_histogram_bins_as_the_program():
    import jax.numpy as jnp

    from repro.storage import SketchSpec, stream_from_values

    spec = SketchSpec(lo=1e-3, hi=1e4, bins=512)
    lat = np.random.default_rng(2).lognormal(1.0, 2.0, 5000).astype(np.float32)
    lat[:3] = [1e-5, 1e5, spec.lo]  # below, above and on an edge
    edges = fcfs.sketch_edges(spec.lo, spec.hi, spec.bins)
    want = fcfs.summary(lat.astype(np.float64), edges)
    got = stream_from_values(jnp.asarray(lat), spec)
    np.testing.assert_array_equal(np.asarray(got.hist), want["hist"])
    assert abs(float(got.m2) / 5000 - want["var"]) <= 1e-5 * want["var"]
