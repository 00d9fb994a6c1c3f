"""Rack failure domains in the planner, against the plain float64 reference
(`_rack_reference.py`, which imports nothing of the program): the
rack-capped projection and its KKT conditions, the per-rack round of the
deployed plan, the starts, a solve on a rack cluster, and a closed loop
through the loss of a whole rack."""
import numpy as np
import pytest

import _rack_reference as rr
import jax
import jax.numpy as jnp

from repro.core import (
    JLCMProblem,
    feasible_uniform,
    proportional_lb_pi,
    project_capped_simplex,
    rack_count,
    round_racks,
    solve,
    stack_problems,
)
from repro.core.jlcm import RACK_GAP_TOL
from repro.core.projection import _project_racks_impl
from repro.kernels.rack_projection import rack_project_pallas
from repro.storage import Cluster, StorageNode

TOL = 1e-3  # the solver's SUPPORT_TOL: an entry above it stores a chunk
CHUNK_MB = 4.194304


def rack_cluster(racks, hosts, jitter=0.0, seed=1):
    rng = np.random.default_rng(seed)
    return Cluster(tuple(
        StorageNode(name=f"r{d}h{h}", site="cell",
                    overhead_s=0.012 * (1.0 + jitter * rng.random()),
                    bandwidth_mbps=120.0, cost_per_chunk=1.0, rack=d)
        for d in range(racks) for h in range(hosts)
    ))


def tiered_rates(r, k, cluster, util=0.5):
    """Rates in three tiers 4:2:1 by contiguous thirds of the index, scaled
    to a mean host utilization of ``util``."""
    mu = np.asarray(cluster.moments(CHUNK_MB).mu, np.float64)
    lam = np.asarray([4.0, 2.0, 1.0])[(3 * np.arange(r)) // r]
    return lam / lam.sum() * util * mu.sum() / k


def projected(v, k, mask, racks):
    return np.asarray(project_capped_simplex(
        jnp.asarray(v, jnp.float32), jnp.asarray(k, jnp.float32), jnp.asarray(mask),
        racks=racks), np.float64)


def seeded_rows(seed, r=64, racks=4, hosts=5, down=None):
    rng = np.random.default_rng(seed)
    v = rng.normal(0.3, 0.6, (r, racks * hosts))
    k = rng.choice([1.0, 2.0, 3.0], r)
    mask = np.ones(v.shape, bool)
    if down is not None:
        mask[:, down * hosts:(down + 1) * hosts] = False
    return v, k, mask


@pytest.mark.parametrize("down", [None, 1], ids=["all-up", "rack-1-down"])
@pytest.mark.parametrize("seed", [0, 1])
def test_projection_matches_reference(seed, down):
    v, k, mask = seeded_rows(seed, down=down)
    got = projected(v, k, mask, 4)
    want = rr.project(v, k, mask, 4)
    # float32 bisection against float64 Dykstra: a few float32 ulps of v
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert rr.feasibility_error(got, k, 4, down=~mask[0]) < 2e-6


@pytest.mark.parametrize("seed", [2, 3])
def test_projection_meets_kkt_conditions(seed):
    """x_j = clip(v_j - tau - mu_d, 0, 1): free entries of a rack share one
    threshold; racks under their cap share tau; a capped rack's threshold
    is at least tau (mu_d >= 0); entries at 0 lie below their rack's
    threshold and entries at 1 above it plus 1."""
    v, k, mask = seeded_rows(seed, down=2)
    x = projected(v, k, mask, 4)
    eps = 1e-4
    assert np.allclose(x.sum(-1), k, atol=1e-5)
    assert x.min() >= 0.0 and x.max() <= 1.0 and not x[~mask].any()
    sums = rr.rack_sums(x, 4)
    assert sums.max() <= 1.0 + 1e-5
    checked = 0
    for i in range(v.shape[0]):
        thr = {}
        for d in range(4):
            sl = slice(5 * d, 5 * d + 5)
            free = mask[i, sl] & (x[i, sl] > 1e-6) & (x[i, sl] < 1 - 1e-6)
            if free.any():
                w = (v[i, sl] - x[i, sl])[free]
                assert np.ptp(w) < eps
                thr[d] = float(w.mean())
        under = [t for d, t in thr.items() if sums[i, d] < 1 - eps]
        if under:
            tau = under[0]
            assert np.ptp(under) < eps
            assert all(t >= tau - eps for d, t in thr.items() if sums[i, d] >= 1 - eps)
        for d, t in thr.items():
            sl = slice(5 * d, 5 * d + 5)
            on, xs, vs = mask[i, sl], x[i, sl], v[i, sl]
            assert np.all(vs[on & (xs <= 1e-6)] <= t + eps)
            assert np.all(vs[on & (xs >= 1 - 1e-6)] - 1.0 >= t - eps)
            checked += 1
    assert checked > 100


def kernel_case(case):
    """(v, k, mask, racks, rows the float64 reference checks) of one case;
    ``v`` is (lanes, r, m) for the vmapped case, else (r, m)."""
    if case == "4x5":
        v, k, mask = seeded_rows(0)
        return v, k, mask, 4, slice(None)
    if case == "5x3-r130":  # rows over two lane tiles, 3 hosts a rack
        rng = np.random.default_rng(7)
        v = rng.normal(0.3, 0.6, (130, 15))
        k = rng.choice([1.0, 2.0, 3.0, 4.0], 130)
        return v, k, np.ones(v.shape, bool), 5, slice(None)
    if case == "14x15-r1000":  # the f4 cell's shape, rack 3 down, plan-like values
        rng = np.random.default_rng(8)
        v = rng.normal(0.05, 0.08, (1000, 210))
        k = rng.choice([10.0, 11.0, 12.0, 13.0], 1000)
        mask = np.ones(v.shape, bool)
        mask[:, 45:60] = False
        return v, k, mask, 14, slice(None, None, 25)
    if case == "rack-down":  # rack 1 down everywhere, rack 2 in every third row too
        v, k, mask = seeded_rows(1, down=1)
        mask[::3, 10:15] = False
        return v, np.minimum(k, 2.0), mask, 4, slice(None)
    if case == "k-all-racks":  # k = the racks with an allowed host
        v, _, mask = seeded_rows(2)
        mask[::2, 5:10] = False
        mask[1::4, 0:5] = False
        k = mask.reshape(-1, 4, 5).any(-1).sum(-1).astype(np.float64)
        return v, k, mask, 4, slice(None)
    assert case == "vmap"  # two candidate lanes, as the batched solve runs it
    (v0, k0, m0), (v1, k1, m1) = seeded_rows(3, down=0), seeded_rows(4, down=2)
    return np.stack([v0, v1]), np.stack([k0, k1]), np.stack([m0, m1]), 4, slice(None)


@pytest.mark.parametrize(
    "case", ["4x5", "5x3-r130", "14x15-r1000", "rack-down", "k-all-racks", "vmap"]
)
def test_rack_kernel_matches_xla_and_reference(case):
    """The VMEM-resident kernel (interpret mode) against the XLA projection
    and the float64 reference: float32 ulps apart, within the caps, row
    sums k, nothing on a masked host."""
    v, k, mask, racks, rows = kernel_case(case)
    args = (jnp.asarray(v, jnp.float32), jnp.asarray(k, jnp.float32), jnp.asarray(mask))
    kernel = lambda v, k, m: rack_project_pallas(v, k, m, racks=racks, interpret=True)
    xla = lambda v, k, m: _project_racks_impl(v, k, m, iters=60, racks=racks)
    if v.ndim == 3:
        kernel, xla = jax.vmap(kernel), jax.vmap(xla)
    got = np.asarray(kernel(*args), np.float64)
    np.testing.assert_allclose(got, np.asarray(xla(*args), np.float64), atol=2e-6)
    for g, vv, kk, mm in zip(*(np.reshape(a, (-1,) + np.shape(a)[-2:]) for a in
                               (got, v, np.expand_dims(k, -1), mask))):
        kk = kk[:, 0]
        want = rr.project(vv[rows], kk[rows], mm[rows], racks)
        np.testing.assert_allclose(g[rows], want, atol=2e-6)
        assert not g[~mm].any()
        assert g.min() >= 0.0 and g.max() <= 1.0
        assert rr.rack_sums(g, racks).max() <= 1.0 + 2e-6
        assert np.max(np.abs(g.sum(-1) - kk) / np.maximum(kk, 1.0)) < 2e-6


def test_rack_round_invariants():
    v, k, mask = seeded_rows(4, down=3)
    pi = projected(v, k, mask, 4)
    lam = np.random.default_rng(4).uniform(0.5, 2.0, len(k))
    out, keep, merged = (np.asarray(a) for a in round_racks(
        jnp.asarray(pi, jnp.float32), jnp.asarray(mask), 4, TOL, jnp.asarray(lam)))
    assert np.allclose(out.sum(-1), pi.sum(-1), atol=1e-5)
    assert out.min() >= 0.0 and out.max() <= 1.0
    assert (out > 0).reshape(-1, 4, 5).sum(-1).max() <= 1  # one host per rack
    assert not out[~mask].any()
    assert np.array_equal(keep, out > 0)
    assert int(merged) == rr.spread_count(pi, 4, TOL) > 0
    before, after = pi.reshape(-1, 4, 5), out.reshape(-1, 4, 5)
    # a rack whose mass sits on one host keeps it there
    single = (before > TOL).sum(-1) == 1
    assert single.any()
    assert np.array_equal(after.argmax(-1)[single], before.argmax(-1)[single])
    # every host keeps its load up to one row's load on its rack, plus
    # what the kept racks held below TOL elsewhere
    row_load = lam[:, None] * before.sum(-1)
    slack = row_load.max() + (lam[:, None, None] * np.where(before > TOL, 0.0, before)).sum()
    assert np.abs(lam @ out - lam @ pi).max() <= slack


def test_rack_round_scatters_alike_hosts():
    """An even plan whose entries differ only in rounding is dealt out
    evenly over each rack's hosts, not sent to one host of every rack."""
    r, racks, hosts = 1000, 14, 15
    rng = np.random.default_rng(5)
    even = np.full((r, racks * hosts), 10.0 / (racks * hosts))
    even *= 1.0 + 1e-6 * rng.standard_normal(racks * hosts)  # the same noise in every row
    lam = np.asarray([4.0, 2.0, 1.0])[(3 * np.arange(r)) // r]
    out, _, _ = round_racks(jnp.asarray(even, jnp.float32),
                            jnp.ones(even.shape, bool), racks, TOL, jnp.asarray(lam))
    out = np.asarray(out, np.float64)
    assert (out > 0).sum(0).sum() == r * racks
    load, want = lam @ out, lam @ even
    # each host within one row's load of what the even plan sends it
    assert np.abs(load - want).max() <= lam.max() * 10.0 / racks + 1e-3


def test_starts_meet_the_caps():
    cl = rack_cluster(4, 5)
    mask = np.ones((32, cl.m), bool)
    mask[:, 5:10] = False
    mask[::3, 0] = False
    k = np.full(32, 3.0)
    uni = np.asarray(feasible_uniform(jnp.asarray(mask), jnp.asarray(k), 4), np.float64)
    assert rr.feasibility_error(uni, k, 4, down=~mask[1]) < 1e-6
    assert np.allclose(rr.rack_sums(uni, 4)[:, [0, 2, 3]], 1.0, atol=1e-6)
    lb = np.asarray(proportional_lb_pi(jnp.asarray(mask), jnp.asarray(k),
                                       cl.moments(CHUNK_MB), 4), np.float64)
    assert rr.feasibility_error(lb, k, 4, down=~mask[1]) < 1e-5


def test_rack_layout_is_checked():
    assert rack_count(None, 6) is None
    assert rack_count(np.repeat(np.arange(3), 2), 6) == 3
    with pytest.raises(ValueError, match="rack-major"):
        rack_count(np.array([0, 1, 0, 1, 2, 2]), 6)
    with pytest.raises(ValueError, match="rack-major"):
        rack_count(np.array([0, 0, 0, 1, 1]), 5)
    nodes = list(rack_cluster(2, 2).nodes)
    nodes[0] = StorageNode("x", "cell", 0.01, 100.0, 1.0)
    with pytest.raises(ValueError, match="every node has a rack"):
        Cluster(tuple(nodes)).domain
    assert rack_cluster(2, 2).domain.tolist() == [0, 0, 1, 1]


def test_stack_problems_carries_the_domain():
    cl = rack_cluster(3, 2)
    prob = JLCMProblem(lam=jnp.ones(4), k=jnp.full(4, 2.0), moments=cl.moments(CHUNK_MB),
                       cost=cl.cost, theta=1.0, domain=cl.domain)
    st = stack_problems([prob, prob])
    assert np.asarray(st.domain).shape == (2, 6) and rack_count(st.domain, 6) == 3
    with pytest.raises(ValueError, match="domain"):
        stack_problems([prob, prob._replace(domain=None)])


def test_solve_on_a_rack_cluster_passes_the_reference():
    """6 racks of 3 hosts, k = 3: a Theorem-1 plan within the caps, one
    host per (volume, rack), and near stationary on its own placement."""
    cl = rack_cluster(6, 3, jitter=0.2)
    r, k, theta, beta = 40, 3.0, 2e-3, 1e3
    lam = tiered_rates(r, k, cl)
    mom = cl.moments(CHUNK_MB)
    prob = JLCMProblem(lam=jnp.asarray(lam, jnp.float32), k=jnp.full(r, k, jnp.float32),
                       moments=mom, cost=cl.cost, theta=theta, domain=cl.domain)
    sol = solve(prob, max_iters=300, beta=beta)
    pi = np.asarray(sol.pi, np.float64)
    kk = np.full(r, k)
    assert rr.feasibility_error(pi, kk, 6) < 1e-5
    assert rr.spread_count(pi, 6, TOL) == 0
    assert int(sol.rack_merges) > 0
    assert np.array_equal(np.asarray(sol.n), (pi > TOL).sum(-1))
    mu, m2, m3 = (np.asarray(x, np.float64) for x in mom)
    args = (lam, kk, mu, m2, m3, np.ones(cl.m), theta, beta, np.ones(cl.m, bool), 6, TOL)
    gap = rr.fw_gap(pi, *args)
    start = np.asarray(feasible_uniform(jnp.ones((r, cl.m), bool), jnp.full(r, k), 6))
    # the even start holds every host of every rack, so its oracle ranges
    # over all of them: the solved plan's gap is a small share of it
    assert 0.0 <= gap < 0.1 * rr.fw_gap(start, *args)
    # the re-solve on the rounded placement stops near stationary there
    assert gap < 2 * RACK_GAP_TOL
    # one trace through both loops, trimmed to the iterations run
    trace = np.asarray(sol.objective_trace)
    assert trace.shape == (int(sol.iterations) + 1,) and np.isfinite(trace).all()


def test_closed_loop_through_a_rack_failure():
    from repro.scenarios import ScenarioSpec, run_scenario
    from repro.serving import AdaptiveReplanner

    cl = rack_cluster(6, 3)
    r, k = 24, 3.0
    lam = tiered_rates(r, k, cl)
    spec = ScenarioSpec(
        name="rack-loss", description="rack 2 down for segments 1-2",
        probes="rack-capped replans", expected="no mass on the lost rack",
        n_segments=4, requests_per_segment=300, chunk_mb=CHUNK_MB,
        lam=tuple(float(x) for x in lam), k=(k,) * r, theta=2e-3, replan_every=1,
        failures=tuple((2 * 3 + h, 1, 2) for h in range(3)),
    )
    seen = []
    orig = AdaptiveReplanner.replan

    def replan(self, rates, avail, **kw):
        pi = orig(self, rates, avail, **kw)
        seen.append((np.asarray(avail, bool), np.asarray(pi, np.float64)))
        return pi

    AdaptiveReplanner.replan = replan
    try:
        out = run_scenario(spec, "adaptive", seed=3, cluster=cl)
    finally:
        AdaptiveReplanner.replan = orig
    assert out.replans == 3 and len(seen) == 3
    assert [bool((~a).any()) for a, _ in seen] == [True, True, False]
    for avail, pi in seen:
        assert rr.feasibility_error(pi, np.full(r, k), 6, down=~avail) < 1e-5
        assert rr.spread_count(pi, 6, TOL) == 0
        assert not pi[:, ~avail].any()
    assert np.isfinite(out.seg_mean).all()
