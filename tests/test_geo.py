"""Geo-aware client fabric: fabric degeneracy, geo solver path, fleet
simulation, geo scenarios + the geo closed loop (ISSUE acceptance)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    JLCMProblem,
    ServiceMoments,
    feasible_uniform,
    geo_problem,
    geo_shared_z_latency,
    node_mixture_moments,
    make_geo,
    pair_moments,
    shared_z_latency,
    solve,
    solve_batch,
)
from repro.scenarios import get_scenario, run_geo_scenario, scenario_names
from repro.serving import AdaptiveReplanner, EwmaMomentEstimator
from repro.storage import (
    ClientSite,
    GeoFabric,
    fleet_one_raw,
    generate_geo_workload,
    geo_testbed,
    simulate_fleet,
    simulate_geo_segment,
    simulate_geo_segments,
    tahoe_testbed,
)

LAM = jnp.asarray([0.036, 0.028, 0.016, 0.012])
K = jnp.asarray([4.0, 4.0, 6.0, 6.0])

# chunk sizes of the fig8/fig13 catalogs (§V.B: 150 MB files, k quarters
# 6/7/6/4) plus the paper's (7,4)-on-50MB measurement chunk
CATALOG_CHUNKS = (150.0 / 6, 150.0 / 7, 150.0 / 4, 12.5)


@pytest.fixture(scope="module")
def fabric():
    return geo_testbed()


@pytest.fixture(scope="module")
def cluster():
    return tahoe_testbed()


class TestFabric:
    def test_degenerate_single_site_reproduces_cluster_exactly(self, cluster):
        """ISSUE acceptance: the one-client-site fabric reproduces
        Cluster.moments() bit-for-bit across the fig8/fig13 catalog chunk
        sizes (the degeneracy anchor for every existing calibration)."""
        deg = GeoFabric.single_site(cluster)
        assert deg.n_sites == 1
        for chunk in CATALOG_CHUNKS:
            got = deg.moments(chunk)
            want = cluster.moments(chunk)
            for g, w in zip(got, want):
                np.testing.assert_array_equal(np.asarray(g[0]), np.asarray(w))

    def test_reference_row_of_testbed_is_cluster(self, fabric, cluster):
        """geo_testbed row 0 (NJ) is the paper's own client placement."""
        assert fabric.site_names == ("NJ", "TX", "CA", "EU")
        np.testing.assert_array_equal(
            np.asarray(fabric.overheads()[0]), np.asarray(cluster.overheads())
        )
        np.testing.assert_array_equal(
            np.asarray(fabric.bandwidths()[0]), np.asarray(cluster.bandwidths())
        )

    def test_locality_profile(self, fabric):
        """Each co-located client sees its own site faster than NJ does."""
        ovh = np.asarray(fabric.overheads())
        tx, ca = fabric.site_index("TX"), fabric.site_index("CA")
        assert (ovh[tx, 4:8] < ovh[0, 4:8]).all()  # TX client -> TX nodes
        assert (ovh[ca, 8:12] < ovh[0, 8:12]).all()  # CA client -> CA nodes
        assert (ovh > 0).all()

    def test_missing_profile_rejected(self, cluster):
        bad = ClientSite(
            name="X", rtt_s={"NJ": 0.0}, bandwidth_scale={"NJ": 1.0}
        )
        with pytest.raises(ValueError, match="lacks a profile"):
            GeoFabric(cluster=cluster, sites=(bad,))

    def test_nonpositive_bandwidth_scale_rejected(self, cluster):
        bad = ClientSite(
            name="X",
            rtt_s={"NJ": 0.0, "TX": 0.0, "CA": 0.0},
            bandwidth_scale={"NJ": 0.0, "TX": 1.0, "CA": 1.0},
        )
        with pytest.raises(ValueError, match="bandwidth_scale"):
            GeoFabric(cluster=cluster, sites=(bad,))

    def test_nonpositive_overhead_rejected(self, cluster):
        bad = ClientSite(
            name="X",
            rtt_s={"NJ": -5.0, "TX": 0.0, "CA": 0.0},
            bandwidth_scale={"NJ": 1.0, "TX": 1.0, "CA": 1.0},
        )
        with pytest.raises(ValueError, match="overhead"):
            GeoFabric(cluster=cluster, sites=(bad,))


class TestGeoSolver:
    def test_degenerate_problem_collapses_and_solves_bit_for_bit(self, cluster):
        """ISSUE acceptance: a single-client-site geo problem reproduces
        the current solver output exactly (pi bitwise, objective exact)."""
        mom = cluster.moments(12.5)
        plain = JLCMProblem(
            lam=LAM, k=K, moments=mom, cost=cluster.cost, theta=2.0
        )
        site_mom = ServiceMoments(
            mu=mom.mu[None], m2=mom.m2[None], m3=mom.m3[None]
        )
        gprob = geo_problem(
            LAM, K, site_mom, np.ones((4, 1)), cluster.cost, 2.0
        )
        assert gprob.geo is None  # C == 1 collapses to the plain path
        sol = solve(plain, max_iters=150)
        gsol = solve(gprob, max_iters=150)
        np.testing.assert_array_equal(np.asarray(gsol.pi), np.asarray(sol.pi))
        assert float(gsol.objective) == float(sol.objective)
        assert float(gsol.latency_tight) == float(sol.latency_tight)

    def test_identical_sites_match_plain_path(self, cluster):
        """C identical reference sites under any mix are mathematically the
        plain problem; the general (r, m)-fold path must agree to float32
        tolerance (pi within the acceptance 3e-7 is not required here —
        that is the degenerate case above — but it lands ~1e-6)."""
        mom = cluster.moments(12.5)
        site_mom = ServiceMoments(
            mu=jnp.broadcast_to(mom.mu, (4, 12)),
            m2=jnp.broadcast_to(mom.m2, (4, 12)),
            m3=jnp.broadcast_to(mom.m3, (4, 12)),
        )
        gprob = geo_problem(
            LAM, K, site_mom, np.full((4, 4), 0.25), cluster.cost, 2.0
        )
        assert gprob.geo is not None
        plain = JLCMProblem(
            lam=LAM, k=K, moments=mom, cost=cluster.cost, theta=2.0
        )
        sol = solve(plain, max_iters=150)
        gsol = solve(gprob, max_iters=150)
        np.testing.assert_allclose(
            np.asarray(gsol.pi), np.asarray(sol.pi), atol=1e-4
        )
        np.testing.assert_allclose(
            float(gsol.objective), float(sol.objective), rtol=1e-5
        )
        # function-level equivalence at a fixed iterate, not just at optima
        pi0 = feasible_uniform(jnp.ones((4, 12), bool), K)
        z = jnp.asarray(5.0)
        np.testing.assert_allclose(
            float(geo_shared_z_latency(pi0, z, LAM, gprob.geo)),
            float(shared_z_latency(pi0, z, LAM, mom)),
            rtol=1e-6,
        )

    def test_mixture_moments_shapes_and_values(self, fabric):
        geo = make_geo(fabric.moments(12.5), fabric.uniform_mix(4))
        p1, p2, p3 = pair_moments(geo)
        assert p1.shape == (4, 12)
        node_mom = node_mixture_moments(LAM, geo)
        assert node_mom.m2.shape == (12,)
        # mixture raw moments are convex combinations: bounded by extremes
        assert (np.asarray(p1) <= np.asarray(geo.m1).max(0) + 1e-6).all()
        assert (np.asarray(p1) >= np.asarray(geo.m1).min(0) - 1e-6).all()
        ServiceMoments(
            mu=1.0 / p1, m2=p2, m3=p3
        ).validate()  # mixtures are valid distributions
        node_mom.validate()

    def test_placement_follows_the_client_mix(self, fabric):
        """The tentpole claim at the solver level: moving the client
        population toward TX moves dispatch mass onto TX nodes relative
        to the NJ-anchored plan (locality now pays)."""
        site_mom = fabric.moments(12.5)
        r = 4
        nj_mix = np.tile([0.9, 0.04, 0.03, 0.03], (r, 1))
        tx_mix = np.tile([0.04, 0.9, 0.03, 0.03], (r, 1))
        sols = solve_batch(
            [
                geo_problem(LAM, K, site_mom, nj_mix, fabric.cluster.cost, 2.0),
                geo_problem(LAM, K, site_mom, tx_mix, fabric.cluster.cost, 2.0),
            ],
            max_iters=300,
        )
        mass_tx_under_nj = float(np.asarray(sols.pi)[0][:, 4:8].sum())
        mass_tx_under_tx = float(np.asarray(sols.pi)[1][:, 4:8].sum())
        assert mass_tx_under_tx > mass_tx_under_nj + 0.5, (
            mass_tx_under_nj,
            mass_tx_under_tx,
        )

    def test_solve_batch_sweeps_mixes_matches_sequential(self, fabric):
        site_mom = fabric.moments(12.5)
        rng = np.random.default_rng(0)
        mixes = [rng.dirichlet(np.ones(4), size=4) for _ in range(3)]
        probs = [
            geo_problem(LAM, K, site_mom, mx, fabric.cluster.cost, 2.0)
            for mx in mixes
        ]
        batch = solve_batch(probs, max_iters=120)
        for i, p in enumerate(probs):
            single = solve(p, max_iters=120)
            np.testing.assert_allclose(
                np.asarray(batch.pi[i]), np.asarray(single.pi), atol=2e-5
            )

    def test_stacking_mixed_geo_none_rejected(self, fabric, cluster):
        site_mom = fabric.moments(12.5)
        gp = geo_problem(
            LAM, K, site_mom, fabric.uniform_mix(4), fabric.cluster.cost, 2.0
        )
        plain = JLCMProblem(
            lam=LAM, k=K, moments=cluster.moments(12.5), cost=cluster.cost,
            theta=2.0,
        )
        with pytest.raises(ValueError, match="geo"):
            solve_batch([gp, plain])


class TestGeoSimulator:
    def test_workload_marks_match_rates(self, fabric):
        lam_cs = np.asarray([[1.0, 2.0], [3.0, 2.0]])  # (C=2, r=2)
        t, fid, site = generate_geo_workload(
            jax.random.key(0), lam_cs, 40000
        )
        assert float(t[-1]) > 0 and (np.diff(np.asarray(t)) >= 0).all()
        frac = np.zeros((2, 2))
        for c in range(2):
            for i in range(2):
                frac[c, i] = float(
                    ((np.asarray(site) == c) & (np.asarray(fid) == i)).mean()
                )
        np.testing.assert_allclose(frac, lam_cs / lam_cs.sum(), atol=0.01)

    def test_device_segments_match_host_loop(self, fabric):
        pi = feasible_uniform(jnp.ones((4, fabric.m), bool), K)
        lam_cs = np.asarray(fabric.uniform_mix(4)).T * np.asarray(LAM)
        lam_cs_seq = np.stack([lam_cs, 1.5 * lam_cs, 0.7 * lam_cs])
        key = jax.random.key(5)
        dev = simulate_geo_segments(
            key, pi, lam_cs_seq, fabric, 12.5, 400
        )
        seg_keys = jax.random.split(key, 3)
        carry = None
        for s in range(3):
            res, carry = simulate_geo_segment(
                seg_keys[s], pi, lam_cs_seq[s], fabric, 12.5, 400, carry=carry
            )
            np.testing.assert_allclose(
                np.asarray(dev.latency[s]), np.asarray(res.latency), rtol=1e-6
            )
            np.testing.assert_array_equal(
                np.asarray(dev.site_id[s]), np.asarray(res.site_id)
            )

    def test_pair_observations_partition_node_counts(self, fabric):
        pi = feasible_uniform(jnp.ones((4, fabric.m), bool), K)
        lam_cs = np.asarray(fabric.uniform_mix(4)).T * np.asarray(LAM)
        res, _ = simulate_geo_segment(
            jax.random.key(1), pi, lam_cs, fabric, 12.5, 600
        )
        counts = np.asarray(res.obs.count)  # (C, m)
        assert counts.shape == (4, fabric.m)
        k_req = np.asarray([4, 4, 6, 6])[np.asarray(res.file_id)]
        assert counts.sum() == k_req.sum()  # every chunk read attributed
        # each site's rows only accrue from its own requests
        for c in range(4):
            n_c = int((np.asarray(res.site_id) == c).sum())
            assert counts[c].sum() <= n_c * 6

    def test_remote_site_sees_higher_latency(self, fabric):
        """EU (remote from every DC) must empirically pay more than the
        co-located reference client under the same dispatch."""
        pi = feasible_uniform(jnp.ones((4, fabric.m), bool), K)
        lam_cs = np.asarray(fabric.uniform_mix(4)).T * np.asarray(LAM)
        res, _ = simulate_geo_segment(
            jax.random.key(2), pi, lam_cs, fabric, 12.5, 4000
        )
        lat = np.asarray(res.latency)
        site = np.asarray(res.site_id)
        eu = fabric.site_index("EU")
        assert lat[site == eu].mean() > lat[site == 0].mean()


class TestFleet:
    def test_fleet_matches_per_seed_kernel_bitwise(self, fabric):
        pi = feasible_uniform(jnp.ones((4, fabric.m), bool), K)
        lam_cs = jnp.asarray(
            np.asarray(fabric.uniform_mix(4)).T * np.asarray(LAM), jnp.float32
        )
        key = jax.random.key(7)
        n, s = 800, 6
        fleet = simulate_fleet(key, pi, lam_cs, fabric, 12.5, n, s)
        assert fleet.latency.shape == (s, n - n // 10)
        d, rates = fabric.service_params(12.5)
        keys = jax.random.split(key, s)
        for i in (0, 3, 5):
            lat_i, fid_i, site_i, busy_i, hit_i = fleet_one_raw(
                keys[i], pi, lam_cs, d, rates, n, n // 10
            )
            assert hit_i is None  # no cache tier in this run
            np.testing.assert_allclose(
                np.asarray(fleet.latency[i]), np.asarray(lat_i), rtol=1e-6
            )
            np.testing.assert_array_equal(
                np.asarray(fleet.file_id[i]), np.asarray(fid_i)
            )

    def test_per_site_mean_nan_for_silent_sites(self, fabric):
        """Contract: a client site with zero requests reports NaN, never a
        0-count mean (same convention as SimResult.per_file_mean)."""
        pi = feasible_uniform(jnp.ones((4, fabric.m), bool), K)
        lam_cs = np.asarray(fabric.uniform_mix(4)).T * np.asarray(LAM)
        lam_cs[2] = 0.0  # CA clients silent
        fleet = simulate_fleet(
            jax.random.key(9), pi, jnp.asarray(lam_cs, jnp.float32),
            fabric, 12.5, 600, 4,
        )
        means = np.asarray(fleet.per_site_mean(4))
        assert np.isnan(means[2])
        assert np.isfinite(means[[0, 1, 3]]).all()

    def test_fleet_agrees_with_segment_simulator_statistically(self, fabric):
        """Two independent implementations of the same system (fleet
        kernel vs availability-aware segment path) must agree on mean
        latency — the cross-validation the benchmark also asserts."""
        pi = feasible_uniform(jnp.ones((4, fabric.m), bool), K)
        lam_cs = jnp.asarray(
            np.asarray(fabric.uniform_mix(4)).T * np.asarray(LAM), jnp.float32
        )
        fleet = simulate_fleet(
            jax.random.key(3), pi, lam_cs, fabric, 12.5, 3000, 8
        )
        res, _ = simulate_geo_segment(
            jax.random.key(4), pi, lam_cs, fabric, 12.5, 3000
        )
        a = float(fleet.mean_latency())
        b = float(np.asarray(res.latency)[300:].mean())
        assert abs(a - b) / b < 0.2, (a, b)


class TestGeoScenarios:
    def test_registered_and_wellformed(self, fabric):
        for name in ("geo-client-shift", "cross-site-outage"):
            assert name in scenario_names()
            spec = get_scenario(name)
            assert spec.is_geo and spec.n_sites == 4
            spec.validate(fabric.m)
            spec.validate_geo_fabric(fabric)

    def test_validation_rejects_malformed_geo(self):
        spec = get_scenario("geo-client-shift")
        bad = dataclasses.replace(spec, mix_trace=spec.mix_trace[:3])
        with pytest.raises(ValueError, match="mix_trace"):
            bad.validate(12)
        bad = dataclasses.replace(
            spec, mix_trace=((0.5, 0.5, 0.5, 0.5),) * spec.n_segments
        )
        with pytest.raises(ValueError, match="distribution"):
            bad.validate(12)
        bad = dataclasses.replace(
            spec, failures=((0, 2, 5),), repair_rate=0.05
        )
        with pytest.raises(ValueError, match="repair"):
            bad.validate(12)
        bad = dataclasses.replace(
            get_scenario("steady-state"),
            egress_degrade=(("NJ", 0, 1, 2.0, 0.5),),
        )
        with pytest.raises(ValueError, match="sites"):
            bad.validate(12)

    def test_egress_scales_hit_cross_pairs_only(self, fabric):
        spec = get_scenario("cross-site-outage")
        ovh, bw = spec.egress_scales(fabric)
        nj_client = fabric.site_index("NJ")
        # NJ-local clients untouched, remote clients scaled on NJ columns
        assert (ovh[2:6, nj_client, :] == 1.0).all()
        assert (ovh[2:6, 1:, 0:4] > 1.0).all()
        assert (bw[2:6, 1:, 0:4] < 1.0).all()
        # non-NJ columns and out-of-window segments untouched
        assert (ovh[2:6, :, 4:] == 1.0).all()
        assert (ovh[[0, 1, 6, 7]] == 1.0).all()

    @pytest.fixture(scope="class")
    def shift_outcomes(self):
        spec = get_scenario("geo-client-shift").scaled(0.2, min_requests=300)
        return {
            policy: run_geo_scenario(spec, policy, seed=0)
            for policy in ("static", "adaptive")
        }

    def test_geo_closed_loop_beats_geo_oblivious_static(self, shift_outcomes):
        """ISSUE acceptance: adaptive re-placement beats the static
        geo-oblivious plan on mean latency while the population
        migrates."""
        ada, sta = shift_outcomes["adaptive"], shift_outcomes["static"]
        assert ada.replans > 0 and sta.replans == 0
        assert np.isfinite(ada.mean) and np.isfinite(sta.mean)
        assert ada.mean < sta.mean
        assert ada.site_mean.shape == (4,)
        assert "site_means" in ada.row()


class TestGeoReplanner:
    def test_replan_shapes_and_mask(self, fabric):
        est = EwmaMomentEstimator(prior=fabric.moments(12.5))
        rp = AdaptiveReplanner(
            k=np.asarray(K),
            cost=np.asarray(fabric.cluster.cost),
            theta=2.0,
            estimator=est,
            max_iters=150,
        )
        lam_cs = np.asarray(fabric.uniform_mix(4)).T * np.asarray(LAM)
        avail = np.ones((fabric.m,), bool)
        avail[0] = False
        pi = rp.replan(lam_cs, avail)
        assert pi.shape == (4, fabric.m)
        assert (pi[:, 0] <= 1e-6).all()
        np.testing.assert_allclose(pi.sum(-1), np.asarray(K), atol=1e-2)
        assert rp.replans == 1

    @pytest.mark.parametrize("extra", ["cache", "repair", "domain"])
    def test_geo_replan_rejects_cache_repair_and_domains(self, fabric, extra):
        """The (C, r) geo case takes no hot tier, repair flow or failure
        domains: asking for one raises before any state changes."""
        from repro.storage import CacheModel, build_repair_flow

        avail = np.ones((fabric.m,), bool)
        kw, fields = {}, {}
        if extra == "cache":
            fields["cache"] = CacheModel(
                file_bytes=np.full((4,), 50.0 * 2**20),
                capacity_bytes=100.0 * 2**20,
                hit_latency=0.5,
                hot_price_per_mb=0.02,
            )
        elif extra == "repair":
            placement = np.ones((4, fabric.m), bool)
            avail[0] = False
            kw["repair"] = build_repair_flow(placement, np.asarray(K), avail, 0.05)
            assert kw["repair"].active
        else:
            fields["domain"] = np.arange(fabric.m) // (fabric.m // 3)
        rp = AdaptiveReplanner(
            k=np.asarray(K),
            cost=np.asarray(fabric.cluster.cost),
            theta=2.0,
            estimator=EwmaMomentEstimator(prior=fabric.moments(12.5)),
            max_iters=20,
            **fields,
        )
        lam_cs = np.asarray(fabric.uniform_mix(4)).T * np.asarray(LAM)
        with pytest.raises(ValueError, match="geo"):
            rp.replan(lam_cs, avail, **kw)
        assert rp.replans == 0 and rp.last_raw is None

    def test_estimator_tracks_pair_moments_from_geo_obs(self, fabric):
        """Seeded with a wrong prior, the (C, m) EWMA converges toward the
        fabric's true per-pair moments on a stationary geo trace."""
        true = fabric.moments(12.5)
        wrong = ServiceMoments(
            mu=true.mu * 1.6, m2=true.m2 * 0.5, m3=true.m3 * 0.4
        )
        est = EwmaMomentEstimator(prior=wrong, alpha=0.5)
        pi = feasible_uniform(jnp.ones((4, fabric.m), bool), K)
        lam_cs = np.asarray(fabric.uniform_mix(4)).T * np.asarray(LAM)
        carry = None
        for s in range(8):
            res, carry = simulate_geo_segment(
                jax.random.key(300 + s), pi, lam_cs, fabric, 12.5, 2500,
                carry=carry,
            )
            est.update(res.obs)
        np.testing.assert_allclose(
            est.m1, np.asarray(true.mean), rtol=0.15
        )
