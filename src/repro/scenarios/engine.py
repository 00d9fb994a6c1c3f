"""Scenario engine: run a spec under a dispatch policy.

Three policies, deliberately spanning the control spectrum:

* ``static``    — Algorithm JLCM once, from the *pre-run ground-truth*
  moments on the healthy cluster; the plan never changes. This is the
  paper's own operating model (plan offline, dispatch forever).
* ``oblivious`` — the Fig.-9 'Oblivious LB' baseline: rate-proportional
  dispatch on full support, never re-planned. No optimization at all.
* ``adaptive``  — closed loop: after every segment the engine feeds the
  simulator's node-side service observations to an EWMA moment estimator
  and the observed per-file traffic to an EWMA rate estimator; at each
  re-plan boundary (``spec.replan_every``) it re-solves JLCM from those
  *estimated* inputs plus the current health mask — warm- and cold-started
  candidates in one batched ``solve_batch`` call, arbitrated by a short
  exact-simulator rollout from the live queue state under the estimated
  service family (`serving.router.AdaptiveReplanner`).

All solving policies (static's one-shot plan, every adaptive re-plan, and
the rollout scoring that arbitrates candidates) optimize the scenario's
*composed* objective when the spec declares a tenant mix
(``ScenarioSpec.objective()`` -> ``core.objectives.ObjectiveSpec``);
multi-class scenarios additionally report per-class empirical mean/p99.

Open-loop policies run the whole schedule as ONE nested-``lax.scan``
device call (``simulate_segments``); the closed loop alternates compiled
segment calls with host-side re-planning. All policies see identical
arrival streams and service draws for a given seed (same PRNG splits), so
differences are attributable to the plans alone.

Detection model: the adaptive policy learns moments and rates only from
measurements, but node availability is taken from the scenario's health
trace at each segment boundary — i.e. we assume a health checker flags
dead nodes within one segment, and study the value of *re-planning*, not
of failure detection.

Repair traffic (``spec.repair_rate > 0``): the physical reconstruction
process is policy-independent — whoever plans dispatch, the chunks that
sat on a dead node must be re-built — so the engine injects the repair
rows (`storage.repair.repair_schedule`, derived from the *initial* JLCM
plan's placement: that is where the bytes physically live) into the
simulation under EVERY policy, as extra (pi, lam) rows activated per
segment through the simulator's per-file rate scaling. What differs is
the control plane: static/oblivious are repair-*oblivious* by
construction, while the adaptive policy passes each segment's
``RepairFlow`` into ``AdaptiveReplanner.replan`` (repair-aware: candidate
solves see the reconstruction load and jointly optimize the repair reads'
dispatch). ``repair_aware=False`` runs the ablation — a closed loop that
re-plans around the failure but never sees the repair load. All reported
statistics cover client requests only (``file_id < r``); repair traffic
is load, not workload.

Cache-tier scenarios (``spec.cache_capacity_mb > 0``): the simulator runs
the hot tier in the data plane (TTL cache in front of the FCFS queues,
``storage/cache.py``), so hits never load a storage node and return at
the hot tier's latency. Policies differ only in the control plane: static
and oblivious deploy the Che deploy-time TTLs (design rates) and never
move; the adaptive loop feeds its rate estimator MISS traffic only
(``EwmaRateEstimator.update_misses``), inverts misses back to raw rates
through the deployed TTLs, re-derives TTLs (promotion/demotion) and
re-plans the warm tier cache-aware at every boundary. Hot-tier up/down is
a binary health signal like node availability — a transition *forces* a
replan so the warm tier is ready before the miss storm arrives. All
client statistics include hits (that is the latency clients experience);
``hit_frac`` and ``storage_cost`` (time-averaged warm plan cost + the
provisioned hot tier) join the outcome.

Geo scenarios (``spec.sites`` set) run through :func:`run_geo_scenario`
against the 4-client-site fabric: per-(client-site, node) service
sampling, a per-segment client-population mix schedule, optional egress
degradation — and a geo-aware closed loop (``GeoAdaptiveReplanner``)
whose static baseline is deliberately *geo-oblivious* (the paper's
single-implicit-client plan). See that function's docstring.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import diag
from repro.core import (
    Hierarchy,
    JLCMProblem,
    materialize,
    proportional_lb_pi,
    rack_count,
    solve,
    solve_hierarchical,
)
from repro.serving import (
    AdaptiveReplanner,
    EwmaMomentEstimator,
    EwmaRateEstimator,
    GeoAdaptiveReplanner,
    HierarchicalReplanner,
)
from repro.storage import (
    Cluster,
    GeoFabric,
    build_repair_flow,
    geo_testbed,
    per_class_latency_stats,
    repair_schedule,
    simulate_geo_segment,
    simulate_geo_segments,
    simulate_segment,
    simulate_segments,
    tahoe_testbed,
)

from .spec import ScenarioSpec

POLICIES = ("static", "oblivious", "adaptive")


@dataclasses.dataclass(frozen=True)
class ScenarioOutcome:
    """Per-policy result of one scenario run."""

    scenario: str
    policy: str
    seg_mean: np.ndarray  # (S,) mean latency per segment
    seg_p99: np.ndarray  # (S,) p99 latency per segment
    mean: float  # overall mean latency
    p99: float  # overall p99 latency
    degraded_frac: float  # fraction of requests that hit a down node
    replans: int  # closed-loop re-solves performed
    repair_frac: float = 0.0  # reconstruction reads / all simulated requests
    # per-tenant-class empirical stats (multi-class scenarios only)
    class_mean: np.ndarray | None = None  # (C,)
    class_p99: np.ndarray | None = None  # (C,)
    # per-client-site empirical mean latency (geo scenarios only)
    site_mean: np.ndarray | None = None  # (C_sites,)
    # cache-tier scenarios only: fraction of client requests served by the
    # hot tier, and total storage cost = time-averaged warm-tier plan cost
    # + the provisioned (constant) hot-tier cost
    hit_frac: float = 0.0
    storage_cost: float = float("nan")
    # closed-loop solver telemetry: per-replan iteration count of the
    # deployed candidate and wall seconds of the (batched) solve; empty
    # for open-loop policies
    solve_iters: tuple = ()
    solve_walls: tuple = ()
    # per-replan wall seconds of the rollout arbitration (the fused
    # batched candidate-scoring call, `serving.router.
    # batched_rollout_scores`); empty for open-loop policies and for
    # replanners that never roll out (hierarchical)
    rollout_walls: tuple = ()
    # hierarchical loop only: clusters re-solved per replan (full replans
    # report the whole cluster count, incremental ones just the movers)
    resolved_counts: tuple = ()

    @property
    def p99_windowed(self) -> float:
        """Mean of the per-segment p99s — the SLO-dashboard view.

        The pooled :attr:`p99` of a run with a storm window is a quantile
        of the storm alone (the worst 1% of all requests land inside the
        window for every policy, so pooled tails compare storm physics,
        not plans). Averaging the p99 of each reporting window instead —
        exactly how production SLO dashboards aggregate — weighs every
        segment's tail, so a policy that drags slow nodes into its
        dispatch sets during *healthy* windows pays for it here.
        """
        return float(np.nanmean(self.seg_p99))

    def row(self) -> dict:
        out = dict(
            scenario=self.scenario,
            policy=self.policy,
            mean=round(self.mean, 3),
            p99=round(self.p99, 3),
            p99_windowed=round(self.p99_windowed, 3),
            degraded_frac=round(self.degraded_frac, 4),
            replans=self.replans,
            repair_frac=round(self.repair_frac, 4),
            seg_means="|".join(f"{v:.2f}" for v in self.seg_mean),
            solve_iters="|".join(str(int(v)) for v in self.solve_iters),
            solve_wall_ms="|".join(
                f"{1e3 * v:.1f}" for v in self.solve_walls
            ),
            rollout_wall_ms="|".join(
                f"{1e3 * v:.1f}" for v in self.rollout_walls
            ),
        )
        if self.resolved_counts:
            out["resolved_clusters"] = "|".join(
                str(int(v)) for v in self.resolved_counts
            )
        if self.class_mean is not None:
            out["class_means"] = "|".join(f"{v:.2f}" for v in self.class_mean)
            out["class_p99s"] = "|".join(f"{v:.2f}" for v in self.class_p99)
        if self.site_mean is not None:
            out["site_means"] = "|".join(f"{v:.2f}" for v in self.site_mean)
        if np.isfinite(self.storage_cost):
            out["hit_frac"] = round(self.hit_frac, 4)
            out["storage_cost"] = round(self.storage_cost, 3)
        return out


def _segment_stats(
    lat: np.ndarray, include: np.ndarray | None = None
) -> tuple[np.ndarray, np.ndarray, float, float]:
    """Per-window (segment) and pooled latency statistics.

    ``lat`` is (S, N); ``include`` an optional (S, N) boolean mask of the
    requests that count (client rows — repair traffic is background
    load). Returns ``(seg_mean, seg_p99, mean, p99)``. A window with no
    included requests reports NaN, never a 0-count statistic — the same
    contract as ``SimResult.per_file_mean``. This is the materialized
    counterpart of the fleet path's per-window quantile sketches
    (`storage.streaming.windowed_quantile_mean`).
    """
    if include is None:
        seg_mean = lat.mean(-1)
        seg_p99 = np.percentile(lat, 99, axis=-1)
        pool = lat.reshape(-1)
    else:
        seg_mean = np.asarray(
            [lat[s][include[s]].mean() if include[s].any() else np.nan
             for s in range(lat.shape[0])]
        )
        seg_p99 = np.asarray(
            [np.percentile(lat[s][include[s]], 99)
             if include[s].any() else np.nan
             for s in range(lat.shape[0])]
        )
        pool = lat[include]
    return seg_mean, seg_p99, float(pool.mean()), float(
        np.percentile(pool, 99)
    )


def initial_plan(
    spec: ScenarioSpec,
    cluster: Cluster,
    *,
    max_iters: int = 300,
    cache_aware: bool = True,
):
    """The pre-run JLCM plan from ground-truth healthy-cluster moments.

    Solves the scenario's *composed* objective (tenant weights / deadlines
    from ``spec.objective()``) so static and adaptive policies both start
    from the plan the scenario actually asks for. Returns
    ``(pi, moments, solution)`` — the full solution carries the Lemma-4
    placement that fixes where chunks physically live (the repair
    inventory and the batched codec both read it,
    ``storage.codec.CodecPlan.from_solution``).

    Cache-tier scenarios solve cache-aware even for the static policy:
    deploy-time planning legitimately knows the catalog's design rates, so
    the static plan sizes the warm tier for the *steady-state miss*
    traffic (Che hit rates at ``spec.lam``) — the production artifact a
    team that read the f4 papers would ship. What static cannot do is
    react: to cold-cache warmup storms, to hot-tier outages, or to rate
    drift (its hit rates and TTLs are frozen at design time).

    ``cache_aware=False`` is the CACHE-OBLIVIOUS baseline: the plan is
    solved for the raw design rates as if the hot tier did not exist (the
    cache still runs in the data plane — the planner just never hears
    about it). It over-provisions the warm tier for traffic the cache
    will absorb: wider support (higher storage cost) that drags slow
    nodes into the dispatch sets.
    """
    mom = cluster.moments(spec.chunk_mb)
    cache = (
        spec.cache_model().spec(np.asarray(spec.lam))
        if spec.has_cache and cache_aware
        else None
    )
    prob = JLCMProblem(
        lam=jnp.asarray(spec.lam, jnp.float32),
        k=jnp.asarray(spec.k, jnp.float32),
        moments=mom,
        cost=cluster.cost,
        theta=spec.theta,
        objective=spec.objective(),
        cache=cache,
        domain=cluster.domain,
    )
    sol = solve(prob, max_iters=max_iters)
    return np.asarray(sol.pi), mom, sol


def oblivious_plan(spec: ScenarioSpec, cluster: Cluster) -> np.ndarray:
    """Fig.-9 'Oblivious LB': mu-proportional dispatch on full support."""
    mom = cluster.moments(spec.chunk_mb)
    mask = jnp.ones((spec.r, cluster.m), bool)
    racks = rack_count(cluster.domain, cluster.m)
    return np.asarray(proportional_lb_pi(mask, jnp.asarray(spec.k), mom, racks))


def run_scenario(
    spec: ScenarioSpec,
    policy: str = "adaptive",
    *,
    seed: int = 0,
    cluster: Cluster | None = None,
    requests_per_segment: int | None = None,
    pi0: np.ndarray | None = None,
    placement0: np.ndarray | None = None,
    repair_aware: bool = True,
    cache_aware: bool = True,
    hierarchy: Hierarchy | None = None,
) -> ScenarioOutcome:
    """Simulate ``spec`` under ``policy``; see module docstring.

    ``hierarchy`` (``core.aggregate.Hierarchy`` built from the spec's
    catalog) switches every solving policy onto the hierarchical path:
    the initial plan is a cluster-granularity ``solve_hierarchical``
    disaggregated by gather, and the adaptive policy runs
    ``serving.HierarchicalReplanner`` (full re-solves on moment/mask
    drift, ``resolve_incremental`` otherwise) instead of the dense
    per-file loop — the only way a 10^5-file catalog re-plans inside a
    segment budget. Composes only with plain scenarios (no geo fabric,
    cache tier, repair traffic, or tenant mix).

    ``pi0`` lets callers reuse an already-solved initial plan (the suite
    shares one across the static and adaptive policies); ``placement0``
    is the physical chunk layout repair traffic derives from (defaults to
    the initial JLCM plan's Lemma-4 placement). ``repair_aware=False``
    runs the adaptive policy WITHOUT folding repair flows into its
    re-solves — the repair-oblivious closed-loop ablation.

    ``cache_aware=False`` (cache scenarios only) runs the CACHE-OBLIVIOUS
    control-plane ablation: the data-plane hot tier still serves hits
    (physics are policy-independent), but plans are solved for raw design
    rates, the closed loop treats observed warm-tier misses as if they
    were the whole workload (no Che inversion, no TTL management, no
    forced replan at hot-tier transitions). Outcome policy names get a
    ``-cacheblind`` suffix so suite CSVs keep the variants apart.
    """
    if policy not in POLICIES:
        raise ValueError(f"unknown policy {policy!r}; one of {POLICIES}")
    if hierarchy is not None and (
        spec.is_geo
        or spec.has_cache
        or spec.repair_rate > 0
        or spec.objective() is not None
    ):
        raise ValueError(
            f"{spec.name}: hierarchical planning composes only with plain "
            "scenarios (no geo fabric, cache tier, repair traffic, or "
            "tenant mix)"
        )
    if spec.is_geo:
        return run_geo_scenario(
            spec,
            policy,
            seed=seed,
            fabric=None if cluster is None else geo_testbed(cluster),
            requests_per_segment=requests_per_segment,
            pi0=pi0,
        )
    cluster = tahoe_testbed() if cluster is None else cluster
    m = cluster.m
    spec.validate(m)
    n_req = requests_per_segment or spec.requests_per_segment
    n_seg = spec.n_segments
    r = spec.r
    lam = jnp.asarray(spec.lam, jnp.float32)
    avail_tr = spec.avail_trace(m)
    rate_tr = spec.rate_scales()
    ovh_tr = spec.overhead_scales(m)
    bw_tr = spec.bandwidth_scales(m)
    key = jax.random.key(seed)

    # Hot/warm cache tier: the deploy-time TTL vector comes from the Che
    # characteristic time at the catalog's DESIGN rates — the artifact a
    # production rollout ships. Static/oblivious run it unchanged (masked
    # by outage windows); the adaptive control plane re-derives TTLs from
    # estimated raw rates at each replan (promotion/demotion).
    has_cache = spec.has_cache
    cache_model = spec.cache_model() if has_cache else None
    cache_up = spec.cache_up_trace()
    ttl0 = (
        cache_model.ttl(np.asarray(spec.lam, float)) if has_cache else None
    )

    with_repair = spec.repair_rate > 0
    plan0 = None
    if hierarchy is not None and pi0 is None and policy != "oblivious":
        # cluster-granularity initial plan, disaggregated by gather — the
        # dense per-file solve this replaces is exactly what a 10^5-file
        # catalog cannot afford
        plan0, _ = solve_hierarchical(
            hierarchy,
            cluster.moments(spec.chunk_mb),
            cluster.cost,
            spec.theta,
            max_iters=300,
        )
        pi_init = np.asarray(materialize(plan0))
    elif (pi0 is None and policy != "oblivious") or (
        with_repair and placement0 is None
    ):
        pi_init, _, sol0 = initial_plan(spec, cluster, cache_aware=cache_aware)
        if placement0 is None:
            placement0 = np.asarray(sol0.placement, bool)
    else:
        pi_init = None

    if policy == "oblivious":
        pi = oblivious_plan(spec, cluster)
    elif pi0 is not None:
        pi = np.asarray(pi0)
    else:
        pi = pi_init

    # The physical reconstruction process: per-segment repair rows from
    # the placement, activated through per-file rate scaling. lam of every
    # repair row is fixed at 1.0; the actual reads/sec ride in the scale.
    if with_repair:
        lam_rep_seq, pi_rep_seq = repair_schedule(
            placement0, np.asarray(spec.k), avail_tr, spec.repair_rate
        )
        lam_sim = jnp.concatenate([lam, jnp.ones((r,), jnp.float32)])
    else:
        lam_rep_seq = pi_rep_seq = None
        lam_sim = lam

    def seg_scale(s: int) -> np.ndarray | float:
        if not with_repair:
            return float(rate_tr[s])
        return np.concatenate(
            [np.full((r,), float(rate_tr[s])), lam_rep_seq[s]]
        )

    def seg_pi(client_pi: np.ndarray, s: int, repair_pi=None) -> np.ndarray:
        if not with_repair:
            return np.asarray(client_pi)
        rep = pi_rep_seq[s] if repair_pi is None else repair_pi
        return np.concatenate([np.asarray(client_pi), rep], axis=0)

    replans = 0
    solve_iters = solve_walls = rollout_walls = resolved_counts = ()
    hit = None
    pi_deployed = None  # (S, r, m) what actually dispatched, for cost
    if policy in ("static", "oblivious"):
        pi_seq = (
            jnp.asarray(np.stack([seg_pi(pi, s) for s in range(n_seg)]))
            if with_repair
            else jnp.asarray(pi)
        )
        scale_seq = (
            np.stack([seg_scale(s) for s in range(n_seg)])
            if with_repair
            else rate_tr
        )
        ttl_seq = (
            np.where(cache_up[:, None], ttl0[None, :], 0.0)
            if has_cache
            else None
        )
        res = simulate_segments(
            key,
            pi_seq,
            lam_sim,
            cluster,
            spec.chunk_mb,
            n_req,
            avail_seq=avail_tr,
            rate_scale_seq=scale_seq,
            overhead_scale_seq=ovh_tr,
            bandwidth_scale_seq=bw_tr,
            cache_ttl_seq=ttl_seq,
            cache_hit_latency=spec.cache_hit_latency,
        )
        lat = np.asarray(res.latency)  # (S, N)
        degraded = np.asarray(res.degraded)
        fid = np.asarray(res.file_id)
        if has_cache:
            hit = np.asarray(res.hit)
        pi_deployed = np.broadcast_to(
            np.asarray(pi)[None], (n_seg,) + np.asarray(pi).shape
        )
    else:
        mom0 = cluster.moments(spec.chunk_mb)
        moment_est = EwmaMomentEstimator(prior=mom0)
        # with a cache tier the estimator tracks MISS rates (the only
        # traffic the warm tier observes); prior = design-rate misses.
        # The cache-blind loop ALSO only ever sees misses — it just
        # mistakes them for the whole workload (prior = raw design rates,
        # no inversion downstream).
        rate_est = EwmaRateEstimator(
            prior=cache_model.thin(np.asarray(spec.lam, float))
            if has_cache and cache_aware
            else np.asarray(spec.lam)
        )
        if hierarchy is not None:
            replanner = HierarchicalReplanner(
                hierarchy=hierarchy,
                cost=np.asarray(cluster.cost),
                theta=spec.theta,
                estimator=moment_est,
            )
            if plan0 is not None:
                # seed the incumbent factored plan so the first boundary
                # can go incremental instead of re-solving from scratch
                replanner.plan = plan0
                replanner._solved_mom = mom0
                replanner._solved_avail = avail_tr[0].copy()
        else:
            replanner = AdaptiveReplanner(
                k=np.asarray(spec.k),
                cost=np.asarray(cluster.cost),
                theta=spec.theta,
                estimator=moment_est,
                objective=spec.objective(),
                cache=cache_model if cache_aware else None,
                domain=cluster.domain,
            )
        if has_cache and cache_aware:
            # seed the inversion state with what is actually deployed
            replanner.last_ttl = ttl0.copy()
            replanner.last_raw = np.asarray(spec.lam, float)
        ttl_cur = ttl0  # TTLs currently deployed to the data plane
        # same per-segment keys as the device path splits internally
        seg_keys = jax.random.split(key, n_seg)
        rollout_keys = jax.random.split(jax.random.key(seed + 0x5EED), n_seg)
        carry = None
        repair_pi = None  # replanner-optimized reconstruction dispatch
        repair_avail = None  # the health mask repair_pi was solved under
        lats, degs, fids, hits, pis = [], [], [], [], []
        for s in range(n_seg):
            # the hot tier's up/down state is a binary health signal known
            # at segment boundaries (same detection model as node
            # availability): a transition forces a replan so the warm tier
            # is re-planned for full raw load BEFORE the miss storm lands,
            # not a segment after it
            cache_flip = has_cache and cache_aware and s > 0 and bool(
                cache_up[s] != cache_up[s - 1]
            )
            cadence = s % spec.replan_every == 0
            if has_cache and cache_aware and not cache_up[s]:
                # hold the flip-time storm plan for the whole outage
                # window: it was solved from the CONVERGED pre-outage raw
                # estimate, while mid-storm the miss EWMA still blends
                # pre-outage observations and would re-tighten the plan
                # exactly when head-room matters most
                cadence = False
            if s > 0 and (cadence or cache_flip):
                if hierarchy is not None:
                    pi = replanner.replan(rate_est.rates, avail_tr[s])
                else:
                    flow = (
                        build_repair_flow(
                            placement0,
                            np.asarray(spec.k),
                            avail_tr[s],
                            spec.repair_rate,
                        )
                        if with_repair and repair_aware
                        else None
                    )
                    pi = replanner.replan(
                        rate_est.rates,
                        avail_tr[s],
                        pi0=pi,
                        carry=carry,
                        key=rollout_keys[s],
                        repair=flow,
                        cache_up=bool(cache_up[s]),
                    )
                    repair_pi = replanner.repair_pi
                    repair_avail = avail_tr[s].copy()
                    if has_cache and cache_aware:
                        ttl_cur = replanner.last_ttl
            # the optimized reconstruction dispatch is only valid for the
            # health mask it was solved under; if availability moved
            # between replans (replan_every > 1, staggered failures) fall
            # back to the schedule's k-of-surviving rows for this segment
            rep_s = (
                repair_pi
                if repair_pi is not None
                and np.array_equal(avail_tr[s], repair_avail)
                else None
            )
            with diag.span("loop.simulate"):
                t_start = 0.0 if carry is None else float(carry.t0)
                res_s, carry = simulate_segment(
                    seg_keys[s],
                    jnp.asarray(seg_pi(pi, s, rep_s)),
                    lam_sim,
                    cluster,
                    spec.chunk_mb,
                    n_req,
                    avail=avail_tr[s],
                    rate_scale=seg_scale(s),
                    overhead_scale=ovh_tr[s],
                    bandwidth_scale=bw_tr[s],
                    carry=carry,
                    cache_ttl=(
                        np.where(cache_up[s], ttl_cur, 0.0)
                        if has_cache
                        else None
                    ),
                    cache_hit_latency=spec.cache_hit_latency,
                )
                # the segment's results to the host (pure reads: where they
                # sit beside the estimator updates changes no value)
                fid_s = np.asarray(res_s.file_id)
                dur = float(res_s.t_end) - t_start
                hit_s = np.asarray(res_s.hit) if has_cache else None
                lats.append(np.asarray(res_s.latency))
                degs.append(np.asarray(res_s.degraded))
            with diag.span("loop.observe"):
                moment_est.update(res_s.obs)
                client_s = fid_s < r
                if has_cache:
                    rate_est.update_misses(
                        fid_s[client_s], hit_s[client_s], dur
                    )
                    hits.append(hit_s)
                else:
                    rate_est.update(fid_s[client_s], dur)
            fids.append(fid_s)
            pis.append(np.asarray(pi))
        lat = np.stack(lats)
        degraded = np.stack(degs)
        fid = np.stack(fids)
        if has_cache:
            hit = np.stack(hits)
        pi_deployed = np.stack(pis)
        replans = replanner.replans
        solve_iters = tuple(replanner.solve_iters)
        solve_walls = tuple(replanner.solve_walls)
        rollout_walls = tuple(getattr(replanner, "rollout_walls", ()))
        resolved_counts = tuple(getattr(replanner, "resolved_counts", ()))

    # All reported statistics cover CLIENT requests only; repair rows
    # (file_id >= r) are background load.
    client = fid < r
    seg_mean, seg_p99, pooled_mean, pooled_p99 = _segment_stats(lat, client)

    class_mean = class_p99 = None
    if spec.class_id is not None:
        stats = per_class_latency_stats(
            lat[client], fid[client], np.asarray(spec.class_id), spec.n_classes
        )
        class_mean, class_p99 = stats.mean, stats.p99

    hit_frac = 0.0
    storage_cost = float("nan")
    if has_cache:
        hit_frac = float(hit[client].mean())
        # warm-tier cost of what actually dispatched (support x V_j, the
        # solver's own true-cost convention), time-averaged over segments,
        # plus the provisioned hot tier — one comparable total per policy
        cost_v = np.asarray(cluster.cost, float)
        warm = float(
            np.mean(
                [((pi_deployed[s] > 1e-3) * cost_v).sum() for s in range(n_seg)]
            )
        )
        storage_cost = warm + cache_model.hot_cost()

    return ScenarioOutcome(
        scenario=spec.name,
        policy=policy if cache_aware or not has_cache
        else f"{policy}-cacheblind",
        seg_mean=seg_mean,
        seg_p99=seg_p99,
        mean=pooled_mean,
        p99=pooled_p99,
        degraded_frac=float(degraded[client].mean()),
        replans=replans,
        repair_frac=float(1.0 - client.mean()),
        class_mean=class_mean,
        class_p99=class_p99,
        hit_frac=hit_frac,
        storage_cost=storage_cost,
        solve_iters=solve_iters,
        solve_walls=solve_walls,
        rollout_walls=rollout_walls,
        resolved_counts=resolved_counts,
    )


def run_geo_scenario(
    spec: ScenarioSpec,
    policy: str = "adaptive",
    *,
    seed: int = 0,
    fabric: GeoFabric | None = None,
    requests_per_segment: int | None = None,
    pi0: np.ndarray | None = None,
) -> ScenarioOutcome:
    """Run a geo scenario (``spec.sites`` set) under ``policy``.

    The policies keep their control-spectrum roles, re-read for the
    client fabric:

    * ``static`` — the *geo-oblivious* plan: Algorithm JLCM from the base
      cluster's single-implicit-client moments (exactly today's
      ``initial_plan``), never re-planned. It knows nothing of client
      sites, so its placement is anchored to the reference (NJ) view —
      the operating model the ISSUE's motivation calls out.
    * ``oblivious`` — rate-proportional dispatch, as before.
    * ``adaptive`` — the geo closed loop: per-(site, node) moment EWMA +
      per-(site, file) rate EWMA feeding ``GeoAdaptiveReplanner``, which
      re-solves *geo* problems (estimated pair moments + estimated client
      mix) so placement follows the active client population.

    All policies simulate against the same fabric ground truth: per-pair
    service sampling, the spec's mix schedule, and its egress-degradation
    trace. Statistics additionally report per-client-site means
    (``site_mean``).
    """
    if policy not in POLICIES:
        raise ValueError(f"unknown policy {policy!r}; one of {POLICIES}")
    fabric = geo_testbed() if fabric is None else fabric
    m, r, c = fabric.m, spec.r, fabric.n_sites
    spec.validate(m)
    spec.validate_geo_fabric(fabric)
    n_req = requests_per_segment or spec.requests_per_segment
    n_seg = spec.n_segments
    lam_cs_seq = spec.lam_cs_schedule()  # (S, C, r)
    avail_tr = spec.avail_trace(m)
    ovh_tr, bw_tr = spec.egress_scales(fabric)  # (S, C, m) each
    key = jax.random.key(seed)

    if policy == "oblivious":
        pi = oblivious_plan(spec, fabric.cluster)
    elif pi0 is not None:
        pi = np.asarray(pi0)
    else:
        pi, _, _ = initial_plan(spec, fabric.cluster)  # geo-oblivious

    replans = 0
    solve_iters = solve_walls = rollout_walls = ()
    if policy in ("static", "oblivious"):
        res = simulate_geo_segments(
            key,
            jnp.asarray(pi),
            lam_cs_seq,
            fabric,
            spec.chunk_mb,
            n_req,
            avail_seq=avail_tr,
            overhead_scale_seq=ovh_tr,
            bandwidth_scale_seq=bw_tr,
        )
        lat = np.asarray(res.latency)  # (S, N)
        degraded = np.asarray(res.degraded)
        site = np.asarray(res.site_id)
    else:
        moment_est = EwmaMomentEstimator(prior=fabric.moments(spec.chunk_mb))
        rate_est = EwmaRateEstimator(prior=lam_cs_seq[0].reshape(-1))
        replanner = GeoAdaptiveReplanner(
            k=np.asarray(spec.k),
            cost=np.asarray(fabric.cluster.cost),
            theta=spec.theta,
            estimator=moment_est,
            objective=spec.objective(),
        )
        seg_keys = jax.random.split(key, n_seg)
        rollout_keys = jax.random.split(jax.random.key(seed + 0x5EED), n_seg)
        carry = None
        lats, degs, sites = [], [], []
        for s in range(n_seg):
            if s > 0 and s % spec.replan_every == 0:
                pi = replanner.replan(
                    rate_est.rates.reshape(c, r),
                    avail_tr[s],
                    pi0=pi,
                    carry=carry,
                    key=rollout_keys[s],
                )
            with diag.span("loop.simulate"):
                t_start = 0.0 if carry is None else float(carry.t0)
                res_s, carry = simulate_geo_segment(
                    seg_keys[s],
                    jnp.asarray(pi),
                    lam_cs_seq[s],
                    fabric,
                    spec.chunk_mb,
                    n_req,
                    avail=avail_tr[s],
                    overhead_scale=ovh_tr[s],
                    bandwidth_scale=bw_tr[s],
                    carry=carry,
                )
                fid_s = np.asarray(res_s.file_id)
                site_s = np.asarray(res_s.site_id)
                dur = float(res_s.t_end) - t_start
                lats.append(np.asarray(res_s.latency))
                degs.append(np.asarray(res_s.degraded))
            with diag.span("loop.observe"):
                moment_est.update(res_s.obs)
                rate_est.update(site_s * r + fid_s, dur)
            sites.append(site_s)
        lat = np.stack(lats)
        degraded = np.stack(degs)
        site = np.stack(sites)
        replans = replanner.replans
        solve_iters = tuple(replanner.solve_iters)
        solve_walls = tuple(replanner.solve_walls)
        rollout_walls = tuple(replanner.rollout_walls)

    site_mean = np.asarray(
        [
            lat[site == ci].mean() if (site == ci).any() else np.nan
            for ci in range(c)
        ]
    )
    seg_mean, seg_p99, pooled_mean, pooled_p99 = _segment_stats(lat)
    return ScenarioOutcome(
        scenario=spec.name,
        policy=policy,
        seg_mean=seg_mean,
        seg_p99=seg_p99,
        mean=pooled_mean,
        p99=pooled_p99,
        degraded_frac=float(degraded.mean()),
        replans=replans,
        site_mean=site_mean,
        solve_iters=solve_iters,
        solve_walls=solve_walls,
        rollout_walls=rollout_walls,
    )


def run_all_policies(
    spec: ScenarioSpec,
    *,
    seed: int = 0,
    cluster: Cluster | None = None,
    requests_per_segment: int | None = None,
    repair_aware: bool = True,
    include_cacheblind: bool = False,
    hierarchy: Hierarchy | None = None,
) -> list[ScenarioOutcome]:
    """All three policies on identical arrival/service randomness, sharing
    one initial JLCM solve between static and adaptive — and one physical
    placement (hence one repair schedule) across all three.

    ``include_cacheblind=True`` (cache scenarios only) appends the
    cache-oblivious static baseline — planned for raw design rates with
    the hot tier invisible to the control plane — as a fourth outcome
    (policy ``static-cacheblind``).

    ``hierarchy`` routes every policy through the hierarchical path (see
    :func:`run_scenario`); the cluster-granularity initial solve is cheap
    enough (O(100) rows) that each policy re-solves it rather than
    sharing one dense plan."""
    if hierarchy is not None:
        return [
            run_scenario(
                spec,
                policy,
                seed=seed,
                cluster=cluster,
                requests_per_segment=requests_per_segment,
                hierarchy=hierarchy,
            )
            for policy in POLICIES
        ]
    if spec.is_geo:
        fabric = geo_testbed(cluster) if cluster is not None else geo_testbed()
        pi0, _, _ = initial_plan(spec, fabric.cluster)
        return [
            run_geo_scenario(
                spec,
                policy,
                seed=seed,
                fabric=fabric,
                requests_per_segment=requests_per_segment,
                pi0=None if policy == "oblivious" else pi0,
            )
            for policy in POLICIES
        ]
    cluster = tahoe_testbed() if cluster is None else cluster
    pi0, _, sol0 = initial_plan(spec, cluster)
    placement0 = np.asarray(sol0.placement, bool)
    out = [
        run_scenario(
            spec,
            policy,
            seed=seed,
            cluster=cluster,
            requests_per_segment=requests_per_segment,
            pi0=None if policy == "oblivious" else pi0,
            placement0=placement0,
            repair_aware=repair_aware,
        )
        for policy in POLICIES
    ]
    if include_cacheblind and spec.has_cache:
        out.append(
            run_scenario(
                spec,
                "static",
                seed=seed,
                cluster=cluster,
                requests_per_segment=requests_per_segment,
                placement0=placement0,
                cache_aware=False,
            )
        )
    return out
