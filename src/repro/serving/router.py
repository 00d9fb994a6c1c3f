"""Probabilistic-scheduling request router for model serving.

Inference replicas play the role of storage nodes; request classes (e.g.
per-model or per-SLA tier) are the paper's files with k_i = 1. JLCM tunes
the dispatch probabilities pi (and which replicas to keep provisioned —
the 'cost' axis) to minimize mean latency + theta * replica cost; the
router then dispatches every batch with Theorem-1 exact marginals.

Straggler mitigation beyond the paper: *hedged dispatch* — send each
request to 1 + hedge replicas sampled without replacement and take the
first completion. The simulator quantifies the tail-latency win (see
benchmarks/serving_hedge.py).

Closed-loop control (scenario engine): the paper optimizes against
ground-truth service moments, but an operating system only sees
measurements. :class:`EwmaMomentEstimator` folds per-segment node-side
service observations (``storage.simulator.NodeObservations``) into EWMA
estimates of the Lemma-3 moments, :class:`EwmaRateEstimator` tracks the
per-class arrival rates the same way, and :class:`AdaptiveReplanner`
re-solves JLCM from those *estimated* inputs — batching all candidate
(theta, availability-mask) re-plans into one ``solve_batch`` call — to
produce the next segment's dispatch matrix. Candidate *arbitration* is
equally batched: :func:`batched_rollout_scores` fuses every candidate's
exact-simulator rollout, its composed-objective scoring, the
``+ theta * cost`` fold, and the winning ``argmin`` into ONE compiled
device program (candidate axis padded to a power of two for program
reuse, optional common-random-number seed axis, ``shard_map`` over the
local mesh when >1 device) with a single host sync per replan. `src/repro/scenarios/` wires
this loop against the segmented simulator. :class:`GeoAdaptiveReplanner`
is the client-fabric variant: it estimates the full (C, m) per-(client-
site, node) service family and the (C, r) traffic matrix, and re-solves
*geo* problems so placement follows the active client population
(`core/geo.py`).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import diag
from repro.core import (
    FactoredPlan,
    Hierarchy,
    JLCMProblem,
    ObjectiveSpec,
    ServiceMoments,
    build_problem,
    empirical_objective,
    empirical_objective_device,
    feasible_uniform,
    fit_shifted_exponential,
    madow_sample,
    materialize,
    project_capped_simplex,
    rack_count,
    resolve_incremental,
    solve,
    solve_batch,
)


@dataclasses.dataclass
class ReplicaPool:
    moments: ServiceMoments  # per-replica service moments (measured/EWMA)
    cost: jnp.ndarray  # per-replica provisioning cost

    @property
    def m(self) -> int:
        return int(self.cost.shape[0])


@dataclasses.dataclass
class Router:
    pool: ReplicaPool
    pi: np.ndarray  # (r, m) dispatch probabilities per request class
    hedge: int = 0  # extra replicas per request (first-wins)
    latency_bound: float = float("nan")
    # replica id -> (pi, latency_bound) re-plan with that replica removed,
    # precomputed in one batched solve (see precompute_failover)
    failover: dict[int, tuple[np.ndarray, float]] = dataclasses.field(
        default_factory=dict
    )
    # (class_rates, theta) the failover table was computed for; drop_replica
    # only consults the table when called with matching conditions
    failover_inputs: tuple[np.ndarray, float] | None = None

    @classmethod
    def plan(
        cls,
        pool: ReplicaPool,
        class_rates: jnp.ndarray,
        *,
        theta: float = 0.0,
        hedge: int = 0,
        max_iters: int = 200,
    ) -> "Router":
        r = int(class_rates.shape[0])
        prob = JLCMProblem(
            lam=jnp.asarray(class_rates),
            k=jnp.ones((r,)),
            moments=pool.moments,
            cost=pool.cost,
            theta=theta,
        )
        sol = solve(prob, max_iters=max_iters)
        return cls(
            pool=pool,
            # jaxcheck: JX001 ok end-of-plan materialization, single sync
            pi=np.asarray(sol.pi),
            hedge=hedge,
            # jaxcheck: JX001 ok scalar leaves the solver exactly once
            latency_bound=float(sol.latency_tight),
        )

    def route(self, key, class_id: int) -> list[int]:
        """Replica ids for one request (1 + hedge distinct replicas)."""
        pi = jnp.asarray(self.pi[class_id])
        if self.hedge > 0:
            kk = 1 + self.hedge
            scaled = project_capped_simplex(
                pi[None] * kk, jnp.asarray([float(kk)])
            )[0]
            mask = madow_sample(key, scaled)
        else:
            mask = madow_sample(key, pi)
        return [int(j) for j in np.where(np.asarray(mask))[0]]

    @classmethod
    def plan_sweep(
        cls,
        pool: ReplicaPool,
        class_rates: jnp.ndarray,
        thetas,
        *,
        hedge: int = 0,
        max_iters: int = 200,
    ) -> list["Router"]:
        """Plan one router per tradeoff factor — the whole theta sweep is a
        single batched device solve (pick the cheapest plan meeting an SLA
        downstream)."""
        r = int(class_rates.shape[0])
        probs = [
            JLCMProblem(
                lam=jnp.asarray(class_rates),
                k=jnp.ones((r,)),
                moments=pool.moments,
                cost=pool.cost,
                theta=float(theta),
            )
            for theta in thetas
        ]
        sols = solve_batch(probs, max_iters=max_iters)
        # ONE materialization for the whole sweep — indexing the device
        # arrays per theta would cost a host sync per candidate
        # jaxcheck: JX001 ok end-of-sweep materialization, single sync
        pi_np = np.asarray(sols.pi)
        # jaxcheck: JX001 ok end-of-sweep materialization, single sync
        lat_np = np.asarray(sols.latency_tight)
        return [
            cls(
                pool=pool,
                pi=pi_np[i],
                hedge=hedge,
                latency_bound=float(lat_np[i]),
            )
            for i in range(len(probs))
        ]

    def _masked_problem(self, dead: list[int], class_rates, theta) -> JLCMProblem:
        mask = np.ones((self.pi.shape[0], self.pool.m), bool)
        mask[:, dead] = False
        return JLCMProblem(
            lam=jnp.asarray(class_rates),
            k=jnp.ones((self.pi.shape[0],)),
            moments=self.pool.moments,
            cost=self.pool.cost,
            theta=theta,
            mask=jnp.asarray(mask),
        )

    def precompute_failover(
        self, class_rates: jnp.ndarray, theta: float = 0.0, *, max_iters: int = 150
    ) -> "Router":
        """Re-optimize dispatch for EVERY possible single-replica failure in
        one `solve_batch` call (m masked problems, one XLA program), so a
        later `drop_replica` is a dictionary lookup instead of a solve."""
        probs = [
            self._masked_problem([j], class_rates, theta)
            for j in range(self.pool.m)
        ]
        sols = solve_batch(probs, max_iters=max_iters)
        # ONE materialization for all m failure plans (was one device
        # sync per replica: np.asarray(sols.pi[j]) inside the dict comp)
        # jaxcheck: JX001 ok end-of-solve materialization, single sync
        pi_np = np.asarray(sols.pi)
        # jaxcheck: JX001 ok end-of-solve materialization, single sync
        lat_np = np.asarray(sols.latency_tight)
        failover = {
            j: (pi_np[j], float(lat_np[j]))
            for j in range(self.pool.m)
        }
        return dataclasses.replace(
            self,
            failover=failover,
            failover_inputs=(np.asarray(class_rates), float(theta)),
        )

    def drop_replica(self, replica: int, class_rates: jnp.ndarray, theta: float = 0.0) -> "Router":
        """Elastic scale-down / failure: mask the replica and re-plan.

        Uses the precomputed failover table only when it was computed for
        the same ``class_rates``/``theta`` (see `precompute_failover`);
        a stale table is ignored and the masked problem is solved now."""
        if replica in self.failover and self.failover_inputs is not None:
            rates0, theta0 = self.failover_inputs
            if theta0 == float(theta) and np.allclose(
                rates0, np.asarray(class_rates)
            ):
                pi, bound = self.failover[replica]
                return dataclasses.replace(
                    self, pi=pi, latency_bound=bound,
                    failover={}, failover_inputs=None,
                )
        sol = solve(self._masked_problem([replica], class_rates, theta), max_iters=150)
        return dataclasses.replace(
            self,
            # jaxcheck: JX001 ok end-of-solve materialization, single sync
            pi=np.asarray(sol.pi),
            # jaxcheck: JX001 ok scalar leaves the solver exactly once
            latency_bound=float(sol.latency_tight),
            failover={},
            failover_inputs=None,
        )


# ---------------------------------------------------------------------------
# Closed-loop control: measured state in, batched re-plans out.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EwmaMomentEstimator:
    """EWMA tracker of per-node service moments from segment observations.

    Each :meth:`update` consumes one segment's ``NodeObservations`` (counts
    + raw power sums of observed chunk service times), forms the segment's
    unbiased raw-moment estimates, and blends them into exponentially-
    weighted running estimates of E[X_j], E[X_j^2], E[X_j^3] — the inputs
    Lemma 3's P-K formulas need. Nodes with no observations this segment
    (down, or zero dispatch mass) keep their previous estimate, so a node
    that fails and recovers resumes from its pre-failure state instead of
    garbage. ``prior`` seeds the estimates (e.g. the moments the initial
    plan was computed from); with a prior, :meth:`moments` is total —
    every node always has a finite estimate.

    On a stationary trace the per-segment estimates are unbiased and the
    EWMA converges to the true moments (tested in
    ``tests/test_scenarios.py``); under drift it tracks with time constant
    ``~1/alpha`` segments.
    """

    prior: ServiceMoments
    alpha: float = 0.35
    m1: np.ndarray = dataclasses.field(init=False)
    m2: np.ndarray = dataclasses.field(init=False)
    m3: np.ndarray = dataclasses.field(init=False)

    def __post_init__(self) -> None:
        self.m1 = np.asarray(self.prior.mean, float).copy()
        self.m2 = np.asarray(self.prior.m2, float).copy()
        self.m3 = np.asarray(self.prior.m3, float).copy()

    def update(self, obs: Any) -> ServiceMoments:
        count = np.asarray(obs.count, float)
        seen = count > 0
        safe = np.maximum(count, 1.0)
        h1 = np.asarray(obs.s1, float) / safe
        h2 = np.asarray(obs.s2, float) / safe
        h3 = np.asarray(obs.s3, float) / safe
        a = self.alpha
        self.m1 = np.where(seen, (1 - a) * self.m1 + a * h1, self.m1)
        self.m2 = np.where(seen, (1 - a) * self.m2 + a * h2, self.m2)
        self.m3 = np.where(seen, (1 - a) * self.m3 + a * h3, self.m3)
        return self.moments()

    def moments(self) -> ServiceMoments:
        return ServiceMoments(
            mu=jnp.asarray(1.0 / self.m1, jnp.float32),
            m2=jnp.asarray(self.m2, jnp.float32),
            m3=jnp.asarray(self.m3, jnp.float32),
        )

    def fitted_shifted_exp(self) -> tuple[np.ndarray, np.ndarray]:
        """Method-of-moments fit of the cluster's service family D + Exp.

        Returns per-node ``(overheads D_j, exp rates 1/s_j)`` matching the
        estimated first two moments via ``core.queueing.
        fit_shifted_exponential`` (the inverse of
        ``shifted_exponential_moments``). Used to *sample* service times
        from estimated state — e.g. the replanner's candidate rollouts —
        without ever touching the simulator's ground-truth parameters.
        """
        d, rate = fit_shifted_exponential(self.m1, self.m2)
        return np.asarray(d), np.asarray(rate)


@dataclasses.dataclass
class EwmaRateEstimator:
    """EWMA of per-class (per-file) arrival rates from observed traffic.

    :meth:`update` takes the request class ids seen in one segment and the
    segment's wall-clock duration; the empirical rates ``n_i / duration``
    are EWMA-blended so flash crowds and diurnal ramps show up in the
    re-planner's lambda within ``~1/alpha`` segments.
    """

    prior: np.ndarray
    alpha: float = 0.5
    rates: np.ndarray = dataclasses.field(init=False)
    dropped: int = dataclasses.field(init=False, default=0)

    def __post_init__(self) -> None:
        self.rates = np.asarray(self.prior, float).copy()

    def update(self, class_id: Any, duration: float) -> np.ndarray:
        """Fold one segment's observed class ids into the EWMA rates.

        Ids outside ``[0, r)`` are *not* client classes — the engine
        appends repair pseudo-file rows at ids >= r, and a caller that
        forgets the client mask would otherwise make ``np.bincount``
        return an array longer than r, silently mis-shaping (or raising
        on) the EWMA blend. Such ids are dropped here (counted in
        :attr:`dropped` for callers that want to alarm on the leak):
        clamping them onto the last class would inflate a real tenant's
        estimated rate instead.
        """
        ids = np.asarray(class_id).ravel()
        r = self.rates.shape[0]
        valid = (ids >= 0) & (ids < r)
        self.dropped += int(ids.size - valid.sum())
        counts = np.bincount(ids[valid], minlength=r).astype(float)
        emp = counts / max(float(duration), 1e-9)
        self.rates = (1 - self.alpha) * self.rates + self.alpha * emp
        return self.rates.copy()

    def update_misses(
        self, class_id: Any, hit: Any, duration: float
    ) -> np.ndarray:
        """Cache-tier variant of :meth:`update`: fold in *miss* traffic only.

        With a hot tier in front of the warm tier, requests that hit the
        cache never reach a storage queue — the only arrivals the warm
        tier's control plane actually observes are the misses. Feeding the
        full id stream would make the estimator track raw rates the warm
        tier never sees; feeding ``ids[~hit]`` makes :attr:`rates` a
        *miss-rate* estimate, which the cache-aware replanner inverts back
        to raw rates through the deployed TTLs
        (``storage.cache.CacheModel.reconstruct_raw_rates``).
        """
        ids = np.asarray(class_id).ravel()
        miss = np.logical_not(np.asarray(hit, bool).ravel())
        return self.update(ids[miss], duration)


def _pow2(n: int) -> int:
    """Smallest power of two >= n (candidate-lane padding)."""
    return 1 << max(0, n - 1).bit_length()


def _rollout_lane_score(
    carry, key, pi, lam, overheads, rates, avail, ttl, hit_latency, spec,
    *, n_requests: int, n_clients: int, geo: bool,
):
    """Simulate ONE (candidate, seed) rollout lane and score it on device.

    The unit the batched arbitration parallelizes over: one exact-simulator
    segment from the live queue state under the estimated service family,
    folded straight into the composed empirical objective
    (``core.objectives.empirical_objective_device``) with repair pseudo-file
    rows (``file_id >= n_clients``) masked out of the statistic — the
    latency stream never leaves the device.
    """
    from repro.storage.simulator import _run_geo_segment, _run_segment

    if geo:
        _, res = _run_geo_segment(
            carry, key, pi, lam, overheads, rates, avail, n_requests
        )
    else:
        _, res = _run_segment(
            carry, key, pi, lam, overheads, rates, avail, n_requests,
            ttl, hit_latency,
        )
    return empirical_objective_device(
        res.latency, res.file_id, spec, valid=res.file_id < n_clients
    )


@functools.partial(
    jax.jit, static_argnames=("n_requests", "n_clients", "geo", "shard")
)
def _arbitrate_device(
    carry, keys, pi_stack, lam, overheads, rates, avail, cost_term,
    lane_ok, spec, ttl, hit_latency,
    *, n_requests: int, n_clients: int, geo: bool, shard: bool,
):
    """ONE compiled program scoring every candidate plan: vmapped (or
    shard_mapped) rollouts -> device empirical objective -> ``+ cost`` ->
    lane masking -> argmin. Returns ``(scores (B,), best ())`` as device
    arrays; the caller's ``int(best)`` is the replan's single host sync.

    ``keys`` (K,) is the common-random-number seed axis: every candidate
    is rolled out under the SAME K keys, so per-candidate scores are
    K-seed means over identical workload randomness. ``lane_ok`` masks
    padded candidate lanes (scores forced to +inf), which is what lets
    the candidate axis pad to a power of two and reuse this program
    across replans with varying candidate counts. With ``shard`` the
    flattened (candidate x seed) lane axis is split over the local device
    mesh (`shard_map`), each lane entirely on one device — same math,
    measured for parity by ``tests/test_replan_batch.py``.
    """
    score = functools.partial(
        _rollout_lane_score,
        n_requests=n_requests, n_clients=n_clients, geo=geo,
    )
    b = pi_stack.shape[0]
    k = keys.shape[0]
    if shard:
        lanes_pi = jnp.repeat(pi_stack, k, axis=0)  # (B*K, r, m)
        lanes_key = jnp.broadcast_to(keys[None], (b, k)).reshape(-1)
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("cand",))
        pspec = jax.sharding.PartitionSpec

        def lanes_fn(kl, pl, carry, lam, ovh, rts, avail, ttl, hl, spec):
            return jax.vmap(
                lambda kk, pp: score(
                    carry, kk, pp, lam, ovh, rts, avail, ttl, hl, spec
                )
            )(kl, pl)

        # lanes never communicate: collective-free body, so no varying-
        # axis types (see simulate_fleet)
        lane_scores = jax.shard_map(
            lanes_fn,
            mesh=mesh,
            in_specs=(pspec("cand"), pspec("cand")) + (pspec(),) * 8,
            out_specs=pspec("cand"),
            check_vma=False,
        )(
            lanes_key, lanes_pi, carry, lam, overheads, rates, avail,
            ttl, hit_latency, spec,
        )
        scores = lane_scores.reshape(b, k).mean(axis=1)
    else:
        per_lane = jax.vmap(
            lambda pi: jax.vmap(
                lambda kk: score(
                    carry, kk, pi, lam, overheads, rates, avail,
                    ttl, hit_latency, spec,
                )
            )(keys)
        )(pi_stack)  # (B, K)
        scores = per_lane.mean(axis=1)
    scores = scores + cost_term
    scores = jnp.where(lane_ok, scores, jnp.inf)
    return scores, jnp.argmin(scores)


@diag.hot_path("serving.batched_rollout_scores")
def batched_rollout_scores(
    carry,
    key,
    pi_stack,
    lam,
    overheads,
    rates,
    avail,
    cost_term,
    objective: ObjectiveSpec | None = None,
    *,
    n_clients: int,
    n_requests: int = 600,
    rollout_seeds: int = 1,
    ttl=None,
    hit_latency=0.0,
    devices: str = "auto",
    geo: bool = False,
):
    """Score a (B, r, m) candidate-plan stack in ONE device program.

    The replanners' arbitration hot path, public so benchmarks and parity
    tests drive the exact production surface
    (`benchmarks/replan_wall.py`, ``tests/test_replan_batch.py``). The
    candidate axis is padded to a power of two (padded lanes replay
    candidate 0 and score +inf via the dynamic ``lane_ok`` mask), so one
    compiled program serves every replan whose padded width matches —
    warm/cold and mask-count variation does not recompile. With
    ``rollout_seeds == 1`` the key is used UNSPLIT (``key[None]``), which
    makes each candidate's simulated latency stream bitwise identical to
    a sequential ``run_segment_raw(carry, key, pi_i, ...)`` call — the
    legacy loop's common-random-number contract; ``rollout_seeds > 1``
    splits the key once and scores each candidate by its K-seed mean.
    ``devices="auto"`` shards the (candidate x seed) lanes over all local
    devices when >1 (growing the pad until the lane count divides the
    mesh); ``"never"`` forces the single-program vmap.

    Returns device arrays ``(scores (B_pad,), best ())`` — no host sync
    happens here; callers take ``int(best)`` as the one transfer and may
    keep ``scores[:B]`` for telemetry without forcing it.
    """
    pi_stack = jnp.asarray(pi_stack)
    b = int(pi_stack.shape[0])
    keys = (
        key[None] if rollout_seeds == 1 else jax.random.split(key, rollout_seeds)
    )
    n_dev = len(jax.devices())
    shard = devices == "auto" and n_dev > 1
    b_pad = _pow2(b)
    if shard:
        grow = 0
        while (b_pad * rollout_seeds) % n_dev and grow < 4:
            b_pad *= 2
            grow += 1
        if (b_pad * rollout_seeds) % n_dev:
            shard, b_pad = False, _pow2(b)  # odd mesh: vmap fallback
    cost = jnp.asarray(cost_term, jnp.float32)
    if b_pad > b:
        pi_stack = jnp.concatenate(
            [
                pi_stack,
                jnp.broadcast_to(
                    pi_stack[:1], (b_pad - b,) + pi_stack.shape[1:]
                ),
            ]
        )
        cost = jnp.concatenate([cost, jnp.zeros((b_pad - b,), cost.dtype)])
    lane_ok = jnp.arange(b_pad) < b  # dynamic: no recompile across counts
    return _arbitrate_device(
        carry,
        keys,
        pi_stack,
        jnp.asarray(lam, jnp.float32),
        jnp.asarray(overheads, jnp.float32),
        jnp.asarray(rates, jnp.float32),
        jnp.asarray(avail),
        cost,
        lane_ok,
        objective,
        ttl,
        jnp.asarray(hit_latency, jnp.float32),
        n_requests=n_requests,
        n_clients=n_clients,
        geo=geo,
        shard=shard,
    )


@dataclasses.dataclass
class AdaptiveReplanner:
    """Re-solve JLCM from estimated state, one batched solve per re-plan.

    Holds the pieces of the control loop that face the solver: the catalog
    shape (``k``, per-node ``cost``), the operating tradeoff ``theta``, and
    the moment estimator. :meth:`replan` builds the candidate set — the
    cross product of ``thetas`` (defaults to the operating theta) and
    candidate availability masks (defaults to the health-check mask alone),
    each solved from BOTH a cold (feasible-uniform) and, when the current
    plan is supplied, a warm start — and solves them all in ONE
    ``solve_batch`` call. With the defaults that is two masked re-solves in
    one XLA program, exactly the shape ``Router.precompute_failover``
    batches over hypothetical failures.

    Candidate selection is *model-predictive* when the caller supplies the
    live queue state: each candidate plan is scored by a short exact-
    simulator rollout from ``carry`` under the **estimated** service family
    (:meth:`EwmaMomentEstimator.fitted_shifted_exp`) and the estimated
    rates, and the lowest ``rollout mean + theta * cost`` (the same
    objective the analytic fallback scores, with the rollout mean standing
    in for the bound) wins. This matters twice over:
    (a) the Lemma-2 bound is loose enough at high load to mis-rank plans
    (a wide-spread plan can have a lower bound but a higher true latency —
    slow nodes enter the k-th order statistic), and (b) after a surge or
    failure the bound knows nothing about queue backlog, while the rollout
    starts from the actual per-node departure state and so prefers plans
    that drain it. Without ``carry``/``key`` the scorer falls back to the
    analytic ``latency_tight + theta * cost``.

    Rollout arbitration runs as ONE compiled device program
    (:func:`batched_rollout_scores`): candidates vmap over the rollout,
    scores fold the device empirical objective plus ``theta * cost``, and
    only the winning index crosses to the host — at ``rollout_seeds=1``
    (the default) bit-identical in its chosen plan to the sequential
    per-candidate loop (``rollout_batched=False``) it replaced, and at
    ``rollout_seeds=K`` averaging K common-random-number rollouts per
    candidate for variance-reduced selection at near-flat wall.
    Per-replan arbitration wall time lands in :attr:`rollout_walls`
    (surfaced as the scenario CSVs' ``rollout_wall_ms`` column).

    Warm starts track slow drift with fewer iterations (DC programming
    keeps support); cold starts escape a stale support after abrupt
    changes. The rollout arbitrates — no hand-tuned margins.

    ``objective`` (an ``ObjectiveSpec``) makes the whole loop multi-tenant:
    candidate solves optimize the composed per-class objective, the
    analytic fallback scores plans by the composed tight bound
    (``latency_tight`` already folds weights and tail terms), and rollout
    scoring applies the SAME objective to the simulated latencies
    (``core.objectives.empirical_objective``) — so a premium class is
    protected by the *selection* step too, e.g. during node failures.

    Repair awareness (``storage/repair.py``): passing a ``RepairFlow`` to
    :meth:`replan` folds reconstruction traffic into every candidate —
    the repair rows join the solve as extra (lam, k, mask) rows (their
    arrival rates are *known* from the repair pacer, not estimated), so
    the optimizer sees the background load repair puts on each node and
    steers client dispatch around it, while simultaneously optimizing
    *which* surviving chunks the repair reads fetch. With a tenant
    ``objective``, repair rows get a zero-weight class: their latency does
    not count, but their queueing load still shifts every client class's
    bound. Rollout candidates simulate the augmented plan and are scored
    on client requests only. The chosen repair dispatch lands in
    :attr:`repair_pi` for the caller to inject into the next segment.

    Cache awareness (``storage/cache.py``): with a ``cache`` model the
    estimated ``class_rates`` entering :meth:`replan` are *miss* rates
    (:meth:`EwmaRateEstimator.update_misses`), and the replanner closes
    the hot-tier loop: it inverts the misses back to raw rates through the
    TTLs it last deployed (:attr:`last_ttl`, with :attr:`last_raw` as the
    branch prior), re-derives the hot set and per-file TTLs at the new raw
    estimate — promotion/demotion — and hands every candidate solve the
    raw rates plus a ``CacheSpec`` so the optimizer plans the warm tier
    against miss traffic while the objective blends hit latency and the
    replicated hot tier's cost. Repair pseudo-file rows join with hit 0
    and TTL 0: a reconstruction read fetches *lost* chunks, which no hot
    tier holds. Rollouts replay candidates with the planned TTL vector so
    the scorer sees the same thinned queue load the solver planned for.
    ``cache_up=False`` (health-checked hot-tier outage) plans the next
    segment at the full raw load with zero hit everywhere — replanning
    *before* the miss storm arrives instead of reacting to it a segment
    late. The caller deploys :attr:`last_ttl` to the data plane after each
    replan.
    """

    k: np.ndarray  # (r,) MDS k_i per class/file
    cost: np.ndarray  # (m,) per-node cost V_j
    theta: float
    estimator: EwmaMomentEstimator
    objective: ObjectiveSpec | None = None  # scenario's composed objective
    thetas: tuple[float, ...] | None = None
    max_iters: int = 400
    rollout_requests: int = 600
    # common-random-number rollout seeds per candidate (K): 1 keeps the
    # historical bitwise stream (unsplit key), >1 scores each candidate by
    # its K-seed mean — variance-reduced arbitration at near-flat wall
    rollout_seeds: int = 1
    # False restores the legacy per-candidate Python loop (one device
    # dispatch + host sync per candidate); kept as the parity/benchmark
    # baseline the batched arbitration is asserted bit-identical against
    rollout_batched: bool = True
    # mesh policy for batched rollouts: "auto" shards (candidate x seed)
    # lanes over all local devices when >1, "never" forces plain vmap
    rollout_devices: str = "auto"
    replans: int = 0
    # optimized reconstruction-read dispatch from the last repair-aware
    # replan (None when the last replan saw no active repair flow)
    repair_pi: np.ndarray | None = None
    # hot-tier cache model (storage.cache.CacheModel) — None = no cache
    cache: Any | None = None
    # TTLs deployed by the last replan (the inversion key for the next
    # one) and the tracked raw-rate estimate (branch prior); both seeded
    # by the caller at deploy time
    last_ttl: np.ndarray | None = None
    last_raw: np.ndarray | None = None
    # per-replan solver telemetry: iteration count of the deployed
    # candidate and wall time of the batched candidate solve (appended by
    # every replan; the scenario engine surfaces them as CSV columns)
    solve_iters: list = dataclasses.field(default_factory=list)
    solve_walls: list = dataclasses.field(default_factory=list)
    # wall seconds of each replan's rollout arbitration (scoring only —
    # candidate solves ride in solve_walls); empty entries never appear:
    # analytic-fallback replans simply do not append
    rollout_walls: list = dataclasses.field(default_factory=list)
    # per-candidate arbitration scores of the last replan; a device array
    # on the batched path (reading it does NOT add a host sync — callers
    # that want numbers np.asarray it themselves)
    last_scores: Any = None
    # rate head-room multiplier for hot-tier-outage replans
    # (``cache_up=False``). The raw-rate estimate entering an outage plan
    # is an EWMA that lags the storm by construction (pre-outage miss
    # observations still carry weight), so planning for the point
    # estimate runs the warm tier near saturation exactly when there is
    # no hot tier to absorb variance. The margin buys back that head-room
    # — the storage-cost price is bounded (it applies only to outage
    # windows) and far below the cache-blind plan's permanent
    # over-provisioning.
    surge_margin: float = 1.25
    # (m,) rack of each node (``Cluster.domain``): every candidate solve
    # carries the rack caps and deploys one chunk per rack. None = no
    # failure domains
    domain: np.ndarray | None = None

    def _repair_objective(self) -> ObjectiveSpec | None:
        """The client objective extended with a zero-weight repair class.

        Even with no tenant mix (``objective=None``) the repair-augmented
        solve gets a two-class spec — clients weight 1, repair weight 0 —
        so reconstruction reads contribute *load* (through every node's
        P-K term) but never latency credit: the optimizer cannot trade
        client latency away to make repair finish sooner.
        """
        r = int(np.asarray(self.k).shape[0])
        if self.objective is None:
            return ObjectiveSpec(
                class_id=jnp.concatenate(
                    [jnp.zeros((r,), jnp.int32), jnp.ones((r,), jnp.int32)]
                ),
                weight=jnp.asarray([1.0, 0.0], jnp.float32),
            )
        spec = self.objective
        n_classes = int(spec.weight.shape[-1])
        cid = jnp.concatenate(
            [spec.class_id, jnp.full((r,), n_classes, jnp.int32)]
        )
        weight = jnp.concatenate([spec.weight, jnp.zeros((1,), jnp.float32)])
        deadline = tail_weight = None
        if spec.deadline is not None:
            deadline = jnp.concatenate(
                [spec.deadline, jnp.asarray([jnp.inf], jnp.float32)]
            )
            tail_weight = jnp.concatenate(
                [spec.tail_weight, jnp.zeros((1,), jnp.float32)]
            )
        return ObjectiveSpec(
            class_id=cid, weight=weight, deadline=deadline,
            tail_weight=tail_weight,
        )

    def replan(
        self,
        class_rates: np.ndarray,
        avail: np.ndarray,
        *,
        candidate_masks: list[np.ndarray] | None = None,
        pi0: np.ndarray | None = None,
        carry: Any | None = None,
        key: Any | None = None,
        repair: Any | None = None,
        cache_up: bool = True,
    ) -> np.ndarray:
        """New (r, m) dispatch matrix from estimated moments + health mask.

        ``pi0`` (the plan currently dispatching) adds warm-started
        candidates; ``carry`` (``storage.simulator.SimCarry``) plus a PRNG
        ``key`` switch scoring to predictive rollouts from the live queue
        state. ``repair`` (a ``storage.repair.RepairFlow``) folds known
        reconstruction traffic into every candidate solve and rollout; the
        jointly-optimized repair dispatch is left in :attr:`repair_pi`.
        With a ``cache`` model, ``class_rates`` are *miss* rates and
        ``cache_up`` is the hot tier's health-check verdict for the
        upcoming segment (False plans for full raw load, zero hits).
        All other inputs are measured/estimated quantities — ground truth
        never enters.
        """
        from repro.storage.cache import che_hit_rates
        from repro.storage.repair import augment_plan

        with diag.span("replan.step"):
            with diag.span("replan.estimate"):
                r = int(np.asarray(self.k).shape[0])
                avail = np.asarray(avail, bool)
                masks = [avail] if candidate_masks is None else candidate_masks
                thetas = (self.theta,) if self.thetas is None else tuple(self.thetas)
                mom = self.estimator.moments()
                with_repair = repair is not None and repair.active
                k_vec = np.asarray(self.k, np.float32)
                lam_np = np.asarray(class_rates, np.float64)
                cache_spec = None
                ttl_plan = None
                if self.cache is not None:
                    # invert miss -> raw through the TTLs those misses were
                    # observed under (zeros when the tier was down: identity)
                    ttl_prev = (
                        np.zeros((r,))
                        if self.last_ttl is None
                        else np.asarray(self.last_ttl, np.float64)
                    )
                    raw = self.cache.reconstruct_raw_rates(
                        lam_np, ttl_prev, prior=self.last_raw
                    )
                    self.last_raw = raw
                    if cache_up:
                        ttl_plan = self.cache.ttl(raw)  # promotion/demotion
                        hit = che_hit_rates(raw, ttl_plan)
                        lam_np = raw
                    else:
                        ttl_plan = np.zeros((r,))
                        hit = np.zeros((r,))
                        # outage plan: full raw load plus surge head-room (the
                        # EWMA raw estimate lags the storm; see surge_margin)
                        lam_np = raw * float(self.surge_margin)
                    self.last_ttl = ttl_plan
                if with_repair:
                    lam_np = np.concatenate([lam_np, np.asarray(repair.lam)])
                    k_vec = np.concatenate([k_vec, np.asarray(repair.k, np.float32)])
                if self.cache is not None:
                    # repair rows join with hit 0 — reconstruction reads fetch
                    # lost chunks, which no hot tier holds
                    from repro.core import make_cache_spec

                    cache_spec = make_cache_spec(
                        np.concatenate([hit, np.zeros((lam_np.shape[0] - r,))]),
                        hit_latency=self.cache.hit_latency,
                        hot_cost=self.cache.hot_cost(),
                    )
                lam = jnp.asarray(lam_np, jnp.float32)
                objective = self._repair_objective() if with_repair else self.objective
            with diag.span("replan.assemble"):
                probs, starts = [], []
                racks = rack_count(self.domain, avail.shape[-1])
                for t in thetas:
                    for mk in masks:
                        mask = np.broadcast_to(
                            np.asarray(mk, bool), (r, avail.shape[-1])
                        )
                        if with_repair:
                            mask = np.concatenate(
                                [mask, np.asarray(repair.mask, bool)], axis=0
                            )
                        mask = jnp.asarray(mask)
                        prob = JLCMProblem(
                            lam=lam,
                            k=jnp.asarray(k_vec),
                            moments=mom,
                            cost=jnp.asarray(self.cost, jnp.float32),
                            theta=float(t),
                            mask=mask,
                            objective=objective,
                            cache=cache_spec,
                            domain=self.domain,
                        )
                        probs.append(prob)
                        starts.append(feasible_uniform(mask, prob.k, racks))
                        if pi0 is not None:
                            if with_repair:
                                start, _ = augment_plan(pi0, lam_np[:r], repair)
                            else:
                                start = np.asarray(pi0)
                            probs.append(prob)
                            starts.append(jnp.asarray(start, jnp.float32))
            with diag.span("replan.solve") as solve_span:
                sols = solve_batch(probs, max_iters=self.max_iters, pi0=jnp.stack(starts))
                with diag.span("replan.solve_wait"):
                    jax.block_until_ready(sols.pi)
            self.solve_walls.append(solve_span.seconds)
            self.replans += 1

            if carry is not None and key is not None:
                with diag.span("replan.arbitrate") as arb_span:
                    with diag.span("replan.rollout_fit"):
                        d, srv_rates = self.estimator.fitted_shifted_exp()
                    ttl_roll = hit_lat = None
                    if self.cache is not None:
                        # roll out with the planned TTLs so the scorer sees the
                        # same thinned queue load the solver planned for (repair
                        # rows TTL 0: never cached)
                        ttl_roll = jnp.asarray(
                            np.concatenate(
                                [ttl_plan, np.zeros((lam_np.shape[0] - r,))]
                            ),
                            jnp.float32,
                        )
                        hit_lat = jnp.asarray(self.cache.hit_latency, jnp.float32)
                        cache_st = getattr(carry, "cache", None)
                        if cache_st is None or cache_st.shape != ttl_roll.shape:
                            carry = carry._replace(
                                cache=jnp.full(ttl_roll.shape, -jnp.inf)
                            )
                    if self.rollout_batched:
                        # every candidate rolled out + scored (the same composed
                        # empirical objective as the sequential loop, repair rows
                        # masked out) + cost-folded + argmin'd in ONE compiled
                        # device program; int(best) below is the replan's single
                        # host sync
                        scores, best_dev = batched_rollout_scores(
                            carry,
                            key,
                            sols.pi,
                            lam,
                            jnp.asarray(d, jnp.float32),
                            jnp.asarray(srv_rates, jnp.float32),
                            jnp.asarray(avail),
                            self.theta * sols.cost,  # device-side cost fold
                            self.objective,
                            n_clients=r,
                            n_requests=self.rollout_requests,
                            rollout_seeds=self.rollout_seeds,
                            ttl=ttl_roll,
                            hit_latency=0.0 if hit_lat is None else hit_lat,
                            devices=self.rollout_devices,
                        )
                        with diag.span("replan.sync"):
                            # jaxcheck: JX001 ok the ONE host sync per replan (arbitration argmin)
                            best = int(best_dev)
                        self.last_scores = scores[: len(probs)]
                    else:
                        from repro.storage.simulator import run_segment_raw

                        cost_term = self.theta * np.asarray(sols.cost)
                        scores = []
                        for i in range(len(probs)):
                            _, res = run_segment_raw(
                                carry,
                                key,
                                sols.pi[i],
                                lam,
                                jnp.asarray(d, jnp.float32),
                                jnp.asarray(srv_rates, jnp.float32),
                                jnp.asarray(avail),
                                self.rollout_requests,
                                ttl_roll,
                                0.0 if hit_lat is None else hit_lat,
                            )
                            lat_np = np.asarray(res.latency)
                            fid_np = np.asarray(res.file_id)
                            if with_repair:  # score client traffic only
                                client = fid_np < r
                                lat_np, fid_np = lat_np[client], fid_np[client]
                            # same objective as the analytic fallback, with the
                            # empirical composed objective (weighted mean + per-
                            # class exceedance frequencies) replacing the loose,
                            # backlog-blind analytic bound
                            scores.append(
                                empirical_objective(lat_np, fid_np, self.objective)
                                + float(cost_term[i])
                            )
                        best = int(np.argmin(scores))
                        self.last_scores = np.asarray(scores)
                self.rollout_walls.append(arb_span.seconds)
            else:
                cost_term = self.theta * np.asarray(sols.cost)
                scores = (np.asarray(sols.latency_tight) + cost_term).tolist()
                best = int(np.argmin(scores))
                self.last_scores = np.asarray(scores)
            with diag.span("replan.deploy"):
                if sols.iterations is not None:
                    it = np.asarray(sols.iterations)
                    # the vmapped while loop runs until its slowest lane stops
                    diag.count("solver.trips", int(it.max()))
                    diag.count("solver.lanes", int(it.size))
                    self.solve_iters.append(int(it[best] if it.ndim else it))
                if sols.rack_merges is not None:
                    # jaxcheck: JX001 ok per-replan telemetry read after the decision sync, one transfer
                    merges = int(np.asarray(sols.rack_merges)[best])
                    diag.count("plan.rack_merges", merges)
                pi_best = np.asarray(sols.pi[best])
                self.repair_pi = pi_best[r:] if with_repair else None
        return pi_best[:r]


@dataclasses.dataclass
class HierarchicalReplanner:
    """Cluster-granularity closed loop for very large catalogs.

    The million-file variant of :class:`AdaptiveReplanner`: the catalog
    is aggregated once into O(100) clusters (``core.aggregate``), every
    replan solves at cluster granularity, and the per-file dispatch
    matrix is the exact gather ``cluster_pi[cluster_of_file]`` — O(C m)
    solver work and plan state no matter how many files the catalog
    holds. Two replan tiers keep the steady state cheap:

    * **incremental** (the default): ``resolve_incremental`` re-solves
      only the clusters whose estimated rates moved by more than
      ``rate_threshold`` (relative), freezing the rest as background
      load at their *new* rates; a quiet segment costs near-zero solver
      work.
    * **full**: when the estimated service moments drift beyond
      ``moment_threshold`` (relative, any node — a hotspot is a moment
      shift no rate diff can see) or the availability mask changes, the
      whole cluster problem is re-solved, warm-started from the
      incumbent cluster plan when the mask allows it.

    Telemetry mirrors :class:`AdaptiveReplanner` (``solve_iters``,
    ``solve_walls``) plus the per-replan count of re-solved clusters
    (``resolved_counts``) so scenario CSVs can show the incremental
    path's work saving.
    """

    hierarchy: Hierarchy
    cost: np.ndarray  # (m,) per-node cost V_j
    theta: float
    estimator: EwmaMomentEstimator
    max_iters: int = 300
    eps: float = 1e-4
    rate_threshold: float = 0.2
    moment_threshold: float = 0.05
    plan: FactoredPlan | None = None
    replans: int = 0
    full_solves: int = 0
    solve_iters: list = dataclasses.field(default_factory=list)
    solve_walls: list = dataclasses.field(default_factory=list)
    resolved_counts: list = dataclasses.field(default_factory=list)
    # inputs of the last *full* solve (drift is measured against these,
    # not the previous segment: slow creep must accumulate, not evade
    # the threshold one small step at a time)
    _solved_mom: ServiceMoments | None = None
    _solved_avail: np.ndarray | None = None

    def cluster_rates(self, file_rates: np.ndarray) -> np.ndarray:
        """Exact (C,) cluster rates from per-file estimates (one bincount)."""
        cid = self.hierarchy.cluster_of_file()
        return np.bincount(
            cid,
            weights=np.asarray(file_rates, np.float64),
            minlength=self.hierarchy.n_clusters,
        )

    def _moments_moved(self, mom: ServiceMoments) -> bool:
        if self._solved_mom is None:
            return True
        for new, old in zip(mom, self._solved_mom):
            new = np.asarray(new, np.float64)
            old = np.asarray(old, np.float64)
            tol = self.moment_threshold * np.maximum(np.abs(old), 1e-12)
            if np.any(np.abs(new - old) > tol):
                return True
        return False

    def replan(self, file_rates: np.ndarray, avail: np.ndarray) -> np.ndarray:
        """New (r, m) dispatch matrix from estimated per-file rates + mask.

        All inputs are measured/estimated, as in the plain loop. Returns
        the materialized per-file matrix for the data plane; the factored
        plan stays in :attr:`plan` for the next incremental step.
        """
        avail = np.asarray(avail, bool)
        mom = self.estimator.moments()
        lam_c = self.cluster_rates(file_rates)
        cost = jnp.asarray(self.cost, jnp.float32)
        with diag.span("replan.solve") as solve_span:
            full = (
                self.plan is None
                or self._moments_moved(mom)
                or self._solved_avail is None
                or not np.array_equal(avail, self._solved_avail)
            )
            if full:
                h = self.hierarchy._replace(lam=lam_c)
                mask = jnp.asarray(
                    np.broadcast_to(avail, (h.n_clusters, avail.shape[-1]))
                )
                prob = build_problem(h, mom, cost, self.theta)._replace(
                    mask=mask
                )
                # warm AND cold candidates, arbitrated by solved objective
                # (mirrors AdaptiveReplanner's candidate grid): a warm start
                # from the incumbent can stall the relative stopping rule
                # right at its starting point when the moments moved under
                # it, while on mild drift it converges in a handful of
                # iterations — solving both costs one extra batch lane and
                # keeps whichever is actually better. The incumbent is only
                # a valid candidate while every node it uses is up.
                starts = [feasible_uniform(mask, prob.k)]
                if self.plan is not None and bool(avail.all()):
                    starts.append(
                        jnp.asarray(self.plan.cluster_pi, jnp.float32)
                    )
                sols = solve_batch(
                    [prob] * len(starts),
                    max_iters=self.max_iters,
                    eps=self.eps,
                    pi0=jnp.stack(starts),
                )
                # device argmin: transfer the winning index, not the whole
                # objective vector (the same one-sync contract the rollout
                # replanners' batched arbitration keeps)
                best = int(jnp.argmin(sols.objective))
                self.plan = FactoredPlan(
                    h, jnp.asarray(sols.pi[best]), lam_c.copy()
                )
                it = np.asarray(sols.iterations)
                iters = int(it[best] if it.ndim else it)
                self.resolved_counts.append(int(h.n_clusters))
                self.full_solves += 1
                self._solved_mom = mom
                self._solved_avail = avail.copy()
            else:
                self.plan, info = resolve_incremental(
                    self.plan,
                    lam_c,
                    mom,
                    cost,
                    self.theta,
                    threshold=self.rate_threshold,
                    max_iters=self.max_iters,
                    eps=self.eps,
                )
                iters = int(info.iterations)
                self.resolved_counts.append(int(info.n_resolved))
            pi = np.asarray(jax.block_until_ready(materialize(self.plan)))
        self.solve_walls.append(solve_span.seconds)
        self.solve_iters.append(iters)
        self.replans += 1
        return pi


@dataclasses.dataclass
class GeoAdaptiveReplanner:
    """Geo-aware closed loop: re-place chunks toward the active client site.

    The geo twin of :class:`AdaptiveReplanner`. Its estimated state is one
    dimension richer on both axes of the loop:

    * **moments** — the :class:`EwmaMomentEstimator` is seeded with the
      fabric's (C, m) per-(client-site, node) moments and fed the geo
      simulator's per-pair observations (``GeoSegmentResult.obs``, every
      field (C, m)); the estimator is elementwise, so it tracks the full
      pair family unchanged. Cross-site egress degradation shows up as a
      *row-pattern* drift no per-node estimate could represent.
    * **rates** — an :class:`EwmaRateEstimator` over flattened
      (site, file) ids tracks the (C, r) arrival matrix; its column sums
      are the catalog rates and its normalized rows the per-file client
      mix, which is how a migrating population ("follow the sun") enters
      the solver.

    Each :meth:`replan` builds geo problems (``core.geo.geo_problem``)
    from those estimates — per-pair moments AND mix, so the solve trades
    locality against storage cost — for the same warm/cold x theta x mask
    candidate grid as :meth:`AdaptiveReplanner.replan` (the grid-build /
    warm-start / score-and-argmin conventions deliberately mirror that
    method; a change to either candidate loop should be applied to both),
    in ONE ``solve_batch`` call
    (the ``GeoSpec`` is a pytree: a candidate sweep over client mixes is
    a single vmapped program). Candidates are arbitrated by geo rollouts
    from the live queue state — batched like the plain loop
    (:func:`batched_rollout_scores` with the geo segment kernel) and
    scored under the composed empirical ``objective`` (tenant weights and
    deadlines bind geo arbitration exactly as they bind solves), falling
    back to the analytic composed bound when no ``carry``/``key`` is
    given.
    """

    k: np.ndarray  # (r,) MDS k_i per file
    cost: np.ndarray  # (m,) per-node cost V_j
    theta: float
    estimator: EwmaMomentEstimator  # prior/updates carry (C, m) arrays
    # tenant mix: candidate solves optimize the composed geo objective and
    # rollout arbitration scores candidates under the SAME spec (shared
    # device empirical objective) — geo replans honor per-class weights
    # and deadlines exactly like the non-geo loop
    objective: ObjectiveSpec | None = None
    thetas: tuple[float, ...] | None = None
    max_iters: int = 400
    rollout_requests: int = 600
    # batched-arbitration knobs; see AdaptiveReplanner for semantics
    rollout_seeds: int = 1
    rollout_batched: bool = True
    rollout_devices: str = "auto"
    replans: int = 0
    # per-replan solver telemetry (mirrors AdaptiveReplanner)
    solve_iters: list = dataclasses.field(default_factory=list)
    solve_walls: list = dataclasses.field(default_factory=list)
    rollout_walls: list = dataclasses.field(default_factory=list)
    last_scores: Any = None

    def replan(
        self,
        lam_cs: np.ndarray,
        avail: np.ndarray,
        *,
        candidate_masks: list[np.ndarray] | None = None,
        pi0: np.ndarray | None = None,
        carry: Any | None = None,
        key: Any | None = None,
    ) -> np.ndarray:
        """New (r, m) dispatch matrix from the estimated (C, r) traffic
        matrix plus the health mask. All inputs are measured/estimated —
        ground truth never enters (availability is the health-checker
        input, same detection model as the plain loop)."""
        from repro.core import geo_problem

        lam_cs = np.asarray(lam_cs, np.float64)
        c, r = lam_cs.shape
        avail = np.asarray(avail, bool)
        lam = lam_cs.sum(axis=0)
        # a file observed at (essentially) zero rate has no empirical mix;
        # give it the population-average mix rather than 0/0
        pop = lam_cs.sum(axis=1)
        pop_mix = pop / max(pop.sum(), 1e-12)
        safe = np.maximum(lam, 1e-12)
        mix = np.where(
            (lam > 1e-12)[:, None], (lam_cs / safe).T, pop_mix[None, :]
        )
        site_mom = self.estimator.moments()  # ServiceMoments, (C, m) arrays

        masks = [avail] if candidate_masks is None else candidate_masks
        thetas = (self.theta,) if self.thetas is None else tuple(self.thetas)
        probs, starts = [], []
        for t in thetas:
            for mk in masks:
                mask = jnp.asarray(
                    np.broadcast_to(np.asarray(mk, bool), (r, avail.shape[-1]))
                )
                prob = geo_problem(
                    jnp.asarray(lam, jnp.float32),
                    jnp.asarray(self.k, jnp.float32),
                    site_mom,
                    mix,
                    jnp.asarray(self.cost, jnp.float32),
                    float(t),
                    mask=mask,
                    objective=self.objective,
                )
                probs.append(prob)
                starts.append(feasible_uniform(mask, prob.k))
                if pi0 is not None:
                    probs.append(prob)
                    starts.append(jnp.asarray(np.asarray(pi0), jnp.float32))
        with diag.span("replan.solve") as solve_span:
            sols = solve_batch(probs, max_iters=self.max_iters, pi0=jnp.stack(starts))
            with diag.span("replan.solve_wait"):
                jax.block_until_ready(sols.pi)
        self.solve_walls.append(solve_span.seconds)
        self.replans += 1

        if carry is not None and key is not None:
            d, srv_rates = self.estimator.fitted_shifted_exp()  # (C, m) each
            lam_cs_j = jnp.asarray(lam_cs, jnp.float32)
            with diag.span("replan.arbitrate") as arb_span:
                if self.rollout_batched:
                    # geo twin of the fused arbitration: all candidates rolled
                    # out, scored under the composed empirical objective (NOT
                    # a bare latency mean — tenant weights/deadlines bind geo
                    # arbitration too), cost-folded, and argmin'd on device
                    scores, best_dev = batched_rollout_scores(
                        carry,
                        key,
                        sols.pi,
                        lam_cs_j,
                        jnp.asarray(d, jnp.float32),
                        jnp.asarray(srv_rates, jnp.float32),
                        jnp.asarray(avail),
                        self.theta * sols.cost,  # device-side cost fold
                        self.objective,
                        n_clients=r,
                        n_requests=self.rollout_requests,
                        rollout_seeds=self.rollout_seeds,
                        devices=self.rollout_devices,
                        geo=True,
                    )
                    # jaxcheck: JX001 ok the ONE host sync per replan (arbitration argmin)
                    best = int(best_dev)
                    self.last_scores = scores[: len(probs)]
                else:
                    from repro.storage.simulator import run_geo_segment_raw

                    cost_term = self.theta * np.asarray(sols.cost)
                    scores = []
                    for i in range(len(probs)):
                        _, res = run_geo_segment_raw(
                            carry,
                            key,
                            sols.pi[i],
                            lam_cs_j,
                            jnp.asarray(d, jnp.float32),
                            jnp.asarray(srv_rates, jnp.float32),
                            jnp.asarray(avail),
                            self.rollout_requests,
                        )
                        scores.append(
                            empirical_objective(
                                np.asarray(res.latency),
                                np.asarray(res.file_id),
                                self.objective,
                            )
                            + float(cost_term[i])
                        )
                    best = int(np.argmin(scores))
                    self.last_scores = np.asarray(scores)
            self.rollout_walls.append(arb_span.seconds)
        else:
            cost_term = self.theta * np.asarray(sols.cost)
            scores = (np.asarray(sols.latency_tight) + cost_term).tolist()
            best = int(np.argmin(scores))
            self.last_scores = np.asarray(scores)
        if sols.iterations is not None:
            it = np.asarray(sols.iterations)
            self.solve_iters.append(int(it[best] if it.ndim else it))
        return np.asarray(sols.pi[best])


def simulate_serving(
    key,
    router: Router,
    class_rates: jnp.ndarray,
    moments_sampler,
    n_requests: int = 20000,
):
    """Event-driven FCFS simulation with hedging (first completion wins;
    hedged copies still occupy their queues — conservative model)."""
    from repro.storage.simulator import generate_workload

    m = router.pool.m
    k_wl, k_route, k_srv = jax.random.split(jax.random.key(0) if key is None else key, 3)
    arrival, class_id = generate_workload(k_wl, class_rates, n_requests)
    service = moments_sampler(k_srv, (n_requests,))  # (N, m)
    route_keys = jax.random.split(k_route, n_requests)

    pi_all = jnp.asarray(router.pi)
    kk = 1 + router.hedge

    def pick(rk, cid):
        pi = pi_all[cid]
        if router.hedge > 0:
            pi = project_capped_simplex(pi[None] * kk, jnp.asarray([float(kk)]))[0]
        return madow_sample(rk, pi)

    masks = jax.vmap(pick)(route_keys, class_id)

    def step(dep, inp):
        t, mask, srv = inp
        start = jnp.maximum(t, dep)
        finish = start + srv
        new_dep = jnp.where(mask, finish, dep)
        lat = jnp.min(jnp.where(mask, finish, jnp.inf)) - t  # first-wins
        return new_dep, lat

    _, lat = jax.lax.scan(step, jnp.zeros((m,)), (arrival, masks, service))
    warm = n_requests // 10
    return np.asarray(lat[warm:]), np.asarray(class_id[warm:])
