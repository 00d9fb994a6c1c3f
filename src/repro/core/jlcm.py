"""Algorithm JLCM (paper §IV): joint latency + storage-cost minimization.

Problem JLCM (Eq. 9-14) minimizes, over dispatch probabilities pi (r, m)
and the auxiliary z,

  z + sum_j Lambda_j/(2 lam_hat) [X_j + sqrt(X_j^2 + Y_j)]
    + theta * sum_i sum_j V_j 1(pi_ij > 0)

subject to Theorem-1 feasibility (capped simplex per file). Placement S_i
and code length n_i are recovered from the support of pi (Lemma 4).

The discontinuous cost indicator is handled exactly as in the paper: a
log-smoothed surrogate  V_j log(beta pi + 1)/log(beta)  (Eq. 20) whose
linearization around the reference point pi^(t) is Eq. (17); iterating
"linearize -> solve convex subproblem -> re-linearize" is the DC-programming
outer loop, with the inner convex subproblem solved by projected gradient
descent (paper Fig. 4 routine). Gradients come from JAX autodiff instead of
hand-derived formulas; the projection is `project_capped_simplex`.

Three modes:
  * ``merged``  — all updates on one time-scale (single loop), which is
    what the paper itself uses for the r=1000 experiment (§V.B, Fig. 8).
    The whole outer loop (linearize -> PGD step -> z-refresh -> two-level
    backtracking -> adaptive lr re-growth -> relative stopping rule) runs
    inside one ``jax.lax.while_loop``: one ``solve`` is a single compiled
    XLA call with no per-iteration host transfers.
  * ``debug``   — the same merged-timescale algorithm as a Python loop with
    host-side control flow, for step-by-step trace inspection. Numerically
    equivalent to ``merged``; orders of magnitude slower.
  * ``nested``  — faithful Algorithm JLCM structure (outer linearization,
    inner PGD to convergence, then the z-minimization step).

Batching: :func:`solve_batch` vmaps the device-resident loop over a stacked
leading axis of problems (shared (r, m) shape; ``lam``/``theta``/``cost``/
``moments``/``k``/``mask`` may all vary), so a whole theta- or lambda-sweep
is one jitted call.

Objective: the latency term is pluggable (``core/objectives.py``). A
:class:`JLCMProblem` may carry an :class:`ObjectiveSpec` — per-file tenant
classes, per-class weights, optional per-class tail deadlines — and every
mode/batch path optimizes the composed convex objective instead of the
paper's single request-weighted mean; objective *values* may vary across a
stacked batch (the tenant-tradeoff sweep), only the structure must match.
``objective=None`` is the paper's scalar objective, bit-for-bit.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array

from repro import diag

from .geo import GeoSpec, geo_eq_varq
from .latency_bound import file_latency_bounds
from .objectives import (
    CacheSpec,
    ObjectiveSpec,
    apply_cache_thinning,
    class_mean_bounds,
    class_tail_bounds,
    compose_file_bounds,
    composed_latency,
    refresh_shared_z,
)
from .projection import (
    feasible_uniform,
    project_capped_simplex,
    rack_count,
    round_racks,
)
from .queueing import (
    ServiceMoments,
    node_arrival_rates,
    pk_sojourn_moments,
    stability_penalty,
)

SUPPORT_TOL = 1e-3  # pi below this counts as "not placed" when reading S_i
BACKTRACK_SLACK = 1e-9  # accept a step iff obj <= prev + this
# the rack-domain re-solve stops only once the Frank-Wolfe gap on its
# placement is under this share of the latency bound (as well as on the
# relative tolerance)
RACK_GAP_TOL = 5e-3


class JLCMProblem(NamedTuple):
    lam: Array  # (r,) request arrival rates
    k: Array  # (r,) MDS k_i per file
    moments: ServiceMoments  # per-node service moments, arrays of (m,)
    cost: Array  # (m,) per-chunk storage price V_j
    theta: float | Array  # tradeoff factor (sec/dollar)
    mask: Array | None = None  # (r, m) optional allowed-placement support
    # pluggable objective (core/objectives.py): per-class weighted mean +
    # tail-probability terms; None = the paper's uniform mean, bit-for-bit
    objective: ObjectiveSpec | None = None
    # geo-aware client fabric (core/geo.py): per-(client-site, node)
    # service moments + per-file client mix. None = the single-implicit-
    # client model, op-for-op; build geo problems with `core.geo.
    # geo_problem` (which also keeps `moments` consistent as the node
    # mixture and collapses C == 1 to the plain path exactly)
    geo: GeoSpec | None = None
    # hot/warm cache tier (core/objectives.py::CacheSpec, built by
    # storage/cache.py): per-file hot-cache hit rates thin the arrivals
    # the warm-tier solve plans against to lam_i (1 - h_i), hits blend
    # back in at hit_latency, and the replicated hot tier's cost joins
    # the reported objective. None = every read hits the warm tier,
    # op-for-op identical to the pre-cache solver
    cache: CacheSpec | None = None
    # hierarchical planning (core/aggregate.py): a row may stand for many
    # files (a cluster or volume); cost_weight (r,) multiplies that row's
    # storage-cost contribution by its file multiplicity. None = every row
    # is one stored object, bit-for-bit the dense objective
    cost_weight: Array | None = None
    # partial re-solves (aggregate.resolve_incremental): (m,) node arrival
    # rates contributed by rows frozen outside this problem; added to the
    # queue utilizations (P-K moments + stability) so the re-optimized rows
    # see the congestion the frozen traffic causes. None = no frozen
    # traffic, bit-for-bit the standalone solve
    background: Array | None = None
    # failure domains: (m,) rack index of each node, racks equal in size
    # and laid out rack-major (projection.rack_count). A read then takes
    # at most one chunk from a rack (the rack caps of the projection) and
    # the deployed placement stores at most one chunk of a row per rack
    # (the per-rack round of _solve_racks). None = no domains, bit-for-bit
    # the capped-simplex solve
    domain: Array | None = None

    @property
    def r(self) -> int:
        return self.lam.shape[-1]

    @property
    def m(self) -> int:
        return self.cost.shape[-1]


class JLCMSolution(NamedTuple):
    pi: Array  # (r, m) dispatch probabilities
    z: Array  # shared auxiliary variable at optimum
    objective: Array  # composed latency + theta * true (indicator) cost
    latency: Array  # shared-z composed latency objective value
    latency_tight: Array  # per-file-z composed objective (reporting)
    cost: Array  # true storage cost sum_i sum_{S_i} V_j
    n: Array  # (r,) chosen code lengths n_i
    placement: Array  # (r, m) boolean S_i
    objective_trace: Array  # per-iteration smoothed objective (monitoring)
    # per-class reporting, present iff the problem carried an ObjectiveSpec:
    class_latency: Array | None = None  # (C,) per-class tight mean bounds
    class_tail: Array | None = None  # (C,) per-class P[T_c > d_c] bounds
    # solver iterations actually run (scalar for `solve`, (B,) for
    # `solve_batch`); what the warm-start win is measured by
    iterations: Array | None = None
    # rack-domain problems only: the (row, rack) pairs the per-rack round
    # collapsed from more than one host above SUPPORT_TOL to one
    rack_merges: Array | None = None


def _true_cost(
    pi: Array, cost: Array, tol: float = SUPPORT_TOL, weight: Array | None = None
) -> Array:
    if weight is None:
        return jnp.sum((pi > tol) * cost[..., None, :], axis=(-2, -1))
    body = weight[..., :, None] * (pi > tol) * cost[..., None, :]
    return jnp.sum(body, axis=(-2, -1))


def _smoothed_cost(
    pi: Array, cost: Array, beta: float, weight: Array | None = None
) -> Array:
    """Eq. (20): sum_ij V_j log(beta pi + 1) / log(beta)."""
    body = cost[..., None, :] * jnp.log(beta * pi + 1.0) / jnp.log(beta)
    if weight is not None:
        body = weight[..., :, None] * body
    return jnp.sum(body, axis=(-2, -1))


def _linearized_cost(
    pi: Array,
    pi_ref: Array,
    cost: Array,
    beta: float,
    weight: Array | None = None,
) -> Array:
    """Eq. (17): value at ref + gradient of the log surrogate at ref."""
    if weight is None:
        base = jnp.sum((pi_ref > 0.0) * cost[..., None, :], axis=(-2, -1))
        slope = cost[..., None, :] / ((pi_ref + 1.0 / beta) * jnp.log(beta))
        return base + jnp.sum(slope * (pi - pi_ref), axis=(-2, -1))
    w = weight[..., :, None]
    base = jnp.sum(w * (pi_ref > 0.0) * cost[..., None, :], axis=(-2, -1))
    slope = w * cost[..., None, :] / ((pi_ref + 1.0 / beta) * jnp.log(beta))
    return base + jnp.sum(slope * (pi - pi_ref), axis=(-2, -1))


def _latency_term(pi: Array, z: Array, prob: JLCMProblem) -> Array:
    lat = composed_latency(
        pi, z, prob.lam, prob.moments, prob.objective, prob.geo, prob.cache,
        background=prob.background,
    )
    # stability is a property of the queues the warm tier actually serves:
    # node arrival rates are evaluated at the cache-thinned miss traffic
    # (plus any frozen-row background load the subproblem doesn't control)
    rates = node_arrival_rates(pi, apply_cache_thinning(prob.lam, prob.cache))
    if prob.background is not None:
        rates = rates + prob.background
    return lat + stability_penalty(rates, prob.moments)


def _refresh_z(pi: Array, prob: JLCMProblem) -> Array:
    return refresh_shared_z(
        pi, prob.lam, prob.moments, prob.objective, prob.geo, prob.cache,
        background=prob.background,
    )


def smoothed_objective(pi: Array, z: Array, prob: JLCMProblem, beta: float) -> Array:
    """Descent-monitored objective z + sum_j F(Lambda_j) + theta*C_hat (Thm 2)."""
    return _latency_term(pi, z, prob) + prob.theta * _smoothed_cost(
        pi, prob.cost, beta, weight=prob.cost_weight
    )


def _merged_grad(pi: Array, z: Array, prob: JLCMProblem, beta) -> Array:
    """Gradient of Eq. (19) linearized at the current point (merged mode)."""

    def sub_obj(p):
        return _latency_term(p, z, prob) + prob.theta * _linearized_cost(
            p, jax.lax.stop_gradient(p), prob.cost, beta,
            weight=prob.cost_weight,
        )

    return jax.grad(sub_obj)(pi)


# ---------------------------------------------------------------------------
# Device-resident merged-mode loop (one XLA program per solve).
# ---------------------------------------------------------------------------


class _LoopState(NamedTuple):
    pi: Array  # (r, m) current iterate
    z: Array  # current shared auxiliary variable
    prev: Array  # smoothed objective at (pi, z)
    lr: Array  # calibrated base learning rate (adaptive)
    t: Array  # iterations completed, int32
    done: Array  # bool: converged or lr collapsed
    trace: Array  # (max_iters + 1,) objective per iteration, NaN-padded


def _device_merged_loop(
    pi: Array,
    prob: JLCMProblem,
    mask: Array,
    beta: Array,
    lr: Array,
    eps: Array,
    max_iters: int,
    racks: int | None = None,
    gap_racks: int | None = None,
) -> tuple[Array, Array, Array, Array]:
    """Merged-timescale JLCM entirely on device.

    Per iteration: linearize the cost surrogate at the current pi, take one
    projected-gradient step, refresh z, and run a two-level backtracking
    line search (lr, lr/4, lr/16 via nested ``lax.cond``) with adaptive lr
    re-growth on acceptance / a 16x shrink on persistent failure (the
    round probed down to lr/16 already). Stops on the
    paper's relative tolerance or when lr collapses, with `max_iters` as
    the trip-count bound of the ``lax.while_loop``. With ``racks`` every
    projection carries the rack caps. With ``gap_racks`` (``mask`` holding
    at most one host of a row per rack) the relative tolerance stops the
    loop only where the Frank-Wolfe gap on ``mask`` is under
    ``RACK_GAP_TOL`` of the latency bound (:func:`_placement_gap`).

    Returns (pi, z, trace, iters); trace is NaN beyond entry `iters`.
    """
    pi = project_capped_simplex(pi, prob.k, mask, racks=racks)
    z = _refresh_z(pi, prob)
    prev = smoothed_objective(pi, z, prob, beta)

    g0 = jnp.max(jnp.abs(_merged_grad(pi, z, prob, beta)))
    lr0 = lr / jnp.maximum(g0, 1e-9)  # first step moves ~lr in pi
    lr_cap = lr0 * 16.0

    trace = jnp.full((max_iters + 1,), jnp.nan, dtype=prev.dtype).at[0].set(prev)
    state = _LoopState(
        pi=pi,
        z=z,
        prev=prev,
        lr=lr0,
        t=jnp.asarray(0, jnp.int32),
        done=jnp.asarray(False),
        trace=trace,
    )

    def cond(s: _LoopState) -> Array:
        return jnp.logical_and(s.t < max_iters, jnp.logical_not(s.done))

    def body(s: _LoopState) -> _LoopState:
        with diag.scope("jlcm.iterate"):
            g = _merged_grad(s.pi, s.z, prob, beta)

            def attempt(step_lr):
                with diag.scope("jlcm.project"):
                    p = project_capped_simplex(
                        s.pi - step_lr * g, prob.k, mask, racks=racks
                    )
                zz = _refresh_z(p, prob)
                return p, zz, smoothed_objective(p, zz, prob, beta)

            def backtrack(_):
                second = attempt(s.lr / 4.0)
                return jax.lax.cond(
                    second[2] > s.prev + BACKTRACK_SLACK,
                    lambda _: attempt(s.lr / 16.0),
                    lambda _: second,
                    None,
                )

            first = attempt(s.lr)
            cand = jax.lax.cond(
                first[2] > s.prev + BACKTRACK_SLACK, backtrack, lambda _: first, None
            )

            accepted = cand[2] <= s.prev + BACKTRACK_SLACK
            pi_n = jnp.where(accepted, cand[0], s.pi)
            z_n = jnp.where(accepted, cand[1], s.z)
            obj = jnp.where(accepted, cand[2], s.prev)  # stalled step keeps prev
            # a rejected round already probed {lr, lr/4, lr/16}, so shrinking
            # 16x continues the geometric /4 probe grid with nothing skipped —
            # and a warm start at a converged point collapses in ~4 rounds
            # instead of ~40 halvings
            lr_n = jnp.where(accepted, jnp.minimum(s.lr * 1.1, lr_cap), s.lr / 16.0)
            collapsed = jnp.logical_and(~accepted, lr_n <= lr_cap * 1e-6)
            # relative stopping rule (paper: tolerance on normalized objective);
            # a rejected step only stops once lr has collapsed — otherwise it
            # shrinks lr and retries (obj == prev would trip the eps test).
            converged = jnp.logical_and(
                accepted,
                jnp.abs(s.prev - obj) < eps * jnp.maximum(1.0, jnp.abs(obj)),
            )
            if gap_racks is not None:
                gap = _placement_gap(s.pi, g, mask, prob.k, gap_racks)
                converged &= gap <= RACK_GAP_TOL * _latency_term(s.pi, s.z, prob)
            return _LoopState(
                pi=pi_n,
                z=z_n,
                prev=obj,
                lr=lr_n,
                t=s.t + 1,
                done=jnp.logical_or(collapsed, converged),
                trace=s.trace.at[s.t + 1].set(obj),
            )

    out = jax.lax.while_loop(cond, body, state)
    return out.pi, out.z, out.trace, out.t


def _placement_gap(pi: Array, g: Array, mask: Array, k: Array, racks: int) -> Array:
    """Frank-Wolfe gap of ``pi`` for gradient ``g`` over the plans on
    ``mask``, which holds at most one host of a row per rack: <g, pi> less
    each row's k smallest entries of ``g`` on ``mask`` (the vertex of its
    capped simplex), found among the row's one host per rack."""
    shape = pi.shape[:-1] + (racks, pi.shape[-1] // racks)
    best = jnp.sort(jnp.min(jnp.where(mask, g, jnp.inf).reshape(shape), axis=-1), axis=-1)
    take = (jnp.arange(racks) < k[..., None]) & jnp.isfinite(best)
    return jnp.sum(g * pi) - jnp.sum(jnp.where(take, best, 0.0))


def _finalize(pi: Array, z: Array, prob: JLCMProblem, trace: Array) -> JLCMSolution:
    """Read the solution (Lemma 4 support extraction + reporting bounds)."""
    spec = prob.objective
    placement = pi > SUPPORT_TOL
    n = jnp.sum(placement, axis=-1)
    lam_eff = apply_cache_thinning(prob.lam, prob.cache)
    if prob.geo is not None:
        # per-(file, node) sojourn moments: the Lemma-2 machinery is
        # batch-safe in (r, m) shapes, so the geo fabric drops straight in
        eq_b, varq_b = geo_eq_varq(pi, lam_eff, prob.geo)
    else:
        rates = node_arrival_rates(pi, lam_eff)
        if prob.background is not None:
            rates = rates + prob.background
        eq, varq = pk_sojourn_moments(rates, prob.moments)
        eq_b, varq_b = eq[..., None, :], varq[..., None, :]
    t = file_latency_bounds(pi, eq_b, varq_b)
    tight = compose_file_bounds(t, pi, eq_b, varq_b, prob.lam, spec, prob.cache)
    latency = composed_latency(
        pi, z, prob.lam, prob.moments, spec, prob.geo, prob.cache,
        background=prob.background,
    )
    cost = _true_cost(pi, prob.cost, weight=prob.cost_weight)
    if prob.cache is not None:
        cost = cost + prob.cache.hot_cost
    class_latency = class_tail = None
    # per-class reporting needs a statically-sized class axis: any of the
    # per-class arrays provides it (a spec with none of them set is a pure
    # fold-through and reports like the scalar objective)
    if spec is not None and (
        spec.weight is not None or spec.deadline is not None
    ):
        t_report = t
        if prob.cache is not None:
            t_report = (
                1.0 - prob.cache.hit
            ) * t + prob.cache.hit * prob.cache.hit_latency
        class_latency = class_mean_bounds(t_report, prob.lam, spec)
        class_tail = class_tail_bounds(
            pi, eq_b, varq_b, lam_eff, spec,
            lam_total=None if prob.cache is None else prob.lam,
        )
    return JLCMSolution(
        pi=pi,
        z=z,
        objective=latency + prob.theta * cost,
        latency=latency,
        latency_tight=tight,
        cost=cost,
        n=n,
        placement=placement,
        objective_trace=trace,
        class_latency=class_latency,
        class_tail=class_tail,
    )


def _solve_racks(pi0, prob, mask, beta, lr, eps, max_iters, racks):
    """The rack-domain solve: the rack-capped loop for up to half of
    ``max_iters``, the round to one host per (row, rack)
    (:func:`round_racks`), then the loop again on the rounded placement
    for the trips left: the re-solve on the merged placement. There a row
    holds at most one host of a rack, so the box x <= 1 is the rack cap and
    the re-solve is the plain capped simplex on that placement; it stops
    once its plan is near stationary there (``RACK_GAP_TOL``).

    Returns (solution, iterations), the trace holding both loops' trips."""
    half = max_iters // 2
    pi, _, trace1, t1 = _device_merged_loop(
        pi0, prob, mask, beta, lr, eps, half, racks
    )
    with diag.scope("jlcm.round"):
        pi, keep, merges = round_racks(pi, mask, racks, SUPPORT_TOL, prob.lam)
    pi, z, trace2, t2 = _device_merged_loop(
        pi, prob, keep, beta, lr, eps, max_iters - half, gap_racks=racks
    )
    trace = jnp.full((max_iters + 1,), jnp.nan, trace1.dtype).at[: half + 1].set(trace1)
    trace = jax.lax.dynamic_update_slice(trace, trace2, (t1,))
    with diag.scope("jlcm.finalize"):
        sol = _finalize(pi, z, prob, trace)
    return sol._replace(rack_merges=merges), t1 + t2


def _solve_one(pi0, prob, mask, beta, lr, eps, max_iters, racks):
    if racks is not None:
        return _solve_racks(pi0, prob, mask, beta, lr, eps, max_iters, racks)
    pi, z, trace, iters = _device_merged_loop(
        pi0, prob, mask, beta, lr, eps, max_iters
    )
    with diag.scope("jlcm.finalize"):
        return _finalize(pi, z, prob, trace), iters


@functools.partial(jax.jit, static_argnames=("max_iters", "racks"))
def _solve_merged_device(pi0, prob, mask, beta, lr, eps, max_iters, racks=None):
    return _solve_one(pi0, prob, mask, beta, lr, eps, max_iters, racks)


@functools.partial(jax.jit, static_argnames=("max_iters", "racks"))
def _solve_merged_device_batch(pi0, prob, mask, beta, lr, eps, max_iters, racks=None):
    def one(p0, pr, mk):
        return _solve_one(p0, pr, mk, beta, lr, eps, max_iters, racks)

    return jax.vmap(one)(pi0, prob, mask)


# ---------------------------------------------------------------------------
# Host-loop paths: `debug` (merged algorithm, Python control flow) and
# `nested` (faithful two-timescale Algorithm JLCM).
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("beta", "inner_steps", "lr"))
def _inner_pgd(
    pi: Array,
    z: Array,
    pi_ref: Array,
    prob: JLCMProblem,
    mask: Array,
    *,
    beta: float,
    inner_steps: int,
    lr: float,
) -> Array:
    """Projected gradient descent on Eq. (19) for a fixed reference point."""

    def sub_obj(p):
        return _latency_term(p, z, prob) + prob.theta * _linearized_cost(
            p, pi_ref, prob.cost, beta, weight=prob.cost_weight
        )

    grad = jax.grad(sub_obj)

    def step(s, p):
        g = grad(p)
        step_lr = lr / jnp.sqrt(1.0 + s)
        return project_capped_simplex(p - step_lr * g, prob.k, mask)

    return jax.lax.fori_loop(0, inner_steps, step, pi)


@functools.partial(jax.jit, static_argnames=("beta",))
def _merged_step(
    pi: Array, z: Array, prob: JLCMProblem, mask: Array, lr: Array, *, beta: float
):
    """One merged-timescale update: linearize at current pi, one PGD step
    (inf-norm-normalized gradient -> scale-free step size), then refresh z
    (the paper's single-loop speedup for large r)."""
    g = _merged_grad(pi, z, prob, beta)
    pi = project_capped_simplex(pi - lr * g, prob.k, mask)
    z = _refresh_z(pi, prob)
    obj = smoothed_objective(pi, z, prob, beta)
    return pi, z, obj, jnp.max(jnp.abs(g))


def _solve_host_loop(
    prob: JLCMProblem,
    pi: Array,
    mask: Array,
    *,
    beta: float,
    mode: str,
    max_iters: int,
    inner_steps: int,
    lr: float,
    eps: float,
    verbose: bool,
) -> JLCMSolution:
    z = _refresh_z(pi, prob)
    trace = []
    prev = smoothed_objective(pi, z, prob, beta)
    trace.append(float(prev))
    lr0 = None  # calibrated on the first step from the gradient scale
    lr_cap = None
    for t in range(max_iters):
        if mode == "debug":
            if lr0 is None:
                _, _, _, g0 = _merged_step(
                    pi, z, prob, mask, jnp.asarray(0.0, jnp.float32), beta=beta
                )
                lr0 = lr / max(float(g0), 1e-9)  # first step moves ~lr in pi
                lr_cap = lr0 * 16
            cand = _merged_step(
                pi, z, prob, mask, jnp.asarray(lr0, jnp.float32), beta=beta
            )
            if float(cand[2]) > float(prev) + BACKTRACK_SLACK:  # backtrack
                cand = _merged_step(
                    pi, z, prob, mask, jnp.asarray(lr0 / 4, jnp.float32), beta=beta
                )
            if float(cand[2]) > float(prev) + BACKTRACK_SLACK:
                cand = _merged_step(
                    pi, z, prob, mask, jnp.asarray(lr0 / 16, jnp.float32), beta=beta
                )
            if float(cand[2]) > float(prev) + BACKTRACK_SLACK:  # persistent
                lr0 /= 16.0  # mirrors the device loop's probe-grid shrink
                obj = prev
                if lr0 > lr_cap * 1e-6:
                    trace.append(float(obj))
                    prev = obj
                    continue  # stalled step: shrink and retry, don't stop
            else:
                pi, z, obj, _ = cand
                lr0 = min(lr0 * 1.1, lr_cap)  # adaptive re-growth
        else:  # nested
            pi = _inner_pgd(
                pi, z, pi, prob, mask, beta=beta, inner_steps=inner_steps, lr=lr
            )
            z = _refresh_z(pi, prob)
            obj = smoothed_objective(pi, z, prob, beta)
        trace.append(float(obj))
        if verbose and t % 20 == 0:
            print(f"[jlcm] iter {t:4d} objective {float(obj):.6f}")
        # relative stopping rule (paper: tolerance on normalized objective)
        if abs(float(prev) - float(obj)) < eps * max(1.0, abs(float(obj))):
            prev = obj
            break
        prev = obj

    return _finalize(pi, z, prob, jnp.asarray(trace))._replace(
        iterations=jnp.asarray(len(trace) - 1)
    )


def _resolve_mask(prob: JLCMProblem) -> Array:
    if prob.mask is None:
        return jnp.ones(prob.lam.shape + prob.cost.shape[-1:], bool)
    return jnp.asarray(prob.mask, bool)


def solve(
    prob: JLCMProblem,
    *,
    beta: float = 1e3,
    mode: str = "merged",
    max_iters: int = 300,
    inner_steps: int = 40,
    lr: float = 0.1,
    eps: float = 1e-5,
    pi0: Array | None = None,
    verbose: bool = False,
) -> JLCMSolution:
    """Run Algorithm JLCM. Returns the solution plus convergence trace.

    ``mode="merged"`` (default) runs the whole outer loop on device as one
    compiled call; ``mode="debug"`` is the same algorithm with host-side
    control flow (use it to inspect iterates; ``verbose`` only prints
    there); ``mode="nested"`` is the paper's two-timescale structure.
    """
    if prob.geo is not None and prob.background is not None:
        raise ValueError(
            "background node load is not supported on geo problems: the "
            "per-site sojourn moments have no single node-rate axis to "
            "add it to (solve the geo problem densely instead)"
        )
    mask = _resolve_mask(prob)
    racks = rack_count(prob.domain, prob.m)
    if racks is not None and mode != "merged":
        raise ValueError(f"rack domains are solved in merged mode only, not {mode!r}")
    if pi0 is None:
        pi = feasible_uniform(mask, prob.k, racks)
    else:
        pi = jnp.asarray(pi0)
        if pi.shape != mask.shape:
            raise ValueError(
                f"pi0 shape {pi.shape} does not match the problem's "
                f"(r, m) = {tuple(mask.shape)}"
            )
    pi = project_capped_simplex(pi, prob.k, mask, racks=racks)

    if mode == "merged":
        with diag.hot_path(
            "core.solve_merged", compiled=(_solve_merged_device,)
        ):
            sol, iters = _solve_merged_device(
                pi,
                prob._replace(mask=None, domain=None),
                mask,
                jnp.asarray(beta, jnp.float32),
                jnp.asarray(lr, jnp.float32),
                jnp.asarray(eps, jnp.float32),
                max_iters,
                racks,
            )
        if racks is not None:
            # jaxcheck: JX001 ok end-of-solve counter, one read beside the trace trim
            diag.count("plan.rack_merges", int(sol.rack_merges))
        # single host sync at the end: trim the NaN-padded trace
        return sol._replace(
            # jaxcheck: JX001 ok deliberate end-of-solve trace trim, one sync
            objective_trace=sol.objective_trace[: int(iters) + 1],
            iterations=iters,
        )
    if mode in ("debug", "nested"):
        return _solve_host_loop(
            prob,
            pi,
            mask,
            beta=beta,
            mode=mode,
            max_iters=max_iters,
            inner_steps=inner_steps,
            lr=lr,
            eps=eps,
            verbose=verbose,
        )
    raise ValueError(f"unknown mode {mode!r}")


# ---------------------------------------------------------------------------
# Batched solving: a stacked axis of problems in one compiled call.
# ---------------------------------------------------------------------------


def stack_problems(probs: Sequence[JLCMProblem]) -> JLCMProblem:
    """Stack problems with a shared (r, m) shape along a new leading axis.

    ``lam``/``k``/``theta``/``cost``/``moments`` may vary per problem — and
    so may the values inside an :class:`ObjectiveSpec` (class weights,
    deadlines, tail weights: the tenant-tradeoff sweep stacks exactly
    those) — but every problem must carry the same objective *structure*
    (same class count, same None-ness of the optional fields), since the
    stacked batch is one vmapped XLA program. A ``mask`` of ones is
    substituted where a problem has ``mask=None`` (all placements allowed).
    """
    probs = list(probs)
    if not probs:
        raise ValueError("stack_problems needs at least one problem")
    r, m = probs[0].r, probs[0].m
    for p in probs:
        if (p.r, p.m) != (r, m):
            raise ValueError(
                f"all problems must share (r, m): got {(p.r, p.m)} vs {(r, m)}"
            )
    specs = [p.objective for p in probs]
    if any(s is None for s in specs) and not all(s is None for s in specs):
        raise ValueError(
            "cannot stack problems mixing objective=None with ObjectiveSpec; "
            "give every problem a spec (uniform: weight=None, deadline=None) "
            "or none"
        )
    if specs[0] is not None:
        shape0 = tuple(None if f is None else f.shape for f in specs[0])
        for s in specs[1:]:
            if tuple(None if f is None else f.shape for f in s) != shape0:
                raise ValueError(
                    "all problems must share the objective structure "
                    "(class count and which optional fields are set)"
                )
    geos = [p.geo for p in probs]
    if any(g is None for g in geos) and not all(g is None for g in geos):
        raise ValueError(
            "cannot stack problems mixing geo=None with GeoSpec; build every "
            "problem through core.geo.geo_problem (values may vary, e.g. a "
            "client-mix sweep — the structure must match)"
        )
    if geos[0] is not None:
        shape0 = tuple(f.shape for f in geos[0])
        for g in geos[1:]:
            if tuple(f.shape for f in g) != shape0:
                raise ValueError(
                    "all problems must share the geo structure "
                    "(site count and (C, m)/(r, C) shapes)"
                )
    caches = [p.cache for p in probs]
    if any(c is None for c in caches) and not all(c is None for c in caches):
        raise ValueError(
            "cannot stack problems mixing cache=None with CacheSpec; give "
            "every problem a spec (degenerate: all-zero hit rates) or none"
        )
    if caches[0] is not None:
        shape0 = tuple(jnp.shape(f) for f in caches[0])
        for c in caches[1:]:
            if tuple(jnp.shape(f) for f in c) != shape0:
                raise ValueError(
                    "all problems must share the cache structure (per-file "
                    "hit vector length; values may vary, e.g. a capacity "
                    "sweep)"
                )
    for field in ("cost_weight", "background", "domain"):
        vals = [getattr(p, field) for p in probs]
        if any(v is None for v in vals) and not all(v is None for v in vals):
            raise ValueError(
                f"cannot stack problems mixing {field}=None with arrays; "
                f"set it on every problem (values may vary) or none"
            )
        if vals[0] is not None:
            shape0 = jnp.shape(vals[0])
            for v in vals[1:]:
                if jnp.shape(v) != shape0:
                    raise ValueError(
                        f"all problems must share the {field} shape: "
                        f"got {jnp.shape(v)} vs {shape0}"
                    )
    normalized = [
        p._replace(
            theta=jnp.asarray(p.theta, jnp.float32),
            mask=_resolve_mask(p),
            domain=None,
        )
        for p in probs
    ]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *normalized)
    if probs[0].domain is None:
        return stacked
    # the rack layout sets the compiled program's shapes: kept on the host
    return stacked._replace(domain=np.stack([np.asarray(p.domain) for p in probs]))


def solve_batch(
    probs: Sequence[JLCMProblem] | JLCMProblem,
    *,
    beta: float = 1e3,
    max_iters: int = 300,
    lr: float = 0.1,
    eps: float = 1e-5,
    pi0: Array | None = None,
) -> JLCMSolution:
    """Solve a batch of JLCM instances in ONE jitted, vmapped device call.

    ``probs`` is either a sequence of :class:`JLCMProblem` sharing (r, m)
    (stacked here via :func:`stack_problems`) or an already-stacked problem
    whose leaves carry a leading batch axis. Returns a :class:`JLCMSolution`
    whose every field has the leading batch axis; ``objective_trace`` is
    (B, max_iters + 1) and NaN-padded past each instance's convergence
    point (per-instance iteration counts differ — use ``~isnan`` to trim).

    This is the hot path for theta-/lambda-sweeps (Figs. 8/13) and for
    what-if re-optimization (e.g. one re-plan per hypothetical node
    failure): hundreds of solver instances become one XLA program.
    """
    with diag.span("solve.stack"):
        stacked = probs if isinstance(probs, JLCMProblem) else stack_problems(probs)
        if stacked.mask is None:
            raise ValueError("stacked problems must carry an explicit mask")
        mask = jnp.asarray(stacked.mask, bool)
        racks = rack_count(stacked.domain, stacked.m)
        if pi0 is None:
            pi0 = feasible_uniform(mask, stacked.k, racks)
        else:
            pi0 = jnp.asarray(pi0)
            if pi0.shape not in (mask.shape, mask.shape[1:]):
                raise ValueError(
                    f"pi0 shape {pi0.shape} matches neither the stacked batch "
                    f"{tuple(mask.shape)} nor a shared per-instance start "
                    f"{tuple(mask.shape[1:])}"
                )
        pi0 = jnp.broadcast_to(jnp.asarray(pi0), mask.shape)
    with diag.span("solve.dispatch"):
        sol, iters = _solve_merged_device_batch(
            pi0,
            stacked._replace(mask=None, domain=None),
            mask,
            jnp.asarray(beta, jnp.float32),
            jnp.asarray(lr, jnp.float32),
            jnp.asarray(eps, jnp.float32),
            max_iters,
            racks,
        )
    return sol._replace(iterations=iters)


# ---------------------------------------------------------------------------
# Oblivious baselines from §V.B Fig. 9 (for the comparison benchmark).
# ---------------------------------------------------------------------------


def proportional_lb_pi(
    mask: Array, k: Array, moments: ServiceMoments, racks: int | None = None
) -> Array:
    """'Oblivious LB': dispatch proportional to service rates on a given
    placement (then projected to the feasible polytope, rack caps
    included with ``racks``)."""
    mask = jnp.asarray(mask, bool)
    mu = jnp.broadcast_to(moments.mu, mask.shape)
    w = jnp.where(mask, mu, 0.0)
    pi = jnp.asarray(k)[:, None] * w / jnp.sum(w, axis=-1, keepdims=True)
    return project_capped_simplex(pi, k, mask, racks=racks)


def random_placement_mask(key: Array, r: int, m: int, n: Array) -> Array:
    """'Random CP': each file picks n_i nodes uniformly at random."""
    def one(key, n_i):
        perm = jax.random.permutation(key, m)
        return jnp.zeros((m,), bool).at[perm].set(jnp.arange(m) < n_i)

    keys = jax.random.split(key, r)
    return jax.vmap(one)(keys, jnp.asarray(n))


def max_ec_solution(prob: JLCMProblem, **kw) -> JLCMSolution:
    """'Maximum EC': n_i = m (all nodes), optimize scheduling only.

    Implemented as JLCM with theta = 0 and full support, so the optimizer
    never prunes placements (cost is whatever full placement costs)."""
    full = prob._replace(theta=0.0, mask=jnp.ones((prob.r, prob.m), bool))
    sol = solve(full, **kw)
    full_cost = jnp.broadcast_to(prob.cost, (prob.r, prob.m))
    if prob.cost_weight is not None:
        full_cost = prob.cost_weight[:, None] * full_cost
    cost = jnp.sum(full_cost)
    return sol._replace(
        cost=cost,
        objective=sol.latency + prob.theta * cost,
        n=jnp.full((prob.r,), prob.m),
        placement=jnp.ones((prob.r, prob.m), bool),
    )
