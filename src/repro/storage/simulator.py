"""Exact discrete-event simulation of probabilistic scheduling.

Under probabilistic scheduling each node runs an independent FCFS queue, so
the whole system's dynamics reduce to one `lax.scan` over the merged
arrival stream with per-node last-departure state:

    start_j  = max(t_req, dep_j)            (FCFS, work-conserving)
    finish_j = start_j + service_j
    dep_j   <- finish_j  where node j was selected for this batch
    file latency = max_{j in A} finish_j - t_req

This is an *exact* simulation of Def. 2 (not an approximation), fully
vectorized over the node axis; 10^5+ requests simulate in milliseconds.
Used to validate Lemma 2/3's analytic bound (Figs. 10-12) and to measure
the true optimality gap of JLCM solutions.

Non-stationary extension (scenario engine): :func:`simulate_segment` runs
one *segment* of requests against a per-segment node-availability mask,
arrival-rate scale, and service-moment perturbation, threading the FCFS
queue state (:class:`SimCarry`) across segment boundaries so a multi-
segment trace is one continuous system history. When a Madow-selected
node is down the request performs a *degraded read*: the dead picks are
replaced by uniformly-random available spares so the k-of-n MDS read size
is preserved (any k chunks decode — `storage/rs.py`). Each segment also
reports per-node service-time observations (:class:`NodeObservations`)
that a control plane can feed to a moment estimator — the measured-state
half of the closed loop in `serving/router.py`. :func:`simulate_segments`
stacks per-segment parameters and runs the whole schedule as one nested
``lax.scan`` (segments outer, requests inner) in a single compiled call —
the open-loop fast path used for static/oblivious policies.

Geo extension (client fabric, ``storage/cluster.py::GeoFabric``):
:func:`generate_geo_workload` merges per-(client-site, file) Poisson
streams, :func:`simulate_geo_segment` / :func:`simulate_geo_segments`
sample each request's service from its origin site's (C, m) network
profile while all sites contend for the same per-node FCFS queues, and
observations come back per (site, node) pair so the control plane can
estimate the full geo service family. :func:`simulate_fleet` vmaps (and,
when multiple devices are present, ``shard_map``s) independent seeds into
one program — the fleet-scale path measured by
`benchmarks/fleet_scale.py`.

Hot/warm cache tier (`storage/cache.py`): every segment entry point takes
an optional per-file TTL vector; when set, the merged arrival stream first
runs through a device-resident TTL-with-reset cache (the exact surrogate
of the Che LRU approximation) and only the *misses* proceed to dispatch
and the FCFS queues — hits return at the hot tier's service latency.
Cache warmth threads across segments in :class:`SimCarry` alongside the
queue state; a TTL of all zeros is bitwise identical to no cache.

Multi-tenant reporting: :func:`per_class_latency_stats` groups simulated
latencies by tenant class (per-class mean and empirical p95/p99), the
measurement counterpart of the pluggable objective layer
(``core/objectives.py``) — analytic per-class mean/tail bounds are
validated against these empirical statistics.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array

from repro import diag
from repro.core.scheduling import madow_sample
from repro.kernels.fcfs_queue import fcfs_scan
from .cache import ttl_cache_scan
from .cluster import Cluster
from .streaming import (
    DEFAULT_SKETCH,
    SketchSpec,
    StreamingStats,
    bucketize,
    stream_from_values,
    stream_init,
    stream_mean,
    stream_merge,
    stream_quantile,
    stream_reduce,
    windowed_quantile_mean,
)


class ClassLatencyStats(NamedTuple):
    """Per-tenant-class empirical latency statistics (host-side reporting).

    Shapes are all (C,). A class that received zero (post-warmup) requests
    gets NaN mean/quantiles and count 0 — same contract as
    :meth:`SimResult.per_file_mean`.
    """

    count: np.ndarray  # requests observed per class
    mean: np.ndarray  # empirical mean latency
    p95: np.ndarray  # empirical 95th percentile
    p99: np.ndarray  # empirical 99th percentile


def per_class_latency_stats(
    latency: np.ndarray,
    file_id: np.ndarray,
    class_of_file: np.ndarray,
    n_classes: int,
) -> ClassLatencyStats:
    """Group simulated request latencies by tenant class.

    ``class_of_file`` maps file id -> class id (the ``ObjectiveSpec.
    class_id`` vector of the plan under test). This is the measurement side
    of the pluggable objective layer: the analytic per-class mean and tail
    bounds (``core/objectives.py``) are validated against exactly these
    empirical means and p95/p99 quantiles. Host-side numpy — reporting, not
    a jit path; arrays may carry leading segment axes (flattened here).
    """
    latency = np.asarray(latency).ravel()
    cls = np.asarray(class_of_file)[np.asarray(file_id).ravel()]
    count = np.zeros(n_classes, np.int64)
    mean = np.full(n_classes, np.nan)
    p95 = np.full(n_classes, np.nan)
    p99 = np.full(n_classes, np.nan)
    for c in range(n_classes):
        lat_c = latency[cls == c]
        count[c] = lat_c.size
        if lat_c.size:
            mean[c] = lat_c.mean()
            p95[c], p99[c] = np.percentile(lat_c, [95, 99])
    return ClassLatencyStats(count=count, mean=mean, p95=p95, p99=p99)


class SimResult(NamedTuple):
    latency: Array  # (N,) per-request file latency
    file_id: Array  # (N,) which file each request was for
    arrival: Array  # (N,) arrival times
    node_busy: Array  # (m,) total busy seconds per node (utilisation check)
    # optional streaming view of the same run (moments + quantile sketch,
    # `storage/streaming.py`) — populated when `simulate` is given a
    # SketchSpec; the validation bridge between sketch percentiles and
    # the exact Fig. 10-12 CDFs
    stream: StreamingStats | None = None

    def mean_latency(self) -> Array:
        return jnp.mean(self.latency)

    def per_class_stats(
        self, class_of_file: np.ndarray, n_classes: int
    ) -> ClassLatencyStats:
        """Per-class empirical mean/p95/p99; see :func:`per_class_latency_stats`."""
        return per_class_latency_stats(
            self.latency, self.file_id, class_of_file, n_classes
        )

    def per_file_mean(self, r: int) -> Array:
        """Mean simulated latency per file, shape (r,).

        Contract: entry ``i`` is the empirical mean over the requests that
        file ``i`` actually received; a file with **zero** requests in the
        (post-warmup) trace gets **NaN**, never a silently-wrong 0-count
        mean. Callers that aggregate across files must mask with
        ``jnp.isnan`` (or ``np.nanmean``) rather than assume finiteness.
        """
        one_hot = jax.nn.one_hot(self.file_id, r, dtype=jnp.float32)
        tot = one_hot.T @ self.latency
        cnt = one_hot.sum(0)
        return jnp.where(cnt > 0, tot / jnp.maximum(cnt, 1.0), jnp.nan)


def generate_workload(
    key: Array, lam: Array, n_requests: int
) -> tuple[Array, Array]:
    """Merged Poisson stream: arrival times (N,) + file ids (N,).

    Superposition of per-file Poisson(lambda_i) == Poisson(sum lambda) with
    iid categorical file marks (probability lambda_i / sum).
    """
    lam = jnp.asarray(lam)
    k_gap, k_mark = jax.random.split(key)
    gaps = jax.random.exponential(k_gap, (n_requests,)) / jnp.sum(lam)
    t = jnp.cumsum(gaps)
    ids = jax.random.categorical(
        k_mark, jnp.log(lam / jnp.sum(lam))[None, :].repeat(n_requests, 0)
    )
    return t, ids


def simulate(
    key: Array,
    pi: Array,
    lam: Array,
    cluster: Cluster,
    chunk_mb: float | Array,
    n_requests: int = 20000,
    *,
    drop_warmup: float = 0.1,
    per_file_chunk_mb: Array | None = None,
    sketch: SketchSpec | None = None,
) -> SimResult:
    """Simulate probabilistic scheduling for dispatch matrix ``pi`` (r, m).

    ``per_file_chunk_mb`` (r,) enables heterogeneous per-file chunk sizes
    (the §V.B catalog where quarters use k = 6,7,6,4 on equal file sizes).
    ``sketch`` additionally folds the (post-warmup) latencies into
    streaming moments + a quantile sketch (``SimResult.stream``) — the
    surface Fig. 10-12 CDF validation uses to check sketch percentiles
    against the exact empirical distribution.
    """
    pi = jnp.asarray(pi)
    r, m = pi.shape
    assert m == cluster.m
    k_wl, k_sel, k_srv = jax.random.split(key, 3)
    arrival, file_id = generate_workload(k_wl, lam, n_requests)
    sel_keys = jax.random.split(k_sel, n_requests)
    if per_file_chunk_mb is not None:
        req_chunk = jnp.asarray(per_file_chunk_mb)[file_id]
        service = cluster.sample_service_per_request(k_srv, req_chunk, n_requests)
    else:
        service = cluster.sample_service(k_srv, chunk_mb, (n_requests,))  # (N, m)

    masks = jax.vmap(lambda skey, fid: madow_sample(skey, pi[fid]))(
        sel_keys, file_id
    )
    latency, _, busy = fcfs_scan(arrival, masks, service)
    warm = int(n_requests * drop_warmup)
    return SimResult(
        latency=latency[warm:],
        file_id=file_id[warm:],
        arrival=arrival[warm:],
        node_busy=busy,
        stream=None if sketch is None else stream_from_values(
            latency[warm:], sketch
        ),
    )


def simulate_latency_cdf(result: SimResult, qs: np.ndarray | None = None):
    """Empirical CDF knots (for Fig. 10-style outputs)."""
    qs = np.linspace(0.01, 0.99, 99) if qs is None else qs
    lat = np.asarray(result.latency)
    return qs, np.quantile(lat, qs)


# ---------------------------------------------------------------------------
# Segmented (non-stationary) simulation: failures, flash crowds, drift.
# ---------------------------------------------------------------------------


class NodeObservations(NamedTuple):
    """Per-node service-time measurements from one segment.

    ``count`` chunks served per node plus raw power sums of the observed
    chunk service times — exactly what a node-side agent would report to a
    control plane, and enough to form unbiased estimates of the first three
    raw moments (E[X], E[X^2], E[X^3]) that Lemma 3 needs. Nodes that
    served nothing (down, or zero dispatch mass) have ``count == 0``.
    """

    count: Array  # (m,) chunks served
    s1: Array  # (m,) sum of service times
    s2: Array  # (m,) sum of squares
    s3: Array  # (m,) sum of cubes


class SimCarry(NamedTuple):
    """FCFS queue state threaded across segment boundaries.

    ``cache`` is the hot-tier cache state — per-file absolute expiry
    times (`storage/cache.py`) — or None when no cache tier is simulated.
    It rides in the carry for the same reason ``dep`` does: cache warmth,
    like queue depth, is continuous history that must survive segment
    boundaries (a cache-warmup scenario is *about* that transient).
    """

    dep: Array  # (m,) last scheduled departure per node
    t0: Array  # () absolute clock at the segment boundary
    cache: Array | None = None  # (r,) per-file expiry times, or None


class SegmentResult(NamedTuple):
    latency: Array  # (N,) per-request file latency
    file_id: Array  # (N,)
    arrival: Array  # (N,) absolute arrival times
    node_busy: Array  # (m,) busy seconds added this segment
    degraded: Array  # (N,) bool: >= 1 selected node was down (read fell back)
    obs: NodeObservations
    t_end: Array  # () absolute time of the last arrival
    hit: Array | None = None  # (N,) bool cache hits, or None (no cache tier)

    def mean_latency(self) -> Array:
        return jnp.mean(self.latency)


def init_carry(m: int, *, cache_files: int | None = None) -> SimCarry:
    """Fresh carry: idle queues and — when ``cache_files`` is given — a
    cold hot-tier cache over that many files (all expiries at -inf)."""
    cache = None if cache_files is None else jnp.full((cache_files,), -jnp.inf)
    return SimCarry(dep=jnp.zeros((m,)), t0=jnp.asarray(0.0), cache=cache)


def dispatch_masks(
    key: Array, pi: Array, file_id: Array, avail: Array
) -> tuple[Array, Array]:
    """Per-request service sets under availability mask ``avail`` (m,).

    Each request Madow-samples its k_i-subset from ``pi[file_id]`` (exact
    Theorem-1 marginals). Selected-but-down nodes are then replaced by
    uniformly-random *available* spares, preserving the read size k_i —
    a degraded read: any k chunks of an (n, k) MDS code decode.

    Returns ``(masks, degraded)``: (N, m) bool service sets and (N,) bool
    flags marking requests whose original selection hit a down node.

    Thin availability (fewer than ``k_i`` nodes up at all): the spare pool
    cannot restore the read size, so the service set is *exactly* the
    available node set — ``masks[n] == avail`` — and the request is
    flagged degraded. This is a partially-degraded read: strictly fewer
    than ``k_i`` chunks cannot decode an MDS stripe, so the data plane
    must fall back to a partial/object-repair path. The behavior mirrors
    ``storage/repair.py``'s convention (repair dispatch widens thin
    placements to ``avail``) so client and reconstruction reads degrade
    identically; it is asserted by
    ``tests/test_scenarios.py::TestSegmentedSimulator::
    test_thin_availability_widens_to_avail``, and scenario specs keep out
    of the regime entirely (``ScenarioSpec.validate`` requires every
    segment to leave >= max k_i nodes up).
    """
    pi = jnp.asarray(pi)
    avail = jnp.asarray(avail, bool)
    n = file_id.shape[0]
    k_per_file = jnp.round(jnp.sum(pi, axis=-1))
    k_sel, k_prio = jax.random.split(key)
    sel_keys = jax.random.split(k_sel, n)
    prio = jax.random.uniform(k_prio, (n, pi.shape[-1]))

    def one(skey, fid, pr):
        sel = madow_sample(skey, pi[fid])
        alive = jnp.logical_and(sel, avail)
        need = k_per_file[fid].astype(jnp.int32) - jnp.sum(alive)
        cand = jnp.logical_and(avail, jnp.logical_not(sel))
        score = jnp.where(cand, pr, -1.0)
        rank = jnp.argsort(jnp.argsort(-score))
        # when need exceeds the candidate pool (thin availability) every
        # available non-selected node is added: the union below is then
        # exactly `avail` — never a silent wrap back onto down nodes
        add = jnp.logical_and(cand, rank < need)
        return jnp.logical_or(alive, add), jnp.any(sel & ~avail)

    return jax.vmap(one)(sel_keys, file_id, prio)


def _run_segment(
    carry: SimCarry,
    key: Array,
    pi: Array,
    lam: Array,
    overheads: Array,
    rates: Array,
    avail: Array,
    n_requests: int,
    ttl: Array | None = None,
    hit_latency: Array | float = 0.0,
) -> tuple[SimCarry, SegmentResult]:
    """One segment of the non-stationary simulation (jit-/scan-friendly).

    ``lam`` is the (already rate-scaled) per-file arrival vector for this
    segment; ``overheads``/``rates`` are the (already drift-scaled) shifted-
    exponential service parameters; ``avail`` the (m,) availability mask.
    Queue state flows in and out through ``carry`` so consecutive segments
    form one continuous FCFS history (no warmup transient at boundaries).

    ``ttl`` switches on the hot-tier cache (`storage/cache.py`): the merged
    arrival stream first runs through the TTL-with-reset cache; hits return
    at ``hit_latency`` and never reach the warm-tier queues (no dispatch,
    no busy time, no service observations — the control plane's estimators
    see miss traffic only). The cache pre-scan consumes no randomness and a
    ``ttl`` of all zeros hits nothing, so that run is bitwise identical to
    ``ttl=None``; per-file zeros express demoted files, repair pseudo-file
    rows (reconstruction reads of *lost* chunks cannot hit a cache), and
    hot-tier outage windows.
    """
    m = overheads.shape[-1]
    k_wl, k_sel, k_srv = jax.random.split(key, 3)
    rel, file_id = generate_workload(k_wl, lam, n_requests)
    arrival = carry.t0 + rel
    e = jax.random.exponential(k_srv, (n_requests, m))
    service = overheads + e / rates
    masks, degraded = dispatch_masks(k_sel, pi, file_id, avail)

    if ttl is None:
        hit = None
        serve = masks
        new_cache = carry.cache
    else:
        expiry = (
            jnp.full(jnp.shape(ttl), -jnp.inf)
            if carry.cache is None
            else carry.cache
        )
        new_cache, hit = ttl_cache_scan(expiry, arrival, file_id, ttl)
        serve = jnp.logical_and(masks, jnp.logical_not(hit)[:, None])
        degraded = jnp.logical_and(degraded, jnp.logical_not(hit))

    latency, dep, busy = fcfs_scan(arrival, serve, service, carry.dep)
    if hit is not None:
        latency = jnp.where(hit, jnp.asarray(hit_latency), latency)
    served = jnp.where(serve, service, 0.0)
    obs = NodeObservations(
        count=jnp.sum(serve, axis=0),
        s1=jnp.sum(served, axis=0),
        s2=jnp.sum(served**2, axis=0),
        s3=jnp.sum(served**3, axis=0),
    )
    new_carry = SimCarry(dep=dep, t0=arrival[-1], cache=new_cache)
    return new_carry, SegmentResult(
        latency=latency,
        file_id=file_id,
        arrival=arrival,
        node_busy=busy,
        degraded=degraded,
        obs=obs,
        t_end=arrival[-1],
        hit=hit,
    )


# Public raw-parameter entry point: one compiled segment from explicit
# shifted-exponential service parameters (no Cluster object). This is the
# surface control-plane code uses to roll out candidate plans from
# *estimated* parameters (serving.router.AdaptiveReplanner); positional
# signature: (carry, key, pi, lam, overheads, rates, avail, n_requests).
run_segment_raw = jax.jit(_run_segment, static_argnames=("n_requests",))


def simulate_segment(
    key: Array,
    pi: Array,
    lam: Array,
    cluster: Cluster,
    chunk_mb: float,
    n_requests: int,
    *,
    avail: Array | None = None,
    rate_scale: float | Array = 1.0,
    overhead_scale: float | Array = 1.0,
    bandwidth_scale: float | Array = 1.0,
    carry: SimCarry | None = None,
    cache_ttl: Array | None = None,
    cache_hit_latency: float = 0.0,
) -> tuple[SegmentResult, SimCarry]:
    """Simulate one segment against a possibly-perturbed cluster state.

    The host-facing entry point of the scenario engine's closed loop: the
    caller owns ``pi`` (and may re-plan it between segments) while queue
    state persists in ``carry``. ``rate_scale`` multiplies arrival rates —
    a scalar scales every file (flash crowds / diurnal ramps), an (r,)
    vector scales per file (e.g. switching repair-traffic rows on and off
    per segment, `storage/repair.py`). ``overhead_scale`` /
    ``bandwidth_scale`` (scalar or per-node) drift the service moments the
    same way :meth:`Cluster.perturbed` does. ``cache_ttl`` (r,) switches
    on the hot-tier cache in front of the queues (see :func:`_run_segment`
    — zeros mark uncached files, and cache warmth persists in ``carry``).
    """
    m = cluster.m
    avail = jnp.ones((m,), bool) if avail is None else jnp.asarray(avail, bool)
    if carry is None:
        r_cache = None if cache_ttl is None else int(np.shape(cache_ttl)[0])
        carry = init_carry(m, cache_files=r_cache)
    elif cache_ttl is not None and carry.cache is None:
        carry = carry._replace(
            cache=jnp.full((int(np.shape(cache_ttl)[0]),), -jnp.inf)
        )
    overheads = cluster.overheads() * jnp.asarray(overhead_scale)
    rates = cluster.bandwidths() * jnp.asarray(bandwidth_scale) / chunk_mb
    lam_s = jnp.asarray(lam) * rate_scale
    new_carry, res = run_segment_raw(
        carry,
        key,
        jnp.asarray(pi),
        lam_s,
        overheads,
        rates,
        avail,
        n_requests,
        None if cache_ttl is None else jnp.asarray(cache_ttl, jnp.float32),
        jnp.asarray(cache_hit_latency, jnp.float32),
    )
    return res, new_carry


@functools.partial(jax.jit, static_argnames=("n_requests",))
def _simulate_segments_device(
    key,
    pi_seq,
    lam,
    rate_scale,
    overheads_seq,
    rates_seq,
    avail_seq,
    n_requests,
    ttl_seq=None,
    hit_latency=0.0,
):
    n_seg = rate_scale.shape[0]
    keys = jax.random.split(key, n_seg)
    cached = ttl_seq is not None
    # scan xs must be a fixed pytree: feed zero TTLs when uncached and a
    # None carry.cache keeps that branch out of the program entirely
    if not cached:
        ttl_seq = jnp.zeros((n_seg, 1))

    def seg(carry, inp):
        skey, pi, scale, ovh, rt, av, ttl = inp
        return _run_segment(
            carry,
            skey,
            pi,
            lam * scale,
            ovh,
            rt,
            av,
            n_requests,
            ttl if cached else None,
            hit_latency,
        )

    carry0 = init_carry(
        overheads_seq.shape[-1],
        cache_files=int(ttl_seq.shape[-1]) if cached else None,
    )
    _, results = jax.lax.scan(
        seg,
        carry0,
        (keys, pi_seq, rate_scale, overheads_seq, rates_seq, avail_seq, ttl_seq),
    )
    return results


def simulate_segments(
    key: Array,
    pi_seq: Array,
    lam: Array,
    cluster: Cluster,
    chunk_mb: float,
    n_requests: int,
    *,
    avail_seq: Array | None = None,
    rate_scale_seq: Array | None = None,
    overhead_scale_seq: Array | None = None,
    bandwidth_scale_seq: Array | None = None,
    cache_ttl_seq: Array | None = None,
    cache_hit_latency: float = 0.0,
) -> SegmentResult:
    """Run a whole segment schedule as ONE nested ``lax.scan`` device call.

    ``pi_seq`` is (S, r, m) — or (r, m), broadcast to every segment — and
    the optional per-segment sequences are ``avail_seq`` (S, m) bool,
    ``rate_scale_seq`` (S,) — or (S, r) for per-file scaling, the hook
    `storage/repair.py` uses to activate reconstruction-read rows only in
    outage segments — and ``overhead_scale_seq`` / ``bandwidth_scale_seq``
    (S,) or (S, m). The outer scan threads the FCFS carry across segments;
    the inner scan replays each segment's merged arrival stream. Every
    field of the returned :class:`SegmentResult` gains a leading (S,)
    axis.

    This is the open-loop fast path (static / oblivious policies, or any
    precomputed plan schedule). The closed-loop engine instead alternates
    :func:`simulate_segment` with host-side re-planning.

    ``cache_ttl_seq`` (S, r) — or (r,), broadcast — runs the hot-tier
    cache in front of the queues with per-segment TTLs; an all-zero row
    expresses a hot-tier outage window (nothing hits, and because expiry
    times keep being refreshed to the *past*, the cache drains naturally —
    re-warming happens on-stream when the outage lifts).
    """
    m = cluster.m
    pi_seq = jnp.asarray(pi_seq)
    n_seg = None
    for cand in (
        pi_seq.shape[0] if pi_seq.ndim == 3 else None,
        None if rate_scale_seq is None else np.shape(rate_scale_seq)[0],
        None if avail_seq is None else np.shape(avail_seq)[0],
        None if overhead_scale_seq is None else np.shape(overhead_scale_seq)[0],
        None if bandwidth_scale_seq is None else np.shape(bandwidth_scale_seq)[0],
    ):
        if cand is None:
            continue
        if n_seg is None:
            n_seg = int(cand)
        elif n_seg != int(cand):
            raise ValueError(
                f"inconsistent segment counts: {n_seg} vs {int(cand)}"
            )
    if n_seg is None:
        raise ValueError(
            "cannot infer the segment count: pass a (S, r, m) pi_seq or any "
            "per-segment sequence"
        )
    if rate_scale_seq is None:
        rate_scale_seq = jnp.ones((n_seg,))
    rate_scale_seq = jnp.asarray(rate_scale_seq, jnp.float32)
    if pi_seq.ndim == 2:
        pi_seq = jnp.broadcast_to(pi_seq, (n_seg,) + pi_seq.shape)
    avail_seq = (
        jnp.ones((n_seg, m), bool)
        if avail_seq is None
        else jnp.asarray(avail_seq, bool)
    )

    def scales(seq):
        if seq is None:
            return jnp.ones((n_seg, m))
        seq = jnp.asarray(seq, jnp.float32)
        return jnp.broadcast_to(
            seq[:, None] if seq.ndim == 1 else seq, (n_seg, m)
        )

    overheads_seq = cluster.overheads() * scales(overhead_scale_seq)
    rates_seq = cluster.bandwidths() * scales(bandwidth_scale_seq) / chunk_mb
    if cache_ttl_seq is not None:
        cache_ttl_seq = jnp.asarray(cache_ttl_seq, jnp.float32)
        if cache_ttl_seq.ndim == 1:
            cache_ttl_seq = jnp.broadcast_to(
                cache_ttl_seq, (n_seg,) + cache_ttl_seq.shape
            )
    return _simulate_segments_device(
        key,
        pi_seq,
        jnp.asarray(lam),
        rate_scale_seq,
        overheads_seq,
        rates_seq,
        avail_seq,
        n_requests,
        cache_ttl_seq,
        jnp.asarray(cache_hit_latency, jnp.float32),
    )


# ---------------------------------------------------------------------------
# Geo-aware simulation: per-(client-site, node) service + fleet scale.
# ---------------------------------------------------------------------------


def generate_geo_workload(
    key: Array, lam_cs: Array, n_requests: int
) -> tuple[Array, Array, Array]:
    """Merged Poisson stream over (client site, file) pairs.

    ``lam_cs`` is (C, r): per-site per-file arrival rates. Superposition
    of the C*r independent Poisson streams == Poisson(sum) with iid
    categorical (site, file) marks. Returns ``(t, file_id, site_id)``,
    each (N,).

    The marks are drawn by inverse CDF: one uniform per request, whose
    bin in the C*r-bin CDF is the number of CDF entries ``<= u``
    (:func:`~.streaming.bucketize`: counted on the TPU, binary-searched
    elsewhere, the same index either way). That is Gumbel-max
    ``jax.random.categorical``'s distribution at a fraction of its
    elementwise work, which matters on the fleet path where workload
    generation would otherwise dominate the whole simulation
    (`benchmarks/fleet_scale.py`).
    """
    lam_cs = jnp.asarray(lam_cs)
    c, r = lam_cs.shape
    flat = lam_cs.reshape(-1)
    k_gap, k_mark = jax.random.split(key)
    gaps = jax.random.exponential(k_gap, (n_requests,)) / jnp.sum(flat)
    t = jnp.cumsum(gaps)
    cdf = jnp.cumsum(flat / jnp.sum(flat))
    u = jax.random.uniform(k_mark, (n_requests,))
    marks = jnp.clip(bucketize(cdf, u), 0, flat.shape[0] - 1)
    return t, marks % r, marks // r


class GeoSegmentResult(NamedTuple):
    """One geo segment: like :class:`SegmentResult` plus the client axis.

    ``site_id`` records each request's origin site; ``obs`` carries
    per-(site, node) observation sums — arrays shaped (C, m) instead of
    (m,), which the EWMA moment estimator consumes unchanged (it is
    elementwise) to track the full per-pair service family.
    """

    latency: Array  # (N,)
    file_id: Array  # (N,)
    site_id: Array  # (N,) request origin client site
    arrival: Array  # (N,) absolute arrival times
    node_busy: Array  # (m,) busy seconds added this segment
    degraded: Array  # (N,) bool
    obs: NodeObservations  # per-(site, node): every field (C, m)
    t_end: Array  # ()

    def mean_latency(self) -> Array:
        return jnp.mean(self.latency)


def _run_geo_segment(
    carry: SimCarry,
    key: Array,
    pi: Array,
    lam_cs: Array,
    overheads_cs: Array,
    rates_cs: Array,
    avail: Array,
    n_requests: int,
) -> tuple[SimCarry, GeoSegmentResult]:
    """One geo segment: site-dependent service, shared per-node FCFS queues.

    ``overheads_cs`` / ``rates_cs`` are (C, m) shifted-exponential
    parameters (client site x node); each request samples service from its
    *origin site's* row, but all sites contend for the same m queues —
    locality buys a shorter service time, not a private server.
    """
    m = overheads_cs.shape[-1]
    c = overheads_cs.shape[0]
    k_wl, k_sel, k_srv = jax.random.split(key, 3)
    rel, file_id, site_id = generate_geo_workload(k_wl, lam_cs, n_requests)
    arrival = carry.t0 + rel
    e = jax.random.exponential(k_srv, (n_requests, m))
    service = overheads_cs[site_id] + e / rates_cs[site_id]
    masks, degraded = dispatch_masks(k_sel, pi, file_id, avail)

    latency, dep, busy = fcfs_scan(arrival, masks, service, carry.dep)
    served = jnp.where(masks, service, 0.0)
    site_oh = jax.nn.one_hot(site_id, c, dtype=jnp.float32)  # (N, C)
    mask_f = masks.astype(jnp.float32)
    obs = NodeObservations(
        count=jnp.einsum("nc,nm->cm", site_oh, mask_f).astype(jnp.int32),
        s1=jnp.einsum("nc,nm->cm", site_oh, served),
        s2=jnp.einsum("nc,nm->cm", site_oh, served**2),
        s3=jnp.einsum("nc,nm->cm", site_oh, served**3),
    )
    new_carry = SimCarry(dep=dep, t0=arrival[-1])
    return new_carry, GeoSegmentResult(
        latency=latency,
        file_id=file_id,
        site_id=site_id,
        arrival=arrival,
        node_busy=busy,
        degraded=degraded,
        obs=obs,
        t_end=arrival[-1],
    )


# Raw-parameter jitted entry point (the geo twin of `run_segment_raw`):
# rollout surface for the geo-aware replanner. Positional signature:
# (carry, key, pi, lam_cs, overheads_cs, rates_cs, avail, n_requests).
run_geo_segment_raw = jax.jit(_run_geo_segment, static_argnames=("n_requests",))


# ---------------------------------------------------------------------------
# Candidate-batched rollouts: every candidate plan (x every rollout seed)
# simulated in ONE program — the replanner's arbitration surface.
# ---------------------------------------------------------------------------


def _run_segment_candidates(
    carry: SimCarry,
    keys: Array,
    pi_stack: Array,
    lam: Array,
    overheads: Array,
    rates: Array,
    avail: Array,
    n_requests: int,
    ttl: Array | None = None,
    hit_latency: Array | float = 0.0,
) -> SegmentResult:
    """Roll out a (B, r, m) stack of candidate plans from ONE queue state.

    The candidate axis vmaps over :func:`_run_segment` with the carry,
    segment parameters, and PRNG ``keys`` broadcast — *common random
    numbers*: every candidate sees the identical arrival stream, service
    draws, and Madow/spare randomness, so score differences are purely
    plan differences (and at one seed the per-candidate latency stream is
    bitwise the stream ``run_segment_raw`` produces for that plan alone).
    ``keys`` is a (K,) key array — a seed axis nested inside the candidate
    axis for variance-reduced arbitration; callers wanting the bitwise
    K=1 contract pass ``key[None]`` (the unsplit key), mirroring the
    fleet path's ``n_chunks == 1`` convention. Every field of the
    returned :class:`SegmentResult` carries leading (B, K) axes; the
    advanced carry is not returned — rollouts are hypothetical, the real
    segment still advances the caller's carry.
    """

    def one(key: Array, pi: Array) -> SegmentResult:
        return _run_segment(
            carry, key, pi, lam, overheads, rates, avail, n_requests,
            ttl, hit_latency,
        )[1]

    return jax.vmap(lambda pi: jax.vmap(lambda k: one(k, pi))(keys))(
        jnp.asarray(pi_stack)
    )


def _run_geo_segment_candidates(
    carry: SimCarry,
    keys: Array,
    pi_stack: Array,
    lam_cs: Array,
    overheads_cs: Array,
    rates_cs: Array,
    avail: Array,
    n_requests: int,
) -> GeoSegmentResult:
    """Geo twin of :func:`_run_segment_candidates`: (B, K) batched
    :func:`_run_geo_segment` rollouts under common random numbers."""

    def one(key: Array, pi: Array) -> GeoSegmentResult:
        return _run_geo_segment(
            carry, key, pi, lam_cs, overheads_cs, rates_cs, avail, n_requests
        )[1]

    return jax.vmap(lambda pi: jax.vmap(lambda k: one(k, pi))(keys))(
        jnp.asarray(pi_stack)
    )


# Jitted candidate-batched entry points. Positional signatures mirror the
# single-plan `run_segment_raw` / `run_geo_segment_raw` with (keys (K,),
# pi_stack (B, r, m)) replacing (key, pi); results gain leading (B, K)
# axes. `serving.router.batched_rollout_scores` fuses these with device
# scoring + argmin into the replanner's one-host-sync arbitration.
run_segment_batch = jax.jit(
    _run_segment_candidates, static_argnames=("n_requests",)
)
run_geo_segment_batch = jax.jit(
    _run_geo_segment_candidates, static_argnames=("n_requests",)
)


def simulate_geo_segment(
    key: Array,
    pi: Array,
    lam_cs: Array,
    fabric,
    chunk_mb: float,
    n_requests: int,
    *,
    avail: Array | None = None,
    rate_scale: float | Array = 1.0,
    overhead_scale: float | Array = 1.0,
    bandwidth_scale: float | Array = 1.0,
    carry: SimCarry | None = None,
) -> tuple[GeoSegmentResult, SimCarry]:
    """Host-facing geo segment against a :class:`~.cluster.GeoFabric`.

    ``lam_cs`` is the (C, r) per-site arrival matrix (a migrating client
    population is just a per-segment reweighting of its rows);
    ``rate_scale`` multiplies it (scalar, (C, 1)-broadcastable, or full
    (C, r)). ``overhead_scale`` / ``bandwidth_scale`` are broadcastable
    against the fabric's (C, m) network profile — per-*pair* drift, e.g. a
    DC's egress degrading for cross-site clients only, which no per-node
    scale can express.
    """
    m = fabric.m
    avail = jnp.ones((m,), bool) if avail is None else jnp.asarray(avail, bool)
    carry = init_carry(m) if carry is None else carry
    d, rates = fabric.service_params(chunk_mb)
    overheads = d * jnp.asarray(overhead_scale)
    rates = rates * jnp.asarray(bandwidth_scale)
    lam_s = jnp.asarray(lam_cs) * rate_scale
    new_carry, res = run_geo_segment_raw(
        carry, key, jnp.asarray(pi), lam_s, overheads, rates, avail, n_requests
    )
    return res, new_carry


@functools.partial(jax.jit, static_argnames=("n_requests",))
def _simulate_geo_segments_device(
    key, pi_seq, lam_cs_seq, overheads_seq, rates_seq, avail_seq, n_requests
):
    n_seg = lam_cs_seq.shape[0]
    keys = jax.random.split(key, n_seg)

    def seg(carry, inp):
        skey, pi, lam_cs, ovh, rt, av = inp
        return _run_geo_segment(carry, skey, pi, lam_cs, ovh, rt, av, n_requests)

    carry0 = init_carry(overheads_seq.shape[-1])
    _, results = jax.lax.scan(
        seg, carry0, (keys, pi_seq, lam_cs_seq, overheads_seq, rates_seq, avail_seq)
    )
    return results


def simulate_geo_segments(
    key: Array,
    pi_seq: Array,
    lam_cs_seq: Array,
    fabric,
    chunk_mb: float,
    n_requests: int,
    *,
    avail_seq: Array | None = None,
    overhead_scale_seq: Array | None = None,
    bandwidth_scale_seq: Array | None = None,
) -> GeoSegmentResult:
    """Whole geo segment schedule as ONE nested ``lax.scan`` device call.

    ``lam_cs_seq`` is (S, C, r) — the per-segment client-population mix is
    already folded into the rates (follow-the-sun is a row reweighting).
    ``pi_seq`` is (S, r, m) or (r, m) broadcast; the optional scale
    sequences are (S, C, m)-broadcastable per-pair drift (egress
    degradation). Open-loop fast path: static / oblivious geo policies run
    their full schedule in a single compiled call, exactly like
    :func:`simulate_segments` for the single-site model.
    """
    lam_cs_seq = jnp.asarray(lam_cs_seq, jnp.float32)
    if lam_cs_seq.ndim != 3:
        raise ValueError(
            f"lam_cs_seq must be (S, C, r), got shape {lam_cs_seq.shape}"
        )
    n_seg = lam_cs_seq.shape[0]
    m = fabric.m
    c = fabric.n_sites
    pi_seq = jnp.asarray(pi_seq)
    if pi_seq.ndim == 2:
        pi_seq = jnp.broadcast_to(pi_seq, (n_seg,) + pi_seq.shape)
    avail_seq = (
        jnp.ones((n_seg, m), bool)
        if avail_seq is None
        else jnp.asarray(avail_seq, bool)
    )

    def scales(seq):
        if seq is None:
            return jnp.ones((n_seg, c, m))
        return jnp.broadcast_to(jnp.asarray(seq, jnp.float32), (n_seg, c, m))

    d, rates = fabric.service_params(chunk_mb)
    overheads_seq = d * scales(overhead_scale_seq)
    rates_seq = rates * scales(bandwidth_scale_seq)
    return _simulate_geo_segments_device(
        key, pi_seq, lam_cs_seq, overheads_seq, rates_seq, avail_seq, n_requests
    )


# ---------------------------------------------------------------------------
# Fleet-scale simulation: many independent systems in one program.
# ---------------------------------------------------------------------------


class FleetResult(NamedTuple):
    """A fleet of independent geo simulations, leading axis = seed.

    Every field carries a leading (S,) seed axis; within a seed the run is
    an independent replica of the full system (own workload randomness,
    own FCFS queues) — the estimator-variance / what-if-ensemble shape,
    and the throughput unit for `benchmarks/fleet_scale.py`.

    Two mutually exclusive reporting modes:

    * **materialized** (``stream=None``): per-request ``latency`` /
      ``file_id`` / ``site_id`` (S, N) arrays — memory scales with the
      simulated horizon.
    * **streaming** (``latency=None``): constant-size per-seed
      :class:`~.streaming.StreamingStats` in ``stream`` plus per-window
      (chunk) stats in ``windows`` (S, W); the horizon no longer scales
      memory. ``sketch`` records the bin geometry the sketches used.
    """

    latency: Array | None  # (S, N), or None in streaming mode
    file_id: Array | None  # (S, N), or None in streaming mode
    site_id: Array | None  # (S, N), or None in streaming mode
    node_busy: Array  # (S, m)
    hit: Array | None = None  # (S, N) bool cache hits, or None (no cache)
    stream: StreamingStats | None = None  # (S,)-batched, streaming mode
    windows: StreamingStats | None = None  # (S, W)-batched per-chunk stats
    hit_count: Array | None = None  # (S,) post-warmup hits (streaming+cache)
    sketch: SketchSpec | None = None  # bin geometry of stream/windows

    def mean_latency(self) -> Array:
        # stream wins when both exist: keep_latency re-materializes the
        # warmup region too, so the raw array is a superset of the
        # post-warm population the accumulators track
        if self.stream is not None:
            return stream_mean(stream_reduce(self.stream))
        return jnp.mean(self.latency)

    def quantile(self, q: float) -> Array:
        """Fleet-pooled latency quantile from the streaming sketch (merged
        across seeds — exact: integer bucket counts add)."""
        if self.stream is None:
            raise ValueError(
                "quantile() needs a streaming run (simulate_fleet(stream="
                "True)); materialized runs expose raw .latency instead"
            )
        return stream_quantile(stream_reduce(self.stream), q, self.sketch)

    def p99_windowed(self, q: float = 0.99) -> Array:
        """Mean of per-window (chunk) fleet-pooled sketch p99s — the
        streaming counterpart of ``ScenarioOutcome.p99_windowed`` (the
        SLO-dashboard aggregation; see `scenarios/engine.py`)."""
        if self.windows is None:
            raise ValueError("p99_windowed() needs a streaming run")
        merged = stream_reduce(self.windows, axis=0)  # (W,) pooled per window
        return windowed_quantile_mean(merged, q, self.sketch)

    def per_site_mean(self, n_sites: int) -> Array:
        """(C,) empirical mean latency by request origin site.

        A site that originated zero requests gets NaN, never a 0-count
        mean — the same contract as :meth:`SimResult.per_file_mean` and
        ``ScenarioOutcome.site_mean``. Materialized runs only (streaming
        accumulators are site-pooled).
        """
        if self.site_id is None:
            raise ValueError("per_site_mean() needs a materialized run")
        one_hot = jax.nn.one_hot(self.site_id, n_sites, dtype=jnp.float32)
        tot = jnp.einsum("snc,sn->c", one_hot, self.latency)
        cnt = one_hot.sum((0, 1))
        return jnp.where(cnt > 0, tot / jnp.maximum(cnt, 1.0), jnp.nan)


def _fleet_inputs(key, pi, lam_cs, overheads_cs, rates_cs, n_requests, ttl,
                  cache=None):
    """One seed's merged request stream: arrivals, marks, service draws,
    Madow service sets, and (when a hot tier is simulated) cache hits
    thinned out of the dispatch masks. Vmapped over the seed axis by every
    fleet driver; the FCFS recurrence itself runs in the shared
    `kernels/fcfs_queue.py` scan afterwards."""
    m = overheads_cs.shape[-1]
    k_wl, k_sel, k_srv = jax.random.split(key, 3)
    t, file_id, site_id = generate_geo_workload(k_wl, lam_cs, n_requests)
    sel_keys = jax.random.split(k_sel, n_requests)
    e = jax.random.exponential(k_srv, (n_requests, m))
    service = overheads_cs[site_id] + e / rates_cs[site_id]
    masks = jax.vmap(lambda sk, fid: madow_sample(sk, pi[fid]))(
        sel_keys, file_id
    )
    if ttl is None:
        hit = None
        new_cache = cache
    else:
        # every site shares one hot tier: the cache is keyed by file only,
        # so cross-site reads of the same object warm each other
        expiry = jnp.full(jnp.shape(ttl), -jnp.inf) if cache is None else cache
        new_cache, hit = ttl_cache_scan(expiry, t, file_id, ttl)
        masks = jnp.logical_and(masks, jnp.logical_not(hit)[:, None])
    return t, file_id, site_id, masks, service, hit, new_cache


def _fleet_one(
    key, pi, lam_cs, overheads_cs, rates_cs, n_requests, warm,
    ttl=None, hit_latency=0.0, backend="ref",
):
    t, file_id, site_id, masks, service, hit, _ = _fleet_inputs(
        key, pi, lam_cs, overheads_cs, rates_cs, n_requests, ttl
    )
    # busy accrues in the fcfs carry (an (m,) add per step) instead of
    # being emitted per step: an (N, m) stacked output would dominate the
    # whole kernel in memory traffic at fleet widths
    latency, _, busy = fcfs_scan(t, masks, service, backend=backend)
    if hit is not None:
        latency = jnp.where(hit, jnp.asarray(hit_latency), latency)
    return (
        latency[warm:],
        file_id[warm:],
        site_id[warm:],
        busy,
        None if hit is None else hit[warm:],
    )


# Jitted single-seed entry point — the sequential baseline that
# `benchmarks/fleet_scale.py` loops over to measure the vmap win.
fleet_one_raw = jax.jit(
    _fleet_one, static_argnames=("n_requests", "warm", "backend")
)


@functools.partial(
    jax.jit, static_argnames=("n_requests", "warm", "backend", "cached")
)
def _fleet_vmapped(
    keys, pi, lam_cs, overheads_cs, rates_cs, ttl, hit_latency,
    n_requests, warm, backend="ref", cached=False,
):
    """Materialized fleet: per-seed streams vmapped, then ONE batched
    (S, m)-wide FCFS scan (`kernels/fcfs_queue.py`) over the whole fleet.

    ``ttl``/``hit_latency`` are always present positionally so the
    shard_map in/out specs cover cached and uncached fleets alike; the
    static ``cached`` flag constant-folds the cache pre-scan out of
    uncached programs (a dummy ttl rides along, never read).
    """
    prep = lambda k: _fleet_inputs(
        k, pi, lam_cs, overheads_cs, rates_cs, n_requests,
        ttl if cached else None,
    )
    t, file_id, site_id, masks, service, hit, _ = jax.vmap(prep)(keys)
    latency, _, busy = fcfs_scan(t, masks, service, backend=backend)
    if cached:
        latency = jnp.where(hit, jnp.asarray(hit_latency), latency)
    return (
        latency[:, warm:],
        file_id[:, warm:],
        site_id[:, warm:],
        busy,
        hit[:, warm:] if cached else None,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_chunks", "block", "warm", "sketch", "backend", "cached",
        "materialize",
    ),
)
def _fleet_stream_batched(
    keys, pi, lam_cs, overheads_cs, rates_cs, ttl, hit_latency,
    n_chunks, block, warm, sketch, backend="ref", cached=False,
    materialize=False,
):
    """Streaming fleet: scan over ``n_chunks`` fixed-size request blocks.

    Carry = FCFS queue state + accrued busy + clock origin + cache
    warmth + the :class:`~.streaming.StreamingStats` accumulators, all
    (S,)-batched — so memory is O(S * block), constant in the total
    horizon ``n_chunks * block``. Each chunk draws its own workload block
    and continues one system history per seed (the same contract as
    ``SimCarry``) on a re-based clock: chunk arrivals count from the
    previous chunk's last arrival, and the carried departure and cache
    expiry times shift by that origin on entry. A float32 clock thus
    keeps the resolution of one chunk's span however long the horizon
    (an absolute clock loses it: at ~10^5 s its ulp reaches the
    smallest arrival gaps and arrivals stop increasing). It runs the
    (S, m)-wide FCFS kernel, and folds the block's latencies into both
    the global accumulators and that chunk's *window* stats (the
    streaming `p99_windowed` surface). With ``n_chunks == 1`` the random
    stream is identical to the materialized path's (`_fleet_vmapped`):
    the per-seed key is used directly instead of being split once more.

    ``materialize=True`` additionally stacks every block's latencies —
    O(total horizon) memory again — as the validation twin the parity
    tests and `benchmarks/fleet_scale.py` compare the streaming
    accumulators against.
    """
    s = keys.shape[0]
    m = overheads_cs.shape[-1]
    r = lam_cs.shape[-1]
    if n_chunks == 1:
        chunk_keys = keys[:, None]
    else:
        chunk_keys = jax.vmap(lambda k: jax.random.split(k, n_chunks))(keys)
    chunk_keys = jnp.swapaxes(chunk_keys, 0, 1)  # (W, S): scan xs
    ttl_arr = ttl if cached else None

    def chunk_step(carry, ckeys):
        dep, busy, origin, cache, stats, hitcnt, idx0 = carry
        dep = dep - origin[:, None]
        if cached:
            cache = cache - origin[:, None]
        prep = lambda k, ca: _fleet_inputs(
            k, pi, lam_cs, overheads_cs, rates_cs, block, ttl_arr, cache=ca
        )
        with diag.scope("fleet.inputs"):
            t, _, _, masks, service, hit, new_cache = jax.vmap(prep)(ckeys, cache)
        with diag.scope("fleet.fcfs"):
            latency, dep, busy = fcfs_scan(
                t, masks, service, dep, busy, backend=backend
            )
            if cached:
                latency = jnp.where(hit, jnp.asarray(hit_latency), latency)
        with diag.scope("fleet.stats"):
            inc = jnp.broadcast_to(
                idx0 + jnp.arange(block) >= warm, latency.shape
            )
            wstats = stream_from_values(latency, sketch, include=inc)
            stats = stream_merge(stats, wstats)
            if cached:
                hitcnt = hitcnt + jnp.sum(
                    jnp.logical_and(hit, inc), axis=1, dtype=jnp.int32
                )
        new_carry = (
            dep, busy, t[:, -1], new_cache, stats, hitcnt, idx0 + block
        )
        return new_carry, (wstats, latency if materialize else None)

    carry0 = (
        jnp.zeros((s, m)),  # dep
        jnp.zeros((s, m)),  # busy
        jnp.zeros((s,)),  # clock origin of the next chunk
        jnp.full((s, r), -jnp.inf) if cached else None,  # cache warmth
        stream_init(sketch, (s,)),
        jnp.zeros((s,), jnp.int32) if cached else None,
        jnp.asarray(0, jnp.int32),
    )
    (_, busy, _, _, stats, hitcnt, _), (windows, lats) = jax.lax.scan(
        chunk_step, carry0, chunk_keys
    )
    # scan stacks the chunk axis in front; every output must lead with the
    # seed axis so shard_map's out_specs shard seeds, not chunks
    windows = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), windows)
    if materialize:
        lats = jnp.swapaxes(lats, 0, 1).reshape(s, n_chunks * block)
    return stats, windows, busy, hitcnt, lats


@diag.hot_path("storage.simulate_fleet")
def simulate_fleet(
    key: Array,
    pi: Array,
    lam_cs: Array,
    fabric,
    chunk_mb: float,
    n_requests: int,
    n_seeds: int,
    *,
    drop_warmup: float = 0.1,
    devices: str = "auto",
    cache_ttl: Array | None = None,
    cache_hit_latency: float = 0.0,
    stream: bool = False,
    n_chunks: int = 1,
    sketch: SketchSpec | None = None,
    backend: str = "auto",
    keep_latency: bool = False,
) -> FleetResult:
    """Simulate ``n_seeds`` independent geo systems in ONE device program.

    The fleet axis is pure data parallelism — seeds never interact — so
    per-seed workload/dispatch prep vmaps and the FCFS recurrence runs as
    ONE (S, m)-wide scan in the shared `kernels/fcfs_queue.py` kernel
    (``backend="auto"``: fused Pallas on TPU, ``lax.scan`` ref elsewhere),
    amortizing the per-step dispatch that dominates a Python loop over
    seeds (``fleet_one_raw``; the >= 10x win is asserted by
    `benchmarks/fleet_scale.py`). With multiple local devices the program
    is additionally ``shard_map``-ped over a seed mesh axis
    (``devices="auto"``; ``"never"`` forces plain vmap) with no change in
    semantics: each seed's trajectory is identical to the sequential run
    of the same key (asserted by ``tests/test_fleet_parity.py``). Cached
    fleets shard like uncached ones — the ttl/hit streams are covered by
    the spec set — and when ``n_seeds`` is not a device multiple the seed
    axis is padded up to one (padded seeds recompute early keys and are
    sliced away) instead of silently falling back to a single device.

    ``stream=True`` switches to the streaming path: per-request latency
    arrays are never materialized; instead constant-size streaming
    moments + quantile sketches (``FleetResult.stream``, per-window
    ``windows``; `storage/streaming.py`) accumulate in the scan carry, so
    the simulated horizon is memory-unbounded. ``n_chunks`` runs the
    horizon as ``n_chunks`` x ``n_requests``-sized blocks at O(block)
    memory (requires ``stream=True``); ``sketch`` sets the quantile bin
    geometry (default :data:`~.streaming.DEFAULT_SKETCH`).
    ``keep_latency=True`` (validation only) re-materializes the full
    latency matrix alongside the accumulators.

    ``cache_ttl`` (r,) puts one shared hot-tier cache (cold at t=0) in
    front of every seed's queues; each seed replays its own cache history
    (independent workloads → independent warmth trajectories). Streaming
    cache runs report post-warmup ``hit_count`` per seed instead of the
    per-request hit stream.
    """
    if n_chunks < 1:
        raise ValueError(f"n_chunks must be >= 1, got {n_chunks}")
    if n_chunks > 1 and not stream:
        raise ValueError(
            "chunked horizons (n_chunks > 1) require stream=True — the "
            "materialized path would allocate the full horizon anyway"
        )
    if keep_latency and not stream:
        raise ValueError("keep_latency only applies to stream=True runs")
    keys = jax.random.split(key, n_seeds)
    d, rates = fabric.service_params(chunk_mb)
    lam_cs = jnp.asarray(lam_cs, jnp.float32)
    total = n_requests * n_chunks
    warm = int(total * drop_warmup)
    cached = cache_ttl is not None
    sketch = DEFAULT_SKETCH if sketch is None else sketch
    ttl = (
        jnp.asarray(cache_ttl, jnp.float32)
        if cached
        else jnp.zeros((1,), jnp.float32)  # dummy; constant-folded away
    )
    hit_lat = jnp.asarray(cache_hit_latency, jnp.float32)

    if stream:
        fn = functools.partial(
            _fleet_stream_batched,
            n_chunks=n_chunks, block=n_requests, warm=warm, sketch=sketch,
            backend=backend, cached=cached, materialize=keep_latency,
        )
    else:
        fn = functools.partial(
            _fleet_vmapped,
            n_requests=n_requests, warm=warm, backend=backend, cached=cached,
        )

    n_dev = len(jax.devices())
    if devices == "auto" and n_dev > 1:
        # pad the seed axis up to a device multiple (padded seeds rerun
        # early keys and are masked out below) — never a silent
        # single-device fallback for odd seed counts
        s_run = n_seeds + (-n_seeds) % n_dev
        if s_run != n_seeds:
            keys = keys[jnp.arange(s_run) % n_seeds]
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("seed",))
        spec = jax.sharding.PartitionSpec
        # seeds never communicate, so the body is collective-free and
        # needs no varying-axis types; with them, every scan carry built
        # inside (queues, clock, cache warmth) would have to be cast to
        # "varying" by hand to match its per-seed outputs
        sharded = jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(spec("seed"),) + (spec(),) * 6,
            out_specs=spec("seed"),
            check_vma=False,
        )
        out = sharded(keys, jnp.asarray(pi), lam_cs, d, rates, ttl, hit_lat)
        if s_run != n_seeds:
            out = jax.tree.map(lambda x: x[:n_seeds], out)
    else:
        out = fn(keys, jnp.asarray(pi), lam_cs, d, rates, ttl, hit_lat)

    if stream:
        stats, windows, busy, hitcnt, lats = out
        return FleetResult(
            latency=lats,
            file_id=None,
            site_id=None,
            node_busy=busy,
            hit=None,
            stream=stats,
            windows=windows,
            hit_count=hitcnt,
            sketch=sketch,
        )
    return FleetResult(*out)
