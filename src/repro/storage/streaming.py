"""Online latency statistics: streaming moments + log-spaced quantile sketch.

Materializing one latency per request caps the simulated horizon at
whatever an (S, N) array fits — the fleet simulator's old contract. This
module replaces that array with constant-size accumulators that fold a
block of latencies at a time and merge associatively, so the horizon is
unbounded and a multi-device fleet can combine per-shard statistics
exactly:

**Moments** — count / running mean / M2 (sum of squared deviations from
the running mean), i.e. Welford's online algorithm in its batched
(Chan et al.) form: two accumulators over disjoint blocks merge with

    n      = n_a + n_b
    mean   = mean_a + (mean_b - mean_a) * n_b / n
    M2     = M2_a + M2_b + (mean_b - mean_a)^2 * n_a * n_b / n

which is exact in infinite precision and numerically stable in fp32
(the fleet's dtype); ``tests/test_streaming.py`` property-tests the
fp32 tolerance against exact ``np``/``jnp`` mean/variance.

**Quantile sketch** — a fixed histogram over log-spaced bins. With
``bins`` buckets spanning ``[lo, hi)`` the growth factor is
``g = (hi/lo)**(1/bins)`` and bucket ``b`` covers
``[lo*g^(b-1), lo*g^b)``; two clamp buckets catch ``x < lo`` and
``x >= hi``.  :func:`stream_quantile` returns the *upper edge* of the
bucket holding the rank-``ceil(q*n)`` order statistic, giving the
documented deterministic guarantee (for values in the regular range):

    x_(ceil(q*n))  <=  estimate  <=  g * x_(ceil(q*n))

i.e. a one-sided relative value error of at most ``g - 1``
(:attr:`SketchSpec.rel_error`; 3.2% at the 512-bin default spanning
1 ms..10^4 s). Values below ``lo`` resolve to ``lo`` (absolute error
< ``lo``); the overflow bucket resolves to the tracked maximum, which
is always a valid upper bound. Bucket counts are integers, so merged
sketches equal the single-pass sketch *exactly* — the property the
multi-device fleet relies on when combining per-shard results.

Everything here is shape-polymorphic over leading batch axes (a fleet
carries (S,)-batched stats; the chunked driver stacks an (S, W) window
axis) and jit/scan/shard_map-friendly: :class:`StreamingStats` holds
arrays only, while the static bin geometry lives in the hashable
:class:`SketchSpec` passed alongside.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np
from jax import Array

from repro.kernels.fcfs_queue import _on_tpu


@dataclasses.dataclass(frozen=True)
class SketchSpec:
    """Static bin geometry of the quantile sketch (hashable, jit-static).

    ``lo``/``hi`` bound the regular log-spaced range; latencies outside
    land in clamp buckets (below: resolve to ``lo``; above: resolve to
    the tracked max). ``bins`` regular buckets give a per-quantile
    relative error bound of ``(hi/lo)**(1/bins) - 1``.
    """

    lo: float = 1e-3
    hi: float = 1e4
    bins: int = 512

    def __post_init__(self):
        if not (0.0 < self.lo < self.hi):
            raise ValueError(f"need 0 < lo < hi, got {self.lo}, {self.hi}")
        if self.bins < 1:
            raise ValueError(f"need >= 1 bin, got {self.bins}")

    @property
    def growth(self) -> float:
        """Per-bucket growth factor ``g``."""
        return (self.hi / self.lo) ** (1.0 / self.bins)

    @property
    def rel_error(self) -> float:
        """Documented one-sided relative quantile error bound, ``g - 1``."""
        return self.growth - 1.0

    @property
    def n_buckets(self) -> int:
        """Total buckets including the two clamp buckets."""
        return self.bins + 2

    @functools.cached_property
    def edges(self) -> np.ndarray:
        """(bins + 1,) ascending bucket edges ``lo * g**i`` (float64 host
        constant; cached — baked into jitted programs as a literal)."""
        return self.lo * self.growth ** np.arange(self.bins + 1)


DEFAULT_SKETCH = SketchSpec()


class StreamingStats(NamedTuple):
    """Constant-size latency accumulators; arrays only (pytree-safe).

    All fields share the same leading batch shape ``(...)``: scalar for
    one stream, (S,) for a fleet, (S, W) for per-window stats. ``count``
    / ``hist`` are exact integer counts; ``mean``/``m2`` are fp32
    Welford state; ``minv``/``maxv`` track the observed range (+inf/-inf
    when empty).
    """

    count: Array  # (...,) int32 values folded
    mean: Array  # (...,) running mean
    m2: Array  # (...,) sum of squared deviations from the mean
    minv: Array  # (...,) smallest value seen (+inf when empty)
    maxv: Array  # (...,) largest value seen (-inf when empty)
    hist: Array  # (..., bins + 2) integer bucket counts


def stream_init(
    spec: SketchSpec = DEFAULT_SKETCH, batch_shape: tuple[int, ...] = ()
) -> StreamingStats:
    """Empty accumulators with the given leading batch shape."""
    z = jnp.zeros(batch_shape, jnp.float32)
    return StreamingStats(
        count=jnp.zeros(batch_shape, jnp.int32),
        mean=z,
        m2=z,
        minv=jnp.full(batch_shape, jnp.inf, jnp.float32),
        maxv=jnp.full(batch_shape, -jnp.inf, jnp.float32),
        hist=jnp.zeros(batch_shape + (spec.n_buckets,), jnp.int32),
    )


def stream_fold(
    stats: StreamingStats,
    x: Array,
    spec: SketchSpec = DEFAULT_SKETCH,
    *,
    include: Array | None = None,
) -> StreamingStats:
    """Fold a block of values into the accumulators (one vectorized pass).

    ``x`` is (..., K) with leading axes matching ``stats``; ``include``
    (same shape, bool) masks values out of the fold — the chunked fleet
    driver uses it to drop warmup requests without changing block shapes.
    The block's own moments are computed vectorized, then merged with the
    carried state via the batched-Welford combine, so folding is O(K)
    with O(bins) state.
    """
    x = jnp.asarray(x, jnp.float32)
    inc = (
        jnp.ones(x.shape, bool)
        if include is None
        else jnp.asarray(include, bool)
    )
    incf = inc.astype(jnp.float32)
    n_b = jnp.sum(inc, axis=-1).astype(jnp.int32)
    n_bf = jnp.maximum(n_b.astype(jnp.float32), 1.0)
    mean_b = jnp.sum(x * incf, axis=-1) / n_bf
    dev = jnp.where(inc, x - mean_b[..., None], 0.0)
    m2_b = jnp.sum(dev * dev, axis=-1)
    # the initial values make an empty block (K == 0) an identity fold
    min_b = jnp.min(jnp.where(inc, x, jnp.inf), axis=-1, initial=jnp.inf)
    max_b = jnp.max(jnp.where(inc, x, -jnp.inf), axis=-1, initial=-jnp.inf)

    edges = jnp.asarray(spec.edges, jnp.float32)
    idx = bucketize(edges, x)  # (..., K) in [0, bins+1]
    # masked-out values are routed to bucket 0 with weight 0
    hist_b = _scatter_counts(
        jnp.where(inc, idx, 0), inc.astype(jnp.int32), spec.n_buckets
    )

    block = StreamingStats(
        count=n_b, mean=mean_b, m2=m2_b, minv=min_b, maxv=max_b, hist=hist_b
    )
    return stream_merge(stats, block)


def bucketize(edges: Array, x: Array, *, method: str = "auto") -> Array:
    """Bucket of each ``x`` among the non-decreasing ``edges``: the
    number of edges ``<= x``, i.e. ``jnp.searchsorted(edges, x,
    side="right")``, whichever ``method`` computes it.

    ``"auto"`` counts on the TPU (``"compare_all"``: a compare and a sum
    that XLA fuses into one reduction over the table, with no gather) and
    binary-searches elsewhere (``"scan"``). Each of the scan's
    log2(len(edges)) levels gathers one table entry per query, which a
    TPU does slowly; on a CPU the scan is the cheaper of the two.
    """
    if method == "auto":
        method = "compare_all" if _on_tpu() else "scan"
    return jnp.searchsorted(edges, x, side="right", method=method)


def _scatter_counts(idx: Array, weights: Array, n_buckets: int) -> Array:
    """Histogram of ``idx`` (..., K) with integer ``weights`` into
    (..., n_buckets); batched scatter-add."""
    rows_shape = (int(np.prod(idx.shape[:-1])), idx.shape[-1])  # K may be 0
    flat_idx = idx.reshape(rows_shape)
    flat_w = weights.reshape(rows_shape)
    out = jnp.zeros((flat_idx.shape[0], n_buckets), jnp.int32)
    rows = jnp.broadcast_to(
        jnp.arange(flat_idx.shape[0])[:, None], flat_idx.shape
    )
    out = out.at[rows, flat_idx].add(flat_w)
    return out.reshape(idx.shape[:-1] + (n_buckets,))


def stream_merge(a: StreamingStats, b: StreamingStats) -> StreamingStats:
    """Combine two accumulators over disjoint value sets (associative).

    Histogram/count/min/max merge exactly; moments merge by the batched
    Welford combine (exact in infinite precision, fp32-stable). Safe when
    either side is empty.
    """
    n_a = a.count.astype(jnp.float32)
    n_b = b.count.astype(jnp.float32)
    n = n_a + n_b
    nf = jnp.maximum(n, 1.0)
    delta = b.mean - a.mean
    # empty sides carry mean 0 — route through the weighted form so an
    # empty accumulator is a true identity element
    mean = jnp.where(n > 0, a.mean + delta * n_b / nf, 0.0)
    m2 = a.m2 + b.m2 + delta * delta * n_a * n_b / nf
    return StreamingStats(
        count=a.count + b.count,
        mean=mean,
        m2=jnp.where(n > 0, m2, 0.0),
        minv=jnp.minimum(a.minv, b.minv),
        maxv=jnp.maximum(a.maxv, b.maxv),
        hist=a.hist + b.hist,
    )


def stream_reduce(stats: StreamingStats, axis: int = 0) -> StreamingStats:
    """Merge accumulators along a batch axis (e.g. the fleet's seed axis)
    in one vectorized pass — the generalized Chan combine:

        n = sum n_i;  mean = sum(n_i mean_i)/n;
        M2 = sum M2_i + sum n_i (mean_i - mean)^2
    """
    n_i = stats.count.astype(jnp.float32)
    n = jnp.sum(n_i, axis=axis)
    nf = jnp.maximum(n, 1.0)
    mean = jnp.sum(n_i * stats.mean, axis=axis) / nf
    mean = jnp.where(n > 0, mean, 0.0)
    dev = stats.mean - jnp.expand_dims(mean, axis)
    m2 = jnp.sum(stats.m2 + n_i * dev * dev, axis=axis)
    return StreamingStats(
        count=jnp.sum(stats.count, axis=axis),
        mean=mean,
        m2=jnp.where(n > 0, m2, 0.0),
        minv=jnp.min(stats.minv, axis=axis),
        maxv=jnp.max(stats.maxv, axis=axis),
        hist=jnp.sum(stats.hist, axis=axis if axis >= 0 else axis - 1),
    )


def stream_mean(stats: StreamingStats) -> Array:
    """Running mean; NaN for empty accumulators."""
    return jnp.where(stats.count > 0, stats.mean, jnp.nan)


def stream_var(stats: StreamingStats) -> Array:
    """Population variance (ddof=0, matching ``jnp.var``); NaN if empty."""
    return jnp.where(
        stats.count > 0,
        stats.m2 / jnp.maximum(stats.count.astype(jnp.float32), 1.0),
        jnp.nan,
    )


def stream_quantile(
    stats: StreamingStats, q: float, spec: SketchSpec = DEFAULT_SKETCH
) -> Array:
    """Sketch quantile: upper edge of the bucket holding the rank-
    ``ceil(q * count)`` order statistic (clamped to the observed max).

    Guarantee (see module docstring): the estimate is >= the true order
    statistic and overshoots it by at most a factor ``spec.growth`` for
    values in ``[lo, hi)``; below-range values resolve to ``lo``,
    above-range to the exact observed maximum. NaN for empty stats.
    Vectorized over leading batch axes.
    """
    count = stats.count.astype(jnp.float32)
    rank = jnp.clip(jnp.ceil(q * count), 1.0, jnp.maximum(count, 1.0))
    cum = jnp.cumsum(stats.hist, axis=-1).astype(jnp.float32)
    b = jnp.sum(cum < rank[..., None], axis=-1)  # first bucket with cum >= rank
    edges = jnp.asarray(spec.edges, jnp.float32)
    in_range = jnp.clip(b, 0, spec.bins)
    est = jnp.minimum(edges[in_range], stats.maxv)
    est = jnp.where(b > spec.bins, stats.maxv, est)
    return jnp.where(stats.count > 0, est, jnp.nan)


def stream_from_values(
    x: Array,
    spec: SketchSpec = DEFAULT_SKETCH,
    *,
    include: Array | None = None,
) -> StreamingStats:
    """Accumulators of a materialized block (the test/validation bridge
    between streaming and materialized paths)."""
    x = jnp.asarray(x, jnp.float32)
    return stream_fold(
        stream_init(spec, x.shape[:-1]), x, spec, include=include
    )


def windowed_quantile_mean(
    windows: StreamingStats, q: float = 0.99, spec: SketchSpec = DEFAULT_SKETCH
) -> Array:
    """Mean of per-window sketch quantiles over the LAST batch axis — the
    streaming counterpart of ``ScenarioOutcome.p99_windowed`` (mean of
    per-segment p99s, the SLO-dashboard aggregation; see
    `scenarios/engine.py`). Empty windows are skipped (NaN-mean).
    """
    qs = stream_quantile(windows, q, spec)
    return jnp.nanmean(qs, axis=-1)
