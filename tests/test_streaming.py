"""Streaming moments + log-spaced quantile sketch (`storage/streaming.py`).

Deterministic unit tests always run; the property tests use hypothesis
when installed (`_hypothesis_compat`) and are skipped cleanly otherwise.
The contracts under test are the ones the fleet simulator leans on:

* moments (count/mean/M2) match exact mean/variance to fp32 tolerance,
  under any split into blocks and any merge order (Chan's method);
* sketch quantiles bracket the exact inverted-CDF order statistic within
  one bucket's growth factor: ``x_(ceil(q n)) <= est <= g * x_(ceil(q n))``
  for in-range values;
* merged per-device sketches equal the single-device sketch (integer
  bucket counts add exactly).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

from repro.storage import streaming
from repro.storage.streaming import (
    DEFAULT_SKETCH,
    SketchSpec,
    StreamingStats,
    bucketize,
    stream_fold,
    stream_from_values,
    stream_init,
    stream_mean,
    stream_merge,
    stream_quantile,
    stream_reduce,
    stream_var,
    windowed_quantile_mean,
)

SPEC = SketchSpec(lo=1e-3, hi=1e3, bins=256)


def _exact_quantile(x, q):
    return float(np.quantile(np.asarray(x), q, method="inverted_cdf"))


class TestMoments:
    def test_fold_matches_exact(self):
        x = np.random.default_rng(0).gamma(2.0, 0.05, size=2048).astype(
            np.float32
        )
        s = stream_from_values(jnp.asarray(x), SPEC)
        np.testing.assert_allclose(float(stream_mean(s)), x.mean(), rtol=1e-5)
        np.testing.assert_allclose(
            float(stream_var(s)), x.var(), rtol=1e-4
        )
        assert int(s.count) == x.size
        np.testing.assert_allclose(float(s.minv), x.min(), rtol=1e-6)
        np.testing.assert_allclose(float(s.maxv), x.max(), rtol=1e-6)

    def test_blockwise_fold_matches_single_fold(self):
        x = np.random.default_rng(1).exponential(0.1, 1000).astype(np.float32)
        whole = stream_from_values(jnp.asarray(x), SPEC)
        s = stream_init(SPEC, ())
        for blk in np.array_split(x, 7):
            s = stream_fold(s, jnp.asarray(blk), SPEC)
        assert int(s.count) == int(whole.count)
        np.testing.assert_array_equal(
            np.asarray(s.hist), np.asarray(whole.hist)
        )
        np.testing.assert_allclose(
            float(stream_mean(s)), float(stream_mean(whole)), rtol=1e-6
        )
        np.testing.assert_allclose(
            float(stream_var(s)), float(stream_var(whole)), rtol=1e-4
        )

    def test_include_mask(self):
        x = jnp.arange(1, 11, dtype=jnp.float32)
        inc = x > 5
        s = stream_from_values(x, SPEC, include=inc)
        assert int(s.count) == 5
        np.testing.assert_allclose(float(stream_mean(s)), 8.0, rtol=1e-6)

    def test_empty_is_nan(self):
        s = stream_init(SPEC, ())
        assert np.isnan(float(stream_mean(s)))
        assert np.isnan(float(stream_var(s)))
        assert np.isnan(float(stream_quantile(s, 0.5, SPEC)))

    def test_merge_with_empty_is_identity(self):
        x = jnp.asarray([0.5, 1.5, 2.5])
        s = stream_from_values(x, SPEC)
        e = stream_init(SPEC, ())
        for merged in (stream_merge(s, e), stream_merge(e, s)):
            assert int(merged.count) == 3
            np.testing.assert_allclose(
                float(stream_mean(merged)), float(stream_mean(s)), rtol=1e-6
            )

    def test_reduce_matches_pooled(self):
        x = np.random.default_rng(2).exponential(0.2, (6, 300)).astype(
            np.float32
        )
        batched = stream_from_values(jnp.asarray(x), SPEC)  # (6,)-batched
        red = stream_reduce(batched)
        pooled = stream_from_values(jnp.asarray(x.reshape(-1)), SPEC)
        assert int(red.count) == int(pooled.count)
        np.testing.assert_array_equal(
            np.asarray(red.hist), np.asarray(pooled.hist)
        )
        np.testing.assert_allclose(
            float(stream_mean(red)), x.mean(), rtol=1e-5
        )
        np.testing.assert_allclose(float(stream_var(red)), x.var(), rtol=1e-4)


class TestSketch:
    def test_quantile_within_growth_bound(self):
        rng = np.random.default_rng(3)
        x = rng.gamma(2.0, 0.05, 4096).astype(np.float32)
        s = stream_from_values(jnp.asarray(x), SPEC)
        for q in (0.5, 0.9, 0.99, 0.999):
            est = float(stream_quantile(s, q, SPEC))
            exact = _exact_quantile(x, q)
            assert exact <= est * (1 + 1e-6), (q, exact, est)
            assert est <= exact * SPEC.growth * (1 + 1e-6), (q, exact, est)

    def test_quantile_clamped_to_tracked_max(self):
        x = jnp.asarray([0.01, 0.02, 0.03])
        s = stream_from_values(x, SPEC)
        assert float(stream_quantile(s, 1.0, SPEC)) <= 0.03 * (1 + 1e-6)

    def test_overflow_bucket_reports_max(self):
        """Values past ``hi`` land in the clamp bucket; the quantile
        estimate degrades to the tracked max, never silently under."""
        x = jnp.asarray([0.5, 2e3, 5e3])
        s = stream_from_values(x, SPEC)
        est = float(stream_quantile(s, 0.99, SPEC))
        np.testing.assert_allclose(est, 5e3, rtol=1e-6)

    def test_merged_devices_equal_single(self):
        """Per-device sketches merged == one sketch over everything —
        integer bucket counts add exactly, so this is equality, not
        approximation."""
        rng = np.random.default_rng(4)
        x = rng.exponential(0.1, (8, 512)).astype(np.float32)
        per_dev = stream_from_values(jnp.asarray(x), SPEC)  # (8,)-batched
        merged = stream_reduce(per_dev)
        single = stream_from_values(jnp.asarray(x.reshape(-1)), SPEC)
        np.testing.assert_array_equal(
            np.asarray(merged.hist), np.asarray(single.hist)
        )
        for q in (0.5, 0.95, 0.99):
            assert float(stream_quantile(merged, q, SPEC)) == float(
                stream_quantile(single, q, SPEC)
            )

    def test_windowed_quantile_mean(self):
        x = np.random.default_rng(5).exponential(0.1, (4, 10, 200)).astype(
            np.float32
        )
        windows = stream_from_values(jnp.asarray(x), SPEC)  # (4, 10) windows
        got = np.asarray(windowed_quantile_mean(windows, 0.99, SPEC))
        per_w = np.asarray(
            jax.vmap(
                jax.vmap(lambda w: stream_quantile(w, 0.99, SPEC))
            )(windows)
        )
        assert got.shape == (4,)  # reduces the window axis, keeps the batch
        np.testing.assert_allclose(got, np.nanmean(per_w, axis=-1), rtol=1e-6)

    def test_spec_geometry(self):
        spec = SketchSpec(lo=1e-3, hi=1e4, bins=512)
        assert spec.n_buckets == 512 + 2
        np.testing.assert_allclose(
            spec.growth ** 512, 1e4 / 1e-3, rtol=1e-9
        )
        # documented relative error: one bucket's growth factor
        assert spec.rel_error == pytest.approx(spec.growth - 1.0)


def _tahoe_cdf():
    """The 1000-bin mark CDF of the paper's Tahoe catalog (r = 1000 files,
    rates by thirds), built as `generate_geo_workload` builds it."""
    lam = np.zeros(1000, np.float32)
    for i, rate in enumerate((1.25e-4, 1.25e-4, 1.0 / 9600)):
        lam[i::3] = rate
    flat = jnp.asarray(lam)
    return jnp.cumsum(flat / jnp.sum(flat))


def _around(edges):
    """Queries that hit a table hard: every entry, its float32 neighbours
    on both sides, values past both ends, 0 and +-inf."""
    e = np.asarray(edges, np.float32)
    return np.concatenate([
        e,
        np.nextafter(e, np.float32(np.inf)),
        np.nextafter(e, np.float32(-np.inf)),
        np.asarray([e[0] / 2, e[0] - 1, e[-1] * 2, e[-1] + 1, 0.0,
                    np.inf, -np.inf], np.float32),
    ])


def _log_uniform(lo, hi, n, seed):
    rng = np.random.default_rng(seed)
    return np.exp(rng.uniform(np.log(lo), np.log(hi), n)).astype(np.float32)


def _tables():
    tied = np.asarray([0.5, 1.0, 1.0, 1.0, 2.0, 3.0, 3.0], np.float32)
    cdf = np.asarray(_tahoe_cdf())
    u = np.asarray(jax.random.uniform(jax.random.key(0), (1 << 14,)))

    def sketch(spec, seed):
        x = _log_uniform(spec.lo / 10, spec.hi * 10, 4096, seed)
        return spec.edges.astype(np.float32), np.concatenate(
            [_around(spec.edges), x]
        )

    return {
        "ties": (
            tied, np.concatenate([_around(tied), _log_uniform(0.1, 10, 256, 0)])
        ),
        "tahoe_cdf": (cdf, np.concatenate([_around(cdf), u])),
        "default_sketch": sketch(DEFAULT_SKETCH, 2),
        "spec_256": sketch(SPEC, 3),
    }


class TestBucketize:
    """`bucketize` is ``searchsorted(side="right")`` whichever way it is
    computed: the fleet's marks and sketch buckets must not move when the
    TPU counts where the CPU binary-searches. (`DEFAULT_SKETCH` is the
    fleet benchmark cell's 512-bin sketch.)"""

    @pytest.mark.parametrize("method", ["scan", "compare_all"])
    @pytest.mark.parametrize(
        "table", ["ties", "tahoe_cdf", "default_sketch", "spec_256"]
    )
    def test_matches_numpy(self, method, table):
        edges, x = _tables()[table]
        got = bucketize(jnp.asarray(edges), jnp.asarray(x), method=method)
        want = np.searchsorted(edges, x, side="right")
        np.testing.assert_array_equal(np.asarray(got), want)
        # the same queries as a (2, K) block, the fleet chunk step's shape
        k = x.size // 2
        block = jnp.asarray(x[: 2 * k]).reshape(2, k)
        got2 = bucketize(jnp.asarray(edges), block, method=method)
        np.testing.assert_array_equal(np.asarray(got2).ravel(), want[: 2 * k])

    @pytest.mark.parametrize("on_tpu", [False, True])
    def test_stream_fold_buckets_and_mask(self, monkeypatch, on_tpu):
        """`stream_fold` takes each platform's method (compare-and-count
        on the TPU) and counts only the included values, +-inf in the
        clamp buckets."""
        monkeypatch.setattr(streaming, "_on_tpu", lambda: on_tpu)
        x = np.concatenate([
            _log_uniform(1e-4, 1e5, 1000, 4), _around(DEFAULT_SKETCH.edges)
        ])
        inc = np.random.default_rng(5).random(x.size) < 0.7
        inc[-2:] = True  # +-inf
        s = stream_from_values(
            jnp.asarray(x), DEFAULT_SKETCH, include=jnp.asarray(inc)
        )
        edges = DEFAULT_SKETCH.edges.astype(np.float32)
        want = np.bincount(
            np.searchsorted(edges, x[inc], side="right"),
            minlength=DEFAULT_SKETCH.n_buckets,
        )
        np.testing.assert_array_equal(np.asarray(s.hist), want)
        assert want[0] > 0 and want[-1] > 0


class TestSimResultStream:
    def test_simulate_exposes_stream(self):
        """`simulate(..., sketch=...)` folds post-warmup latencies into a
        StreamingStats pytree consistent with the materialized array."""
        from repro.storage import homogeneous_cluster, simulate

        cluster = homogeneous_cluster(6, 12.5)
        pi = jnp.full((4, 6), 0.5, jnp.float32)
        lam = jnp.full((4,), 0.02, jnp.float32)
        res = simulate(
            jax.random.key(0), pi, lam, cluster, 12.5, 500,
            sketch=DEFAULT_SKETCH,
        )
        assert res.stream is not None
        lat = np.asarray(res.latency)
        assert int(res.stream.count) == lat.size
        np.testing.assert_allclose(
            float(stream_mean(res.stream)), lat.mean(), rtol=1e-5
        )
        est = float(stream_quantile(res.stream, 0.99, DEFAULT_SKETCH))
        exact = float(np.quantile(lat, 0.99, method="inverted_cdf"))
        assert exact <= est <= exact * DEFAULT_SKETCH.growth * (1 + 1e-6)

    def test_simulate_default_has_no_stream(self):
        from repro.storage import homogeneous_cluster, simulate

        cluster = homogeneous_cluster(6, 12.5)
        pi = jnp.full((4, 6), 0.5, jnp.float32)
        lam = jnp.full((4,), 0.02, jnp.float32)
        res = simulate(jax.random.key(1), pi, lam, cluster, 12.5, 200)
        assert res.stream is None


# hypothesis refuses float32 bounds that float32 cannot hold exactly
pos_floats = st.lists(
    st.floats(
        min_value=float(np.float32(2e-3)), max_value=float(np.float32(5e2)),
        allow_nan=False, allow_infinity=False, width=32,
    ),
    min_size=4,
    max_size=400,
)


class TestProperties:
    @given(pos_floats)
    @settings(max_examples=60, deadline=None)
    def test_moments_match_exact(self, xs):
        x = np.asarray(xs, np.float64)
        s = stream_from_values(jnp.asarray(x, jnp.float32), SPEC)
        assert int(s.count) == x.size
        np.testing.assert_allclose(
            float(stream_mean(s)), x.mean(), rtol=5e-5, atol=1e-6
        )
        np.testing.assert_allclose(
            float(stream_var(s)), x.var(), rtol=5e-3, atol=1e-7
        )

    @given(pos_floats, st.sampled_from([0.5, 0.9, 0.95, 0.99]))
    @settings(max_examples=60, deadline=None)
    def test_quantile_rank_error_bound(self, xs, q):
        x = np.asarray(xs, np.float32)
        s = stream_from_values(jnp.asarray(x), SPEC)
        est = float(stream_quantile(s, q, SPEC))
        exact = _exact_quantile(x, q)
        assert exact <= est * (1 + 1e-5)
        assert est <= exact * SPEC.growth * (1 + 1e-5)

    @given(pos_floats, st.integers(min_value=2, max_value=6))
    @settings(max_examples=40, deadline=None)
    def test_merge_order_invariant(self, xs, parts):
        x = np.asarray(xs, np.float32)
        chunks = np.array_split(x, parts)
        fwd = stream_init(SPEC, ())
        for c in chunks:
            fwd = stream_merge(fwd, stream_from_values(jnp.asarray(c), SPEC))
        rev = stream_init(SPEC, ())
        for c in reversed(chunks):
            rev = stream_merge(rev, stream_from_values(jnp.asarray(c), SPEC))
        assert int(fwd.count) == int(rev.count) == x.size
        np.testing.assert_array_equal(
            np.asarray(fwd.hist), np.asarray(rev.hist)
        )
        np.testing.assert_allclose(
            float(stream_mean(fwd)), float(stream_mean(rev)), rtol=1e-5
        )
        np.testing.assert_allclose(
            float(stream_var(fwd)),
            float(stream_var(rev)),
            rtol=1e-3,
            atol=1e-8,
        )
