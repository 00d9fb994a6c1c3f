"""Device-parity harness for the fleet simulator.

Three layers of trust, each asserted independently:

1. **Kernel parity** — the fused Pallas FCFS scan (interpret mode on CPU)
   against the ``lax.scan`` ref backend over randomized (t, mask, service)
   workloads, including all-false mask rows (cache hits) and carried-in
   queue state.
2. **Batching parity** — sequential ``fleet_one_raw`` vs the vmapped fleet
   on the same keys: identical trajectories.
3. **Sharding parity** — vmap vs ``shard_map`` over a forced 8-device host
   mesh (subprocess: the device count must be set before jax initializes),
   covering cached fleets (regression: they used to bypass shard_map),
   odd seed counts (regression: they used to silently drop to one
   device), and the streaming path.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import feasible_uniform
from repro.kernels.fcfs_queue import fcfs_scan
from repro.storage import fleet_one_raw, geo_testbed, simulate_fleet

K = 6


def _random_workload(key, s, n, m, p_empty=0.1):
    """Randomized (t, masks, service) with some all-false mask rows."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    t = jnp.cumsum(jax.random.exponential(k1, (s, n)), axis=-1)
    masks = jax.random.bernoulli(k2, 0.5, (s, n, m))
    empty = jax.random.bernoulli(k3, p_empty, (s, n))
    masks = jnp.logical_and(masks, jnp.logical_not(empty)[..., None])
    service = 0.01 + jax.random.exponential(k4, (s, n, m)) * 0.05
    return t, masks, service


class TestKernelParity:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    @pytest.mark.parametrize("s,n,m", [(1, 64, 4), (5, 128, 6), (16, 32, 3)])
    def test_pallas_matches_ref_randomized(self, seed, s, n, m):
        t, masks, service = _random_workload(jax.random.key(seed), s, n, m)
        lat_r, dep_r, busy_r = fcfs_scan(t, masks, service, backend="ref")
        lat_p, dep_p, busy_p = fcfs_scan(t, masks, service, backend="pallas")
        np.testing.assert_array_equal(np.asarray(lat_r), np.asarray(lat_p))
        np.testing.assert_array_equal(np.asarray(dep_r), np.asarray(dep_p))
        np.testing.assert_allclose(
            np.asarray(busy_r), np.asarray(busy_p), rtol=1e-6
        )

    def test_pallas_matches_ref_with_carried_state(self):
        """Chunked-horizon contract: queue state carried across calls."""
        key = jax.random.key(3)
        t, masks, service = _random_workload(key, 4, 96, 5)
        dep0 = jax.random.exponential(jax.random.key(9), (4, 5))
        busy0 = jax.random.exponential(jax.random.key(10), (4, 5))
        ref = fcfs_scan(t, masks, service, dep0, busy0, backend="ref")
        pal = fcfs_scan(t, masks, service, dep0, busy0, backend="pallas")
        for r, p in zip(ref[:2], pal[:2]):
            np.testing.assert_array_equal(np.asarray(r), np.asarray(p))

    def test_unbatched_shapes(self):
        t, masks, service = _random_workload(jax.random.key(4), 1, 50, 4)
        lat_b, dep_b, _ = fcfs_scan(t, masks, service, backend="ref")
        lat_u, dep_u, _ = fcfs_scan(
            t[0], masks[0], service[0], backend="ref"
        )
        assert lat_u.shape == (50,) and dep_u.shape == (4,)
        np.testing.assert_array_equal(np.asarray(lat_b[0]), np.asarray(lat_u))
        lat_up, _, _ = fcfs_scan(t[0], masks[0], service[0], backend="pallas")
        np.testing.assert_array_equal(
            np.asarray(lat_u), np.asarray(lat_up)
        )

    def test_empty_service_set_is_neg_inf(self):
        """All-false mask row → -inf latency, queue state untouched — the
        convention cache-hit patching relies on."""
        t = jnp.array([1.0, 2.0, 3.0])
        masks = jnp.array([[1, 0], [0, 0], [0, 1]], bool)
        service = jnp.full((3, 2), 0.5)
        lat, dep, _ = fcfs_scan(t, masks, service, backend="ref")
        assert np.asarray(lat)[1] == -np.inf
        lat_p, dep_p, _ = fcfs_scan(t, masks, service, backend="pallas")
        np.testing.assert_array_equal(np.asarray(lat), np.asarray(lat_p))
        np.testing.assert_array_equal(np.asarray(dep), np.asarray(dep_p))

    def test_unknown_backend_raises(self):
        t, masks, service = _random_workload(jax.random.key(5), 1, 8, 2)
        with pytest.raises(ValueError, match="backend"):
            fcfs_scan(t[0], masks[0], service[0], backend="cuda")


class TestBatchingParity:
    @pytest.fixture(scope="class")
    def fabric(self):
        return geo_testbed()

    def test_sequential_vs_vmapped_identical(self, fabric):
        pi = feasible_uniform(jnp.ones((4, fabric.m), bool), K)
        lam_cs = jnp.asarray(
            np.asarray(fabric.uniform_mix(4)).T * 0.1, jnp.float32
        )
        key, n, s = jax.random.key(11), 400, 4
        fleet = simulate_fleet(
            key, pi, lam_cs, fabric, 12.5, n, s, devices="never"
        )
        d, rates = fabric.service_params(12.5)
        keys = jax.random.split(key, s)
        for i in range(s):
            lat, fid, sid, busy, _ = fleet_one_raw(
                keys[i], pi, lam_cs, d, rates, n, n // 10
            )
            np.testing.assert_array_equal(
                np.asarray(fleet.latency[i]), np.asarray(lat)
            )
            np.testing.assert_array_equal(
                np.asarray(fleet.site_id[i]), np.asarray(sid)
            )

    def test_streaming_matches_materialized_same_keys(self, fabric):
        """Streaming accumulators vs the materialized arrays they replace:
        same keys, exact count/histogram, fp32-tight mean, p99 within the
        sketch's documented rank-error bound."""
        from repro.storage import (
            stream_from_values, stream_mean, stream_quantile, stream_reduce,
        )

        pi = feasible_uniform(jnp.ones((4, fabric.m), bool), K)
        lam_cs = jnp.asarray(
            np.asarray(fabric.uniform_mix(4)).T * 0.1, jnp.float32
        )
        key, n, s = jax.random.key(12), 600, 5
        mat = simulate_fleet(
            key, pi, lam_cs, fabric, 12.5, n, s, devices="never"
        )
        st = simulate_fleet(
            key, pi, lam_cs, fabric, 12.5, n, s, devices="never",
            stream=True, keep_latency=True,
        )
        warm = n // 10
        np.testing.assert_array_equal(
            np.asarray(st.latency)[:, warm:], np.asarray(mat.latency)
        )
        lat = np.asarray(mat.latency)
        assert int(np.asarray(st.stream.count).sum()) == lat.size
        np.testing.assert_allclose(
            float(st.mean_latency()), lat.mean(), rtol=1e-5
        )
        # sketch p99 vs exact inverted-CDF p99: within one bucket's growth
        pooled = stream_reduce(st.stream)
        est = float(stream_quantile(pooled, 0.99, st.sketch))
        exact = float(np.quantile(lat, 0.99, method="inverted_cdf"))
        assert exact <= est <= exact * st.sketch.growth * (1 + 1e-6)
        # the accumulators are what the driver folded — identical to an
        # offline fold of the same values
        offline = stream_from_values(jnp.asarray(lat).reshape(-1), st.sketch)
        np.testing.assert_array_equal(
            np.asarray(pooled.hist), np.asarray(offline.hist)
        )

    def test_chunked_horizon_statistically_consistent(self, fabric):
        """10 chunks x n/10 block ≈ one n-length run: same system, so the
        streaming means must agree statistically (different randomness)."""
        pi = feasible_uniform(jnp.ones((4, fabric.m), bool), K)
        lam_cs = jnp.asarray(
            np.asarray(fabric.uniform_mix(4)).T * 0.1, jnp.float32
        )
        one = simulate_fleet(
            jax.random.key(13), pi, lam_cs, fabric, 12.5, 2000, 4,
            devices="never", stream=True,
        )
        chunked = simulate_fleet(
            jax.random.key(14), pi, lam_cs, fabric, 12.5, 200, 4,
            devices="never", stream=True, n_chunks=10,
        )
        assert chunked.windows.count.shape == (4, 10)
        assert int(np.asarray(chunked.stream.count).sum()) == int(
            np.asarray(one.stream.count).sum()
        )
        a, b = float(one.mean_latency()), float(chunked.mean_latency())
        assert abs(a - b) / b < 0.15, (a, b)

    def test_search_methods_give_identical_fleets(self, fabric, monkeypatch):
        """The TPU's compare-and-count and the CPU's binary search find the
        same marks and sketch buckets, so a chunked streaming fleet's
        statistics and windows are bit-identical under either."""
        from repro.storage import streaming

        pi = feasible_uniform(jnp.ones((4, fabric.m), bool), K)
        lam_cs = jnp.asarray(
            np.asarray(fabric.uniform_mix(4)).T * 0.1, jnp.float32
        )
        runs = []
        for on_tpu in (False, True):
            monkeypatch.setattr(streaming, "_on_tpu", lambda: on_tpu)
            jax.clear_caches()  # the method is fixed when a program traces
            out = simulate_fleet(
                jax.random.key(16), pi, lam_cs, fabric, 12.5, 300, 3,
                devices="never", stream=True, n_chunks=4,
            )
            runs.append(jax.tree.map(np.asarray, (out.stream, out.windows)))
        jax.clear_caches()
        for a, b in zip(*(jax.tree.leaves(r) for r in runs)):
            np.testing.assert_array_equal(a, b)
        assert runs[0][1].count.shape == (3, 4)

    def test_streaming_path_materializes_nothing(self, fabric):
        pi = feasible_uniform(jnp.ones((4, fabric.m), bool), K)
        lam_cs = jnp.asarray(
            np.asarray(fabric.uniform_mix(4)).T * 0.1, jnp.float32
        )
        st = simulate_fleet(
            jax.random.key(15), pi, lam_cs, fabric, 12.5, 300, 3,
            devices="never", stream=True,
        )
        assert st.latency is None and st.file_id is None
        assert st.site_id is None and st.hit is None
        assert st.stream is not None and st.windows is not None

    def test_chunked_requires_stream(self, fabric):
        pi = feasible_uniform(jnp.ones((4, fabric.m), bool), K)
        lam_cs = jnp.asarray(
            np.asarray(fabric.uniform_mix(4)).T * 0.1, jnp.float32
        )
        with pytest.raises(ValueError, match="stream=True"):
            simulate_fleet(
                jax.random.key(0), pi, lam_cs, fabric, 12.5, 100, 2,
                n_chunks=4,
            )


@pytest.mark.slow
def test_shard_map_parity_on_8_fake_devices():
    """Sequential vs vmap vs shard_map trajectories on a forced 8-device
    host mesh — the docstring's "no change in semantics" claim, plus the
    two regressions this PR fixes: cached fleets now shard, and odd seed
    counts pad-and-mask instead of dropping to one device. Runs in a
    subprocess because the device count must be set before jax init."""
    prog = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.core import feasible_uniform
        from repro.storage import fleet_one_raw, geo_testbed, simulate_fleet

        assert len(jax.devices()) == 8
        fabric = geo_testbed()
        pi = feasible_uniform(jnp.ones((4, fabric.m), bool), 6)
        lam_cs = jnp.asarray(
            np.asarray(fabric.uniform_mix(4)).T * 0.1, jnp.float32
        )
        key, n = jax.random.key(21), 256
        d, rates = fabric.service_params(12.5)
        ttl = jnp.full((4,), 0.8, jnp.float32)

        for s in (8, 5):  # device multiple AND odd count (pad-and-mask)
            sh = simulate_fleet(key, pi, lam_cs, fabric, 12.5, n, s)
            vm = simulate_fleet(
                key, pi, lam_cs, fabric, 12.5, n, s, devices="never"
            )
            assert sh.latency.shape[0] == s
            np.testing.assert_array_equal(
                np.asarray(sh.latency), np.asarray(vm.latency)
            )
            keys = jax.random.split(key, s)
            for i in range(s):
                lat, _, _, _, _ = fleet_one_raw(
                    keys[i], pi, lam_cs, d, rates, n, n // 10
                )
                np.testing.assert_array_equal(
                    np.asarray(sh.latency[i]), np.asarray(lat)
                )

        # cached fleets shard too (regression: used to bypass shard_map)
        csh = simulate_fleet(
            key, pi, lam_cs, fabric, 12.5, n, 8,
            cache_ttl=ttl, cache_hit_latency=0.003,
        )
        cvm = simulate_fleet(
            key, pi, lam_cs, fabric, 12.5, n, 8, devices="never",
            cache_ttl=ttl, cache_hit_latency=0.003,
        )
        np.testing.assert_array_equal(
            np.asarray(csh.latency), np.asarray(cvm.latency)
        )
        np.testing.assert_array_equal(
            np.asarray(csh.hit), np.asarray(cvm.hit)
        )

        # streaming path: counts/histograms exact across sharding, moments
        # fp32-tight (XLA reduction order differs with the padded batch)
        ssh = simulate_fleet(
            key, pi, lam_cs, fabric, 12.5, 64, 5, stream=True, n_chunks=4,
            cache_ttl=ttl, cache_hit_latency=0.003,
        )
        svm = simulate_fleet(
            key, pi, lam_cs, fabric, 12.5, 64, 5, stream=True, n_chunks=4,
            cache_ttl=ttl, cache_hit_latency=0.003, devices="never",
        )
        np.testing.assert_array_equal(
            np.asarray(ssh.stream.count), np.asarray(svm.stream.count)
        )
        np.testing.assert_array_equal(
            np.asarray(ssh.stream.hist), np.asarray(svm.stream.hist)
        )
        np.testing.assert_array_equal(
            np.asarray(ssh.windows.hist), np.asarray(svm.windows.hist)
        )
        np.testing.assert_allclose(
            np.asarray(ssh.stream.mean), np.asarray(svm.stream.mean),
            rtol=1e-6,
        )
        np.testing.assert_array_equal(
            np.asarray(ssh.hit_count), np.asarray(svm.hit_count)
        )
        print("FLEET_SHARD_PARITY_OK")
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
    assert "FLEET_SHARD_PARITY_OK" in out.stdout, out.stderr[-3000:]
