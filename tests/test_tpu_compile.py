"""Ahead-of-time compiles of the main path's kernels for a TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests catch what interpret mode hides:
Mosaic refusing an unaligned or dynamic lane access, a kernel that asks
for more VMEM than a v5e grants, a program that does not fit 16 GB of
HBM. Nothing runs; results are checked by the interpret-mode parity
tests (`test_fleet_parity.py`, `test_kernels.py`).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and a test worker that cannot
skips these tests there instead of failing collection everywhere.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.fcfs_queue import fcfs_scan_pallas
from repro.kernels.gf256_matmul import (
    gf256_matmul_pallas,
    gf256_matmul_pallas_batched,
    select_block_sizes,
)
from repro.kernels.ops import gf256_matmul_batch_bitplane
from repro.storage import encode_batch

HBM_BYTES = 16 * 10**9  # one v5e chip
M = 12  # nodes of the Tahoe testbed
K = 6  # RS data chunks of the paper's objects
CHUNK = 150 * 10**6 // K  # one chunk of a 150 MB object


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip."""
    one_chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A compile for a described chip cannot be read back without one, so
    keep it out of the persistent cache (and the cache's warnings out)."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _fcfs(t, masks, service, dep0, busy0):
    return fcfs_scan_pallas(t, masks, service, dep0, busy0, interpret=False)


@pytest.mark.parametrize(
    "s,n",
    [(1, 20000), (32, 2048)],
    ids=["simulate", "fleet-chunk"],
)
def test_fcfs_kernel_compiles_for_v5e(sds, s, n):
    f32 = jnp.float32
    compiled = (
        jax.jit(_fcfs)
        .lower(
            sds((s, n), f32), sds((s, n, M), jnp.bool_), sds((s, n, M), f32),
            sds((s, M), f32), sds((s, M), f32),
        )
        .compile()
    )
    assert _has_kernel(compiled)


def test_fcfs_kernel_compiles_under_rollout_vmap(sds):
    """The arbitration's (candidate, seed) lanes vmap a single-system scan."""
    f32 = jnp.float32
    b, k, n = 8, 2, 600

    def one(t, masks, service, dep0):
        lat, _, _ = _fcfs(
            t[None], masks[None], service[None], dep0[None], jnp.zeros_like(dep0)[None]
        )
        return lat[0]

    compiled = (
        jax.jit(jax.vmap(jax.vmap(one)))
        .lower(
            sds((b, k, n), f32), sds((b, k, n, M), jnp.bool_),
            sds((b, k, n, M), f32), sds((b, k, M), f32),
        )
        .compile()
    )
    assert _has_kernel(compiled)


RACK_M = 210  # hosts of the f4 cell: 14 racks of 15


@pytest.mark.parametrize("b,n", [(1, 2000), (2, 600)], ids=["segment", "rollout"])
def test_fcfs_kernel_compiles_for_a_rack_cell(sds, b, n):
    """210 node rows on sublanes: a segment of the closed loop and the
    arbitration's two candidate lanes fit the kernel's VMEM budget."""
    f32 = jnp.float32

    def one(t, masks, service, dep0):
        lat, _, _ = _fcfs(
            t[None], masks[None], service[None], dep0[None], jnp.zeros_like(dep0)[None]
        )
        return lat[0]

    compiled = (
        jax.jit(jax.vmap(one))
        .lower(
            sds((b, n), f32), sds((b, n, RACK_M), jnp.bool_),
            sds((b, n, RACK_M), f32), sds((b, RACK_M), f32),
        )
        .compile()
    )
    assert _has_kernel(compiled)


def _batched_solve(sds, cluster, r, racks):
    """The replan's batched solve, two candidate lanes of an (r, m) plan,
    compiled for one v5e."""
    import numpy as np

    from repro.core import JLCMProblem, stack_problems
    from repro.core.jlcm import _solve_merged_device_batch

    prob = JLCMProblem(
        lam=jnp.ones(r), k=jnp.full(r, 10.0 if racks else float(K)),
        moments=cluster.moments(4.194304), cost=cluster.cost, theta=2e-5,
        mask=jnp.ones((r, cluster.m), bool), domain=cluster.domain,
    )
    st = stack_problems([prob, prob])
    shaped = jax.tree.map(
        lambda x: sds(jnp.shape(x), jnp.asarray(x).dtype),
        (jnp.zeros((2, r, cluster.m)), st._replace(mask=None, domain=None), st.mask,
         np.float32(1e3), np.float32(0.1), np.float32(1e-5)),
    )
    return _solve_merged_device_batch.lower(*shaped, max_iters=400, racks=racks).compile()


def test_rack_capped_batched_solve_fits_v5e(sds):
    """The f4 cell's replan solve: two candidate lanes of a 1000 x 210 plan
    with the rack caps of 14 racks, as one program, its rack-capped
    projections the VMEM-resident kernel."""
    from repro.storage import Cluster, StorageNode

    cluster = Cluster(tuple(
        StorageNode(f"r{d}h{h}", "cell", 0.012, 120.0, 1.0, rack=d)
        for d in range(14) for h in range(15)
    ))
    compiled = _batched_solve(sds, cluster, 1000, 14)
    assert _has_kernel(compiled)
    assert "rack_project" in compiled.as_text()
    ma = compiled.memory_analysis()
    total = ma.temp_size_in_bytes + ma.argument_size_in_bytes + ma.output_size_in_bytes
    assert total < HBM_BYTES, total


@pytest.mark.parametrize(
    "racks,kernel", [(14, True), (28, False)], ids=["f4-cell", "too-wide-for-vmem"]
)
def test_rack_projection_takes_the_kernel_where_it_fits(sds, racks, kernel):
    """Racks of 15 hosts over 1000 rows: 14 racks run the VMEM-resident
    kernel; 28 (420 hosts) would overflow its row block and stay in XLA."""
    from repro.core import project_capped_simplex

    r, m = 1000, racks * 15
    compiled = (
        jax.jit(lambda v, k, mask: project_capped_simplex(v, k, mask, racks=racks))
        .lower(sds((r, m), jnp.float32), sds((r,), jnp.float32), sds((r, m), jnp.bool_))
        .compile()
    )
    assert _has_kernel(compiled) == kernel


def test_plain_batched_solve_runs_no_kernel(sds):
    """Tahoe's replan solve (no racks) keeps the plain XLA projection: the
    rack kernel is bypassed."""
    from repro.storage import tahoe_testbed

    compiled = _batched_solve(sds, tahoe_testbed(), 1000, None)
    assert not _has_kernel(compiled)


def test_gf256_encode_kernel_compiles_for_v5e(sds):
    """Parity rows of one object: (n-k, k) @GF (k, chunk bytes)."""
    u8 = jnp.uint8
    bm, bn, bk = select_block_sizes(3, CHUNK, K)
    f = lambda a, b: gf256_matmul_pallas(
        a, b, block_m=bm, block_n=bn, block_k=bk, interpret=False
    )
    compiled = jax.jit(f).lower(sds((3, K), u8), sds((K, CHUNK), u8)).compile()
    assert _has_kernel(compiled)


def test_gf256_batched_decode_kernel_compiles_for_v5e(sds):
    """Degraded reads of a few objects: (B, k, k) @GF (B, k, chunk bytes)."""
    u8 = jnp.uint8
    b = 4
    f = lambda a, c: gf256_matmul_pallas_batched(a, c, interpret=False)
    compiled = jax.jit(f).lower(sds((b, K, K), u8), sds((b, K, CHUNK), u8)).compile()
    assert _has_kernel(compiled)


def test_bitplane_decode_of_one_object_fits_v5e(sds):
    u8 = jnp.uint8
    compiled = (
        jax.jit(gf256_matmul_batch_bitplane)
        .lower(sds((1, K, K), u8), sds((1, K, CHUNK), u8))
        .compile()
    )
    ma = compiled.memory_analysis()
    total = ma.temp_size_in_bytes + ma.argument_size_in_bytes + ma.output_size_in_bytes
    assert total < HBM_BYTES, total


def test_bitplane_encode_of_objects_fits_v5e(sds):
    """Parity of several objects at once: one batched matmul, no transpose
    of the batch into the byte axis (that relayout stalled the compiler)."""
    b = 4
    compiled = (
        jax.jit(lambda d: encode_batch(d, 2 * K, backend="bitplane"))
        .lower(sds((b, K, CHUNK), jnp.uint8))
        .compile()
    )
    ma = compiled.memory_analysis()
    total = ma.temp_size_in_bytes + ma.argument_size_in_bytes + ma.output_size_in_bytes
    assert total < HBM_BYTES, total
