"""Pallas TPU kernel: fused FCFS queue scan for the fleet simulator.

The exact discrete-event simulation of probabilistic scheduling
(`storage/simulator.py`) reduces every path — single run, segment,
geo segment, fleet — to ONE sequential recurrence over the merged
arrival stream:

    start_j  = max(t_req, dep_j)          (FCFS, work-conserving)
    finish_j = start_j + service_j
    dep_j   <- finish_j   where node j served this request
    latency  = max_{j in service set} finish_j - t_req
    busy_j  += service_j  where node j served this request

The recurrence is inherently sequential in the request axis but embar-
rassingly parallel in the *fleet* axis (independent seeds), so the hot
loop's natural unit is an (S, m)-wide step: S seeds x m nodes per
request index. As a ``lax.scan`` this is memory-bound — every step
round-trips the (S, m) carry plus an (S, m) slice of the mask/service
streams through HBM with no fusion across steps. The Pallas backend
keeps the carry VMEM-resident for a block of up to 128 seeds (one per
lane, nodes on sublanes) and walks the request axis in ONE kernel
launch: a grid axis over request blocks, a ``fori_loop`` within each,
so every access is a whole-tile load at a leading index (Mosaic has no
dynamic single-lane access) and VMEM does not grow with the horizon.

Two interchangeable backends (same contract as `kernels/ops.py`):

  * ``ref``    — ``lax.scan`` over requests (vmapped over seeds). The
                 semantics anchor: bit-identical to the scans the
                 simulator has always run.
  * ``pallas`` — the fused kernel above (interpret-mode on CPU).

``backend="auto"`` picks ``pallas`` on TPU and ``ref`` elsewhere.
Parity over randomized (t, mask, service) workloads — including
all-false masks (cache hits) and carried-in queue state — is asserted
by ``tests/test_fleet_parity.py``.

Conventions shared with the simulator:

  * A request whose service set is empty (all-false mask row, e.g. a
    cache hit thinned before dispatch) gets latency ``-inf`` — callers
    patch it (``jnp.where(hit, hit_latency, latency)``) downstream.
  * ``busy`` accrues in the carry (an (S, m) add per step) instead of
    being emitted per step: an (N, m) stacked output would dominate the
    whole scan in memory traffic at fleet widths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import Array
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _step(dep, busy, t, mask, srv, axis=-1):
    """One FCFS update over the node ``axis`` of (..., m)-shaped state;
    ``t`` has size 1 on that axis, and so has the returned latency. The op
    sequence is shared verbatim by both backends so they agree bit-for-bit."""
    start = jnp.maximum(t, dep)
    finish = start + srv
    new_dep = jnp.where(mask, finish, dep)
    latency = jnp.max(jnp.where(mask, finish, -jnp.inf), axis=axis, keepdims=True) - t
    new_busy = busy + jnp.where(mask, srv, 0.0)
    return new_dep, new_busy, latency


def _fcfs_scan_ref_one(
    t: Array, masks: Array, service: Array, dep0: Array, busy0: Array
) -> tuple[Array, Array, Array]:
    """Single-system ref backend: the simulator's historical ``lax.scan``."""

    def step(carry, inp):
        dep, busy = carry
        tt, mask, srv = inp
        new_dep, new_busy, latency = _step(dep, busy, tt[None], mask, srv)
        return (new_dep, new_busy), latency[0]

    (dep, busy), latency = jax.lax.scan(
        step, (dep0, busy0), (t, masks, service)
    )
    return latency, dep, busy


def _fcfs_kernel(t_ref, m_ref, s_ref, d0_ref, b0_ref, lat_ref, dep_ref, busy_ref):
    """Grid (seed block, request block). Nodes lie on sublanes and seeds on
    lanes, so request ``i`` of the block is the leading index of every
    stream: a (1, S) arrival row and (m, S) mask/service tiles. The queue
    state lives in the dep/busy output blocks, which stay VMEM-resident
    across the request axis (their block index ignores it)."""

    @pl.when(pl.program_id(1) == 0)
    def _init():
        dep_ref[...] = d0_ref[...]
        busy_ref[...] = b0_ref[...]

    def body(i, carry):
        dep, busy = carry
        new_dep, new_busy, lat = _step(
            dep, busy, t_ref[i], m_ref[i] != 0, s_ref[i], axis=0
        )
        lat_ref[i] = lat
        return new_dep, new_busy

    dep, busy = jax.lax.fori_loop(
        0, t_ref.shape[0], body, (dep_ref[...], busy_ref[...])
    )
    dep_ref[...] = dep
    busy_ref[...] = busy


_LANES = 128
# double-buffered request streams of one grid step; well inside the 16 MiB
# of scoped VMEM a v5e kernel gets by default
_VMEM_BUDGET = 8 * 2**20


def _block_requests(n: int, m: int, lanes: int) -> int:
    """Requests per grid step: as many as the VMEM budget holds, evened out
    so the padded horizon wastes less than one step per block."""
    rows = 2 * 8 + 2 * (-(-m // 8) * 8)  # t + latency, mask + service
    per_request = 2 * 4 * rows * (-(-lanes // _LANES) * _LANES)
    cap = max(8, _VMEM_BUDGET // per_request)
    blocks = -(-n // cap)
    return -(-n // blocks)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fcfs_scan_pallas(
    t: Array,
    masks: Array,
    service: Array,
    dep0: Array,
    busy0: Array,
    *,
    interpret: bool = False,
) -> tuple[Array, Array, Array]:
    """Fused FCFS scan over a seed batch in one kernel launch.

    Shapes: ``t`` (S, N), ``masks`` (S, N, m) bool/int, ``service``
    (S, N, m), ``dep0``/``busy0`` (S, m). Returns ``(latency (S, N),
    dep (S, m), busy (S, m))``. Inside, the streams are laid out request-
    major with seeds on lanes ((N, m, S)); seeds beyond 128 are padded to
    128-lane blocks, and the request axis is cut into blocks sized by
    :func:`_block_requests`, so VMEM does not grow with the horizon.
    Padded requests carry an empty mask and leave the queues untouched.
    """
    t = jnp.asarray(t, jnp.float32)
    service = jnp.asarray(service, jnp.float32)
    masks = jnp.asarray(masks, jnp.int32)
    dep0 = jnp.asarray(dep0, jnp.float32)
    busy0 = jnp.asarray(busy0, jnp.float32)
    s, n = t.shape
    m = service.shape[-1]
    sl = s if s <= _LANES else _LANES
    sp = -(-s // sl) * sl
    bn = _block_requests(n, m, sl)
    np_ = -(-n // bn) * bn
    pad_s, pad_n = sp - s, np_ - n
    t_k = jnp.pad(t.T, ((0, pad_n), (0, pad_s)))[:, None, :]  # (N, 1, S)
    m_k = jnp.pad(masks.transpose(1, 2, 0), ((0, pad_n), (0, 0), (0, pad_s)))
    s_k = jnp.pad(service.transpose(1, 2, 0), ((0, pad_n), (0, 0), (0, pad_s)))
    d_k = jnp.pad(dep0.T, ((0, 0), (0, pad_s)))  # (m, S)
    b_k = jnp.pad(busy0.T, ((0, 0), (0, pad_s)))
    req = lambda rows: pl.BlockSpec((bn, rows, sl), lambda i, j: (j, 0, i))
    state = pl.BlockSpec((m, sl), lambda i, j: (0, i))
    latency, dep, busy = pl.pallas_call(
        _fcfs_kernel,
        grid=(sp // sl, np_ // bn),
        in_specs=[req(1), req(m), req(m), state, state],
        out_specs=[req(1), state, state],
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1, sp), jnp.float32),
            jax.ShapeDtypeStruct((m, sp), jnp.float32),
            jax.ShapeDtypeStruct((m, sp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(t_k, m_k, s_k, d_k, b_k)
    return latency[:n, 0, :s].T, dep[:, :s].T, busy[:, :s].T


def fcfs_scan(
    t: Array,
    masks: Array,
    service: Array,
    dep0: Array | None = None,
    busy0: Array | None = None,
    *,
    backend: str = "auto",
) -> tuple[Array, Array, Array]:
    """Dispatching FCFS queue scan; ref/pallas agree bit-for-bit.

    Accepts a single system (``t`` (N,), ``masks``/``service`` (N, m),
    carries (m,)) or a seed batch (leading (S,) axis on everything).
    ``dep0``/``busy0`` default to idle queues / zero accrued busy time.
    Returns ``(latency, dep, busy)`` with the same leading axes.
    """
    t = jnp.asarray(t)
    masks_b = jnp.asarray(masks, bool)
    service = jnp.asarray(service)
    m = service.shape[-1]
    batched = t.ndim == 2
    cshape = t.shape[:-1] + (m,)
    dep0 = jnp.zeros(cshape) if dep0 is None else jnp.asarray(dep0)
    busy0 = jnp.zeros(cshape) if busy0 is None else jnp.asarray(busy0)
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "ref"
    if backend == "ref":
        fn = _fcfs_scan_ref_one
        if batched:
            fn = jax.vmap(fn)
        return fn(t, masks_b, service, dep0, busy0)
    if backend == "pallas":
        if not batched:
            lat, dep, busy = fcfs_scan_pallas(
                t[None], masks_b[None], service[None], dep0[None], busy0[None],
                interpret=not _on_tpu(),
            )
            return lat[0], dep[0], busy[0]
        return fcfs_scan_pallas(
            t, masks_b, service, dep0, busy0, interpret=not _on_tpu()
        )
    raise ValueError(f"unknown backend {backend!r}")
