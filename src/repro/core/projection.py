"""Euclidean projection onto the capped simplex (paper's feasibility set).

The probabilistic-scheduling polytope for file i (Theorem 1) is

  P_i = { x in [0,1]^m : sum_j x_j = k_i, x_j = 0 for j not in S_i }.

Projection of v onto P_i is x = clip(v - tau, 0, 1) on the allowed support,
where tau solves g(tau) = sum_j clip(v_j - tau, 0, 1) = k_i. g is
nonincreasing and piecewise-linear; we solve by bisection, vectorized over
files and jit/vmap-friendly (used inside the projected-gradient loop of
Algorithm JLCM).

With failure domains (racks, laid out rack-major: host j lies in rack
j // H, every rack H hosts) a read takes at most one chunk from a rack, so
the set gains the caps sum_{j in rack d} x_j <= 1. The KKT conditions give
x_j = clip(v_j - tau - mu_d, 0, 1) with mu_d >= 0 nonzero only where rack
d's cap binds. For a fixed tau rack d's mass is min(1, h_d(tau)), h_d(s) =
sum_{j in d} clip(v_j - s, 0, 1), so the rack's threshold is max(tau,
s_d) with s_d the root of h_d(s) = 1, which does not depend on tau: one
bisection for every s_d, then one on tau over the rows, one after the
other.

Which path runs where: the plain projection (``racks=None``) is XLA on
every platform. The rack-capped one is the VMEM-resident Pallas kernel
``rack_project`` (`kernels/rack_projection.py`) when the program is
compiled for a TPU, and ``_project_racks_impl`` in XLA elsewhere, where it
is also the kernel's oracle; a plan too wide for the kernel's row block
takes the XLA path everywhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array


def project_capped_simplex(
    v: Array,
    k: Array,
    mask: Array | None = None,
    *,
    iters: int = 60,
    racks: int | None = None,
) -> Array:
    """Project rows of ``v`` (..., r, m) onto {x in [0,1]^m, sum x = k_row}.

    ``mask`` (..., r, m) restricts support: masked-out entries are pinned to
    0 (chunk placement constraint pi_ij = 0 for j not in S_i). ``k`` may be
    a scalar or (..., r) array; requires k <= #allowed per row. Batch-safe:
    all reductions are over the last axis only, so stacked problem batches
    (and `vmap`) work unchanged — `solve_batch` relies on this.

    Eager callers (``solve``'s pi0 projection, the replanner, baselines) go
    through a module-level ``jax.jit`` wrapper: an un-jitted call would
    dispatch the bisection ``fori_loop`` as a fresh one-off XLA program on
    every invocation (the eager control-flow cache keys on jaxpr identity),
    recompiling ~150 ms per call — which used to dominate every ``solve``.
    Traced callers (inside the merged loop) inline it as before.

    ``racks`` (the number of racks of a rack-major layout, see
    :func:`rack_count`) adds the caps of one unit of mass per rack and
    row; it requires k <= the number of racks with an allowed host.
    ``None`` is the capped simplex alone.
    """
    if racks is None:
        return _project_impl(v, k, mask, iters=iters)
    return _project_racks(v, k, mask, iters=iters, racks=racks)


@functools.partial(jax.jit, static_argnames=("iters",))
def _project_impl(
    v: Array,
    k: Array,
    mask: Array | None,
    *,
    iters: int,
) -> Array:
    v = jnp.asarray(v)
    k = jnp.broadcast_to(jnp.asarray(k, v.dtype), v.shape[:-1])
    if mask is None:
        mask = jnp.ones_like(v, dtype=bool)
    else:
        mask = jnp.broadcast_to(jnp.asarray(mask, bool), v.shape)

    neg = jnp.asarray(jnp.finfo(v.dtype).min, v.dtype)
    vm = jnp.where(mask, v, neg)

    lo = jnp.min(jnp.where(mask, v, jnp.inf), axis=-1) - 1.0  # g(lo) = #allowed >= k
    hi = jnp.max(jnp.where(mask, v, -jnp.inf), axis=-1)  # g(hi) = 0 <= k

    def g(tau):
        x = jnp.clip(vm - tau[..., None], 0.0, 1.0)
        return jnp.sum(jnp.where(mask, x, 0.0), axis=-1)

    def step(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        too_big = g(mid) > k  # need larger tau
        lo = jnp.where(too_big, mid, lo)
        hi = jnp.where(too_big, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, step, (lo, hi))
    tau = 0.5 * (lo + hi)
    x = jnp.clip(vm - tau[..., None], 0.0, 1.0)
    return jnp.where(mask, x, 0.0)


@functools.partial(jax.jit, static_argnames=("iters", "racks"))
def _project_racks(
    v: Array,
    k: Array,
    mask: Array | None,
    *,
    iters: int,
    racks: int,
) -> Array:
    """The rack-capped projection on the platform the program is compiled
    for: the VMEM-resident kernel on a TPU, ``_project_racks_impl``
    elsewhere and for plans whose row block would not fit VMEM."""
    from repro.kernels import rack_projection  # repro.kernels imports storage

    v = jnp.asarray(v)
    k = jnp.broadcast_to(jnp.asarray(k, v.dtype), v.shape[:-1])
    if mask is None:
        mask = jnp.ones_like(v, dtype=bool)
    else:
        mask = jnp.broadcast_to(jnp.asarray(mask, bool), v.shape)
    xla = functools.partial(_project_racks_impl, iters=iters, racks=racks)
    if not rack_projection.fits(v.shape[-1]):
        return xla(v, k, mask)
    kernel = functools.partial(rack_projection.rack_project_pallas, iters=iters, racks=racks)
    return jax.lax.platform_dependent(v, k, mask, tpu=kernel, default=xla)


@functools.partial(jax.jit, static_argnames=("iters", "racks"))
def _project_racks_impl(
    v: Array,
    k: Array,
    mask: Array | None,
    *,
    iters: int,
    racks: int,
) -> Array:
    v = jnp.asarray(v)
    k = jnp.broadcast_to(jnp.asarray(k, v.dtype), v.shape[:-1])
    if mask is None:
        mask = jnp.ones_like(v, dtype=bool)
    else:
        mask = jnp.broadcast_to(jnp.asarray(mask, bool), v.shape)
    shape = v.shape[:-1] + (racks, v.shape[-1] // racks)
    mr = mask.reshape(shape)
    neg = jnp.asarray(jnp.finfo(v.dtype).min, v.dtype)
    vr = jnp.where(mask, v, neg).reshape(shape)

    # s_d: root of h_d(s) = 1 on [min_d v - 1, max_d v] (h_d = #allowed
    # there, then 0); a rack with no allowed host keeps a finite 0
    has = jnp.any(mr, axis=-1)
    lo_d = jnp.where(has, jnp.min(jnp.where(mr, vr, jnp.inf), axis=-1) - 1.0, 0.0)
    hi_d = jnp.where(has, jnp.max(jnp.where(mr, vr, -jnp.inf), axis=-1), 0.0)

    def rack_step(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        x = jnp.clip(vr - mid[..., None], 0.0, 1.0)
        too_big = jnp.sum(jnp.where(mr, x, 0.0), axis=-1) > 1.0
        return jnp.where(too_big, mid, lo), jnp.where(too_big, hi, mid)

    lo_d, hi_d = jax.lax.fori_loop(0, iters, rack_step, (lo_d, hi_d))
    s_d = 0.5 * (lo_d + hi_d)

    def place(tau):
        thr = jnp.maximum(tau[..., None], s_d)[..., None]
        return jnp.where(mr, jnp.clip(vr - thr, 0.0, 1.0), 0.0)

    # tau on [min v - 1, max v]: every rack with an allowed host is full at
    # the bottom (s_d >= its min v - 1), nothing is placed at the top
    lo = jnp.min(jnp.where(mask, v, jnp.inf), axis=-1) - 1.0
    hi = jnp.max(jnp.where(mask, v, -jnp.inf), axis=-1)

    def step(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        too_big = jnp.sum(place(mid), axis=(-2, -1)) > k
        return jnp.where(too_big, mid, lo), jnp.where(too_big, hi, mid)

    lo, hi = jax.lax.fori_loop(0, iters, step, (lo, hi))
    return place(0.5 * (lo + hi)).reshape(v.shape)


def rack_count(domain, m: int) -> int | None:
    """The number of racks D of a rack-major failure-domain index.

    ``domain`` is (m,) (or stacked (..., m), every row the same) with
    ``domain[j]`` the rack of host j; the caps reshape a plan to
    (..., r, D, H), so racks must be equal in size and host j must lie in
    rack j // H. ``None`` (no failure domains) gives ``None``. A concrete
    array only: the layout sets the shapes of the compiled program.
    """
    if domain is None:
        return None
    dom = np.asarray(domain)
    if dom.shape[-1] != m or dom.size == 0:
        raise ValueError(f"domain shape {dom.shape} does not end in m = {m}")
    racks = int(dom.max()) + 1
    want = np.arange(m) // max(m // racks, 1)
    if m % racks or not np.array_equal(dom, np.broadcast_to(want, dom.shape)):
        raise ValueError(
            "failure domains must be equal racks laid out rack-major "
            f"(host j in rack j // H); got {dom.reshape(-1, m)[0].tolist()}"
        )
    return racks


def feasible_uniform(mask: Array, k: Array, racks: int | None = None) -> Array:
    """A strictly feasible interior start: pi_ij = k_i / |S_i| on support.

    With ``racks`` every rack that holds an allowed host gets mass k_i / D_i
    (D_i such racks), spread evenly over its allowed hosts, so the start
    meets the caps (k_i <= D_i)."""
    mask = jnp.asarray(mask, bool)
    k = jnp.asarray(k, jnp.float32)
    if racks is not None:
        mr = mask.reshape(mask.shape[:-1] + (racks, mask.shape[-1] // racks))
        per_rack = jnp.sum(mr, axis=-1).astype(jnp.float32)
        n_racks = jnp.sum(per_rack > 0, axis=-1).astype(jnp.float32)
        val = (k / n_racks)[..., None, None] / jnp.maximum(per_rack, 1.0)[..., None]
        return jnp.where(mr, jnp.minimum(val, 1.0), 0.0).reshape(mask.shape)
    n_allowed = jnp.sum(mask, axis=-1).astype(jnp.float32)
    val = (k / n_allowed)[..., None]
    return jnp.where(mask, jnp.minimum(val, 1.0), 0.0)


def round_racks(
    pi: Array, mask: Array, racks: int, tol: float, lam: Array
) -> tuple[Array, Array, Array]:
    """One host per rack: each (row, rack)'s mass moves onto one of its
    hosts, the rack's other hosts go to 0.

    Row sums are kept, entries stay in [0, 1] (a rack's mass is at most
    1), and no mass lands on a host ``mask`` leaves out. A rack that holds
    a row's mass on one host above ``tol`` keeps it there. The other rows
    are dealt out over the rack's hosts so that each host keeps the load
    it had: in row order, row i's load on the rack, ``lam_i`` times its
    mass there, takes the next stretch of the rack's load, and goes to the
    host whose share of that load (what the plan sent it, less the rows it
    keeps) covers the stretch's middle. Each host's load then moves by less
    than one row's, so no host is overloaded by the round. Taking a largest
    entry, or drawing one, piles the rows of alike hosts onto a few of
    them. Returns ``(pi, placement, merged)``: the rounded plan, its
    support as a mask, and the (row, rack) pairs that held more than one
    entry above ``tol``.
    """
    shape = pi.shape[:-1] + (racks, pi.shape[-1] // racks)
    p = pi.reshape(shape)
    mr = jnp.asarray(mask, bool).reshape(shape)
    lam = jnp.asarray(lam, pi.dtype)[..., :, None]  # (..., r, 1)
    above = mr & (p > tol)
    merged = jnp.sum(jnp.sum(above, axis=-1) > 1)
    mass = jnp.sum(p, axis=-1)  # (..., r, D)
    own = jnp.argmax(jnp.where(mr, p, -1.0), axis=-1)  # the row's largest allowed host
    single = jnp.sum(above, axis=-1) == 1
    # per host: the load the plan sends it and the part of it rows keep
    load = jnp.sum(lam[..., None] * p, axis=-3)  # (..., D, H)
    kept = jnp.sum(
        jnp.where(single[..., None] & above, (lam * mass)[..., None], 0.0), axis=-3
    )
    share = jnp.cumsum(jnp.maximum(load - kept, 0.0), axis=-1)
    w = jnp.where(single, 0.0, lam * mass)
    total = jnp.sum(w, axis=-2)  # (..., D)
    mid = jnp.cumsum(w, axis=-2) - 0.5 * w
    edge = share * (total / jnp.maximum(share[..., -1], 1e-30))[..., None]
    dealt = jnp.sum(edge[..., None, :, :] < mid[..., None], axis=-1)
    chosen = jnp.where(single, own, jnp.minimum(dealt, shape[-1] - 1))
    ok = jnp.take_along_axis(mr, chosen[..., None], axis=-1)[..., 0]
    chosen = jnp.where(ok, chosen, own)
    keep = (jnp.arange(shape[-1]) == chosen[..., None]) & mr & (mass[..., None] > 0.0)
    out = jnp.where(keep, jnp.minimum(mass, 1.0)[..., None], 0.0)
    return out.reshape(pi.shape), keep.reshape(pi.shape), merged
