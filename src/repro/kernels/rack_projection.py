"""Pallas TPU kernel: the rack-capped projection with its rows in VMEM.

The rack-capped projection (`core/projection.py`) bisects every rack's
threshold s_d (60 steps), then every row's tau (60 steps). Each step of
either bisection re-reads the whole plan, so as XLA fusions the 120 steps
are 120 round trips of the (lanes, r, m) plan through HBM, each doing a few
VPU operations an element. Rows are independent and both bisections are
row-local, so this kernel loads a block of rows (plan and mask) into VMEM
once, runs both bisections there, and writes the projected block once.

Layout: hosts and racks on the leading (untiled) axes, rows on sublanes
and lanes, ``(H, D, rows / 128, 128)`` for a plan of D racks of H hosts
(host j = d * H + h). Every (host, rack) pair of a block of 1024 rows is
then one (8, 128) vreg: the racks' masses are a sum over H of (D, 8, 128)
values, ``max(tau, s_d)`` a vreg maximum, a row's mass a sum over the
leading rack axis, and no step needs a cross-lane or cross-sublane
reduction. The kernel's code is vectorized over racks, so its trace and
lowering grow with H, not with D * H. Rows are padded to the block and sliced away; a padded row has
no allowed host and projects to 0.

The arithmetic is ``_project_racks_impl``'s, in float32: the same bounds,
``iters`` steps of ``mid = 0.5 * (lo + hi)`` on each loop, the same ``> 1``
and ``> k`` tests, no early exit. Only the order of the sums differs (a
rack's hosts in turn, then the racks in turn), so entries may differ from
XLA's by float32 ulps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import Array
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
# plan, mask and output blocks, each double-buffered; well inside the
# 16 MiB of scoped VMEM a v5e kernel gets by default
_VMEM_BUDGET = 8 * 2**20


def fits(m: int) -> bool:
    """Whether a block of 1024 rows of ``m`` hosts fits the VMEM budget."""
    return 2 * 3 * m * _SUBLANES * _LANES * 4 <= _VMEM_BUDGET


def _clip01(x):
    return jnp.minimum(jnp.maximum(x, 0.0), 1.0)


def _rack_project_kernel(k_ref, v_ref, m_ref, o_ref, *, iters: int):
    """One block of rows. Values are (D, rows / 128, 128): one vreg a rack
    for a block of 1024 rows. ``o_ref`` first holds the plan with masked
    hosts at float32's lowest value (their clipped mass is then 0 at any
    finite threshold), which both bisections read, then the projection."""
    hosts = v_ref.shape[0]
    neg = jnp.finfo(jnp.float32).min
    ok = m_ref[0] != 0
    v = v_ref[0]
    o_ref[0] = jnp.where(ok, v, neg)
    has, vmin, vmax = ok, jnp.where(ok, v, jnp.inf), jnp.where(ok, v, -jnp.inf)
    for h in range(1, hosts):
        ok = m_ref[h] != 0
        v = v_ref[h]
        o_ref[h] = jnp.where(ok, v, neg)
        has = has | ok
        vmin = jnp.minimum(vmin, jnp.where(ok, v, jnp.inf))
        vmax = jnp.maximum(vmax, jnp.where(ok, v, -jnp.inf))

    def mass(thr):  # each rack's clipped mass above its threshold
        acc = _clip01(o_ref[0] - thr)
        for h in range(1, hosts):
            acc = acc + _clip01(o_ref[h] - thr)
        return acc

    # s_d: root of h_d(s) = 1 on [min_d v - 1, max_d v]; a rack with no
    # allowed host keeps a finite 0
    def rack_step(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        too_big = mass(mid) > 1.0
        return jnp.where(too_big, mid, lo), jnp.where(too_big, hi, mid)

    start = (jnp.where(has, vmin - 1.0, 0.0), jnp.where(has, vmax, 0.0))
    lo_d, hi_d = jax.lax.fori_loop(0, iters, rack_step, start)
    s_d = 0.5 * (lo_d + hi_d)

    # tau on [min v - 1, max v] over the row's allowed hosts
    lo = jnp.min(vmin, axis=0) - 1.0
    hi = jnp.max(vmax, axis=0)
    k = k_ref[...]

    def step(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        too_big = jnp.sum(mass(jnp.maximum(mid, s_d)), axis=0) > k
        return jnp.where(too_big, mid, lo), jnp.where(too_big, hi, mid)

    lo, hi = jax.lax.fori_loop(0, iters, step, (lo, hi))
    thr = jnp.maximum(0.5 * (lo + hi), s_d)
    for h in range(hosts):
        o_ref[h] = jnp.where(m_ref[h] != 0, _clip01(o_ref[h] - thr), 0.0)


@functools.partial(jax.jit, static_argnames=("racks", "iters", "interpret"))
def rack_project_pallas(
    v: Array,
    k: Array,
    mask: Array,
    *,
    racks: int,
    iters: int = 60,
    interpret: bool = False,
) -> Array:
    """Rack-capped projection of the rows of ``v`` (..., r, m), racks laid
    out rack-major, in one kernel launch. ``k`` is (..., r) and ``mask``
    (..., r, m) boolean, both already broadcast to ``v``'s rows. The grid
    runs over the leading axes and blocks of up to 1024 rows."""
    lead, (r, m) = v.shape[:-2], v.shape[-2:]
    hosts = m // racks
    v = jnp.asarray(v, jnp.float32).reshape(-1, r, racks, hosts)
    b = v.shape[0]
    k = jnp.asarray(k, jnp.float32).reshape(b, r)
    mask = jnp.asarray(mask, jnp.int32).reshape(b, r, racks, hosts)
    sub = min(_SUBLANES, -(-r // _LANES))
    rows = sub * _LANES
    rp = -(-r // rows) * rows

    def lay(x):  # (b, r, D, H) -> (b, H, D, rp / 128, 128)
        x = jnp.pad(x.transpose(0, 3, 2, 1), ((0, 0), (0, 0), (0, 0), (0, rp - r)))
        return x.reshape(b, hosts, racks, rp // _LANES, _LANES)

    plan = pl.BlockSpec((None, hosts, racks, sub, _LANES), lambda i, j: (i, 0, 0, j, 0))
    out = pl.pallas_call(
        functools.partial(_rack_project_kernel, iters=iters),
        grid=(b, rp // rows),
        in_specs=[
            pl.BlockSpec((None, sub, _LANES), lambda i, j: (i, j, 0)),
            plan,
            plan,
        ],
        out_specs=plan,
        out_shape=jax.ShapeDtypeStruct((b, hosts, racks, rp // _LANES, _LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="rack_project",
    )(
        jnp.pad(k, ((0, 0), (0, rp - r))).reshape(b, rp // _LANES, _LANES),
        lay(v),
        lay(mask),
    )
    out = out.reshape(b, hosts, racks, rp)[..., :r].transpose(0, 3, 2, 1)
    return out.reshape(lead + (r, m))
