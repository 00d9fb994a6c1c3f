"""Plain float64 reference for plans over racks, importing nothing of the
program: the rack-capped projection by Dykstra's alternating projections,
rack-cap and one-host-per-rack feasibility, and the Frank-Wolfe gap of a
plan on the rack-capped smoothed JLCM problem.

Racks are laid out rack-major: host j lies in rack j // H of D racks.
The feasible set of a row is {x in [0, 1]^m : sum x = k, x_j = 0 off the
mask, sum_{j in rack d} x_j <= 1 for every rack d}.
"""
from __future__ import annotations

import numpy as np

RHO_MAX = 0.999


def _capped_simplex(v, k, mask, iters: int = 200):
    """Projection of each row onto {[0, 1]^m, sum = k, 0 off the mask}:
    clip(v - tau, 0, 1) with tau found by bisection in float64."""
    lo = np.min(np.where(mask, v, np.inf), axis=-1) - 1.0
    hi = np.max(np.where(mask, v, -np.inf), axis=-1)
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        big = np.where(mask, np.clip(v - mid[:, None], 0.0, 1.0), 0.0).sum(-1) > k
        lo, hi = np.where(big, mid, lo), np.where(big, hi, mid)
    tau = 0.5 * (lo + hi)
    return np.where(mask, np.clip(v - tau[:, None], 0.0, 1.0), 0.0)


def _rack_halfspaces(x, racks: int):
    """Projection onto {sum over each rack <= 1}: a rack over its cap
    gives the excess back evenly over its hosts."""
    r, m = x.shape
    xr = x.reshape(r, racks, m // racks)
    excess = np.maximum(xr.sum(-1, keepdims=True) - 1.0, 0.0)
    return (xr - excess / xr.shape[-1]).reshape(r, m)


def project(v, k, mask, racks: int, iters: int = 20000, tol: float = 1e-13):
    """Rack-capped projection of the rows of ``v`` (r, m), by Dykstra's
    alternating projections between the capped simplex and the rack
    halfspaces; returns the capped-simplex iterate, which meets row sums,
    box and mask exactly and the caps to within ``tol``-sized steps."""
    v = np.asarray(v, np.float64)
    k = np.broadcast_to(np.asarray(k, np.float64), v.shape[:1])
    mask = np.broadcast_to(np.asarray(mask, bool), v.shape)
    x = v.copy()
    p = np.zeros_like(v)
    q = np.zeros_like(v)
    y = x
    for _ in range(iters):
        y = _capped_simplex(x + p, k, mask)
        p = x + p - y
        x_new = _rack_halfspaces(y + q, racks)
        q = y + q - x_new
        if np.max(np.abs(x_new - x)) < tol:
            x = x_new
            break
        x = x_new
    return y


def rack_sums(pi, racks: int):
    pi = np.asarray(pi, np.float64)
    return pi.reshape(pi.shape[:-1] + (racks, pi.shape[-1] // racks)).sum(-1)


def spread_count(pi, racks: int, tol: float) -> int:
    """(row, rack) pairs with more than one host above ``tol``: a stripe
    storing two chunks in one rack."""
    pi = np.asarray(pi, np.float64)
    above = (pi > tol).reshape(pi.shape[:-1] + (racks, pi.shape[-1] // racks))
    return int(np.sum(above.sum(-1) > 1))


def feasibility_error(pi, k, racks: int, down=None) -> float:
    """Largest violation relative to k: row sums off k, entries outside
    [0, 1], a rack over its cap of 1, mass on a host that is ``down``."""
    pi = np.asarray(pi, np.float64)
    k = np.asarray(k, np.float64)
    row = np.max(np.abs(pi.sum(-1) - k) / np.maximum(k, 1.0))
    box = max(0.0, -pi.min(), pi.max() - 1.0)
    cap = max(0.0, float(rack_sums(pi, racks).max()) - 1.0)
    dead = 0.0
    if down is not None and np.any(down):
        dead = float(np.max(pi[..., np.asarray(down, bool)]))
    return float(max(row, box, cap, dead))


def _argmin_z(w, eq, varq, iters: int = 200) -> float:
    scale = eq.max() + np.sqrt(varq.max()) + 1.0
    lo, hi = -64.0 * scale, 4.0 * scale
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        x = eq - mid
        if 1.0 - np.sum(0.5 * w * (1.0 + x / np.sqrt(x * x + varq))) < 0.0:
            lo = mid
        else:
            hi = mid
    return 0.5 * (lo + hi)


def latency_at(rates, lam_hat, mu, m2, m3, z=None):
    """(latency bound, z) at node rates ``rates``: Pollaczek-Khinchin
    sojourn moments, 1 - rho clamped at 1 - 0.999, one shared z."""
    slack = np.maximum(1.0 - rates / mu, 1.0 - RHO_MAX)
    eq = 1.0 / mu + rates * m2 / (2.0 * slack)
    varq = (m2 - 1.0 / mu**2) + rates * m3 / (3.0 * slack) + rates**2 * m2**2 / (
        4.0 * slack**2)
    w = rates / float(lam_hat)
    if z is None:
        z = _argmin_z(w, eq, varq)
    x = eq - z
    return float(z + np.sum(0.5 * w * (x + np.sqrt(x * x + varq)))), z


def fw_gap(pi, lam, k, mu, m2, m3, cost, theta, beta, allowed, racks: int,
           tol: float) -> float:
    """Frank-Wolfe gap of plan ``pi`` on the rack-capped smoothed problem
    (latency bound plus theta x sum V_j log(beta pi + 1) / log(beta)), over
    its latency bound. The linear oracle takes, per row, the best host of
    each rack and then the k best racks, among the hosts of the plan's own
    placement (pi > ``tol``) that ``allowed`` keeps."""
    pi = np.asarray(pi, np.float64)
    lam = np.asarray(lam, np.float64)
    mu, m2, m3, cost = (np.asarray(x, np.float64) for x in (mu, m2, m3, cost))
    lam_hat = lam.sum()
    rates = lam @ pi
    latency, z = latency_at(rates, lam_hat, mu, m2, m3)
    h = 1e-7 * lam_hat
    d_lat = np.empty(rates.shape)
    for j in range(rates.size):
        step = np.zeros(rates.shape)
        step[j] = h
        up = latency_at(rates + step, lam_hat, mu, m2, m3, z)[0]
        dn = latency_at(rates - step, lam_hat, mu, m2, m3, z)[0]
        d_lat[j] = (up - dn) / (2.0 * h)
    grad = lam[:, None] * d_lat[None, :] + float(theta) * cost[None, :] * beta / (
        (beta * pi + 1.0) * np.log(beta))
    cand = (pi > tol) & np.asarray(allowed, bool)[None, :]
    g = np.where(cand, grad, np.inf)
    best = g.reshape(g.shape[0], racks, -1).min(-1)  # (r, D)
    best = np.sort(best, axis=-1)
    kk = np.rint(np.asarray(k, np.float64)).astype(int)
    vertex = np.array([best[i, : kk[i]].sum() for i in range(best.shape[0])])
    return float(np.sum(grad * pi) - vertex.sum()) / latency
