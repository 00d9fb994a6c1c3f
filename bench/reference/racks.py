"""Plain reference for plans over racks, in float64 NumPy.

Racks are laid out rack-major: host j lies in rack j // H of D racks, the
layout of the configuration's cell. A plan row's feasible set is

    {x in [0, 1]^m : sum x = k, x_j = 0 on a host that is down,
     sum_{j in rack d} x_j <= 1 for every rack d}

(a read takes at most one chunk from a rack), and the deployed placement,
the entries above the support tolerance, holds at most one host of a rack
per row (a stripe stores at most one block on a rack).

- :func:`project`: the projection onto that set, by Dykstra's
  alternating projections between the capped simplex and the rack caps;
- :func:`feasibility_error`, :func:`spread_count`: how far a plan is from
  the set and from one host per rack;
- :func:`fw_gap`: the Frank-Wolfe gap of a plan on the rack-capped
  smoothed problem, with the latency gradient of ``plan.py``.
"""
from __future__ import annotations

import numpy as np

from reference import plan as ref


def _capped_simplex(v, k, mask, iters: int = 200):
    """Each row onto {[0, 1]^m, sum = k, 0 off the mask}: clip(v - tau,
    0, 1) with tau found by bisection in float64."""
    lo = np.min(np.where(mask, v, np.inf), axis=-1) - 1.0
    hi = np.max(np.where(mask, v, -np.inf), axis=-1)
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        big = np.where(mask, np.clip(v - mid[:, None], 0.0, 1.0), 0.0).sum(-1) > k
        lo, hi = np.where(big, mid, lo), np.where(big, hi, mid)
    tau = 0.5 * (lo + hi)
    return np.where(mask, np.clip(v - tau[:, None], 0.0, 1.0), 0.0)


def _rack_caps(x, racks: int):
    """Onto {sum over each rack <= 1}: a rack over its cap gives the
    excess back evenly over its hosts."""
    r, m = x.shape
    xr = x.reshape(r, racks, m // racks)
    excess = np.maximum(xr.sum(-1, keepdims=True) - 1.0, 0.0)
    return (xr - excess / xr.shape[-1]).reshape(r, m)


def project(v, k, mask, racks: int, iters: int = 20000, tol: float = 1e-13):
    """The rack-capped projection of the rows of ``v`` (r, m); returns the
    capped-simplex iterate of Dykstra's method, exact in row sums, box and
    mask, and within the last step's size of the caps."""
    v = np.asarray(v, np.float64)
    k = np.broadcast_to(np.asarray(k, np.float64), v.shape[:1])
    mask = np.broadcast_to(np.asarray(mask, bool), v.shape)
    x, p, q = v.copy(), np.zeros_like(v), np.zeros_like(v)
    y = x
    for _ in range(iters):
        y = _capped_simplex(x + p, k, mask)
        p = x + p - y
        x_new = _rack_caps(y + q, racks)
        q = y + q - x_new
        done = np.max(np.abs(x_new - x)) < tol
        x = x_new
        if done:
            break
    return y


def rack_sums(pi, racks: int) -> np.ndarray:
    pi = np.asarray(pi, np.float64)
    return pi.reshape(pi.shape[:-1] + (racks, pi.shape[-1] // racks)).sum(-1)


def spread_count(pi, racks: int, tol: float) -> int:
    """(row, rack) pairs with more than one host above ``tol``: stripes
    that store two blocks on one rack."""
    pi = np.asarray(pi, np.float64)
    above = (pi > tol).reshape(pi.shape[:-1] + (racks, pi.shape[-1] // racks))
    return int(np.sum(above.sum(-1) > 1))


def feasibility_error(pi, k, racks: int, down=None) -> float:
    """Largest violation relative to k: Theorem-1 feasibility and mass on
    a host that is ``down`` (``plan.feasibility_error``), and a rack over
    its cap of 1."""
    cap = max(0.0, float(rack_sums(pi, racks).max()) - 1.0)
    return max(ref.feasibility_error(pi, k, down), cap)


def latency_gradient(pi, lam, mu, m2, m3) -> tuple[np.ndarray, float]:
    """(dL/dLambda_j at the plan's node rates, the latency bound): central
    differences in float64 at the plan's optimal z."""
    lam = np.asarray(lam, np.float64)
    lam_hat = lam.sum()
    rates = lam @ np.asarray(pi, np.float64)
    latency, z = ref.latency_at(rates, lam_hat, mu, m2, m3)
    h = 1e-7 * lam_hat
    d_lat = np.empty(rates.shape)
    for j in range(rates.size):
        step = np.zeros(rates.shape)
        step[j] = h
        up = ref.latency_at(rates + step, lam_hat, mu, m2, m3, z)[0]
        dn = ref.latency_at(rates - step, lam_hat, mu, m2, m3, z)[0]
        d_lat[j] = (up - dn) / (2.0 * h)
    return d_lat, latency


def fw_gap(pi, lam, k, mu, m2, m3, cost, theta, beta, allowed, racks: int,
           tol: float) -> float:
    """Frank-Wolfe gap of plan ``pi`` (r x m) on the rack-capped smoothed
    JLCM problem (the latency bound plus theta x sum V_j log(beta pi + 1) /
    log(beta)), over its latency bound. The linear oracle takes, per row,
    the best host of each rack and then the k best racks, among the hosts
    of the plan's own placement (pi > ``tol``) that ``allowed`` (m,)
    keeps."""
    pi = np.asarray(pi, np.float64)
    lam = np.asarray(lam, np.float64)
    cost = np.asarray(cost, np.float64)
    d_lat, latency = latency_gradient(pi, lam, mu, m2, m3)
    grad = lam[:, None] * d_lat[None, :] + float(theta) * cost[None, :] * beta / (
        (beta * pi + 1.0) * np.log(beta))
    cand = (pi > tol) & np.asarray(allowed, bool)[None, :]
    best = np.where(cand, grad, np.inf).reshape(pi.shape[0], racks, -1).min(-1)
    best = np.sort(best, axis=-1)
    kk = np.rint(np.asarray(k, np.float64)).astype(int)
    vertex = sum(best[i, : kk[i]].sum() for i in range(pi.shape[0]))
    return float(np.sum(grad * pi) - vertex) / latency


def cell_moments(config: dict):
    """(mu, E[X^2], E[X^3], cost) per host of the configuration's cell:
    identical hosts, shifted-exponential service D + Exp(bw / chunk)."""
    cell, hosts = config["cell"], config["hosts"]
    m = int(cell["racks"]) * int(cell["hosts_per_rack"])
    d = float(hosts["overhead_s"])
    rate = float(hosts["bandwidth_mbps"]) / float(config["catalog"]["chunk_mb"])
    one = np.ones(m)
    m1 = d + 1 / rate
    m2 = d**2 + 2 * d / rate + 2 / rate**2
    m3 = d**3 + 3 * d**2 / rate + 6 * d / rate**2 + 6 / rate**3
    return one / m1, one * m2, one * m3, one * float(hosts["cost_per_chunk"])


def catalog(config: dict) -> tuple[np.ndarray, np.ndarray]:
    """(lam, k) per volume: rates by contiguous thirds of the index."""
    cat = config["catalog"]
    r = int(cat["r"])
    tier = (3 * np.arange(r)) // r
    lam = np.asarray(cat["rate_by_tier"], np.float64)[tier]
    return lam, np.full(r, float(config["code"]["k"]))
