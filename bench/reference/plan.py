"""Plain reference for a plan: Theorem-1 feasibility and the JLCM
objective of arXiv:1404.4975, Eq. (9), evaluated in float64 NumPy.

For a dispatch matrix pi (r files x m nodes), node arrival rates
Lambda_j = sum_i lam_i pi_ij; each node is an M/G/1 queue whose sojourn
moments follow Pollaczek-Khinchin (Eqs. 6-7) from the service moments
(mu_j, E[X^2], E[X^3]), with 1 - rho clamped at 1 - 0.999 as the paper's
stability region ends there. The latency bound with one shared z is

    z + sum_j Lambda_j / (2 lam_hat) [X_j + sqrt(X_j^2 + Y_j)],
    X_j = E[Q_j] - z,  Y_j = Var[Q_j],

minimized over z (convex in z), and the cost is sum_i sum_j V_j
1(pi_ij > tol). The objective is latency + theta * cost.

``dtype`` is float64 for the reference and ``bfloat16`` for the control
(the reference computed one precision below the program's float32).

How good a plan is, independently of the solver that made it, is read as
its Frank-Wolfe gap on the problem Algorithm JLCM solves, the latency
bound plus theta times the log-smoothed cost sum_ij V_j log(beta pi_ij +
1) / log(beta) (Eq. 20), over the feasible set of Theorem 1 with the
nodes that are down left out:

    gap(pi) = max_s  grad(pi) . (pi - s),

the most that one linear step could still gain, zero exactly at a
stationary point. The LMO puts 1 on the k_i allowed nodes of smallest
gradient in each row. The latency part of the gradient depends on pi
only through the node rates Lambda_j, so it is lam_i dL/dLambda_j, taken
by central differences in float64 at the plan's optimal z (the bound's
derivative in z is 0 there).
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

RHO_MAX = 0.999
SUPPORT_TOL = 1e-3
BF16 = ml_dtypes.bfloat16


def feasibility_error(pi, k, down=None) -> float:
    """Largest violation of Theorem-1 feasibility, relative to k: row sums
    off k_i, entries outside [0, 1], mass on nodes that are ``down``."""
    pi = np.asarray(pi, np.float64)
    k = np.asarray(k, np.float64)
    row = np.max(np.abs(pi.sum(-1) - k) / np.maximum(k, 1.0))
    box = max(0.0, -pi.min(), pi.max() - 1.0)
    dead = 0.0
    if down is not None and np.any(down):
        dead = float(np.max(pi[..., np.asarray(down, bool)]))
    return float(max(row, box, dead))


def objective(pi, lam, mu, m2, m3, cost, theta, dtype=np.float64):
    """(objective, latency, cost) of plan ``pi`` in ``dtype``."""
    f = lambda x: np.asarray(x, np.float64).astype(dtype)  # noqa: E731
    pi, lam, mu, m2, m3, cost = map(f, (pi, lam, mu, m2, m3, cost))
    rates = (lam[:, None] * pi).sum(0).astype(dtype)
    rho = (rates / mu).astype(dtype)
    slack = np.maximum(f(1.0) - rho, f(1.0 - RHO_MAX)).astype(dtype)
    var = (m2 - (f(1.0) / mu) ** 2).astype(dtype)
    eq = (f(1.0) / mu + rates * m2 / (f(2.0) * slack)).astype(dtype)
    varq = (
        var + rates * m3 / (f(3.0) * slack) + rates**2 * m2**2 / (f(4.0) * slack**2)
    ).astype(dtype)
    w = (rates / lam.sum()).astype(dtype)

    def bound(z):
        x = (eq - f(z)).astype(dtype)
        body = (w / f(2.0) * (x + np.sqrt((x * x + varq).astype(dtype)))).astype(dtype)
        return float(f(z) + body.sum().astype(dtype))

    z = _argmin_z(np.asarray(w, np.float64), np.asarray(eq, np.float64),
                  np.asarray(varq, np.float64))
    latency = bound(z)
    c = support_cost(pi, cost)
    return latency + float(theta) * c, latency, c


def support_cost(pi, cost) -> float:
    """sum_ij V_j 1(pi_ij > tol): one term per stored chunk, in float64."""
    support = np.asarray(pi, np.float64) > SUPPORT_TOL
    return float((support * np.asarray(cost, np.float64)[None, :]).sum())


def _argmin_z(w, eq, varq, iters: int = 200) -> float:
    """Root of d/dz = 1 - sum_j w_j/2 (1 + X_j / sqrt(X_j^2 + Y_j)), which
    rises from 1 - sum(w) < 0 to 1: bisection in float64."""
    scale = eq.max() + np.sqrt(varq.max()) + 1.0
    lo, hi = -64.0 * scale, 4.0 * scale
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        x = eq - mid
        d = 1.0 - np.sum(0.5 * w * (1.0 + x / np.sqrt(x * x + varq)))
        if d < 0.0:
            lo = mid
        else:
            hi = mid
    return 0.5 * (lo + hi)


def testbed_moments(config: dict, chunk_mb: float):
    """(mu, E[X^2], E[X^3], cost) per node of the configuration's testbed:
    shifted-exponential service D_j + Exp(bw_j / chunk_mb)."""
    tb = config["testbed"]
    nodes = [n for s in tb["site_order"] for n in tb["nodes"][s]]
    d = np.asarray([n[0] for n in nodes], np.float64)
    rate = np.asarray([n[1] for n in nodes], np.float64) / float(chunk_mb)
    m1 = d + 1 / rate
    m2 = d**2 + 2 * d / rate + 2 / rate**2
    m3 = d**3 + 3 * d**2 / rate + 6 * d / rate**2 + 6 / rate**3
    cost = np.asarray(
        [tb["cost"][s] for s in tb["site_order"] for _ in tb["nodes"][s]], np.float64
    )
    return 1 / m1, m2, m3, cost


def latency_at(rates, lam_hat, mu, m2, m3, z=None) -> tuple[float, float]:
    """(latency bound, z) at node rates ``rates`` in float64; ``z`` is the
    minimizing one unless given."""
    rates, mu, m2, m3 = (np.asarray(x, np.float64) for x in (rates, mu, m2, m3))
    slack = np.maximum(1.0 - rates / mu, 1.0 - RHO_MAX)
    eq = 1.0 / mu + rates * m2 / (2.0 * slack)
    varq = (m2 - 1.0 / mu**2) + rates * m3 / (3.0 * slack) + rates**2 * m2**2 / (
        4.0 * slack**2)
    w = rates / float(lam_hat)
    if z is None:
        z = _argmin_z(w, eq, varq)
    x = eq - z
    return float(z + np.sum(0.5 * w * (x + np.sqrt(x * x + varq)))), z


def fw_gap(pi, lam, k, mu, m2, m3, cost, theta, beta, allowed) -> float:
    """Frank-Wolfe gap of plan ``pi`` (r x m) on the smoothed JLCM problem,
    as a share of the plan's latency bound; ``allowed`` (m,) marks the
    nodes that are up."""
    pi = np.asarray(pi, np.float64)
    lam = np.asarray(lam, np.float64)
    cost = np.asarray(cost, np.float64)
    lam_hat = lam.sum()
    rates = lam @ pi
    latency, z = latency_at(rates, lam_hat, mu, m2, m3)
    h = 1e-7 * lam_hat
    d_lat = np.empty(rates.shape)
    for j in range(rates.size):
        step = np.zeros(rates.shape)
        step[j] = h
        up = latency_at(rates + step, lam_hat, mu, m2, m3, z)[0]
        down = latency_at(rates - step, lam_hat, mu, m2, m3, z)[0]
        d_lat[j] = (up - down) / (2.0 * h)
    grad = lam[:, None] * d_lat[None, :] + float(theta) * cost[None, :] * beta / (
        (beta * pi + 1.0) * np.log(beta))
    masked = np.where(np.asarray(allowed, bool)[None, :], grad, np.inf)
    rank = np.argsort(np.argsort(masked, axis=1, kind="stable"), axis=1)
    vertex = rank < np.rint(np.asarray(k, np.float64))[:, None]
    gap = float(np.sum(grad * pi) - np.sum(np.where(vertex, grad, 0.0)))
    return gap / latency


def relative_gap(got: float, ref: float) -> float:
    return abs(float(got) - float(ref)) / max(abs(float(ref)), 1e-30)


def as_bf16(x) -> np.ndarray:
    """``x`` held in bfloat16, as a plan computed in it would be."""
    return np.asarray(x, np.float32).astype(BF16).astype(np.float64)
