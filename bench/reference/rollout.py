"""Plain reference for the closed loop's rollout arbitration.

A replan scores each candidate plan by one short rollout from the live
queue state: the mean latency of ``n`` requests simulated under the
estimated rates and service family, plus theta times the plan's storage
cost. The streams follow the segment simulator's published contract, one
PRNG key per replan split three ways: a merged Poisson stream with
categorical file marks; shifted-exponential service per node; per
request a Madow systematic sample of k_i nodes from the file's plan row,
a selected node that is down replaced by the available spares of highest
uniform priority. They are written here in plain ``jax.numpy``, and the
requests are walked first come, first served from the carried departure
times in NumPy (``fcfs.walk``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference import fcfs
from reference.plan import support_cost


def _dispatch(key, pi, fid, avail):
    n, m = fid.shape[0], pi.shape[-1]
    k_row = jnp.round(jnp.sum(pi, axis=-1))
    k_sel, k_prio = jax.random.split(key)
    prio = jax.random.uniform(k_prio, (n, m))

    def one(skey, f, pr):
        sel = fcfs._madow(skey, pi[f])
        alive = sel & avail
        need = k_row[f].astype(jnp.int32) - jnp.sum(alive)
        spare = avail & ~sel
        rank = jnp.argsort(jnp.argsort(-jnp.where(spare, pr, -1.0)))
        return alive | (spare & (rank < need))

    return jax.vmap(one)(jax.random.split(k_sel, n), fid, prio)


def _inputs(key, pi, lam, d, rates, avail, n):
    m = d.shape[-1]
    k_wl, k_sel, k_srv = jax.random.split(key, 3)
    k_gap, k_mark = jax.random.split(k_wl)
    rel = jnp.cumsum(jax.random.exponential(k_gap, (n,)) / jnp.sum(lam))
    logits = jnp.log(lam / jnp.sum(lam))[None, :].repeat(n, 0)
    fid = jax.random.categorical(k_mark, logits)
    service = d + jax.random.exponential(k_srv, (n, m)) / rates
    return rel, _dispatch(k_sel, pi, fid, avail), service


inputs = jax.jit(_inputs, static_argnames=("n",))


def score(carry, key, pi, lam, d, rates, avail, n, cost, theta,
          dtype=np.float32) -> tuple[float, float]:
    """(rollout mean latency, arbitration score) of plan ``pi``: the walk
    in ``dtype``, the mean and the cost term in float64."""
    rel, masks, service = inputs(
        key, jnp.asarray(pi, jnp.float32), jnp.asarray(lam, jnp.float32),
        jnp.asarray(d, jnp.float32), jnp.asarray(rates, jnp.float32),
        jnp.asarray(avail, bool), n=int(n))
    arrival = np.float32(np.asarray(carry.t0)) + np.asarray(rel, np.float32)
    lat, _ = fcfs.walk(arrival, masks, service, np.asarray(carry.dep, np.float32), dtype)
    mean = float(np.mean(lat))
    return mean, mean + float(theta) * support_cost(pi, cost)
