"""Plain reference for the fleet simulation: the request streams rebuilt
from the call's key, and a first-come-first-served walk over them.

The streams follow the fleet simulator's published contract (one PRNG
key per seed, split into one key per chunk of requests; per chunk a
merged Poisson stream over (client site, file) with inverse-CDF marks,
shifted-exponential service per (site, node), and a Madow systematic
sample of k nodes from the file's plan row), written here in plain
``jax.numpy`` from the configuration's numbers. The walk is a NumPy loop:
a request starts on each of its nodes when both it and the node are
free, and finishes when its last chunk does; each chunk's clock counts
from the previous chunk's last arrival, as the simulator's does.

Each simulated system's statistics, after the warm-up requests, are its
count, mean, population variance and largest latency, and a histogram on
the quantile sketch's log-spaced edges (``lo * g**i``, ``g = (hi /
lo)**(1 / bins)``, a value counted in the first edge above it, with one
bucket below ``lo`` and one at or above ``hi``), for the whole run and
for each chunk of it (the window statistics).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

BF16 = ml_dtypes.bfloat16


def service_params(config: dict, chunk_mb: float) -> tuple[np.ndarray, np.ndarray]:
    """(C, m) service floors and exponential rates, float32, from the
    configuration's testbed and client sites."""
    tb = config["testbed"]
    d, bw, site = [], [], []
    for s in tb["site_order"]:
        for od, b in tb["nodes"][s]:
            d.append(od)
            bw.append(b)
            site.append(s)
    d = np.asarray(d, np.float32)
    bw = np.asarray(bw, np.float32)
    rows_d, rows_r = [], []
    for c in config["client_sites"]:
        rows_d.append(d + np.asarray([c["rtt_s"][s] for s in site], np.float32))
        rows_r.append(
            (bw * np.asarray([c["bandwidth_scale"][s] for s in site], np.float32))
            / np.float32(chunk_mb)
        )
    return np.stack(rows_d), np.stack(rows_r)


def _madow(key, p):
    c = jnp.concatenate([jnp.zeros((1,), p.dtype), jnp.cumsum(p)])
    u = jax.random.uniform(key, (), dtype=p.dtype)
    return (jnp.floor(c[1:] - u) - jnp.floor(c[:-1] - u)) >= 1.0


def _inputs(key, pi, lam_cs, d, rates, n):
    r = lam_cs.shape[1]
    m = d.shape[-1]
    k_wl, k_sel, k_srv = jax.random.split(key, 3)
    flat = lam_cs.reshape(-1)
    k_gap, k_mark = jax.random.split(k_wl)
    t = jnp.cumsum(jax.random.exponential(k_gap, (n,)) / jnp.sum(flat))
    cdf = jnp.cumsum(flat / jnp.sum(flat))
    u = jax.random.uniform(k_mark, (n,))
    marks = jnp.clip(jnp.searchsorted(cdf, u, side="right"), 0, flat.shape[0] - 1)
    fid, site = marks % r, marks // r
    e = jax.random.exponential(k_srv, (n, m))
    service = d[site] + e / rates[site]
    masks = jax.vmap(lambda sk, f: _madow(sk, pi[f]))(jax.random.split(k_sel, n), fid)
    return t, masks, service


inputs = jax.jit(_inputs, static_argnames=("n",))


def walk(t, masks, service, dep, dtype=np.float32):
    """FCFS over one chunk in ``dtype``; returns (latencies, departures)."""
    t = np.asarray(t, np.float32).astype(dtype)
    service = np.asarray(service, np.float32).astype(dtype)
    masks = np.asarray(masks, bool)
    dep = np.asarray(dep).astype(dtype)
    lat = np.empty(t.shape, np.float64)
    for i in range(t.shape[0]):
        start = np.maximum(t[i], dep)
        finish = (start + service[i]).astype(dtype)
        lat[i] = float(np.max(np.where(masks[i], finish, -np.inf)).astype(dtype) - t[i])
        dep = np.where(masks[i], finish, dep).astype(dtype)
    return lat, dep


def seed_latencies(seed_key, pi, lam_cs, d, rates, n_chunks, block, dtype=np.float32):
    """Every latency of one simulated system (one seed of a fleet call),
    chunk by chunk on the re-based clock: a list of one array a chunk."""
    chunk_keys = jax.random.split(seed_key, n_chunks) if n_chunks > 1 else [seed_key]
    dep = np.zeros((d.shape[-1],), np.float32)
    out = []
    origin = np.float32(0)
    for ck in chunk_keys:
        t, masks, service = inputs(ck, pi, lam_cs, d, rates, n=block)
        dep = (np.asarray(dep, np.float32) - origin).astype(np.float32)
        lat, dep = walk(t, masks, service, dep, dtype)
        out.append(lat)
        origin = np.float32(np.asarray(t)[-1])
    return out


def sketch_edges(lo: float, hi: float, bins: int) -> np.ndarray:
    """The (bins + 1,) bucket edges of the quantile sketch, in float32."""
    growth = (float(hi) / float(lo)) ** (1.0 / int(bins))
    return (float(lo) * growth ** np.arange(int(bins) + 1)).astype(np.float32)


def summary(lat: np.ndarray, edges: np.ndarray) -> dict:
    """Count, mean, variance, largest value and sketch histogram of ``lat``."""
    hist = np.bincount(
        np.searchsorted(edges, np.asarray(lat, np.float32), side="right"),
        minlength=edges.size + 1,
    )
    if not lat.size:
        return {"count": 0, "hist": hist}
    return {"count": int(lat.size), "mean": float(lat.mean()), "var": float(lat.var()),
            "max": float(lat.max()), "hist": hist}


def stats(chunks: list, warm: int, edges: np.ndarray) -> list[dict]:
    """The statistics of one system after its first ``warm`` requests: the
    whole run's first, then each chunk's."""
    block = chunks[0].size
    kept = [c[max(0, warm - i * block):] for i, c in enumerate(chunks)]
    return [summary(np.concatenate(kept), edges)] + [summary(c, edges) for c in kept]
