"""A configuration file turned into the program's own objects.

The configuration (``bench/configs/<name>.json``) is the deployment as
run: the testbed's nodes, the client sites, the catalog and the tradeoff.
Both the program (through its public constructors, here) and the plain
references in ``bench/reference/`` are built from it, so neither takes
the other's tables.
"""
from __future__ import annotations

import numpy as np


def cluster(config: dict):
    """The program's ``Cluster`` for the configuration's testbed."""
    from repro.storage import Cluster, StorageNode

    tb = config["testbed"]
    nodes = []
    for site in tb["site_order"]:
        for i, (d, bw) in enumerate(tb["nodes"][site]):
            nodes.append(StorageNode(
                name=f"{site.lower()}{i}", site=site, overhead_s=float(d),
                bandwidth_mbps=float(bw), cost_per_chunk=float(tb["cost"][site]),
            ))
    return Cluster(tuple(nodes))


def fabric(config: dict):
    """The program's ``GeoFabric``: the testbed read from every client site."""
    from repro.storage import ClientSite, GeoFabric

    sites = tuple(
        ClientSite(name=c["name"], rtt_s=dict(c["rtt_s"]),
                   bandwidth_scale=dict(c["bandwidth_scale"]))
        for c in config["client_sites"]
    )
    return GeoFabric(cluster=cluster(config), sites=sites)


def paper_catalog(config: dict) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(lam, k, chunk_mb) per file of the configuration's catalog: k by
    quarters of the file index, rates by thirds."""
    cat = config["catalog"]
    r = int(cat["r"])
    k = np.zeros(r, np.int32)
    for q, kq in enumerate(cat["k_by_quarter"]):
        k[q::4] = kq
    lam = np.zeros(r)
    for t, lt in enumerate(cat["rate_by_third"]):
        lam[t::3] = lt
    return lam, k, float(cat["file_mb"]) / k


def effective_chunk_mb(lam: np.ndarray, chunk_mb: np.ndarray) -> float:
    """The request-weighted chunk size one service family is planned at."""
    return float(np.average(chunk_mb, weights=lam))

