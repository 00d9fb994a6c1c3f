"""Storage cluster model — the paper's testbed as a calibrated substrate.

The prototype (§V.A, Fig. 5) runs 12 Tahoe storage VMs across three
OpenStack DCs (New Jersey / Texas / California) with the client in NJ.
Chunk service time is dominated by per-request protocol overhead (Tahoe is
chatty and single-threaded) plus transfer time, so we model node j serving
a chunk of size B as

    X_j  =  D_j + Exp(bw_j / B)        (shifted exponential)

with D_j the deterministic overhead (RTT x protocol round-trips) and bw_j
the effective client<->site bandwidth. Moments in closed form feed the
analysis; the same distribution is sampled by the simulator. The control
plane inverts this parameterization from measured moments with
``core.queueing.fit_shifted_exponential`` (tested to round-trip
:meth:`Cluster.moments` exactly).

Default constants are calibrated so a (7,4)-coded 50 MB file (12.5 MB
chunks) read from a site mix reproduces the paper's measured service
moments (mean 13.9 s, sigma 4.3 s, E[X^2] 211.8, E[X^3] 3476.8) to within
a few percent; exact Fig.-5 ping/bandwidth values are not recoverable from
the paper and are marked as calibrated here.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array

from repro.core.queueing import ServiceMoments, shifted_exponential_moments


@dataclasses.dataclass(frozen=True)
class StorageNode:
    name: str
    site: str
    overhead_s: float  # deterministic per-chunk service floor D_j
    bandwidth_mbps: float  # effective MB/s for chunk transfer
    cost_per_chunk: float  # V_j, dollars per stored chunk
    # failure domain (rack) index; None = the node shares no domain
    rack: int | None = None


@dataclasses.dataclass(frozen=True)
class Cluster:
    nodes: tuple[StorageNode, ...]

    @property
    def m(self) -> int:
        return len(self.nodes)

    @property
    def domain(self) -> np.ndarray | None:
        """(m,) rack index of each node, or None when no node has a rack
        (the planner then runs without rack caps)."""
        racks = [nd.rack for nd in self.nodes]
        if all(r is None for r in racks):
            return None
        if any(r is None for r in racks):
            raise ValueError("either every node has a rack or none has")
        return np.asarray(racks, np.int32)

    @property
    def cost(self) -> Array:
        return jnp.asarray([nd.cost_per_chunk for nd in self.nodes], jnp.float32)

    def overheads(self) -> Array:
        return jnp.asarray([nd.overhead_s for nd in self.nodes], jnp.float32)

    def bandwidths(self) -> Array:
        return jnp.asarray([nd.bandwidth_mbps for nd in self.nodes], jnp.float32)

    def service_params(self, chunk_mb: float | Array) -> tuple[Array, Array]:
        """The shared shifted-exponential parameterization ``(D_j, bw_j/B)``.

        The ONE place the cluster's service family is turned into sampler/
        moment parameters: ``moments``, ``sample_service``, and
        ``sample_service_per_request`` all read it, so a refactor of the
        rate/overhead computation (e.g. the geo fabric's per-client-site
        override) touches a single code path. ``chunk_mb`` may be a scalar
        or any shape broadcastable against the trailing node axis (e.g.
        ``(n, 1)`` for per-request chunk sizes).
        """
        rate = self.bandwidths() / jnp.asarray(chunk_mb)
        return self.overheads(), rate

    def moments(self, chunk_mb: float) -> ServiceMoments:
        """Per-node service moments for a given chunk size (MB)."""
        d, rate = self.service_params(chunk_mb)
        return shifted_exponential_moments(d, rate)

    def sample_service(self, key: Array, chunk_mb: float, shape: tuple[int, ...]) -> Array:
        """Sample service times, shape (..., m) — shifted exponential."""
        d, rate = self.service_params(chunk_mb)
        e = jax.random.exponential(key, shape + (self.m,))
        return d + e / rate

    def sample_service_per_request(
        self, key: Array, chunk_mb: Array, n: int
    ) -> Array:
        """Per-request service samples (n, m) where request i transfers
        ``chunk_mb[i]`` MB (heterogeneous per-file chunk sizes, §V.B)."""
        d, rate = self.service_params(jnp.asarray(chunk_mb)[:, None])
        e = jax.random.exponential(key, (n, self.m))
        return d + e / rate

    def subset(self, keep: Sequence[int]) -> "Cluster":
        """Surviving-node cluster after failures (elastic replanning)."""
        return Cluster(tuple(self.nodes[i] for i in keep))

    def perturbed(
        self,
        overhead_scale: float | Sequence[float] = 1.0,
        bandwidth_scale: float | Sequence[float] = 1.0,
    ) -> "Cluster":
        """Cluster with drifted service parameters (same node identities).

        Scales each node's deterministic overhead D_j and/or effective
        bandwidth bw_j (scalar = every node, sequence = per node), so the
        shifted-exponential service distribution — and therefore all three
        moments fed to Lemma 3 — drifts consistently between what the
        simulator samples and what :meth:`moments` reports. This is the
        substrate for non-stationary scenarios (hotspots, congestion,
        slow-disk degradation) where plans computed from stale moments go
        sour and the closed loop must re-estimate.
        """
        ovh = np.broadcast_to(np.asarray(overhead_scale, float), (self.m,))
        bwd = np.broadcast_to(np.asarray(bandwidth_scale, float), (self.m,))
        nodes = tuple(
            dataclasses.replace(
                nd,
                overhead_s=nd.overhead_s * float(o),
                bandwidth_mbps=nd.bandwidth_mbps * float(b),
            )
            for nd, o, b in zip(self.nodes, ovh, bwd)
        )
        return Cluster(nodes)


def tahoe_testbed(
    *,
    cost_nj: float = 1.0,
    cost_tx: float = 0.7,
    cost_ca: float = 0.85,
) -> Cluster:
    """12 nodes, 4 per site; client co-located with NJ (paper Fig. 5).

    CA has higher bandwidth than TX despite larger RTT (the paper remarks
    on exactly this inversion). Per-node jitter keeps nodes heterogeneous
    within a site (VM colocation effects).
    """
    # Calibration note: these constants are chosen so the paper's §V.B
    # workload (r=1000 files, 50-200 MB, aggregate ~0.118 req/s) is
    # FEASIBLE but heavily loaded (rho ~ 0.5-0.9 under optimized routing),
    # matching the regimes of Figs. 9-13. The paper's Fig.-6 moment
    # measurement (mean 13.9 s at 12.5 MB chunks) is reproduced separately
    # by `homogeneous_cluster()`; one static testbed cannot match both
    # (the paper's own service times must scale sublinearly with chunk
    # size for its Fig. 11/12 loads to be stable — see EXPERIMENTS.md).
    sites = {
        # site: (overhead_s, bandwidth_mbps) for the 4 nodes
        "NJ": [(2.2, 6.5), (2.5, 6.0), (2.8, 5.5), (3.2, 5.0)],
        "TX": [(7.5, 2.0), (8.0, 1.8), (8.5, 1.7), (9.0, 1.5)],
        "CA": [(3.2, 4.8), (3.5, 4.5), (3.8, 4.2), (4.2, 3.8)],
    }
    cost = {"NJ": cost_nj, "TX": cost_tx, "CA": cost_ca}
    nodes = []
    for site, specs in sites.items():
        for i, (d, bw) in enumerate(specs):
            nodes.append(
                StorageNode(
                    name=f"{site.lower()}{i}",
                    site=site,
                    overhead_s=d,
                    bandwidth_mbps=bw,
                    cost_per_chunk=cost[site],
                )
            )
    return Cluster(tuple(nodes))


def homogeneous_cluster(m: int, overhead_s: float = 9.6, bandwidth_mbps: float | None = None, chunk_mb: float = 12.5, sigma_s: float = 4.3, cost: float = 1.0) -> Cluster:
    """All-identical cluster matching the paper's measured Fig.-6 moments:
    sigma = chunk/bw => bw = chunk/sigma; mean = overhead + sigma = 13.9."""
    bw = bandwidth_mbps if bandwidth_mbps is not None else chunk_mb / sigma_s
    nodes = tuple(
        StorageNode(name=f"n{i}", site="X", overhead_s=overhead_s, bandwidth_mbps=bw, cost_per_chunk=cost)
        for i in range(m)
    )
    return Cluster(nodes)


def measured_fig6_moments() -> ServiceMoments:
    """The paper's measured chunk service moments (single node view)."""
    return ServiceMoments(
        mu=jnp.asarray([1.0 / 13.9]),
        m2=jnp.asarray([211.8]),
        m3=jnp.asarray([3476.8]),
    )


# ---------------------------------------------------------------------------
# Geo-aware client fabric: per-(client-site, node) network profiles.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ClientSite:
    """One client population site and its network profile to each DC.

    The base :class:`Cluster` constants are calibrated for the paper's
    implicit NJ client (§V.A: the client VM sits in the NJ data center),
    so a client site's profile is expressed *relative to that reference*:

    ``rtt_s``            additive RTT delta (seconds) to each storage
                         site's nodes — 0.0 for the reference client,
                         negative when this client sits closer to a site
                         than NJ does (the baked-in NJ↔site RTT comes
                         back out), positive when farther.
    ``bandwidth_scale``  multiplicative factor on the node's effective
                         bandwidth — 1.0 for the reference client.

    A request from this site served by node j then draws

        X_{c,j} = D_j + rtt_s[site_j] + Exp(bw_j * scale[site_j] / B)

    which for the reference profile (all 0.0 / 1.0) is *bitwise* the base
    cluster's service distribution — the degeneracy anchor every existing
    calibration and test relies on.
    """

    name: str
    rtt_s: dict[str, float]
    bandwidth_scale: dict[str, float]

    @classmethod
    def reference(cls, name: str, storage_sites: Sequence[str]) -> "ClientSite":
        """The zero-delta profile (the cluster's own calibration view)."""
        return cls(
            name=name,
            rtt_s={s: 0.0 for s in storage_sites},
            bandwidth_scale={s: 1.0 for s in storage_sites},
        )


@dataclasses.dataclass(frozen=True)
class GeoFabric:
    """A cluster plus the client sites reading from it (paper Fig. 5).

    Wraps the calibrated :class:`Cluster` with C :class:`ClientSite`
    profiles, exposing (C, m)-shaped network-aware service parameters:
    row c is what client site c sees of every node. Row 0 of the default
    fabric is the reference (NJ) profile and reproduces
    :meth:`Cluster.moments` bit-for-bit (see :meth:`single_site` and
    ``tests/test_geo.py``), so the whole geo layer is a strict
    generalization — one client site degrades to today's model exactly.
    """

    cluster: Cluster
    sites: tuple[ClientSite, ...]

    def __post_init__(self) -> None:
        storage_sites = {nd.site for nd in self.cluster.nodes}
        for cs in self.sites:
            missing = storage_sites - set(cs.rtt_s) | (
                storage_sites - set(cs.bandwidth_scale)
            )
            if missing:
                raise ValueError(
                    f"client site {cs.name!r} lacks a profile for storage "
                    f"site(s) {sorted(missing)}"
                )
        for cs in self.sites:
            bad = [s for s, v in cs.bandwidth_scale.items() if not v > 0]
            if bad:
                raise ValueError(
                    f"client site {cs.name!r} has non-positive "
                    f"bandwidth_scale for {sorted(bad)}; scales must be > 0 "
                    "(a dead path is a failure trace, not a zero bandwidth)"
                )
        ovh = np.asarray(self.overheads())
        if (ovh <= 0).any():
            raise ValueError(
                "negative rtt_s delta drove a pair overhead <= 0; deltas "
                "must keep D_j + rtt_s positive"
            )

    @property
    def n_sites(self) -> int:
        return len(self.sites)

    @property
    def m(self) -> int:
        return self.cluster.m

    @property
    def site_names(self) -> tuple[str, ...]:
        return tuple(cs.name for cs in self.sites)

    def overheads(self) -> Array:
        """(C, m) deterministic floors D_j + RTT_{c, site_j}."""
        base = self.cluster.overheads()
        rows = [
            base + jnp.asarray(
                [cs.rtt_s[nd.site] for nd in self.cluster.nodes], jnp.float32
            )
            for cs in self.sites
        ]
        return jnp.stack(rows)

    def bandwidths(self) -> Array:
        """(C, m) effective bandwidths bw_j * scale_{c, site_j}."""
        base = self.cluster.bandwidths()
        rows = [
            base * jnp.asarray(
                [cs.bandwidth_scale[nd.site] for nd in self.cluster.nodes],
                jnp.float32,
            )
            for cs in self.sites
        ]
        return jnp.stack(rows)

    def service_params(self, chunk_mb: float | Array) -> tuple[Array, Array]:
        """(C, m) shifted-exponential params — the geo twin of
        :meth:`Cluster.service_params` (same single-code-path contract)."""
        return self.overheads(), self.bandwidths() / jnp.asarray(chunk_mb)

    def moments(self, chunk_mb: float) -> ServiceMoments:
        """Per-(client site, node) service moments, arrays shaped (C, m)."""
        d, rate = self.service_params(chunk_mb)
        return shifted_exponential_moments(d, rate)

    def uniform_mix(self, r: int) -> np.ndarray:
        """(r, C) client mix with every file read uniformly from all sites."""
        return np.full((r, self.n_sites), 1.0 / self.n_sites)

    def site_index(self, name: str) -> int:
        return self.site_names.index(name)

    @classmethod
    def single_site(cls, cluster: Cluster, name: str = "ref") -> "GeoFabric":
        """The degenerate one-client-site fabric: today's model, exactly.

        The single site carries the zero-delta reference profile, so
        ``fabric.moments(chunk)[0]`` is bitwise ``cluster.moments(chunk)``
        (adding 0.0 and multiplying by 1.0 are float identities).
        """
        sites = sorted({nd.site for nd in cluster.nodes})
        return cls(cluster=cluster, sites=(ClientSite.reference(name, sites),))


def geo_testbed(cluster: Cluster | None = None) -> GeoFabric:
    """Four client sites on the 3-DC testbed (paper Fig. 5, plus a remote).

    * ``NJ`` — the reference profile: the paper's own client placement,
      bitwise identical to the base calibration (degeneracy anchor).
    * ``TX`` / ``CA`` — clients co-located with the other two DCs: the
      baked-in NJ↔site RTT comes back out of the local site's overhead
      (negative delta) and local bandwidth multiplies up, while the path
      back to NJ pays the same WAN RTT in reverse. The CA profile keeps
      the paper's RTT/bandwidth *inversion* (higher RTT, more bandwidth
      than TX) from every vantage point.
    * ``EU`` — a remote client far from all three DCs: every read is a
      WAN read, the regime where placement is pure cost-vs-tail.

    Deltas are calibrated, not measured (the paper publishes no
    per-pair RTT matrix); they preserve ordering facts the paper states —
    locality wins, TX egress is the thinnest pipe, CA bandwidth-rich.
    """
    cluster = tahoe_testbed() if cluster is None else cluster
    sites = (
        ClientSite.reference("NJ", ("NJ", "TX", "CA")),
        ClientSite(
            name="TX",
            rtt_s={"NJ": 4.5, "TX": -5.5, "CA": 0.4},
            bandwidth_scale={"NJ": 0.55, "TX": 2.6, "CA": 0.9},
        ),
        ClientSite(
            name="CA",
            rtt_s={"NJ": 1.4, "TX": 0.6, "CA": -1.8},
            bandwidth_scale={"NJ": 0.75, "TX": 1.05, "CA": 1.7},
        ),
        ClientSite(
            name="EU",
            rtt_s={"NJ": 2.2, "TX": 3.5, "CA": 3.0},
            bandwidth_scale={"NJ": 0.7, "TX": 0.75, "CA": 0.7},
        ),
    )
    return GeoFabric(cluster=cluster, sites=sites)
