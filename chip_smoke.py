#!/usr/bin/env python3
"""Smoke run of the storage system's main path on one TPU chip.

Drives catalog -> JLCM plan -> closed-loop replan (batched rollout
arbitration) -> fleet simulation -> degraded-read decode through the
public entry points, at the sizes a storage operator runs, and checks each
phase against an independent reference. Prints one line per phase (what
ran, its size, wall seconds including compilation, and the comparison)
and, last, one JSON object naming the device.

    python chip_smoke.py                # one chip, every phase
    python chip_smoke.py --four-chips   # sharded fleet + arbitration only
    JAX_PLATFORMS=cpu python chip_smoke.py --small   # CPU rehearsal

It exits nonzero on the first failed check, and on any platform other
than ``tpu``: without a TPU it runs only as the ``--small`` rehearsal
and then stops before the last line. The compile cache lives in
``$JAX_COMPILATION_CACHE_DIR`` when that is set and in ``.jax_cache/``
next to this file otherwise.
"""
from __future__ import annotations

import argparse
import contextlib
import copy
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
MIB = 2**20


@dataclasses.dataclass(frozen=True)
class Sizes:
    dense_files: int = 1000
    hier_files: int = 10**6
    sim_requests: int = 20000
    segment_requests: int | None = None  # None: the scenario's own
    hier_scenario_files: int = 10**5
    fleet_seeds: int = 32
    fleet_chunks: int = 16
    fleet_block: int = 2048
    ref_requests: int = 4096
    object_bytes: int = 150 * MIB
    max_objects: int = 64


SMALL = Sizes(
    dense_files=100,
    hier_files=10**4,
    sim_requests=2000,
    segment_requests=200,
    hier_scenario_files=1000,
    fleet_seeds=4,
    fleet_chunks=3,
    fleet_block=256,
    ref_requests=512,
    object_bytes=3 * MIB // 2,
    max_objects=4,
)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def report(phase: str, size: str, wall: float, outcome: str) -> None:
    print(f"{phase}: {size}; wall_s={wall:.3f}; {outcome}", flush=True)


def feasible(pi, k, tol: float = 1e-3) -> bool:
    """Each row of ``pi`` sums to k_i and every entry lies in [0, 1]."""
    import numpy as np

    pi = np.asarray(pi, np.float64)
    k = np.asarray(k, np.float64)
    return bool(
        np.all(np.abs(pi.sum(-1) - k) <= tol * np.maximum(k, 1.0))
        and pi.min() >= -1e-6
        and pi.max() <= 1.0 + 1e-6
    )


# --------------------------------------------------------------------- plan
def phase_plan(sz: Sizes):
    import jax
    import numpy as np

    from benchmarks.common import paper_catalog
    from repro.core import (
        JLCMProblem,
        cluster_catalog,
        effective_chunk_mb,
        materialize,
        solve,
        solve_hierarchical,
        synthetic_catalog,
    )
    from repro.storage import simulate, tahoe_testbed

    cl = tahoe_testbed()
    t0 = time.perf_counter()
    lam, ks, chunk_mb = paper_catalog(r=sz.dense_files)
    eff = float(np.average(chunk_mb, weights=np.asarray(lam)))
    prob = JLCMProblem(
        lam=lam, k=ks, moments=cl.moments(eff), cost=cl.cost, theta=2.0
    )
    sol = solve(prob, max_iters=300, eps=0.01)
    check(feasible(sol.pi, ks), "dense plan infeasible")
    sim = simulate(jax.random.key(0), sol.pi, lam, cl, eff, sz.sim_requests)
    mean, bound = float(sim.mean_latency()), float(sol.latency_tight)
    check(np.isfinite(mean) and mean <= bound * 1.05,
          f"Lemma-2 bound {bound} below simulated mean {mean}")
    report(
        "plan.dense", f"r={sz.dense_files} m={cl.m} sim_requests="
        f"{sz.sim_requests}", time.perf_counter() - t0,
        f"iterations={int(sol.iterations)} feasible=ok bound={bound:.4f} "
        f"simulated_mean={mean:.4f} bound>=sim: ok",
    )

    t0 = time.perf_counter()
    cat = synthetic_catalog(sz.hier_files)
    h = cluster_catalog(cat)
    plan, hsol = solve_hierarchical(
        h, cl.moments(effective_chunk_mb(h)), cl.cost, 2.0,
        max_iters=300, eps=0.01,
    )
    pi = materialize(plan)
    check(feasible(pi, cat.k), "hierarchical plan infeasible")
    report(
        "plan.hierarchical", f"r={sz.hier_files} clusters={h.n_clusters}",
        time.perf_counter() - t0,
        f"iterations={int(hsol.iterations)} feasible=ok "
        f"objective={float(hsol.objective):.4f}",
    )
    return sol, ks


# ------------------------------------------------------------------- replan
@contextlib.contextmanager
def patched(cls, name, wrap):
    orig = getattr(cls, name)
    setattr(cls, name, wrap(orig))
    try:
        yield
    finally:
        setattr(cls, name, orig)


def phase_replan(sz: Sizes) -> None:
    import numpy as np

    from repro.scenarios import get_scenario, hotspot_drift_hierarchical
    from repro.scenarios import run_scenario
    from repro.serving import AdaptiveReplanner, HierarchicalReplanner
    from repro.serving import router

    rollouts = []

    def count_rollouts(orig):
        def wrapped(*a, **kw):
            rollouts.append(1)
            return orig(*a, **kw)
        return wrapped

    twins = []

    def with_sequential_twin(orig):
        # the same replan from the same state through the legacy per-
        # candidate loop; the batched arbitration must choose its plan
        def wrapped(self, *a, **kw):
            twin = copy.deepcopy(self)
            twin.rollout_batched = False
            pi = orig(self, *a, **kw)
            if kw.get("carry") is not None:
                twins.append(np.array_equal(pi, orig(twin, *a, **kw)))
            return pi
        return wrapped

    spec = get_scenario("node-failure")
    t0 = time.perf_counter()
    with patched(router, "batched_rollout_scores", count_rollouts), patched(
        AdaptiveReplanner, "replan", with_sequential_twin
    ):
        out = run_scenario(
            spec, "adaptive", seed=0, requests_per_segment=sz.segment_requests
        )
    check(len(twins) == out.replans > 0 and len(rollouts) == out.replans,
          f"{out.replans} replans, {len(twins)} twin checks, "
          f"{len(rollouts)} batched arbitrations")
    check(all(twins), "batched arbitration chose another plan than the "
          f"sequential loop at replans {[i for i, t in enumerate(twins) if not t]}")
    check(np.isfinite(out.mean) and np.isfinite(out.p99), "non-finite latency")
    n_req = sz.segment_requests or spec.requests_per_segment
    report(
        "replan.node-failure", f"segments={spec.n_segments} "
        f"requests/segment={n_req} r={spec.r}", time.perf_counter() - t0,
        f"replans={out.replans} batched_arbitrations={len(rollouts)} "
        f"plan==sequential_twin: {sum(twins)}/{len(twins)} "
        f"mean={out.mean:.4f} p99={out.p99:.4f}",
    )

    deployed = []

    def check_feasible(orig):
        def wrapped(self, *a, **kw):
            pi = orig(self, *a, **kw)
            h = self.hierarchy
            deployed.append(feasible(pi, h.k[h.cluster_of_file()]))
            return pi
        return wrapped

    spec, h = hotspot_drift_hierarchical(
        r=sz.hier_scenario_files,
        **({} if sz.segment_requests is None
           else {"requests_per_segment": sz.segment_requests}),
    )
    # this loop arbitrates its warm and cold cluster solves by solved
    # objective on the device; it runs no rollouts
    t0 = time.perf_counter()
    with patched(HierarchicalReplanner, "replan", check_feasible):
        out = run_scenario(spec, "adaptive", seed=0, hierarchy=h)
    check(len(deployed) == out.replans > 0 and all(deployed),
          f"infeasible deployed plans: {deployed}")
    check(bool(np.isfinite(out.mean)), "non-finite mean latency")
    report(
        "replan.hotspot-drift-hier", f"segments={spec.n_segments} "
        f"requests/segment={spec.requests_per_segment} "
        f"r={sz.hier_scenario_files} clusters={h.n_clusters}",
        time.perf_counter() - t0,
        f"replans={out.replans} arbitration=solved objective "
        f"deployed_feasible={sum(deployed)}/{len(deployed)} "
        f"mean={out.mean:.4f} p99={out.p99:.4f}",
    )


# -------------------------------------------------------------------- fleet
FLEET_LAM = (0.036, 0.028, 0.016, 0.012)
FLEET_K = (4.0, 4.0, 6.0, 6.0)
FLEET_MIX = (0.4, 0.25, 0.25, 0.1)
FLEET_CHUNK_MB = 12.5


def fleet_setup():
    import jax.numpy as jnp
    import numpy as np

    from repro.core import JLCMProblem, solve
    from repro.storage import geo_testbed

    fabric = geo_testbed()
    prob = JLCMProblem(
        lam=jnp.asarray(FLEET_LAM, jnp.float32),
        k=jnp.asarray(FLEET_K, jnp.float32),
        moments=fabric.cluster.moments(FLEET_CHUNK_MB),
        cost=fabric.cluster.cost,
        theta=2.0,
    )
    pi = solve(prob, max_iters=300).pi
    lam_cs = jnp.asarray(
        np.asarray(FLEET_MIX)[:, None] * np.asarray(FLEET_LAM)[None, :],
        jnp.float32,
    )
    return fabric, pi, lam_cs


def numpy_fcfs(t, masks, service, dep):
    """Plain float32 FCFS: the reference the kernel is checked against."""
    import numpy as np

    lat = np.empty(t.shape, np.float32)
    for i in range(t.shape[0]):
        start = np.maximum(t[i], dep)
        finish = (start + service[i]).astype(np.float32)
        lat[i] = np.max(np.where(masks[i], finish, -np.inf)) - t[i]
        dep = np.where(masks[i], finish, dep)
    return lat, dep


def phase_fleet(sz: Sizes) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.storage import simulate_fleet
    from repro.storage.simulator import _fleet_inputs

    fabric, pi, lam_cs = fleet_setup()
    key = jax.random.key(7)
    s, w, n = sz.fleet_seeds, sz.fleet_chunks, sz.fleet_block
    run = lambda backend: simulate_fleet(
        key, pi, lam_cs, fabric, FLEET_CHUNK_MB, n, s, stream=True,
        n_chunks=w, keep_latency=True, backend=backend,
    )
    t0 = time.perf_counter()
    fleet = run("pallas")
    jax.block_until_ready(fleet.latency)
    wall = time.perf_counter() - t0
    ref = run("ref")
    check(bool(jnp.array_equal(fleet.latency, ref.latency)),
          "pallas fleet latencies differ from the ref backend")
    check(bool(jnp.all(jnp.isfinite(fleet.latency))), "non-finite latency")

    # the fleet's own request streams, rebuilt per (seed, chunk) key
    d, rates = fabric.service_params(FLEET_CHUNK_MB)
    chunk_keys = jax.vmap(lambda k: jax.random.split(k, w))(
        jax.random.split(key, s)
    )
    inputs = jax.vmap(jax.vmap(
        lambda k: _fleet_inputs(k, pi, lam_cs, d, rates, n, None)
    ))(chunk_keys)
    t = np.asarray(inputs[0])  # (S, W, block), clock re-based per chunk
    gaps = np.diff(t, axis=-1)
    check(bool((t[..., 0] > 0).all() and (gaps >= 0).all()),
          "arrival clock decreases")
    ties = int((gaps == 0).sum())

    # seed 0, first chunks: a NumPy FCFS walk with the chunk re-basing
    n_ref = min(sz.ref_requests, w * n)
    dep = np.zeros((fabric.m,), np.float32)
    lat_np = []
    for c in range(-(-n_ref // n)):
        dep = dep - (np.float32(0) if c == 0 else t[0, c - 1, -1])
        lat_c, dep = numpy_fcfs(
            t[0, c], np.asarray(inputs[3][0, c]),
            np.asarray(inputs[4][0, c]), dep,
        )
        lat_np.append(lat_c)
    lat_np = np.concatenate(lat_np)[:n_ref]
    got = np.asarray(fleet.latency[0, :n_ref])
    # rebuilt streams may differ from the fused program's by an ulp (XLA
    # may rewrite the service draw's division), so compare in float32 ulps
    # of each request's clock: latency = finish - t cancels those digits
    clock = np.abs(t[0].reshape(-1)[:n_ref]) + np.abs(lat_np)
    err = float(np.max(np.abs(got - lat_np) / np.spacing(clock)))
    check(err <= 16, f"fleet vs NumPy FCFS: {err} clock ulps apart")
    report(
        "fleet", f"seeds={s} chunks={w} block={n} requests={s * w * n} "
        f"m={fabric.m}", wall,
        f"pallas==ref bitwise: ok; numpy_fcfs[{n_ref}] max_err={err:.3g} clock ulps; "
        f"arrivals non-decreasing: ok (exact ties {ties} of {t.size}); "
        f"mean={float(fleet.mean_latency()):.4f} "
        f"p99={float(fleet.quantile(0.99)):.4f}",
    )


# -------------------------------------------------------------------- codec
def phase_codec(sz: Sizes, sol, ks) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import gf256_matmul_batch
    from repro.storage import CodecPlan, encode_batch

    plan = CodecPlan.from_solution(sol, ks)
    group = max((g for g in plan.groups if g.k == 6), key=lambda g: g.file_ids.size)
    n, k = group.n, group.k
    chunk = -(-sz.object_bytes // k)

    # drawn in one compiled program: op by op, the threefry counters of a
    # few 150 MB objects alone would fill the device
    draw = jax.jit(jax.random.bits, static_argnums=(1, 2))

    def degraded_read(b, backend):
        """Encode ``b`` objects, lose the node of one data chunk of each
        (chunk ``i % k`` of object ``i``), decode through the plan; returns
        whether every object came back bitwise, and the pattern count."""
        data = draw(jax.random.key(b), (b, k, chunk), jnp.uint8)
        words = encode_batch(data, n, backend=backend)
        fids = [int(group.file_ids[i % group.file_ids.size]) for i in range(b)]
        pats = [
            plan.degraded_patterns(f, [plan.chunk_nodes(f)[i % k]])
            for i, f in enumerate(fids)
        ]
        # row by row: a gather of rows compiles ~20x slower for a TPU
        chunks = [jnp.stack([words[i, c] for c in p]) for i, p in enumerate(pats)]
        del words
        out = plan.decode_requests(fids, pats, chunks, backend=backend)
        src = np.asarray(data)
        return all(np.array_equal(o, src[i]) for i, o in enumerate(out)), len(
            {tuple(p) for p in pats})

    def footprint(f, *shapes) -> int:
        ma = jax.jit(f).lower(*shapes).compile().memory_analysis()
        return (ma.temp_size_in_bytes + ma.argument_size_in_bytes
                + ma.output_size_in_bytes)

    # objects per batch: what the memory analyses of the encode and decode
    # programs say fits, beside the source bytes kept for the comparison
    # and the gathered and stacked chunks
    one = jax.ShapeDtypeStruct((1, k, chunk), jnp.uint8)
    per_object = max(
        footprint(lambda d: encode_batch(d, n), one),
        footprint(gf256_matmul_batch,
                  jax.ShapeDtypeStruct((1, k, k), jnp.uint8), one),
    ) + 3 * k * chunk
    stats = jax.devices()[0].memory_stats() or {}
    free = stats.get("bytes_limit", 16 * 2**30) - stats.get("bytes_in_use", 0)
    b = int(max(1, min(sz.max_objects, 0.8 * free // per_object)))

    t0 = time.perf_counter()
    ok, n_pat = degraded_read(b, "auto")
    wall = time.perf_counter() - t0
    check(ok, "decoded bytes differ from the source (auto backend)")
    ok1, _ = degraded_read(1, "pallas")
    check(ok1, "decoded bytes differ from the source (pallas backend)")
    report(
        "codec", f"RS({n},{k}) objects={b} object_bytes={k * chunk} "
        f"bytes_decoded={b * k * chunk} erasure_patterns={n_pat} "
        f"per_object_bytes={per_object}", wall,
        "decode==source bitwise: ok (auto); 1 object on pallas: ok",
    )


# --------------------------------------------------------------- four chips
def phase_four_chips(sz: Sizes) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import JLCMProblem, solve_batch, stack_problems
    from repro.serving import batched_rollout_scores
    from repro.storage import init_carry, simulate_fleet, tahoe_testbed

    meshes = []
    real_shard_map = jax.shard_map

    def counting_shard_map(f, **kw):
        meshes.append(int(kw["mesh"].devices.size))
        return real_shard_map(f, **kw)

    n_dev = len(jax.devices())
    fabric, pi, lam_cs = fleet_setup()
    key = jax.random.key(7)
    s, w, n = sz.fleet_seeds, sz.fleet_chunks, sz.fleet_block
    run = lambda devices: simulate_fleet(
        key, pi, lam_cs, fabric, FLEET_CHUNK_MB, n, s, stream=True,
        n_chunks=w, keep_latency=True, devices=devices,
    )
    t0 = time.perf_counter()
    jax.shard_map = counting_shard_map
    try:
        sharded = run("auto")
        jax.block_until_ready(sharded.latency)
    finally:
        jax.shard_map = real_shard_map
    wall = time.perf_counter() - t0
    single = run("never")
    placed = len(sharded.stream.count.sharding.device_set)
    check(meshes == [n_dev] and placed == n_dev,
          f"fleet not sharded: shard_map meshes {meshes}, output on {placed}")
    check(bool(jnp.array_equal(sharded.latency, single.latency)),
          "sharded fleet latencies differ from the single-device run")
    check(bool(jnp.array_equal(sharded.stream.hist, single.stream.hist)),
          "sharded fleet histograms differ")
    report(
        "four_chips.fleet", f"seeds={s} chunks={w} block={n} devices={n_dev}",
        wall, f"shard_map over {meshes[0]} devices, seed axis on {placed}; "
        "sharded==single-device bitwise: ok",
    )

    cl = tahoe_testbed()
    lam = np.asarray([0.030, 0.020, 0.015, 0.012])
    d, rates = cl.service_params(150.0 / 4)
    probs = [
        JLCMProblem(
            lam=jnp.asarray(lam * f, jnp.float32),
            k=jnp.asarray([4.0, 4.0, 6.0, 6.0], jnp.float32),
            moments=cl.moments(150.0 / 4), cost=cl.cost, theta=2.0,
        )
        for f in np.linspace(0.8, 1.2, 6)
    ]
    sols = solve_batch(stack_problems(probs), max_iters=80)
    args = (
        init_carry(cl.m), jax.random.key(20), sols.pi,
        jnp.asarray(lam, jnp.float32), jnp.asarray(d, jnp.float32),
        jnp.asarray(rates, jnp.float32), jnp.ones((cl.m,), bool),
        jnp.asarray(2.0 * np.asarray(sols.cost), jnp.float32), None,
    )
    arb = lambda devices: batched_rollout_scores(
        *args, n_clients=4, n_requests=600, rollout_seeds=2, devices=devices
    )
    meshes.clear()
    t0 = time.perf_counter()
    jax.shard_map = counting_shard_map
    try:
        sc_sh, best_sh = arb("auto")
        jax.block_until_ready(sc_sh)
    finally:
        jax.shard_map = real_shard_map
    wall = time.perf_counter() - t0
    sc_vm, best_vm = arb("never")
    check(meshes == [n_dev], f"arbitration not sharded: meshes {meshes}")
    b = len(probs)
    diff = float(np.max(np.abs(np.asarray(sc_sh)[:b] - np.asarray(sc_vm)[:b])))
    check(int(best_sh) == int(best_vm) and diff <= 1e-6 * max(
        1.0, float(np.max(np.abs(np.asarray(sc_vm)[:b])))),
        f"sharded arbitration differs: best {int(best_sh)} vs "
        f"{int(best_vm)}, max score diff {diff}")
    report(
        "four_chips.arbitration", f"candidates={b} rollout_seeds=2 "
        f"lanes={np.asarray(sc_sh).size * 2} devices={n_dev}", wall,
        f"shard_map over {meshes[0]} devices; best={int(best_sh)} "
        f"== single-device; max score diff {diff:.3g}",
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--small", action="store_true",
                    help="shrink every size (CPU rehearsal)")
    ap.add_argument("--four-chips", action="store_true",
                    help="only the sharded fleet and arbitration, each "
                    "beside its single-device twin")
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no repository source under {ROOT}")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.small:
        raise SystemExit(f"chip_smoke: needs a TPU, found {dev.platform}")
    from benchmarks.common import use_compile_cache

    use_compile_cache()
    sz = SMALL if args.small else Sizes()
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}",
          flush=True)
    if args.four_chips:
        check(len(jax.devices()) == 4, f"--four-chips needs 4 devices, "
              f"found {len(jax.devices())}")
        phase_four_chips(sz)
    else:
        sol, ks = phase_plan(sz)
        phase_replan(sz)
        phase_fleet(sz)
        phase_codec(sz, sol, ks)
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: rehearsal on {dev.platform} passed; "
                         "no result without a TPU")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
