"""Capacity-planning fleet simulation, streamed (mix driver).

Set-up solves the configuration's dense plan and splits the catalog's
rates over the client sites by the configured mix. Each step of the
window is one ``simulate_fleet(..., stream=True, backend="auto")`` call
of the mix's shape on a fresh key drawn from the run's seed, ending in
``block_until_ready`` on its streaming statistics.

End to end: ``fleet_mreq_per_s``, simulated requests over the window's
seconds. Check: for a seeded sample of calls and of their seeds, every
streaming statistic the program kept, for the whole run and for each
chunk's window (count, mean, variance from ``m2``, largest latency and
the quantile sketch's bucket counts, after warm-up), against a NumPy FCFS
walk over the request streams that the plain reference rebuilds from the
call's key, binned on the sketch edges the mix states (``mean_rel``,
``var_rel``, ``max_rel``, ``hist_moved``: the requests counted in
another bucket, as a share of the run's; the counts exactly).
"""
from __future__ import annotations

import dataclasses

import numpy as np

import deploy
from harness import Reservoir, worse
from reference import fcfs as ref

LIMIT_KEYS = ("mean_rel", "var_rel", "max_rel", "hist_moved")


@dataclasses.dataclass
class State:
    fabric: object
    pi: object
    lam_cs: object
    chunk_mb: float
    keys: object
    sample: Reservoir
    sketch: object
    recording: bool = False


def setup(run) -> State:
    import jax.numpy as jnp

    from repro.core import JLCMProblem, solve
    from repro.storage import SketchSpec

    cfg = run.config
    cl = deploy.cluster(cfg)
    lam, k, chunk = deploy.paper_catalog(cfg)
    eff = deploy.effective_chunk_mb(lam, chunk)
    planner = cfg["planner"]
    sol = solve(
        JLCMProblem(lam=jnp.asarray(lam, jnp.float32), k=jnp.asarray(k, jnp.float32),
                    moments=cl.moments(eff), cost=cl.cost, theta=float(cfg["theta"])),
        max_iters=int(planner["max_iters"]), eps=float(planner["eps"]),
    )
    mix = np.asarray(cfg["client_mix"], np.float64)
    rng = run.rng("keys")
    state = State(
        fabric=deploy.fabric(cfg), pi=sol.pi,
        lam_cs=jnp.asarray(mix[:, None] * lam[None, :], jnp.float32), chunk_mb=eff,
        keys=iter(lambda: int(rng.integers(0, 2**31)), None),
        sample=Reservoir(int(run.mix["sampled_calls"]), run.rng("sample")),
        sketch=SketchSpec(**run.mix["sketch"]),
    )
    _call(run, state)
    state.recording = True
    return state


def _call(run, state: State) -> None:
    import jax

    from repro.storage import simulate_fleet

    shape = run.mix["call"]
    seed = next(state.keys)
    with run.spans.span("fleet_call"):
        out = simulate_fleet(
            jax.random.key(seed), state.pi, state.lam_cs, state.fabric,
            state.chunk_mb, int(shape["block"]), int(shape["seeds"]), stream=True,
            n_chunks=int(shape["chunks"]), drop_warmup=float(shape["drop_warmup"]),
            backend="auto", sketch=state.sketch,
        )
        jax.block_until_ready(out.stream)
    if state.recording:
        run.count("attempted", int(shape["seeds"]) * int(shape["chunks"]) * int(shape["block"]))
        state.sample.offer(lambda: (seed, out.stream, out.windows))


def step(run) -> None:
    _call(run, run.state)


def end_to_end(run) -> dict:
    return {"fleet_mreq_per_s": run.counters["attempted"] / run.window_s / 1e6}


def release(run) -> None:
    pass


def _program_stats(stream, windows, j: int) -> list[dict]:
    """Seed ``j``'s statistics as the program kept them: the whole run's
    first, then each window's."""
    rows = [_row(stream, (j,))]
    rows += [_row(windows, (j, w)) for w in range(np.asarray(windows.count).shape[1])]
    return rows


def _row(st, idx) -> dict:
    count = int(np.asarray(st.count)[idx])
    return {"count": count, "mean": float(np.asarray(st.mean)[idx]),
            "var": float(np.asarray(st.m2)[idx]) / max(count, 1),
            "max": float(np.asarray(st.maxv)[idx]),
            "hist": np.asarray(st.hist)[idx].astype(np.int64)}


def compare(got: dict, want: dict, run_count: int) -> dict:
    """The numbers compared for one statistic of one system. Requests the
    sketch counts in another bucket are a share of the whole run's
    requests, so that one rounding flip at a bucket edge weighs the same
    in a short window as in the run."""
    out = {"count_err": float(got["count"] != want["count"])}
    if not want["count"]:
        return out
    rel = lambda key: abs(got[key] - want[key]) / abs(want[key])  # noqa: E731
    out.update(mean_rel=rel("mean"), var_rel=rel("var"), max_rel=rel("max"),
               hist_moved=float(np.abs(got["hist"] - want["hist"]).sum())
               / (2.0 * max(run_count, 1)))
    return out


def _summarize(run, dtype):
    import jax

    state, shape, lim = run.state, run.mix["call"], run.mix["limits"]
    s, w, n = int(shape["seeds"]), int(shape["chunks"]), int(shape["block"])
    warm = int(w * n * float(shape["drop_warmup"]))
    d, rates = ref.service_params(run.config, state.chunk_mb)
    edges = ref.sketch_edges(**run.mix["sketch"])
    pick = run.rng("seeds")
    worst = {key: 0.0 for key in LIMIT_KEYS}
    count_bad = failed = 0
    for seed, stream, windows in state.sample.items:
        seed_keys = jax.random.split(jax.random.key(seed), s)
        for j in pick.choice(s, size=int(run.mix["sampled_seeds"]), replace=False):
            chunks = ref.seed_latencies(seed_keys[int(j)], state.pi, state.lam_cs, d,
                                        rates, w, n, dtype)
            want = ref.stats(chunks, warm, edges)
            got = _program_stats(stream, windows, int(j))
            bad = len(got) != len(want)
            for g, wt in zip(got, want):
                nums = compare(g, wt, want[0]["count"])
                miscount = bool(nums.pop("count_err"))
                count_bad += miscount
                bad |= miscount
                for key, val in nums.items():
                    worst[key] = worse(worst[key], float(val))
                    bad |= not val <= lim[key]
            failed += bad
    checks = [(key, worst[key], float(lim[key]),
               "largest over sampled seeds and their windows, at most")
              for key in LIMIT_KEYS]
    checks.append(("count_err", float(count_bad), 0.0,
                   "sampled statistics whose request count differs, at most"))
    if not state.sample.items:
        checks.append(("none_checked", 1.0, 0.0, "no call came to be checked"))
        failed += 1
    return checks, int(failed)


def check(run):
    return _summarize(run, np.float32)


def control(run):
    """The reference walk computed in bfloat16 in the program's place."""
    return _summarize(run, ref.BF16)
