"""Closed-loop replanning of a rack cell through the loss of a whole rack
(mix driver).

The configuration is a cell of racks of identical hosts (``cell``,
``hosts``), laid out rack-major, with one code for every volume
(``code``) and rates by tier (``catalog``). This driver builds the
program's ``Cluster`` from it, each host with its rack, so every plan the
program makes carries the rack caps and deploys at most one chunk of a
volume per rack. The mix names the lost racks as ``(rack, first, last)``
triples; they are written out as the scenario's ``(node, first, last)``
triples of the rack's hosts. The loop itself is ``replan_loop``'s:
``run_scenario`` with the adaptive policy from a fresh seed each step, the
initial plan solved once in set-up, every ``AdaptiveReplanner.replan``
timed by a span from this side and its candidates kept for the check.

End to end: ``replan_p95_ms``. Check, for every replan in the window,
against plain references (``reference/racks.py`` beside ``plan.py`` and
``rollout.py``):

- ``plan_err``: Theorem-1 feasibility, the rack caps, and no mass on a
  host the schedule has down;
- ``spread_err``: the (volume, rack) pairs whose deployed placement holds
  more than one host above the support tolerance, which has to be 0;
- ``obj_err``, ``score_err``, ``arb_regret``: as in ``replan_loop``;
- ``fw_gap``: the Frank-Wolfe gap on the rack-capped smoothed problem,
  its oracle restricted to the plan's own placement, over its latency
  bound.
"""
from __future__ import annotations

import numpy as np

from drivers import replan_loop as base
from harness import worse
from reference import plan as ref
from reference import racks as rref
from reference import rollout

LIMIT_KEYS = ("plan_err", "spread_err", "obj_err", "fw_gap", "score_err", "arb_regret")

step = base.step
end_to_end = base.end_to_end
release = base.release


def cluster(config: dict):
    """The program's ``Cluster`` for the configuration's cell: host
    rack x H + slot lies in rack ``rack``."""
    from repro.storage import Cluster, StorageNode

    cell, hosts = config["cell"], config["hosts"]
    return Cluster(tuple(
        StorageNode(
            name=f"rack{d:02d}-host{h:02d}", site="cell",
            overhead_s=float(hosts["overhead_s"]),
            bandwidth_mbps=float(hosts["bandwidth_mbps"]),
            cost_per_chunk=float(hosts["cost_per_chunk"]), rack=d,
        )
        for d in range(int(cell["racks"])) for h in range(int(cell["hosts_per_rack"]))
    ))


def _with_hosts(mix: dict, hosts_per_rack: int) -> dict:
    """The mix with each lost rack written out as its hosts' triples."""
    sc = mix["scenario"]
    failures = [[d * hosts_per_rack + h, first, last]
                for d, first, last in sc["rack_failures"] for h in range(hosts_per_rack)]
    return dict(mix, scenario=dict(sc, failures=failures))


def setup(run) -> base.State:
    import jax.numpy as jnp

    from repro.core import JLCMProblem, solve

    cfg = run.config
    run.mix = _with_hosts(run.mix, int(cfg["cell"]["hosts_per_rack"]))
    cl = cluster(cfg)
    lam, k = rref.catalog(cfg)
    chunk = float(cfg["catalog"]["chunk_mb"])
    spec = base._scenario_spec(run, lam, k, chunk)
    planner = cfg["planner"]
    sol = solve(
        JLCMProblem(
            lam=jnp.asarray(lam, jnp.float32), k=jnp.asarray(k, jnp.float32),
            moments=cl.moments(chunk), cost=cl.cost, theta=float(cfg["theta"]),
            domain=cl.domain,
        ),
        max_iters=int(planner["max_iters"]), eps=float(planner["eps"]),
    )
    rng = run.rng("scenario")
    state = base.State(
        spec=spec, cluster=cl, pi0=np.asarray(sol.pi), restore=[],
        seeds=iter(lambda: int(rng.integers(0, 2**31 - 2**16)), None),
    )
    base._install(run, state)
    base._scenario(run, state, int(run.rng("warm-up").integers(0, 2**31 - 2**16)))
    state.recording = True
    return state


def _readings(rec, run, dtype) -> dict:
    """The numbers compared for one replan; below float64 the reference
    computed in ``dtype`` stands in the program's place (the control), as
    in ``replan_loop``."""
    cfg, planner = run.config, run.config["planner"]
    racks, tol = int(cfg["cell"]["racks"]), float(planner["support_tol"])
    control = dtype != np.float64
    prob = rec["probs"][0]  # the candidates differ only in their start
    lam, k = np.asarray(prob.lam, np.float64), np.asarray(prob.k, np.float64)
    mu, m2, m3 = (np.asarray(x, np.float64) for x in (prob.moments.mu, prob.moments.m2,
                                                       prob.moments.m3))
    cost = rref.cell_moments(cfg)[3]
    theta = float(cfg["theta"])
    plans = np.asarray(rec["sols"].pi, np.float64)
    deployed = np.asarray(rec["pi"], np.float64)
    if control:
        plans, deployed = ref.as_bf16(plans), ref.as_bf16(deployed)
    same = [i for i in range(len(plans)) if np.array_equal(plans[i], deployed)]
    down = base._down(run.mix, deployed.shape[-1], rec["segment"])
    want_obj = ref.objective(deployed, lam, mu, m2, m3, cost, theta)[0]
    if control:
        reported = ref.objective(deployed, lam, mu, m2, m3, cost, theta, dtype=dtype)[0]
    else:
        reported = np.asarray(rec["sols"].objective)[same[0]] if same else np.inf

    carry, key, _, lam_roll, d, srv, avail = rec["rollout"]

    def roll(plan, walk=np.float32):
        return rollout.score(carry, key, plan, lam_roll, d, srv, avail,
                             int(planner["rollout_requests"]), cost, theta, walk)

    want = [roll(p) for p in plans]  # (rollout mean, score) of each candidate
    if control:
        got = [roll(p, dtype)[1] for p in plans]
        chosen = want[int(np.argmin(got))]
    else:
        got = np.asarray(rec["scores"], np.float64)[: len(plans)]
        chosen = want[same[0]] if same else roll(deployed)
    return {
        "plan_err": rref.feasibility_error(deployed, k, racks, down),
        "spread_err": float(rref.spread_count(deployed, racks, tol)),
        "obj_err": ref.relative_gap(reported, want_obj),
        "fw_gap": rref.fw_gap(deployed, lam, k, mu, m2, m3, cost, theta,
                              float(planner["beta"]), ~down, racks, tol),
        "score_err": max(abs(g - w[1]) / w[0] for g, w in zip(got, want)),
        "arb_regret": (chosen[1] - min(w[1] for w in want)) / chosen[0],
    }


def _summarize(run, dtype) -> tuple[list, int]:
    lim = run.mix["limits"]
    worst = {key: 0.0 for key in LIMIT_KEYS}
    failed = 0
    for rec in run.state.records:
        nums = _readings(rec, run, dtype)
        bad = False
        for key, val in nums.items():
            worst[key] = worse(worst[key], float(val))
            bad |= not val <= lim[key]
        failed += bad
    checks = [(key, worst[key], float(lim[key]), "largest over replans, at most")
              for key in LIMIT_KEYS]
    if not run.state.records:
        checks.append(("none_checked", 1.0, 0.0, "no replan came to be checked"))
        failed += 1
    return checks, failed


def check(run) -> tuple[list, int]:
    return _summarize(run, np.float64)


def control(run) -> tuple[list, int]:
    return _summarize(run, ref.BF16)
