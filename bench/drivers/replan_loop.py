"""Closed-loop replanning under a failure schedule (mix driver).

The mix names a schedule of segments and node failures; each step of
the window runs it once over the configuration's catalog through the
program's scenario engine (``run_scenario``, adaptive policy), from a
fresh seed, with the initial plan solved once in set-up. Every call of
``AdaptiveReplanner.replan`` is timed by a span from this side and its
candidate solve, arbitration and deployed plan are kept for the check.

End to end: ``replan_p95_ms``, the 95th percentile of every replan in
the window. Check, for every replan in the window, against plain
references that take the problem the loop posed (its estimated rates and
moments, the schedule's down nodes) and the configuration's numbers:

- ``plan_err``: the deployed plan is feasible, with no mass on a node the
  schedule has down;
- ``obj_err``: the objective the solver reported for it is the float64
  Eq. (9) objective of that plan;
- ``fw_gap``: how far the deployed plan is from stationary on the
  smoothed problem (its Frank-Wolfe gap over its latency bound), so an
  early-stopped or cut solve reads high;
- ``score_err``: every candidate's arbitration score against a plain
  rollout of the configured length from the replan's queue state and key;
- ``arb_regret``: how much worse, by those reference scores, the deployed
  plan is than the best candidate.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

import deploy
from harness import quantile, worse
from reference import plan as ref
from reference import rollout

LIMIT_KEYS = ("plan_err", "obj_err", "fw_gap", "score_err", "arb_regret")


@dataclasses.dataclass
class State:
    spec: object
    cluster: object
    pi0: np.ndarray
    restore: list
    seeds: object
    records: list = dataclasses.field(default_factory=list)
    recording: bool = False
    replan_idx: int = 0


def _scenario_spec(run, lam, k, chunk_mb):
    from repro.scenarios import ScenarioSpec

    sc = run.mix["scenario"]
    return ScenarioSpec(
        name=run.cell["traffic"],
        description=run.mix["why"],
        probes="closed-loop replan latency",
        expected="every replan deploys a feasible plan",
        n_segments=int(sc["n_segments"]),
        requests_per_segment=int(sc["requests_per_segment"]),
        chunk_mb=float(chunk_mb),
        lam=tuple(float(x) for x in lam),
        k=tuple(float(x) for x in k),
        theta=float(run.config["theta"]),
        replan_every=1,
        failures=tuple(tuple(int(v) for v in f) for f in sc["failures"]),
    )


def _down(mix, m: int, segment: int) -> np.ndarray:
    """Nodes the mix's schedule has down in ``segment``."""
    down = np.zeros(m, bool)
    for node, first, last in mix["scenario"]["failures"]:
        if first <= segment <= last:
            down[node] = True
    return down


def _install(run, state: State) -> None:
    """Wrap the replan, its batched solve and its arbitration, from this
    side: spans and captures only, the calls themselves unchanged."""
    from repro.serving import AdaptiveReplanner, router

    orig_replan = AdaptiveReplanner.replan
    orig_solve = router.solve_batch
    orig_score = router.batched_rollout_scores
    call: dict = {}

    @functools.wraps(orig_solve)
    def solve_batch(probs, **kw):
        sols = orig_solve(probs, **kw)
        call["probs"], call["sols"] = probs, sols
        return sols

    @functools.wraps(orig_score)
    def scores(*a, **kw):
        out = orig_score(*a, **kw)
        call["scores"], call["best"] = out
        call["rollout"] = a[:7]  # carry, key, plans, rates, d, service rates, avail
        return out

    @functools.wraps(orig_replan)
    def replan(self, class_rates, avail, **kw):
        call.clear()
        with run.spans.span("replan"):
            pi = orig_replan(self, class_rates, avail, **kw)
        total = run.spans.get("replan")[-1]
        solve_s, arb_s = self.solve_walls[-1], self.rollout_walls[-1]
        run.spans.add("solve", solve_s)
        run.spans.add("arbitration", arb_s)
        run.spans.add("replan_host", total - solve_s - arb_s)
        run.count("solver_iters", self.solve_iters[-1])
        run.count("attempted")
        state.replan_idx += 1
        if state.recording:
            state.records.append(dict(call, pi=pi, segment=state.replan_idx))
        return pi

    AdaptiveReplanner.replan = replan
    router.solve_batch = solve_batch
    router.batched_rollout_scores = scores
    state.restore = [
        (AdaptiveReplanner, "replan", orig_replan),
        (router, "solve_batch", orig_solve),
        (router, "batched_rollout_scores", orig_score),
    ]


def setup(run) -> State:
    import jax.numpy as jnp

    from repro.core import JLCMProblem, solve

    cfg = run.config
    cluster = deploy.cluster(cfg)
    lam, k, chunk = deploy.paper_catalog(cfg)
    eff = deploy.effective_chunk_mb(lam, chunk)
    spec = _scenario_spec(run, lam, k, eff)
    planner = cfg["planner"]
    sol = solve(
        JLCMProblem(
            lam=jnp.asarray(lam, jnp.float32), k=jnp.asarray(k, jnp.float32),
            moments=cluster.moments(eff), cost=cluster.cost, theta=float(cfg["theta"]),
        ),
        max_iters=int(planner["max_iters"]), eps=float(planner["eps"]),
    )
    rng = run.rng("scenario")
    state = State(
        spec=spec, cluster=cluster, pi0=np.asarray(sol.pi), restore=[],
        seeds=iter(lambda: int(rng.integers(0, 2**31 - 2**16)), None),
    )
    _install(run, state)
    _scenario(run, state, int(run.rng("warm-up").integers(0, 2**31 - 2**16)))
    state.recording = True
    return state


def _scenario(run, state: State, seed: int):
    from repro.scenarios import run_scenario

    state.replan_idx = 0
    with run.spans.span("scenario"):
        return run_scenario(
            state.spec, "adaptive", seed=seed, cluster=state.cluster, pi0=state.pi0
        )


def step(run) -> None:
    _scenario(run, run.state, next(run.state.seeds))


def end_to_end(run) -> dict:
    return {"replan_p95_ms": quantile(run.spans.get("replan"), 0.95) * 1e3}


def release(run) -> None:
    for owner, name, orig in run.state.restore:
        setattr(owner, name, orig)


def _readings(rec, run, dtype) -> dict:
    """The numbers compared for one replan; with ``dtype`` below float64
    the reference computed in it stands in the program's place (the
    control): its plans held in that precision, its objective and its
    rollouts computed in it, its choice the lowest of its own scores."""
    cfg, planner = run.config, run.config["planner"]
    control = dtype != np.float64
    prob = rec["probs"][0]  # the candidates differ only in their start
    lam, k = np.asarray(prob.lam, np.float64), np.asarray(prob.k, np.float64)
    mu, m2, m3 = (np.asarray(x, np.float64) for x in (prob.moments.mu, prob.moments.m2,
                                                       prob.moments.m3))
    cost = ref.testbed_moments(cfg, 1.0)[3]
    theta = float(cfg["theta"])
    plans = np.asarray(rec["sols"].pi, np.float64)
    deployed = np.asarray(rec["pi"], np.float64)
    if control:
        plans, deployed = ref.as_bf16(plans), ref.as_bf16(deployed)
    same = [i for i in range(len(plans)) if np.array_equal(plans[i], deployed)]
    down = _down(run.mix, deployed.shape[-1], rec["segment"])
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
        "plan_err": ref.feasibility_error(deployed, k, down),
        "obj_err": ref.relative_gap(reported, want_obj),
        "fw_gap": ref.fw_gap(deployed, lam, k, mu, m2, m3, cost, theta,
                             float(planner["beta"]), ~down),
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
