"""Mean solver iterations of the deployed candidate per replan, as the
program's ``AdaptiveReplanner.solve_iters`` counts them."""


def read(run):
    n = run.counters.get("attempted", 0)
    return run.counters["solver_iters"] / n if n else None
