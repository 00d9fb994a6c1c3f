"""Mean host part of a replan: the benchmark's span around
``AdaptiveReplanner.replan`` less its solve and arbitration walls
(estimator, candidate assembly, transfers)."""


def read(run):
    v = run.spans.mean("replan_host")
    return None if v is None else v * 1e3
