"""Mean wall of a replan's rollout arbitration, as the program's
``AdaptiveReplanner.rollout_walls`` records it (ends in the replan's one
host sync)."""


def read(run):
    v = run.spans.mean("arbitration")
    return None if v is None else v * 1e3
