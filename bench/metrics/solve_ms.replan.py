"""Mean wall of a replan's batched candidate solve, as the program's
``AdaptiveReplanner.solve_walls`` records it (ends in block_until_ready)."""


def read(run):
    v = run.spans.mean("solve")
    return None if v is None else v * 1e3
