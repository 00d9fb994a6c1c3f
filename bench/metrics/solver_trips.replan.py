"""Mean trip count of the batched solver's while loop per replan: the
program's ``solver.trips`` counter, the most iterations of any candidate
lane (the vmapped loop runs until its slowest lane stops)."""

import program_trace


def read(run):
    t = program_trace.load(run)
    v = t.values("solver.trips") if t else []
    return sum(v) / len(v) if v else None
