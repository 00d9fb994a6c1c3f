"""Mean host part of a replan's batched solve: the program's
``replan.solve`` span less the ``replan.solve_wait`` inside it (stacking
the candidate problems and dispatching the solver, ``solve.stack`` and
``solve.dispatch``)."""

import program_trace


def read(run):
    t = program_trace.load(run)
    n = t.count("replan.solve") if t else 0
    if not n or t.count("replan.solve_wait") != n:
        return None
    return (t.total_s("replan.solve") - t.total_s("replan.solve_wait")) / n * 1e3
