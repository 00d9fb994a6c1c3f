"""Mean time the host waits on the device for a replan's batched solve:
the program's ``replan.solve_wait`` span (``block_until_ready`` on the
candidate plans)."""

import program_trace


def read(run):
    t = program_trace.load(run)
    v = t.mean_s("replan.solve_wait") if t else None
    return None if v is None else v * 1e3
