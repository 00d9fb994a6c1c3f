"""Mean host wall of the closed loop's own work per segment, outside the
replan: the program's ``loop.simulate`` (the segment's simulation and its
results to the host) plus ``loop.observe`` (the moment and rate
estimators), over the segments simulated in the window."""

import program_trace


def read(run):
    t = program_trace.load(run)
    n = t.count("loop.simulate") if t else 0
    if not n:
        return None
    return (t.total_s("loop.simulate") + t.total_s("loop.observe")) / n * 1e3
