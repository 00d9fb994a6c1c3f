"""Device time of the rack-capped projection per trip of the batched
solver's while loop: device self time of the operations under the
``jlcm.project`` scope (the projections of the loop body) over the
``solver.trips`` of the replans whose solve program the trace recorded (a
device trace can stop before the window ends). None where the program
has no such scope."""

import program_trace

SOLVE = "jit__solve_merged_device_batch"


def read(run):
    t = program_trace.load(run)
    dev = t.scope_seconds("jlcm.project") if t else 0.0
    if dev <= 0:
        return None
    trips = t.values("solver.trips")
    kept = t.recorded("replan.solve", SOLVE)
    if len(trips) != len(kept):
        return None
    n = sum(v for v, k in zip(trips, kept) if k)
    return dev / n * 1e6 if n else None
