"""Share of the HBM roofline the rack-capped projection reaches: the least
time the chip could read each projection's input and mask and write its
output once (``rack_roofline.projection_bytes``, at the peak HBM rate of
bench/peaks.json), over the device self time under the ``jlcm.project``
scope. Counted over the replans whose solve program the trace recorded:
each trip of the batched loop projects three times (the step and its two
backtracking probes, which the vmapped ``lax.cond`` runs as selects), over
every candidate lane (``solver.lanes``). None where the program has no
such scope."""

import program_trace
import rack_roofline

SOLVE = "jit__solve_merged_device_batch"


def read(run):
    t = program_trace.load(run)
    dev = t.scope_seconds("jlcm.project") if t else 0.0
    if dev <= 0:
        return None
    trips, lanes = t.values("solver.trips"), t.values("solver.lanes")
    kept = t.recorded("replan.solve", SOLVE)
    if not (len(trips) == len(lanes) == len(kept)):
        return None
    cell = run.config["cell"]
    m = int(cell["racks"]) * int(cell["hosts_per_rack"])
    r = int(run.config["catalog"]["r"])
    least = sum(
        rack_roofline.PROJECTIONS_PER_TRIP * n * rack_roofline.projection_bytes(int(b), r, m)
        for n, b, k in zip(trips, lanes, kept) if k
    ) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / dev if least else None
