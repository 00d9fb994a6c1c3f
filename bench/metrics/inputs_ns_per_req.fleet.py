"""Device busy time outside the Pallas FCFS kernel per simulated request
of the traced window: input generation (arrivals, marks, Madow samples,
service draws) and the streaming statistics' fold."""

KERNEL = "fcfs_scan_pallas"


def read(run):
    r = run.reduced
    n = run.counters.get("attempted", 0)
    if r is None or not n or r.busy_s <= 0:
        return None
    return (r.busy_s - r.op_seconds(KERNEL)) / n * 1e9
