"""Device time of the Pallas FCFS kernel (``fcfs_scan_pallas``
custom calls in the trace) per simulated request of the traced window."""

KERNEL = "fcfs_scan_pallas"


def read(run):
    r = run.reduced
    n = run.counters.get("attempted", 0)
    if r is None or not n:
        return None
    t = r.op_seconds(KERNEL)
    return t / n * 1e9 if t > 0 else None
