"""Device self time of the fleet's streaming statistics per simulated
request: operations under the ``fleet.stats`` scope (moments, maximum and
the quantile sketch's fold, whole run and window)."""

import program_trace


def read(run):
    t = program_trace.load(run)
    n = run.counters.get("attempted", 0)
    dev = t.scope_seconds("fleet.stats") if t else 0.0
    return dev / n * 1e9 if n and dev > 0 else None
