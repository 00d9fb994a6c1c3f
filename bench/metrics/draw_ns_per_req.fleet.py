"""Device self time of the fleet's input generation per simulated
request: operations under the ``fleet.inputs`` scope (arrivals, marks,
Madow dispatch samples, service draws)."""

import program_trace


def read(run):
    t = program_trace.load(run)
    n = run.counters.get("attempted", 0)
    dev = t.scope_seconds("fleet.inputs") if t else 0.0
    return dev / n * 1e9 if n and dev > 0 else None
