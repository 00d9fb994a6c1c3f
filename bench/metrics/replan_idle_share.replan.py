"""Share of the device's idle time that lies inside the program's
``replan.step`` spans, by interval overlap (a gap that straddles a span's
edge counts only its part inside), in %, over the stretch of the window
the device trace covers."""

import program_trace


def read(run):
    t = program_trace.load(run)
    if not t or not t.count("replan.step") or t.idle_s() <= 0:
        return None
    return 100.0 * t.idle_within_s("replan.step") / t.idle_s()
