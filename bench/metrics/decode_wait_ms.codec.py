"""Time the host waits on the device's decode per batch: the program's
``codec.wait`` spans (``block_until_ready`` on each decoded group) over
the batches of the window (the benchmark's ``decode`` spans)."""

import program_trace


def read(run):
    t = program_trace.load(run)
    batches = len(run.spans.get("decode"))
    if not t or not t.count("codec.wait") or not batches:
        return None
    return t.total_s("codec.wait") / batches * 1e3
