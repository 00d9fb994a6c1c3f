"""Rate of the decoded batches' copy to the host: the ``bytes`` of the
program's ``codec.to_host`` spans over their seconds (the copy alone; the
wait for the decode is ``codec.wait``)."""

import program_trace


def read(run):
    t = program_trace.load(run)
    if not t:
        return None
    moved, secs = sum(t.values("codec.to_host", "bytes")), t.total_s("codec.to_host")
    return moved / secs / 1e9 if moved and secs > 0 else None
