"""Bytes the rack-capped projection has to move, computed from shapes: the
yardstick of ``project_roofline.rack``, the same whichever backend runs."""
from __future__ import annotations

# the batched solver's trip: the step and its two backtracking probes, all
# three projected (under vmap the lax.cond between them runs as selects)
PROJECTIONS_PER_TRIP = 3


def projection_bytes(lanes: int, r: int, m: int) -> int:
    """Least bytes one projection of ``lanes`` stacked (r, m) plans moves:
    the float32 input read, the boolean mask read and the float32 output
    written, once each."""
    return int(lanes) * int(r) * int(m) * (4 + 1 + 4)
