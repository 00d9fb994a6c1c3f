#!/usr/bin/env python3
"""``readings.py`` with the rack cell's planted faults beside those of
``faults.py``:

    python3 bench/rack_readings.py --workload replan.f4-rack-failure \
        --seeds 1,2,3 [--control-seeds ...] [--fault <name>] [--out <dir>]

Every option is ``readings.py``'s; ``--fault`` may also name a fault of
``rack_faults.py``.
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import faults  # noqa: E402
import rack_faults  # noqa: E402
import readings  # noqa: E402

if __name__ == "__main__":
    for fault in rack_faults.RACK_FAULTS:
        setattr(faults, fault.__name__, fault)
    sys.exit(readings.main())
