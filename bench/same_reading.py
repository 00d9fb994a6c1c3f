"""The rack cell's per-layer metrics that measure what a ``.replan``
metric measures, on the same spans, counters and scopes of the same
program path: their readers are that metric's reader, loaded from its
file in ``bench/metrics/``, so one reading is written once."""
from __future__ import annotations

import importlib.util
from pathlib import Path

METRICS = Path(__file__).resolve().parent / "metrics"


def reader(name: str):
    """The ``read`` function of per-layer metric ``name``'s own file."""
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
