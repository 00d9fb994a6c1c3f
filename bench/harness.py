"""Shared pieces of one benchmark run: spans, compile counting, the
measured window, the device record and the result line.

Nothing here knows a configuration, a traffic mix or a metric by name:
`run.py` finds those as files and hands them a :class:`Run`.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import sys
import time
from typing import Any, Callable

# jax.monitoring event names (jax._src.dispatch): one lowering per program
# JAX builds, one backend compile per program that the persistent cache
# did not hold
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(SystemExit):
    """A run that cannot give a result: exits non-zero, prints none."""

    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}")


@dataclasses.dataclass
class Spans:
    """Host-clock spans recorded from the benchmark's side of each call
    into a layer, kept in memory: name -> list of seconds."""

    closed: dict[str, list[float]] = dataclasses.field(default_factory=dict)
    # also write each span into the profiler's trace (a --trace 1 run), so
    # the reduction can say what the host did in each idle gap
    annotate: bool = False

    @contextlib.contextmanager
    def span(self, name: str):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
        t0 = time.perf_counter()
        try:
            with ann:
                yield
        finally:
            self.add(name, time.perf_counter() - t0)

    def add(self, name: str, seconds: float) -> None:
        self.closed.setdefault(name, []).append(float(seconds))

    def get(self, name: str) -> list[float]:
        return self.closed.get(name, [])

    def names(self) -> set[str]:
        return set(self.closed)

    def mean(self, name: str) -> float | None:
        v = self.get(name)
        return sum(v) / len(v) if v else None


class CompileCounter:
    """Counts the programs JAX lowers and compiles while it is active."""

    def __init__(self) -> None:
        self.lowered = 0
        self.compiled = 0
        self.active = False

    def _listener(self, event: str, duration: float, **_: Any) -> None:
        if not self.active:
            return
        if event == LOWER_EVENT:
            self.lowered += 1
        elif event == COMPILE_EVENT:
            self.compiled += 1

    def install(self) -> None:
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listener)


@dataclasses.dataclass
class Run:
    """Everything one run of one cell shares with its driver and readers."""

    cell: dict
    config: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float  # perf_counter at process start
    spans: Spans = dataclasses.field(default_factory=Spans)
    counters: dict[str, float] = dataclasses.field(default_factory=dict)
    # filled by run.py: the reduced device trace of a --trace 1 run
    reduced: Any = None
    window_s: float = 0.0
    state: Any = None
    peaks: dict | None = None  # bench/peaks.json entry of this chip

    def count(self, name: str, n: float = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n

    def rng(self, stream: str):
        """A NumPy generator for one named purpose, drawn from the seed:
        the same seed gives the same draws, whatever else the run does."""
        import zlib

        import numpy as np

        return np.random.default_rng(
            [int(self.seed) & (2**63 - 1), zlib.crc32(stream.encode())]
        )


def measure_window(step: Callable[[], Any], seconds: float) -> tuple[float, int]:
    """Call ``step`` until ``seconds`` have passed; every step finishes.

    Returns the window's length, from the first call to the end of the
    last one, and the number of steps. Each step ends in a host sync, so
    the window holds all the work that was started in it."""
    t0 = time.perf_counter()
    n = 0
    while True:
        step()
        n += 1
        if time.perf_counter() - t0 >= seconds:
            break
    return time.perf_counter() - t0, n


def quantile(values: list[float], q: float) -> float:
    """Order statistic of rank ceil(q n): a tail over every sample."""
    v = sorted(values)
    if not v:
        return math.nan
    return v[max(0, min(len(v) - 1, math.ceil(q * len(v)) - 1))]


def worse(a: float, b: float) -> float:
    """The larger of two readings compared against a limit; a NaN, once
    read, stays."""
    return a if a != a else (b if not b <= a else a)


def device_record(peak_bytes: int | None) -> dict:
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": peak_bytes,
    }


def peak_memory_bytes() -> int | None:
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def emit(result: dict, checks: list[tuple[str, float, float, str]]) -> None:
    """Print the compared numbers beside their limits, last on standard
    error, and the result line last on standard output with the checks
    under the key that comes last."""
    for name, value, limit, how in checks:
        print(f"check {name}: {value!r} (limit {limit!r}, {how})",
              file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = {
        name: {"value": value, "limit": limit} for name, value, limit, _ in checks
    }
    print(json.dumps(result, allow_nan=True), flush=True)


class Reservoir:
    """A uniform sample of ``size`` items from a stream of unknown length,
    drawn with ``rng`` (so from the run's seed)."""

    def __init__(self, size: int, rng) -> None:
        self.size = size
        self.rng = rng
        self.seen = 0
        self.items: list = []

    def offer(self, make: Callable[[], Any]) -> None:
        """Count one item; keep it (``make()``) if the sample takes it."""
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append(make())
            return
        j = int(self.rng.integers(0, self.seen))
        if j < self.size:
            self.items[j] = make()
