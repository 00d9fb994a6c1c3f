"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time,
device time per operation and idle gaps by what the host was doing.

What a TPU trace holds (read by hand from a v5e trace): the plane
``/device:TPU:0`` has a line ``XLA Modules`` with one event per program
run, named ``jit_<function>(<hash>)``, and a line ``XLA Ops`` with one
event per operation, named by its HLO text: ``%fusion.224 = f32[...]
fusion(...)``. Operations nest (a ``while`` covers the operations of its
body). A Pallas kernel is an operation ``%<kernel name>.<n> = ...
custom-call(...), custom_call_target="tpu_custom_call"``, for example
``%fcfs_scan_pallas.12``. Host threads live on ``/host:CPU``; spans the
benchmark writes with ``jax.profiler.TraceAnnotation`` appear there under
their own names, on the same clock as the device.

- Busy time is the union of the program intervals of each device, so
  programs that overlap count once; the idle share is 1 - busy / window.
- Device time per operation is its self time: its duration less the part
  its nested operations cover.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path
from typing import Iterable

MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
DEVICE_PREFIX = "/device:TPU:"

Interval = tuple[float, float]  # (start_ns, end_ns)


def union_ns(intervals: Iterable[Interval]) -> float:
    """Length of the union of intervals: overlaps count once."""
    total = 0.0
    end = None
    start = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            if end is not None:
                total += end - start
            start, end = s, e
        else:
            end = max(end, e)
    if end is not None:
        total += end - start
    return total


def gaps(intervals: Iterable[Interval], lo: float, hi: float) -> list[Interval]:
    """The stretches of [lo, hi] that no interval covers."""
    out = []
    cur = lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def op_name(hlo_text: str) -> str:
    """``%fcfs_scan_pallas.12 = (...) custom-call(...)`` -> ``fcfs_scan_pallas.12``."""
    return hlo_text.split(" ", 1)[0].lstrip("%")


def kernel_of(op: str) -> str:
    """``fcfs_scan_pallas.12`` -> ``fcfs_scan_pallas``."""
    return re.sub(r"\.\d+$", "", op)


def module_name(event_name: str) -> str:
    """``jit__fleet_stream_batched(1617...)`` -> ``jit__fleet_stream_batched``."""
    return event_name.split("(", 1)[0]


def self_times(
    events: list[tuple[float, float, str]],
) -> list[tuple[float, str, float]]:
    """(start, name, self ns) of nested events on one line: each event's
    duration less the durations of the events directly inside it."""
    out = []
    stack: list[list] = []  # [end, start, name, child_ns, dur]
    for s, d, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and s >= stack[-1][0]:
            _, s0, n, child, dur = stack.pop()
            out.append((s0, n, dur - child))
        if stack:
            stack[-1][3] += d
        stack.append([s + d, s, name, 0.0, d])
    while stack:
        _, s0, n, child, dur = stack.pop()
        out.append((s0, n, dur - child))
    return out


@dataclasses.dataclass
class Reduced:
    """One traced window, reduced."""

    window_s: float  # first to last event of the benchmark's spans
    busy_s: float  # mean over devices of the union of program intervals
    op_s: dict[str, float]  # device self seconds by module/op name
    idle_by_host: dict[str, float]  # idle seconds by the host span over it
    n_devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, kernel: str) -> float:
        """Device seconds of the operations named ``kernel`` (a Pallas
        kernel's name, without the ``.<n>`` HLO suffix), in every program."""
        return sum(
            v for k, v in self.op_s.items()
            if kernel_of(k.rsplit("/", 1)[-1]) == kernel
        )

    def breakdown(self) -> dict:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        idle = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:10]
        return {
            "device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in idle],
        }


def _events(line) -> list[tuple[float, float, str]]:
    return [(e.start_ns, e.duration_ns, e.name) for e in line.events]


def reduce_profile(profile, host_names: set[str] | None = None) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData``.

    ``host_names`` are the benchmark's span names: the window runs from
    the first of them to the end of the last, and each idle gap is put
    down to the innermost of them that covers it ("host" where none
    does)."""
    devices = [p for p in profile.planes if p.name.startswith(DEVICE_PREFIX)]
    host_spans: list[tuple[float, float, str]] = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for s, d, name in _events(line):
                if host_names is None or name in host_names:
                    host_spans.append((s, d, name))
    busy, ops, idle = [], {}, {}
    lo = min((s for s, _, _ in host_spans), default=None)
    hi = max((s + d for s, d, _ in host_spans), default=None)
    for plane in devices:
        lines = {line.name: _events(line) for line in plane.lines}
        mods = sorted(lines.get(MODULE_LINE, []))
        ivals = [(s, s + d) for s, d, _ in mods]
        if lo is None and ivals:
            lo, hi = min(s for s, _ in ivals), max(e for _, e in ivals)
        if lo is not None:  # the window's part of each program
            ivals = [(max(s, lo), min(e, hi)) for s, e in ivals if e > lo and s < hi]
        busy.append(union_ns(ivals))
        for start, name, ns in self_times(lines.get(OPS_LINE, [])):
            key = f"{_module_at(mods, start)}/{op_name(name)}"
            ops[key] = ops.get(key, 0.0) + ns * 1e-9
        for g0, g1 in gaps(ivals, lo, hi):
            mid = 0.5 * (g0 + g1)
            over = [(d, n) for s, d, n in host_spans if s <= mid <= s + d]
            who = min(over)[1] if over else "host"
            idle[who] = idle.get(who, 0.0) + (g1 - g0) * 1e-9 / len(devices)
    if lo is None:
        raise ValueError("the trace holds no device program and no span")
    return Reduced(
        window_s=(hi - lo) * 1e-9,
        busy_s=(sum(busy) / len(busy) * 1e-9) if busy else 0.0,
        op_s=ops,
        idle_by_host=idle,
        n_devices=len(devices),
    )


def _module_at(mods: list[tuple[float, float, str]], t: float) -> str:
    """The program running at ``t`` (``mods`` sorted by start)."""
    i = bisect.bisect_right(mods, (t, float("inf"), "")) - 1
    if i >= 0 and mods[i][0] <= t <= mods[i][0] + mods[i][1]:
        return module_name(mods[i][2])
    return "?"


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir: Path, host_names: set[str] | None = None) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_profile(
        ProfileData.from_file(str(find_xplane(trace_dir))), host_names
    )
