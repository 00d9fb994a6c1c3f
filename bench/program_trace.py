"""The program's own spans, counters and device scopes in a run's trace.

The program writes spans and counters into the profiler's trace with
``repro.diag.span`` / ``repro.diag.count``: events on ``/host:CPU`` named
with a dotted layer prefix (``replan.solve``, ``codec.wait``), a counter's
number in its ``value`` stat and other numbers in stats of their own
(``codec.to_host``'s ``bytes``). Device code names its parts with
``jax.named_scope`` (``jlcm.iterate``, ``fleet.inputs``); on a v5e trace
each operation of the ``XLA Ops`` line carries its scope path in the
``tf_op`` stat of its event metadata, for example
``jit(_fleet_stream_batched)/while/body/fleet.inputs/vmap(jit(_uniform))/add:``.
``jax.profiler.ProfileData`` gives events and their own stats but not the
metadata's stats, so those are read here from the ``.xplane.pb`` itself
(protobuf wire format; only the device planes' metadata is decoded, the
event lines are skipped by length).

:func:`load` parses a run's trace once per process and keeps, within the
window ``bench/reduce.py`` uses (first to last benchmark span):

- each program span's intervals and stats, and each counter's values;
- device self time by named scope (an operation counts under every scope
  in its path);
- each device program's recorded runs, by module name;
- the device's idle gaps, so that :meth:`ProgramTrace.idle_within_s`
  gives the idle time inside a span by interval overlap: a gap that
  straddles two spans is split between them, where ``bench/reduce.py``
  gives it whole to the span over its midpoint.

A device trace can stop early: on a v5e the replan cell's solver writes
about 40,000 operation events a replan, and a 4 s trace of that cell
records the programs of 10 of its 42 replans. So the idle gaps are taken over the stretch the device trace covers (its
first to its last recorded program in the window), and an operation
counts only inside a recorded program run; a reader that divides device
time by work pairs it with the work whose programs were recorded.

A trace without the program's spans or scopes reads as empty: every
reader then returns None.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
import struct
import warnings
from pathlib import Path

import reduce

# where run.py writes a --trace 1 run's trace (its TRACE_DIR)
TRACE_DIR = Path(__file__).resolve().parents[1] / ".bench_trace"
# a program span, counter or scope name: lower-case words joined by dots
# (a runtime's ``copy.215`` or ``while.393`` is an HLO name, not one)
PROGRAM_NAME = re.compile(r"^[a-z_][a-z0-9_]*(?:\.[a-z_][a-z0-9_]*)+$")
SCOPE_STAT = "tf_op"

Interval = tuple[float, float]


def merge(intervals) -> list[Interval]:
    """The union of intervals as sorted, disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap_ns(a: list[Interval], b: list[Interval]) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def scopes_of(path: str) -> set[str]:
    """The named scopes in an operation's scope path, transforms peeled:
    ``jit(f)/while/body/transpose(jvp(jlcm.iterate))/mul:`` holds
    ``jlcm.iterate``."""
    return {t for t in re.split(r"[/():]", path) if PROGRAM_NAME.match(t)}


@dataclasses.dataclass
class ProgramTrace:
    """The program's side of one traced window."""

    spans: dict[str, list[Interval]]  # name -> (start_ns, end_ns) of each
    stats: dict[str, list[dict]]  # name -> the stats of each event
    scope_s: dict[str, float]  # named scope -> device self seconds
    idle: list[list[Interval]]  # per device, the idle gaps the trace covers
    # device program (``jit_<function>``) -> its recorded runs in the window
    modules: dict[str, list[Interval]] = dataclasses.field(default_factory=dict)

    def count(self, name: str) -> int:
        return len(self.spans.get(name, ()))

    def total_s(self, name: str) -> float:
        return sum(e - s for s, e in self.spans.get(name, ())) * 1e-9

    def mean_s(self, name: str) -> float | None:
        n = self.count(name)
        return self.total_s(name) / n if n else None

    def values(self, name: str, stat: str = "value") -> list[float]:
        """The stat ``stat`` of every event named ``name`` that has it."""
        return [float(d[stat]) for d in self.stats.get(name, ()) if stat in d]

    def scope_seconds(self, scope: str) -> float:
        return self.scope_s.get(scope, 0.0)

    def recorded(self, name: str, module: str) -> list[bool]:
        """For each span named ``name``: whether a recorded run of device
        program ``module`` (``jit_<function>``) overlaps it."""
        runs = merge(self.modules.get(module, ()))
        return [overlap_ns([(s, e)], runs) > 0 for s, e in self.spans.get(name, ())]

    def idle_s(self) -> float:
        """Device idle seconds in the stretch of the window the device trace
        covers, the mean over devices."""
        if not self.idle:
            return 0.0
        return sum(sum(e - s for s, e in g) for g in self.idle) / len(self.idle) * 1e-9

    def idle_within_s(self, name: str) -> float:
        """Device idle seconds inside the spans named ``name``, by overlap,
        the mean over devices."""
        if not self.idle:
            return 0.0
        inside = merge(self.spans.get(name, ()))
        return sum(overlap_ns(g, inside) for g in self.idle) / len(self.idle) * 1e-9


# ---------------------------------------------------------------------------
# Reading a trace.
# ---------------------------------------------------------------------------


def _varint(b: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b: bytes, i: int, end: int):
    """(field number, value) of one message: an int for a varint, a
    (start, end) slice for a length-delimited field, raw bytes otherwise."""
    while i < end:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, v


def _text(b: bytes, span: tuple[int, int]) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _stat(b: bytes, span, names: dict[int, str]) -> tuple[str | None, object]:
    """An XStat: (metadata_id=1; double=2, uint64=3, int64=4, str=5,
    bytes=6, ref=7)."""
    mid, value = None, None
    for f, v in _fields(b, *span):
        if f == 1:
            mid = v
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f in (3, 4):
            value = v
        elif f == 5:
            value = _text(b, v)
        elif f == 7:
            value = ("ref", v)
    if isinstance(value, tuple):  # a string kept once in the stat metadata
        value = names.get(value[1])
    return names.get(mid), value


def _map_entries(b: bytes, span):
    """The (key, value span) of one map<int64, message> entry."""
    key, val = None, None
    for f, v in _fields(b, *span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def device_scopes(data: bytes) -> dict[str, dict[tuple[int, str], str]]:
    """Per device plane: (program id, operation text) -> its scope path,
    from the ``tf_op`` stat of the plane's event metadata.

    XSpace.planes=1; XPlane: name=2, lines=3 (skipped), event_metadata=4,
    stat_metadata=5; XEventMetadata: name=2, stats=5."""
    out = {}
    for f, plane in _fields(data, 0, len(data)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(data, *plane):
            if pf == 2:
                name = _text(data, v)
            elif pf == 4:
                events.append(v)
            elif pf == 5:
                key, val = _map_entries(data, v)
                for sf, sv in _fields(data, *val):
                    if sf == 2:
                        stat_names[key] = _text(data, sv)
        if not name.startswith(reduce.DEVICE_PREFIX):
            continue
        scopes = {}
        for entry in events:
            _, val = _map_entries(data, entry)
            text, stats = "", {}
            for ef, ev in _fields(data, *val):
                if ef == 2:
                    text = _text(data, ev)
                elif ef == 5:
                    k, sv = _stat(data, ev, stat_names)
                    stats[k] = sv
            if SCOPE_STAT in stats:
                scopes[(int(stats.get("program_id") or 0), text)] = str(stats[SCOPE_STAT])
        out[name] = scopes
    return out


def _program_id(module_event_name: str) -> int:
    """``jit_f(16177138737253158946)`` -> 16177138737253158946."""
    m = re.search(r"\((\d+)\)\s*$", module_event_name)
    return int(m.group(1)) if m else 0


def read_trace(profile, data: bytes, host_names: set[str]) -> ProgramTrace:
    """Reduce a ``ProfileData`` (and its file's bytes, for the device
    metadata) to the program's side of the benchmark's window."""
    host = [p for p in profile.planes if p.name == reduce.HOST_PLANE]
    bench, mine = [], []
    with warnings.catch_warnings():  # ProfileData's stats type warns per read
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in host:
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name in host_names:
                        bench.append((e.start_ns, e.start_ns + e.duration_ns))
                    elif PROGRAM_NAME.match(name):
                        mine.append((name, e.start_ns, e.start_ns + e.duration_ns,
                                     dict(e.stats)))
    if not bench:
        raise ValueError("the trace holds none of the benchmark's spans")
    lo, hi = min(s for s, _ in bench), max(e for _, e in bench)
    spans: dict[str, list[Interval]] = {}
    stats: dict[str, list[dict]] = {}
    for name, s, e, st in mine:
        if lo <= s and e <= hi:
            spans.setdefault(name, []).append((s, e))
            stats.setdefault(name, []).append(st)

    scope_paths = device_scopes(data) if data else {}
    scope_s: dict[str, float] = {}
    idle, modules = [], {}
    for plane in profile.planes:
        if not plane.name.startswith(reduce.DEVICE_PREFIX):
            continue
        lines = {line.name: line for line in plane.lines}
        mods = sorted(
            (e.start_ns, e.duration_ns, e.name)
            for e in (lines[reduce.MODULE_LINE].events
                      if reduce.MODULE_LINE in lines else ())
            if e.start_ns + e.duration_ns > lo and e.start_ns < hi
        )
        if not mods:
            continue
        for s, d, name in mods:
            modules.setdefault(reduce.module_name(name), []).append((s, s + d))
        first, last = max(lo, mods[0][0]), min(hi, max(s + d for s, d, _ in mods))
        idle.append(reduce.gaps([(s, s + d) for s, d, _ in mods], first, last))
        paths = scope_paths.get(plane.name, {})
        if not paths or reduce.OPS_LINE not in lines:
            continue
        ops = [(e.start_ns, e.duration_ns, e.name)
               for e in lines[reduce.OPS_LINE].events if lo <= e.start_ns <= hi]
        starts = [m[0] for m in mods]
        tokens: dict[tuple[int, str], set[str]] = {}
        for start, name, ns in reduce.self_times(ops):
            i = bisect.bisect_right(starts, start) - 1
            if i < 0 or start > mods[i][0] + mods[i][1]:
                continue  # an operation of a program run the trace lost
            key = (_program_id(mods[i][2]), name)
            if key not in tokens:
                tokens[key] = scopes_of(paths.get(key, ""))
            for scope in tokens[key]:
                scope_s[scope] = scope_s.get(scope, 0.0) + ns * 1e-9
    return ProgramTrace(spans=spans, stats=stats, scope_s=scope_s, idle=idle,
                        modules=modules)


_CACHE: dict[tuple, ProgramTrace] = {}


def load_dir(trace_dir: Path, host_names: set[str]) -> ProgramTrace:
    """The program's side of the trace under ``trace_dir``, parsed once
    per process for a given file and window."""
    path = reduce.find_xplane(trace_dir)
    key = (str(path.resolve()), path.stat().st_mtime_ns, frozenset(host_names))
    if key not in _CACHE:
        from jax.profiler import ProfileData

        data = path.read_bytes()
        _CACHE.clear()
        _CACHE[key] = read_trace(ProfileData.from_serialized_xspace(data), data,
                                 set(host_names))
    return _CACHE[key]


def load(run) -> ProgramTrace | None:
    """The program's side of a ``--trace 1`` run's trace; None without one."""
    if not run.trace or run.reduced is None:
        return None
    return load_dir(TRACE_DIR, run.spans.names())
