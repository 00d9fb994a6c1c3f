"""Runtime diagnostics: spans and counters on the profiler's clock,
host-sync tripwires and compile-reuse watchers.

**Spans and counters.** :class:`span` times a region on the host
(``perf_counter``, exposed as ``.seconds`` for the program's own
telemetry) and writes it into the JAX profiler's trace as a
``jax.profiler.TraceAnnotation``: under an active trace
(``jax.profiler.trace(dir)``) it lands on ``/host:CPU`` on the same clock
as the device planes; with no trace active it costs about a microsecond
and leaves nothing behind. :func:`count` writes an instant annotation
with a ``value`` stat, a counter attributable to the span around it.
:func:`scope` names a region of device code, so each XLA operation's
device time can be read by the scope it came from.
Every program span name carries a dotted layer prefix (``replan.solve``,
``codec.wait``); ``docs/diagnostics.md`` lists them all and what each
answers.

The closed loop only hits its latency targets while two contracts hold:

* **one host sync per replan** — candidate arbitration, fleet simulation,
  and the merged-mode solver stay on device; results cross to the host
  once, at a deliberate materialization point (PR 9's
  ``batched_rollout_scores`` argmin, ``solve``'s end-of-solve trace trim);
* **one program per shape** — repeated replans reuse one compiled XLA
  executable (candidate lanes pad to a power of two, incremental
  re-solves pad moved rows) instead of recompiling per call.

`tools/jaxcheck` enforces both statically in CI; this module is the
*runtime* half: guards that make a violated contract fail loudly in a
live run instead of silently costing milliseconds per segment.

The guards are inert unless ``REPRO_DIAG=1`` (checked per call, so a
test can flip it with ``monkeypatch.setenv``): disarmed, the
:func:`hot_path` wrapper costs one ``os.environ`` lookup and its span.

Guard mechanics (:func:`hot_path`, usable as decorator or context
manager; it always opens a :class:`span` under its label):

* ``jax.transfer_guard_device_to_host("disallow")`` — the real device
  guard. On an accelerator every implicit device->host readback inside
  the guarded region raises. On the CPU backend device buffers alias
  host memory, so XLA never routes readbacks through the transfer guard
  — which is why the second tripwire exists.
* a **numpy materialization tripwire** — ``np.asarray`` / ``np.array`` /
  ``np.asanyarray`` / ``np.ascontiguousarray`` are patched for the
  duration of the guarded region to raise :class:`HostSyncError` when
  handed a ``jax.Array``. This catches the repo's dominant host-sync
  idiom on *every* backend, including 1-core CPU CI. Scalar coercions
  (``float(x)``, ``int(x)``, ``x.item()``) on CPU are zero-copy and
  cannot be intercepted at runtime; rule JX001 of `tools/jaxcheck`
  covers those statically.

Compile mechanics (:class:`CompileWatcher`): snapshots the executable
cache size (``_cache_size()``) of jitted callables on entry and exposes
the per-function growth, replacing hand-written
``fn._cache_size() == n`` asserts with a reusable fixture that survives
warmup compiles happening before the watched region.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import threading
import time
from typing import Any, Callable

import jax
import numpy as np
from jax.experimental.xla_metadata import set_xla_metadata

__all__ = [
    "CompileWatcher",
    "HostSyncError",
    "RecompileError",
    "count",
    "enabled",
    "hot_path",
    "hot_path_registry",
    "scope",
    "span",
]


class HostSyncError(RuntimeError):
    """A guarded hot path materialized a device array on the host."""


class RecompileError(RuntimeError):
    """A watched compiled function retraced when reuse was required."""


def enabled() -> bool:
    """True when runtime diagnostics are armed (``REPRO_DIAG=1``).

    Read from the environment on every call — cheap, and lets tests
    flip the switch after import with ``monkeypatch.setenv``.
    """
    return os.environ.get("REPRO_DIAG", "").strip().lower() in {
        "1", "true", "on", "yes",
    }


# ---------------------------------------------------------------------------
# Spans and counters on the profiler's clock.
# ---------------------------------------------------------------------------


class span:
    """Time a region on the host and write it into the profiler's trace.

    ``with span("replan.solve") as sp: ...`` leaves the region's host
    seconds in ``sp.seconds``; under an active profiler trace the region
    also appears on ``/host:CPU`` as an event named ``name`` whose stats
    are ``stats`` (``span("codec.to_host", bytes=n)``).
    """

    __slots__ = ("name", "stats", "seconds", "_ann", "_t0")

    def __init__(self, name: str, **stats: Any):
        self.name = name
        self.stats = stats
        self.seconds = 0.0

    def __enter__(self) -> "span":
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.stats)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(exc_type, exc, tb)
        return False


@contextlib.contextmanager
def scope(name: str):
    """Name a region of device code: every operation traced inside carries
    ``name`` in its scope path (``jax.named_scope``; on a TPU trace the
    ``tf_op`` stat of the operation's metadata) and in an XLA frontend
    attribute, ``repro_scope``. The persistent compilation cache keys on
    the program with its debug metadata stripped, so a scope path alone
    would not tell an executable compiled before the scope existed from
    one compiled after; the attribute is part of the program and does.
    Neither changes the compiled operations."""
    with jax.named_scope(name), set_xla_metadata(repro_scope=name):
        yield


def count(name: str, value: int | float) -> None:
    """Write counter ``name`` with ``value`` into the profiler's trace: an
    instant event with a ``value`` stat, inside whatever span is open."""
    with jax.profiler.TraceAnnotation(name, value=value):
        pass


# ---------------------------------------------------------------------------
# Hot-path registry: the names `tools/jaxcheck` treats as device hot paths.
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, "HotPathStats"] = {}
_LOCK = threading.Lock()


@dataclasses.dataclass
class HotPathStats:
    """Per-label guard accounting for a registered hot path (each call is
    a span of the same label in the profiler's trace)."""

    label: str
    guarded_calls: int = 0
    recompiles: int = 0  # cache growth observed after the warmup call
    _sizes: dict[int, int] = dataclasses.field(default_factory=dict)


def hot_path_registry() -> dict[str, HotPathStats]:
    """Live view of every registered hot path (label -> stats)."""
    return _REGISTRY


def _stats(label: str) -> HotPathStats:
    with _LOCK:
        return _REGISTRY.setdefault(label, HotPathStats(label))


# ---------------------------------------------------------------------------
# The numpy materialization tripwire.
# ---------------------------------------------------------------------------

_NP_FUNCS = ("asarray", "array", "asanyarray", "ascontiguousarray")
_tripwire_depth = 0


def _is_device_array(x: Any) -> bool:
    return isinstance(x, jax.Array)


@contextlib.contextmanager
def _numpy_tripwire(label: str):
    """Patch numpy's materializers to reject ``jax.Array`` inputs.

    Re-entrant (nested hot paths patch once); single-threaded by design —
    REPRO_DIAG is a diagnostics mode, not a production default.
    """
    global _tripwire_depth
    if _tripwire_depth > 0:
        _tripwire_depth += 1
        try:
            yield
        finally:
            _tripwire_depth -= 1
        return

    originals = {name: getattr(np, name) for name in _NP_FUNCS}

    def _make(name: str, orig: Callable):
        @functools.wraps(orig)
        def guarded(a, *args, **kwargs):
            if _is_device_array(a):
                raise HostSyncError(
                    f"np.{name}() materialized a device array inside the "
                    f"guarded hot path {label!r} — device values must stay "
                    f"on device here (one host sync per replan). Move the "
                    f"materialization outside the hot path, or mark the "
                    f"site `# jaxcheck: JX001 ok <reason>` and lift the "
                    f"guard deliberately."
                )
            return orig(a, *args, **kwargs)

        return guarded

    _tripwire_depth += 1
    for name, orig in originals.items():
        setattr(np, name, _make(name, orig))
    try:
        yield
    finally:
        _tripwire_depth -= 1
        for name, orig in originals.items():
            setattr(np, name, orig)


# ---------------------------------------------------------------------------
# hot_path: decorator / context manager arming both guards.
# ---------------------------------------------------------------------------


class _HotPathGuard:
    """Armed form of :func:`hot_path` — usable with ``with`` or as a
    decorator. ``compiled`` lists jitted callables whose executable cache
    must not grow after the first guarded call (warmup compiles are
    expected; growth after that is a recompile and raises
    :class:`RecompileError` under ``REPRO_DIAG_STRICT=1``, otherwise it
    is only counted in the registry stats)."""

    def __init__(self, label: str, compiled: tuple = ()):
        self.label = label
        self.compiled = tuple(compiled)
        self._stack: list[contextlib.ExitStack] = []

    # -- context-manager protocol ------------------------------------
    def __enter__(self):
        stats = _stats(self.label)
        stack = contextlib.ExitStack()
        stack.enter_context(span(self.label))
        if enabled():
            stats.guarded_calls += 1
            stack.enter_context(
                jax.transfer_guard_device_to_host("disallow")
            )
            stack.enter_context(_numpy_tripwire(self.label))
        self._stack.append(stack)
        return self

    def __exit__(self, exc_type, exc, tb):
        stack = self._stack.pop()
        stack.close()
        if exc_type is None and enabled() and self.compiled:
            self._check_compiled()
        return False

    def _check_compiled(self) -> None:
        stats = _stats(self.label)
        strict = os.environ.get("REPRO_DIAG_STRICT", "") == "1"
        for fn in self.compiled:
            size = _cache_size(fn)
            prev = stats._sizes.get(id(fn))
            stats._sizes[id(fn)] = size
            if prev is not None and size > prev:
                stats.recompiles += size - prev
                if strict:
                    raise RecompileError(
                        f"{_fn_name(fn)} compiled {size - prev} new "
                        f"program(s) inside hot path {self.label!r} after "
                        f"warmup — the one-program-per-shape contract is "
                        f"broken (check static_argnames churn and input "
                        f"shape drift)."
                    )

    # -- decorator protocol ------------------------------------------
    def __call__(self, fn: Callable) -> Callable:
        label = self.label or f"{fn.__module__}.{fn.__qualname__}"
        guard = _HotPathGuard(label, self.compiled)

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with guard:
                return fn(*args, **kwargs)

        wrapper.__wrapped__ = fn
        wrapper.__jaxcheck_hot_path__ = label  # static-analysis marker
        _stats(label)
        return wrapper


def hot_path(label: str | None = None, *, compiled: tuple = ()):
    """Mark a device hot path: static analysis + runtime guards.

    Usable two ways::

        @hot_path("serving.batched_rollout_scores")
        def batched_rollout_scores(...): ...

        with hot_path("core.solve_merged", compiled=(_solve_merged,)):
            sol, iters = _solve_merged(...)

    Registration is unconditional (``tools/jaxcheck`` keys rule JX001 on
    the decorator and on its per-module hot-path list); the runtime
    guards only arm under ``REPRO_DIAG=1``. ``compiled`` adds
    compile-reuse accounting for the named jitted callables (see
    :class:`_HotPathGuard`).
    """
    return _HotPathGuard(label or "", compiled)


# ---------------------------------------------------------------------------
# CompileWatcher: executable-cache deltas for jitted functions.
# ---------------------------------------------------------------------------


def _unwrap(fn: Callable) -> Callable:
    seen = set()
    while not hasattr(fn, "_cache_size") and hasattr(fn, "__wrapped__"):
        if id(fn) in seen:  # defensive: cyclic wrappers
            break
        seen.add(id(fn))
        fn = fn.__wrapped__
    return fn


def _fn_name(fn: Callable) -> str:
    inner = _unwrap(fn)
    return getattr(inner, "__name__", None) or repr(fn)


def _cache_size(fn: Callable) -> int:
    inner = _unwrap(fn)
    if not hasattr(inner, "_cache_size"):
        raise TypeError(
            f"{_fn_name(fn)} exposes no _cache_size(); CompileWatcher "
            f"tracks jax.jit-compiled callables (or hot_path wrappers "
            f"around them)"
        )
    return int(inner._cache_size())


class CompileWatcher:
    """Context manager asserting compiled-program reuse across a region.

    Snapshots each watched function's executable-cache size on entry;
    :meth:`new_compiles` reports growth since then, and
    :meth:`assert_no_recompiles` / :meth:`assert_compiles` turn the
    one-program-per-shape contract into a one-line test assert::

        with CompileWatcher(_arbitrate_device) as w:
            for n_cand in (3, 4, 2):
                batched_rollout_scores(...)
        w.assert_compiles(_arbitrate_device, exactly=2)

    Unlike a raw ``fn._cache_size() == n`` assert, the watcher is
    robust to compiles that happened *before* the watched region (other
    tests, warmup) — it measures deltas, never absolutes.
    """

    def __init__(self, *fns: Callable):
        if not fns:
            raise ValueError("CompileWatcher needs at least one callable")
        self._fns = {id(fn): fn for fn in fns}
        self._baseline: dict[int, int] = {}
        self._entered = False

    def __enter__(self) -> "CompileWatcher":
        self._baseline = {
            key: _cache_size(fn) for key, fn in self._fns.items()
        }
        self._entered = True
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False

    def _delta(self, fn: Callable) -> int:
        if not self._entered:
            raise RuntimeError("CompileWatcher used outside its context")
        key = id(fn)
        if key not in self._baseline:
            raise KeyError(f"{_fn_name(fn)} is not watched by this watcher")
        return _cache_size(fn) - self._baseline[key]

    def new_compiles(self, fn: Callable) -> int:
        """Programs compiled for ``fn`` since the watcher entered."""
        return self._delta(fn)

    def assert_compiles(self, fn: Callable, *, exactly: int) -> None:
        got = self._delta(fn)
        if got != exactly:
            raise RecompileError(
                f"{_fn_name(fn)}: expected exactly {exactly} new compiled "
                f"program(s) in the watched region, measured {got}"
            )

    def assert_no_recompiles(self, fn: Callable | None = None) -> None:
        """Zero new programs for ``fn`` (or for every watched function)."""
        fns = [fn] if fn is not None else list(self._fns.values())
        for f in fns:
            got = self._delta(f)
            if got != 0:
                raise RecompileError(
                    f"{_fn_name(f)} compiled {got} new program(s) in a "
                    f"region that requires compiled-program reuse "
                    f"(one-program-per-shape contract)"
                )
