#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the root of the
checkout: the cell names a configuration (``bench/configs/<name>.json``,
the deployment as run) and a traffic mix (``bench/traffic/<mix>.json``,
parameters only), and the mix names the general driver that serves it
(``bench/drivers/<driver>.py``). Each per-layer metric is a reader of its
own (``bench/metrics/<name>.py``, or ``bench/metrics/<stem>.py`` for a
name ``<stem>.<cell kind>`` whose reader serves every kind of cell, as
``idle_share.py`` does). Adding any of them adds files and entries; no
file here changes.

A run loads and warms up every shape its traffic uses (``setup_s``),
measures for ``--seconds`` with nothing compiling, checks what the timed
path produced against the plain references in ``bench/reference/`` and
prints one JSON line last. ``--trace 1`` is a separate run that traces a
short steady part of the window and prints the per-layer metrics instead
of the end-to-end ones. Without a TPU listed in ``bench/peaks.json``, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result. The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set and
``.jax_cache/`` at the root of the checkout otherwise.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from harness import (  # noqa: E402
    BenchError,
    CompileCounter,
    Run,
    Spans,
    device_record,
    emit,
    measure_window,
    peak_memory_bytes,
)

TRACE_DIR = ROOT / ".bench_trace"


def load_module(path: Path, name: str):
    """Import a file that a name in the manifest points at."""
    if not path.is_file():
        raise BenchError(f"no file {path.relative_to(ROOT)} for {name!r}")
    key = f"bench_{path.parent.name}_{name}"
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The reader of per-layer metric ``name``: its own file, or the file
    of the name's stem shared by every cell that reports it."""
    own = BENCH / "metrics" / f"{name}.py"
    shared = BENCH / "metrics" / f"{name.split('.')[0]}.py"
    return load_module(own if own.is_file() or not shared.is_file() else shared, name)


def load_json(path: Path, what: str) -> dict:
    if not path.is_file():
        raise BenchError(f"no {what} file {path}")
    return json.loads(path.read_text())


def resolve(manifest: dict, workload: str):
    """The cell, its configuration, its mix and the mix's driver."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; one of {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(ROOT / configs[cell["config"]]["file"], "configuration")
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json", "traffic")
    driver = load_module(BENCH / "drivers" / f"{mix['driver']}.py", mix["driver"])
    return cell, config, mix, driver


def cell_metrics(manifest: dict, workload: str, kind: str) -> list[dict]:
    """The metrics of ``kind`` that this cell reports."""
    mine = []
    e2e = {
        m["name"] for m in manifest["end_to_end"]
        if workload in m.get("workloads", [workload])
    }
    for m in manifest[kind]:
        if "workloads" in m:
            if workload in m["workloads"]:
                mine.append(m)
        elif kind == "end_to_end" or m["moves"] in e2e:
            mine.append(m)
    return mine


def require_chip(cell: dict) -> dict:
    """The peaks of the chip this process holds; no chip, no result."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU, found {devs[0].platform}")
    if len(devs) < int(cell["chips"]):
        raise BenchError(f"cell asks for {cell['chips']} chips, found {len(devs)}")
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    kind = devs[0].device_kind
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def use_compile_cache() -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    # small programs too: every run after a cell's first finds them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = load_json(ROOT / "BENCHMARK.json", "manifest")
    cell, config, mix, driver = resolve(manifest, args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        raise BenchError(f"no program under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))
    peaks = require_chip(cell)
    use_compile_cache()

    run = Run(
        cell=cell, config=config, mix=mix, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), t_start=T_START,
        peaks=peaks,
    )
    counter = CompileCounter()
    counter.install()
    run.state = driver.setup(run)
    setup_s = time.perf_counter() - T_START

    run.spans = Spans(annotate=run.trace)
    run.counters = {}
    window = args.seconds
    if run.trace:
        import jax

        window = min(args.seconds, float(mix.get("trace_seconds", args.seconds)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # spans only: no cost per Python call
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    counter.active = True
    run.window_s, steps = measure_window(lambda: driver.step(run), window)
    counter.active = False
    if run.trace:
        import jax

        jax.profiler.stop_trace()
    print(f"window: {run.window_s:.6f} s, steps {steps}, programs lowered "
          f"{counter.lowered}, compiled {counter.compiled}", file=sys.stderr,
          flush=True)

    peak = peak_memory_bytes()
    e2e = driver.end_to_end(run)
    attempted = int(run.counters.get("attempted", 0))
    device = device_record(peak)
    metrics = {}
    breakdown = None
    if run.trace:
        import reduce

        run.reduced = reduce.reduce_dir(TRACE_DIR, host_names=run.spans.names())
        device["busy_s"] = run.reduced.busy_s
        device["window_s"] = run.reduced.window_s
        breakdown = run.reduced.breakdown()
        for m in cell_metrics(manifest, args.workload, "per_layer"):
            value = metric_reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(e2e, setup_s=setup_s)
        for m in cell_metrics(manifest, args.workload, "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    driver.release(run)
    gc.collect()
    checks, failed = driver.check(run)
    correct = failed == 0 and all(v <= lim for _, v, lim, _ in checks)
    result = {
        "correct": bool(correct),
        "attempted": attempted,
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
