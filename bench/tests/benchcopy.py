"""A copy of the benchmark, shrunk to CPU sizes, that runs in-process.

Tests copy ``BENCHMARK.json`` and ``bench/`` into a temporary root, point
its ``src`` at the program, merge small sizes into the copied
configuration and traffic files, and call ``run.main`` there with the
look for a chip skipped. The harness code that runs is the code under
test: only data files differ.
"""
from __future__ import annotations

import contextlib
import importlib.util
import io
import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

# CPU sizes for each configuration and traffic mix, merged into the copy
SMALL_CONFIG = {
    "tahoe-3dc-r1000": {
        "catalog": {"r": 100},
        "codec": {"object_mib": 1, "objects_per_batch": 3},
        "planner": {"max_iters": 60},
    },
}
SMALL_MIX = {
    "node-failure-loop": {"scenario": {"requests_per_segment": 200}},
    "degraded-read": {},
    "fleet-stream": {"call": {"seeds": 4, "chunks": 3, "block": 256}},
}


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for key, val in over.items():
        out[key] = merge(base[key], val) if isinstance(val, dict) else val
    return out


def edit_json(path: Path, over: dict) -> None:
    path.write_text(json.dumps(merge(json.loads(path.read_text()), over), indent=1))


def small_copy(dest: Path) -> Path:
    """``dest`` holding the benchmark at CPU sizes; returns ``dest``."""
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(BENCH, dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (dest / "src").symlink_to(ROOT / "src")
    manifest = json.loads((dest / "BENCHMARK.json").read_text())
    for cfg in manifest["configs"]:
        edit_json(dest / cfg["file"], SMALL_CONFIG.get(cfg["name"], {}))
    for mix, over in SMALL_MIX.items():
        edit_json(dest / "bench" / "traffic" / f"{mix}.json", over)
    return dest


def load_run(root: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_run_{abs(hash(str(root)))}", root / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_cell(root: Path, workload: str, seed: int = 123456789012,
             seconds: float = 0.5, trace: int = 0, patch=None) -> tuple[dict, str]:
    """Run one cell of the copy at ``root`` on this host's CPU; returns
    the parsed result line and standard error. ``patch(run_module)`` may
    change the copy before it runs."""
    mod = load_run(root)
    peaks = json.loads((root / "bench" / "peaks.json").read_text())["devices"]
    mod.require_chip = lambda cell: next(iter(peaks.values()))
    mod.use_compile_cache = lambda: None
    if patch is not None:
        patch(mod)
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        mod.main(["--workload", workload, "--seed", str(seed),
                  "--seconds", str(seconds), "--trace", str(trace)])
    lines = out.getvalue().strip().splitlines()
    return json.loads(lines[-1]), err.getvalue()


def run_control(root: Path, workload: str, seed: int = 987654321123,
                seconds: float = 0.5) -> tuple[list, int]:
    """The cell's control in the program's place, at the copy's sizes:
    set-up, a short window, then the driver's control readings."""
    from harness import Run, measure_window

    mod = load_run(root)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cell, config, mix, driver = mod.resolve(manifest, workload)
    sys.path.insert(0, str(root / "src"))
    peaks = json.loads((root / "bench" / "peaks.json").read_text())["devices"]
    run = Run(cell=cell, config=config, mix=mix, seed=seed, seconds=seconds,
              trace=False, t_start=0.0, peaks=next(iter(peaks.values())))
    run.state = driver.setup(run)
    run.counters = {}
    run.window_s, _ = measure_window(lambda: driver.step(run), seconds)
    try:
        return driver.control(run)
    finally:
        driver.release(run)
