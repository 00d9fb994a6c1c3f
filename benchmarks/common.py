"""Shared benchmark scaffolding: the paper's testbed scenario + CSV sink."""
from __future__ import annotations

import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.storage import tahoe_testbed

RESULTS = Path(__file__).parent / "results"
RESULTS.mkdir(exist_ok=True)


def use_compile_cache() -> None:
    """Keep compiled programs across benchmark processes: in
    ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself),
    else in ``.jax_cache/`` at the repo root. The path is fixed so that
    the next process finds what this one compiled."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        root = Path(__file__).resolve().parent.parent
        jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))


use_compile_cache()


def emit(rows: list[dict], name: str) -> None:
    """Write rows to results/<name>.csv and echo `name,metric,value` lines."""
    if not rows:
        return
    keys = list(rows[0])
    path = RESULTS / f"{name}.csv"
    with path.open("w") as f:
        f.write(",".join(keys) + "\n")
        for r in rows:
            f.write(",".join(str(r[k]) for k in keys) + "\n")
    for r in rows[: min(len(rows), 12)]:
        print(f"{name}," + ",".join(f"{k}={r[k]}" for k in keys))
    if len(rows) > 12:
        print(f"{name},... ({len(rows)} rows -> {path})")


def paper_catalog(r: int = 1000, file_mb: float = 150.0):
    """The §V.B experiment: r files in four quarters with k = 6,7,6,4
    (different chunk-size choices), paper arrival rates (~0.118/s agg)."""
    ks = np.zeros(r, np.int32)
    ks[0::4], ks[1::4], ks[2::4], ks[3::4] = 6, 7, 6, 4
    lam = np.zeros(r)
    lam[0::3] = 1.25 / 10000
    lam[1::3] = 1.25 / 10000
    lam[2::3] = 1.25 / 12000
    chunk_mb = file_mb / ks  # per-file chunk size
    return jnp.asarray(lam), jnp.asarray(ks, jnp.float32), np.asarray(chunk_mb)


def million_file_catalog(r: int = 1_000_000, **kw):
    """A vectorized r-file synthetic catalog (NO Python per-file loops —
    every field is drawn and normalized with whole-array numpy ops, so
    generating 10^6 files costs tens of milliseconds, not minutes).

    Benchmark-facing alias of ``repro.core.synthetic_catalog``; keyword
    arguments (``total_rate``, ``k_classes``, ``file_mb``, ``rate_sigma``,
    ``seed``) pass through. The default keeps total traffic constant as r
    grows ("same traffic, more objects"), so catalog sizes are comparable
    against one fixed testbed."""
    from repro.core import synthetic_catalog

    return synthetic_catalog(r, **kw)


def time_interleaved(fns, repeats: int = 5) -> list[float]:
    """Best-of-repeats wall time for each fn, with the repeats
    *interleaved* so a noisy window on a shared/small machine hits every
    candidate instead of biasing whichever happened to run through it
    (min is the standard noise-robust microbenchmark estimator). Every fn
    is called once first for warmup/compile. Timing-ratio asserts in this
    repo's benchmarks and tests go through this helper — never through a
    single timed pass of each candidate."""
    for fn in fns:
        fn()  # warmup / compile
    best = [float("inf")] * len(fns)
    for _ in range(repeats):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            fn()
            best[i] = min(best[i], time.perf_counter() - t0)
    return best


def timer(fn, *args, repeats: int = 3, **kw):
    fn(*args, **kw)  # warmup / compile
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args, **kw)
        jax.block_until_ready(out) if hasattr(out, "block_until_ready") or isinstance(out, jax.Array) else None
    return (time.perf_counter() - t0) / repeats


def testbed():
    return tahoe_testbed()
