"""Benchmark harness: one module per paper table/figure + framework
benches. Each prints `name,<k=v...>` CSV lines and writes
benchmarks/results/<name>.csv; asserts reproduce the paper's claims."""
import sys
import time
import traceback

MODULES = [
    "fig6_service_time",
    "fig7_bound_comparison",
    "fig8_convergence",
    "fig9_oblivious",
    "fig10_latency_cdf",
    "fig11_file_size",
    "fig12_arrival_rates",
    "fig13_tradeoff",
    "kernel_gf256",
    "codec_throughput",
    "jlcm_scaling",
    "serving_hedge",
    "scenario_suite",
    "tenant_tradeoff",
    "fleet_scale",
    "replan_wall",
    "checkpoint_catalogs",
]


def main() -> None:
    only = sys.argv[1].split(",") if len(sys.argv) > 1 else None
    unknown = sorted(set(only or ()) - set(MODULES))
    if unknown:
        raise SystemExit(f"unknown benchmark(s) {unknown}; choose from {MODULES}")
    failed = []
    for name in MODULES:
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            mod.run()
            print(f"# {name}: OK ({time.perf_counter() - t0:.1f}s)", flush=True)
        except Exception:
            failed.append(name)
            print(f"# {name}: FAILED\n{traceback.format_exc()}", flush=True)
    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()
