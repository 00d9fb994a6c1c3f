"""The harness: ``BENCHMARK.json`` is well formed, a configuration, a mix
and a metric are found by name (a new triple runs with files and entries
only), and a run without a TPU listed in ``bench/peaks.json`` exits
non-zero and prints no result."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

TESTS = Path(__file__).resolve().parent
sys.path.insert(0, str(TESTS))
sys.path.insert(0, str(TESTS.parent))

import benchcopy  # noqa: E402

ROOT = benchcopy.ROOT
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRIC_KEYS = {"name", "unit", "better", "source", "bound"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


def all_names():
    m = MANIFEST
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in m[group]:
            yield entry["name"]
    for w in m["workloads"]:
        yield w["config"]
        yield w["traffic"]
    for c in m["configs"]:
        yield from c["reduced"]


@pytest.mark.parametrize("name", sorted(set(all_names())))
def test_names_are_plain(name):
    assert NAME.match(name), name


@pytest.mark.parametrize(
    "metric", MANIFEST["end_to_end"] + MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_metric_entries(metric):
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    cells = {w["name"] for w in MANIFEST["workloads"]}
    assert set(metric.get("workloads", [])) <= cells
    if "layer" in metric:
        assert set(metric) - {"workloads"} == LAYER_KEYS
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
        assert metric["moves"] in e2e
        moved = e2e[metric["moves"]].get("workloads", sorted(cells))
        assert set(metric["workloads"]) <= set(moved)
        stem = metric["name"].split(".")[0]
        assert any((ROOT / "bench" / "metrics" / f"{n}.py").is_file()
                   for n in (metric["name"], stem))
    else:
        assert set(metric) - {"workloads"} == METRIC_KEYS
        assert metric["source"] in ("device_trace", "host_clock")
        assert 0.01 <= metric["bound"] <= 0.25


def test_manifest_shape():
    m = MANIFEST
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert m["paths"] == ["bench"]
    assert m["command"] == ["python3", "bench/run.py"]
    assert 1 <= m["run_seconds"] <= 51
    names = [e["name"] for g in ("configs", "workloads", "end_to_end", "per_layer")
             for e in m[g]]
    assert len(names) == len(set(names))
    assert any(e["name"] == "setup_s" and e["bound"] <= 0.25 for e in m["end_to_end"])
    used = {w["config"] for w in m["workloads"]}
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert c["file"].startswith("bench/")
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    assert len(json.dumps(m)) <= 64 * 1024


def test_a_new_config_mix_and_metric_run_from_files_and_entries(tmp_path):
    """Add a configuration, a traffic mix, a per-layer metric and a cell as
    new files plus manifest entries in a copy; the unchanged harness finds
    and runs them."""
    root = benchcopy.small_copy(tmp_path)
    shutil.copy(root / "bench" / "configs" / "tahoe-3dc-r1000.json",
                root / "bench" / "configs" / "tahoe-3dc-r1000-b.json")
    benchcopy.edit_json(root / "bench" / "configs" / "tahoe-3dc-r1000-b.json",
                        {"name": "tahoe-3dc-r1000-b", "catalog": {"r": 50}})
    shutil.copy(root / "bench" / "traffic" / "fleet-stream.json",
                root / "bench" / "traffic" / "fleet-stream-b.json")
    benchcopy.edit_json(root / "bench" / "traffic" / "fleet-stream-b.json",
                        {"call": {"seeds": 2, "chunks": 2, "block": 128}})
    (root / "bench" / "metrics" / "call_ms.fleet-b.py").write_text(
        "def read(run):\n    v = run.spans.mean('fleet_call')\n"
        "    return None if v is None else v * 1e3\n")
    m = json.loads((root / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tahoe-3dc-r1000-b", "source": "test",
                         "file": "bench/configs/tahoe-3dc-r1000-b.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "fleet.b", "config": "tahoe-3dc-r1000-b",
                           "traffic": "fleet-stream-b", "chips": 1, "why": "test"})
    for e in m["end_to_end"]:
        if e["name"] == "fleet_mreq_per_s":
            e["workloads"].append("fleet.b")
    m["per_layer"].append({"name": "call_ms.fleet-b", "unit": "ms", "better": "lower",
                           "source": "host_clock", "layer": "simulator",
                           "moves": "fleet_mreq_per_s", "workloads": ["fleet.b"]})
    # a metric whose reader is shared by every kind of cell needs no file
    m["per_layer"].append({"name": "idle_share.fleet-b", "unit": "%", "better": "lower",
                           "source": "device_trace", "layer": "device",
                           "moves": "fleet_mreq_per_s", "workloads": ["fleet.b"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))

    res, err = benchcopy.run_cell(root, "fleet.b", seconds=0.3)
    assert res["correct"], err
    assert set(res["metrics"]) == {"fleet_mreq_per_s", "setup_s"}
    assert res["attempted"] % (2 * 2 * 128) == 0 and res["attempted"] > 0
    res, err = benchcopy.run_cell(root, "fleet.b", seconds=0.3, trace=1)
    assert res["correct"], err
    assert res["metrics"]["call_ms.fleet-b"]["value"] > 0
    assert 0 <= res["metrics"]["idle_share.fleet-b"]["value"] <= 100
    assert "window_s" in res["device"] and "breakdown" in res
    assert list(res)[-1] == "checks"


def _bench_cmd(root: Path, workload="fleet.nj-client"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         "2147483749", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    out = _bench_cmd(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_checkout_without_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _bench_cmd(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


@pytest.mark.parametrize("platform,kind,count,msg", [
    ("tpu", "TPU v99 imaginary", 1, "not in bench/peaks.json"),
    ("cpu", "cpu", 1, "needs a TPU"),
    ("tpu", "TPU v5 lite", 1, None),
])
def test_device_kind_must_be_in_the_peaks_table(monkeypatch, platform, kind, count, msg):
    import jax

    mod = benchcopy.load_run(ROOT)
    fake = [NS(platform=platform, device_kind=kind)] * count
    monkeypatch.setattr(jax, "devices", lambda *a: fake)
    if msg is None:
        assert mod.require_chip({"chips": 1})["hbm_bytes_per_s"] == 819e9
        with pytest.raises(SystemExit, match="asks for 4 chips"):
            mod.require_chip({"chips": 4})
    else:
        with pytest.raises(SystemExit, match=msg):
            mod.require_chip({"chips": 1})


def test_unknown_workload_is_an_error():
    mod = benchcopy.load_run(ROOT)
    with pytest.raises(SystemExit, match="unknown workload"):
        mod.resolve(MANIFEST, "no.such-cell")
