"""Each cell's check, at CPU sizes in a copy of the benchmark with the
look for a chip skipped: it passes on what the program's timed path
produced, and ``correct`` comes out false when the timed path is broken
underneath (``bench/faults.py``): an answer altered where it is
produced, a step that returns its state unchanged, half of the batch left
out, a statistic dropped, a solve cut short, the worse candidate chosen.
(No cell runs across chips, so the fault of a left-out exchange between
chips has no cell.)"""
import sys
from pathlib import Path

import pytest

TESTS = Path(__file__).resolve().parent
sys.path.insert(0, str(TESTS))
sys.path.insert(0, str(TESTS.parent))

import benchcopy  # noqa: E402
import faults  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchcopy.small_copy(tmp_path_factory.mktemp("bench"))


CELLS = sorted(faults.FAULTS)
FAULTS = [(cell, f) for cell, fs in faults.FAULTS.items() for f in fs]


@pytest.mark.parametrize("cell", CELLS)
def test_check_passes_on_the_program(root, cell):
    res, err = benchcopy.run_cell(root, cell)
    assert res["correct"], err
    assert res["failed"] == 0 and res["attempted"] > 0
    assert "programs lowered 0, compiled 0" in err
    assert list(res) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    for check in res["checks"].values():
        assert check["value"] <= check["limit"]


@pytest.mark.parametrize("cell,fault", FAULTS, ids=lambda x: getattr(x, "__name__", x))
def test_check_fails_on_a_broken_timed_path(root, cell, fault, monkeypatch):
    res, err = benchcopy.run_cell(root, cell, patch=lambda mod: fault(monkeypatch.setattr))
    assert not res["correct"], err
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
