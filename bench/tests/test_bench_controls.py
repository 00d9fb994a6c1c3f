"""Each cell's control, at CPU sizes: the plain reference put in the
program's place one precision lower (bfloat16 for the float32 planner
and simulator), or, for the codec, which states no precision, a broken
guarantee (survivors decoded as if the systematic rows had survived).
The cell's comparison has to fail it. The benchmark's own runs never run
the control; ``bench/readings.py`` reads it on the chip at full size."""
import sys
from pathlib import Path

import pytest

TESTS = Path(__file__).resolve().parent
sys.path.insert(0, str(TESTS))
sys.path.insert(0, str(TESTS.parent))

import benchcopy  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchcopy.small_copy(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize(
    "cell", ["replan.node-failure", "codec.degraded-read", "fleet.nj-client"])
def test_control_fails_the_comparison(root, cell):
    checks, failed = benchcopy.run_control(root, cell)
    assert failed > 0
    assert any(value > limit for _, value, limit, _ in checks), checks
