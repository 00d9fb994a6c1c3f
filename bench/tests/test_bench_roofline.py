"""The byte count the codec's roofline share is taken against, and the
table of peaks it divides by."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import roofline  # noqa: E402

MIB = 2**20


def test_decode_bytes_rs_12_6_at_150_mib():
    chunk = roofline.chunk_bytes(150 * MIB, 6)
    assert chunk == 26214400
    # 6 surviving chunks read, 6 x 26214400 object bytes written, 6x6 matrix
    assert roofline.decode_bytes(6, chunk) == 6 * 26214400 * 2 + 36
    assert roofline.decode_bytes(6, chunk, objects=10) == 10 * (6 * 26214400 * 2 + 36)


@pytest.mark.parametrize("object_bytes,k,chunk", [(10, 3, 4), (12, 3, 4), (1, 6, 1)])
def test_chunk_bytes_rounds_up(object_bytes, k, chunk):
    assert roofline.chunk_bytes(object_bytes, k) == chunk


def test_peaks_name_their_source_and_the_v5e():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
