"""Share of the HBM roofline the degraded-read path reaches: the least
time the chip could move the decode's bytes in (bytes over the peak HBM
bandwidth of bench/peaks.json; memory bandwidth is the bound, the GF(256)
arithmetic is a few integer operations per byte), over the device busy
time of the traced window, in which only the decode path runs (survivor
gather, stacking, the GF(256) matmul). The bytes are counted from shapes
by bench/roofline.py, the same whichever backend decodes."""

import roofline


def read(run):
    r = run.reduced
    if r is None or r.busy_s <= 0 or not run.counters.get("attempted"):
        return None
    codec = run.config["codec"]
    objects = int(run.counters["attempted"])
    chunk = roofline.chunk_bytes(int(codec["object_mib"]) * 2**20, int(codec["k"]))
    least = roofline.decode_bytes(int(codec["k"]), chunk, objects) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / r.busy_s
