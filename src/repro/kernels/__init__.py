"""TPU kernels: the GF(256) erasure-coding hot path, the fused FCFS
fleet-queue scan and the planner's rack-capped projection."""

from .fcfs_queue import fcfs_scan, fcfs_scan_pallas
from .gf256_matmul import (
    gf256_matmul_pallas,
    gf256_matmul_pallas_batched,
    select_block_sizes,
)
from .ops import (
    gf256_matmul,
    gf256_matmul_batch,
    gf256_matmul_batch_bitplane,
    gf256_matmul_bitplane,
    rs_decode,
    rs_encode,
)
from .rack_projection import rack_project_pallas
from .ref import gf256_matmul_dense_ref, gf256_matmul_ref
