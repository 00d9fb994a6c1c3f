"""Pallas TPU kernel: blocked GF(2^8) matrix multiply (RS encode/decode).

Erasure encode/decode is the byte-crunching hot-spot of the paper's storage
plane (zfec in the prototype). The CPU/GPU idiom is log/exp *table lookups*
per byte — gathers, which the TPU VPU punishes. TPU adaptation:

  * Per k-slice, the product  a_col (bm,1) x b_row (1,bn)  is computed with
    a branchless 8-round carry-less multiply ("Russian peasant" / xtime):
    every round is a select + shift + xor on full (bm, bn) tiles of bytes
    widened to int32 — pure VPU work, no gathers, no MXU dependency.
  * Blocks are VMEM-resident via BlockSpec; the K grid axis accumulates
    into an int32 VMEM scratch with XOR (the field's addition),
    initialised on the first K step, and the uint8 output block is
    written on the last (standard Pallas accumulation pattern).

VMEM per grid step is bm*bk + bk*bn + bm*bn bytes of uint8 blocks plus the
4*bm*bn-byte scratch — (128, 512, 128) blocks use under 0.5 MiB, far under
the 16 MiB of scoped VMEM a v5e kernel gets.

Validated in interpret mode on CPU against ``ref.gf256_matmul_ref`` over a
shape sweep (see tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import Array
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.storage.gf256 import POLY


def _gf_mul_tile(a: Array, b: Array) -> Array:
    """Branchless GF(256) multiply of equal-shape int32 tiles holding bytes
    (8 rounds). Byte arithmetic is widened to 32 bits, the VPU's native
    width; the ``& 0xFF`` keeps every intermediate a byte."""
    acc = jnp.zeros_like(a)

    def round_fn(_, carry):
        acc, a, b = carry
        take = (b & 1) != 0
        acc = jnp.where(take, acc ^ a, acc)
        hi = (a & 0x80) != 0
        a = jnp.where(hi, (a << 1) ^ (POLY & 0xFF), a << 1) & 0xFF
        b = b >> 1
        return acc, a, b

    acc, _, _ = jax.lax.fori_loop(0, 8, round_fn, (acc, a, b))
    return acc


def _block_matmul(a: Array, b: Array) -> Array:
    """(bm, bk) @GF (bk, bn) -> (bm, bn) on int32-widened bytes: the shared
    per-block inner loop of both kernels, one K-slice outer product per
    round, XOR-reduced. The K loop is unrolled so every column and row is
    a static slice (Mosaic has no dynamic lane slice)."""
    acc = jnp.zeros((a.shape[0], b.shape[1]), jnp.int32)
    for kk in range(a.shape[1]):
        acc = acc ^ _gf_mul_tile(
            jnp.broadcast_to(a[:, kk : kk + 1], acc.shape),
            jnp.broadcast_to(b[kk : kk + 1, :], acc.shape),
        )
    return acc


def _accumulate(k_axis, a, b, o_ref, acc_ref):
    """XOR-accumulate one K block (grid axis ``k_axis``) into the int32
    scratch; the uint8 output block is written once, after the last one."""
    k_step = pl.program_id(k_axis)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] ^= _block_matmul(a.astype(jnp.int32), b.astype(jnp.int32))

    @pl.when(k_step == pl.num_programs(k_axis) - 1)
    def _store():
        o_ref[...] = acc_ref[...].reshape(o_ref.shape).astype(o_ref.dtype)


def _kernel(a_ref, b_ref, o_ref, acc_ref):
    """Grid (Mi, Nj, Kk): XOR-accumulate a_block @GF b_block into o_block."""
    _accumulate(2, a_ref[...], b_ref[...], o_ref, acc_ref)


def select_block_sizes(m: int, n: int, k: int) -> tuple[int, int, int]:
    """(bm, bn, bk) for a GF(256) matmul of logical shape (m, k) x (k, n).

    Everything is byte-wide, so VMEM cost per grid step is just
    ``bm*bk + bk*bn + bm*bn`` bytes — tiny. The binding considerations are
    (a) lane/sublane alignment: bn should be a multiple of 128 lanes when
    the operand allows it, bm/bk multiples of 8 sublanes; (b) grid overhead:
    tiny operands should be a single block. RS shapes are extreme — encode
    is (n-k, k) x (k, bytes) with single-digit m/k and huge n — so blocks
    clamp to the operand and widen along n.
    """

    def _clamp(want: int, dim: int, align: int) -> int:
        if dim <= want:
            return dim
        return max(align, (want // align) * align)

    bm = _clamp(128, m, 8)
    bk = _clamp(128, k, 8)
    # wide-n operands amortize the 8-round multiply over more lanes
    bn = _clamp(512 if n >= 4096 else 256, n, 128)
    return bm, bn, bk


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def gf256_matmul_pallas(
    a: Array,
    b: Array,
    *,
    block_m: int = 128,
    block_n: int = 256,
    block_k: int = 128,
    interpret: bool = False,
) -> Array:
    """GF(256) matmul C (M,N) = A (M,K) @GF B (K,N); uint8 throughout.

    Shapes are padded up to block multiples (zero padding is XOR/multiply
    neutral) and the result sliced back.
    """
    a = jnp.asarray(a, jnp.uint8)
    b = jnp.asarray(b, jnp.uint8)
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bn, bk = (min(block_m, m), min(block_n, n), min(block_k, k))
    # round blocks down to sublane/lane-friendly sizes where possible
    pad_m, pad_n, pad_k = (-m) % bm, (-n) % bn, (-k) % bk
    a_p = jnp.pad(a, ((0, pad_m), (0, pad_k)))
    b_p = jnp.pad(b, ((0, pad_k), (0, pad_n)))
    mp, kp = a_p.shape
    _, np_ = b_p.shape
    grid = (mp // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.uint8),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(a_p, b_p)
    return out[:m, :n]


def _kernel_batched(a_ref, b_ref, o_ref, acc_ref):
    """Grid (B, Mi, Nj, Kk): per-batch-element GF matmul, XOR-accumulated.

    The batch axis is the OUTERMOST grid dimension (not a vmap): every
    (n, k) group of a codec batch runs as one pallas_call whose grid walks
    the B independent decodes, each reusing the same VMEM-resident block
    machinery (`_block_matmul`) as the unbatched kernel. Block refs carry
    a leading batch block of size 1.
    """
    _accumulate(3, a_ref[0], b_ref[0], o_ref, acc_ref)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def gf256_matmul_pallas_batched(
    a: Array,
    b: Array,
    *,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> Array:
    """Batched GF(256) matmul C (B,M,N) = A (B,M,K) @GF B (B,K,N).

    ONE compiled call for the whole batch: the batch axis becomes the
    outermost grid dimension (see :func:`_kernel_batched`), so a codec
    group's B degraded-read decodes issue a single XLA program instead of
    B kernel launches. Block sizes default to :func:`select_block_sizes`
    on the per-element shape.
    """
    a = jnp.asarray(a, jnp.uint8)
    b = jnp.asarray(b, jnp.uint8)
    bsz, m, k = a.shape
    b2, k2, n = b.shape
    assert bsz == b2 and k == k2, (a.shape, b.shape)
    sm, sn, sk = select_block_sizes(m, n, k)
    bm = min(block_m or sm, m)
    bn = min(block_n or sn, n)
    bk = min(block_k or sk, k)
    pad_m, pad_n, pad_k = (-m) % bm, (-n) % bn, (-k) % bk
    a_p = jnp.pad(a, ((0, 0), (0, pad_m), (0, pad_k)))
    b_p = jnp.pad(b, ((0, 0), (0, pad_k), (0, pad_n)))
    _, mp, kp = a_p.shape
    _, _, np_ = b_p.shape
    grid = (bsz, mp // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        _kernel_batched,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda bb, i, j, kk: (bb, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda bb, i, j, kk: (bb, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda bb, i, j, kk: (bb, i, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, mp, np_), jnp.uint8),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(a_p, b_p)
    return out[:, :m, :n]
