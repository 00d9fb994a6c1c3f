"""Jit'd public entry points for the GF(256) compute layer.

Three interchangeable backends (all bit-exact):

* ``pallas``   — the VPU kernel in :mod:`.gf256_matmul` (TPU target;
                 interpret-mode on CPU).
* ``bitplane`` — the MXU adaptation: expand each GF(256) constant into its
                 8x8 GF(2) bit-matrix (Cauchy/Jerasure technique) so the
                 whole GF matmul becomes ONE integer matmul of shape
                 (8M, 8K) x (8K, N) followed by a parity (&1) — systolic-
                 array work instead of byte twiddling. 64x the integer MACs
                 of the byte product, but MXU int8 throughput makes it the
                 fastest path for large encodes on TPU.
* ``ref``      — the K-scan jnp oracle (CPU default).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import Array

from repro.storage.gf256 import (
    bytes_to_bits,
    gf_const_to_bitmatrix,
)
from . import ref as _ref
from .gf256_matmul import (
    gf256_matmul_pallas,
    gf256_matmul_pallas_batched,
    select_block_sizes,
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@jax.jit
def gf256_matmul_bitplane(a: Array, b: Array) -> Array:
    """MXU path: C = A @GF B via GF(2) bit-matrix lifting.

    bits(C[i,j])_p = sum_{k,q} M_{A[i,k]}[p,q] * bits(B[k,j])_q  (mod 2)

    The batched path with a batch of one (same integer contraction).
    """
    return gf256_matmul_batch_bitplane(a[None], b[None])[0]


def gf256_matmul(a: Array, b: Array, *, backend: str = "auto") -> Array:
    """Dispatching GF(256) matmul; bit-exact across backends."""
    if backend == "auto":
        backend = "bitplane" if _on_tpu() else "ref"
    if backend == "ref":
        return _ref.gf256_matmul_ref(a, b)
    if backend == "bitplane":
        return gf256_matmul_bitplane(a, b)
    if backend == "pallas":
        bm, bn, bk = select_block_sizes(a.shape[0], b.shape[1], a.shape[1])
        return gf256_matmul_pallas(
            a, b, block_m=bm, block_n=bn, block_k=bk, interpret=not _on_tpu()
        )
    raise ValueError(f"unknown backend {backend!r}")


# --- the batched (B, k, bytes) contract ------------------------------------
#
# One call, B independent GF matmuls: C[b] = A[b] @GF B[b]. This is the
# codec pipeline's shape — a decode-matrix bank (B, k, k) against gathered
# chunk payloads (B, k, bytes) — and every backend accepts it bit-exactly:
#
#   * ref      — jax.vmap of the K-scan oracle (XLA fuses the batch axis),
#   * bitplane — ONE block-diagonal-free MXU matmul: the bit-lifted batch
#                folds into the contraction via dot_general batching dims,
#   * pallas   — the batch axis as the outermost kernel grid dimension
#                (gf256_matmul_pallas_batched), no vmap-of-pallas_call.


@jax.jit
def _gf256_matmul_batch_ref(a: Array, b: Array) -> Array:
    return jax.vmap(_ref.gf256_matmul_ref)(
        jnp.asarray(a, jnp.uint8), jnp.asarray(b, jnp.uint8)
    )


# Operand bytes per bitplane step. The lifted operand is 8 int8 bits per
# byte and the contraction 8 int32 sums per output byte, so in one step a
# v5e decode of two 150 MB objects needs 1.68 GB of temp, 4x its operand.
# Wide operands walk their byte columns in steps of this size instead, and
# temp memory no longer grows with the batch.
_BITPLANE_STEP_BYTES = 1 << 24


def _bitplane_columns(big_a: Array, b: Array) -> Array:
    """(B, 8M, 8K) int8 bit-matrices against (B, K, T) bytes -> (B, M, T)."""
    bsz, m8, k8 = big_a.shape
    t = b.shape[2]
    big_b = bytes_to_bits(b.transpose(0, 2, 1))  # (B, T, K, 8)
    big_b = big_b.transpose(0, 2, 3, 1).reshape(bsz, k8, t)
    c_bits = (
        jax.lax.dot_general(
            big_a,
            big_b.astype(jnp.int8),
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.int32,
        )
        & 1
    )  # (B, 8M, T)
    c_bits = c_bits.reshape(bsz, m8 // 8, 8, t).transpose(0, 1, 3, 2)
    shifts = jnp.arange(8, dtype=jnp.uint8)
    return jnp.sum(
        (c_bits.astype(jnp.uint8) << shifts).astype(jnp.int32), axis=-1
    ).astype(jnp.uint8)


@jax.jit
def gf256_matmul_batch_bitplane(a: Array, b: Array) -> Array:
    """Batched MXU path: per-element GF(2) bit-lifting, one dot_general.

    bits(C[v,i,j])_p = sum_{k,q} M_{A[v,i,k]}[p,q] * bits(B[v,k,j])_q (mod 2)
    with the batch axis v carried as a dot_general batching dimension, so
    the whole bank issues a single integer contraction per column step
    (``_BITPLANE_STEP_BYTES``); columns are independent, so the steps
    concatenate to the one-shot product bit for bit.
    """
    a = jnp.asarray(a, jnp.uint8)
    b = jnp.asarray(b, jnp.uint8)
    bsz, m, k = a.shape
    n = b.shape[2]
    big_a = gf_const_to_bitmatrix(a)  # (B, M, K, 8, 8) [p, q]
    big_a = big_a.transpose(0, 1, 3, 2, 4).reshape(bsz, m * 8, k * 8)
    big_a = big_a.astype(jnp.int8)
    step = max(128, _BITPLANE_STEP_BYTES // (bsz * max(m, k)) // 128 * 128)
    if n <= step:
        return _bitplane_columns(big_a, b)

    def body(j, out):
        cols = jax.lax.dynamic_slice_in_dim(b, j * step, step, axis=2)
        return jax.lax.dynamic_update_slice_in_dim(
            out, _bitplane_columns(big_a, cols), j * step, axis=2
        )

    full = n // step
    out = jax.lax.fori_loop(0, full, body, jnp.zeros((bsz, m, n), jnp.uint8))
    if full * step < n:
        tail = _bitplane_columns(big_a, b[:, :, full * step :])
        out = jax.lax.dynamic_update_slice_in_dim(out, tail, full * step, axis=2)
    return out


def gf256_matmul_batch(a: Array, b: Array, *, backend: str = "auto") -> Array:
    """C (B,M,N) = A (B,M,K) @GF B (B,K,N); bit-exact across backends."""
    a = jnp.asarray(a, jnp.uint8)
    b = jnp.asarray(b, jnp.uint8)
    if a.ndim != 3 or b.ndim != 3 or a.shape[0] != b.shape[0]:
        raise ValueError(
            f"batched contract needs (B,M,K) x (B,K,N), got {a.shape} x {b.shape}"
        )
    if backend == "auto":
        backend = "bitplane" if _on_tpu() else "ref"
    if backend == "ref":
        return _gf256_matmul_batch_ref(a, b)
    if backend == "bitplane":
        return gf256_matmul_batch_bitplane(a, b)
    if backend == "pallas":
        return gf256_matmul_pallas_batched(a, b, interpret=not _on_tpu())
    raise ValueError(f"unknown backend {backend!r}")


def rs_encode(data_rows: Array, n: int, *, backend: str = "auto") -> Array:
    """(k, B) -> (n, B) systematic RS encode on the selected backend."""
    from repro.storage.rs import encode

    return encode(
        data_rows, n, matmul=functools.partial(gf256_matmul, backend=backend)
    )


def rs_decode(
    chunks: Array, chunk_ids, n: int, k: int, *, backend: str = "auto"
) -> Array:
    from repro.storage.rs import decode

    return decode(
        chunks, chunk_ids, n, k, matmul=functools.partial(gf256_matmul, backend=backend)
    )
