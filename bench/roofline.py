"""Bytes a kernel has to move, computed from shapes: the yardstick that
roofline shares are taken against, the same whichever backend runs."""
from __future__ import annotations


def chunk_bytes(object_bytes: int, k: int) -> int:
    """Bytes of one chunk of an object split into k data chunks."""
    return -(-int(object_bytes) // int(k))


def decode_bytes(k: int, chunk: int, objects: int = 1) -> int:
    """Least bytes a degraded-read decode moves: per object the k surviving
    chunks read, the k x chunk bytes of the object written and the k x k
    decode matrix read."""
    k, chunk = int(k), int(chunk)
    return int(objects) * (k * chunk + k * chunk + k * k)
