"""Plain reference for a degraded read: the object's own bytes.

A decode is correct when every byte it returns equals the byte that was
written. The source bytes are kept on the host from the moment they were
drawn, before any encode, so the comparison takes nothing the codec made.
"""
from __future__ import annotations

import numpy as np


def wrong_bytes(decoded, source) -> int:
    """Bytes of ``decoded`` that differ from ``source`` (all of them when
    the shapes differ)."""
    decoded = np.asarray(decoded)
    source = np.asarray(source)
    if decoded.shape != source.shape:
        return int(source.size)
    return int(np.count_nonzero(decoded != source))
