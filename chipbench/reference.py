"""The plain reference: what every copy of an object must hold.

The payload of an object is a function of its id: ``crc32(id)`` seeds
numpy's ``default_rng``, which draws ``nbytes`` uniform bytes.  This is
the benchmark's own copy of the program's formula; it imports nothing of
the program under test.  Each store of an id writes the index of the
request that stores it over the first ``STAMP_BYTES`` (``stamp``), so
two requests that reuse an id expect different bytes.
"""
from __future__ import annotations

import hashlib
import zlib

import numpy as np

STAMP_BYTES = 8


def payload(data_id: str, nbytes: int) -> np.ndarray:
    """The bytes every copy of ``data_id`` must hold."""
    seed = zlib.crc32(data_id.encode())
    return np.random.default_rng(seed).integers(0, 256, nbytes,
                                                dtype=np.uint8)


def digest(data: np.ndarray) -> str:
    """A digest of a byte array, compared in place of the bytes."""
    return hashlib.blake2b(np.ascontiguousarray(data).data,
                           digest_size=16).hexdigest()


def stamp(request: int) -> np.ndarray:
    """The bytes a store of request ``request`` writes first."""
    return np.array([request], "<i8").view(np.uint8)


def stamped_digests(data_id: str, nbytes: int, requests) -> dict:
    """``digest`` of the payload of ``data_id`` as stored by each of
    ``requests``, the payload drawn once."""
    body = payload(data_id, nbytes)[STAMP_BYTES:]
    out = {}
    for r in requests:
        h = hashlib.blake2b(stamp(r).tobytes(), digest_size=16)
        h.update(body.data)
        out[r] = h.hexdigest()
    return out
