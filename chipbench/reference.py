"""The plain reference: what every copy of an object must hold, and the
digest by which a copy is compared with it.

The payload of an object is a function of its id: ``crc32(id)`` seeds
numpy's ``default_rng``, which draws ``nbytes`` uniform bytes.  This is
the benchmark's own copy of the program's formula; it imports nothing of
the program under test.  Each store of an id writes the index of the
request that stores it over the first ``STAMP_BYTES`` (``stamp``), so
two requests that reuse an id expect different bytes.

The digest.  The bytes, zero-padded to a whole word, are read as
little-endian uint32 words ``w_i``.  Each of ``LANES`` 32-bit lanes is
``sum_i g(w_i ^ k(i)) mod 2**32`` with ``g = fmix32`` (MurmurHash3's
finaliser, a bijection) and ``k(i) = fmix32(i * GOLDEN + LANE_KEYS[l])``:
a non-linear mix of each word keyed by its position and lane, so that no
fixed pattern of flips cancels (a plain weighted sum misses a flip of the
top bit in any two words).  The sum over positions is what lets the check
digest a payload once and derive each stamped store from it (``restamp``),
and digest a copy chunk by chunk on the chip (``harness``): this numpy form
is the definition that both are tested against.
"""
from __future__ import annotations

import zlib

import numpy as np

STAMP_BYTES = 8
LANES = 4
GOLDEN = 0x9E3779B9
LANE_KEYS = (0x243F6A88, 0x85A308D3, 0x13198A2E, 0x03707344)
MASK32 = 0xFFFFFFFF
#: words per block of ``lane_sums``, which bounds its temporaries
BLOCK_WORDS = 1 << 20


def payload(data_id: str, nbytes: int) -> np.ndarray:
    """The bytes every copy of ``data_id`` must hold."""
    seed = zlib.crc32(data_id.encode())
    return np.random.default_rng(seed).integers(0, 256, nbytes,
                                                dtype=np.uint8)


def stamp(request: int) -> np.ndarray:
    """The bytes a store of request ``request`` writes first."""
    return np.array([request], "<i8").view(np.uint8)


def fmix32(h: np.ndarray) -> np.ndarray:
    """MurmurHash3's 32-bit finaliser, elementwise on uint32 (numpy or
    jax arrays)."""
    h = h ^ (h >> 16)
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * np.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def words(data: np.ndarray) -> np.ndarray:
    """The bytes as little-endian uint32 words, the last zero-padded."""
    b = np.ascontiguousarray(data, np.uint8).reshape(-1)
    if b.size % 4:
        b = np.concatenate([b, np.zeros(-b.size % 4, np.uint8)])
    return b.view("<u4").astype(np.uint32)


def lane_sums(w: np.ndarray, start: int = 0) -> tuple[int, ...]:
    """Each lane's ``sum_i g(w[i] ^ k(start + i)) mod 2**32``."""
    out = [0] * LANES
    for s in range(0, w.size, BLOCK_WORDS):
        blk = w[s:s + BLOCK_WORDS]
        pos = ((np.arange(blk.size, dtype=np.uint64) + start + s)
               & MASK32).astype(np.uint32)
        for lane, key in enumerate(LANE_KEYS):
            k = fmix32(pos * np.uint32(GOLDEN) + np.uint32(key))
            out[lane] += int(fmix32(blk ^ k).sum(dtype=np.uint64))
    return tuple(v & MASK32 for v in out)


def digest(data: np.ndarray) -> tuple[int, ...]:
    """The digest of a byte array, compared in place of the bytes."""
    return lane_sums(words(data))


def restamp(d: tuple[int, ...], head: np.ndarray,
            request: int) -> tuple[int, ...]:
    """The digest of the bytes whose digest is ``d`` once their first
    bytes ``head`` (at most ``STAMP_BYTES``) are overwritten by the stamp
    of ``request``: only the words of the head change."""
    old = lane_sums(words(head))
    new = lane_sums(words(stamp(request)[:head.size]))
    return tuple((a - o + n) & MASK32 for a, o, n in zip(d, old, new))
