"""Device bytes of the slab copy programs, counted from shapes.

A gather or a scatter of M slabs reads M slabs and writes M slabs,
whichever arm (Pallas or XLA) implements it.  What the data plane runs
is read from its own record: each plan's hop trace (``b<k>:<hop>``, one
tag per trigger batch and hop) and the object sizes the facade stored.
"""
from __future__ import annotations

import re

SLAB_BYTES = 2 * 2 ** 20

#: device programs each cut-through hop of one trigger batch runs
HOP_PROGRAMS = {"g2g": ("gather", "scatter"), "g2h": ("gather",),
                "h2g": ("scatter",)}
_TAG = re.compile(r"b(\d+):(\w+)$")


def copy_bytes(m_slabs: int) -> int:
    """HBM bytes one gather or scatter of ``m_slabs`` moves."""
    return 2 * m_slabs * SLAB_BYTES


def slabs_of(nbytes: int) -> int:
    return -(-nbytes // SLAB_BYTES)


def plan_programs(hop_trace, n_chunks: int, batch: int) -> list | None:
    """``[(program, m_slabs), ...]`` that a cut-through plan ran, or None
    where its trace holds a tag this count does not know (a
    store-and-forward walk)."""
    out = []
    for tag in hop_trace:
        m = _TAG.match(tag)
        if m is None:
            return None
        k, hop = int(m.group(1)), m.group(2)
        rows = min(batch, n_chunks - k * batch)
        out += [(p, rows) for p in HOP_PROGRAMS.get(hop, ())]
    return out


def put_programs(nbytes: int) -> list:
    """A device store writes the whole object in one scatter."""
    return [("scatter", slabs_of(nbytes))]
