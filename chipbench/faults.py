"""Faults planted under the timed path, to show that the check fails
them.  Not used by the benchmark's own runs.

``flip``
    one byte of every copy a plan delivers is altered where it is
    produced: the control, which breaks the guarantee that fetches (and
    spills, reloads and prefetches) deliver byte-exact copies.
``flip:<kind>``
    the same, for plans of one kind only (``flip:spill``: a spill that
    does not keep a byte-exact host copy).
``half``
    the second half of every delivered copy is left unwritten (zeros).
``skip``
    a plan returns without moving anything: the step leaves the state
    as it was.
``stale``
    a store writes the bytes its id held at the id's previous store:
    the store leaves the old copy in place of the new one.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from chipbench.harness import TableBackend, read_copy
from chipbench.reference import STAMP_BYTES
from repro.kernels.chunked_copy.pipeline import _scatter_into


def _rewrite(store, data_id: str, fn):
    """Replace the bytes a store holds for ``data_id`` by
    ``fn(bytes)``."""
    obj = store.objects[data_id]
    data = fn(read_copy(store, data_id).copy())
    rows = np.zeros((len(obj.rows), *store.slabs.shape[1:]), np.uint8)
    rows.reshape(-1)[:data.nbytes] = data
    if store.device:
        store.slabs = _scatter_into(store.slabs, jnp.asarray(rows),
                                    np.asarray(obj.rows, np.int32))
        store.slabs.block_until_ready()
    else:
        store.slabs[np.asarray(obj.rows)] = rows


def _flip(data):
    data[data.nbytes // 2] ^= 0x01
    return data


def _half(data):
    data[data.nbytes // 2:] = 0
    return data


class FaultyBackend(TableBackend):
    """``TableBackend`` with one fault switched on by ``fault``."""

    fault: str | None = None

    def __init__(self, table: dict, **kw):
        super().__init__(table, **kw)
        #: data_id -> the stamp its previous store wrote
        self._stamps: dict[str, np.ndarray] = {}

    def put_object(self, data_id, endpoint, payload=None, size_mb=None):
        entry = self.table.get(data_id) if payload is None else None
        if entry is not None:
            old = self._stamps.get(data_id)
            self._stamps[data_id] = entry[:STAMP_BYTES].copy()
            if self.fault == "stale" and old is not None:
                payload = entry.copy()
                payload[:STAMP_BYTES] = old
        return super().put_object(data_id, endpoint, payload=payload,
                                  size_mb=size_mb)

    def execute(self, plan, *, on_progress=None):
        fault, _, only = (self.fault or "").partition(":")
        hit = fault in ("flip", "half", "skip") and plan.data_id \
            and not plan.local and only in ("", plan.kind)
        if hit and fault == "skip":
            return None
        rep = super().execute(plan, on_progress=on_progress)
        if hit and rep is not None:
            _rewrite(self.stores[plan.dst], plan.data_id,
                     _flip if fault == "flip" else _half)
        return rep
