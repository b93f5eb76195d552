"""The one traffic generator: "delayed consumers".

A traffic mix is a data file (``traffic/<name>.json``) with two keys:

``mix``
    workflow names of the cell's configuration.  Requests come in rounds
    of ``len(mix)``; each round is the mix in an order drawn from the
    seed, so every seed sends the same work in another order.  The seed
    draws ``ROUNDS`` such rounds, and the requests then repeat with that
    period, so a set-up pass over two periods sees every set of requests
    a window of any length holds in flight.
``hold``
    how many steps a request's consumers are delayed.  At step ``r``
    request ``r`` starts (its host inputs are stored and fetched to its
    device ``a``, and its edges are stored on ``a`` with
    ``consumer_pos = r + hold``); then request ``r - hold`` finishes (its
    edges are fetched to ``b``, or to the host for CPU stages, its
    outputs are stored on ``b`` and fetched to the host, and all its
    objects are consumed).  ``a``/``b`` alternate between the two device
    endpoints by request.

Object ids cycle through ``hold + 1`` slots per workflow, so every id in
flight is distinct and the set of payloads is finite: the harness makes
them once, at set-up, and stamps the request's index into the first
``STAMP_BYTES`` of a payload before each store (``reference.stamp``), so
a copy left over from an earlier request of the same id reads as wrong.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

MB = 2 ** 20
#: shuffled rounds of the mix drawn from the seed before requests repeat
ROUNDS = 8


def nbytes_of(size_mb: float) -> int:
    """Bytes of an object of ``size_mb`` (the facade's own rounding)."""
    return max(1, int(round(size_mb * MB)))


@dataclass(frozen=True)
class Obj:
    data_id: str          # facade id, payload-table key and reference seed
    size_mb: float
    nbytes: int
    producer: str         # stage (or "client") that stores it
    consumer: str         # stage (or "client") that fetches it
    dst_kind: str         # "host" or "gpu": where the consumer runs


@dataclass
class Request:
    index: int
    workflow: str
    slot: int
    a: str                # producing device
    b: str                # consuming device
    inputs: list = field(default_factory=list)
    edges: list = field(default_factory=list)
    outputs: list = field(default_factory=list)

    @property
    def objects(self) -> list:
        return self.inputs + self.edges + self.outputs


def workflow_objects(prefix: str, wf: str, spec: dict, slot: int,
                     scale: float) -> tuple[list, list, list]:
    """The (inputs, edges, outputs) of one workflow at one slot."""
    kinds = spec["stages"]

    def obj(name, mb, producer, consumer, dst_kind):
        size = mb * scale
        return Obj(f"{prefix}/{wf}/{slot}/{name}", size, nbytes_of(size),
                   producer, consumer, dst_kind)

    inputs = [obj(f"in:{st}", mb, "client", st, "gpu")
              for st, mb in spec["input_mb"].items()]
    edges = [obj(f"{src}->{dst}", mb, src, dst,
                 "host" if kinds[dst] == "cpu" else "gpu")
             for src, dst, mb in spec["edges"]]
    outputs = [obj(f"out:{st}", mb, st, "client", "host")
               for st, mb in spec["output_mb"].items()]
    return inputs, edges, outputs


class DelayedConsumers:
    """Requests of one cell, in order, from the seed."""

    def __init__(self, config: dict, traffic: dict, seed: int, *,
                 scale: float = 1.0):
        self.mix = list(traffic["mix"])
        self.hold = int(traffic["hold"])
        self.devices = list(config["endpoints"])
        self.workflows = config["workflows"]
        self.scale = float(config["batch_scale"]) * scale
        self.prefix = f"s{seed}"
        rng = np.random.default_rng([seed, 0x7AFF1C])
        self._order = [self.mix[i] for _ in range(ROUNDS)
                       for i in rng.permutation(len(self.mix))]
        self._count: dict[str, int] = {}
        self._made: list[Request] = []

    def all_objects(self) -> list[Obj]:
        """Every object any request can store: each workflow of the mix
        at each of its ``hold + 1`` slots."""
        out = []
        for wf in dict.fromkeys(self.mix):
            for slot in range(self.hold + 1):
                for part in workflow_objects(self.prefix, wf,
                                             self.workflows[wf], slot,
                                             self.scale):
                    out += part
        return out

    def shape_objects(self, batch: int) -> list[Obj]:
        """One object of each size from 1 to ``batch`` slabs: moved
        through each plan kind at set-up, they compile every trigger
        batch's copy programs."""
        return [Obj(f"{self.prefix}/warm/{m}", 2.0 * m, nbytes_of(2.0 * m),
                    "warm", "warm", "gpu") for m in range(1, batch + 1)]

    @property
    def period(self) -> int:
        """Requests after which the sequence of workflows repeats."""
        return len(self._order)

    def request(self, r: int) -> Request:
        """Request ``r`` (made in order, then remembered)."""
        while len(self._made) <= r:
            self._made.append(self._next(len(self._made)))
        return self._made[r]

    def _next(self, r: int) -> Request:
        wf = self._order[r % self.period]
        slot = self._count.get(wf, 0) % (self.hold + 1)
        self._count[wf] = self._count.get(wf, 0) + 1
        a, b = self.devices if r % 2 == 0 else self.devices[::-1]
        req = Request(r, wf, slot, a, b)
        req.inputs, req.edges, req.outputs = workflow_objects(
            self.prefix, wf, self.workflows[wf], slot, self.scale)
        return req
