"""Real JAX data plane: execute TransferPlans by moving actual bytes.

The simulator decides *when* a transfer completes; this backend makes
the same plan move *real* bytes so every simulated band has an
empirical anchor.  Objects live as 2 MB slab rows inside a real
``ElasticPool``-backed slab store per endpoint (``track_slabs`` mode
hands out concrete row indices into one preallocated ``(n, *SLAB_SHAPE)``
jax array per device, numpy array per host).  Chunked hops execute
through the double-buffered pipeline in ``kernels/chunked_copy`` —
batch k+1's gather dispatches while batch k's scatter drains, with
``block_until_ready`` only at trigger-batch boundaries — and staged
hops bounce through a preallocated host ring that mirrors
``CircularPinnedBuffer`` semantics (one trigger-batch window per
in-flight transfer, occupancy bounded by the ring size).

The two staging modes differ observably, exactly like the simulator:

``cut_through``
    batch-granular handoff — each trigger batch walks ALL hops before
    the next batch enters, intermediate hosts hold only ring windows
    (``peak_staging_mb`` ≤ one window), and the hop trace interleaves
    ``b0:g2h b0:net b0:h2g b1:g2h ...``.

``store_forward``
    full materialization per hop — hop k+1 starts only after hop k has
    landed the ENTIRE object in an intermediate host store
    (``peak_staging_mb`` == the object size), trace ``h0:b0 h0:b1 ...
    h1:b0 ...``.

Progress events carry REAL landed bytes: one event per trigger batch
whose bytes are resident at the plan destination, cumulative MB on
batch multiples (the final event lands the ragged tail).  Execution is
synchronous wall-clock work at submit time and never touches the
LinkSim event stream — a ``backend="jax"`` run's simulated trace stays
byte-identical to a plain run (tests/test_backend_jax.py).
"""
from __future__ import annotations

import time
import zlib
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spans
from repro.core.elastic_pool import BLOCK_MB, SLAB_BYTES, ElasticPool
from repro.errors import PoolCapacityError
from repro.core.linksim import BATCH_CHUNKS
from repro.core.spans import leaf
from repro.core.transfer import TransferPlan, host_of, is_device
from repro.kernels.chunked_copy.pipeline import (
    _scatter_into,
    pool_to_host,
)
from repro.kernels.chunked_copy.ops import gather

MB = 2 ** 20
#: one slab as the pools hold it: 2 MB viewed as (2048, 1024) bytes.  The
#: TPU's Pallas kernels need a block whose two minor dims tile (8, 128)
#: or equal the array's, so a whole slab is the block; host arrays share
#: the shape so every device<->host copy is a plain memcpy.
SLAB_SHAPE = (SLAB_BYTES // 1024, 1024)
#: ``JaxBackend.counters``: bytes through each host-side step — object
#: writes into device and host stores, the row image a put hands on
#: (``pad``: whole slab rows, a view of the payload when it fills whole
#: rows), host copies into the ring or staged rows, writes of host
#: destination rows, uploads and downloads
COUNTERS = ("put.dev", "put.host", "pad", "stage", "write", "h2d", "d2h")


def synth_payload(data_id: str, nbytes: int) -> np.ndarray:
    """Deterministic payload bytes for an object id — the oracle both
    the backend and the conformance tests regenerate independently."""
    seed = zlib.crc32(data_id.encode())
    return np.random.default_rng(seed).integers(
        0, 256, nbytes, dtype=np.uint8)


def nbytes_of(size_mb: float) -> int:
    return max(1, int(round(size_mb * MB)))


@dataclass
class _Obj:
    data_id: str
    nbytes: int
    buf_id: int
    rows: tuple            # slab row indices, payload order


@partial(jax.jit, static_argnums=1)
def _grow_pool(slabs, add: int):
    """``slabs`` with ``add`` zero slabs appended, in one program: the
    peak is the old pool plus the new one (an eager ``concatenate``
    would also hold a separate ``add``-slab zeros buffer)."""
    return jnp.pad(slabs, ((0, add),) + ((0, 0),) * (slabs.ndim - 1))


class SlabStore:
    """One endpoint's slab store: a preallocated pool array whose rows
    are handed out by a ``track_slabs`` ElasticPool.  ``device=True``
    keeps the pool as a jax array moved through the chunked-copy
    kernels; hosts keep numpy."""

    #: initial physical pool — a device pool memset is ~3 s/GB on a
    #: contended CPU, so stores start small and double on demand up to
    #: their capacity instead of paying the worst case up front; each
    #: doubling peaks at old + new pool (``_grow_pool``)
    START_MB = 64.0

    def __init__(self, name: str, capacity_mb: float, *,
                 device: bool = True, counters: dict | None = None):
        self.name = name
        self.device = device
        self.capacity_mb = capacity_mb
        self.counters = dict.fromkeys(COUNTERS, 0) if counters is None \
            else counters
        start = min(self.START_MB, capacity_mb)
        self.pool = ElasticPool(name, capacity_mb=start,
                                elastic=False, track_slabs=True)
        if device:
            self.slabs = jnp.zeros((self.pool.n_slabs, *SLAB_SHAPE),
                                   np.uint8)
        else:
            self.slabs = np.zeros((self.pool.n_slabs, *SLAB_SHAPE),
                                  np.uint8)
        self.objects: dict[str, _Obj] = {}
        #: warm row image of ragged device puts, grown to the most rows
        #: one has needed (``_warm_image``)
        self.image = np.empty((0, *SLAB_SHAPE), np.uint8)

    def __contains__(self, data_id: str) -> bool:
        return data_id in self.objects

    def _grow_for(self, size_mb: float) -> bool:
        """Double the physical pool (at least enough for size_mb, at
        most capacity_mb) and extend the slab array to match.  False
        when already at capacity — the caller's PoolCapacityError
        stands."""
        need = self.pool.used_mb + size_mb + BLOCK_MB
        new_cap = min(max(2 * self.pool.capacity_mb, need),
                      self.capacity_mb)
        if new_cap <= self.pool.capacity_mb:
            return False
        with leaf(spans.GROW):
            self.pool.grow(new_cap)
            add = self.pool.n_slabs - self.slabs.shape[0]
            if self.device:
                self.slabs = _grow_pool(self.slabs, add)
            else:
                grown = np.zeros((self.pool.n_slabs, *SLAB_SHAPE),
                                 np.uint8)
                grown[:self.slabs.shape[0]] = self.slabs
                self.slabs = grown
        return True

    def alloc(self, data_id: str, nbytes: int) -> _Obj:
        """Allocate rows for an incoming object (no bytes moved yet)."""
        assert data_id not in self.objects, (self.name, data_id)
        size_mb = nbytes / MB
        while True:
            try:
                buf_id, _ = self.pool.alloc(data_id, size_mb, 0.0)
                break
            except PoolCapacityError:
                if not self._grow_for(size_mb):
                    raise
        obj = _Obj(data_id, nbytes, buf_id, self.pool.bufs[buf_id].slabs)
        self.objects[data_id] = obj
        return obj

    def put(self, data_id: str, payload: np.ndarray) -> _Obj:
        """Materialize host bytes into the store (the write path).

        The row image is ``(rows, *SLAB_SHAPE)``, zero past the payload.
        A payload that fills whole rows is its own image, a view read in
        place.  A ragged one is copied into the store's warm image on a
        device (``_warm_image``); on a host its whole rows, a view, and
        its tail are written apart (``_put_rows``).  The scatter is
        waited for before returning, so the caller may then change
        ``payload`` and the next put may overwrite the warm image."""
        c = self.counters
        with leaf(spans.PUT_DEV if self.device else spans.PUT_HOST):
            payload = np.ascontiguousarray(payload, dtype=np.uint8).ravel()
            obj = self.alloc(data_id, payload.nbytes)
            n = len(obj.rows)
            whole = payload.nbytes // SLAB_BYTES
            with leaf(spans.PUT_PAD):
                if self.device and whole < n:
                    image = self._warm_image(payload, n)
                else:
                    image = payload[:whole * SLAB_BYTES].reshape(
                        whole, *SLAB_SHAPE)
            c["pad"] += n * SLAB_BYTES
            if self.device:
                idx = np.asarray(obj.rows, np.int32)
                with leaf(spans.PUT_H2D):
                    up = jnp.asarray(image)
                c["h2d"] += image.nbytes
                with leaf(spans.PUT_SCATTER):
                    self.slabs = _scatter_into(self.slabs, up, idx,
                                               use_pallas=False)
                with leaf(spans.PUT_SYNC):
                    self.slabs.block_until_ready()
                c["put.dev"] += payload.nbytes
            else:
                with leaf(spans.PUT_WRITE):
                    _put_rows(self.slabs, obj.rows, image,
                              payload[whole * SLAB_BYTES:])
                c["write"] += n * SLAB_BYTES
                c["put.host"] += payload.nbytes
        return obj

    def _warm_image(self, payload: np.ndarray, n: int) -> np.ndarray:
        """The first ``n`` rows of the warm image, holding ``payload``
        and zero after it.  The image grows only when a put needs more
        rows than it has; only the tail of row ``n`` is zeroed, since
        the payload overwrites every byte before it."""
        with leaf(spans.PUT_TAIL):
            if len(self.image) < n:
                self.image = np.empty((n, *SLAB_SHAPE), np.uint8)
            flat = self.image[:n].reshape(-1)
            flat[:payload.nbytes] = payload
            flat[payload.nbytes:] = 0
        return self.image[:n]

    def read(self, data_id: str) -> np.ndarray:
        """Materialize an object back to host bytes (verification path,
        not the data plane)."""
        obj = self.objects[data_id]
        if self.device:
            out = np.empty((len(obj.rows), *SLAB_SHAPE), np.uint8)
            pool_to_host(self.slabs, list(obj.rows), out,
                         batch=len(obj.rows))
        else:
            out = self.slabs[list(obj.rows)]
        return out.reshape(-1)[:obj.nbytes].copy()

    def drop(self, data_id: str):
        obj = self.objects.pop(data_id, None)
        if obj is not None:
            self.pool.free(obj.buf_id, 0.0)

    @property
    def used_mb(self) -> float:
        return self.pool.used_mb


def _is_run(rows) -> bool:
    """Whether ``rows`` are one ascending contiguous run.  Fresh
    allocations hand out sequential slab rows, so that is the common
    case, and a slice copies it ~2x faster than ``np.take``/fancy
    indexing for trigger-batch-sized copies."""
    return all(rows[i] == rows[0] + i for i in range(1, len(rows)))


def _take_rows(pool: np.ndarray, rows, out: np.ndarray):
    """Copy ``pool[rows]`` into ``out``."""
    if _is_run(rows):
        out[:] = pool[rows[0]:rows[0] + len(rows)]
    else:
        out[:] = pool[list(rows)]


def _put_rows(pool: np.ndarray, rows, body: np.ndarray, tail: np.ndarray):
    """Write ``body`` (whole rows) into the first ``len(body)`` of
    ``pool[rows]``, then ``tail`` (under one row of flat bytes) into the
    last row, zeroing the rest of that row."""
    n = len(body)
    if _is_run(rows):
        pool[rows[0]:rows[0] + n] = body
    else:
        pool[list(rows[:n])] = body
    if len(rows) > n:
        last = pool[rows[-1]].reshape(-1)
        last[:tail.size] = tail
        last[tail.size:] = 0


class HostRing:
    """Preallocated pinned-staging ring mirroring CircularPinnedBuffer:
    ``size_mb`` of warm chunk slots per staging host.  A staged transfer
    reserves ONE trigger-batch window (``min(transfer, batch_mb)``) for
    its lifetime and lands every batch in that same window — bounded
    occupancy is the point; double-buffering lives in the XLA dispatch
    queue, not in extra ring space.  The preallocation pays the
    first-touch page faults once, where a per-transfer buffer would pay
    them on every transfer — the CPU analogue of the paper's §6.1
    per-transfer cudaHostAlloc vs pre-pinned circular buffer."""

    def __init__(self, host: str, size_mb: float = 40.0,
                 chunk_mb: float = BLOCK_MB):
        self.host = host
        self.size_mb = size_mb
        self.slots = max(1, int(size_mb // chunk_mb))
        self.buf = np.zeros((self.slots, *SLAB_SHAPE), np.uint8)
        self.buf[:] = 0                 # first-touch every page now
        self.in_flight_mb = 0.0
        self.peak_mb = 0.0
        self.stalls = 0
        self._used = [False] * self.slots

    def acquire(self, win_chunks: int) -> tuple[int, int]:
        """Reserve a contiguous run of warm slots (contiguity keeps the
        window a VIEW of the ring, so batches really land in the
        preallocated pages).  Returns (start, n)."""
        win_chunks = min(win_chunks, self.slots)
        for start in range(self.slots - win_chunks + 1):
            if not any(self._used[start:start + win_chunks]):
                for i in range(start, start + win_chunks):
                    self._used[i] = True
                self.in_flight_mb += win_chunks * BLOCK_MB
                self.peak_mb = max(self.peak_mb, self.in_flight_mb)
                return start, win_chunks
        # a real executor would queue here; the synchronous hop walk
        # holds at most one window per ring, so a miss marks a
        # mis-sized ring rather than a deadlock
        self.stalls += 1
        self.in_flight_mb += win_chunks * BLOCK_MB
        self.peak_mb = max(self.peak_mb, self.in_flight_mb)
        return 0, win_chunks

    def release(self, win: tuple[int, int]):
        start, n = win
        for i in range(start, min(start + n, self.slots)):
            self._used[i] = False
        self.in_flight_mb -= n * BLOCK_MB

    def window(self, win: tuple[int, int], n: int) -> np.ndarray:
        """A view of the first n chunk rows of a reserved window (every
        batch reuses the same warm slots — bounded occupancy)."""
        start, cap = win
        assert n <= cap, (n, cap)
        return self.buf[start:start + n]


@dataclass
class ExecReport:
    """What one real plan execution did — the observable record the
    conformance suite and the demo read."""
    kind: str
    func: str
    src: str
    dst: str
    size_mb: float
    staging: str
    n_chunks: int
    n_batches: int
    stripes: int
    wall_ms: float = 0.0
    peak_staging_mb: float = 0.0
    #: (landed_mb_at_destination, wall_ms_since_start) per trigger batch
    events: list = field(default_factory=list)
    #: per-batch per-hop steps, in execution order
    hop_trace: list = field(default_factory=list)


class JaxBackend:
    """Executes TransferPlans with real bytes.  One instance owns every
    endpoint's slab store and every host's staging ring; stores are
    created lazily so a fleet topology only pays for endpoints that
    actually move data.  Capacity here is physical (bytes must land
    somewhere) — admission/spill POLICY stays with the simulator's own
    ElasticPools."""

    def __init__(self, *, store_mb: float = 256.0, host_mb: float = 1024.0,
                 ring_mb: float = 40.0, batch_chunks: int = BATCH_CHUNKS,
                 use_pallas: bool = False):
        self.store_mb = store_mb
        self.host_mb = host_mb
        self.ring_mb = ring_mb
        self.batch_chunks = batch_chunks
        self.use_pallas = use_pallas
        self.stores: dict[str, SlabStore] = {}
        self.rings: dict[str, HostRing] = {}
        self.reports: list[ExecReport] = []
        #: int bytes per ``COUNTERS`` key, beside the spans that time them
        self.counters = dict.fromkeys(COUNTERS, 0)

    # ------------------------------------------------------------ stores --
    def store_for(self, endpoint: str) -> SlabStore:
        st = self.stores.get(endpoint)
        if st is None:
            dev = is_device(endpoint)
            st = SlabStore(endpoint,
                           self.store_mb if dev else self.host_mb,
                           device=dev, counters=self.counters)
            self.stores[endpoint] = st
        return st

    def ring_for(self, host: str) -> HostRing:
        r = self.rings.get(host)
        if r is None:
            r = HostRing(host, self.ring_mb)
            self.rings[host] = r
        return r

    def put_object(self, data_id: str, endpoint: str,
                   payload: np.ndarray | None = None,
                   size_mb: float | None = None):
        """Register real bytes at an endpoint.  Without an explicit
        payload the deterministic synthetic one is materialized (the
        facade stores declared-size objects, not user tensors)."""
        if payload is None:
            payload = synth_payload(data_id, nbytes_of(size_mb))
        st = self.store_for(endpoint)
        if data_id in st:
            st.drop(data_id)
        return st.put(data_id, payload)

    def read_object(self, data_id: str, endpoint: str) -> np.ndarray:
        return self.store_for(endpoint).read(data_id)

    def drop_object(self, data_id: str, endpoint: str | None = None):
        stores = ([self.stores[endpoint]] if endpoint in self.stores
                  else self.stores.values()) if endpoint else \
            self.stores.values()
        for st in list(stores):
            st.drop(data_id)

    def where(self, data_id: str) -> list[str]:
        return sorted(n for n, st in self.stores.items() if data_id in st)

    # ----------------------------------------------------------- execute --
    def execute(self, plan: TransferPlan, *, on_progress=None
                ) -> ExecReport | None:
        """Move a plan's real bytes src -> dst, synchronously.

        Returns the ExecReport (also appended to ``self.reports``), or
        None for plans with no object identity / no hops — those move
        nothing real.  The source object is synthesized on demand so
        every identified plan can execute."""
        if not getattr(plan, "data_id", "") or plan.local:
            return None
        src_st = self.store_for(plan.src)
        if plan.data_id not in src_st:
            self.put_object(plan.data_id, plan.src, size_mb=plan.size_mb)
        obj = src_st.objects[plan.data_id]
        n_chunks = len(obj.rows)
        batch = self.batch_chunks
        n_batches = -(-n_chunks // batch)
        stripes = 2 if any(h.multipath for h in plan.hops) \
            and n_chunks > 1 else 1
        rep = ExecReport(plan.kind, plan.func, plan.src, plan.dst,
                         plan.size_mb, plan.staging, n_chunks, n_batches,
                         stripes)

        def landed(nrows: int, tag: str):
            mb = min(nrows * BLOCK_MB, plan.size_mb)
            rep.events.append(
                (mb, (time.perf_counter() - t0) * 1e3))
            if on_progress is not None:
                on_progress(mb)
            rep.hop_trace.append(tag)

        with spans.span("exec." + plan.kind, mb=plan.size_mb,
                        staging=plan.staging):
            t0 = time.perf_counter()
            if plan.staging == "store_forward" and len(plan.hops) > 1:
                self._store_forward(plan, obj, rep, landed)
            else:
                self._cut_through(plan, obj, rep, landed)
            rep.wall_ms = (time.perf_counter() - t0) * 1e3
        self.reports.append(rep)
        return rep

    # one trigger batch's row range, striped round-robin when multipath
    def _batches(self, n: int):
        for s in range(0, n, self.batch_chunks):
            yield s, min(s + self.batch_chunks, n)

    def _dst_rows(self, plan: TransferPlan, obj: _Obj) -> tuple:
        """Rows at the final destination store (fresh copy; replaces a
        stale same-id copy so re-fetch after update stays coherent)."""
        dst_st = self.store_for(plan.dst)
        if plan.data_id in dst_st:
            dst_st.drop(plan.data_id)
        return dst_st.alloc(plan.data_id, obj.nbytes).rows

    # --------------------------------------------------- cut-through walk -
    def _cut_through(self, plan: TransferPlan, obj: _Obj, rep: ExecReport,
                     landed):
        """Batch-granular handoff: each trigger batch walks the whole
        hop chain before the next enters; intermediate hosts hold only
        one ring window."""
        src_st = self.store_for(plan.src)
        dst_st = self.store_for(plan.dst)
        dst_rows = self._dst_rows(plan, obj)
        hops = plan.hops
        staged_hosts = []
        for h in hops:
            if h.staged:
                key = h.src if h.kind == "h2g" else h.dst
                staged_hosts.append(key)
        # one trigger-batch window per staging host, held for the whole
        # transfer — CircularPinnedBuffer's window_mb reservation
        win_chunks = min(self.batch_chunks, len(obj.rows))
        wins = {hk: self.ring_for(hk).acquire(win_chunks)
                for hk in dict.fromkeys(staged_hosts)}
        rep.peak_staging_mb = max(
            (self.rings[hk].in_flight_mb for hk in wins), default=0.0)
        c = self.counters
        try:
            for bi, (s, e) in enumerate(self._batches(len(obj.rows))):
                nb = e - s
                cur = None          # host-side rows of the batch in flight
                for hi, h in enumerate(hops):
                    tag = f"b{bi}:{h.kind}"
                    if h.kind == "g2g":
                        # direct device->device, striped across the
                        # multipath set chunk-by-chunk (round-robin —
                        # same bytes, observable stripe interleave)
                        order = self._stripe_order(nb, rep.stripes)
                        sidx = np.asarray(obj.rows[s:e], np.int32)[order]
                        didx = np.asarray(dst_rows[s:e], np.int32)[order]
                        with leaf(spans.G2G_GATHER):
                            g = gather(src_st.slabs, sidx,
                                       use_pallas=self.use_pallas)
                        with leaf(spans.SYNC):
                            dst_st.slabs.block_until_ready()
                        with leaf(spans.G2G_SCATTER):
                            dst_st.slabs = _scatter_into(
                                dst_st.slabs, g, didx,
                                use_pallas=self.use_pallas)
                    elif h.kind == "g2h":
                        win = self.ring_for(h.dst).window(wins[h.dst], nb)
                        with leaf(spans.G2H_GATHER):
                            g = gather(src_st.slabs,
                                       np.asarray(obj.rows[s:e], np.int32),
                                       use_pallas=self.use_pallas)
                        with leaf(spans.G2H_D2H):
                            got = np.asarray(g)    # d2h sync is the copy
                        c["d2h"] += got.nbytes
                        with leaf(spans.G2H_STAGE):
                            win[:] = got
                        c["stage"] += win.nbytes
                        cur = win
                        if h.dst == plan.dst:      # plan ends on a host
                            with leaf(spans.G2H_WRITE):
                                dst_st.slabs[list(dst_rows[s:e])] = win
                            c["write"] += win.nbytes
                    elif h.kind in ("net", "h2h"):
                        dwin_key = hops[hi + 1].src \
                            if hi + 1 < len(hops) else None
                        if dwin_key is not None and dwin_key in wins:
                            dwin = self.ring_for(dwin_key).window(
                                wins[dwin_key], nb)
                            with leaf(spans.NET_COPY):
                                np.copyto(dwin, cur)
                            c["stage"] += dwin.nbytes
                            cur = dwin
                        else:       # pure h2h plan: host store rows
                            src_rows = obj.rows[s:e]
                            with leaf(spans.NET_COPY):
                                dst_st.slabs[list(dst_rows[s:e])] = \
                                    src_st.slabs[list(src_rows)]
                            c["write"] += nb * SLAB_BYTES
                    elif h.kind == "h2g":
                        if cur is None:        # plan starts on a host:
                            # stage the batch through the src host's
                            # warm ring window, like pinned staging —
                            # gathered straight into the warm pages,
                            # no temp copy
                            with leaf(spans.H2G_STAGE):
                                if h.src in wins:
                                    cur = self.ring_for(h.src).window(
                                        wins[h.src], nb)
                                    _take_rows(src_st.slabs,
                                               obj.rows[s:e], cur)
                                else:
                                    cur = src_st.slabs[
                                        list(obj.rows[s:e])]
                            c["stage"] += cur.nbytes
                        with leaf(spans.H2G_H2D):
                            up = jnp.asarray(np.ascontiguousarray(cur))
                        c["h2d"] += cur.nbytes
                        with leaf(spans.SYNC):
                            dst_st.slabs.block_until_ready()
                        with leaf(spans.H2G_SCATTER):
                            dst_st.slabs = _scatter_into(
                                dst_st.slabs, up,
                                np.asarray(dst_rows[s:e], np.int32),
                                use_pallas=self.use_pallas)
                    rep.hop_trace.append(tag)
                # boundary sync: the batch is REALLY at the destination
                if dst_st.device:
                    with leaf(spans.SYNC):
                        dst_st.slabs.block_until_ready()
                landed(e, f"b{bi}:landed")
        finally:
            for hk, slots in wins.items():
                self.rings[hk].release(slots)

    def _stripe_order(self, n: int, stripes: int) -> np.ndarray:
        if stripes <= 1:
            return np.arange(n)
        # round-robin chunk assignment across the stripe set, then
        # stripe-major order — the interleave a striped submission lands
        return np.argsort(np.arange(n) % stripes, kind="stable")

    # ------------------------------------------------- store-forward walk -
    def _store_forward(self, plan: TransferPlan, obj: _Obj,
                       rep: ExecReport, landed):
        """Full materialization per hop: hop k lands the WHOLE object at
        an intermediate host store before hop k+1 starts."""
        n = len(obj.rows)
        cur_ep, cur_rows = plan.src, obj.rows
        inter: list[str] = []
        c = self.counters
        for hi, h in enumerate(plan.hops):
            final = hi + 1 == len(plan.hops)
            dst_ep = plan.dst if final else \
                (h.dst if not is_device(h.dst) else host_of(h.dst))
            src_st = self.store_for(cur_ep)
            dst_st = self.store_for(dst_ep)
            if final:
                nxt_rows = self._dst_rows(plan, obj)
            else:
                if plan.data_id in dst_st:
                    dst_st.drop(plan.data_id)
                nxt_rows = dst_st.alloc(plan.data_id, obj.nbytes).rows
                inter.append(dst_ep)
            for bi, (s, e) in enumerate(self._batches(n)):
                nbytes = (e - s) * SLAB_BYTES
                if src_st.device and dst_st.device:
                    with leaf(spans.G2G_GATHER):
                        g = gather(src_st.slabs,
                                   np.asarray(cur_rows[s:e], np.int32),
                                   use_pallas=self.use_pallas)
                    with leaf(spans.SYNC):
                        dst_st.slabs.block_until_ready()
                    with leaf(spans.G2G_SCATTER):
                        dst_st.slabs = _scatter_into(
                            dst_st.slabs, g,
                            np.asarray(nxt_rows[s:e], np.int32),
                            use_pallas=self.use_pallas)
                elif src_st.device:
                    with leaf(spans.G2H_STAGE):
                        out = dst_st.slabs[list(nxt_rows[s:e])]
                    c["stage"] += nbytes
                    with leaf(spans.G2H_D2H):
                        pool_to_host(src_st.slabs, list(cur_rows[s:e]),
                                     out, batch=self.batch_chunks,
                                     use_pallas=self.use_pallas)
                    c["d2h"] += nbytes
                    with leaf(spans.G2H_WRITE):
                        dst_st.slabs[list(nxt_rows[s:e])] = out
                    c["write"] += nbytes
                elif dst_st.device:
                    with leaf(spans.H2G_STAGE):
                        rows = src_st.slabs[list(cur_rows[s:e])]
                    c["stage"] += nbytes
                    with leaf(spans.H2G_H2D):
                        up = jnp.asarray(rows)
                    c["h2d"] += nbytes
                    with leaf(spans.SYNC):
                        dst_st.slabs.block_until_ready()
                    with leaf(spans.H2G_SCATTER):
                        dst_st.slabs = _scatter_into(
                            dst_st.slabs, up,
                            np.asarray(nxt_rows[s:e], np.int32),
                            use_pallas=self.use_pallas)
                else:
                    with leaf(spans.NET_COPY):
                        dst_st.slabs[list(nxt_rows[s:e])] = \
                            src_st.slabs[list(cur_rows[s:e])]
                    c["write"] += nbytes
                if final:
                    if dst_st.device:
                        with leaf(spans.SYNC):
                            dst_st.slabs.block_until_ready()
                    landed(e, f"h{hi}:b{bi}")
                else:
                    rep.hop_trace.append(f"h{hi}:b{bi}")
            if dst_st.device:
                with leaf(spans.SYNC):
                    dst_st.slabs.block_until_ready()
            # the whole object now sits at this hop's landing store
            rep.peak_staging_mb = max(
                rep.peak_staging_mb,
                sum(self.stores[ep].objects[plan.data_id].nbytes / MB
                    for ep in inter if plan.data_id in self.stores[ep]))
            cur_ep, cur_rows = dst_ep, nxt_rows
        for ep in inter:            # intermediates drain after landing
            if ep not in (plan.src, plan.dst):
                self.stores[ep].drop(plan.data_id)
