"""Named host spans of the data plane, on the profiler's clock.

Every span is a ``jax.profiler.TraceAnnotation``, so it lands in the
same trace as the device ops it dispatches and waits for.  With no
profiler session active an annotation records nothing and costs about a
microsecond: the spans stay in the code, and nothing turns them on or
off.  Every name starts with ``PREFIX``.

``span`` opens a facade- or plan-level span with its arguments.  The
per-batch leaves are the constants below, opened with ``leaf``: one
fixed name per branch, so the hot path formats no string and builds no
argument dict.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

PREFIX = "ft."

#: one object write into a slab store, device or host, and its parts
PUT_DEV = PREFIX + "put.dev"
PUT_HOST = PREFIX + "put.host"
#: the row image a put hands on: a view of the payload's whole rows,
#: or for a ragged device payload a copy into the store's warm image
PUT_PAD = PREFIX + "put.pad"
#: that copy, inside ``PUT_PAD``: its count over ``PUT_DEV``'s is the
#: share of device puts that still copy
PUT_TAIL = PREFIX + "put.tail"
PUT_H2D = PREFIX + "put.h2d"          # the upload of the row image
PUT_SCATTER = PREFIX + "put.scatter"  # dispatch of the whole-object scatter
PUT_SYNC = PREFIX + "put.sync"        # wait for that scatter
PUT_WRITE = PREFIX + "put.write"      # a host store's rows and tail
GROW = PREFIX + "grow"                # one slab pool growth
#: one trigger batch of one hop
H2G_STAGE = PREFIX + "h2g.stage"      # host rows into the ring window
H2G_H2D = PREFIX + "h2g.h2d"
H2G_SCATTER = PREFIX + "h2g.scatter"
G2H_GATHER = PREFIX + "g2h.gather"
G2H_D2H = PREFIX + "g2h.d2h"
G2H_STAGE = PREFIX + "g2h.stage"      # downloaded rows into host memory
G2H_WRITE = PREFIX + "g2h.write"      # rows of a host destination store
G2G_GATHER = PREFIX + "g2g.gather"
G2G_SCATTER = PREFIX + "g2g.scatter"
NET_COPY = PREFIX + "net.copy"
SYNC = PREFIX + "sync"                # block_until_ready on a device pool


def span(name: str, **args) -> TraceAnnotation:
    """The span ``PREFIX + name`` carrying ``args``."""
    return TraceAnnotation(PREFIX + name, **args)


def leaf(name: str) -> TraceAnnotation:
    """The span ``name`` (one of the constants above), with no args."""
    return TraceAnnotation(name)
