"""Chunked pool-slab gather/scatter Pallas TPU kernels.

The data plane of FaaSTube's store: intermediate tensors live as 2 MB
slabs in the elastic pool; a fetch materializes a logical tensor by
gathering its slab list (and a store scatters it back).  On GPU this is
cudaMemcpyAsync per chunk; on TPU one kernel walks the slab list, its
BlockSpec index_map reading the slab table via scalar prefetch — each
grid step DMAs one slab with no host round-trip.

A pool is ``(N, *slab)``: the leading axis indexes slabs and the block
is one whole slab with that axis squeezed, so the block's trailing dims
equal the array's and Mosaic's (8, 128) tiling rule holds for any slab
shape.  The device stores keep slabs 3-D (``(rows, 1024)`` per slab);
a flat ``(N, 2 MB)`` pool would put a 1-row block on the sublane axis,
which the TPU compiler refuses.

``interpret`` has no default: the caller decides (``ops`` derives it
from the JAX backend, so nothing interprets on a TPU).
"""
from __future__ import annotations

import jax
import jax.experimental.pallas.tpu as pltpu
from jax.experimental import pallas as pl


def _slab_spec(shape, index_map):
    """One whole slab of an ``(N, *slab)`` pool, slab axis squeezed."""
    zeros = (0,) * (len(shape) - 1)
    return pl.BlockSpec((None, *shape[1:]),
                        lambda i, idx_ref: (index_map(i, idx_ref), *zeros))


def _copy_kernel(idx_ref, src_ref, out_ref):
    out_ref[...] = src_ref[...]


def gather_chunks(src, idx, *, interpret: bool):
    """out[i] = src[idx[i]].  src: (N, *slab); idx: (M,) int32 ->
    (M, *slab).  Grid over M: traffic scales with the batch, not the
    pool."""
    M = idx.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M,),
        in_specs=[_slab_spec(src.shape, lambda i, idx_ref: idx_ref[i])],
        out_specs=_slab_spec(src.shape, lambda i, idx_ref: i),
    )
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, *src.shape[1:]), src.dtype),
        interpret=interpret,
    )(idx, src)


def _scatter_kernel(idx_ref, dst_ref, src_ref, out_ref):
    del dst_ref                 # aliased to out: untouched slabs stay
    out_ref[...] = src_ref[...]


def scatter_chunks(dst, src, idx, *, interpret: bool):
    """dst[idx[i]] = src[i] (non-aliasing slab writes).

    dst: (N, *slab); src: (M, *slab); idx: (M,) int32 with unique
    entries.  The output aliases ``dst`` and the grid covers only the M
    incoming slabs, so slabs not in ``idx`` are never read or written:
    in place when the caller donates ``dst`` (pipeline._scatter_into),
    one pool copy by XLA when it does not.
    """
    M = idx.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  _slab_spec(src.shape, lambda i, idx_ref: i)],
        out_specs=_slab_spec(dst.shape, lambda i, idx_ref: idx_ref[i]),
    )
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(dst.shape, dst.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(idx, dst, src)
