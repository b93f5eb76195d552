"""Jit'd wrappers for pool-slab gather/scatter.

Two arms, picked by ``use_pallas``: the Pallas kernels, and plain XLA.
The Pallas arm runs compiled on a TPU and interpreted on any other
backend (the CPU tests).  Nothing here falls back from one arm to the
other.
"""
from __future__ import annotations

from functools import partial

import jax
from jax import lax

from repro.kernels.chunked_copy.kernel import gather_chunks, scatter_chunks
from repro.kernels.chunked_copy.ref import scatter_chunks_ref


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _gather_xla(src, idx):
    """out[i] = src[idx[i]] as one dynamic slice per index: temp memory
    is the batch, where XLA's gather (``src[idx]``) stages a multiple of
    the pool on TPU."""
    return jax.numpy.concatenate(
        [lax.dynamic_slice_in_dim(src, idx[i], 1)
         for i in range(idx.shape[0])])


@partial(jax.jit, static_argnames=("use_pallas",))
def gather(src, idx, *, use_pallas: bool = True):
    if not use_pallas:
        return _gather_xla(src, idx)
    return gather_chunks(src, idx, interpret=_interpret())


@partial(jax.jit, static_argnames=("use_pallas",))
def scatter(dst, src, idx, *, use_pallas: bool = True):
    if not use_pallas:
        return scatter_chunks_ref(dst, src, idx)
    return scatter_chunks(dst, src, idx, interpret=_interpret())
