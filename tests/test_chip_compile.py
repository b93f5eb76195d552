"""The data plane's slab kernels compile for a TPU v5e at deployment size.

Nothing runs: each program is lowered against a described (not
attached) ``v5e:2x2`` and compiled by the TPU compiler, which refuses
what would not fit the chip or tile its memory.  The pool is 8 GiB of
2 MB slabs, the batch one trigger batch.  The topology is described in
a module fixture, never at import, and every compile stays in this one
file: only one process may load the TPU compiler's library at a time.
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.backend_jax import SLAB_SHAPE, _grow_pool
from repro.kernels.chunked_copy import gather_chunks, scatter_chunks
from repro.kernels.chunked_copy.ops import gather
from repro.kernels.chunked_copy.pipeline import BATCH_CHUNKS, _scatter_into

POOL_SLABS = 4096                       # 8 GiB of 2 MB slabs
TEMP_LIMIT = 64 * 2 ** 20


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.fixture(scope="module")
def shapes(one_chip):
    return {
        "pool": _sds((POOL_SLABS, *SLAB_SHAPE), jnp.uint8, one_chip),
        "half": _sds((POOL_SLABS // 2, *SLAB_SHAPE), jnp.uint8, one_chip),
        "batch": _sds((BATCH_CHUNKS, *SLAB_SHAPE), jnp.uint8, one_chip),
        "idx": _sds((BATCH_CHUNKS,), jnp.int32, one_chip),
    }


def test_backend_gather_temp_is_batch_sized(shapes):
    c = gather.lower(shapes["pool"], shapes["idx"],
                     use_pallas=False).compile()
    assert c.memory_analysis().temp_size_in_bytes < TEMP_LIMIT


def test_backend_donated_scatter_in_place(shapes):
    c = _scatter_into.lower(shapes["pool"], shapes["batch"], shapes["idx"],
                            use_pallas=False).compile()
    ma = c.memory_analysis()
    assert ma.temp_size_in_bytes < TEMP_LIMIT
    assert ma.alias_size_in_bytes == ma.output_size_in_bytes


def test_store_growth_peak_is_old_plus_new(shapes):
    c = _grow_pool.lower(shapes["half"], POOL_SLABS // 2).compile()
    assert c.memory_analysis().temp_size_in_bytes < TEMP_LIMIT


@pytest.mark.parametrize("kernel", ["gather", "scatter"])
def test_pallas_kernel_compiles(kernel, shapes):
    if kernel == "gather":
        fn = jax.jit(partial(gather_chunks, interpret=False))
        args = (shapes["pool"], shapes["idx"])
    else:
        fn = jax.jit(partial(scatter_chunks, interpret=False),
                     donate_argnums=0)
        args = (shapes["pool"], shapes["batch"], shapes["idx"])
    c = fn.lower(*args).compile()
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < TEMP_LIMIT
