"""Pallas kernel validation: interpret-mode vs pure-jnp oracles, with
property sweeps over shapes/dtypes (hypothesis when installed, the
deterministic _hyp sweep otherwise)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels.chunked_copy import (
    copy_slabs_pipelined, copy_slabs_sequential, gather_chunks,
    gather_chunks_ref, scatter_chunks, scatter_chunks_ref)
from repro.kernels.chunked_copy.ops import gather, scatter
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.paged_attention import paged_attention, paged_attention_ref


# ------------------------------------------------------ flash attention ---

@settings(max_examples=12, deadline=None)
@given(
    b=st.sampled_from([1, 2]),
    hkv=st.sampled_from([1, 2, 4]),
    group=st.sampled_from([1, 2, 4]),
    lq=st.sampled_from([128, 256]),
    lk_extra=st.sampled_from([0, 128]),
    d=st.sampled_from([64, 128]),
    causal=st.booleans(),
    window=st.sampled_from([0, 64]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_flash_attention_property(b, hkv, group, lq, lk_extra, d, causal,
                                  window, dtype):
    lk = lq + lk_extra
    hq = hkv * group
    key = jax.random.key(hash((b, hq, lq, lk, d, causal, window)) % 2**31)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, hq, lq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, hkv, lk, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, hkv, lk, d), jnp.float32).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol)


def test_flash_attention_matches_blockwise_model_path():
    """kernel == the jnp blockwise twin used in the dry-run lowering."""
    from repro.models.attention import blockwise_attention
    key = jax.random.key(7)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (2, 4, 256, 64), jnp.float32)
    k = jax.random.normal(ks[1], (2, 2, 256, 64), jnp.float32)
    v = jax.random.normal(ks[2], (2, 2, 256, 64), jnp.float32)
    a = flash_attention(q, k, v, causal=True, interpret=True)
    b = blockwise_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


# ------------------------------------------------------ paged attention ---

@settings(max_examples=10, deadline=None)
@given(
    b=st.sampled_from([1, 2, 3]),
    hkv=st.sampled_from([1, 2]),
    group=st.sampled_from([1, 2, 4]),
    d=st.sampled_from([64, 128]),
    page=st.sampled_from([128, 256]),
    np_=st.sampled_from([2, 4]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_paged_attention_property(b, hkv, group, d, page, np_, dtype):
    P = np_ * 4
    hq = hkv * group
    key = jax.random.key(hash((b, hq, d, page, np_)) % 2**31)
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[1], (P, page, hkv, d), jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], (P, page, hkv, d), jnp.float32).astype(dtype)
    pt = jax.random.randint(ks[3], (b, np_), 0, P, jnp.int32)
    sl = jax.random.randint(ks[4], (b,), 1, np_ * page, jnp.int32)
    out = paged_attention(q, kp, vp, pt, sl, interpret=True)
    ref = paged_attention_ref(q, kp, vp, pt, sl)
    tol = 5e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol)


# -------------------------------------------------------- chunked copy ----

@settings(max_examples=10, deadline=None)
@given(
    n=st.sampled_from([8, 32]),
    m=st.integers(1, 8),
    c=st.sampled_from([128, 256]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16, jnp.int8]),
)
def test_chunked_gather_scatter_property(n, m, c, dtype):
    key = jax.random.key(hash((n, m, c)) % 2**31)
    if dtype == jnp.int8:
        src = jax.random.randint(key, (n, c), -128, 127, jnp.int32).astype(jnp.int8)
        new = jax.random.randint(jax.random.key(1), (m, c), -128, 127,
                                 jnp.int32).astype(jnp.int8)
    else:
        src = jax.random.normal(key, (n, c), jnp.float32).astype(dtype)
        new = jax.random.normal(jax.random.key(1), (m, c),
                                jnp.float32).astype(dtype)
    idx = jax.random.permutation(jax.random.key(2), n)[:m].astype(jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(gather_chunks(src, idx, interpret=True)),
        np.asarray(gather_chunks_ref(src, idx)))
    dst = jnp.zeros((n, c), dtype)
    np.testing.assert_array_equal(
        np.asarray(scatter_chunks(dst, new, idx, interpret=True)),
        np.asarray(scatter_chunks_ref(dst, new, idx)))


# both kernel arms: the pallas interpret kernel and the XLA arm must be
# interchangeable everywhere the backend flips use_pallas
PALLAS_ARMS = [False, True]


@pytest.mark.parametrize("use_pallas", PALLAS_ARMS)
def test_gather_scatter_roundtrip(use_pallas):
    """gather(pool_a) -> scatter(pool_b) round-trips bytes exactly on
    both kernel arms, including out-of-order row mappings."""
    rng = np.random.default_rng(11)
    src = jnp.asarray(rng.integers(0, 256, (12, 256), dtype=np.uint8))
    dst = jnp.zeros((12, 256), jnp.uint8)
    sidx = jnp.asarray([3, 0, 7, 11, 5], jnp.int32)
    didx = jnp.asarray([1, 9, 2, 6, 10], jnp.int32)
    g = gather(src, sidx, use_pallas=use_pallas)
    out = scatter(dst, g, didx, use_pallas=use_pallas)
    np.testing.assert_array_equal(
        np.asarray(out)[np.asarray(didx)], np.asarray(src)[np.asarray(sidx)])
    untouched = [i for i in range(12) if i not in np.asarray(didx)]
    assert not np.asarray(out)[untouched].any()


@pytest.mark.parametrize("use_pallas", PALLAS_ARMS)
@pytest.mark.parametrize("copy_fn", [copy_slabs_sequential,
                                     copy_slabs_pipelined])
def test_copy_slabs_roundtrip(copy_fn, use_pallas):
    """Both pipeline arms move identical bytes pool-to-pool on both
    kernel arms, with a ragged final batch (7 chunks, batch 5)."""
    rng = np.random.default_rng(13)
    src = jnp.asarray(rng.integers(0, 256, (9, 128), dtype=np.uint8))
    dst = jnp.zeros((9, 128), jnp.uint8)
    sidx = list(range(7))
    didx = [8, 6, 4, 2, 0, 1, 3]
    events = []
    kw = {"on_chunk" if copy_fn is copy_slabs_sequential else "on_batch":
          events.append, "use_pallas": use_pallas}
    out = copy_fn(src, sidx, dst, didx, **kw)
    np.testing.assert_array_equal(
        np.asarray(out)[didx], np.asarray(src)[sidx])
    assert events[-1] == 7 and events == sorted(events)
    if copy_fn is copy_slabs_pipelined:
        assert events == [5, 7]      # trigger-batch boundaries + tail
