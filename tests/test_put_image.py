"""The row image a put hands on: a view of a payload that fills whole
slab rows, a warm image for a ragged device payload, a direct write of
whole rows and tail for a ragged host payload.  Whatever the path, the
slab rows hold the payload and zeros after it, and nothing the caller
does to its array after the put reaches the store."""
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import spans
from repro.core.backend_jax import SLAB_SHAPE, SlabStore
from repro.core.elastic_pool import SLAB_BYTES

SIZES = [1, SLAB_BYTES - 1, SLAB_BYTES, SLAB_BYTES + 1, 3 * SLAB_BYTES,
         5 * SLAB_BYTES // 2]
KINDS = {"device": True, "host": False}


def _payload(nbytes: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(1, 256, nbytes,
                                                dtype=np.uint8)


def _rows(st: SlabStore, data_id: str) -> np.ndarray:
    """The raw slab rows of an object, padding included."""
    return np.asarray(st.slabs)[list(st.objects[data_id].rows)]


def _want_rows(payload: np.ndarray) -> np.ndarray:
    rows = -(-payload.nbytes // SLAB_BYTES)
    want = np.zeros(rows * SLAB_BYTES, np.uint8)
    want[:payload.nbytes] = payload
    return want.reshape(rows, *SLAB_SHAPE)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("nbytes", SIZES)
def test_rows_hold_the_payload_then_zeros(kind, nbytes):
    st = SlabStore("s", 16.0, device=KINDS[kind])
    p = _payload(nbytes, nbytes)
    st.put("a", p)
    np.testing.assert_array_equal(st.read("a"), p)
    np.testing.assert_array_equal(_rows(st, "a"), _want_rows(p))


def test_host_rows_out_of_order():
    """Rows freed out of order come back as no run: the host write takes
    them by index, tail row last."""
    st = SlabStore("h", 16.0, device=False)
    for i in "abcde":
        st.put(i, _payload(SLAB_BYTES, ord(i)))
    for i in "ace":
        st.drop(i)
    p = _payload(2 * SLAB_BYTES + 5, 9)
    st.put("f", p)
    assert st.objects["f"].rows == (4, 2, 0)
    np.testing.assert_array_equal(st.read("f"), p)
    np.testing.assert_array_equal(_rows(st, "f"), _want_rows(p))


@pytest.mark.parametrize("kind", KINDS)
def test_smaller_ragged_put_zeroes_its_last_row(kind):
    """A ragged put after a larger one, into the same warm image and the
    same freed rows, leaves zeros past its payload."""
    st = SlabStore("s", 16.0, device=KINDS[kind])
    st.put("big", _payload(5 * SLAB_BYTES // 2, 1))
    st.drop("big")
    p = _payload(3 * SLAB_BYTES // 2, 2)
    st.put("small", p)
    np.testing.assert_array_equal(st.read("small"), p)
    raw = _rows(st, "small")
    np.testing.assert_array_equal(raw, _want_rows(p))
    assert not raw.reshape(-1)[p.nbytes:].any()


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("nbytes", [2 * SLAB_BYTES, SLAB_BYTES + 3])
def test_source_changed_after_put_leaves_the_copy(kind, nbytes):
    """The caller may alter its array in place after the put returns, as
    the benchmark stamps its payload table at its next store."""
    st = SlabStore("s", 16.0, device=KINDS[kind])
    p = _payload(nbytes, 3)
    want = p.copy()
    st.put("a", p)
    p[:] = 0
    np.testing.assert_array_equal(st.read("a"), want)
    np.testing.assert_array_equal(_rows(st, "a"), _want_rows(want))


def test_warm_image_is_reused_until_a_put_needs_more_rows():
    st = SlabStore("d", 32.0, device=True)
    sizes = {"a": 7 * SLAB_BYTES // 2, "b": 3 * SLAB_BYTES // 2,
             "c": 2 * SLAB_BYTES, "e": 9 * SLAB_BYTES // 2}
    put = {i: _payload(n, ord(i)) for i, n in sizes.items()}
    st.put("a", put["a"])
    image = st.image
    assert len(image) == 4
    st.put("b", put["b"])
    st.put("c", put["c"])                   # whole rows: a view, no copy
    assert st.image is image
    st.put("e", put["e"])
    assert st.image is not image and len(st.image) == 5
    for i, p in put.items():
        np.testing.assert_array_equal(st.read(i), p)


def test_tail_span_opens_once_per_ragged_device_put(tmp_path):
    """``ft.put.tail`` marks the device puts that copy: the ragged ones,
    never whole-row puts nor host puts."""
    dev = SlabStore("d", 16.0, device=True)
    host = SlabStore("h", 16.0, device=False)
    puts = [(dev, SLAB_BYTES + 1), (dev, 2 * SLAB_BYTES), (dev, 7),
            (dev, SLAB_BYTES), (host, SLAB_BYTES + 1), (host, SLAB_BYTES)]
    with jax.profiler.trace(str(tmp_path)):
        for i, (st, n) in enumerate(puts):
            st.put(f"o{i}", _payload(n, i))
    xplane, = Path(tmp_path).rglob("*.xplane.pb")
    names = [e.name
             for plane in jax.profiler.ProfileData.from_file(
                 str(xplane)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(spans.PREFIX)]
    assert names.count(spans.PUT_DEV) == 4
    assert names.count(spans.PUT_HOST) == 2
    assert names.count(spans.PUT_PAD) == 6
    assert names.count(spans.PUT_TAIL) == 2
