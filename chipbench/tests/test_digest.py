"""The check's digest: the chip form (``harness``) against the plain
definition (``reference``), a device copy against a host copy, the
stamp put in by arithmetic against a digest of the stamped bytes, and
the faults each of whose bytes must change every lane."""
import numpy as np
import pytest

from chipbench import harness, reference
from repro.core.backend_jax import SlabStore
from repro.core.elastic_pool import SLAB_BYTES

CHUNK = harness.READ_ROWS * SLAB_BYTES


def _bytes(nbytes: int, seed: int = 7) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, 256, nbytes,
                                                dtype=np.uint8)


@pytest.mark.parametrize("nbytes", [1, 7, SLAB_BYTES, SLAB_BYTES + 1,
                                    CHUNK + 3 * SLAB_BYTES + 5])
def test_chip_form_is_the_definition(nbytes):
    data = _bytes(nbytes)
    assert harness.Digester().payload(data) == reference.digest(data)


def _stores_holding(data: np.ndarray):
    """A device and a host slab store that each hold ``data`` as "x", at
    rows that are neither the first nor all in one run."""
    out = []
    for device in (True, False):
        st = SlabStore("s", 64.0, device=device)
        st.put("a", _bytes(SLAB_BYTES, 1))
        st.put("b", _bytes(3 * SLAB_BYTES, 2))
        st.drop("a")
        st.put("x", data)
        out.append(st)
    return out


@pytest.mark.parametrize("nbytes", [5, CHUNK + SLAB_BYTES + 3])
def test_device_and_host_copies_digest_alike(nbytes):
    data = _bytes(nbytes, 3)
    dev, host = _stores_holding(data)
    want = reference.digest(data)
    dig = harness.Digester()
    assert dig.copy(dev, "x") == want
    assert dig.copy(host, "x") == want


@pytest.mark.parametrize("nbytes,request_", [
    (8, 0), (9, 1), (1001, 12345), (SLAB_BYTES + 3, 2 ** 40 + 17),
    (5, 3), (64, -1)])
def test_restamp_is_a_digest_of_the_stamped_bytes(nbytes, request_):
    data = _bytes(nbytes, 4)
    head = data[:reference.STAMP_BYTES].copy()
    stamped = data.copy()
    n = min(nbytes, reference.STAMP_BYTES)
    stamped[:n] = reference.stamp(request_)[:n]
    assert reference.restamp(reference.digest(data), head, request_) \
        == reference.digest(stamped)


#: an object of three slabs and three bytes: its last word holds three
N = 3 * SLAB_BYTES + 3


def _flip(i, bit=0x01):
    def f(d):
        d[i] ^= bit
        return d
    return f


def _top_bits(d):
    for word in (10, 100_000):
        d[4 * word + 3] ^= 0x80
    return d


def _swap_rows(d):
    rows = d[:3 * SLAB_BYTES].reshape(3, SLAB_BYTES)
    rows[[0, 2]] = rows[[2, 0]]
    return d


def _zero_half(d):
    d[N // 2:] = 0
    return d


FAULTS = {
    "first_byte": _flip(0), "last_byte": _flip(N - 1),
    "stamp_byte": _flip(reference.STAMP_BYTES - 1),
    "tail_word": _flip(N - 3),
    "top_bit_of_two_words": _top_bits, "rows_swapped": _swap_rows,
    "second_half_zeroed": _zero_half,
    "another_object": lambda d: reference.payload("other", N)}


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_changes_every_lane(fault):
    data = reference.payload("obj", N)
    bad = FAULTS[fault](data.copy())
    assert bad.nbytes == N and not np.array_equal(bad, data)
    good, got = reference.digest(data), reference.digest(bad)
    assert all(a != b for a, b in zip(good, got)), (good, got)


def test_fault_on_a_device_copy_changes_every_lane():
    """The same on the chip form: one byte flipped in a stored copy."""
    data = reference.payload("obj", N)
    dev, host = _stores_holding(data)
    dig = harness.Digester()
    good = dig.copy(dev, "x")
    for st in (dev, host):
        bad = data.copy()
        bad[N // 2] ^= 0x01
        st.drop("x")
        st.put("x", bad)
        assert all(a != b for a, b in zip(good, dig.copy(st, "x")))


def test_staging_buffers_are_reused_safely():
    """One digester, several payloads of two chunks each, a longer after
    a shorter, all through its one staging buffer: every digest is the
    definition's."""
    dig = harness.Digester()
    arrays = [_bytes(CHUNK + 9 + k, 10 + k) for k in (4, 0, 3, 1)]
    assert [dig.payload(a) for a in arrays] == [
        reference.digest(a) for a in arrays]
