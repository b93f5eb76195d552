"""Bytes of the slab copy programs, counted from shapes, are the same
whichever arm runs the copy."""
import numpy as np
import pytest

from chipbench import copybytes
from repro.core.backend_jax import JaxBackend
from repro.core.transfer import Hop, TransferPlan

MB = 2 ** 20


def test_copy_bytes_reads_and_writes_every_slab():
    assert copybytes.copy_bytes(5) == 2 * 5 * 2 * MB
    assert copybytes.put_programs(3 * 2 * MB + 1) == [("scatter", 4)]


def test_plan_programs_by_hop():
    tags = ["b0:g2g", "b0:landed", "b1:g2g", "b1:landed"]
    assert copybytes.plan_programs(tags, 7, 5) == [
        ("gather", 5), ("scatter", 5), ("gather", 2), ("scatter", 2)]
    assert copybytes.plan_programs(["b0:g2h", "b0:landed"], 3, 5) == [
        ("gather", 3)]
    assert copybytes.plan_programs(["b0:h2g", "b0:landed"], 1, 5) == [
        ("scatter", 1)]
    assert copybytes.plan_programs(["h0:b0", "h1:b0"], 1, 5) is None


def _programs(use_pallas: bool, kind: str):
    be = JaxBackend(store_mb=64, host_mb=64, use_pallas=use_pallas)
    src, dst = {"g2g": ("gpu0", "gpu1"), "g2h": ("gpu0", "host"),
                "h2g": ("host", "gpu0")}[kind]
    be.put_object("x", src, payload=np.arange(13 * MB, dtype=np.uint8))
    plan = TransferPlan(kind, "f", src, dst, 13.0,
                        [Hop(src, dst, kind, staged=kind != "g2g")],
                        data_id="x")
    rep = be.execute(plan)
    return copybytes.plan_programs(rep.hop_trace, rep.n_chunks,
                                   be.batch_chunks)


@pytest.mark.parametrize("kind", ["g2g", "g2h", "h2g"])
def test_count_is_the_same_for_both_arms(kind):
    progs = _programs(True, kind)
    assert progs == _programs(False, kind)
    assert sum(m for _, m in progs) == 7 * len(copybytes.HOP_PROGRAMS[kind])
