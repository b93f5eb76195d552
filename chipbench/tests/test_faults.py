"""The check fails a broken data plane: each planted fault, under the
rest of a run at 1/32 size on the CPU, turns ``correct`` false."""
from pathlib import Path

import pytest

from chipbench import harness
from chipbench.faults import FaultyBackend

ROOT = Path(__file__).resolve().parents[2]


def _faulty_run(cell: str, fault: str):
    class Broken(FaultyBackend):
        pass
    Broken.fault = fault
    spec = harness.load_spec(cell, ROOT)
    return harness.run_cell(spec, 2 ** 31 + 99, 1.0, False, t_start=0.0,
                            peaks=None, scale=1 / 32, backend_cls=Broken,
                            workers=2, log=lambda s: None)


@pytest.mark.parametrize("cell,fault", [
    ("table1.media", "flip"), ("table1.media", "half"),
    ("table1.media", "skip"), ("table1.media", "stale"),
    ("memstress_b2.held", "flip:spill"), ("memstress_b2.held", "flip:reload"),
    ("memstress_b2.held", "skip"), ("memstress_b2.held", "stale")])
def test_fault_is_not_correct(cell, fault):
    out = _faulty_run(cell, fault)
    assert not out["correct"]
    c = out["checks"]
    assert c["copies_wrong"]["value"] + c["copies_missing"]["value"] \
        + c["index_wrong"]["value"] + c["sources_missing"]["value"] > 0


def test_stale_store_reads_wrong_only_by_its_stamp():
    """A store that leaves the previous request's copy differs from the
    reference in the stamp alone: the check fails it by the stamp."""
    out = _faulty_run("table1.media", "stale")
    c = out["checks"]
    assert c["copies_wrong"]["value"] > 0
    assert c["copies_missing"]["value"] == c["index_wrong"]["value"] == 0
