"""CPU rehearsal of the benchmark: each cell's traffic at 1/32 of its
size through the harness body, and the refusal to run without a TPU."""
from pathlib import Path

import pytest

from chipbench import harness, run

ROOT = Path(__file__).resolve().parents[2]
SCALE = 1 / 32
SEED = 2 ** 31 + 12345


def _run(cell: str, traced: bool = False, seconds: float = 1.0):
    spec = harness.load_spec(cell, ROOT)
    lines = []
    out = harness.run_cell(spec, SEED, seconds, traced, t_start=0.0,
                           peaks=None, scale=SCALE, workers=2,
                           log=lines.append)
    return spec, out, lines


CELLS = ["table1.media", "memstress_b2.held"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_body_tiny(cell):
    spec, out, lines = _run(cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["checks"]["window_compiles"]["value"] == 0
    assert any(line.startswith("chipbench: window") for line in lines)
    # every finished request is read back, and every store stayed at the
    # size set-up grew it to
    assert out["checks"]["copies_checked"]["value"] > 0
    assert 0 < out["store_live_peak_bytes"] <= out["store_pool_bytes"]


@pytest.mark.parametrize("cell", CELLS)
def test_row_ledger_counts_what_the_stores_hold(cell):
    """Driven through the same calls, the row ledger holds as many slab
    rows at each endpoint as the real store, step by step."""
    spec = harness.load_spec(cell, ROOT)
    dry = harness.Cell(spec, SEED, scale=SCALE)
    real = harness.Cell(spec, SEED, scale=SCALE, table=harness.make_table(
        dry.gen.all_objects() + dry.gen.shape_objects(5), 2))
    for c in (dry, real):
        c.warm_up()
    for _ in range(dry.gen.period):
        for c in (dry, real):
            c.step(c.r)
            c.r += 1
        assert {ep: st.used for ep, st in dry.backend.stores.items()} == {
            ep: st.pool.used_blocks
            for ep, st in real.backend.stores.items()}


@pytest.mark.parametrize("cell", CELLS)
def test_stores_grow_once_to_the_traffic_peak(cell):
    """Each store is grown to the first doubling of its start size that
    holds the ledger's peak, not to its capacity."""
    spec = harness.load_spec(cell, ROOT)
    targets = harness.pool_targets(spec, SEED, SCALE)
    built = harness.build(spec, SEED, scale=SCALE, workers=2)
    for ep, st in built.backend.stores.items():
        assert st.pool.capacity_mb == targets[ep] < st.capacity_mb


def test_traced_body_reports_per_layer_metrics():
    spec, out, _ = _run("memstress_b2.held", traced=True)
    assert out["correct"], out["checks"]
    names = {m["name"] for m in spec["per_layer"]}
    # the CPU trace has no device plane: the trace readers stay silent
    assert set(out["metrics"]) == names - {"copy_roofline", "device_idle"}
    assert out["metrics"]["spill_MB_per_req"]["value"] > 0
    assert out["metrics"]["reload_MB_per_req"]["value"] > 0


def test_main_refuses_cpu(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "table1.media", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert "no TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""


def test_unknown_device_kind_is_an_error():
    assert run.load_peaks("TPU v5 lite")["hbm_GBps"] == 819.0
    with pytest.raises(SystemExit):
        run.load_peaks("cpu")
