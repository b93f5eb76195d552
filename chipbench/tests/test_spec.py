"""BENCHMARK.json and the files it names: every cell finds its
configuration, traffic and metric readers by name."""
import json
import re
from pathlib import Path

import pytest

from chipbench.traffic import DelayedConsumers

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()


@pytest.mark.parametrize("cell", BENCH["workloads"],
                         ids=lambda c: c["name"])
def test_cell_finds_its_files(cell):
    assert NAME.match(cell["name"]) and cell["chips"] in (1, 4)
    cfg = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / cfg["file"]).read_text())
    traffic = json.loads((ROOT / "chipbench" / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    assert set(traffic["mix"]) <= set(config["workflows"])
    gen = DelayedConsumers(config, traffic, 2 ** 31 + 1)
    reqs = [gen.request(r) for r in range(3 * len(traffic["mix"]))]
    in_flight = [{o.data_id for o in q.objects}
                 for q in reqs[:traffic["hold"] + 1]]
    assert len(set().union(*in_flight)) == sum(map(len, in_flight))
    # the sequence repeats with the generator's period
    assert [gen.request(r).workflow for r in range(gen.period)] == [
        gen.request(r + 2 * gen.period).workflow for r in range(gen.period)]
    # every seed sends the same work, in another order
    other = DelayedConsumers(config, traffic, 7)
    assert sorted(q.workflow for q in reqs) == sorted(
        other.request(r).workflow for r in range(len(reqs)))


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_has_a_reader(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert (ROOT / "chipbench" / "metrics" / f"{metric['name']}.py").is_file()
    cells = {c["name"] for c in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if "bound" in metric:
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}


def test_configs_state_their_cuts():
    for cfg in BENCH["configs"]:
        config = json.loads((ROOT / cfg["file"]).read_text())
        assert config["name"] == cfg["name"]
        assert set(cfg["reduced"]) <= set(config["assumed"])
        assert config["guarantees"]
