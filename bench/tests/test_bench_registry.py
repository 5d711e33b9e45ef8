import json
import re

import benchpath  # noqa: F401
import pytest

from harness import registry

BENCH = registry.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_files_by_name(cell):
    c = registry.cell(BENCH, cell)
    assert c.config["name"] == next(
        w["config"] for w in BENCH["workloads"] if w["name"] == cell)
    assert c.traffic["kind"] in ("open", "closed", "train")
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(registry.metric_reader(m["name"]))
    # a per-layer metric goes only where the metric it moves is reported
    assert all(m["moves"] in names for m in c.per_layer)


def test_unknown_names_are_refused():
    with pytest.raises(registry.UnknownName):
        registry.cell(BENCH, "nosuch.cell")
    with pytest.raises(registry.UnknownName):
        registry.metric_reader("nosuch_metric")
    bad = json.loads(json.dumps(BENCH))
    bad["workloads"][0]["traffic"] = "nosuch.mix"
    with pytest.raises(registry.UnknownName):
        registry.cell(bad, CELLS[0])


def test_a_new_metric_without_workloads_follows_what_it_moves(tmp_path):
    bench = json.loads(json.dumps(BENCH))
    moved = BENCH["per_layer"][0]["moves"]
    bench["per_layer"].append({"name": "x.new", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "l", "moves": moved})
    for cell in CELLS:
        c = registry.cell(bench, cell)
        reports = moved in {m["name"] for m in c.end_to_end}
        assert ("x.new" in {m["name"] for m in c.per_layer}) == reports
    (tmp_path / "x.new.py").write_text("def read(run):\n    return None\n")
    assert registry.read_metrics(
        [{"name": "x.new", "unit": "%"}], None, tmp_path) == {}


def test_benchmark_file_keeps_to_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"] and 1 <= BENCH["run_seconds"] <= 51
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
    cfgs = {c["name"] for c in BENCH["configs"]}
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(BENCH["workloads"])
    assert {w["config"] for w in BENCH["workloads"]} == cfgs
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and m["workloads"]
        for cell in m["workloads"]:
            assert registry.cell(BENCH, cell).end_to_end
            assert m["moves"] in {x["name"] for x in
                                  registry.cell(BENCH, cell).end_to_end}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
