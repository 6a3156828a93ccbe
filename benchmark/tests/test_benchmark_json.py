"""BENCHMARK.json keeps to the contract's limits, and everything that
belongs to one configuration, traffic mix or per-layer metric is a file of
its own that the harness finds by name."""

import importlib
import json
import os
import re

import pytest

from benchmark import harness
from benchmark.cluster import load_config
from benchmark.traffic import load_traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
BENCH = harness.load_benchmark()
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def test_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    assert len(json.dumps(BENCH)) < 64 * 1024
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_names_units_and_entries():
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["reduced"]) <= 16
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        names.append(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        names.append(w["name"])
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in [e["name"] for e in BENCH["end_to_end"]]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= \
            {w["name"] for w in BENCH["workloads"]}
        names.append(m["name"])
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    assert "setup_s" in [m["name"] for m in BENCH["end_to_end"]]
    for e in BENCH["configs"] + BENCH["workloads"]:
        for k in ("why", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] \
                    and "\t" not in e[k]
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert {w["config"] for w in BENCH["workloads"]} == \
        {c["name"] for c in BENCH["configs"]}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_resolves_to_files_by_name(cell):
    _, config_path, traffic_path = harness.find_cell(BENCH, cell)
    config, traffic = load_config(config_path), load_traffic(traffic_path)
    listed = next(c for c in BENCH["configs"]
                  if c["file"] == os.path.relpath(config_path, harness.ROOT))
    assert sorted(config["reduced"]) == sorted(listed["reduced"])
    assert traffic["rate_ops_s"] > 0
    assert harness.metrics_of(BENCH, "end_to_end", cell)
    assert harness.metrics_of(BENCH, "per_layer", cell)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_each_per_layer_metric_has_a_reader_of_its_own(metric):
    mod = importlib.import_module(f"benchmark.layer_metrics.{metric}")
    assert callable(mod.read)


def test_file_names_use_only_the_allowed_characters():
    for p in BENCH["paths"]:
        for d, _, files in os.walk(os.path.join(harness.ROOT, p)):
            if "__pycache__" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), harness.ROOT)
                assert PATH.match(rel), rel


def test_a_new_cell_is_one_data_file_and_one_entry(tmp_path, monkeypatch):
    """Open-questions row 1 (``multiraft-1k-3v.ycsb-a-over``): a traffic
    file and a ``workloads`` entry, no edit to any file that is there."""
    import copy
    import shutil

    from benchmark.traffic import make_schedule

    here = tmp_path / "benchmark"
    shutil.copytree(os.path.join(harness.HERE, "traffic"), here / "traffic")
    steady = json.load(open(here / "traffic" / "ycsb-a-steady.json"))
    over = dict(steady, name="ycsb-a-over",
                rate_ops_s=steady["rate_ops_s"] * 1.5 / 0.8)
    json.dump(over, open(here / "traffic" / "ycsb-a-over.json", "w"))
    bench = copy.deepcopy(BENCH)
    bench["workloads"].append(
        {"name": "multiraft-1k-3v.ycsb-a-over",
         "config": "multiraft-1k-3v", "traffic": "ycsb-a-over", "chips": 1,
         "why": "as ycsb-a-steady at 1.5 x knee; only goodput is judged"})
    monkeypatch.setattr(harness, "HERE", str(here))
    cell, config_path, traffic_path = harness.find_cell(
        bench, "multiraft-1k-3v.ycsb-a-over")
    config = load_config(config_path)
    sched = make_schedule(load_traffic(traffic_path), 5, 10.0,
                          config["open_groups"])
    assert len(sched) == round(over["rate_ops_s"] * 10.0)
    # every end-to-end metric is reported in every cell, so the new cell
    # reports them too
    e2e = {m["name"] for m in
           harness.metrics_of(bench, "end_to_end", cell["name"])}
    assert e2e == {"read_p50_ms", "goodput", "setup_s"}
