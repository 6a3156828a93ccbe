"""The cell PR 34 adds, ``multiraft-100k-3v.ycsb-a-steady``: its files
resolve by name and state what ISSUE 34 asks of them, and the configuration
runs through the rehearsal's path at 16 and 64 lanes on the CPU, ``correct``
with the five counts 0 against ``benchmark/reference.py`` (counts and
``correct`` only: no time leaves a CPU run)."""

import re
import time

import pytest

from benchmark import harness
from benchmark.cluster import load_config
from benchmark.rehearse import overrides_for
from benchmark.traffic import load_traffic

CELL = "multiraft-100k-3v.ycsb-a-steady"
TEN_K = "multiraft-10k-3v.ycsb-a-steady"
BENCH = harness.load_benchmark()
NEW_READERS = ("hb_round_ms", "transfer_mb_per_step")


def files(cell=CELL):
    _, config_path, traffic_path = harness.find_cell(BENCH, cell)
    return load_config(config_path), load_traffic(traffic_path)


def test_the_configuration_is_the_10k_one_but_for_two_numbers():
    config, traffic = files()
    ten_k, ten_k_traffic = files(TEN_K)
    rc = config["raft_config"]
    assert config["open_groups"] == 99999 and rc["n_groups"] == 100000
    assert {k: v for k, v in rc.items() if k != "n_groups"} == \
        {k: v for k, v in ten_k["raft_config"].items() if k != "n_groups"}
    assert rc["tick_ms"] == 1000 and rc["election_mul"] == 10.0
    assert rc["heartbeat_mul"] == 1.0 and rc["tick_stagger"] is True
    for k in ("voters", "lifecycle", "machine", "wal", "transport",
              "injected_delay_ms", "trace_slice_s", "guarantees"):
        assert config[k] == ten_k[k], k
    assert sorted(config["reduced"]) == ["chips_per_node", "heartbeat_mul",
                                         "lifecycle", "load_phase"]
    assert sorted(config["assumed"]) == [
        "engine_shape", "latency_limit_ms", "massive_regions_page",
        "recordcount", "store_size", "tick_stagger", "writeallfields"]
    one_k = files("multiraft-1k-3v.ycsb-a-steady")[0]
    assert config["guarantees"] == one_k["guarantees"]   # word for word
    assert config["latency_limit_ms"] % 10 == 0
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "multiraft-100k-3v")
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert all(s in entry["source"] for s in (
        "workloads/workloada", "tikv-configuration-file",
        "massive-regions-best-practices"))
    steady = files("multiraft-1k-3v.ycsb-a-steady")[1]
    same = [k for k in steady if k not in ("name", "what", "rate_ops_s")]
    assert all(traffic[k] == steady[k] == ten_k_traffic[k] for k in same)
    assert traffic["rate_ops_s"] % 4 == 0


def test_the_cell_reports_the_new_readers_and_every_listed_one():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1
    names = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", CELL)}
    assert set(NEW_READERS) <= names
    assert "log_ring_fill_pct" not in names
    listed = [m for m in BENCH["per_layer"] if "workloads" in m
              and m["name"] != "log_ring_fill_pct"]
    assert all(CELL in m["workloads"] for m in listed)
    every = [w["name"] for w in BENCH["workloads"]]
    for m in BENCH["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == every and m["moves"] == "read_p50_ms"


@pytest.mark.parametrize("lanes", [16, 64])
def test_rehearsal_is_correct_with_the_five_counts_zero(lanes, capsys):
    config, _ = files()
    ov = overrides_for(config, lanes)
    ov["traffic"]["key_space"] = 40     # reads meet keys already written
    res = harness.run_cell(CELL, 2_600_000_347 + lanes, 4.0, True,
                           time.time(), on_chip=False, overrides=ov)
    assert res["correct"] and res["failed"] == 0, res
    counts = re.findall(r"\[compare\] number=(\w+)=(\d+) limit=0",
                        capsys.readouterr().out)
    assert len(counts) == 5 and all(n == "0" for _, n in counts), counts
    assert set(NEW_READERS) <= set(res["metrics"])
    assert res["metrics"]["hb_round_ms"]["value"] > 0.0
    assert res["metrics"]["transfer_mb_per_step"]["value"] > 0.0
    assert res["metrics"]["transfers_per_step"]["value"] >= 4.0
    assert res["metrics"]["leaderless_pct"]["value"] == 0.0
    assert 0.0 <= res["metrics"]["lease_read_share"]["value"] <= 1.0
