"""The cell PR 31 adds, ``multiraft-10k-3v.ycsb-a-steady``: its files
resolve by name and state what ISSUE 31 asks of them, and the configuration
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

CELL = "multiraft-10k-3v.ycsb-a-steady"
BENCH = harness.load_benchmark()
NEW_READERS = ("lease_read_share", "host_lanes_per_step",
               "transfers_per_step", "leaderless_pct")


def files():
    _, config_path, traffic_path = harness.find_cell(BENCH, CELL)
    return load_config(config_path), load_traffic(traffic_path)


def test_the_configuration_is_the_sourced_size_on_the_sources_tick():
    config, traffic = files()
    rc = config["raft_config"]
    assert config["open_groups"] == 9999 and rc["n_groups"] == 10000
    assert rc["tick_ms"] == 1000 and rc["election_mul"] == 10.0
    assert rc["tick_stagger"] is True
    assert sorted(config["reduced"]) == ["chips_per_node", "heartbeat_mul",
                                         "lifecycle", "load_phase"]
    one_k = load_config(harness.find_cell(
        BENCH, "multiraft-1k-3v.ycsb-a-steady")[1])
    assert config["guarantees"] == one_k["guarantees"]   # word for word
    steady = load_traffic(harness.find_cell(
        BENCH, "multiraft-1k-3v.ycsb-a-steady")[2])
    same = [k for k in steady if k not in ("name", "what", "rate_ops_s")]
    assert all(traffic[k] == steady[k] for k in same)
    assert traffic["rate_ops_s"] % 4 == 0


def test_the_cell_reports_the_new_readers_and_every_listed_one():
    names = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", CELL)}
    assert set(NEW_READERS) <= names
    assert "log_ring_fill_pct" not in names
    listed = [m for m in BENCH["per_layer"] if "workloads" in m
              and m["name"] != "log_ring_fill_pct"]
    assert all(CELL in m["workloads"] for m in listed)


@pytest.mark.parametrize("lanes", [16, 64])
def test_rehearsal_is_correct_with_the_five_counts_zero(lanes, capsys):
    config, _ = files()
    ov = overrides_for(config, lanes)
    ov["traffic"]["key_space"] = 40     # reads meet keys already written
    res = harness.run_cell(CELL, 2_600_000_011 + lanes, 4.0, True,
                           time.time(), on_chip=False, overrides=ov)
    assert res["correct"] and res["failed"] == 0, res
    counts = re.findall(r"\[compare\] number=(\w+)=(\d+) limit=0",
                        capsys.readouterr().out)
    assert len(counts) == 5 and all(n == "0" for _, n in counts), counts
    assert set(NEW_READERS) <= set(res["metrics"])
    assert 0.0 <= res["metrics"]["lease_read_share"]["value"] <= 1.0
    assert res["metrics"]["leaderless_pct"]["value"] == 0.0
    assert res["metrics"]["transfers_per_step"]["value"] >= 4.0
