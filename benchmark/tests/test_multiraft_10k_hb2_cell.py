"""The cell PR 39 adds, ``multiraft-10k-3v-hb2.ycsb-a-steady``: it resolves by
name to the new configuration and to the traffic file it shares with
``multiraft-10k-3v.ycsb-a-steady``, the configuration differs from that cell's
in ``raft_config.heartbeat_mul`` alone and no longer lists it as reduced, it
runs through the rehearsal's path at 16 and 64 lanes on the CPU (``correct``,
the five counts 0, reads riding a carried lease: counts and ``correct`` only,
no time leaves a CPU run), and the two new readers read a recorded slice."""

import re
import time

import pytest

from benchmark import harness, readings as rd, spanstats
from benchmark.cluster import load_config
from benchmark.rehearse import overrides_for
from benchmark.traffic import load_traffic

CELL = "multiraft-10k-3v-hb2.ycsb-a-steady"
TWIN = "multiraft-10k-3v.ycsb-a-steady"
BENCH = harness.load_benchmark()
NEW_READERS = ("lease_carried_share", "read_kicks_per_query")


def test_the_cell_is_the_10k_store_on_the_sources_heartbeat():
    cell, config_path, traffic_path = harness.find_cell(BENCH, CELL)
    _, twin_config, twin_traffic = harness.find_cell(BENCH, TWIN)
    assert cell["chips"] == 1 and traffic_path == twin_traffic
    assert load_traffic(traffic_path)["rate_ops_s"] == 40
    config, twin = load_config(config_path), load_config(twin_config)
    rc = config["raft_config"]
    assert rc == dict(twin["raft_config"], heartbeat_mul=2.0)
    assert rc["tick_ms"] == 1000 and rc["election_mul"] == 10.0
    for key in ("open_groups", "voters", "latency_limit_ms", "lifecycle",
                "machine", "wal", "transport", "trace_slice_s"):
        assert config[key] == twin[key], key
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == \
        ["chips_per_node", "lifecycle", "load_phase"]
    assert {k: config["reduced"][k] for k in config["reduced"]} == \
        {k: twin["reduced"][k] for k in config["reduced"]}   # in its words
    for text in (config["source"], entry["source"]):
        assert "workloads/workloada" in text
        assert "raft-heartbeat-ticks 2" in text
    assert "lease" in config["guarantees"]["reads"]
    assert "election 10" in config["guarantees"]["reads"]
    assert {"max_leader_lease", "lease_length"} <= set(config["assumed"])
    assert "whole" in config["status"]
    # what the engine makes of it: the lease is carried one tick
    from rafting_tpu.api.config import RaftConfig
    keys = {f for f in RaftConfig.__dataclass_fields__}
    ec = RaftConfig(local="raft://h:1", peers=("raft://h:2", "raft://h:3"),
                    **{k: v for k, v in rc.items() if k in keys}
                    ).engine_config()
    assert (ec.heartbeat_ticks, ec.election_ticks,
            ec.lease_carry_ticks) == (2, 10, 1)


def test_the_cell_reports_the_new_readers_and_every_listed_one():
    names = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", CELL)}
    assert set(NEW_READERS) <= names
    assert "log_ring_fill_pct" not in names
    listed = [m for m in BENCH["per_layer"] if "workloads" in m
              and m["name"] != "log_ring_fill_pct"]
    assert all(CELL in m["workloads"] for m in listed)
    for m in BENCH["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [w["name"] for w in BENCH["workloads"]]
            assert (m["layer"], m["moves"], m["source"]) == \
                ("apply and reads", "read_p50_ms", "program_span")


@pytest.mark.parametrize("lanes", [16, 64])
def test_rehearsal_is_correct_and_reads_ride_a_carried_lease(lanes, capsys):
    config = load_config(harness.find_cell(BENCH, CELL)[1])
    ov = overrides_for(config, lanes)
    ov["traffic"]["key_space"] = 40     # reads meet keys already written
    res = harness.run_cell(CELL, 2_900_000_011 + lanes, 4.0, True,
                           time.time(), on_chip=False, overrides=ov)
    assert res["correct"] and res["failed"] == 0, res
    counts = re.findall(r"\[compare\] number=(\w+)=(\d+) limit=0",
                        capsys.readouterr().out)
    assert len(counts) == 5 and all(n == "0" for _, n in counts), counts
    m = res["metrics"]
    assert set(NEW_READERS) <= set(m)
    assert 0.0 < m["lease_carried_share"]["value"] \
        <= m["lease_read_share"]["value"] <= 1.0
    assert 0.0 <= m["read_kicks_per_query"]["value"] <= 1.0


# One node, three steps.  Step 5 served 4 queries, 3 by the lease of which 1
# on carried evidence; step 6 stamped a batch it could not release (a kick)
# and served nothing; step 7 served that query and stamped none.
STATS = {1: "node", 2: "tick", 3: "queries", 4: "barriers", 5: "lease_hits",
         6: "lease_carried", 7: "kicks"}
EVENTS = [(5, {3: 4, 4: 3, 5: 3, 6: 1, 7: 0}), (6, {7: 1}),
          (7, {3: 1, 4: 1, 5: 0, 6: 0})]


def trace(stat_names=STATS):
    events = "".join(
        f"events {{ metadata_id: 1 offset_ps: {i}000000 "
        f"duration_ps: 1000000 stats {{ metadata_id: 1 int64_value: 0 }} "
        f"stats {{ metadata_id: 2 int64_value: {tick} }} "
        + "".join(f"stats {{ metadata_id: {k} int64_value: {v} }} "
                  for k, v in stats.items()) + "} "
        for i, (tick, stats) in enumerate(EVENTS))
    return ('planes { id: 2 name: "/host:CPU" lines { id: 7 name: "python" '
            f'timestamp_ns: 1000 {events} }} '
            'event_metadata { key: 1 value { id: 1 name: "raft.reads" } } '
            + "".join(f'stat_metadata {{ key: {k} value {{ id: {k} '
                      f'name: "{n}" }} }} ' for k, n in stat_names.items())
            + "}")


def readings(monkeypatch, tmp_path, text):
    from jax.profiler import ProfileData
    s = spanstats.reduce_planes(ProfileData.from_text_proto(text).planes)
    monkeypatch.setattr(spanstats, "reduce_file", lambda path: s)
    r = rd.Readings(window_s=10.0, histograms=[], ticks=[3, 3, 3],
                    fsync_calls=0, acked_writes=0, commit_latencies_s=[],
                    read_latencies_s=[], gen_late_s=[])
    r.xplane = str(tmp_path / "x.xplane.pb")
    return r


@pytest.mark.parametrize("metric, value", [
    ("lease_carried_share", 1 / 5),         # 1 of 5 queries
    ("read_kicks_per_query", 1 / 5),        # 1 kick, 5 queries
    ("lease_read_share", 3 / 5),
])
def test_the_new_readers_read_a_recorded_slice(monkeypatch, tmp_path,
                                               metric, value):
    r = readings(monkeypatch, tmp_path, trace())
    assert rd.read_metric(metric, r) == pytest.approx(value)


@pytest.mark.parametrize("metric, stat", [
    ("lease_carried_share", "lease_carried"),
    ("read_kicks_per_query", "kicks")])
def test_a_parent_without_the_statistic_reads_as_nothing(
        monkeypatch, tmp_path, metric, stat):
    names = {k: ("other_" + n if n == stat else n) for k, n in STATS.items()}
    r = readings(monkeypatch, tmp_path, trace(names))
    assert rd.read_metric(metric, r) is None
