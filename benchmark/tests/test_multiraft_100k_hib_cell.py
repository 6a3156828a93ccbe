"""The cell PR 41 adds, ``multiraft-100k-3v-hib.ycsb-a-steady``: it resolves by
name to the new configuration and to the traffic file it shares with
``multiraft-100k-3v.ycsb-a-steady``, the configuration differs from that
cell's in ``raft_config.heartbeat_mul`` and ``raft_config.hibernate_regions``
alone and lists the twin's three cuts of scale in the twin's words, the engine
makes TiKV's timing and hibernation of it, it runs through the rehearsal's
path at 16 and 64 lanes on the CPU (``correct``, the five counts 0; at 64
lanes under a rate that leaves lanes idle for an election timeout, lanes
asleep when the window opens and operations that woke one: counts and
``correct`` only, no time leaves a CPU run), and the two new readers read a
recorded slice."""

import re
import time

import pytest

from benchmark import harness, readings as rd, spanstats
from benchmark.cluster import load_config
from benchmark.rehearse import overrides_for
from benchmark.traffic import load_traffic

CELL = "multiraft-100k-3v-hib.ycsb-a-steady"
TWIN = "multiraft-100k-3v.ycsb-a-steady"
BENCH = harness.load_benchmark()
NEW_READERS = ("asleep_lane_pct", "wake_op_share")


def test_the_cell_is_the_100k_store_as_tikv_ships_it():
    cell, config_path, traffic_path = harness.find_cell(BENCH, CELL)
    _, twin_config, twin_traffic = harness.find_cell(BENCH, TWIN)
    assert cell["chips"] == 1 and traffic_path == twin_traffic
    assert traffic_path.endswith("ycsb-a-100k-steady.json")
    assert load_traffic(traffic_path)["rate_ops_s"] == 32
    config, twin = load_config(config_path), load_config(twin_config)
    rc = config["raft_config"]
    assert rc == dict(twin["raft_config"], heartbeat_mul=2.0,
                      hibernate_regions=True)
    assert rc["tick_ms"] == 1000 and rc["election_mul"] == 10.0
    assert config["open_groups"] == 99999 and rc["n_groups"] == 100000
    for key in ("open_groups", "voters", "latency_limit_ms", "lifecycle",
                "machine", "wal", "transport", "trace_slice_s",
                "injected_delay_ms"):
        assert config[key] == twin[key], key
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == \
        ["chips_per_node", "lifecycle", "load_phase"]
    assert dict(config["reduced"]) == \
        {k: twin["reduced"][k] for k in config["reduced"]}   # in its words
    for text in (config["source"], entry["source"]):
        assert "workloads/workloada" in text
        for setting in ("raft-base-tick-interval 1", "raft-heartbeat-ticks 2",
                        "raft-election-timeout-ticks 10", "prevote",
                        "hibernate-regions true"):
            assert setting in text, setting
    g = config["guarantees"]
    assert g["write_acknowledged_after"] == \
        twin["guarantees"]["write_acknowledged_after"]
    assert g["after_drain"] == twin["guarantees"]["after_drain"]
    assert "lease" in g["reads"] and "election 10" in g["reads"]
    assert "ReadIndex barrier" in g["reads"]
    assert "election_ticks + 2 x election_ticks" in g["after_node_loss"]
    assert {"hibernate_default", "idle_threshold", "all_members_rule",
            "node_beat", "not_modelled"} <= set(config["assumed"])
    assert "v5.0.2" in config["assumed"]["hibernate_default"]
    assert "whole" in config["status"]
    # what the engine makes of it
    from rafting_tpu.api.config import RaftConfig
    keys = {f for f in RaftConfig.__dataclass_fields__}
    assert set(rc) <= keys
    ec = RaftConfig(local="raft://h:1", peers=("raft://h:2", "raft://h:3"),
                    **rc).engine_config()
    assert (ec.heartbeat_ticks, ec.election_ticks, ec.lease_carry_ticks,
            ec.hibernate) == (2, 10, 1, True)
    twin_ec = RaftConfig(local="raft://h:1",
                         peers=("raft://h:2", "raft://h:3"),
                         **twin["raft_config"]).engine_config()
    assert not twin_ec.hibernate and twin_ec.heartbeat_ticks == 1


def test_the_twins_file_is_as_it_was():
    """The 100k twin keeps its heartbeat cut and its words: this PR adds a
    file beside it and edits none."""
    twin = load_config(harness.find_cell(BENCH, TWIN)[1])
    assert twin["raft_config"]["heartbeat_mul"] == 1.0
    assert "hibernate_regions" not in twin["raft_config"]
    assert sorted(twin["reduced"]) == ["chips_per_node", "heartbeat_mul",
                                       "lifecycle", "load_phase"]
    assert "Hibernate Region" in twin["assumed"]["massive_regions_page"]


def test_the_cell_reports_the_new_readers_and_every_listed_one():
    names = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", CELL)}
    assert set(NEW_READERS) <= names
    assert "log_ring_fill_pct" not in names
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells[-1] == CELL and len(cells) == 6
    listed = [m for m in BENCH["per_layer"] if "workloads" in m
              and m["name"] != "log_ring_fill_pct"]
    assert all(m["workloads"] == cells for m in listed)
    new = {m["name"]: m for m in BENCH["per_layer"][-2:]}
    assert tuple(new) == NEW_READERS
    assert (new["asleep_lane_pct"]["layer"], new["asleep_lane_pct"]["better"],
            new["asleep_lane_pct"]["unit"]) == ("device step", "higher", "%")
    assert (new["wake_op_share"]["layer"], new["wake_op_share"]["better"]) \
        == ("apply and reads", "lower")
    for m in new.values():
        assert (m["moves"], m["source"]) == ("read_p50_ms", "program_span")


@pytest.mark.parametrize("lanes", [16, 64])
def test_rehearsal_is_correct_and_lanes_sleep_and_wake(lanes, capsys):
    config = load_config(harness.find_cell(BENCH, CELL)[1])
    ov = overrides_for(config, lanes)
    ov["traffic"]["key_space"] = 40     # reads meet keys already written
    if lanes == 64:
        # A rate and a spread that leave a lane idle for longer than an
        # election timeout (1 s at the rehearsal's 100 ms tick) between
        # two operations, and a slice long enough to hold some.
        ov["traffic"].update(rate_ops_s=12, key_dist={"kind": "uniform"})
        ov["trace_slice_s"] = 2
    res = harness.run_cell(CELL, 2_900_000_041 + lanes, 4.0, True,
                           time.time(), on_chip=False, overrides=ov)
    assert res["correct"] and res["failed"] == 0, res
    counts = re.findall(r"\[compare\] number=(\w+)=(\d+) limit=0",
                        capsys.readouterr().out)
    assert len(counts) == 5 and all(n == "0" for _, n in counts), counts
    m = res["metrics"]
    assert set(NEW_READERS) <= set(m)
    assert 0.0 <= m["asleep_lane_pct"]["value"] <= 100.0
    assert 0.0 <= m["wake_op_share"]["value"] <= 1.0
    assert m["wake_op_share"]["value"] + m["lease_read_share"]["value"] \
        <= 1.0 + 1e-9
    if lanes == 64:
        assert m["asleep_lane_pct"]["value"] > 25.0
        assert m["wake_op_share"]["value"] > 0.0


def test_the_twin_reads_zero_through_the_new_readers(capsys):
    """Off, the spans carry ``asleep`` 0 and ``woke`` 0: the readers find
    something to read in every cell that lists them, and read nothing
    asleep."""
    config = load_config(harness.find_cell(BENCH, TWIN)[1])
    ov = overrides_for(config, 16)
    ov["traffic"]["key_space"] = 40
    res = harness.run_cell(TWIN, 2_900_000_099, 4.0, True, time.time(),
                           on_chip=False, overrides=ov)
    assert res["correct"] and res["failed"] == 0, res
    m = res["metrics"]
    assert m["asleep_lane_pct"]["value"] == 0.0
    assert m["wake_op_share"]["value"] == 0.0


# Two nodes.  Node 0 (the busiest: its ticks cost most) took two timer steps
# with 90 and 80 of its 100 open lanes asleep and an arrival step that
# carries no ``asleep``; node 1 one timer step with 10 of 100.  On
# ``raft.reads``: step 5 served 4 queries under 3 barriers, 1 stamped in a
# step that woke its lane and 2 by the lease; step 7 served 1 that woke one.
MIRRORS = {1: "node", 2: "tick", 3: "open", 4: "asleep", 5: "woken"}
READS = {1: "node", 2: "tick", 6: "queries", 7: "barriers", 8: "lease_hits",
         9: "woke"}
EVENTS = [("raft.mirrors", 0, 5, {3: 100, 4: 90, 5: 0}),
          ("raft.mirrors", 0, 6, {3: 100, 5: 2}),
          ("raft.mirrors", 0, 7, {3: 100, 4: 80, 5: 0}),
          ("raft.mirrors", 1, 5, {3: 100, 4: 10, 5: 0}),
          ("raft.reads", 0, 5, {6: 4, 7: 3, 8: 2, 9: 1}),
          ("raft.reads", 1, 7, {6: 1, 7: 1, 8: 0, 9: 1})]


def trace(rename=None):
    names = {**MIRRORS, **READS}
    if rename:
        names = {k: ("other_" + n if n == rename else n)
                 for k, n in names.items()}
    kinds = {"raft.mirrors": 1, "raft.reads": 2}
    events = "".join(
        f"events {{ metadata_id: {kinds[name]} offset_ps: {i}000000 "
        f"duration_ps: 1000000 "
        f"stats {{ metadata_id: 1 int64_value: {node} }} "
        f"stats {{ metadata_id: 2 int64_value: {tick} }} "
        + "".join(f"stats {{ metadata_id: {k} int64_value: {v} }} "
                  for k, v in stats.items()) + "} "
        for i, (name, node, tick, stats) in enumerate(EVENTS))
    return ('planes { id: 2 name: "/host:CPU" lines { id: 7 name: "python" '
            f'timestamp_ns: 1000 {events} }} '
            + "".join(f'event_metadata {{ key: {k} value {{ id: {k} '
                      f'name: "{n}" }} }} ' for n, k in kinds.items())
            + "".join(f'stat_metadata {{ key: {k} value {{ id: {k} '
                      f'name: "{n}" }} }} ' for k, n in names.items())
            + "}")


def readings(monkeypatch, tmp_path, text):
    from jax.profiler import ProfileData
    s = spanstats.reduce_planes(ProfileData.from_text_proto(text).planes)
    monkeypatch.setattr(spanstats, "reduce_file", lambda path: s)
    tick = lambda mean: {rd.TICK: (10, 10 * mean)}
    r = rd.Readings(window_s=10.0, histograms=[tick(0.03), tick(0.01)],
                    ticks=[3, 3], fsync_calls=0, acked_writes=0,
                    commit_latencies_s=[], read_latencies_s=[], gen_late_s=[])
    r.xplane = str(tmp_path / "x.xplane.pb")
    return r


@pytest.mark.parametrize("metric, value", [
    ("asleep_lane_pct", 85.0),          # node 0's timer steps: 90 and 80
    ("wake_op_share", 2 / 5),           # 2 of 5 queries
    ("lease_read_share", 2 / 5),
])
def test_the_new_readers_read_a_recorded_slice(monkeypatch, tmp_path,
                                               metric, value):
    r = readings(monkeypatch, tmp_path, trace())
    assert rd.read_metric(metric, r) == pytest.approx(value)


@pytest.mark.parametrize("metric, stat", [
    ("asleep_lane_pct", "asleep"), ("wake_op_share", "woke")])
def test_a_parent_without_the_statistic_reads_as_nothing(
        monkeypatch, tmp_path, metric, stat):
    r = readings(monkeypatch, tmp_path, trace(rename=stat))
    assert rd.read_metric(metric, r) is None
