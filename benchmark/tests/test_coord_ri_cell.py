"""The cell PR 45 adds, ``coord-1g-3v-ri.mixed-steady``: the coordination
service as etcd ships it (raft ``ReadOnlyOption`` = ``ReadOnlySafe``).  It
resolves by name to the new configuration and to the traffic file of its twin
``coord-1g-3v.mixed-steady``; the configuration is the twin's with
``raft_config.read_lease`` false (and what says so: ``guarantees.reads``,
``assumed``, the ``source`` and the name); it runs through the rehearsal's
path at 16 lanes on the CPU (``correct``, the five counts 0, no read released
by a lease: counts and ``correct`` only, no time leaves a CPU run); the
``stale_reads`` control is caught on it; and the two new readers read a
recorded slice."""

import re
import time

import pytest

from benchmark import harness, readings as rd, spanstats
from benchmark.cluster import load_config
from benchmark.rehearse import overrides_for
from benchmark.traffic import load_traffic

CELL = "coord-1g-3v-ri.mixed-steady"
TWIN = "coord-1g-3v.mixed-steady"
BENCH = harness.load_benchmark()
NEW_READERS = ("read_round_ms", "read_arrival_stamp_share")


def test_the_cell_is_the_coordination_service_without_the_lease():
    cell, config_path, traffic_path = harness.find_cell(BENCH, CELL)
    _, twin_config, twin_traffic = harness.find_cell(BENCH, TWIN)
    assert cell["chips"] == 1 and traffic_path == twin_traffic
    assert load_traffic(traffic_path)["rate_ops_s"] == 32
    config, twin = load_config(config_path), load_config(twin_config)
    assert config["raft_config"] == dict(twin["raft_config"],
                                         read_lease=False)
    differ = {k for k in set(config) | set(twin)
              if config.get(k) != twin.get(k)}
    assert differ == {"name", "source", "raft_config", "guarantees",
                      "assumed"}
    assert {k for k in config["guarantees"]
            if config["guarantees"][k] != twin["guarantees"][k]} == {"reads"}
    reads = config["guarantees"]["reads"]
    assert "ReadIndex alone" in reads and "no assumption on clocks" in reads
    assert set(config["assumed"]) - set(twin["assumed"]) == \
        {"read_only_option", "not_modelled"}
    assert all(config["assumed"][k] == v for k, v in twin["assumed"].items())
    assert "from memory" in config["assumed"]["read_only_option"]
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    twin_entry = next(c for c in BENCH["configs"]
                      if c["name"] == "coord-1g-3v")
    assert entry["reduced"] == twin_entry["reduced"] == \
        ["tick_ms", "chips_per_node", "lifecycle", "load_phase"]
    assert config["reduced"] == twin["reduced"]          # in its words
    for text in (config["source"], entry["source"]):
        assert "ReadOnlySafe" in text and "op-guide" in text.replace(
            "operations guide", "op-guide")
    assert len(entry["source"]) <= 200 and len(cell["why"]) <= 200
    # what the engine makes of it
    from rafting_tpu.api.config import RaftConfig
    ec = RaftConfig(local="raft://h:1", peers=("raft://h:2", "raft://h:3"),
                    **config["raft_config"]).engine_config()
    assert (ec.read_lease, ec.heartbeat_ticks, ec.election_ticks,
            ec.lease_carry_ticks) == (False, 1, 10, 0)


def test_the_entries_are_appended_and_every_list_names_the_cell():
    assert BENCH["configs"][-1]["name"] == "coord-1g-3v-ri"
    assert BENCH["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in BENCH["per_layer"][-2:]] == list(NEW_READERS)
    cells = [w["name"] for w in BENCH["workloads"]]
    for m in BENCH["per_layer"][-2:]:
        assert m["workloads"] == cells
        assert (m["layer"], m["moves"], m["source"]) == \
            ("apply and reads", "read_p50_ms", "program_span")
    assert all(m["workloads"][-1] == CELL
               for m in BENCH["per_layer"] if "workloads" in m)
    names = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", CELL)}
    assert set(NEW_READERS) <= names and "log_ring_fill_pct" in names
    assert BENCH.get("claim") is None


def test_rehearsal_is_correct_and_no_read_rides_a_lease(capsys):
    config = load_config(harness.find_cell(BENCH, CELL)[1])
    ov = overrides_for(config, 16)
    ov["traffic"]["key_space"] = 40     # reads meet keys already written
    res = harness.run_cell(CELL, 2_900_000_045, 4.0, True, time.time(),
                           on_chip=False, overrides=ov)
    assert res["correct"] and res["failed"] == 0, res
    out = capsys.readouterr().out
    counts = re.findall(r"\[compare\] number=(\w+)=(\d+) limit=0", out)
    assert len(counts) == 5 and all(n == "0" for _, n in counts), counts
    m = res["metrics"]
    assert set(NEW_READERS) <= set(m)
    assert m["lease_read_share"]["value"] == 0.0
    assert m["read_round_ms"]["value"] > 0.0
    assert 0.0 <= m["read_arrival_stamp_share"]["value"] <= 1.0
    assert m["read_kicks_per_query"]["value"] > 0.0
    assert re.search(r"read_lease_hits=0\b", out)


def test_the_stale_reads_control_is_caught_on_the_cell():
    config = load_config(harness.find_cell(BENCH, CELL)[1])
    ov = overrides_for(config, 16)
    ov["traffic"]["key_space"] = 40
    res = harness.run_cell(CELL, 2_900_000_046, 4.0, False, time.time(),
                           on_chip=False, overrides=ov, fault="stale_reads")
    assert res["attempted"] > 0 and res["correct"] is False


# One node, four steps of the traced slice.  Step 5 (an arrival step)
# stamped 2 batches; step 6 released both after 12 and 18 ms; step 7 (the
# timer's) stamped 1; step 8 released it after 10 ms.
STATS = {1: "node", 2: "tick", 3: "stamps", 4: "arrival_stamps",
         5: "rounds", 6: "round_ms"}
EVENTS = [(5, {3: 2, 4: 2, 5: 0, 6: 0}), (6, {3: 0, 4: 0, 5: 2, 6: 30}),
          (7, {3: 1, 4: 0, 5: 0, 6: 0}), (8, {3: 0, 4: 0, 5: 1, 6: 10})]


def trace(stat_names=STATS, events=EVENTS):
    text = "".join(
        f"events {{ metadata_id: 1 offset_ps: {i}000000 "
        f"duration_ps: 1000000 stats {{ metadata_id: 1 int64_value: 0 }} "
        f"stats {{ metadata_id: 2 int64_value: {tick} }} "
        + "".join(f"stats {{ metadata_id: {k} int64_value: {v} }} "
                  for k, v in stats.items()) + "} "
        for i, (tick, stats) in enumerate(events))
    return ('planes { id: 2 name: "/host:CPU" lines { id: 7 name: "python" '
            f'timestamp_ns: 1000 {text} }} '
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
    ("read_round_ms", 40 / 3),                  # 3 rounds, 40 ms in all
    ("read_arrival_stamp_share", 2 / 3)])       # 2 of 3 stamps
def test_the_new_readers_read_a_recorded_slice(monkeypatch, tmp_path,
                                               metric, value):
    r = readings(monkeypatch, tmp_path, trace())
    assert rd.read_metric(metric, r) == pytest.approx(value)


@pytest.mark.parametrize("metric, stat", [
    ("read_round_ms", "rounds"), ("read_arrival_stamp_share", "stamps")])
def test_a_parent_without_the_statistic_reads_as_nothing(
        monkeypatch, tmp_path, metric, stat):
    names = {k: ("other_" + n if n == stat else n) for k, n in STATS.items()}
    r = readings(monkeypatch, tmp_path, trace(names))
    assert rd.read_metric(metric, r) is None


def test_a_slice_with_no_round_yields_no_round_time(monkeypatch, tmp_path):
    r = readings(monkeypatch, tmp_path, trace(events=EVENTS[:1]))
    assert rd.read_metric("read_round_ms", r) is None
    assert rd.read_metric("read_arrival_stamp_share", r) == 1.0
