"""The reader PR 40 adds, on a small synthetic trace: ``scanned`` on the four
host-phase spans (``raft.wal``, ``raft.apply``, ``raft.reads``,
``raft.maintain``), summed per step over the steps that have all four inside
the slice; a step cut by the slice's edge; and a program whose spans carry no
such statistic (the parent)."""

import pytest

from benchmark import readings as rd, spanstats

STATS = {1: "node", 2: "tick", 3: "scanned", 4: "lanes"}
SPANS = {1: "raft.wal", 2: "raft.apply", 3: "raft.reads", 4: "raft.maintain",
         5: "raft.send"}
# (span, node, tick, {stat: value}).  Node 0, step 4: the slice opens inside
# it and holds its last two spans alone.  Steps 5 and 6 are whole: a step
# that looked at every one of 1,000 lanes in 15 passes and a step worked from
# 3 rows.  Node 1, step 9: whole, 40 lanes.  Node 1, step 10: the slice ends
# before its maintain stage.  ``raft.send`` carries no ``scanned``.
EVENTS = [
    (3, 0, 4, {3: 7000, 4: 0}), (4, 0, 4, {3: 2000, 4: 0}),
    (1, 0, 5, {3: 7000, 4: 2}), (5, 0, 5, {4: 2}), (2, 0, 5, {3: 2000, 4: 1}),
    (3, 0, 5, {3: 4000, 4: 0}), (4, 0, 5, {3: 2000, 4: 0}),
    (1, 0, 6, {3: 21, 4: 0}), (2, 0, 6, {3: 3, 4: 0}),
    (3, 0, 6, {3: 15, 4: 1}), (4, 0, 6, {3: 9, 4: 0}),
    (1, 1, 9, {3: 10, 4: 0}), (2, 1, 9, {3: 10, 4: 0}),
    (3, 1, 9, {3: 10, 4: 0}), (4, 1, 9, {3: 10, 4: 0}),
    (1, 1, 10, {3: 5000, 4: 0}), (2, 1, 10, {3: 5000, 4: 0}),
    (3, 1, 10, {3: 5000, 4: 0}),
]


def trace(events=EVENTS, stat_names=STATS):
    body = "".join(
        f"events {{ metadata_id: {span} offset_ps: {i}000000 "
        f"duration_ps: 1000000 stats {{ metadata_id: 1 int64_value: {node} }} "
        f"stats {{ metadata_id: 2 int64_value: {tick} }} "
        + "".join(f"stats {{ metadata_id: {k} int64_value: {v} }} "
                  for k, v in stats.items()) + "} "
        for i, (span, node, tick, stats) in enumerate(events))
    return ('planes { id: 2 name: "/host:CPU" lines { id: 7 name: "python" '
            f'timestamp_ns: 1000 {body} }} '
            + "".join(f'event_metadata {{ key: {k} value {{ id: {k} '
                      f'name: "{n}" }} }} ' for k, n in SPANS.items())
            + "".join(f'stat_metadata {{ key: {k} value {{ id: {k} '
                      f'name: "{n}" }} }} ' for k, n in stat_names.items())
            + "}")


def readings(monkeypatch, tmp_path, text):
    from jax.profiler import ProfileData
    s = spanstats.reduce_planes(ProfileData.from_text_proto(text).planes)
    monkeypatch.setattr(spanstats, "reduce_file", lambda path: s)
    r = rd.Readings(window_s=10.0, histograms=[], ticks=[2, 2, 2],
                    fsync_calls=0, acked_writes=0, commit_latencies_s=[],
                    read_latencies_s=[], gen_late_s=[])
    r.xplane = str(tmp_path / "x.xplane.pb")
    return r


def step_events(node, tick):
    return [e for e in EVENTS if (e[1], e[2]) == (node, tick)]


@pytest.mark.parametrize("events, value", [
    # the three whole steps: 15,000, 48 and 40 lanes
    (EVENTS, (15000 + 48 + 40) / 3),
    # a step with all four spans, alone
    (step_events(0, 5), 15000.0),
    # a step worked from its rows
    (step_events(0, 6), 48.0),
    # steps cut by the slice's edges alone: nothing to read
    (step_events(0, 4) + step_events(1, 10), None),
], ids=["slice", "whole-step", "row-step", "cut-steps"])
def test_reader_sums_the_four_spans_of_the_steps_whole_in_the_slice(
        monkeypatch, tmp_path, events, value):
    r = readings(monkeypatch, tmp_path, trace(events))
    got = rd.read_metric("host_scan_lanes_per_step", r)
    assert got == (None if value is None else pytest.approx(value))


def test_a_parent_without_the_statistic_reads_as_nothing(monkeypatch,
                                                         tmp_path):
    names = {k: ("other_" + n if n == "scanned" else n)
             for k, n in STATS.items()}
    r = readings(monkeypatch, tmp_path, trace(stat_names=names))
    assert rd.read_metric("host_scan_lanes_per_step", r) is None
    # ... while the companion, which reads ``lanes``, still reads.
    assert rd.read_metric("host_lanes_per_step", r) is not None
