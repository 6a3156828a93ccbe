"""The three readers PR 37 adds, on a small synthetic trace: the statistics
``win_occupied`` / ``win_slots`` and ``unready`` / ``led`` (``raft.mirrors``)
and ``merged`` (``raft.dispatch_intake``); a program whose spans carry none
of them (the parent); and a slice in which they are there and nothing
happened."""

import pytest

from benchmark import readings as rd, spanstats

METRICS = ("window_leaked_pct", "unready_lane_pct", "replies_merged_per_step")

# Three nodes.  Node 0 leads 10 lanes (80 slots): its windows hold 30, 20 and
# 26 slots over steps 5-7, so 20 of 80 stand for good (a heartbeat round adds
# to them and is answered); 2, 0 and 1 of its lanes are unready.  Node 1
# leads 5 lanes (40 slots) and holds 24 and 30: 24 of 40, the worst node;
# none unready.  Node 2 leads nothing: it has no window to leak and no share
# of unready lanes.  Node 0's step 6 collapsed four slices and merged 7
# replies; every other step merged none.
STATS = {1: "node", 2: "tick", 3: "win_occupied", 4: "win_slots",
         5: "unready", 6: "led", 7: "merged", 8: "collapsed", 9: "arrival"}
SPANS = {1: "raft.mirrors", 2: "raft.dispatch_intake"}
EVENTS = [  # (span, node, tick, {stat: value})
    (1, 0, 5, {3: 30, 4: 80, 5: 2, 6: 10}), (2, 0, 5, {7: 0, 8: 0, 9: 0}),
    (1, 0, 6, {3: 20, 4: 80, 5: 0, 6: 10}), (2, 0, 6, {7: 7, 8: 4, 9: 1}),
    (1, 0, 7, {3: 26, 4: 80, 5: 1, 6: 10}), (2, 0, 7, {7: 0, 8: 0, 9: 1}),
    (1, 1, 3, {3: 24, 4: 40, 5: 0, 6: 5}), (2, 1, 3, {7: 0, 8: 0, 9: 0}),
    (1, 1, 4, {3: 30, 4: 40, 5: 0, 6: 5}), (2, 1, 4, {7: 0, 8: 0, 9: 1}),
    (1, 2, 9, {3: 0, 4: 0, 5: 0, 6: 0}), (2, 2, 9, {7: 0, 8: 0, 9: 0}),
]
QUIET = [(span, node, tick, {k: (v if k in (4, 6) else 0)
                             for k, v in stats.items()})
         for span, node, tick, stats in EVENTS]


def trace(events=EVENTS, stat_names=STATS):
    text = "".join(
        f"events {{ metadata_id: {span} offset_ps: {i}000000 "
        f"duration_ps: 1000000 stats {{ metadata_id: 1 int64_value: {node} }} "
        f"stats {{ metadata_id: 2 int64_value: {tick} }} "
        + "".join(f"stats {{ metadata_id: {k} int64_value: {v} }} "
                  for k, v in stats.items()) + "} "
        for i, (span, node, tick, stats) in enumerate(events))
    return ('planes { id: 2 name: "/host:CPU" lines { id: 7 name: "python" '
            f'timestamp_ns: 1000 {text} }} '
            + "".join(f'event_metadata {{ key: {k} value {{ id: {k} '
                      f'name: "{n}" }} }} ' for k, n in SPANS.items())
            + "".join(f'stat_metadata {{ key: {k} value {{ id: {k} '
                      f'name: "{n}" }} }} ' for k, n in stat_names.items())
            + "}")


def bare():
    return rd.Readings(window_s=10.0, histograms=[], ticks=[3, 2, 1],
                       fsync_calls=0, acked_writes=0, commit_latencies_s=[],
                       read_latencies_s=[], gen_late_s=[])


def readings(monkeypatch, tmp_path, text):
    from jax.profiler import ProfileData
    s = spanstats.reduce_planes(ProfileData.from_text_proto(text).planes)
    monkeypatch.setattr(spanstats, "reduce_file", lambda path: s)
    r = bare()
    r.xplane = str(tmp_path / "x.xplane.pb")
    return r


@pytest.mark.parametrize("metric, value", [
    # A node's minimum over its steps, the worst node: 24 of 40, not node
    # 0's 20 of 80 and not its mean; the node that leads nothing reads 0.
    ("window_leaked_pct", 100.0 * 24 / 40),
    # Five steps of the two nodes that lead: 20%, 0, 10%, 0, 0.
    ("unready_lane_pct", (20.0 + 0.0 + 10.0 + 0.0 + 0.0) / 5),
    ("replies_merged_per_step", 7 / 6),     # six steps, all nodes
])
def test_reader_reads_the_slice(monkeypatch, tmp_path, metric, value):
    r = readings(monkeypatch, tmp_path, trace())
    assert rd.read_metric(metric, r) == pytest.approx(value)


@pytest.mark.parametrize("metric", METRICS)
def test_present_and_zero_reads_as_zero(monkeypatch, tmp_path, metric):
    r = readings(monkeypatch, tmp_path, trace(QUIET))
    assert rd.read_metric(metric, r) == 0.0


@pytest.mark.parametrize("metric, stat", [
    ("window_leaked_pct", "win_slots"), ("unready_lane_pct", "unready"),
    ("replies_merged_per_step", "merged"),
])
def test_a_parent_without_the_statistic_reads_as_nothing(
        monkeypatch, tmp_path, metric, stat):
    names = {k: ("other_" + n if n == stat else n) for k, n in STATS.items()}
    r = readings(monkeypatch, tmp_path, trace(stat_names=names))
    assert rd.read_metric(metric, r) is None


def test_no_slice_at_all_reads_as_nothing(monkeypatch):
    monkeypatch.setattr(spanstats, "find_run_xplane", lambda: None)
    r = bare()
    assert [rd.read_metric(m, r) for m in METRICS] == [None] * 3
