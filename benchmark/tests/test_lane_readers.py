"""The four readers PR 31 adds, on a small synthetic trace: the statistics
``lease_hits`` (``raft.reads``), ``lanes`` (the host phase's spans),
``transfers`` (``raft.dispatch_upload``, ``raft.scan_fetch``) and
``leaderless`` / ``open`` (``raft.mirrors``); and a program whose spans carry
none of them (the parent)."""

import pytest

from benchmark import readings as rd, spanstats

# One node, two steps.  Step 5: 3 queries of which 2 by the lease, the host
# phase walked 1 + 2 + 1 lanes (wal, send, reads), 3 arrays up and 3 down,
# 10 of 100 open lanes leaderless.  Step 6: 1 query, none by the lease, the
# phase walked 0 lanes (a statistic of 0 on one span), 3 up and 3 down, no
# lane leaderless.
STATS = {1: "node", 2: "tick", 3: "queries", 4: "barriers", 5: "lease_hits",
         6: "lanes", 7: "transfers", 8: "leaderless", 9: "open"}
SPANS = {1: "raft.reads", 2: "raft.wal", 3: "raft.send",
         4: "raft.dispatch_upload", 5: "raft.scan_fetch", 6: "raft.mirrors"}
EVENTS = [  # (span, tick, {stat: value})
    (1, 5, {3: 3, 4: 2, 5: 2, 6: 1}), (2, 5, {6: 1}), (3, 5, {6: 2}),
    (4, 5, {7: 3}), (5, 5, {7: 3}), (6, 5, {8: 10, 9: 100}),
    (1, 6, {3: 1, 4: 1, 5: 0}), (2, 6, {6: 0}),
    (4, 6, {7: 3}), (5, 6, {7: 3}), (6, 6, {8: 0, 9: 100}),
]


def trace(stat_names=STATS):
    events = "".join(
        f"events {{ metadata_id: {span} offset_ps: {i}000000 "
        f"duration_ps: 1000000 stats {{ metadata_id: 1 int64_value: 0 }} "
        f"stats {{ metadata_id: 2 int64_value: {tick} }} "
        + "".join(f"stats {{ metadata_id: {k} int64_value: {v} }} "
                  for k, v in stats.items()) + "} "
        for i, (span, tick, stats) in enumerate(EVENTS))
    return ('planes { id: 2 name: "/host:CPU" lines { id: 7 name: "python" '
            f'timestamp_ns: 1000 {events} }} '
            + "".join(f'event_metadata {{ key: {k} value {{ id: {k} '
                      f'name: "{n}" }} }} ' for k, n in SPANS.items())
            + "".join(f'stat_metadata {{ key: {k} value {{ id: {k} '
                      f'name: "{n}" }} }} ' for k, n in stat_names.items())
            + "}")


def bare():
    return rd.Readings(window_s=10.0, histograms=[], ticks=[2, 2, 2],
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
    ("lease_read_share", 2 / 4),            # 2 of 4 queries
    ("host_lanes_per_step", (4 + 0) / 2),   # two steps: 4 lanes and none
    ("transfers_per_step", (6 + 6) / 2),
    ("leaderless_pct", (10.0 + 0.0) / 2),
])
def test_reader_reads_the_slice(monkeypatch, tmp_path, metric, value):
    r = readings(monkeypatch, tmp_path, trace())
    assert rd.read_metric(metric, r) == pytest.approx(value)


@pytest.mark.parametrize("metric, stat", [
    ("lease_read_share", "lease_hits"), ("host_lanes_per_step", "lanes"),
    ("transfers_per_step", "transfers"), ("leaderless_pct", "leaderless"),
])
def test_a_parent_without_the_statistic_reads_as_nothing(
        monkeypatch, tmp_path, metric, stat):
    names = {k: ("other_" + n if n == stat else n) for k, n in STATS.items()}
    r = readings(monkeypatch, tmp_path, trace(names))
    assert rd.read_metric(metric, r) is None


def test_no_slice_at_all_reads_as_nothing(monkeypatch):
    monkeypatch.setattr(spanstats, "find_run_xplane", lambda: None)
    r = bare()
    assert [rd.read_metric(m, r) for m in (
        "lease_read_share", "host_lanes_per_step", "transfers_per_step",
        "leaderless_pct")] == [None] * 4
