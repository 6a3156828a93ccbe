"""The two readers PR 34 adds, on a small synthetic trace: the statistics
``hb_round_s`` (``raft.tail`` of the step that closes a heartbeat round) and
``bytes`` (``raft.dispatch_upload``, ``raft.scan_fetch``); and a program whose
spans carry neither (the parent)."""

import pytest

from benchmark import readings as rd, spanstats

# Two nodes.  Node 0 closes two rounds, of 0.2 s and 0.4 s (steps 5 and 9);
# node 1 closes one of 0.25 s (step 7) and leads: the longest mean is node
# 0's, 0.3 s.  Steps 5 and 6 of node 0 move 48 MB up and 50 MB down each;
# step 9 has only its upload inside the slice and is left out.
STATS = {1: "node", 2: "tick", 3: "hb_round_s", 4: "bytes", 5: "transfers"}
SPANS = {1: "raft.tail", 2: "raft.dispatch_upload", 3: "raft.scan_fetch"}
EVENTS = [  # (span, node, tick, {stat: value})
    (2, 0, 5, {4: 48_000_000, 5: 12}), (3, 0, 5, {4: 50_000_000, 5: 14}),
    (1, 0, 5, {3: 0.2}),
    (2, 0, 6, {4: 48_000_000, 5: 12}), (3, 0, 6, {4: 50_000_000, 5: 14}),
    (1, 0, 6, {}),
    (1, 1, 7, {3: 0.25}),
    (2, 0, 9, {4: 48_000_000, 5: 12}), (1, 0, 9, {3: 0.4}),
]


def _stat(k, v):
    kind = "double_value" if isinstance(v, float) else "int64_value"
    return f"stats {{ metadata_id: {k} {kind}: {v} }} "


def trace(stat_names=STATS):
    events = "".join(
        f"events {{ metadata_id: {span} offset_ps: {i}000000 "
        f"duration_ps: 1000000 " + _stat(1, node) + _stat(2, tick)
        + "".join(_stat(k, v) for k, v in stats.items()) + "} "
        for i, (span, node, tick, stats) in enumerate(EVENTS))
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
    ("hb_round_ms", 300.0),                 # node 0: (0.2 + 0.4) / 2
    ("transfer_mb_per_step", 98.0),         # steps 5 and 6: 48 + 50 MB
])
def test_reader_reads_the_slice(monkeypatch, tmp_path, metric, value):
    r = readings(monkeypatch, tmp_path, trace())
    assert rd.read_metric(metric, r) == pytest.approx(value)


@pytest.mark.parametrize("metric, stat", [
    ("hb_round_ms", "hb_round_s"), ("transfer_mb_per_step", "bytes"),
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
        "hb_round_ms", "transfer_mb_per_step")] == [None] * 2
