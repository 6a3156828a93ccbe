"""The reader PR 35 adds, on a small synthetic trace: ``column_step_share``
from the ``dense`` statistic of ``raft.dispatch_upload`` and
``raft.scan_fetch``; a shape that keeps the dense program; and a program
whose spans carry no ``dense`` (the parent)."""

import pytest

from benchmark import readings as rd, spanstats

STATS = {1: "node", 2: "tick", 3: "dense", 4: "columns", 5: "bytes"}
SPANS = {1: "raft.dispatch_upload", 2: "raft.scan_fetch"}
# Node 0: steps 5 and 6 cross as columns both ways, step 7 goes up as
# columns and comes down dense (its outbox overflowed), step 8 is a
# heartbeat round, dense both ways; step 9 has only its upload inside the
# slice and is left out.  Node 1: one column step.  3 of 5 whole steps.
MIXED = [  # (span, node, tick, dense, columns)
    (1, 0, 5, 0, 2), (2, 0, 5, 0, 1), (1, 0, 6, 0, 0), (2, 0, 6, 0, 3),
    (1, 0, 7, 0, 1), (2, 0, 7, 1, 0), (1, 0, 8, 1, 0), (2, 0, 8, 1, 0),
    (1, 0, 9, 0, 1), (1, 1, 4, 0, 4), (2, 1, 4, 0, 4),
]
DENSE_ONLY = [(s, n, t, 1, 0) for s, n, t, _, _ in MIXED]


def _stat(k, v):
    return f"stats {{ metadata_id: {k} int64_value: {v} }} "


def trace(events, stat_names=STATS):
    body = "".join(
        f"events {{ metadata_id: {span} offset_ps: {i}000000 "
        f"duration_ps: 1000000 " + _stat(1, node) + _stat(2, tick)
        + _stat(3, dense) + _stat(4, columns) + _stat(5, 1000) + "} "
        for i, (span, node, tick, dense, columns) in enumerate(events))
    return ('planes { id: 2 name: "/host:CPU" lines { id: 7 name: "python" '
            f'timestamp_ns: 1000 {body} }} '
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


@pytest.mark.parametrize("events, value", [
    (MIXED, 0.6), (DENSE_ONLY, 0.0), (MIXED[:4], 1.0),
], ids=["mixed", "dense-only", "columns-only"])
def test_reader_reads_the_slice(monkeypatch, tmp_path, events, value):
    r = readings(monkeypatch, tmp_path, trace(events))
    assert rd.read_metric("column_step_share", r) == pytest.approx(value)


def test_a_parent_without_the_statistic_reads_as_nothing(monkeypatch,
                                                         tmp_path):
    names = {k: ("other_" + n if n == "dense" else n)
             for k, n in STATS.items()}
    r = readings(monkeypatch, tmp_path, trace(MIXED, names))
    assert rd.read_metric("column_step_share", r) is None
    # ... while the readers of the statistics it does carry still read
    assert rd.read_metric("transfer_mb_per_step", r) == pytest.approx(0.002)


def test_no_slice_at_all_reads_as_nothing(monkeypatch):
    monkeypatch.setattr(spanstats, "find_run_xplane", lambda: None)
    assert rd.read_metric("column_step_share", bare()) is None
