"""The readers PR 24 adds: the stage spans of a small synthetic trace
(``stagespans.py`` and the seven readers over it), and the registry readers
over a hand-made ``program`` (``program_marks.py`` and the nine readers that
wait for the harness to fill it)."""

import json
import os

import pytest

from benchmark import harness, program_marks, readings as rd, stagespans

# One device: ops [0,2) and [10,12) us -> idle [2,10) inside the window
# 0..12.  Two nodes.  Node 0, tick 5: dispatch_intake [0,1) upload [1,4)
# enqueue [4,5) scan_device [5,6) scan_fetch [6,7) tail [7,8.5): 8.5 us
# from first start to tail end, 8.5 covered but for the hole [6.5,7):
# scan_fetch is [6,6.5).  Node 0 waits [8.5,20); node 1 (tick 9, intake
# and tail only, 1 us each) waits [4,9.5).  Both asleep: [8.5,9.5) of the
# idle [2,10) -> 12.5%.
TRACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 7 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000
      stats { metadata_id: 1 int64_value: 0 } stats { metadata_id: 2 int64_value: 5 } }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 3000000
      stats { metadata_id: 1 int64_value: 0 } stats { metadata_id: 2 int64_value: 5 } }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 1000000
      stats { metadata_id: 1 int64_value: 0 } stats { metadata_id: 2 int64_value: 5 } }
    events { metadata_id: 4 offset_ps: 5000000 duration_ps: 1000000
      stats { metadata_id: 1 int64_value: 0 } stats { metadata_id: 2 int64_value: 5 } }
    events { metadata_id: 5 offset_ps: 6000000 duration_ps: 500000
      stats { metadata_id: 1 int64_value: 0 } stats { metadata_id: 2 int64_value: 5 } }
    events { metadata_id: 6 offset_ps: 7000000 duration_ps: 1500000
      stats { metadata_id: 1 int64_value: 0 } stats { metadata_id: 2 int64_value: 5 } }
    events { metadata_id: 7 offset_ps: 8500000 duration_ps: 11500000
      stats { metadata_id: 1 int64_value: 0 } stats { metadata_id: 2 int64_value: 5 } }
    events { metadata_id: 2 offset_ps: 30000000 duration_ps: 9000000
      stats { metadata_id: 1 int64_value: 0 } stats { metadata_id: 2 int64_value: 6 } }
    events { metadata_id: 8 offset_ps: 0 duration_ps: 90000000 } }
  lines { id: 8 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 1000000
      stats { metadata_id: 1 int64_value: 1 } stats { metadata_id: 2 int64_value: 9 } }
    events { metadata_id: 6 offset_ps: 3000000 duration_ps: 1000000
      stats { metadata_id: 1 int64_value: 1 } stats { metadata_id: 2 int64_value: 9 } }
    events { metadata_id: 7 offset_ps: 4000000 duration_ps: 5500000
      stats { metadata_id: 1 int64_value: 1 } stats { metadata_id: 2 int64_value: 9 } } }
  event_metadata { key: 1 value { id: 1 name: "raft.dispatch_intake" } }
  event_metadata { key: 2 value { id: 2 name: "raft.dispatch_upload" } }
  event_metadata { key: 3 value { id: 3 name: "raft.dispatch_enqueue" } }
  event_metadata { key: 4 value { id: 4 name: "raft.scan_device" } }
  event_metadata { key: 5 value { id: 5 name: "raft.scan_fetch" } }
  event_metadata { key: 6 value { id: 6 name: "raft.tail" } }
  event_metadata { key: 7 value { id: 7 name: "raft.wait" } }
  event_metadata { key: 8 value { id: 8 name: "PjitFunction(node_step)" } }
  stat_metadata { key: 1 value { id: 1 name: "node" } }
  stat_metadata { key: 2 value { id: 2 name: "tick" } } }
"""
NEW_FROM_SPANS = ("dispatch_ms", "dispatch_upload_ms", "tick_tail_ms",
                  "tick_unspanned_ms", "scan_device_ms", "scan_fetch_ms",
                  "idle_in_wait_pct")


def planes(text=TRACE):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(text).planes


def readings(**extra):
    r = rd.Readings(window_s=10.0, histograms=[], ticks=[20, 20, 20],
                    fsync_calls=0, acked_writes=0, commit_latencies_s=[],
                    read_latencies_s=[], gen_late_s=[])
    for k, v in extra.items():
        setattr(r, k, v)
    return r


def test_stage_spans_of_a_synthetic_trace():
    s = stagespans.reduce_planes(planes())
    assert s.busiest() == 0              # node 0's tick costs 8 us, node 1's 2
    assert len(s.complete(0)) == 1       # tick 6 is cut by the slice's end
    assert s.mean_ms("dispatch_intake", "dispatch_upload",
                     "dispatch_enqueue") == pytest.approx(5e-3)
    assert s.mean_ms("dispatch_upload") == pytest.approx(3e-3)
    assert s.mean_ms("scan_fetch") == pytest.approx(0.5e-3)
    assert s.mean_ms("tail") == pytest.approx(1.5e-3)
    assert s.unspanned_ms() == pytest.approx(0.5e-3)
    assert s.idle == [(pytest.approx(3e-6), pytest.approx(11e-6))]
    assert s.idle_in_wait_pct() == pytest.approx(12.5)


def test_intersect():
    assert stagespans.intersect([(0, 4), (6, 9)], [(1, 2), (3, 7), (8, 12)]) \
        == [(1, 2), (3, 4), (6, 7), (8, 9)]


def test_the_span_readers_read_the_slice(monkeypatch, tmp_path):
    s = stagespans.reduce_planes(planes())
    monkeypatch.setattr(stagespans, "reduce_file", lambda path: s)
    r = readings(xplane=str(tmp_path / "x.xplane.pb"))
    got = {m: rd.read_metric(m, r) for m in NEW_FROM_SPANS}
    assert got == {"dispatch_ms": pytest.approx(5e-3),
                   "dispatch_upload_ms": pytest.approx(3e-3),
                   "tick_tail_ms": pytest.approx(1.5e-3),
                   "tick_unspanned_ms": pytest.approx(0.5e-3),
                   "scan_device_ms": pytest.approx(1e-3),
                   "scan_fetch_ms": pytest.approx(0.5e-3),
                   "idle_in_wait_pct": pytest.approx(12.5)}


def test_a_program_without_stage_spans_reads_as_nothing(monkeypatch):
    """The parent of PR 24 writes no raft.* span: every reader returns
    None and raises nothing, with and without a traced slice."""
    bare = TRACE.replace('"raft.', '"other.')
    s = stagespans.reduce_planes(planes(bare))
    monkeypatch.setattr(stagespans, "reduce_file", lambda path: s)
    for r in (readings(xplane="somewhere"), readings()):
        if getattr(r, "xplane", None) is None:
            monkeypatch.setattr(stagespans, "find_run_xplane", lambda: None)
        assert [rd.read_metric(m, r) for m in NEW_FROM_SPANS] == [None] * 7


def test_this_process_s_slice_is_found(tmp_path, monkeypatch):
    import tempfile
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    assert stagespans.find_run_xplane() is None
    d = tmp_path / "raftbench-abc" / "trace" / "plugins" / "profile" / "t1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"")
    assert stagespans.find_run_xplane() == str(d / "host.xplane.pb")


PROGRAM = [
    {"histograms": {"lat_submit_offer_s": (2, 0.5), "lat_offer_stage_s": (2, 0.1),
                    "lat_stage_fsync_s": (2, 0.1), "lat_fsync_send_s": (2, 0.2),
                    "lat_send_commit_s": (2, 3.0), "lat_commit_apply_s": (2, 0.1),
                    "lat_apply_ack_s": (2, 0.0), "lat_e2e_s": (2, 4.0),
                    "lat_read_queue_s": (1, 0.3), "lat_read_confirm_s": (1, 0.6),
                    "lat_read_e2e_s": (1, 0.9),
                    "inbox_wait_s": (40, 10.0), "inbox_backlog": (20, 0.0)},
     "counters": {"ticks_late": 0}, "gauges": {}},
    {"histograms": {"lat_submit_offer_s": (2, 0.7), "lat_offer_stage_s": (2, 0.1),
                    "lat_stage_fsync_s": (2, 0.1), "lat_fsync_send_s": (2, 0.2),
                    "lat_send_commit_s": (2, 5.0), "lat_commit_apply_s": (2, 0.1),
                    "lat_apply_ack_s": (2, 0.0), "lat_e2e_s": (2, 6.2),
                    "lat_read_queue_s": (3, 0.9), "lat_read_confirm_s": (3, 1.8),
                    "lat_read_e2e_s": (3, 2.7),
                    "inbox_wait_s": (40, 30.0), "inbox_backlog": (20, 20.0)},
     "counters": {"ticks_late": 1, "inbox_collapsed": 0}, "gauges": {}},
]


def test_the_registry_readers_on_a_hand_made_program():
    r = readings(program=PROGRAM)
    got = {m["name"]: rd.read_metric(m["name"], r)
           for m in program_marks.extra_entries()
           if m["source"] != "device_trace"}
    assert got == {
        "commit_queue_ms": pytest.approx(300.0),
        "commit_persist_ms": pytest.approx(100.0),
        "commit_replicate_ms": pytest.approx(2100.0),
        "commit_release_ms": pytest.approx(50.0),
        "read_queue_ms": pytest.approx(300.0),
        "read_confirm_ms": pytest.approx(600.0),
        "inbox_wait_ms": pytest.approx(750.0),        # the worst node
        "inbox_backlog_slices": pytest.approx(1.0),   # the worst node
        "ticks_late": 1.0}
    # The four parts of a commit are over the same spans: they add up to
    # the pooled mean of lat_e2e_s, as the two of a read to lat_read_e2e_s.
    assert sum(got[f"commit_{p}_ms"] for p in
               ("queue", "persist", "replicate", "release")) \
        == pytest.approx(program_marks.pooled_mean_ms(r, "lat_e2e_s"))
    assert got["read_queue_ms"] + got["read_confirm_ms"] \
        == pytest.approx(program_marks.pooled_mean_ms(r, "lat_read_e2e_s"))
    # No program field (today's harness), or no sample: nothing, no raise.
    assert all(rd.read_metric(name, readings()) is None for name in got)
    empty = [{"histograms": {}, "counters": {}, "gauges": {}}]
    assert rd.read_metric("commit_queue_ms", readings(program=empty)) is None
    assert rd.read_metric("ticks_late", readings(program=empty)) == 0.0


def test_marks_and_delta_cover_every_name_of_a_registry():
    from rafting_tpu.utils.metrics import Metrics

    class Node:
        metrics = Metrics()

    m = Node.metrics
    m.observe("lat_e2e_s", 2.0)
    m["ticks_late"] += 1
    before = program_marks.marks(Node)
    m.observe("lat_e2e_s", 4.0)
    m.observe("a_histogram_nobody_named", 1.0)
    m["ticks_late"] += 2
    m.gauge("inbox_backlog_src1", 1)
    d = program_marks.delta(before, program_marks.marks(Node))
    assert d["histograms"]["lat_e2e_s"] == (1, pytest.approx(4.0))
    assert d["histograms"]["a_histogram_nobody_named"] == (1, pytest.approx(1.0))
    assert d["counters"]["ticks_late"] == 2
    assert d["gauges"]["inbox_backlog_src1"] == 1


def test_the_waiting_entries_are_entries_of_the_benchmark_s_form():
    bench = harness.load_benchmark()
    listed = {m["name"] for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]} | {"inbox"}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in program_marks.extra_entries():
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["name"] not in listed and m["moves"] in e2e
        assert m["layer"] in layers
        assert os.path.exists(os.path.join(
            harness.HERE, "layer_metrics", m["name"] + ".py"))
    waiting = {m["name"] for m in program_marks.extra_entries()}
    assert set(NEW_FROM_SPANS) <= listed | waiting
    assert json.dumps(bench)     # still one JSON object
