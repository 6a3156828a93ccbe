"""The readers PR 26 adds: the statistics on ``raft.reads`` and
``raft.maintain`` spans of a small synthetic trace (``spanstats.py`` and the
two readers over it), and a program whose spans carry none (the parent)."""

import pytest

from benchmark import readings as rd, spanstats

# Two nodes.  Node 0 leads (led 1): its reads spans served 3 queries under
# 1 barrier at tick 5 and 2 under 2 at tick 6; its ring stood at 16 and 32
# of 64.  Node 1 follows (led 0): one reads span with nothing served (no
# statistic at all, as the program writes it), ring at 48 of 64.
TRACE = """
planes { id: 2 name: "/host:CPU"
  lines { id: 7 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000
      stats { metadata_id: 1 int64_value: 0 } stats { metadata_id: 2 int64_value: 5 }
      stats { metadata_id: 3 int64_value: 3 } stats { metadata_id: 4 int64_value: 1 } }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 1000000
      stats { metadata_id: 1 int64_value: 0 } stats { metadata_id: 2 int64_value: 5 }
      stats { metadata_id: 5 int64_value: 16 } stats { metadata_id: 6 int64_value: 64 }
      stats { metadata_id: 7 int64_value: 1 } }
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 1000000
      stats { metadata_id: 1 int64_value: 0 } stats { metadata_id: 2 int64_value: 6 }
      stats { metadata_id: 3 int64_value: 2 } stats { metadata_id: 4 int64_value: 2 } }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 1000000
      stats { metadata_id: 1 int64_value: 0 } stats { metadata_id: 2 int64_value: 6 }
      stats { metadata_id: 5 int64_value: 32 } stats { metadata_id: 6 int64_value: 64 }
      stats { metadata_id: 7 int64_value: 1 } } }
  lines { id: 8 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 1000000
      stats { metadata_id: 1 int64_value: 1 } stats { metadata_id: 2 int64_value: 9 } }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000
      stats { metadata_id: 1 int64_value: 1 } stats { metadata_id: 2 int64_value: 9 }
      stats { metadata_id: 5 int64_value: 48 } stats { metadata_id: 6 int64_value: 64 }
      stats { metadata_id: 7 int64_value: 0 } } }
  event_metadata { key: 1 value { id: 1 name: "raft.reads" } }
  event_metadata { key: 2 value { id: 2 name: "raft.maintain" } }
  stat_metadata { key: 1 value { id: 1 name: "node" } }
  stat_metadata { key: 2 value { id: 2 name: "tick" } }
  stat_metadata { key: 3 value { id: 3 name: "queries" } }
  stat_metadata { key: 4 value { id: 4 name: "barriers" } }
  stat_metadata { key: 5 value { id: 5 name: "ring_used" } }
  stat_metadata { key: 6 value { id: 6 name: "ring_slots" } }
  stat_metadata { key: 7 value { id: 7 name: "led" } } }
"""
NEW = ("reads_per_barrier", "log_ring_fill_pct")


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


def test_span_statistics_of_a_synthetic_trace():
    s = spanstats.reduce_planes(planes())
    assert s["reads"] == {0: {5: {"queries": 3.0, "barriers": 1.0},
                              6: {"queries": 2.0, "barriers": 2.0}}}
    assert s["maintain"][1][9] == {"ring_used": 48.0, "ring_slots": 64.0,
                                   "led": 0.0}
    assert set(spanstats.rows(s, "maintain", "ring_used")) == {0, 1}
    assert spanstats.rows(s, "reads", "by_pressure") == {}
    assert spanstats.rows(None, "reads", "barriers") == {}


def test_the_two_readers_read_the_slice(monkeypatch, tmp_path):
    s = spanstats.reduce_planes(planes())
    monkeypatch.setattr(spanstats, "reduce_file", lambda path: s)
    r = readings(xplane=str(tmp_path / "x.xplane.pb"))
    # 5 queries under 3 barriers; node 0 leads: (16 + 32) / 2 of 64 slots.
    assert rd.read_metric("reads_per_barrier", r) == pytest.approx(5 / 3)
    assert rd.read_metric("log_ring_fill_pct", r) == pytest.approx(37.5)


@pytest.mark.parametrize("strip", ["statistics", "spans", "slice"])
def test_a_program_without_the_statistics_reads_as_nothing(monkeypatch,
                                                           tmp_path, strip):
    """The parent of PR 26 writes spans without these statistics (here:
    under other names); the parent of PR 24 writes no span; a run that
    traced nothing has no slice: both readers return None, raising
    nothing."""
    if strip == "slice":
        monkeypatch.setattr(spanstats, "find_run_xplane", lambda: None)
        r = readings()
    else:
        text = TRACE.replace('"raft.', '"other.')
        if strip == "statistics":
            text = TRACE
            for name in ("queries", "barriers", "ring_used", "ring_slots",
                         "led"):
                text = text.replace(f'name: "{name}"', f'name: "other_{name}"')
        s = spanstats.reduce_planes(planes(text))
        monkeypatch.setattr(spanstats, "reduce_file", lambda path: s)
        r = readings(xplane=str(tmp_path / "x.xplane.pb"))
    assert [rd.read_metric(m, r) for m in NEW] == [None, None]
