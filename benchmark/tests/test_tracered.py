"""The trace reduction on a small synthetic trace: busy is the union of the
device's op intervals, steps are the module events named like the step, and
idle gaps are labelled by the host span that covers them."""

import pytest

from benchmark import tracered

# One device, times in ps from the line's 1 us origin.  Ops: [0,4) [2,6)
# overlap -> 6 us busy; [10,12) -> 2 us; window 0..20 us -> busy 8/20.
TRACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 4000000 }
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 2000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 7000000 }
    events { metadata_id: 4 offset_ps: 10000000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "copy.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_node_step(123)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_other(9)" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 7 name: "tick-thread" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 3000000000 } }
  event_metadata { key: 1 value { id: 1 name: "fsync" } } }
"""


def planes():
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(TRACE).planes


def test_busy_share_steps_and_gaps():
    r = tracered.reduce_planes(planes(), window=(1e-6, 21e-6))
    assert r.n_devices == 1
    assert r.window_s == pytest.approx(20e-6)
    assert r.busy_s == pytest.approx(8e-6)
    assert r.step_executions == 1
    assert r.step_device_s == pytest.approx(7e-6)
    assert r.ops_in_steps_s == pytest.approx(8e-6)   # 4 + 4, nested or not
    assert r.device_ops[0] == ["fusion.1", pytest.approx(6e-6)]
    gaps = dict((n, s) for n, s in r.idle_gaps)
    assert gaps["fsync"] == pytest.approx(4e-6 + 8e-6)   # [7,11) and [13,21)


def test_default_window_is_first_to_last_device_event():
    r = tracered.reduce_planes(planes())
    assert r.window_s == pytest.approx(12e-6)
    assert r.busy_s == pytest.approx(8e-6)


def test_union():
    assert tracered.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
