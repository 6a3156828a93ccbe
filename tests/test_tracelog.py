"""Flight recorder: device event rings, decoding, and kernel↔oracle parity.

The recorder is itself correctness-checked: the scalar oracle emits the
same logical event stream at the same phase boundaries, and the parity
harness (test_oracle_parity.run_parity) compares every trace lane —
tick, kind, term, aux, count — tick-for-tick under partition +
crash-restart + clock-stall chaos, lease on and off (ISSUE 3 acceptance).
"""

import jax
import numpy as np
import pytest

from rafting_tpu.core.cluster import DeviceCluster
from rafting_tpu.core.sim import run_cluster_ticks, run_cluster_ticks_nemesis
from rafting_tpu.core.types import (
    LEADER, TR_BECAME_LEADER, TR_COMMIT_ADVANCE, TR_CRASH_RESTART,
    TRACE_EVENTS, EngineConfig, TraceState, init_state, trace_append,
)
from rafting_tpu.testkit import nemesis
from rafting_tpu.utils.tracelog import (
    TraceLog, decode_group, load_dump, save_dump, trace_to_numpy,
)

from rafting_tpu.testkit.parity import run_parity

CFG_KW = dict(n_groups=8, n_peers=3, log_slots=16, batch=4, max_submit=4,
              election_ticks=6, heartbeat_ticks=2, rpc_timeout_ticks=5,
              pre_vote=True)


# ------------------------------------------------------------ zero-cost ----

def test_trace_depth_zero_compiles_away():
    """cfg.trace_depth=0 must leave the state pytree bit-identical to the
    seed (the trace subtree is None — no leaves), through init, step and
    the fused scan."""
    cfg = EngineConfig(**CFG_KW)
    s = init_state(cfg, 0)
    assert s.trace is None
    # The traced step keeps it None (no lanes appear mid-scan).
    c = DeviceCluster(cfg, seed=0)
    assert c.states.trace is None
    sub = np.zeros((cfg.n_peers, cfg.n_groups), np.int32)
    states, _, _ = run_cluster_ticks(
        cfg, 8, c.states, c.inflight, c.last_info,
        c.conn, jax.numpy.asarray(sub))
    assert states.trace is None
    # Structure equality with an explicitly traceless tree: None added a
    # field but zero leaves, so flatten sees the seed layout.
    leaves_now = len(jax.tree.leaves(states))
    leaves_traced = len(jax.tree.leaves(
        init_state(EngineConfig(trace_depth=16, **CFG_KW), 0)))
    assert leaves_traced == leaves_now + 5  # the 5 TraceState lanes


# ------------------------------------------------- tier-1 compile smoke ----

def test_trace_enabled_scan_compiles_and_records():
    """CI smoke: the trace-enabled fused scan compiles and the recorder
    captures the election + commit story of a healthy run."""
    cfg = EngineConfig(trace_depth=16, **CFG_KW)
    c = DeviceCluster(cfg, seed=0)
    sub = jax.numpy.full((cfg.n_peers, cfg.n_groups), 2, jax.numpy.int32)
    states, _, _ = run_cluster_ticks(
        cfg, 64, c.states, c.inflight, c.last_info, c.conn, sub)
    lanes = trace_to_numpy(states.trace)
    assert lanes["n"].shape == (cfg.n_peers, cfg.n_groups)
    assert lanes["n"].sum() > 0
    # Every group elected a leader; the winner's ring must hold a
    # BECAME_LEADER and (with traffic flowing) a COMMIT_ADVANCE.
    roles = np.asarray(states.role)
    commits = np.asarray(states.commit)
    lead = np.argwhere(roles == LEADER)
    assert len(lead)
    n_node, g = (int(x) for x in lead[0])
    events, _ = decode_group(lanes, g, node=n_node)
    kinds = {ev["kind"] for ev in events}
    assert TR_BECAME_LEADER in kinds or TR_COMMIT_ADVANCE in kinds
    assert commits.max() > 0


# ----------------------------------------------------------- primitives ----

def test_trace_append_ring_semantics():
    tr = TraceState.empty(2, 4)
    mask = jax.numpy.asarray([True, False])
    for i in range(6):
        tr = trace_append(tr, mask, 7, tick=i, term=i * 10, aux=i)
    lanes = trace_to_numpy(tr)
    assert lanes["n"].tolist() == [6, 0]
    # Ring depth 4: only events 2..5 survive; 2 were overwritten.
    events, dropped = decode_group(lanes, 0)
    assert dropped == 2
    assert [ev["seq"] for ev in events] == [2, 3, 4, 5]
    assert [ev["tick"] for ev in events] == [2, 3, 4, 5]
    # Untouched group decodes empty.
    events, dropped = decode_group(lanes, 1)
    assert events == [] and dropped == 0
    # Incremental decode: draining from a cursor returns only the new.
    events, dropped = decode_group(lanes, 0, since=4)
    assert [ev["seq"] for ev in events] == [4, 5] and dropped == 0


def test_tracelog_ingest_and_labeled_metrics():
    cfg = EngineConfig(trace_depth=16, **CFG_KW)
    tl = TraceLog(cfg)
    tr = TraceState.empty(cfg.n_groups, 16)
    m_all = jax.numpy.ones(cfg.n_groups, bool)
    # Two elections in group order: first win, then churn.
    from rafting_tpu.core.types import TR_BECAME_CANDIDATE
    tr = trace_append(tr, m_all, TR_BECAME_CANDIDATE, 3, 1, 1)  # timer
    tr = trace_append(tr, m_all, TR_BECAME_LEADER, 4, 1, 1)
    d1 = tl.ingest(tr)
    assert d1["elections_won"] == cfg.n_groups
    assert d1["elections_cause_timer"] == cfg.n_groups
    assert d1["leader_churn"] == 0
    tr = trace_append(tr, m_all, TR_BECAME_CANDIDATE, 9, 2, 0)  # prevote
    tr = trace_append(tr, m_all, TR_BECAME_LEADER, 10, 2, 2)
    d2 = tl.ingest(tr)
    assert d2["leader_churn"] == cfg.n_groups
    assert d2["elections_cause_prevote"] == cfg.n_groups
    # Timelines accumulate in order; re-ingesting the same rings adds
    # nothing (the drained-through cursor).
    t0 = tl.timeline(0)
    assert [ev["event"] for ev in t0] == [
        "BECAME_CANDIDATE", "BECAME_LEADER",
        "BECAME_CANDIDATE", "BECAME_LEADER"]
    assert tl.ingest(tr) == {} or tl.ingest(tr)["trace_events"] == 0
    tl.reset_group(0)
    assert tl.timeline(0) == []


def test_dump_roundtrip_and_cli(tmp_path, capsys):
    tr = TraceState.empty(3, 4)
    tr = trace_append(tr, jax.numpy.asarray([True, True, False]),
                      TR_BECAME_LEADER, 5, 2, 9)
    path = str(tmp_path / "trace.json")
    save_dump(path, tr, meta={"run": "unit"})
    lanes = load_dump(path)
    events, _ = decode_group(lanes, 0)
    assert events[0]["event"] == "BECAME_LEADER"
    assert events[0]["tick"] == 5 and events[0]["aux"] == 9
    import sys
    sys.path.insert(0, "tools")
    import dump_timeline
    assert dump_timeline.main([path]) == 0
    out = capsys.readouterr().out
    assert "BECAME_LEADER" in out and "group 0" in out
    assert dump_timeline.main([path, "--group", "1", "--json"]) == 0
    assert "BECAME_LEADER" in capsys.readouterr().out


# ------------------------------------------------------- oracle parity -----

@pytest.mark.parametrize("lease", [True, False])
def test_trace_parity_under_chaos(lease):
    """ISSUE 3 acceptance: decoded device timeline == oracle timeline
    tick-for-tick (the parity harness compares every trace lane each
    tick, so any divergence pinpoints its first tick) under partitions,
    crash-restarts and clock stalls — lease on and off."""
    cfg = EngineConfig(trace_depth=16, read_lease=lease, **CFG_KW)
    seed = 23 if lease else 29
    states, stats = run_parity(seed, n_ticks=60, cfg=cfg, drop_p=0.15,
                               part_p=0.2, crash_p=0.06, stall_p=0.06)
    # The schedule must genuinely have contained both adversaries.
    assert stats["partitions"] > 0, "no partition window drawn — reseed"
    assert stats["crashes"] > 0, "no crash-restart drawn — reseed"
    # And the recorder must have seen them: every crashed node's ring
    # starts with events, incl. CRASH_RESTART somewhere in the run.
    all_kinds = set()
    for s in states:
        lanes = trace_to_numpy(s.trace)
        for g in range(cfg.n_groups):
            evs, _ = decode_group(lanes, g)
            all_kinds |= {ev["kind"] for ev in evs}
    assert TR_CRASH_RESTART in all_kinds
    assert TR_BECAME_LEADER in all_kinds


# ----------------------------------------------- device nemesis decode -----

def test_nemesis_schedule_crash_events_accounted():
    """Fused-scan chaos run: every scheduled crash of a node appears as
    exactly G CRASH_RESTART events in that node's rings (all groups
    restart together), and timelines name the events by kind."""
    cfg = EngineConfig(trace_depth=128, **CFG_KW)
    n_ticks = 40
    sched = nemesis.compose(
        nemesis.split_brain(cfg.n_peers, n_ticks, start=5, stop=15, seed=3),
        nemesis.crash_storm(cfg.n_peers, n_ticks, rate=0.05, seed=4),
    )
    crashes = np.asarray(sched.crash).sum(axis=0)          # [N]
    assert crashes.sum() > 0, "schedule drew no crashes — reseed"
    c = DeviceCluster(cfg, seed=1)
    sub = jax.numpy.full((cfg.n_peers, cfg.n_groups), 1, jax.numpy.int32)
    states, _, _ = run_cluster_ticks_nemesis(
        cfg, c.states, c.inflight, c.last_info, sched, sub)
    lanes = trace_to_numpy(states.trace)
    for n in range(cfg.n_peers):
        got = 0
        for g in range(cfg.n_groups):
            evs, dropped = decode_group(lanes, g, node=n)
            assert dropped == 0, "depth 128 should hold this run"
            got += sum(ev["kind"] == TR_CRASH_RESTART for ev in evs)
            # Event names decode for every record.
            assert all(not ev["event"].startswith("UNKNOWN")
                       for ev in evs)
        assert got == int(crashes[n]) * cfg.n_groups


def test_trace_events_have_names():
    assert set(TRACE_EVENTS) == set(range(1, 13))
