"""Striped parallel host tier (runtime/node.py _host_phase_striped):
tick-for-tick scalar-oracle parity with the group-striped worker pool
under partition + crash + stall nemesis, the eager-send crash window
(acks/futures must never precede the tick's own fsync even though leader
AE frames release before it), and serial/striped outcome convergence.

The parity tests monkeypatch the runtime's ``node_step_packed`` with a
wrapper that also runs the scalar oracle on the SAME inputs (unpacked from
the tick's upload buffers) every tick, so a
striped host tier that corrupts what it feeds the device (WAL staging,
submission arenas, inbox routing) diverges at the exact offending tick —
the striped workers sit between two oracle-checked device steps."""

import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rafting_tpu.runtime.node as node_mod
from rafting_tpu.core.step import step_layouts
from rafting_tpu.core.types import EngineConfig, LEADER
from rafting_tpu.log.store import LogStore, restore_raft_state
from rafting_tpu.testkit import nemesis
from rafting_tpu.testkit.fixtures import NullProvider
from rafting_tpu.testkit.harness import LocalCluster
from rafting_tpu.testkit.oracle import oracle_step

from rafting_tpu.testkit.parity import (
    assert_info_equal, assert_messages_equal, assert_state_equal,
)

CFG = EngineConfig(n_groups=8, n_peers=3, log_slots=16, batch=4,
                   max_submit=4, election_ticks=8, heartbeat_ticks=2,
                   rpc_timeout_ticks=6, pre_vote=True)


@pytest.fixture(autouse=True)
def _python_host_tier(monkeypatch):
    """Pin the pure-Python striped tier: with the native .so present the
    node would auto-route to _host_phase_native and this module's
    subject (the Python worker pool) would never run.  The native phase
    has its own suite (test_native_host.py)."""
    monkeypatch.setenv("RAFT_NATIVE_HOST", "0")


@pytest.fixture
def oracle_checked_step(monkeypatch):
    """Cross-check every runtime node_step_packed call against the scalar
    oracle: the oracle steps what the tick's upload buffers hold, and the
    packed step's readback must unpack to the oracle's outputs (oracle
    FIRST: the step donates its state buffers).  Serial pipeline mode
    only — the oracle has no durable_tail lane."""
    real = node_mod.node_step_packed
    calls = {"n": 0}

    def checked(cfg, inputs, state, buffers):
        host, inbox = jax.tree.map(
            jnp.asarray, inputs.unpack(jax.device_get(buffers)))
        o_state, o_out, o_info = oracle_step(cfg, state, inbox, host)
        k_state, packed = real(cfg, inputs, state, buffers)
        _, readback = step_layouts(cfg, host.durable_tail is not None)
        back = readback.unpack(jax.device_get(packed))
        tag = f"oracle-checked step #{calls['n']}"
        assert_state_equal(k_state, o_state, tag)
        assert_messages_equal(back.outbox, o_out, tag)
        assert_info_equal(back.info, o_info, tag)
        calls["n"] += 1
        return k_state, packed

    monkeypatch.setattr(node_mod, "node_step_packed", checked)
    return calls


# --------------------------------------------------- oracle parity x W ----


@pytest.mark.parametrize("workers,lease", [
    (1, True), (2, True), (4, True),
    (1, False), (2, False), (4, False),
])
def test_striped_oracle_parity_under_nemesis(tmp_path, workers, lease,
                                             oracle_checked_step):
    """W ∈ {1,2,4} striped host tiers drive the identical device-visible
    semantics under a partition + crash-restart + clock-stall schedule
    with submit and linearizable-read load offered throughout — every
    tick of every node is oracle-checked."""
    cfg = EngineConfig(n_groups=8, n_peers=3, log_slots=16, batch=4,
                       max_submit=4, election_ticks=8, heartbeat_ticks=2,
                       rpc_timeout_ticks=6, pre_vote=True, read_lease=lease)
    sched = nemesis.compose(
        nemesis.split_brain(3, 36, start=8, stop=20, seed=21),
        nemesis.crash_storm(3, 36, rate=0.02, seed=22),
        nemesis.clock_stalls(3, 36, rate=0.03, seed=23),
    )
    c = LocalCluster(cfg, str(tmp_path), provider_factory=NullProvider,
                     seed=5, pipeline=False, wal_shards=4,
                     host_workers=workers)
    try:
        assert all(n._w_eff == workers for n in c.nodes.values())

        def audit(t):
            for g in range(cfg.n_groups):
                c.leader_of(g)   # raises on same-term split brain
            # Offered load through the chaos: the striped persist/apply/
            # send path must carry real entries and reads, not just
            # heartbeats.
            for n in c.nodes.values():
                for g in np.nonzero((n.h_role == LEADER) & n.h_ready)[0]:
                    n.submit_batch(int(g), [b"s%d-%d" % (t, g)])
                    n.read(int(g), b"r%d-%d" % (t, g))

        c.replay_schedule(sched, audit=audit)
        for _ in range(50):
            c.tick()
            if all(c.leader_of(g) is not None
                   for g in range(cfg.n_groups)):
                break
        for g in range(cfg.n_groups):
            assert c.wait_leader(g, max_rounds=100) is not None
        assert oracle_checked_step["n"] > 36 * 2, \
            "oracle wrapper never saw the replayed ticks"
        total = sum(int(n.h_commit.astype(np.int64).sum())
                    for n in c.nodes.values())
        assert total > 0, "schedule never committed anything"
    finally:
        c.close()


# ------------------------------------------------- eager-send crash window


def test_eager_window_crash_completes_nothing(tmp_path):
    """Kill a pipelined striped leader inside the eager-send window —
    AE/heartbeat frames for tick N already left the node, tick N+1 may be
    dispatched, but tick N's fsync has NOT run.  No submit future may
    have completed for the un-fsynced range, and WAL recovery from the
    crash image restores the pre-accept durable tail (commit safety holds
    because the device clamps self-match to durable_tail, so an eagerly
    announced-but-lost suffix is merely resent, never counted)."""
    cfg = EngineConfig(n_groups=4, n_peers=3, log_slots=32, batch=4,
                       max_submit=4, election_ticks=10, heartbeat_ticks=3,
                       rpc_timeout_ticks=8)
    c = LocalCluster(cfg, str(tmp_path), pipeline=True, wal_shards=2,
                     host_workers=2)
    try:
        lead = c.wait_leader(0)
        c.tick(5)
        node = c.nodes[lead]
        assert node._w_eff == 2
        assert node.metrics["eager_sends"] > 0, \
            "eager-send window never opened — test is vacuous"
        tail_before = int(node._durable_tail_m[0])

        fut = node.submit_batch(0, [b"eager-%d" % k for k in range(3)])
        # One lockstep round: the scan accepts the batch and the leader's
        # eager sender already released this tick's AE frames, but the
        # batch's host phase (staging + fsync) runs only NEXT tick.
        c.tick(1)
        pend = node._pending
        assert pend is not None
        acc = int(np.asarray(pend.info.submit_acc)[0])
        assert acc == 3, f"device should have accepted the batch, got {acc}"
        start = int(np.asarray(pend.info.submit_start)[0])

        assert not fut.done(), \
            "submit future completed before the range was fsynced"
        assert int(node._durable_tail_m[0]) == tail_before

        img = str(tmp_path / "crash-img")
        shutil.copytree(os.path.join(node.data_dir, "wal"), img)
        store = LogStore(img)
        try:
            assert store.tail(0) == tail_before < start
            state = restore_raft_state(cfg, lead, store)
            assert int(np.asarray(state.log.last)[0]) == tail_before
            for idx in range(start, start + acc):
                assert store.payload(0, idx) is None
        finally:
            store.close()

        # The surviving node drains normally: the future completes only
        # AFTER its own host phase's fsync.
        for _ in range(30):
            c.tick(1)
            if fut.done():
                break
        assert fut.done() and len(fut.result(timeout=1)) == 3
        assert int(node._durable_tail_m[0]) >= start + acc - 1
    finally:
        c.close()


# ------------------------------------------- serial/striped convergence --


def test_striped_serial_convergence(tmp_path):
    """Striped (W=4) and serial (W=1) runtimes drive the same workload to
    the same applied outcome — the stripes repartition WORK, never
    effects."""
    results = {}
    for w in (1, 4):
        root = str(tmp_path / f"w{w}")
        c = LocalCluster(CFG, root, provider_factory=NullProvider,
                         seed=3, pipeline=True, wal_shards=4,
                         host_workers=w)
        try:
            lead = c.wait_leader(0)
            c.tick_until(lambda: c.nodes[lead].is_ready(0),
                         what="leader ready")
            futs = [c.nodes[lead].submit_batch(0, [b"c%d" % k])
                    for k in range(8)]
            for _ in range(60):
                c.tick(1)
                if all(f.done() for f in futs):
                    break
            results[w] = [f.result(timeout=1) for f in futs]
        finally:
            c.close()
    assert results[1] == results[4]


def test_worker_width_clamps_to_stripes(tmp_path):
    """host_workers beyond the WAL stripe count clamps to it (a worker
    without a stripe would idle every tick), and a single-stripe store
    degrades to the serial phase."""
    c = LocalCluster(CFG, str(tmp_path / "a"), provider_factory=NullProvider,
                     wal_shards=2, host_workers=8)
    try:
        assert all(n._w_eff == 2 for n in c.nodes.values())
    finally:
        c.close()
    c = LocalCluster(CFG, str(tmp_path / "b"), provider_factory=NullProvider,
                     wal_shards=1, host_workers=4)
    try:
        assert all(n._w_eff == 1 for n in c.nodes.values())
        c.wait_leader(0)
    finally:
        c.close()
