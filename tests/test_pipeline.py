"""Durable-pipeline tests: the double-buffered tick (runtime/node.py),
its ack-after-fsync crash window, the sharded WAL's recovery parity, the
off-thread checkpoint pool, and the durable-tail feedback lane in the
fused scan.

The load-bearing invariant throughout: no submit future completes, and no
RPC leaves the node, for a log range that has not been fsynced — even
though the next tick's device scan is already executing while the fsync
runs (RaftNode.tick docstring; core/types.py HostInbox.durable_tail)."""

import os
import shutil
import threading

import numpy as np
import pytest

from rafting_tpu.core.types import EngineConfig, LEADER
from rafting_tpu.log.store import LogStore, restore_raft_state
from rafting_tpu.log.wal import native_available
from rafting_tpu.snapshot.policy import MaintainAgreement
from rafting_tpu.testkit.fixtures import NullProvider
from rafting_tpu.testkit.harness import LocalCluster

CFG = EngineConfig(n_groups=4, n_peers=3, log_slots=32, batch=4,
                   max_submit=4, election_ticks=10, heartbeat_ticks=3,
                   rpc_timeout_ticks=8)


# ---------------------------------------------------------------- crash window


def test_late_shed_keeps_offers_riding_the_dispatched_tick(tmp_path):
    """Pipelined mode dispatches tick N+1 before tick N's host phase runs,
    so when that phase sheds aged batches from the submit queues, some
    queued entries are already offered to the device.  The device accepts
    by count: expiring those entries would leave it accepting more than
    the queue holds (the "beyond the queued depth" invariant in
    _persist_prepare).  The shed must leave them queued."""
    c = LocalCluster(CFG, str(tmp_path), pipeline=True, wal_shards=2)
    try:
        lead = c.wait_leader(0)
        c.tick(5)
        node = c.nodes[lead]
        assert node.is_ready(0)
        node.admission.expire_age = lambda: 0.0   # every batch is overage
        futs = [node.submit(0, b"ride-%d" % k) for k in range(3)]
        for _ in range(40):
            c.tick(1)
            if all(f.done() for f in futs):
                break
        assert all(f.done() for f in futs)
        # All three rode the tick dispatched before the shed looked at
        # them, so all three were accepted and must commit.
        assert [f.exception() for f in futs] == [None] * 3
    finally:
        c.close()


def test_crash_between_dispatch_and_fsync_completes_nothing(tmp_path):
    """Kill the node inside the pipeline's overlap window — tick N's scan
    accepted entries and tick N+1 may already be dispatched, but tick N's
    host phase (WAL staging + fsync) has NOT run.  The crash image must
    recover to the pre-accept durable tail, and no submit future may have
    completed for the un-fsynced range."""
    c = LocalCluster(CFG, str(tmp_path), pipeline=True, wal_shards=2)
    try:
        lead = c.wait_leader(0)
        c.tick(5)
        node = c.nodes[lead]
        tail_before = int(node._durable_tail_m[0])

        fut = node.submit_batch(0, [b"crash-%d" % k for k in range(3)])
        # One lockstep round: the leader's scan accepts the batch, but in
        # pipelined mode its host phase runs only NEXT tick — this is
        # exactly the crash window.
        c.tick(1)
        pend = node._pending
        assert pend is not None, "pipelined node must hold a pending tick"
        acc = int(np.asarray(pend.info.submit_acc)[0])
        assert acc == 3, f"device should have accepted the batch, got {acc}"
        start = int(np.asarray(pend.info.submit_start)[0])

        # The un-fsynced range must not be acknowledged in any way.
        assert not fut.done(), \
            "submit future completed before the range was fsynced"
        assert int(node._durable_tail_m[0]) == tail_before

        # Crash disk image: copy the WAL dir as it is at this instant.
        img = str(tmp_path / "crash-img")
        shutil.copytree(os.path.join(node.data_dir, "wal"), img)

        # Recovery from the image: the durable tail excludes the whole
        # accepted-but-never-fsynced range.
        store = LogStore(img)
        try:
            assert store.tail(0) == tail_before < start
            state = restore_raft_state(CFG, lead, store)
            assert int(np.asarray(state.log.last)[0]) == tail_before
            for idx in range(start, start + acc):
                assert store.payload(0, idx) is None
        finally:
            store.close()

        # The surviving cluster drains normally: the same future now
        # completes AFTER its host phase fsync.
        for _ in range(30):
            c.tick(1)
            if fut.done():
                break
        assert fut.done() and len(fut.result(timeout=1)) == 3
        assert int(node._durable_tail_m[0]) >= start + acc - 1
    finally:
        c.close()


def test_close_drains_pending_tick(tmp_path):
    """A graceful close must settle the pending tick's host phase: the
    accepted range becomes durable and survives restart."""
    c = LocalCluster(CFG, str(tmp_path), pipeline=True)
    try:
        lead = c.wait_leader(0)
        c.tick(5)
        node = c.nodes[lead]
        fut = node.submit_batch(0, [b"drain-%d" % k for k in range(2)])
        c.tick(1)
        pend = node._pending
        assert pend is not None
        acc = int(np.asarray(pend.info.submit_acc)[0])
        assert acc == 2
        end = int(np.asarray(pend.info.submit_start)[0]) + acc - 1
        wal_dir = os.path.join(node.data_dir, "wal")
        c.kill_node(lead)   # close() drains the pipeline
        store = LogStore(wal_dir)
        try:
            assert store.tail(0) >= end
        finally:
            store.close()
    finally:
        c.close()


# ------------------------------------------------------- sharded WAL recovery


def _drive(store: LogStore) -> None:
    """One deterministic durable workload over several groups (appends,
    overwrites, stable records, truncation, floor moves)."""
    for g in range(6):
        store.append_entries(g, 1, [1] * 4,
                             [b"g%d-%d" % (g, i) for i in range(4)])
        store.put_stable(g, 3, g % 3)
    store.append_spans([
        (1, 5, b"aabbb", np.asarray([2, 3], np.uint32),
         np.asarray([2, 2], np.int64)),
        (2, 3, b"xyz", np.asarray([3], np.uint32), 2),   # overwrite suffix
    ])
    store.truncate_to(3, 2)
    store.set_floor(4, 2, 1)
    store.put_stable(5, 7, 1)
    store.sync()


def _exports_equal(a: dict, b: dict) -> None:
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


@pytest.mark.parametrize("force_python", [
    True,
    pytest.param(False, marks=pytest.mark.skipif(
        not native_available(), reason="no native WAL toolchain")),
])
def test_sharded_wal_recovery_parity(tmp_path, force_python):
    """The same workload written under S=4 stripes and under the single
    flat WAL recovers to identical reconstructed state."""
    flat = str(tmp_path / "flat")
    striped = str(tmp_path / "striped")
    for path, shards in ((flat, 1), (striped, 4)):
        s = LogStore(path, force_python=force_python, shards=shards)
        _drive(s)
        s.close()

    G, L = 8, 32
    s1 = LogStore(flat, force_python=force_python)
    s4 = LogStore(striped, force_python=force_python)
    try:
        assert s4.wal.n_shards == 4   # pinned by the meta file
        _exports_equal(s1.export_state(G, L), s4.export_state(G, L))
        for g in range(6):
            assert s1.stable(g) == s4.stable(g)
            for idx in range(1, 8):
                assert s1.payload(g, idx) == s4.payload(g, idx), (g, idx)
    finally:
        s1.close()
        s4.close()


def test_sharded_wal_torn_tail_truncation(tmp_path):
    """Garbage appended to every shard's segment tail (a torn write at
    crash) is truncated per shard on reopen; the recovered state equals
    the cleanly-synced image."""
    path = str(tmp_path / "torn")
    s = LogStore(path, force_python=True, shards=4)
    _drive(s)
    clean = s.export_state(8, 32)
    s.close()
    for root, _dirs, files in os.walk(path):
        for f in files:
            if f.endswith(".wal"):
                with open(os.path.join(root, f), "ab") as fh:
                    fh.write(b"\x7ftorn-garbage\x00\x01")
    s2 = LogStore(path, force_python=True)   # meta pins S=4
    try:
        assert s2.wal.n_shards == 4
        _exports_equal(clean, s2.export_state(8, 32))
    finally:
        s2.close()


def test_shard_meta_pins_layout(tmp_path):
    """Reopening with a different requested stripe count honors the
    pinned layout instead of silently reading a half-striped dir."""
    path = str(tmp_path / "pin")
    s = LogStore(path, force_python=True, shards=4)
    _drive(s)
    s.close()
    s2 = LogStore(path, force_python=True, shards=1)   # asks for flat
    try:
        assert s2.wal.n_shards == 4
        assert s2.tail(1) == 6   # 4 appended + the 2-entry span at 5
    finally:
        s2.close()


# --------------------------------------------------- off-thread checkpoints


def test_tick_thread_never_runs_save_checkpoint(tmp_path):
    """Tier-1 smoke for the off-thread checkpoint pool: under a fast
    maintain cadence, every archive save runs on a raft-ckpt worker —
    the tick thread only serializes machines and harvests completions."""
    cfg = EngineConfig(n_groups=4, n_peers=3, log_slots=32, batch=4,
                       max_submit=4, election_ticks=10, heartbeat_ticks=3,
                       rpc_timeout_ticks=8)
    c = LocalCluster(
        cfg, str(tmp_path), provider_factory=NullProvider,
        maintain_factory=lambda: MaintainAgreement(
            cfg.n_groups, state_change_threshold=1, dirty_log_tolerance=1,
            snap_min_interval=1, compact_min_interval=1, compact_slack=1),
        pipeline=True)
    tick_thread = threading.get_ident()
    saver_threads = []
    try:
        for node in c.nodes.values():
            orig = node.archive.save_checkpoint

            def spy(g, src, idx, term, _orig=orig):
                saver_threads.append(threading.get_ident())
                return _orig(g, src, idx, term)
            node.archive.save_checkpoint = spy
        c.wait_leader(0)
        for _ in range(40):
            for g in range(cfg.n_groups):
                lead = c.leader_of(g)
                if lead is not None and c.nodes[lead].is_ready(g):
                    c.nodes[lead].submit(g, b"x" * 16)
            c.tick(1)
        taken = sum(n.metrics["snapshots_taken"] for n in c.nodes.values())
        assert taken > 0, "no checkpoints ran — smoke is vacuous"
        assert saver_threads, "save_checkpoint spy never fired"
        assert tick_thread not in set(saver_threads), \
            "tick thread performed a synchronous save_checkpoint"
    finally:
        c.close()


# -------------------------------------------------- durable-tail feedback lane


def test_fused_scan_durable_lag_still_commits():
    """The in-scan model of the pipeline's durability barrier: with
    ``durable_lag=True`` every node's own commit-quorum match is clamped
    to the previous tick's tail, and the cluster still elects and commits
    (one tick later at worst)."""
    import jax.numpy as jnp

    from rafting_tpu.core.cluster import DeviceCluster
    from rafting_tpu.core.sim import committed_entries, run_cluster_ticks
    from rafting_tpu.core.types import Messages, StepInfo, init_state

    cfg = EngineConfig(n_groups=16, n_peers=3, log_slots=64, batch=8,
                       max_submit=4, election_ticks=10, heartbeat_ticks=3,
                       rpc_timeout_ticks=8)
    import jax
    states = jax.vmap(lambda i: init_state(cfg, i, seed=7))(
        jnp.arange(3, dtype=jnp.int32))
    inflight = jax.vmap(lambda _: Messages.empty(cfg))(jnp.arange(3))
    info = jax.vmap(lambda _: StepInfo.empty(cfg))(jnp.arange(3))
    conn = jnp.ones((3, 3), bool)
    submit = jnp.full((3, cfg.n_groups), 2, jnp.int32)

    states, inflight, info = run_cluster_ticks(
        cfg, 120, states, inflight, info, conn, submit,
        None, True)   # durable_lag=True
    committed = int(committed_entries(states))
    assert committed > 0, "no commits under the durable-lag barrier"
    # Commit never outruns the log tail (the barrier cannot break the
    # basic commit<=tail invariant).
    assert bool((np.asarray(states.commit)
                 <= np.asarray(states.log.last)).all())


def test_pipeline_serial_convergence(tmp_path):
    """The pipelined and serial runtimes drive the same workload to the
    same applied outcome (the pipeline reorders WORK, never effects)."""
    results = {}
    for mode in (True, False):
        root = str(tmp_path / f"m{int(mode)}")
        c = LocalCluster(CFG, root, provider_factory=NullProvider,
                         seed=3, pipeline=mode)
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
            results[mode] = [f.result(timeout=1) for f in futs]
        finally:
            c.close()
    assert results[True] == results[False]
