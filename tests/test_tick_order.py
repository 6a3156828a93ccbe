"""The one tick order (runtime/node.py ``tick``: dispatch, fetch, host
phase behind its own fsync barrier), its crash window, the rule a loop
starts an arrival step by, the sharded WAL's recovery parity, the
off-thread checkpoint pool, and the durable-tail feedback lane in the
fused scan.

The load-bearing invariant throughout: no submit future completes, and no
frame leaves the node, for a log range that has not been fsynced
(RaftNode.tick docstring; core/types.py HostInbox.durable_tail)."""

import os
import shutil
import threading

import numpy as np
import pytest

from rafting_tpu.core.types import EngineConfig
from rafting_tpu.log.store import LogStore, restore_raft_state
from rafting_tpu.log.wal import native_available
from rafting_tpu.runtime.node import SETTLE_MARGIN, arrival_step_at
from rafting_tpu.snapshot.policy import MaintainAgreement
from rafting_tpu.testkit.fixtures import NullProvider
from rafting_tpu.testkit.harness import LocalCluster, wal_store_factory

CFG = EngineConfig(n_groups=4, n_peers=3, log_slots=32, batch=4,
                   max_submit=4, election_ticks=10, heartbeat_ticks=3,
                   rpc_timeout_ticks=8)

SHAPES = ["packed", "columns"]
ENGINES = ["python", pytest.param("native", marks=pytest.mark.skipif(
    not native_available(), reason="no native WAL toolchain"))]


def _spy_sends(c: LocalCluster):
    """Count `transport.send_slice` calls per (source, peer); the caller
    clears the Counter between rounds."""
    from collections import Counter
    sends = Counter()
    for i, node in c.nodes.items():
        def spy(peer, blob, _i=i, _orig=node.transport.send_slice):
            sends[(_i, peer)] += 1
            return _orig(peer, blob)
        node.transport.send_slice = spy
    return sends


def _spy_events(node, events: list) -> None:
    """Log a node's completed barriers and the frames it hands out."""
    barrier_ok, sends = node._barrier_ok, node.transport.send_slice
    node._barrier_ok = lambda: (events.append(("barrier",)),
                                barrier_ok())[1]
    node.transport.send_slice = lambda p, blob: (
        events.append(("send", p)), sends(p, blob))[1]


# ---------------------------------------------------------------- crash window


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("shape", SHAPES)
def test_crash_between_dispatch_and_fsync_completes_nothing(
        tmp_path, take_shape, shape, engine):
    """Kill the node between a tick's dispatch and that tick's fsync:
    the scan accepted entries, the host phase (WAL staging + fsync) has
    NOT run; the window is the instant between fetch and the tick's own
    host phase.  The crash image must recover to the pre-accept durable
    tail, no submit future may have completed for the un-fsynced range,
    and nothing completes and NO frame leaves (AppendEntries included)
    between the tick's dispatch and its barrier.  The same whichever step
    the shape takes and whether the fsync is the Python barrier's or the
    native call's."""
    take_shape(CFG, shape)
    c = LocalCluster(CFG, str(tmp_path),
                     store_factory=wal_store_factory(str(tmp_path), engine, 2))
    try:
        lead = c.wait_leader(0)
        c.tick(5)
        node = c.nodes[lead]
        assert node.store.can_stage_native == (engine == "native")
        tail_before = int(node._durable_tail_m[0])
        img = str(tmp_path / "crash-img")

        fut = node.submit_batch(0, [b"crash-%d" % k for k in range(3)])
        events, seen = [], {}
        fut.add_done_callback(lambda f: events.append(("done",)))
        _spy_events(node, events)
        dispatch, host_phase = node._dispatch, node._host_phase

        def dispatched(*a):
            events.append(("dispatch",))
            return dispatch(*a)

        def crash_window(ctx):
            acc = int(np.asarray(ctx.info.submit_acc)[0])
            if acc and not seen:
                # Fetched, not yet staged or fsynced: the crash window.
                events.append(("accepted",))
                seen.update(
                    acc=acc, done=fut.done(),
                    start=int(np.asarray(ctx.info.submit_start)[0]),
                    tail=int(node._durable_tail_m[0]))
                shutil.copytree(os.path.join(node.data_dir, "wal"), img)
            return host_phase(ctx)
        node._dispatch, node._host_phase = dispatched, crash_window

        # One lockstep round: the leader's scan accepts the batch and its
        # host phase has run by now, behind its own barrier.
        c.tick(1)
        assert seen["acc"] == 3, f"device should have accepted the batch: {seen}"
        start, acc = seen["start"], seen["acc"]

        # The un-fsynced range was not acknowledged in any way.
        assert not seen["done"]
        assert seen["tail"] == tail_before
        # Around the window: nothing left the node between the tick's
        # dispatch and the accept, the tick's own barrier came next, and
        # the future (which needs a quorum anyway) resolves only after it.
        at = events.index(("accepted",))
        assert events[at - 1] == ("dispatch",), events
        assert events[at + 1] == ("barrier",), events
        assert any(e[0] == "send" for e in events[at + 2:]), events
        assert int(node._durable_tail_m[0]) >= start + acc - 1

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
        assert events.index(("done",)) > events.index(("barrier",))
    finally:
        c.close()


# ------------------------------------------- when a loop steps for arriving work

# (now, ended, took, due, cost) -> at.  The first ten are the room rule
# alone (the last step long over: no gap), the other nine the gap and the
# room together.
_LONG_AGO = -1e3


@pytest.mark.parametrize("now,ended,took,due,cost,at", [
    (10.0, _LONG_AGO, 0.0, None, 0.0, None),      # no loop: no deadline
    (10.0, _LONG_AGO, 0.0, None, 0.001, None),
    (10.0, _LONG_AGO, 0.0, 10.5, 0.0, 10.0),      # nothing measured yet
    (10.03, _LONG_AGO, 0.0, 10.5, 0.007, 10.03),  # a cell: 7 ms of 470 left
    (10.2, _LONG_AGO, 0.0, 11.0, 0.1, 10.2),      # tick_ms 1000, 100 ms step
    (10.04, _LONG_AGO, 0.0, 10.1, 0.05, None),    # fits once, not the margin
    (10.04, _LONG_AGO, 0.0, 10.1, 0.03, 10.04),   # fits the margin exactly
    (10.0, _LONG_AGO, 0.0, 10.5, 0.6, None),      # a step longer than a period
    (10.6, _LONG_AGO, 0.0, 10.5, 0.0, None),      # already past the timer
    (10.5, _LONG_AGO, 0.0, 10.5, 0.0, 10.5),
    (10.0, 9.9, 0.008, None, 0.008, None),        # no loop, no step
    (10.0, 9.999, 0.008, 10.15, 0.008, 10.007),   # inside the gap: wait it out
    (10.02, 9.999, 0.008, 10.15, 0.008, 10.02),   # gap passed, room: now
    (10.14, 9.999, 0.008, 10.15, 0.008, None),    # gap passed, no room
    (10.0, 9.999, 0.14, 10.15, 0.008, None),      # the gap ends past the room
    (10.0, 9.0, 0.19, 11.0, 0.19, 10.0),          # 100,000 lanes, 1 s period
    (10.7, 9.0, 0.19, 11.0, 0.19, None),          # ... late in the period
    (10.0, 9.9, 0.6, 10.5, 0.6, None),            # a step as long as a period
    (10.134, 9.0, 0.0, 10.15, 0.008, 10.134),     # fits the margin exactly
], ids=["room:no-deadline", "room:no-deadline-cost", "room:first-tick",
        "room:cell", "room:one-second-tick", "room:fits-once",
        "room:fits-margin", "room:over-period", "room:late",
        "room:on-the-dot",
        "no-deadline", "in-gap", "now", "no-room", "gap-past-room",
        "big-node", "big-node-late", "over-period", "on-the-dot"])
def test_arrival_step_at_on_made_up_readings(now, ended, took, due, cost,
                                             at):
    """When to step for waiting work is a function of what the loop
    observes of itself: the gap its last step leaves, the room before
    its timer.  No clock is read, no node is built, no size is asked."""
    got = arrival_step_at(now, ended, took, due, cost)
    assert got == pytest.approx(at) if at is not None else got is None
    start = max(now, ended + took)
    if got is not None:
        # Never before the gap is over, never in the past, and the margin
        # is the whole of the room rule.
        assert got == start
        assert got + SETTLE_MARGIN * cost <= due
        # More room never takes a step away.
        assert arrival_step_at(now, ended, took, due + 1.0, cost) == got
    elif due is not None:
        assert start + SETTLE_MARGIN * cost > due


def test_arrival_steps_leave_half_the_time_to_others():
    """The gap rule, run forward: a node that always has work waiting and
    always has room steps, waits its own step's length, steps again:
    half of the time at most, whatever a step costs."""
    for cost in (0.002, 0.008, 0.19):
        t, busy, ended, due = 0.0, 0.0, 0.0, 1e9
        for _ in range(100):
            at = arrival_step_at(t, ended, cost, due, cost)
            t = at + cost
            busy += cost
            ended = t
        assert busy / t <= 0.5 + 1e-9


CFG_HB1 = EngineConfig(n_groups=4, n_peers=3, log_slots=32, batch=4,
                       max_submit=4, election_ticks=10, heartbeat_ticks=1,
                       rpc_timeout_ticks=8)


def _rounds_until(c, fut, limit=40) -> int:
    for r in range(1, limit + 1):
        c.tick(1)
        if fut.done():
            return r
    raise AssertionError(f"not done in {limit} rounds")


@pytest.mark.parametrize("shape", SHAPES)
def test_a_write_is_acknowledged_in_three_ticks_and_a_lease_read_in_one(
        tmp_path, monkeypatch, take_shape, shape):
    """Nothing of a tick waits for the next: a write is acknowledged
    within 3 lock-step rounds of its offer (accept, the followers'
    acknowledgements, the commit) and a lease read is served by the tick
    that stamps it, whichever step the shape takes."""
    from rafting_tpu.utils.latency import OFFERED, SERVED
    monkeypatch.setenv("RAFT_LAT_SAMPLE", "1")
    take_shape(CFG_HB1, shape)
    c = LocalCluster(CFG_HB1, str(tmp_path), provider_factory=NullProvider,
                     seed=3)
    try:
        lead = c.wait_leader(0)
        node = c.nodes[lead]
        c.tick_until(lambda: node.is_ready(0), what="leader ready")
        c.tick(6)
        took = _rounds_until(c, node.submit(0, b"fast"))
        assert took <= 3, f"a write took {took} ticks"

        rd = node.read(0, b"q")
        assert _rounds_until(c, rd) == 1
        c.tick(2)           # retired spans are harvested at a tick's tail
        sp = max((sp for sp in node._lat.recent if sp.kind == "r"),
                 key=lambda sp: sp.seq)
        assert sp.outcome == "ok"
        assert sp.n[SERVED] == sp.n[OFFERED], \
            "the read was not served by the tick that stamped it"
    finally:
        c.close()


@pytest.mark.parametrize("step", ["timer-step", "arrival-step"])
@pytest.mark.parametrize("shape", SHAPES)
def test_one_slice_per_peer_per_tick(tmp_path, take_shape, shape, step):
    """The peers' inbox accumulators drain one slice per source per tick:
    a step hands each peer at most one, whichever step the shape takes
    and whether the step is the timer's or one started for arriving work
    (``tick(arrival=True)``: a whole tick that leaves the clock alone)."""
    take_shape(CFG, shape)
    c = LocalCluster(CFG, str(tmp_path), provider_factory=NullProvider,
                     seed=3)
    try:
        lead = c.wait_leader(0)
        node = c.nodes[lead]
        c.tick_until(lambda: node.is_ready(0), what="leader ready")
        c.tick(2)
        sends = _spy_sends(c)
        total, futs = 0, []
        for r in range(12):
            futs.append(node.submit_batch(0, [b"s%d" % r]))
            sends.clear()
            arrival = step == "arrival-step" and r % 3 != 0
            clocks = [n.timer_ticks for n in c.nodes.values()]
            for n in c.nodes.values():
                n.tick(arrival=arrival)
            assert [n.timer_ticks for n in c.nodes.values()] == \
                [t + (not arrival) for t in clocks]
            assert max(sends.values(), default=0) <= 1, (r, dict(sends))
            total += sum(sends.values())
        assert total >= 12
        c.tick(4)
        assert all(f.done() and f.exception() is None for f in futs)
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
            snap_min_interval=1, compact_min_interval=1, compact_slack=1))
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
    """The in-scan model of a durability barrier that lags a tick: with
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
