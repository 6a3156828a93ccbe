"""End-to-end node-runtime tests: full RaftNodes (device engine + WAL +
machines + snapshots) over loopback transport.

This is BASELINE config 1 — the reference's 3-node file-append system test
(test cluster/TestNode1-3, README.md:28-33) — as an in-process suite:
elect, submit, apply, kill/restart the leader, and check the byte-parity
oracle throughout."""

import os

import numpy as np
import pytest

from rafting_tpu.core.types import EngineConfig, LEADER
from rafting_tpu.runtime.node import NotLeaderError
from rafting_tpu.testkit.harness import LocalCluster

CFG = EngineConfig(n_groups=4, n_peers=3, log_slots=32, batch=4,
                   max_submit=4, election_ticks=10, heartbeat_ticks=3,
                   rpc_timeout_ticks=8)


@pytest.fixture
def cluster(tmp_path):
    c = LocalCluster(CFG, str(tmp_path))
    yield c
    c.close()


def test_elect_submit_apply_parity(cluster):
    c = cluster
    lead = c.wait_leader(0)
    # Submit through the leader; future completes with the apply result.
    res = c.submit_via_leader(0, b"hello-0")
    # FileMachine.apply returns the index; the election no-op (Raft §8,
    # step.py phase 3) occupies index 1, so the first command applies
    # right after it — equal to the machine's line count at that point.
    assert res == len(c.machine_lines(c.leader_of(0), 0))
    for k in range(1, 6):
        c.submit_via_leader(0, f"cmd-{k}".encode())
    c.tick(10)  # drain so followers apply too
    c.assert_file_parity(0)
    # All three nodes applied all 6 commands (no-ops excluded).
    for i in c.nodes:
        cmds = c.command_payloads(i, 0)
        assert len(cmds) == 6
        assert cmds[0] == "hello-0"


def test_not_leader_rejection(cluster):
    c = cluster
    lead = c.wait_leader(0)
    follower = next(i for i in c.nodes if i != lead)
    fut = c.nodes[follower].submit(0, b"nope")
    assert isinstance(fut.exception(timeout=1), NotLeaderError)


def test_leader_kill_failover_and_restart(cluster):
    c = cluster
    lead = c.wait_leader(0)
    for k in range(4):
        c.submit_via_leader(0, f"before-{k}".encode())
    c.tick(5)
    c.kill_node(lead)
    new_lead = c.wait_leader(0)
    assert new_lead != lead
    for k in range(4):
        c.submit_via_leader(0, f"after-{k}".encode())
    # Restart the crashed node: it must rejoin from its WAL and catch up.
    c.restart_node(lead)
    c.tick_until(
        lambda: len(c.command_lines(lead, 0)) == 8, 600,
        "restarted node catch-up")
    c.assert_file_parity(0)
    assert c.command_payloads(lead, 0) == \
        [f"before-{k}" for k in range(4)] + [f"after-{k}" for k in range(4)]


def test_multi_group_independence(cluster):
    c = cluster
    for g in range(CFG.n_groups):
        c.wait_leader(g)
    for g in range(CFG.n_groups):
        c.submit_via_leader(g, f"g{g}-x".encode())
    c.tick(10)
    for g in range(CFG.n_groups):
        c.assert_file_parity(g)
        lead = c.leader_of(g)
        assert c.command_payloads(lead, g) == [f"g{g}-x"]


def test_snapshot_install_catches_up_lagging_follower(tmp_path):
    """A follower that falls behind the leader's compaction floor must catch
    up via snapshot transfer + install (reference InstallSnapshot flow,
    context/RaftRoutine.java:408-541), then resume log replication."""
    from rafting_tpu.snapshot.policy import MaintainAgreement

    cfg = EngineConfig(n_groups=2, n_peers=3, log_slots=16, batch=4,
                       max_submit=4, election_ticks=10, heartbeat_ticks=3)
    aggressive = lambda: MaintainAgreement(
        cfg.n_groups, state_change_threshold=2, dirty_log_tolerance=1,
        snap_min_interval=2, compact_min_interval=2, compact_slack=2)
    c = LocalCluster(cfg, str(tmp_path), maintain_factory=aggressive)
    try:
        lead = c.wait_leader(0)
        victim = next(i for i in c.nodes if i != lead)
        c.kill_node(victim)
        victim_tail = len(c.machine_lines(victim, 0))
        # Push until the survivors' compaction floor passes the victim's
        # durable position — then log replication alone cannot catch it up.
        k = 0
        while k < 30 or not all(
                n.h_base[0] > victim_tail for n in c.nodes.values()):
            c.submit_via_leader(0, f"deep-{k}".encode())
            c.tick(3)
            k += 1
            assert k < 200, "compaction floor never passed victim tail"
        c.tick(30)  # let checkpoint + compaction cycles settle
        c.restart_node(victim)
        c.tick_until(
            lambda: len(c.machine_lines(victim, 0)) >= k,
            800, "snapshot catch-up")
        c.assert_file_parity(0)
        assert any(n.metrics["snapshots_installed"] > 0
                   for n in c.nodes.values()), \
            "catch-up happened without snapshot install"
    finally:
        c.close()


def test_wal_survives_full_cluster_restart(tmp_path):
    c = LocalCluster(CFG, str(tmp_path))
    try:
        c.wait_leader(0)
        for k in range(5):
            c.submit_via_leader(0, f"persist-{k}".encode())
        c.tick(10)
    finally:
        c.close()
    # Cold restart of all three nodes from disk.
    c2 = LocalCluster(CFG, str(tmp_path))
    try:
        c2.wait_leader(0)
        c2.tick(20)
        c2.assert_file_parity(0)
        # Logs recovered: the new submission applies as one more line
        # (index = line count incl. the elections' no-ops).
        res = c2.submit_via_leader(0, b"persist-5")
        assert res == len(c2.machine_lines(c2.leader_of(0), 0))
        assert c2.command_payloads(c2.leader_of(0), 0)[-1] == "persist-5"
    finally:
        c2.close()


def test_submit_batch_resolves_in_order(cluster):
    c = cluster
    lead = c.wait_leader(0)
    n = c.nodes[lead]
    c.tick_until(lambda: n.is_ready(0), 100, "leader ready")
    fut = n.submit_batch(0, [f"b-{k}".encode() for k in range(3)])
    c.tick_until(fut.done, 200, "batch committed")
    results = fut.result()
    assert results == sorted(results)  # consecutive indices, in order
    assert len(results) == 3
    c.tick(10)
    c.assert_file_parity(0)
    # Refusal taxonomy rides the single future.
    other = next(i for i in range(3) if i != lead)
    bad = c.nodes[other].submit_batch(0, [b"x"])
    assert isinstance(bad.exception(), NotLeaderError)
    empty = n.submit_batch(0, [])
    assert empty.result() == []


def test_submit_batch_fails_wholesale_on_stepdown(cluster):
    c = cluster
    lead = c.wait_leader(0)
    n = c.nodes[lead]
    c.tick_until(lambda: n.is_ready(0), 100, "leader ready")
    # Partition the leader so the batch cannot commit (a quorumless leader
    # keeps leading — correct Raft), then heal: the majority side has moved
    # to a higher term, the old leader steps down, and the whole batch
    # future fails with the abort error.
    c.net.partition([[lead], [i for i in range(3) if i != lead]])
    fut = n.submit_batch(0, [b"doomed-1", b"doomed-2"])
    c.tick(40)   # majority side elects a new leader at a higher term
    assert not fut.done()
    c.net.heal()
    c.tick_until(fut.done, 400, "batch aborted on step-down")
    from rafting_tpu.api.anomaly import BatchAbortedError
    err = fut.exception()
    assert isinstance(err, BatchAbortedError)
    # Nothing could commit through a quorumless leader: no slot completed,
    # and the cause is the step-down refusal.
    assert err.completed == [False, False]
    assert err.cause is not None


def test_empty_apply_skip_counter(tmp_path):
    """A machine WITHOUT the ``applies_empty`` opt-in (machine/spi.py)
    has election no-ops short-circuited around it — the dispatcher's
    ``empty_skips`` tally counts them and the runtime surfaces the sum
    as the ``empty_apply_skips`` gauge, so a lagging ``last_applied``
    stays diagnosable after the warn-once log line scrolled away."""
    from rafting_tpu.testkit.fixtures import NullMachine, NullProvider

    class OptedOutMachine(NullMachine):
        applies_empty = False

        def apply(self, index, payload):
            assert payload, "opted-out machine must never see b''"
            return super().apply(index, payload)

        def apply_batch(self, start_index, payloads):
            assert all(payloads)
            return super().apply_batch(start_index, payloads)

    class OptedOutProvider(NullProvider):
        def bootstrap(self, group):
            return OptedOutMachine()

    c = LocalCluster(CFG, str(tmp_path), provider_factory=OptedOutProvider)
    try:
        lead = c.wait_leader(0)
        node = c.nodes[lead]
        fut = node.submit(0, b"after-noop")
        for _ in range(60):
            c.tick(1)
            if fut.done():
                break
        assert fut.done()
        # The elected leader's §8 no-op committed and applied cluster-wide
        # without the machine seeing it.
        assert node.dispatcher.empty_skips > 0
        assert node.metrics._gauges.get("empty_apply_skips", 0) > 0
    finally:
        c.close()


def test_arrival_steps_maintain_only_a_pressed_ring(tmp_path):
    """Between two timer ticks a step passes maintenance by, unless a log
    ring under pressure asks: a group fed in arrival steps alone is
    checkpointed and compacted by pressure while the timer count, and
    with it every cadence, stands; an idle one is left alone."""
    import json

    from rafting_tpu.machine.kv_machine import KVMachineProvider

    cfg = EngineConfig(n_groups=2, n_peers=3, log_slots=64, batch=8,
                       max_submit=8, election_ticks=10, heartbeat_ticks=1)
    root = str(tmp_path)
    c = LocalCluster(cfg, root, provider_factory=lambda i: KVMachineProvider(
        os.path.join(root, f"kv{i}")))
    try:
        lead = c.wait_leader(0)
        node = c.nodes[lead]
        c.tick_until(lambda: node.is_ready(0), what="leader ready")
        passes = []
        run_pass = node._maintain_pass
        node._maintain_pass = lambda *a: passes.append(a[0]) or run_pass(*a)
        for _ in range(5):                      # idle arrival steps
            for n in c.nodes.values():
                n.tick(arrival=True)
        assert not passes
        timer0, sent, futs = node.timer_ticks, 0, []
        for t in range(60):                     # a ring that fills
            futs.append(node.submit_batch(0, [
                json.dumps({"op": "set", "k": f"k{j}",
                            "v": sent + j}).encode() for j in range(8)]))
            sent += 8
            for n in c.nodes.values():
                n.tick(arrival=True)
        assert node.timer_ticks == timer0
        assert passes and set(passes) == {timer0}
        m = node.metrics
        assert m["ckpt_by_pressure"] > 0 and m["compactions_by_pressure"] > 0
        assert int(node._durable_tail_m[0]) > cfg.log_slots, \
            "the ring never turned over"
        c.tick_until(lambda: all(f.done() for f in futs), 400,
                     "every batch acknowledged")
        assert all(f.exception() is None for f in futs)
    finally:
        c.close()


def test_a_group_that_never_rests_keeps_its_ring_moving(tmp_path):
    """One group fed ``max_submit`` entries a tick for 200 ticks under the
    policy as it ships (``MaintainAgreement`` defaults: a snapshot no
    sooner than 20 ticks after the last and only after 64 applied
    entries — the whole ring): the ring is released by pressure, so
    intake is never refused for more than a few ticks running, admission
    sheds nothing and the node never evacuates its only group.  Half way
    a follower is cut off until the leader has compacted past it; healed,
    it catches up by snapshot and the three machines agree."""
    import json

    from rafting_tpu.machine.kv_machine import KVMachineProvider

    cfg = EngineConfig(n_groups=2, n_peers=3, log_slots=64, batch=8,
                       max_submit=8, election_ticks=10, heartbeat_ticks=1)
    root = str(tmp_path)
    c = LocalCluster(cfg, root, provider_factory=lambda i: KVMachineProvider(
        os.path.join(root, f"kv{i}")))
    try:
        lead = c.wait_leader(0)
        node = c.nodes[lead]
        c.tick_until(lambda: node.is_ready(0), what="leader ready")
        victim = next(i for i in c.nodes if i != lead)
        S, sent, futs = cfg.max_submit, 0, []
        stuck = worst = 0
        for t in range(200):
            if t == 60:
                c.faults.isolate(victim)
            if t == 120:
                assert node.h_base[0] > c.nodes[victim].h_commit[0], \
                    "the leader never compacted past the cut follower"
                c.faults.heal()
            futs.append(node.submit_batch(0, [
                json.dumps({"op": "set", "k": f"k{(sent + j) % 50}",
                            "v": sent + j}).encode() for j in range(S)]))
            sent += S
            tail = int(node._durable_tail_m[0])
            c.tick()
            moved = int(node._durable_tail_m[0]) > tail
            stuck = 0 if moved else stuck + 1
            worst = max(worst, stuck)
        assert worst <= 3, f"intake refused {worst} ticks running"
        c.tick_until(lambda: all(f.done() for f in futs), 400,
                     "every batch acknowledged")
        assert all(f.exception() is None for f in futs)
        m = node.metrics
        assert m["ckpt_by_pressure"] > 0 and m["compactions_by_pressure"] > 0
        assert sum(n.metrics["leader_evacuations"]
                   for n in c.nodes.values()) == 0
        assert sum(n.metrics["admission_shed"]
                   for n in c.nodes.values()) == 0
        assert node.is_leader(0), "the leadership moved under load"
        machines = [n.dispatcher.machine(0) for n in c.nodes.values()]
        c.tick_until(lambda: len({x.last_applied() for x in machines}) == 1,
                     400, "the cut follower caught up")
        assert c.nodes[victim].metrics["snapshots_installed"] > 0
        assert machines[0].data == machines[1].data == machines[2].data
        assert machines[0].data["k49"] == sent - 1
    finally:
        c.close()
