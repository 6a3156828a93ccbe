"""Jepsen-style chaos plane, end to end: seeded mixed-nemesis timelines
(testkit/chaos.py) over real node runtimes, client histories recorded
through RaftStub (testkit/history.py), verdicts from the Wing & Gong
checker (testkit/linz.py).

Tier-1 keeps a short smoke (lease reads on AND strict ReadIndex), the
byte-for-byte timeline replay pin, and the checker-has-teeth test (the
KV machine's injected stale-read defect must produce a minimal
counterexample through the REAL read plane).  The long TCP soak and the
real-process SIGKILL schedule are ``slow``."""

import json
import os

import pytest

from rafting_tpu.core.types import EngineConfig
from rafting_tpu.machine.kv_machine import KVMachineProvider
from rafting_tpu.testkit import linz
from rafting_tpu.testkit.chaos import (
    ChaosConductor, KVWorkload, ProcCluster, plan_chaos, timeline_json)
from rafting_tpu.testkit.harness import LocalCluster
from rafting_tpu.testkit.history import History
from rafting_tpu.testkit.logcheck import check_logs

# Same engine shape as tests/test_runtime_chaos.py so the jit cache is
# shared across the suite's chaos tier.
CFG_KW = dict(n_groups=3, n_peers=3, log_slots=64, batch=8, max_submit=8,
              election_ticks=10, heartbeat_ticks=3, rpc_timeout_ticks=8)
GROUP = 1


def _mk_cluster(tmp_path, lease=True, stale=False, seed=0,
                transport="loopback", **shape):
    cfg = EngineConfig(read_lease=lease, **{**CFG_KW, **shape})
    root = str(tmp_path)
    return LocalCluster(
        cfg, root, seed=seed,
        provider_factory=lambda i: KVMachineProvider(
            os.path.join(root, f"node{i}", "kv"), stale_reads=stale),
        transport=transport)


def _soak(cluster, seed, ticks, clients=3, tick_sleep=0.002):
    for g in range(cluster.cfg.n_groups):
        cluster.wait_leader(g)
    history = History()
    events = plan_chaos(cluster.cfg.n_peers, ticks, seed=seed,
                        churn_group=GROUP)
    conductor = ChaosConductor(cluster, events)
    load = KVWorkload(cluster, history, group=GROUP, clients=clients,
                      seed=seed)
    load.start()
    conductor.run(extra_ticks=40, tick_sleep=tick_sleep)
    load.stop()
    load.join(tick_fn=conductor.step)
    conductor.finish()
    return history, conductor


def _assert_replicas_converge(cluster, group=GROUP, rounds=800):
    """All live replicas' KV machines reach the same state once the world
    is healed and the apply frontier catches up."""
    def datas():
        return [cluster.nodes[i].dispatcher.machine(group).data
                for i in sorted(cluster.nodes)]

    def converged():
        d = datas()
        return all(x == d[0] for x in d)
    cluster.tick_until(converged, rounds, "replica KV convergence")


def test_timeline_replay_byte_for_byte():
    """The replayability pin: one seed, one timeline — byte for byte."""
    a = timeline_json(plan_chaos(3, 400, seed=11))
    b = timeline_json(plan_chaos(3, 400, seed=11))
    assert a == b and a.encode() == b.encode()
    assert a != timeline_json(plan_chaos(3, 400, seed=12))
    events = plan_chaos(3, 400, seed=11)
    kinds = {e.kind for e in events}
    # The mix really is mixed: network, process, clock, storage, churn.
    assert {"kill", "restart", "heal"} <= kinds
    assert kinds & {"asym_cut", "part", "flaky"}
    assert kinds & {"stall", "storage_delay"}
    assert kinds & {"churn_transfer", "churn_demote"}
    # Destructive events pair with their undo inside the horizon.
    kills = sum(1 for e in events if e.kind == "kill")
    restarts = sum(1 for e in events if e.kind == "restart")
    assert kills == restarts
    # JSON round-trip (what the artifact embeds) is stable too.
    assert timeline_json(events) == json.dumps(
        json.loads(a), sort_keys=True, separators=(",", ":"))


@pytest.mark.parametrize("lease", [True, False],
                         ids=["lease", "readindex"])
def test_mixed_nemesis_smoke_linearizable(tmp_path, lease):
    """The tier-1 acceptance run: asymmetric partitions + flaky links +
    crash/restart + clock stalls + slow storage + membership churn over
    a 3-node group, concurrent recorded clients, and the checker must
    find the history linearizable — with lease reads on and off."""
    cluster = _mk_cluster(tmp_path, lease=lease, seed=7)
    try:
        history, conductor = _soak(cluster, seed=7, ticks=120)
        assert conductor.applied, "no nemesis event ever applied"
        counts = history.counts()
        assert counts["ok"] >= 20, f"workload starved: {counts}"
        res = linz.check(history)
        assert res.ok, res.render()
        _assert_replicas_converge(cluster)
    finally:
        cluster.close()


def test_mixed_nemesis_linearizable_on_tikv_cadence(tmp_path):
    """The same acceptance run on TiKV's cadence (a heartbeat every second
    tick, an election timeout of ten), where a lane hears acknowledgements
    one tick in two and the lease is carried over the other (core/step.py
    phase 6b): partitions, restarts (a restarted follower holds its
    pre-vote), stalls (the conductor tells the node it slept), transfers
    (TimeoutNow ends the carry), and the history stays linearizable with
    reads really served from carried evidence."""
    cluster = _mk_cluster(tmp_path, lease=True, seed=13, heartbeat_ticks=2)
    assert cluster.cfg.lease_carry_ticks == 1
    try:
        history, conductor = _soak(cluster, seed=13, ticks=140)
        kinds = {a["kind"] for a in conductor.applied if "error" not in a}
        assert kinds & {"stall"} and kinds & {"kill"}, kinds
        assert kinds & {"churn_transfer"} and kinds & {"part", "asym_cut"}, kinds
        counts = history.counts()
        assert counts["ok"] >= 20, f"workload starved: {counts}"
        res = linz.check(history)
        assert res.ok, res.render()
        assert sum(int(n.metrics["read_lease_carried"])
                   for n in cluster.nodes.values()) > 0
        _assert_replicas_converge(cluster)
    finally:
        cluster.close()


def test_stale_read_bug_produces_minimal_counterexample(tmp_path):
    """The checker has teeth: arm the KV machine's stale-read defect
    (reads serve each key's PREVIOUS value) and drive real traffic
    through the real read plane — the checker must fail and shrink to a
    small counterexample, not wave the history through."""
    cluster = _mk_cluster(tmp_path, lease=True, stale=True, seed=5)
    try:
        history, _ = _soak(cluster, seed=5, ticks=60, clients=2)
        res = linz.check(history)
        assert not res.ok, "stale reads slipped past the checker"
        assert res.counterexample, "no counterexample produced"
        n_key_ops = sum(1 for o in history.ops() if o.key == res.key)
        assert len(res.counterexample) < max(4, n_key_ops), \
            "counterexample was not shrunk"
        assert "NON-LINEARIZABLE" in res.render()
    finally:
        cluster.close()


def test_conductor_audit_and_metrics_surface(tmp_path):
    """The audited timeline: every applied event lands in ``applied`` in
    tick order, fault counters mirror onto the nodes' /metrics families,
    and a heal drains held frames."""
    cluster = _mk_cluster(tmp_path, seed=3)
    try:
        for g in range(cluster.cfg.n_groups):
            cluster.wait_leader(g)
        events = (plan_chaos(3, 80, seed=3, churn_group=GROUP))
        conductor = ChaosConductor(cluster, events)
        conductor.run()
        conductor.finish()
        ticks = [a["t"] for a in conductor.applied]
        assert ticks == sorted(ticks)
        applied_kinds = {a["kind"] for a in conductor.applied
                         if "error" not in a}
        assert applied_kinds & {"asym_cut", "part", "flaky", "kill"}
        # Counter families pre-registered on every node's metrics.
        node = next(iter(cluster.nodes.values()))
        fams = node.metrics.render_prometheus()
        for name in ("net_faults_cut_total", "net_faults_dropped_total",
                     "net_faults_reordered_total"):
            assert name in fams
        # All nodes alive and led after finish().
        assert len(cluster.nodes) == 3
        for g in range(cluster.cfg.n_groups):
            assert cluster.leader_of(g) is not None
    finally:
        cluster.close()


@pytest.mark.slow
def test_chaos_soak_tcp_linearizable(tmp_path):
    """The full-plane soak: same mixed-nemesis timeline over REAL
    localhost TCP — sender threads run the injected-partition reconnect
    ladder, frames drop/dup/delay/reorder on the wire path."""
    cluster = _mk_cluster(tmp_path, lease=True, seed=13,
                          transport="tcp")
    try:
        history, conductor = _soak(cluster, seed=13, ticks=200,
                                   tick_sleep=0.005)
        assert conductor.applied
        res = linz.check(history)
        assert res.ok, res.render()
        counts = history.counts()
        assert counts["ok"] >= 20, f"workload starved: {counts}"
        _assert_replicas_converge(cluster)
    finally:
        cluster.close()


@pytest.mark.slow
def test_proc_cluster_seeded_sigkill_schedule(tmp_path):
    """Real OS processes under a seeded kill/restart schedule (the
    SIGKILL nemesis): continuous load keeps committing across hard
    kills, cold restarts recover from disk, and the machine files +
    offline WAL diff stay consistent."""
    pc = ProcCluster(tmp_path, n=3, groups=4)
    pc.start_all()
    try:
        pc.wait(lambda: all(pc.ready_count(i) >= 1 for i in range(3)),
                "all nodes READY", 240)
        lanes = set()
        for i in range(3):
            lanes.update(pc.ready_lanes(i))
        assert len(lanes) == 1
        lane = lanes.pop()
        pc.wait(lambda: pc.total_acked() >= 30,
                "initial load committed", 240)
        # Seeded kill/restart plan, interpreted in wall-clock seconds.
        events = plan_chaos(3, 40, seed=21, period=10,
                            mix={"kill": 1.0}, max_dur=8)
        assert any(e.kind == "kill" for e in events)
        applied = pc.run_kill_schedule(events, step_s=1.0)
        assert any(a["kind"] == "kill" for a in applied)
        for i in range(3):          # everyone back up
            if pc.procs[i].poll() is not None:
                pc.start(i)
        pc.wait(lambda: all(pc.procs[i].poll() is None
                            for i in range(3)), "all restarted", 60)
        base = pc.total_acked()
        pc.wait(lambda: pc.total_acked() >= base + 20,
                "progress after chaos", timeout=240)
        assert all(rc == 0 for rc in pc.sigterm_all())
    finally:
        pc.close()
    files = [pc.machine_lines(i, lane) for i in range(3)]
    assert max(len(f) for f in files) >= 30
    shortest = min(len(f) for f in files)
    assert shortest > 0
    for f in files:                 # prefix parity across replicas
        assert f[:shortest] == files[0][:shortest]
    divs = check_logs(pc.wal_dirs())
    assert divs == [], f"log divergence: {divs[:5]}"
