"""Waiting reads of a group share one ReadIndex barrier (runtime/node.py
``_dispatch``): the history clients see stays linearizable.

One group, three full node runtimes, many client threads of SINGLE reads
and writes through all three members (a follower's stub forwards), under
the two nemesis schedules that attack a read barrier — partitions (a cut
leader must not answer from what it last knew) and clock stalls (the
lease's designated adversary) — with the lease on and off.  Every read of
a tick rides one stamp here, so a stamp taken for a read that had not yet
been invoked, or served before its barrier, would show as a stale read;
the Wing & Gong checker (testkit/linz.py) judges the recorded history
against the sequential model, and the KV machine's ``stale_reads`` knob
shows that it would be caught.
"""

import os

import pytest

from rafting_tpu.core.types import EngineConfig
from rafting_tpu.machine.kv_machine import KVMachineProvider
from rafting_tpu.testkit import linz
from rafting_tpu.testkit.chaos import ChaosConductor, KVWorkload, plan_chaos
from rafting_tpu.testkit.harness import LocalCluster
from rafting_tpu.testkit.history import History

SCHEDULES = {"partition": {"part": 1.0}, "clock-stall": {"stall": 1.0}}


def _run(tmp_path, mix, lease, stale_reads=False, seed=11):
    cfg = EngineConfig(n_groups=2, n_peers=3, log_slots=64, batch=8,
                       max_submit=8, election_ticks=10, heartbeat_ticks=2,
                       rpc_timeout_ticks=6, read_lease=lease)
    root = str(tmp_path)
    cluster = LocalCluster(
        cfg, root, seed=seed,
        provider_factory=lambda i: KVMachineProvider(
            os.path.join(root, f"node{i}", "kv"), stale_reads=stale_reads))
    try:
        cluster.wait_leader(1)
        history = History()
        events = plan_chaos(cfg.n_peers, 130, seed=seed, period=14, mix=mix,
                            max_dur=8)
        conductor = ChaosConductor(cluster, events)
        # Nine clients, three on each member, six of ten operations reads:
        # several reads wait in every tick.  Many keys: the barrier is the
        # group's, whatever the key, and a write of unknown outcome (a
        # leader cut off mid-commit) stays concurrent with everything
        # after it on ITS key, so the checker's search is exponential in
        # such writes per key, not per run.
        load = KVWorkload(cluster, history, group=1, clients=9, seed=seed,
                          regs=24, lists=8, read_ratio=0.6, op_timeout=4.0)
        load.start()
        conductor.run(extra_ticks=30, tick_sleep=0.004)
        load.stop()
        load.join(tick_fn=conductor.step)
        conductor.finish()
        assert [ev for ev in conductor.applied if "error" not in ev
                and ev["kind"] in ("part", "stall")], conductor.applied
        nodes = cluster.nodes.values()
        return history, \
            sum(n.metrics["reads_coalesced"] for n in nodes), \
            sum(n.metrics["read_barriers"] for n in nodes)
    finally:
        cluster.close()


def _tractable(history, most=10):
    """The search is exponential in a key's writes of unknown outcome: say
    so, instead of searching for an hour, should a run ever pile them up."""
    unknown = {}
    for op in history.ops():
        if op.status == "info" and op.kind != "r":
            unknown[op.key] = unknown.get(op.key, 0) + 1
    assert max(unknown.values(), default=0) <= most, unknown


@pytest.mark.parametrize("lease", [True, False], ids=["lease", "readindex"])
@pytest.mark.parametrize("schedule", sorted(SCHEDULES))
def test_shared_barriers_stay_linearizable(tmp_path, schedule, lease):
    history, coalesced, barriers = _run(tmp_path, SCHEDULES[schedule], lease)
    counts = history.counts()
    assert counts["ok"] >= 50, f"workload starved: {counts}"
    assert barriers > 0 and coalesced > 0, \
        "no read ever shared a barrier: the test exercised nothing"
    _tractable(history)
    res = linz.check(history)
    assert res.ok, res.render()


def test_stale_answers_under_a_shared_barrier_are_caught(tmp_path):
    """The control: with the machine answering reads one value behind,
    the same run is NOT linearizable and the checker says which read."""
    # (clock stalls, not partitions: a partition leaves writes of unknown
    # outcome, and the search for a MINIMAL counterexample among those is
    # exponential in them)
    history, coalesced, _ = _run(tmp_path, SCHEDULES["clock-stall"], True,
                                 stale_reads=True)
    assert coalesced > 0
    res = linz.check(history)
    assert not res.ok and res.counterexample
