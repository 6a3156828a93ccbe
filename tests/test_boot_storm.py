"""A store of some thousands of lanes boots: three containers over TCP, the
first alone for a while (as ``benchmark/cluster.py`` boots them, and as a
cold compile stretches it on the chip), every group opened from the nodes'
own registries, one election storm.  The set-up has to end the same way
every time: every open group led and routed on every member, nothing
evacuated and nothing shed on the strength of the storm or of the boot
order, and then the store serves.  ``multiraft-10k-3v`` (PERF.md, PR 31):
the parent's cold boot read its own 20 s alone as a flapping link
(``reconnects_total``) and evacuated eight groups it had just won."""

import json
import os
import time

from rafting_tpu.api import RaftConfig, RaftContainer
from rafting_tpu.machine.kv_machine import KVMachineProvider
from rafting_tpu.testkit.harness import free_ports, kv_factory

LANES = 2048
OPEN = LANES - 1            # lane 0 is @raft


def _wait(pred, what, timeout=120.0):
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, f"{what} not reached"
        time.sleep(0.1)


def test_boot_storm_ends_led_routed_unevacuated_and_serves(tmp_path):
    uris = [f"raft://127.0.0.1:{p}" for p in free_ports(3)]
    names = [f"g{i + 1:05d}" for i in range(OPEN)]
    cs = []
    try:
        for i, u in enumerate(uris):
            rc = RaftConfig(
                local=u, peers=tuple(p for p in uris if p != u),
                data_dir=str(tmp_path / f"node{i}"), seed=11,
                n_groups=LANES, tick_ms=100, heartbeat_mul=1.0,
                election_mul=10.0, log_slots=64, batch=8, max_submit=8,
                tick_stagger=True)
            os.makedirs(rc.data_dir)
            with open(os.path.join(rc.data_dir, "groups.json"), "w") as f:
                json.dump({n: [k + 1, True] for k, n in enumerate(names)}, f)
            cs.append(RaftContainer(rc, kv_factory(), admin=False).create())
            if i == 0:
                # The first member alone, its peers not listening yet:
                # two or three refused connects a peer (1 s, 2 s backoff).
                time.sleep(3.5)
        nodes = [c.node for c in cs]
        lanes = range(1, LANES)

        def ready():
            led = sum(any(n.is_leader(g) and n.is_ready(g) for n in nodes)
                      for g in lanes)
            routed = min(sum(n.is_active(g) and n.leader_hint(g) is not None
                             for g in lanes) for n in nodes)
            return led == OPEN and routed == OPEN

        _wait(ready, "every open group led and routed")
        for n in nodes:
            m = n.metrics
            assert m["leader_evacuations"] == 0
            assert m["admission_shed"] == 0
            assert m["reconnects_total"] == 0
            # Nothing of the boot scores against the node itself; a slow
            # fsync of a busy test host may (one point a slow barrier).
            assert m._gauges["health_self_score"] <= m["slow_io_ticks"]
            assert m._gauges["groups_leaderless"] == 0
        assert nodes[0].metrics["connects_refused_total"] >= 2
        # One storm: hardly more than an election a group.
        assert sum(n.metrics["elections"] for n in nodes) <= 1.5 * OPEN
        # Then it serves: a write through one member, read back through
        # every member (two of them forward).
        g = 1234
        v = cs[0].get_stub(names[g]).execute(
            json.dumps({"op": "set", "k": "a", "v": "1"}), timeout=30)
        assert v == "1"
        for c in cs:
            got = c.get_stub(names[g]).execute_read(
                json.dumps({"op": "get", "k": "a"}), timeout=30)
            assert got == "1"
        assert sum(n.metrics["leader_evacuations"] for n in nodes) == 0
    finally:
        for c in cs:
            c.destroy()


def test_a_new_groups_machine_probes_nothing_and_an_old_one_is_found(
        tmp_path, monkeypatch):
    """``KVMachineProvider`` lists its root once: the machine of a group
    with no file there is built without a probe of the directory or the
    file (two round trips a machine on a network filesystem, 10,000
    machines a node in one storm); a file that is there, or that an
    earlier machine of the same provider saved, is loaded."""
    from rafting_tpu.machine import kv_machine

    root = str(tmp_path / "machines")
    provider = KVMachineProvider(root)

    def no_probe(*a, **kw):
        raise AssertionError("a machine known absent probed the disk")

    with monkeypatch.context() as m:
        m.setattr(kv_machine.os.path, "exists", no_probe)
        m.setattr(kv_machine.os, "makedirs", no_probe)
        first = provider.bootstrap(7)
    assert first.last_applied() == 0 and first.data == {}
    first.apply(1, b"")
    first.apply(2, json.dumps({"op": "set", "k": "a", "v": 1}).encode())
    first.close()
    # The same provider again (a lane purged and re-made, a dispatcher that
    # dropped its machine): what the first machine saved is found.
    again = provider.bootstrap(7)
    assert again.last_applied() == 2 and again.data == {"a": 1}
    # A new provider (a restart) lists the file and loads it.
    restarted = KVMachineProvider(root).bootstrap(7)
    assert restarted.last_applied() == 2 and restarted.data == {"a": 1}


def test_close_saves_what_a_command_changed_and_nothing_else(tmp_path):
    """A machine that only ever saw election no-ops has nothing to save
    (the log replays them); one that a command changed is saved, once."""
    provider = KVMachineProvider(str(tmp_path / "machines"))
    idle = provider.bootstrap(1)
    idle.apply(1, b"")
    idle.close()
    assert not os.path.exists(idle.path)
    assert provider.bootstrap(1).last_applied() == 0     # replayed from 1
    busy = provider.bootstrap(2)
    busy.apply(1, b"")
    busy.apply(2, json.dumps({"op": "set", "k": "k", "v": "v"}).encode())
    busy.close()
    stamp = os.stat(busy.path).st_mtime_ns
    loaded = provider.bootstrap(2)
    assert loaded.data == {"k": "v"} and loaded.last_applied() == 2
    loaded.apply(3, b"")
    loaded.close()                  # nothing a command changed since
    assert os.stat(busy.path).st_mtime_ns == stamp
