"""The carried lease through the whole runtime: three containers over
localhost TCP on TiKV's cadence (a heartbeat every second period, an election
timeout of ten) serve reads and writes that agree with a sequential model,
most reads by the lease and some of them on evidence of an earlier period;
and the node's own part of the lease's proof (core/step.py phase 6b): the
veto of a leader whose last ``lease_ticks`` periods ran more than one period
late in sum, and the pre-vote hold of a node that restarts with a term on
disk."""

import json
import time

import numpy as np
import pytest

from rafting_tpu.api import RaftConfig, RaftContainer
from rafting_tpu.core.types import EngineConfig
from rafting_tpu.testkit.harness import LocalCluster, free_ports, kv_factory


def _cmd(op, k, v=None):
    d = {"op": op, "k": k}
    if v is not None:
        d["v"] = v
    return json.dumps(d)


def test_three_containers_on_a_two_tick_heartbeat_ride_the_carried_lease(
        tmp_path):
    ports = free_ports(3)
    uris = [f"raft://127.0.0.1:{p}" for p in ports]
    cs = [RaftContainer(RaftConfig(
        local=u, peers=tuple(p for p in uris if p != u), n_groups=8,
        log_slots=32, batch=4, max_submit=4, tick_ms=150, seed=5,
        heartbeat_mul=2.0, election_mul=10.0,
        data_dir=str(tmp_path / f"node{i}")), kv_factory()).create()
        for i, u in enumerate(uris)]
    try:
        assert cs[0].node.cfg.lease_carry_ticks == 1
        names = ["kv0", "kv1", "kv2"]
        for c in cs:
            for i, name in enumerate(names):
                assert c.open_context(name) == i + 1
        stubs = [[c.get_stub(name) for name in names] for c in cs]
        model = {}
        rng = np.random.default_rng(39)
        # A write a group first (and the election with it), then reads
        # and writes spread over a dozen periods: half the reads arrive in
        # a period in which their lane hears no heartbeat round.
        for g in range(3):
            assert stubs[0][g].execute(_cmd("set", "k0", "first"),
                                       timeout=60) == "first"
            model[(g, "k0")] = "first"
        for i in range(150):
            g, k = int(rng.integers(3)), f"k{int(rng.integers(4))}"
            stub = stubs[i % 3][g]
            if rng.random() < 0.3:
                v = f"v{i}"
                assert stub.execute(_cmd("set", k, v), timeout=30) == v
                model[(g, k)] = v
            else:
                got = stub.execute_read(_cmd("get", k), timeout=30)
                assert got == model.get((g, k)), (i, g, k, got)
            time.sleep(0.012)
        m = lambda k: sum(int(c.node.metrics[k]) for c in cs)
        served, hits = m("reads_served"), m("read_lease_hits")
        carried, kicks = m("read_lease_carried"), m("read_kicks")
        assert served >= 90
        assert hits / served > 0.8, (hits, served, kicks, m("read_vetoes"))
        assert 0 < carried <= hits
        for row in stubs:
            for s in row:
                s.close()
    finally:
        for c in cs:
            c.destroy()


def test_the_veto_counts_lateness_over_the_lease_not_one_gap(tmp_path,
                                                             monkeypatch):
    """``_hold_read_veto`` under a loop: gaps that each pass the old rule
    (none longer than read_fresh_ticks periods) still veto once the last
    ``lease_ticks`` timer steps took more than ``lease_ticks + 1``
    periods; on time they never do.  Where the lease is not carried (a
    1-tick heartbeat) the same lateness vetoes nothing."""
    import rafting_tpu.runtime.node as node_mod

    for hb, vetoed in ((2, True), (1, False)):
        cfg = EngineConfig(n_groups=4, n_peers=3, election_ticks=10,
                           heartbeat_ticks=hb)
        lc = LocalCluster(cfg, str(tmp_path / f"hb{hb}"), seed=3)
        try:
            node = lc.nodes[0]
            node._tick_interval = 1.0          # as under start(): a period
            clock = [100.0]
            monkeypatch.setattr(node_mod.time, "monotonic",
                                lambda: clock[0])
            for _ in range(12):                 # on time: a period apart
                clock[0] += 1.0
                assert node._hold_read_veto(arrival=False) is False
                clock[0] += 0.3                 # and an arrival step
                assert node._hold_read_veto(arrival=True) is False
                clock[0] -= 0.3
            assert node.metrics["read_vetoes"] == 0
            seen = []
            for _ in range(cfg.lease_ticks):    # each 1.6 periods: no gap
                clock[0] += 1.6                 # of 3; four of them are 6.4
                seen.append(node._hold_read_veto(arrival=False))
            assert any(seen) == vetoed
            assert (node.metrics["read_vetoes"] > 0) == vetoed
        finally:
            monkeypatch.undo()
            lc.close()


def test_a_node_that_restarts_with_a_term_holds_its_pre_vote(tmp_path):
    """``restore_raft_state``: a lane that recovers a term comes back with
    ``vote_hold`` = ``lease_hold_ticks`` (its clock restarts at 0), a
    first boot with none, so a set-up elects as fast as it did."""
    cfg = EngineConfig(n_groups=4, n_peers=3, election_ticks=10,
                       heartbeat_ticks=2)
    lc = LocalCluster(cfg, str(tmp_path), seed=3)
    try:
        for n in lc.nodes.values():
            assert not np.asarray(n.state.lease.vote_hold).any()
        for g in range(cfg.n_groups):
            lc.wait_leader(g)
        lc.tick(4)
        lc.kill_node(1)
        node = lc.restart_node(1)
        hold = np.asarray(node.state.lease.vote_hold)
        term = np.asarray(node.state.term)
        assert (term > 0).all() and (hold == cfg.lease_hold_ticks).all()
        assert int(node.state.now) == 0
    finally:
        lc.close()
