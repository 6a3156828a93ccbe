"""The election storm of a store whose lanes all open at once
(``multiraft-100k-3v``, PERF.md PR 34; here at 2,048 lanes on the CPU).

Served: three containers over TCP, started together, every lane re-opened
from the nodes' own registries before the first step, so that every group's
election timer runs out in the same few periods.  The storm has to end
inside a stated number of periods of the engine's clock with every group led
and routed, nothing evacuated, nothing shed and no slice refused at the
inbox's bound, whatever a storm step costs the host.

Staged: what a storm step (election no-ops, (term, ballot) records, adopted
entries, on every lane at once) stages through the native WAL engine's one
call is, shard by shard and byte for byte, what the Python step stages for
the same ``StepInfo``; and a store re-opened over those bytes replays them
to the same state.  (ISSUE 34 asked for these as columns over the moved
lanes; the parent's per-lane spans elected 99,999 groups in a fifth of the
boot limit, so the columns wait for a ``perf_opt``: PERF.md PR 34.)
"""

import json
import os
import time

import numpy as np
import pytest

from rafting_tpu.api import RaftConfig, RaftContainer
from rafting_tpu.core.packing import DenseView
from rafting_tpu.core.types import EngineConfig, StepInfo, LEADER, NIL
from rafting_tpu.log.store import LogStore
from rafting_tpu.transport.codec import PayloadRun
from rafting_tpu.testkit.harness import (
    LocalCluster, free_ports, kv_factory, wal_store_factory)

LANES = 2048
OPEN = LANES - 1            # lane 0 is @raft
STORM_PERIODS = 60          # election timeout 10-20 periods: three rounds


def _wait(pred, what, timeout=180.0):
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, f"{what} not reached"
        time.sleep(0.1)


def test_every_lane_opened_at_once_elects_within_the_stated_periods(tmp_path):
    uris = [f"raft://127.0.0.1:{p}" for p in free_ports(3)]
    names = [f"g{i + 1:05d}" for i in range(OPEN)]
    cs = []
    try:
        for i, u in enumerate(uris):
            rc = RaftConfig(
                local=u, peers=tuple(p for p in uris if p != u),
                data_dir=str(tmp_path / f"node{i}"), seed=23,
                # A period long enough that a storm step of the three
                # loops fits in it while five other test workers load the
                # host: the storm is 19-21 periods here run alone, and at
                # 100 ms a period a loaded run needed 110.
                n_groups=LANES, tick_ms=500, heartbeat_mul=1.0,
                election_mul=10.0, log_slots=64, batch=8, max_submit=8,
                tick_stagger=True)
            os.makedirs(rc.data_dir)
            with open(os.path.join(rc.data_dir, "groups.json"), "w") as f:
                json.dump({n: [k + 1, True] for k, n in enumerate(names)}, f)
            cs.append(RaftContainer(rc, kv_factory(), admin=False).create())
        nodes = [c.node for c in cs]
        lanes = np.arange(1, LANES)

        def ready():
            led = np.zeros(LANES, bool)
            for n in nodes:
                led |= (n.h_role == LEADER) & n.h_ready
            return bool(led[lanes].all()) and all(
                bool((n.h_active[lanes] & (n.h_leader[lanes] != NIL)).all())
                for n in nodes)

        _wait(ready, "every open group led and routed")
        periods = max(n.timer_ticks for n in nodes)
        assert periods <= STORM_PERIODS, periods
        for n in nodes:
            m = n.metrics
            assert m["leader_evacuations"] == 0
            assert m["admission_shed"] == 0
            assert m["inbox_dropped"] == 0
            assert m._gauges["health_self_score"] <= m["slow_io_ticks"]
            assert m._gauges["groups_leaderless"] == 0
        # One storm: hardly more than an election a group.
        assert sum(n.metrics["elections"] for n in nodes) <= 1.5 * OPEN
        # Every group's no-op was applied somewhere.
        assert sum(n.metrics["applies"] for n in nodes) >= OPEN
    finally:
        for c in cs:
            c.destroy()


# ------------------------------------------------- what a storm step stages

CFG = EngineConfig(n_groups=LANES, n_peers=3, log_slots=64, batch=8,
                   max_submit=8, election_ticks=10, heartbeat_ticks=1,
                   rpc_timeout_ticks=8, pre_vote=True)
ME = 0                      # the node whose steps are staged
WIN = np.arange(1, LANES)[np.arange(1, LANES) % 3 == ME]      # lanes it wins
FOLLOW = np.arange(1, LANES)[np.arange(1, LANES) % 3 != ME]   # lanes it loses
FAT = FOLLOW[:5]            # five of those adopt two real entries as well
PART = FOLLOW[100:700:100]  # six adopt the first entry of a frame of two


def _node(root, engine):
    lc = LocalCluster(CFG, str(root), seed=1,
                      store_factory=wal_store_factory(str(root), engine))
    return lc, lc.nodes[ME]


class _Ctx:
    """A fetched step as ``_persist`` takes it (``runtime/node.py
    _TickCtx``): the StepInfo and the state lanes the host mirrors, the
    drained inbox planes and their payload runs."""

    def __init__(self, info: StepInfo, term, voted, leader, arrays=None,
                 staged_payloads=None):
        G = CFG.n_groups
        self.info, self.term, self.voted, self.leader = \
            info, term, voted, leader
        self.submit_n = np.zeros(G, np.int32)
        self.base = np.zeros(G, np.int32)
        self.base_term = np.zeros(G, np.int32)
        self.arrays = None if arrays is None else DenseView(arrays)
        self.staged_payloads = staged_payloads or {}


def _votes(info):
    """Every lane's (term, ballot) moves: the node votes for itself on the
    lanes it will win and grants the winner elsewhere."""
    term = np.zeros(LANES, np.int32)
    voted = np.full(LANES, NIL, np.int32)
    term[1:] = 1
    voted[1:] = np.arange(1, LANES) % 3
    dirty = np.zeros(LANES, bool)
    dirty[1:] = True
    return _Ctx(info.replace(dirty=dirty), term, voted,
                np.full(LANES, NIL, np.int32))


def _wins(info):
    """A third of the lanes win in one step: an election no-op each."""
    votes = _votes(info)
    noop_idx = np.zeros(LANES, np.int32)
    noop_term = np.zeros(LANES, np.int32)
    tail = np.zeros(LANES, np.int32)
    noop_idx[WIN] = tail[WIN] = 1
    noop_term[WIN] = 1
    dirty = np.zeros(LANES, bool)
    dirty[WIN] = True
    leader = np.full(LANES, NIL, np.int32)
    leader[WIN] = ME
    return _Ctx(info.replace(dirty=dirty, noop_idx=noop_idx,
                             noop_term=noop_term, log_tail=tail),
                votes.term, votes.voted, leader)


def _adoptions(info):
    """Two thirds of the lanes adopt their new leader's no-op from its
    frame, five of them two entries with payloads behind it; six, spread
    among the others, only the first entry of a frame that holds two
    (a partial adoption among whole ones)."""
    votes = _votes(info)
    P, B = CFG.n_peers, CFG.batch
    src = (FOLLOW % 3).astype(np.int32)
    n = np.ones(len(FOLLOW), np.int32)
    n[:len(FAT)] = 3
    arrays = {"ae_valid": np.zeros((P, LANES), bool),
              "ae_n": np.zeros((P, LANES), np.int32),
              "ae_prev_idx": np.zeros((P, LANES), np.int32),
              "ae_ents": np.zeros((P, LANES, B), np.int32),
              "ae_cents": np.zeros((P, LANES, B), np.int32)}
    arrays["ae_valid"][src, FOLLOW] = True
    arrays["ae_n"][src, FOLLOW] = n
    arrays["ae_ents"][src, FOLLOW, 0] = 1
    runs = {}
    part = set(PART.tolist())
    for g, p, k in zip(FOLLOW.tolist(), src.tolist(), n.tolist()):
        if g in part:
            arrays["ae_n"][p, g] = 2
            arrays["ae_ents"][p, g, 1] = 1
            runs[p, g] = PayloadRun.from_payloads(1, [b"", b"late-%d" % g])
        elif k == 1:
            runs[p, g] = PayloadRun.single(1, b"")
        else:
            arrays["ae_ents"][p, g, 1:3] = 1
            runs[p, g] = PayloadRun.from_payloads(
                1, [b"", b"fat-%d" % g, b"entry-%d" % g * 3])
    app_to = np.zeros(LANES, np.int32)
    app_from = np.zeros(LANES, np.int32)
    app_from[FOLLOW] = 1
    app_to[FOLLOW] = n
    dirty = np.zeros(LANES, bool)
    dirty[FOLLOW] = True
    leader = np.full(LANES, NIL, np.int32)
    leader[FOLLOW] = src
    return _Ctx(info.replace(dirty=dirty, appended_from=app_from,
                             appended_to=app_to, log_tail=app_to),
                votes.term, votes.voted, leader, arrays, runs)


def _seg_bytes(d: str) -> dict:
    out = {}
    for root, _dirs, files in os.walk(d):
        for f in files:
            if f.endswith(".wal"):
                p = os.path.join(root, f)
                with open(p, "rb") as fh:
                    out[os.path.relpath(p, d)] = fh.read()
    return out


STEPS = {"stable records": [_votes],
         "no-ops": [_votes, _wins],
         "adoptions": [_votes, _adoptions]}


@pytest.mark.parametrize("case", list(STEPS))
def test_a_storm_step_stages_the_same_bytes_through_either_engine(
        tmp_path, case):
    dirs, steps = {}, {}
    for engine in ("native", "python"):
        lc, node = _node(tmp_path / engine, engine)
        try:
            if engine == "native" and not node.store.can_stage_native:
                pytest.skip("no native WAL engine here")
            info = node.tick()      # a StepInfo of this node's own shapes
            taken = []
            for name in ("_persist_stage_native", "_persist_stage"):
                real = getattr(node, name)
                setattr(node, name, lambda prep, real=real, name=name: (
                    taken.append(name), real(prep))[1])
            for make in STEPS[case]:
                node._persist(make(info))
            steps[engine] = taken
            dirs[engine] = os.path.join(str(tmp_path / engine),
                                        f"node{ME}", "wal")
        finally:
            lc.close()
    # The native store took every step in its one call.
    assert set(steps["native"]) == {"_persist_stage_native"}
    assert set(steps["python"]) == {"_persist_stage"}
    a, b = _seg_bytes(dirs["native"]), _seg_bytes(dirs["python"])
    assert sorted(a) == sorted(b) and len(a) == 4      # a segment a shard
    for k in sorted(a):
        assert a[k] == b[k], f"{case}: {k} differs between the two"
    assert sum(len(v) for v in a.values()) > 25 * OPEN  # every lane's record
    # A restart replays either directory to the same state, and that state
    # is the one the steps said.
    want = STEPS[case][-1](info)
    for d in dirs.values():
        r = LogStore(d, shards=4)
        try:
            for g in (1, 2, 3, 1000, LANES - 1, int(FAT[0]), int(FAT[-1])):
                assert r.stable(g) == (int(want.term[g]), int(want.voted[g]))
                tail = int(want.info.log_tail[g])
                assert r.tail(g) == tail
                if tail:
                    assert r.entry_term(g, 1) == 1 and r.payload(g, 1) == b""
            if case == "adoptions":
                g = int(FAT[2])
                assert r.payload(g, 2) == b"fat-%d" % g
                assert r.payload(g, 3) == b"entry-%d" % g * 3
            ex = r.export_state(LANES, CFG.log_slots)
            np.testing.assert_array_equal(ex["stable_term"][1:],
                                          want.term[1:])
            np.testing.assert_array_equal(ex["ballot"][1:], want.voted[1:])
            np.testing.assert_array_equal(ex["tail"][1:],
                                          want.info.log_tail[1:])
        finally:
            r.close()
