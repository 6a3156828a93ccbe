"""Membership plane (ISSUE 7): joint consensus, learner catch-up,
leadership transfer — kernel/oracle parity under membership chaos,
protocol-level walks on DeviceCluster, election-safety + read invariants
under nemesis schedules WHILE a joint config is in flight (lease on and
off), runtime/WAL durability, and the scripted 3->3-disjoint rebalance
acceptance (10k groups marked slow; a small tick-for-tick-parity smoke
stays in tier-1)."""

import jax.numpy as jnp
import numpy as np
import pytest

from rafting_tpu.core.cluster import DeviceCluster, cluster_snapshot
from rafting_tpu.core.types import (
    EngineConfig, HostInbox, LEADER, Messages, conf_learners_of,
    conf_new_of, conf_voters_of, init_state,
)
from rafting_tpu.testkit.invariants import ClusterChecker
from rafting_tpu.testkit import nemesis

from rafting_tpu.testkit.parity import run_parity


# ----------------------------------------------------------------- parity --

@pytest.mark.parametrize("lease", [True, False])
def test_parity_membership_chaos(lease):
    """Kernel <-> scalar-oracle parity with random membership changes and
    leadership transfers riding the partition + crash + stall chaos mix,
    lease on and off.  Every new lane (conf rings, transfer state, the
    tn/ae_cents/is_conf wire fields, the conf/xfer StepInfo outputs) is
    compared bit-for-bit each tick."""
    cfg = EngineConfig(n_groups=8, n_peers=5, log_slots=16, batch=4,
                       max_submit=4, election_ticks=6, heartbeat_ticks=2,
                       rpc_timeout_ticks=5, pre_vote=True, read_lease=lease)
    run_parity(23 + int(lease), n_ticks=52, cfg=cfg, crash_p=0.03,
               stall_p=0.04, conf_p=0.08, xfer_p=0.05, n_voters=3)


def test_parity_membership_trace():
    """Same chaos with the flight recorder on: the CONF_CHANGE_ENTER /
    CONF_CHANGE_COMMIT / LEADER_TRANSFER events (and the widened 11-event
    emission window) must match the oracle's stream tick-for-tick."""
    cfg = EngineConfig(n_groups=6, n_peers=4, log_slots=16, batch=4,
                       max_submit=4, election_ticks=6, heartbeat_ticks=2,
                       rpc_timeout_ticks=5, pre_vote=True, trace_depth=16)
    run_parity(31, n_ticks=48, cfg=cfg, conf_p=0.1, xfer_p=0.06,
               n_voters=3)


# ------------------------------------------------------- protocol (device) --

def _cfg(G=8, P=5, **kw):
    kw.setdefault("log_slots", 32)
    kw.setdefault("batch", 4)
    kw.setdefault("max_submit", 4)
    kw.setdefault("election_ticks", 6)
    kw.setdefault("heartbeat_ticks", 2)
    kw.setdefault("rpc_timeout_ticks", 5)
    return EngineConfig(n_groups=G, n_peers=P, **kw)


def _settle(c, ticks, submit=1):
    for _ in range(ticks):
        c.tick(submit_n=submit)


def _active_conf(c):
    """Max-term leader's conf word per group (the authoritative view)."""
    info = c.last_info
    role = np.asarray(c.states.role)
    term = np.asarray(c.states.term)
    w = np.asarray(info.conf_word)
    out = np.zeros(w.shape[1], np.int64)
    for g in range(w.shape[1]):
        leads = np.nonzero(role[:, g] == LEADER)[0]
        n = leads[np.argmax(term[leads, g])]
        out[g] = w[n, g]
    return out


def test_learner_add_and_promote_walk():
    """add-learner -> catch-up -> promote-to-disjoint-voters: the full §6
    walk on device, with zero committed-entry loss."""
    c = DeviceCluster(_cfg(), seed=3, n_voters=3)
    _settle(c, 40)
    snap0 = cluster_snapshot(c.states)
    assert ((snap0["role"] == LEADER).sum(axis=0) == 1).all()
    committed_before = snap0["commit"].max(axis=0).copy()
    terms_before = {}
    for g in range(c.cfg.n_groups):
        n = int(np.argmax(snap0["role"][:, g] == LEADER))
        L = c.cfg.log_slots
        for idx in range(int(snap0["base"][n, g]) + 1,
                         int(committed_before[g]) + 1):
            terms_before[(g, idx)] = int(snap0["log_term"][n, g, idx % L])

    # Stage 1: slots 3,4 join as learners.
    c.request_membership(voters=0b00111, learners=0b11000)
    _settle(c, 25)
    w = _active_conf(c)
    assert (conf_voters_of(w) == 0b00111).all()
    assert (conf_learners_of(w) == 0b11000).all()
    assert (conf_new_of(w) == 0).all()

    # Learners replicate: their logs advance with the leader's.
    snap = cluster_snapshot(c.states)
    assert (snap["last"][3] >= committed_before).all()
    assert (snap["last"][4] >= committed_before).all()
    # ...but never campaign or lead.
    assert not (snap["role"][3:] == LEADER).any()

    # Stage 2: promote 2,3,4; demote 0,1 (joint walk, auto-leave).
    c.request_membership(voters=0b11100, learners=0)
    _settle(c, 60)
    w = _active_conf(c)
    assert (conf_voters_of(w) == 0b11100).all()
    assert (conf_new_of(w) == 0).all()
    assert (conf_learners_of(w) == 0).all()

    snap = cluster_snapshot(c.states)
    lead_nodes = np.argmax(snap["role"] == LEADER, axis=0)
    assert ((snap["role"] == LEADER).sum(axis=0) == 1).all()
    assert (lead_nodes >= 2).all(), \
        f"removed voters still lead: {lead_nodes}"
    # Zero committed-entry loss: every pre-walk committed entry survives
    # with its term on the new leadership.
    L = c.cfg.log_slots
    for (g, idx), t in terms_before.items():
        n = int(lead_nodes[g])
        if idx <= int(snap["base"][n, g]):
            continue   # compacted (committed by definition)
        assert int(snap["log_term"][n, g, idx % L]) == t, \
            f"committed entry (g={g}, idx={idx}) changed term"
    # Commits keep flowing under the new voter set.
    c0 = snap["commit"].max(axis=0).copy()
    _settle(c, 15)
    assert (cluster_snapshot(c.states)["commit"].max(axis=0) > c0).all()


def test_joint_entry_blocks_without_new_quorum():
    """While joint, commits need BOTH quorums: cutting the incoming set
    off stalls the joint entry (and everything after it), healing
    completes the walk — §6's two-phase safety observable end to end."""
    c = DeviceCluster(_cfg(G=4), seed=5, n_voters=3)
    _settle(c, 40)
    committed = cluster_snapshot(c.states)["commit"].max(axis=0).copy()
    # Partition the incoming voters {3,4} away, then request the switch.
    c.set_partition([[0, 1, 2], [3, 4]])
    c.request_membership(voters=0b11100, learners=0)
    _settle(c, 25)
    info = c.last_info
    # The joint entry is appended on the leader but CANNOT commit.
    w = _active_conf(c)
    assert (conf_new_of(w) == 0b11100).all(), "joint not entered"
    assert np.asarray(info.conf_pending).any(axis=0).all(), \
        "joint entry committed without the new set's quorum"
    # Old-majority-only traffic must not commit past the joint entry.
    stalled = cluster_snapshot(c.states)["commit"].max(axis=0)
    _settle(c, 10)
    again = cluster_snapshot(c.states)["commit"].max(axis=0)
    assert (again == stalled).all(), "commit advanced on C_old alone"
    # Heal: the walk completes.
    c.heal()
    _settle(c, 60)
    w = _active_conf(c)
    assert (conf_voters_of(w) == 0b11100).all()
    assert (conf_new_of(w) == 0).all()
    final = cluster_snapshot(c.states)["commit"].max(axis=0)
    assert (final > committed).all()


def test_transfer_leadership_device():
    """TimeoutNow: leadership lands on the requested target, without
    losing committed entries, and the target campaigns by transfer cause
    (no PreVote round)."""
    c = DeviceCluster(_cfg(G=4, P=3, trace_depth=16), seed=1)
    _settle(c, 40)
    snap = cluster_snapshot(c.states)
    before = np.argmax(snap["role"] == LEADER, axis=0)
    committed = snap["commit"].max(axis=0).copy()
    tgt = (before + 1) % 3
    c.request_transfer(tgt)
    fired = np.zeros(4, bool)
    for _ in range(20):
        info = c.tick()
        fired |= np.asarray(info.xfer_fired).any(axis=0)
    snap = cluster_snapshot(c.states)
    after = np.argmax(snap["role"] == LEADER, axis=0)
    assert fired.all()
    np.testing.assert_array_equal(after, tgt)
    assert ((snap["role"] == LEADER).sum(axis=0) == 1).all()
    assert (snap["commit"].max(axis=0) >= committed).all()
    # The recorder saw LEADER_TRANSFER on the old leader and a
    # transfer-caused candidacy (aux=2) on the target.
    from rafting_tpu.utils.tracelog import (
        TR_BECAME_CANDIDATE, TR_LEADER_TRANSFER, trace_to_numpy,
        decode_group,
    )
    lanes = trace_to_numpy(c.states.trace)
    saw_xfer, saw_cause = False, False
    for g in range(4):
        for n in range(3):
            evs, _ = decode_group(lanes, g, node=n)
            for ev in evs:
                saw_xfer |= ev["kind"] == TR_LEADER_TRANSFER
                saw_cause |= (ev["kind"] == TR_BECAME_CANDIDATE
                              and ev["aux"] == 2)
    assert saw_xfer and saw_cause


def test_submissions_fenced_during_transfer():
    """A pending transfer fences intake (submit_acc = 0) until the
    transfer fires or aborts."""
    c = DeviceCluster(_cfg(G=2, P=3), seed=7)
    _settle(c, 40)
    snap = cluster_snapshot(c.states)
    lead = np.argmax(snap["role"] == LEADER, axis=0)
    # Cut the target off so the transfer can neither fire nor catch up;
    # intake must stay fenced until the deadline aborts it.
    tgt = (lead + 1) % 3
    c.set_partition([[int(lead[0])],
                     [n for n in range(3) if n != int(lead[0])]])
    info = c.request_transfer(tgt, groups=[0])
    fence_seen = False
    for _ in range(3):
        info = c.tick(submit_n=2)
        fence_seen |= bool(np.asarray(info.submit_acc)[:, 0].sum() == 0)
    assert fence_seen
    # Deadline (election_ticks) aborts; intake resumes.
    aborted = False
    for _ in range(2 * c.cfg.election_ticks):
        info = c.tick(submit_n=2)
        aborted |= bool(np.asarray(info.xfer_abort).any())
    assert aborted
    c.heal()


# ------------------------------------------------- nemesis while joint -----

@pytest.mark.parametrize("lease", [True, False])
def test_nemesis_with_joint_in_flight(lease):
    """Election safety + committed-entry stability + linearizable-read
    invariants under partition + crash-restart chaos WHILE a joint config
    is in flight, lease on and off.  The joint entry is parked in flight
    (incoming set partitioned off) before the chaos starts; the checker
    audits every window; a healthy settle tail then completes the walk."""
    cfg = _cfg(G=6, P=5, read_slots=2, read_lease=lease)
    c = DeviceCluster(cfg, seed=11, n_voters=3)
    _settle(c, 40)
    chk = ClusterChecker(cfg)
    chk.check(cluster_snapshot(c.states))
    # Park a joint change in flight.
    c.set_partition([[0, 1, 2], [3, 4]])
    c.request_membership(voters=0b11100, learners=0)
    _settle(c, 15)
    assert np.asarray(c.last_info.conf_pending).any(), "joint not in flight"
    c.heal()
    chk.check(cluster_snapshot(c.states))

    # Chaos: partitions + crash-restarts (+ read offers riding along).
    from rafting_tpu.core.sim import run_cluster_ticks_nemesis
    sched = nemesis.compose(
        nemesis.rolling_partition(5, 64, period=16),
        nemesis.crash_storm(5, 64, rate=0.02, seed=2),
    )
    states, inflight, info = c.states, c.inflight, c.last_info
    sub = jnp.full((5, cfg.n_groups), 2, jnp.int32)
    reads = jnp.full((5, cfg.n_groups), 2, jnp.int32)
    crash_np = np.asarray(sched.crash)
    done = 0
    while done < 64:
        step = 16
        sl = jax.tree.map(lambda a: a[done:done + step], sched)
        states, inflight, info = run_cluster_ticks_nemesis(
            cfg, states, inflight, info, sl, sub, reads)
        crashed = crash_np[done:done + step].any(axis=0)
        done += step
        chk.check(cluster_snapshot(states), crashed=crashed)
    # Settle healthy: the walk completes and the cluster stays live.
    c.states, c.inflight, c.last_info = states, inflight, info
    _settle(c, 60)
    chk.check(cluster_snapshot(c.states))
    chk.check_log_matching(cluster_snapshot(c.states))
    w = _active_conf(c)
    assert (conf_voters_of(w) == 0b11100).all()
    assert (conf_new_of(w) == 0).all()
    snap = cluster_snapshot(c.states)
    c0 = snap["commit"].max(axis=0).copy()
    _settle(c, 10)
    assert (cluster_snapshot(c.states)["commit"].max(axis=0) > c0).all()


import jax  # noqa: E402  (used by the nemesis slicing above)


# ------------------------------------------------------ scripted rebalance --

def _scripted_rebalance(cfg, seed, oracle_parity=False):
    """The acceptance walk: 3 -> 3-disjoint node rebalance (voters
    {0,1,2} -> {3,4,5}) via add-learner -> catch-up -> promote ->
    demote-old -> transfer inside the new set.  Returns (cluster,
    pre-walk committed terms dict) after asserting zero committed-entry
    loss and exactly one leader per group inside the new set."""
    c = DeviceCluster(cfg, seed=seed, n_voters=3)
    _settle(c, 40)
    snap0 = cluster_snapshot(c.states)
    committed0 = snap0["commit"].max(axis=0).copy()
    assert (committed0 > 0).all()
    # add learners {3,4,5}
    c.request_membership(voters=0b000111, learners=0b111000)
    _settle(c, 30)
    # promote {3,4,5}, demote {0,1,2} (joint walk)
    c.request_membership(voters=0b111000, learners=0)
    _settle(c, 80)
    w = _active_conf(c)
    assert (conf_voters_of(w) == 0b111000).all()
    assert (conf_new_of(w) == 0).all()
    snap = cluster_snapshot(c.states)
    lead_nodes = np.argmax(snap["role"] == LEADER, axis=0)
    assert ((snap["role"] == LEADER).sum(axis=0) == 1).all()
    assert (lead_nodes >= 3).all()
    # zero committed-entry loss: the new leaders' commit covers the
    # pre-walk frontier and keeps advancing.
    assert (snap["commit"].max(axis=0) >= committed0).all()
    c1 = snap["commit"].max(axis=0).copy()
    _settle(c, 15)
    assert (cluster_snapshot(c.states)["commit"].max(axis=0) > c1).all()
    # leadership transfer inside the new set rides the same lanes
    tgt = np.where(lead_nodes == 3, 4, 3).astype(np.int32)
    c.request_transfer(tgt)
    fired = np.zeros(cfg.n_groups, bool)
    for _ in range(25):
        info = c.tick()
        fired |= np.asarray(info.xfer_fired).any(axis=0)
    assert fired.all()
    snap = cluster_snapshot(c.states)
    after = np.argmax(snap["role"] == LEADER, axis=0)
    np.testing.assert_array_equal(after, tgt)
    return c


def test_rebalance_walk_smoke():
    """Tier-1 smoke of the acceptance walk at small scale."""
    _scripted_rebalance(_cfg(G=16, P=6), seed=9)


def test_rebalance_walk_parity_tick_for_tick():
    """The scripted walk with kernel <-> oracle parity asserted EVERY
    tick: the same membership schedule (learner add at a fixed tick,
    joint switch later, transfer at the end) drives both engines."""
    from rafting_tpu.testkit.parity import (
        assert_info_equal, assert_messages_equal, assert_state_equal,
        route_numpy,
    )
    from rafting_tpu.core.step import node_step
    from rafting_tpu.testkit.oracle import oracle_step

    cfg = _cfg(G=4, P=6, log_slots=16)
    N, G = cfg.n_peers, cfg.n_groups
    states = [init_state(cfg, i, seed=2, n_voters=3) for i in range(N)]
    outboxes = [Messages.empty(cfg) for _ in range(N)]
    infos = [None] * N
    conn = np.ones((N, N), bool)
    for t in range(140):
        cv = np.zeros(G, np.int32)
        cl = np.zeros(G, np.int32)
        xt = np.full(G, -1, np.int32)
        if t == 45:
            cv[:] = 0b000111
            cl[:] = 0b111000
        elif t == 75:
            cv[:] = 0b111000
        elif t == 110:
            xt[:] = 4
        inboxes = route_numpy(outboxes, conn)
        new_outboxes = []
        for n in range(N):
            # Slack compaction keeps ring space for the conf entries (the
            # real host's maintain policy; without it the ring fills and
            # intake is correctly refused forever).
            compact = np.maximum(
                np.asarray(states[n].commit) - cfg.log_slots // 4,
                0).astype(np.int32)
            host = HostInbox.empty(cfg).replace(
                submit_n=np.full(G, 1, np.int32),
                conf_voters=cv, conf_learners=cl, xfer_target=xt,
                compact_to=compact)
            if infos[n] is not None:
                host = host.replace(
                    snap_done=np.asarray(infos[n].snap_req),
                    snap_idx=np.asarray(infos[n].snap_req_idx),
                    snap_term=np.asarray(infos[n].snap_req_term),
                    snap_conf=np.asarray(infos[n].snap_req_conf))
            o_state, o_out, o_info = oracle_step(cfg, states[n],
                                                 inboxes[n], host)
            k_state, k_out, k_info = node_step(cfg, states[n], inboxes[n],
                                               host)
            tag = f"walk tick={t} node={n}"
            assert_state_equal(k_state, o_state, tag)
            assert_messages_equal(k_out, o_out, tag)
            assert_info_equal(k_info, o_info, tag)
            states[n] = k_state
            new_outboxes.append(k_out)
            infos[n] = k_info
        outboxes = new_outboxes
    # The walk completed under parity: voters are {3,4,5} and node 4
    # holds leadership where the transfer landed.
    final_w = np.asarray(infos[3].conf_word)
    assert (conf_voters_of(final_w) == 0b111000).all()
    roles = np.stack([np.asarray(s.role) for s in states])
    assert ((roles == LEADER).sum(axis=0) == 1).all()


@pytest.mark.slow
def test_rebalance_walk_10k_groups():
    """ISSUE 7 acceptance: the scripted rebalance completes on a
    3 -> 3-disjoint node walk at 10k groups with zero committed-entry
    loss."""
    _scripted_rebalance(_cfg(G=10_000, P=6, log_slots=64,
                             election_ticks=10, heartbeat_ticks=3,
                             rpc_timeout_ticks=8), seed=4)


# ------------------------------------------------------------- runtime -----

def test_runtime_membership_change_and_recovery(tmp_path):
    """Full-runtime walk: change_membership through RaftNode (learner add
    + joint promote), counters move, the config survives a node
    kill/restart (WAL conf meta), and the stub forwards membership ops
    from a follower."""
    from rafting_tpu.testkit.harness import LocalCluster

    cfg = _cfg(G=2, P=4, log_slots=16)
    c = LocalCluster(cfg, str(tmp_path))
    try:
        c.wait_leader(0)
        c.submit_via_leader(0, b"x")
        lead = c.leader_of(0)
        node = c.nodes[lead]
        assert node.membership(0)["voters"] == 0b1111
        # Shrink to {0,1,2} via the joint walk.
        fut = node.change_membership(0, 0b0111)
        for _ in range(400):
            if fut.done():
                break
            c.tick()
        assert fut.result() == {"voters": 0b0111, "learners": 0}
        assert node.membership(0) == {
            "voters": 0b0111, "voters_new": 0, "learners": 0,
            "joint": False, "pending": False,
            "conf_idx": node.membership(0)["conf_idx"]}
        assert node.metrics["membership_changes_entered"] >= 2  # joint+leave
        assert node.metrics["membership_changes_committed"] >= 2
        # Survives crash-restart: the WAL conf meta restores the voter set.
        c.kill_node(lead)
        n2 = c.restart_node(lead)
        assert n2.membership(0)["voters"] == 0b0111
        # Forwarded membership op from a follower stub (FWD_CONF).
        c.tick(30)
        lead = c.wait_leader(0)
        follower = next(i for i in c.nodes if i != lead)
        ok, raw = c.nodes[follower].transport.forward_conf(
            lead, 0, 1, 0b0111, 0, timeout=5.0)
        import json
        assert ok and json.loads(raw) == {"voters": 0b0111, "learners": 0}
    finally:
        c.close()


def test_runtime_transfer_leadership(tmp_path):
    """transfer_leadership through the runtime: the future resolves after
    TimeoutNow + step-down, leadership lands on the target, and the
    transfer counters move."""
    from rafting_tpu.testkit.harness import LocalCluster

    cfg = _cfg(G=1, P=3, log_slots=16)
    c = LocalCluster(cfg, str(tmp_path))
    try:
        lead = c.wait_leader(0)
        c.submit_via_leader(0, b"y")
        node = c.nodes[lead]
        target = (lead + 1) % 3
        fut = node.transfer_leadership(0, target)
        for _ in range(400):
            if fut.done():
                break
            c.tick()
        assert fut.result() == target
        c.tick_until(lambda: c.leader_of(0) == target, 200,
                     "leadership on the target")
        assert node.metrics["leadership_transfers_attempted"] == 1
        assert node.metrics["leadership_transfers_succeeded"] == 1
        assert node.metrics["timeout_now_sent"] >= 1
    finally:
        c.close()


def test_conf_sidecar_overwrite_and_floor_pin(tmp_path):
    """Review regression: (a) a conflicting adoption at index i kills
    recorded config entries at >= i in the membership sidecar (the WAL
    replay drops that suffix — a stale record would resurrect a dead
    voter set at recovery); (b) the snapshot-install floor pin goes
    through the ConfMeta interface and wins over folded entries."""
    from rafting_tpu.log.store import LogStore

    store = LogStore(str(tmp_path / "wal"))
    try:
        store.put_conf(0, 5, 123)
        store.put_conf(0, 8, 456)
        store.conf_overwrite(0, 6)   # conflicting AE adoption at idx 6
        assert store.conf_export()[0] == (0, {5: 123})
        store.set_floor(0, 5, 1, conf_word=789)
        floor_word, entries = store.conf_export()[0]
        assert floor_word == 789 and entries == {}
        store.sync()
    finally:
        store.close()


def test_transfer_to_non_voter_refused(tmp_path):
    """Review regression: a transfer request naming a learner/removed
    slot is refused up front (the device only latches voter targets — a
    silent non-latch would hang the future forever)."""
    from rafting_tpu.api.anomaly import is_refusal
    from rafting_tpu.testkit.harness import LocalCluster

    cfg = _cfg(G=1, P=4, log_slots=16)
    c = LocalCluster(cfg, str(tmp_path))
    try:
        c.wait_leader(0)
        c.submit_via_leader(0, b"x")
        node = c.nodes[c.leader_of(0)]
        fut = node.change_membership(0, 0b0111)   # drop peer 3
        for _ in range(400):
            if fut.done():
                break
            c.tick()
        fut.result()
        bad = node.transfer_leadership(0, 3)      # 3 is no longer a voter
        assert bad.done() and is_refusal(bad.exception())
    finally:
        c.close()
