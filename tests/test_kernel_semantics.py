"""Deterministic kernel-level tests of subtle Raft safety rules.

These drive ``node_step`` directly with handcrafted inboxes — the vectorized
analog of the reference's invariant AssertionErrors (e.g. commit-own-term,
Leader.java:256-261) lifted into unit tests.
"""

import jax.numpy as jnp
import numpy as np

from rafting_tpu import EngineConfig, HostInbox, Messages, init_state, node_step
from rafting_tpu.core.types import FOLLOWER, LEADER, I32


def cfg3(**kw):
    d = dict(n_groups=1, n_peers=3, log_slots=16, batch=4, max_submit=4,
             election_ticks=50, heartbeat_ticks=3)
    d.update(kw)
    return EngineConfig(**d)


def follower_with_log(cfg, term, entry_terms):
    """Node 0, follower at `term`, log = entries 1..len(entry_terms)."""
    st = init_state(cfg, node_id=0, seed=0)
    L = cfg.log_slots
    ring = np.zeros((1, L), np.int32)
    for i, t in enumerate(entry_terms, start=1):
        ring[0, i % L] = t
    st = st.replace(
        term=jnp.full((1,), term, I32),
        log=st.log.replace(term=jnp.asarray(ring),
                           last=jnp.full((1,), len(entry_terms), I32)),
        # keep the election timer far away so the step is purely msg-driven
        elect_deadline=jnp.full((1,), 10_000, I32),
    )
    return st


def ae_from(cfg, peer, *, term, prev_idx, prev_term, n=0, ents=(), commit=0):
    m = Messages.empty(cfg)
    B = cfg.batch
    e = np.zeros((1, B), np.int32)
    e[0, :len(ents)] = ents
    def setp(arr, val):
        return arr.at[peer].set(jnp.asarray(val))
    return m.replace(
        ae_valid=setp(m.ae_valid, [True]),
        ae_term=setp(m.ae_term, [term]),
        ae_prev_idx=setp(m.ae_prev_idx, [prev_idx]),
        ae_prev_term=setp(m.ae_prev_term, [prev_term]),
        ae_n=setp(m.ae_n, [n]),
        ae_ents=m.ae_ents.at[peer].set(jnp.asarray(e)),
        ae_commit=setp(m.ae_commit, [commit]),
    )


def test_passive_commit_bounded_by_verified_prefix():
    """A heartbeat verifying only prefix [1..3] must not commit a divergent
    local tail [4..5], even when leaderCommit = 5 (Raft fig. 2: commit =
    min(leaderCommit, last NEW entry))."""
    cfg = cfg3()
    st = follower_with_log(cfg, term=2, entry_terms=[1, 1, 1, 1, 1])
    inbox = ae_from(cfg, peer=1, term=2, prev_idx=3, prev_term=1, n=0,
                    commit=5)
    st2, out, info = node_step(cfg, st, inbox, HostInbox.empty(cfg))
    assert int(st2.commit[0]) == 3, "must not commit the unverified tail"
    assert bool(out.aer_success[1, 0])
    assert int(out.aer_match[1, 0]) == 3


def test_append_conflict_truncates_then_commits_new_entries():
    cfg = cfg3()
    st = follower_with_log(cfg, term=2, entry_terms=[1, 1, 1, 1, 1])
    # New leader at term 2 overwrites 4..5 with term-2 entries, commit 5.
    inbox = ae_from(cfg, peer=1, term=2, prev_idx=3, prev_term=1, n=2,
                    ents=[2, 2], commit=5)
    st2, out, info = node_step(cfg, st, inbox, HostInbox.empty(cfg))
    assert int(st2.commit[0]) == 5
    assert int(st2.log.last[0]) == 5
    ring = np.asarray(st2.log.term[0])
    assert ring[4 % cfg.log_slots] == 2 and ring[5 % cfg.log_slots] == 2
    assert int(info.log_tail[0]) == 5


def test_conflict_shrinks_log_and_reports_tail():
    """Conflicting shorter suffix truncates; StepInfo.log_tail reflects it so
    the host WAL can invalidate beyond it."""
    cfg = cfg3()
    st = follower_with_log(cfg, term=3, entry_terms=[1, 1, 2, 2, 2])
    # Leader at term 3: entry 3 should be term 3 (conflict), n=1.
    inbox = ae_from(cfg, peer=2, term=3, prev_idx=2, prev_term=1, n=1,
                    ents=[3], commit=0)
    st2, out, info = node_step(cfg, st, inbox, HostInbox.empty(cfg))
    assert int(st2.log.last[0]) == 3, "divergent suffix [4..5] discarded"
    assert int(info.log_tail[0]) == 3
    ring = np.asarray(st2.log.term[0])
    assert ring[3 % cfg.log_slots] == 3


def test_stale_term_append_rejected():
    cfg = cfg3()
    st = follower_with_log(cfg, term=5, entry_terms=[1, 1])
    inbox = ae_from(cfg, peer=1, term=4, prev_idx=2, prev_term=1, n=1,
                    ents=[4], commit=2)
    st2, out, info = node_step(cfg, st, inbox, HostInbox.empty(cfg))
    assert not bool(out.aer_success[1, 0])
    assert int(out.aer_term[1, 0]) == 5, "reply carries our newer term"
    assert int(st2.commit[0]) == 0
    assert int(st2.log.last[0]) == 2


def test_snapshot_install_discards_mismatched_tail():
    """InstallSnapshot receiver rule (Raft fig. 13): a retained suffix is only
    legal when the entry at the milestone matches; otherwise discard."""
    cfg = cfg3()
    st = follower_with_log(cfg, term=3, entry_terms=[1, 1, 1, 1, 1])
    host = HostInbox.empty(cfg).replace(
        snap_done=jnp.asarray([True]),
        snap_idx=jnp.asarray([4], I32),
        snap_term=jnp.asarray([2], I32),  # ring has term 1 at idx 4 -> mismatch
    )
    st2, _, _ = node_step(cfg, st, Messages.empty(cfg), host)
    assert int(st2.log.base[0]) == 4
    assert int(st2.log.base_term[0]) == 2
    assert int(st2.log.last[0]) == 4, "mismatched tail must be discarded"
    assert int(st2.commit[0]) == 4


def test_snapshot_install_keeps_matching_tail():
    cfg = cfg3()
    st = follower_with_log(cfg, term=3, entry_terms=[1, 1, 1, 1, 1])
    host = HostInbox.empty(cfg).replace(
        snap_done=jnp.asarray([True]),
        snap_idx=jnp.asarray([4], I32),
        snap_term=jnp.asarray([1], I32),  # matches -> keep entry 5
    )
    st2, _, _ = node_step(cfg, st, Messages.empty(cfg), host)
    assert int(st2.log.base[0]) == 4
    assert int(st2.log.last[0]) == 5, "matching tail is retained"


def test_vote_granted_once_per_term():
    """Two RequestVotes at the same term in one tick: exactly one grant
    (the sequential fold over peers preserves single-ballot semantics)."""
    cfg = cfg3()
    st = follower_with_log(cfg, term=0, entry_terms=[])
    m = Messages.empty(cfg)
    for peer in (1, 2):
        m = m.replace(
            rv_valid=m.rv_valid.at[peer].set(jnp.asarray([True])),
            rv_term=m.rv_term.at[peer].set(jnp.asarray([7], I32)),
            rv_last_idx=m.rv_last_idx.at[peer].set(jnp.asarray([0], I32)),
            rv_last_term=m.rv_last_term.at[peer].set(jnp.asarray([0], I32)),
        )
    st2, out, _ = node_step(cfg, st, m, HostInbox.empty(cfg))
    grants = [bool(out.rvr_granted[p, 0]) for p in (1, 2)]
    assert grants == [True, False], grants
    assert int(st2.voted_for[0]) == 1
    assert int(st2.term[0]) == 7


def test_vote_rejected_for_stale_log():
    cfg = cfg3()
    st = follower_with_log(cfg, term=1, entry_terms=[1, 1, 1])
    m = Messages.empty(cfg)
    m = m.replace(
        rv_valid=m.rv_valid.at[1].set(jnp.asarray([True])),
        rv_term=m.rv_term.at[1].set(jnp.asarray([2], I32)),
        rv_last_idx=m.rv_last_idx.at[1].set(jnp.asarray([1], I32)),
        rv_last_term=m.rv_last_term.at[1].set(jnp.asarray([1], I32)),
    )
    st2, out, _ = node_step(cfg, st, m, HostInbox.empty(cfg))
    assert not bool(out.rvr_granted[1, 0]), "shorter log must not win a vote"
    assert int(st2.voted_for[0]) == -1
    assert int(st2.term[0]) == 2, "term still adopted"


def test_commit_only_own_term():
    """A leader must not commit prior-term entries by counting a MAJORITY
    of replicas (Raft §5.4.2; reference Leader.java:256-261).  Full
    replication (min of the match row, Leader.java:260) is the one legal
    exception — tested separately below."""
    cfg = cfg3()
    st = follower_with_log(cfg, term=2, entry_terms=[1, 1])
    # Force leadership at term 2 with a MAJORITY-matched old-term log
    # (peer 2 lags, so the full-replication lane stays closed and the
    # own-term fence is what's under test).  own_from = 3 is what the
    # election-win phase would have set (first index of OUR term =
    # tail+1; the rule under test is quorum >= it).
    st = st.replace(
        role=jnp.asarray([LEADER], I32),
        leader_id=jnp.asarray([0], I32),
        match_idx=jnp.asarray([[2, 2, 0]], I32),
        next_idx=jnp.asarray([[3, 3, 1]], I32),
        own_from=jnp.asarray([3], I32),
    )
    st2, _, _ = node_step(cfg, st, Messages.empty(cfg), HostInbox.empty(cfg))
    assert int(st2.commit[0]) == 0, "old-term entries need a new-term cover"
    # Now append an own-term entry and match it on a majority: commits.
    host = HostInbox.empty(cfg).replace(submit_n=jnp.asarray([1], I32))
    st3, _, info = node_step(cfg, st2, Messages.empty(cfg), host)
    st3 = st3.replace(match_idx=jnp.asarray([[3, 3, 0]], I32))
    st4, _, _ = node_step(cfg, st3, Messages.empty(cfg), HostInbox.empty(cfg))
    assert int(st4.commit[0]) == 3, "own-term cover commits the whole prefix"


def test_commit_full_replication_lane():
    """A prior-term suffix replicated on EVERY node commits without an
    own-term cover (reference Leader.java:260 fullIndex): identical on
    all nodes means on every electable future leader — the lane that
    un-wedges a ring-full group whose §8 no-op could not be appended."""
    cfg = cfg3()
    st = follower_with_log(cfg, term=2, entry_terms=[1, 1])
    st = st.replace(
        role=jnp.asarray([LEADER], I32),
        leader_id=jnp.asarray([0], I32),
        match_idx=jnp.asarray([[2, 2, 2]], I32),
        next_idx=jnp.asarray([[3, 3, 3]], I32),
        own_from=jnp.asarray([3], I32),
    )
    st2, _, _ = node_step(cfg, st, Messages.empty(cfg), HostInbox.empty(cfg))
    assert int(st2.commit[0]) == 2, \
        "fully-replicated prior-term suffix must commit"


def test_heartbeat_reply_echoes_empty_flag():
    """Replies to empty AEs (heartbeats) carry aer_empty=True, data AEs
    False — the occupancy echo that keeps the sender's in-flight window
    exact (phase 9 window exemption)."""
    cfg = cfg3()
    st = follower_with_log(cfg, term=2, entry_terms=[1, 1, 1])
    hb = ae_from(cfg, peer=1, term=2, prev_idx=3, prev_term=1, n=0)
    _, out, _ = node_step(cfg, st, hb, HostInbox.empty(cfg))
    assert bool(out.aer_empty[1, 0]) and bool(out.aer_success[1, 0])

    st = follower_with_log(cfg, term=2, entry_terms=[1, 1, 1])
    data = ae_from(cfg, peer=1, term=2, prev_idx=3, prev_term=1, n=1,
                   ents=[2])
    _, out, _ = node_step(cfg, st, data, HostInbox.empty(cfg))
    assert not bool(out.aer_empty[1, 0]) and bool(out.aer_success[1, 0])


def test_exempt_heartbeat_reply_cannot_release_hb_slot():
    """Only replies to OCCUPYING heartbeats (aer_empty & aer_occ) release
    hb_inflight: a reply to a window-full slot-EXEMPT
    heartbeat (ae_occ=False) must not free a slot whose real ack was
    lost — that would disarm the RPC-timeout failure detector for the
    lost reply.  The follower echoes the AE's ae_occ verbatim; the
    leader's release honors it."""
    cfg = cfg3()
    # Follower side: ae_occ echoes through.
    st = follower_with_log(cfg, term=2, entry_terms=[1, 1, 1])
    hb = ae_from(cfg, peer=1, term=2, prev_idx=3, prev_term=1, n=0)
    hb = hb.replace(ae_occ=hb.ae_occ.at[1].set(jnp.asarray([True])))
    _, out, _ = node_step(cfg, st, hb, HostInbox.empty(cfg))
    assert bool(out.aer_empty[1, 0]) and bool(out.aer_occ[1, 0])
    st = follower_with_log(cfg, term=2, entry_terms=[1, 1, 1])
    hb = ae_from(cfg, peer=1, term=2, prev_idx=3, prev_term=1, n=0)
    _, out, _ = node_step(cfg, st, hb, HostInbox.empty(cfg))
    assert bool(out.aer_empty[1, 0]) and not bool(out.aer_occ[1, 0])

    # Leader side: an exempt-echo reply leaves hb_inflight untouched; an
    # occupying-echo reply releases it.
    for occ, expect in ((False, 2), (True, 1)):
        st = follower_with_log(cfg, term=2, entry_terms=[2, 2])
        st = st.replace(
            role=jnp.asarray([LEADER], I32),
            leader_id=jnp.asarray([0], I32),
            own_from=jnp.asarray([1], I32),
            hb_inflight=jnp.asarray([[0, 2, 0]], I32),
            # keep this tick free of NEW heartbeats so the lane isolates
            # the release decision
            hb_due=jnp.asarray([1000], I32),
        )
        reply = Messages.empty(cfg)
        reply = reply.replace(
            aer_valid=reply.aer_valid.at[1].set(jnp.asarray([True])),
            aer_term=reply.aer_term.at[1].set(jnp.asarray([2])),
            aer_success=reply.aer_success.at[1].set(jnp.asarray([True])),
            aer_match=reply.aer_match.at[1].set(jnp.asarray([2])),
            aer_empty=reply.aer_empty.at[1].set(jnp.asarray([True])),
            aer_occ=reply.aer_occ.at[1].set(jnp.asarray([occ])),
        )
        st2, _, _ = node_step(cfg, st, reply, HostInbox.empty(cfg))
        assert int(st2.hb_inflight[0, 1]) == expect, \
            f"occ={occ}: hb_inflight {int(st2.hb_inflight[0, 1])}"


def test_full_window_still_emits_heartbeats():
    """A leader whose data window is saturated still emits empty AEs on
    the heartbeat cadence (slot-exempt; the starvation fix the wedged-
    window cluster test covers end to end — this pins the kernel-level
    contract directly)."""
    cfg = cfg3(heartbeat_ticks=1, rpc_timeout_ticks=40)
    st = follower_with_log(cfg, term=3, entry_terms=[3, 3, 3, 3])
    G, P = 1, cfg.n_peers
    st = st.replace(
        role=jnp.full((G,), LEADER, I32),
        leader_id=jnp.zeros((G,), I32),
        # Window full on both peers; nothing new to send.
        inflight=jnp.full((G, P), cfg.inflight_limit, I32),
        send_next=jnp.full((G, P), 5, I32),
        next_idx=jnp.full((G, P), 1, I32),
        sent_at=jnp.zeros((G, P), I32),
        hb_due=jnp.zeros((G,), I32),
    )
    st2, out, _ = node_step(cfg, st, Messages.empty(cfg),
                            HostInbox.empty(cfg))
    # Heartbeats to both real peers despite the saturated window...
    assert bool(out.ae_valid[1, 0]) and bool(out.ae_valid[2, 0])
    assert int(out.ae_n[1, 0]) == 0 and int(out.ae_n[2, 0]) == 0
    # ...without occupying data slots or spawning hb slots past the cap.
    assert int(st2.inflight[0, 1]) == cfg.inflight_limit
    assert int(st2.hb_inflight[0, 1]) == 0
