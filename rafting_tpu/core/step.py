"""The vectorized Multi-Raft step kernel.

``node_step`` advances EVERY Raft group on a node by one logical tick in a
single fused XLA program: message-driven term sync, vote grant/tally,
AppendEntries consistency + conflict handling, leader bookkeeping, timer
expiry, client submission, replication fan-out and quorum commit — all as
masked vector operations over group-major arrays.

This replaces the reference's entire per-group concurrency layer (event loops,
CAS role switches, timer fencing: support/EventLoop.java, context/
RaftRoutine.java:86-216) with data parallelism.  Semantics are kept faithful
to the reference's Raft implementation; each phase cites the Java code whose
behavior it vectorizes.

Vectorization notes (why no per-peer sequential folds are needed):

* AppendEntries / InstallSnapshot requests: only ONE peer can be the
  current-term leader of a group (election safety), so after term sync at
  most one inbound request per group passes the term check — selecting it
  with an argmax over the peer axis is equivalent to processing peers in
  order.
* Responses (AE replies, vote replies): pure elementwise [G, P] updates.
* Vote requests: grant exclusivity within a tick is the only order-dependent
  rule; granting the lowest-indexed eligible requester reproduces the
  sequential fold exactly.

Phase order within a tick (messages produced in tick t are delivered in t+1):
  1. term sync           — step down on any higher inbound term
  2. vote requests       — grant PreVote/RequestVote, produce replies
  3. vote responses      — tally; PRE_CANDIDATE→CANDIDATE→LEADER transitions
  4. AppendEntries reqs  — consistency check, conflict truncate, append, commit
  5. InstallSnapshot     — offer handling + completion events from host
  6. AppendEntries resps — leader match/next bookkeeping
  6b. read evidence      — same-term ack receipts/echoes feed the barrier
  6c. CheckQuorum        — leader with no voter-quorum contact within an
                           election timeout steps down (cfg.check_quorum;
                           closes the lease: 8b aborts its pending reads)
  7. timers              — election timeout → PreVote round / new election
                           (voters only; TimeoutNow → immediate candidacy)
  7b. transfer intake    — leadership-transfer requests latch/abort; a
                           pending transfer fences submissions
  8. submissions         — leader accepts client commands into the log
  8b. read plane         — stamp ReadIndex batches, release on quorum
                           barrier (lease fast path: evidence of this
                           tick, or of the cfg.lease_carry_ticks before)
  8c. membership         — config-change intake (§6 joint consensus) +
                           automatic C_new leave once C_old,new commits
  9. replication         — leader builds AppendEntries / snapshot offers
                           over MEMBER lanes (+ barrier-kicked heartbeats,
                           tick-stamped); TimeoutNow to a caught-up target
 10. commit advance      — masked-quorum order statistic over matchIndex
                           (joint: both voter sets), own-term rule; a
                           removed leader resigns once C_new commits
 11. flight recorder     — branchless per-group event-ring writes of the
                           tick's phase-boundary events (cfg.trace_depth;
                           compiled away entirely when 0)

Hibernation (cfg.hibernate; compiled away entirely when off) threads
through phases 2, 4, 6, 6b, 6c, 7 and 9: the rule and its proof stand at
the top of ``node_step``.
"""

from __future__ import annotations

import functools
from functools import partial
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.select import take_plane, take_slots
from . import packing
from .packing import ColumnLayout, Layout, RowLayout
from .types import (
    CANDIDATE, FOLLOWER, LEADER, NIL, PRE_CANDIDATE, I32,
    EngineConfig, Hibernate, HostInbox, LogState, Messages, RaftState,
    StepInfo, conf_learners_of, conf_new_of, conf_pack, conf_voters_of,
    init_state,
)

Array = jax.Array

# StepInfo.debug_viol codes (cfg.debug_checks; see the check block at the
# end of node_step).
DEBUG_CODES = {
    1: "live log window exceeds ring capacity",
    2: "commit passed the log end",
    3: "term regressed",
    4: "continuing leader's matchIndex moved backwards",
    5: "candidate ballot is not itself",
    6: "commit regressed",
    7: "pipeline head behind ack base",
    8: "read FIFO length out of range",
    9: "active config has no voters",
}


def raise_debug_violations(info, where: str = "") -> None:
    """Host-side consumer of StepInfo.debug_viol: raise naming the group
    and the violated invariant (the assert analog of the reference's
    AssertionError surfacing, pinned to the faulting phase)."""
    import numpy as np

    viol = np.asarray(info.debug_viol)
    bad = np.nonzero(viol)
    if len(bad[0]):
        first = tuple(int(i) for i in (b[0] for b in bad))
        code = int(viol[first])
        raise AssertionError(
            f"kernel invariant violated{' in ' + where if where else ''}: "
            f"lane {first} code {code} "
            f"({DEBUG_CODES.get(code, 'unknown')}); "
            f"{len(bad[0])} lane(s) total")


# ---------------------------------------------------------------------------
# Log-ring primitives.  The log is a per-group ring of entry terms: index i
# lives at slot i % L.  Entries (base, last] are live; `base` carries
# base_term (the snapshot milestone, reference StableLock.java:82-91).
# ---------------------------------------------------------------------------

def ring_term_at(log: LogState, idx: Array) -> Array:
    """Term of entry `idx` per group ([G] -> [G]).

    idx == base  -> base_term (milestone);  idx < base -> compacted (returns
    base_term; callers treat anything <= base as matching — compacted entries
    are committed, hence matched, the reference's purgeEntries rationale,
    Follower.java:209-221).  idx > last -> -1 (absent).
    """
    return ring_terms_batch(log, idx[:, None])[:, 0]


def ring_terms_batch(log: LogState, idx: Array) -> Array:
    """Terms for a [G, K] index matrix (absent -> -1)."""
    L = log.term.shape[1]
    t = take_slots(log.term, jnp.remainder(idx, L))
    return jnp.where(idx <= log.base[:, None], log.base_term[:, None],
                     jnp.where(idx <= log.last[:, None], t, jnp.asarray(-1, I32)))


def ring_write_batch(log_term: Array, idx: Array, vals: Array, mask: Array) -> Array:
    """Masked write of entry terms at [G, K] indices into the [G, L] ring
    (a row's masked indices are distinct mod L: K <= L consecutive ones).
    K selects over the whole ring, one pass once fused."""
    L = log_term.shape[1]
    j = jnp.arange(L, dtype=I32)[None, :]
    slot = jnp.remainder(idx, L)
    for k in range(idx.shape[1]):
        hit = (j == slot[:, k:k + 1]) & mask[:, k:k + 1]
        log_term = jnp.where(hit, vals[:, k:k + 1], log_term)
    return log_term


def ring_span(L: int, start: Array, n: Array) -> Array:
    """[G, L] mask of the ring slots that hold the ``n`` consecutive
    indices from ``start`` ([G] each, 0 <= n <= L): where one value goes
    to the whole span (a submission's term, a cleared config word) the
    write is one select on it."""
    j = jnp.arange(L, dtype=I32)[None, :]
    return jnp.remainder(j - start[:, None], L) < n[:, None]


def ring_conf_batch(log: LogState, idx: Array) -> Array:
    """Packed config words for a [G, K] index matrix.

    0 outside the live window (compacted entries' configs are folded into
    ``base_conf``; absent entries carry nothing) — the AE build reads
    entry config words with exactly these semantics, so followers adopt
    configs with the same window rules as terms."""
    L = log.conf.shape[1]
    w = take_slots(log.conf, jnp.remainder(idx, L))
    live = (idx > log.base[:, None]) & (idx <= log.last[:, None])
    return jnp.where(live, w, jnp.asarray(0, I32))


def latest_conf(log: LogState, upto: Array) -> Tuple[Array, Array]:
    """The active configuration per group: ``(conf_idx, conf_word)`` of the
    latest config entry in ``(base, min(upto, last)]``, falling back to
    ``(0, base_conf)`` when none is live.

    The §6 apply-on-append rule AND its truncation rollback in one
    derivation: a node uses the newest config present in its log whether
    committed or not, and a conflict truncation that removes an
    uncommitted config entry automatically reverts to the previous one —
    no separate rollback state to maintain.  Two [G, L] sweeps: one finds
    the index, one selects its word (``take_slots``, as every ring read)."""
    G, L = log.conf.shape
    j = jnp.arange(L, dtype=I32)[None, :]
    # The unique index congruent to slot j (mod L) within (last-L, last].
    idx = log.last[:, None] - jnp.remainder(log.last[:, None] - j, L)
    isc = (idx > log.base[:, None]) & (idx <= upto[:, None]) \
        & (log.conf != 0)
    cidx = jnp.where(isc, idx, 0).max(axis=1)
    w = take_slots(log.conf, jnp.remainder(cidx, L)[:, None])[:, 0]
    has = cidx > 0
    return (jnp.where(has, cidx, 0),
            jnp.where(has, w, log.base_conf))


def mask_bits(mask: Array, P: int) -> Array:
    """Expand [G] peer bitmasks into a [G, P] boolean matrix."""
    return ((mask[:, None] >> jnp.arange(P, dtype=I32)[None, :]) & 1) > 0


def dual_quorum(flags: Array, voters: Array, voters_new: Array) -> Array:
    """Popcount-over-masked-lanes quorum: do ``flags`` [G, P] cover a
    majority of ``voters`` — and, when joint (``voters_new`` nonzero), a
    majority of ``voters_new`` TOO (Raft §6: joint decisions need both)?
    Used by vote tallies, PreVote tallies and the leader readiness gate;
    the commit quorum is the order-statistic analog in ops/quorum.py."""
    P = flags.shape[1]
    vb = mask_bits(voters, P)
    nb = mask_bits(voters_new, P)
    ok_v = (flags & vb).sum(axis=1) >= vb.sum(axis=1) // 2 + 1
    ok_n = (flags & nb).sum(axis=1) >= nb.sum(axis=1) // 2 + 1
    return ok_v & ((voters_new == 0) | ok_n)


def _pick_peer(flag_pg: Array) -> Tuple[Array, Array]:
    """Select the lowest-indexed peer whose flag is set, per group.

    Returns (peer_index [G], any_flag [G])."""
    any_f = flag_pg.any(axis=0)
    return jnp.argmax(flag_pg, axis=0).astype(I32), any_f


# ---------------------------------------------------------------------------
# The step
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnums=0, donate_argnums=1)
def node_step(cfg: EngineConfig, state: RaftState, inbox: Messages,
              host: HostInbox) -> Tuple[RaftState, Messages, StepInfo]:
    G, P, B, L, S = (cfg.n_groups, cfg.n_peers, cfg.batch, cfg.log_slots,
                     cfg.max_submit)
    s = state
    # The clock is the host's to advance (HostInbox.clock): by 1 on the
    # step a period's timer starts, by 0 on a step that arriving work
    # starts inside the period.  Every deadline below compares against
    # it, so the steps of one period are one tick of the protocol's
    # clock, delivered in pieces.
    now = s.now + host.clock
    rng, k_to = jax.random.split(s.rng)
    # One randomized election window per group per tick, consumed by whichever
    # lanes reset their timer (reference RaftConfig.electionTimeout re-draws on
    # every read, support/RaftConfig.java:187-190).
    rand_to = jax.random.randint(k_to, (G,), cfg.election_ticks,
                                 2 * cfg.election_ticks, dtype=I32)

    me = s.node_id
    peer_ids = jnp.arange(P, dtype=I32)
    self_hot = peer_ids[None, :] == me            # [1, P] one-hot row for self
    not_me_col = (peer_ids != me)[:, None]        # [P, 1] mask over peer axis

    active = s.active
    term, role, voted = s.term, s.role, s.voted_for
    leader_id, commit = s.leader_id, s.commit
    log = s.log
    next_idx, match_idx = s.next_idx, s.match_idx
    own_from = s.own_from
    send_next, inflight = s.send_next, s.inflight
    hb_inflight = s.hb_inflight
    sent_at, need_snap = s.sent_at, s.need_snap
    ok_at, fail_at, fail_streak = s.ok_at, s.fail_at, s.fail_streak
    votes, prevotes = s.votes, s.prevotes
    elect_dl, hb_due = s.elect_deadline, s.hb_due

    old_term, old_voted, old_last = term, voted, log.last

    # ---- hibernation (cfg.hibernate) ---------------------------------------
    # TiKV's Hibernate Region: a group nobody has asked anything of for an
    # election timeout stops ticking, and the first request or message
    # wakes it.  The lanes are Hibernate's (asleep, busy_at, slept), the
    # wire carries one flag each way (ae_sleep, aer_asleep), the host one
    # signal (HostInbox.wake).  With the field off none of this traces.
    #
    # Entry.  A LEADER is `quiet` when nothing but heartbeats and their
    # acknowledgements has touched the lane for election_ticks ticks of
    # its clock (busy_at), every member's match equals its last index,
    # its commit equals it, and no read batch, config change, joint
    # config, transfer or snapshot offer is pending (the apply frontier
    # is the host's, which applies off its commit mirror whether the lane
    # ticks or not: the device holds none to wait for).  While quiet, its
    # cadence heartbeats carry ae_sleep.  A FOLLOWER that accepts such a
    # heartbeat at its own term with nothing in it, its log ending where
    # the leader's does and its commit there too falls asleep and says so
    # (aer_asleep).  The leader latches each member's word (slept; only
    # an echo of a heartbeat sent inside this quiet stretch counts) and
    # falls asleep itself when EVERY member has given it, until when it
    # heartbeats as ever: an awake follower of a sleeping leader would
    # time out and disturb the rest.  What the acknowledgements prove is
    # that nothing is in flight, so entry clears the lane's window
    # counters (a phantom slot left by a merged reply would otherwise
    # stand for good, with no heartbeat to time it out) and its lease
    # evidence.
    # Asleep.  A leader opens no heartbeat round (phase 9: hb_due does not
    # fire), runs no CheckQuorum (6c) and stores no lease evidence (6b); a
    # follower's election timer does not expire (7) and it grants no
    # pre-vote on the strength of an elect_dl it slept past (2).  The
    # step still runs over the lane; it produces nothing.
    # Wake.  Any message for the lane other than the two that keep it
    # asleep (the sleep heartbeat of its own leader's term that it still
    # agrees to; the acknowledgement that says asleep), the host's
    # peer-lost signal, and on a leader any request of the host's (a
    # submit, a read, a config change, a transfer, a compaction grant, an
    # installed snapshot).  A woken follower starts a whole new election
    # timeout; a woken leader heartbeats in the same step, unflagged,
    # which wakes its followers.
    #
    # Sleep may only LENGTHEN a follower's promise and END a leader's
    # lease.  Five ways that could fail, each closed here and each a test
    # (tests/test_hibernate.py):
    # (a) a promise cut short: lease_open (2) reads elect_dl, which a
    #     sleeper leaves where its last heartbeat put it.  Asleep, the
    #     deadline is taken as not reached however long ago it passed,
    #     and the wake step itself still refuses; waking sets elect_dl a
    #     whole timeout ahead.  No instant's promise is earlier for
    #     having slept.
    # (b) a lease that spans a sleep: entry zeroes read_evid, no evidence
    #     is stored in a step the lane entered asleep (the wake step
    #     included: what arrives then answers a heartbeat from before),
    #     so a woken leader's first read is stamped against nothing and
    #     pays a barrier (read_kick).
    # (c) a leader deposed in its sleep (cut off; its followers woken by
    #     the peer-lost signal elected another): it holds no evidence,
    #     its barrier heartbeat is answered at a higher term or not at
    #     all, and the read fails or waits; it is never served.
    # (d) a sleep heartbeat lost, duplicated or overtaken.  Lost: the
    #     leader keeps proposing.  Behind a data AE: the follower's log
    #     no longer ends at prev_idx, so it stays awake.  Behind an
    #     unflagged heartbeat, or twice: the follower may fall asleep
    #     under an awake leader, whose next cadence heartbeat (unflagged:
    #     it is not quiet, or it would be proposing) wakes it within one
    #     heartbeat period, and whose latch ignores the word (the echo
    #     predates busy_at + election_ticks).  At a stale term: the
    #     heartbeat fails the term check, is not the selected AE, and so
    #     wakes instead.
    # (e) the field off: every line below is under `if hiber`.
    strict = not cfg.read_lease      # 6b: stamps ordered by steps
    hiber = cfg.hibernate
    if hiber:
        asleep0, busy_at, slept = s.hib.asleep, s.hib.busy_at, s.hib.slept
        host_req = ((host.submit_n > 0) | (host.read_n > 0)
                    | (host.conf_voters != 0) | (host.xfer_target >= 0)
                    | (host.compact_to > 0) | host.snap_done)

    # ---- 0. membership view C0 (tick-start) -------------------------------
    # The active config is a function of the log (§6 apply-on-append +
    # truncation rollback, see latest_conf); the state carries it as the
    # conf_idx/conf_word cache, re-derived at the end of every tick's log
    # mutations — so C0 is two state reads, not a [G, L] sweep.  C0
    # anchors the vote/prevote tallies (phase 3): a tally must count
    # against the config of the log the candidacy was launched from — the
    # same log whose position the vote requests carried.
    cidx0, w0 = s.conf_idx, s.conf_word
    voters0 = conf_voters_of(w0)
    vnew0 = conf_new_of(w0)

    # ---- 1. term sync: adopt the highest real term seen this tick ---------
    # (the universal Raft rule; reference applies it per-RPC via
    # switchTo(Follower, term): Follower.java:45-47, Candidate.java:28-41,
    # Leader step-down Leader.java:224-227.  PreVote requests are excluded:
    # their term is speculative and must not bump ours.)
    neg = jnp.asarray(-1, I32)
    def masked(valid, t):
        return jnp.where(valid, t, neg)
    mt = functools.reduce(jnp.maximum, [
        masked(inbox.ae_valid, inbox.ae_term),
        masked(inbox.aer_valid, inbox.aer_term),
        masked(inbox.rv_valid & ~inbox.rv_prevote, inbox.rv_term),
        masked(inbox.rvr_valid, inbox.rvr_term),
        masked(inbox.is_valid, inbox.is_term),
        masked(inbox.isr_valid, inbox.isr_term),
        masked(inbox.tn_valid, inbox.tn_term),
    ]).max(axis=0)                                           # [G]
    stepdown = active & (mt > term)
    term = jnp.where(stepdown, mt, term)
    role = jnp.where(stepdown, FOLLOWER, role)
    voted = jnp.where(stepdown, NIL, voted)
    leader_id = jnp.where(stepdown, NIL, leader_id)
    elect_dl = jnp.where(stepdown, now + rand_to, elect_dl)

    last_term_v = ring_term_at(log, log.last)

    # ---- 2. vote requests --------------------------------------------------
    # (reference Follower.requestVote:108-127 / preVote:91-105.)
    rv_v = inbox.rv_valid & active[None, :] & not_me_col          # [P, G]
    pv = inbox.rv_prevote
    # Log up-to-date check (reference Follower.logUpToDate:193-207).
    utd = ((inbox.rv_last_term > last_term_v[None, :]) |
           ((inbox.rv_last_term == last_term_v[None, :]) &
            (inbox.rv_last_idx >= log.last[None, :])))
    # RequestVote eligibility: same term (sync already adopted any higher),
    # ballot unburned or already ours.
    elig_rv = (rv_v & ~pv & (inbox.rv_term == term[None, :]) & utd &
               ((voted[None, :] == NIL) | (voted[None, :] == peer_ids[:, None])))
    # Exclusivity: grant the lowest-indexed eligible requester (== the
    # sequential fold order).  Re-grants to the peer we already voted for
    # are always allowed.
    first_elig, _ = _pick_peer(elig_rv)
    grant_rv = elig_rv & ((voted[None, :] == peer_ids[:, None]) |
                          (peer_ids[:, None] == first_elig[None, :]))
    granted_any = (grant_rv & (voted[None, :] == NIL)).any(axis=0)
    voted = jnp.where(granted_any & (voted == NIL), first_elig, voted)
    elect_dl = jnp.where(grant_rv.any(axis=0), now + rand_to, elect_dl)
    # PreVote grant (reference Follower.preVote:91-105): only if we ourselves
    # have detected leader silence (lease), log up-to-date, term ahead.  No
    # durable state changes.
    lease_open = (now >= elect_dl) | (leader_id == NIL)
    if hiber:
        # (a): a sleeper's deadline is not reached, whatever the clock.
        lease_open = ((now >= elect_dl) & ~asleep0) | (leader_id == NIL)
    # The carried lease (6b) leans on this refusal, so two more nodes
    # keep it: one that restarted with a term on disk, for as long as a
    # lease it may have acknowledged can last (case b: leader_id is NIL
    # after a restart, which would open the vote at once), and a leader
    # that still hears acknowledgements (its own elect_dl, which 6b
    # refreshes and nothing else reads while it leads: it counts itself
    # in every evidence quorum, so it promises what its followers do).
    carry = cfg.lease_carry_ticks
    guard = s.lease
    if carry:
        lease_open = lease_open & (now >= guard.vote_hold)
    grant_pv = (rv_v & pv & (inbox.rv_term > term[None, :]) & utd &
                lease_open[None, :])
    out_rvr_valid = rv_v
    out_rvr_term = jnp.broadcast_to(term[None, :], (P, G))
    out_rvr_granted = jnp.where(pv, grant_pv, grant_rv)
    out_rvr_prevote = pv
    out_rvr_echo = inbox.rv_term

    # ---- 3. vote responses + tallies --------------------------------------
    rr = inbox.rvr_valid & active[None, :]
    # PreVote tally: accept grants only for the round we are still in — the
    # echoed requested term must equal term+1 (vectorized analog of AsyncHead
    # cancellation of stale rounds, Async.java:70-172).
    g_pv = (rr & inbox.rvr_prevote & inbox.rvr_granted &
            (role == PRE_CANDIDATE)[None, :] &
            (inbox.rvr_echo == (term + 1)[None, :]))
    prevotes = prevotes | g_pv.T
    # Real vote tally (reference Candidate.startElection:112-134): a grant
    # implies the responder adopted our term, so term equality is the fence.
    g_rv = (rr & ~inbox.rvr_prevote & inbox.rvr_granted &
            (role == CANDIDATE)[None, :] & (inbox.rvr_term == term[None, :]))
    votes = votes | g_rv.T

    # Tallies are popcount-over-masked-lanes quorums against C0 (§6: a
    # joint config needs a majority in BOTH voter sets; learners and
    # removed slots never count, though their grants are harmless).  The
    # PreVote and RequestVote tallies share one set of masks/thresholds
    # (both count against C0).
    vb0 = mask_bits(voters0, P)
    nb0 = mask_bits(vnew0, P)
    maj_v0 = vb0.sum(axis=1) // 2 + 1
    maj_n0 = nb0.sum(axis=1) // 2 + 1
    not_joint0 = vnew0 == 0

    def tally0(flags):
        return ((flags & vb0).sum(axis=1) >= maj_v0) \
            & (not_joint0 | ((flags & nb0).sum(axis=1) >= maj_n0))

    pv_win = (role == PRE_CANDIDATE) & tally0(prevotes)
    # PreVote majority -> real candidacy at term+1 (reference
    # Follower.prepareElection:264-267 -> trySwitchTo(Candidate, term+1)).
    become_cand_pv = pv_win
    term = jnp.where(become_cand_pv, term + 1, term)
    role = jnp.where(become_cand_pv, CANDIDATE, role)
    voted = jnp.where(become_cand_pv, me, voted)
    leader_id = jnp.where(become_cand_pv, NIL, leader_id)
    votes = jnp.where(become_cand_pv[:, None], self_hot, votes)
    elect_dl = jnp.where(become_cand_pv, now + rand_to, elect_dl)

    vote_win = (role == CANDIDATE) & tally0(votes)
    # Candidate majority -> Leader (reference Candidate.java:128-131 ->
    # Leader ctor + prepareReplication, Leader.java:25-50): reset the
    # replication matrix, health stats and heartbeat immediately.
    role = jnp.where(vote_win, LEADER, role)
    leader_id = jnp.where(vote_win, me, leader_id)
    next_idx = jnp.where(vote_win[:, None], log.last[:, None] + 1, next_idx)
    match_idx = jnp.where(vote_win[:, None], 0, match_idx)
    send_next = jnp.where(vote_win[:, None], log.last[:, None] + 1, send_next)
    inflight = jnp.where(vote_win[:, None], 0, inflight)
    hb_inflight = jnp.where(vote_win[:, None], 0, hb_inflight)
    need_snap = jnp.where(vote_win[:, None], False, need_snap)
    ok_at = jnp.where(vote_win[:, None], 0, ok_at)
    fail_at = jnp.where(vote_win[:, None], 0, fail_at)
    fail_streak = jnp.where(vote_win[:, None], 0, fail_streak)
    hb_due = jnp.where(vote_win, now, hb_due)
    # First index of OUR term as leader: the slot the no-op below takes
    # (or, with a full ring, the first future own entry).  Terms are
    # monotone along the log, so the phase-10 own-term commit rule is
    # exactly `quorum_idx >= own_from` — no ring gather on the hot path.
    own_from = jnp.where(vote_win, log.last + 1, own_from)
    # Raft §8 liveness: a fresh leader appends an OWN-TERM NO-OP entry so
    # its predecessors' entries become committable immediately — the
    # commit rule (phase 10, reference Leader.java:256-261) only counts a
    # quorum at the leader's own term, so without this a cluster with no
    # new client traffic never surfaces a deposed leader's
    # committed-at-majority suffix (the reference shares the gap; its
    # system test masks it with always-on traffic).  Skipped when the
    # ring is full — such a lane is already acceptance-stalled and drains
    # through compaction first.  The host stages the no-op durably with
    # an empty payload (StepInfo.noop_idx/noop_term), and followers adopt
    # it through ordinary replication; machines see one empty command.
    noop_ok = vote_win & (log.last - log.base < L)
    noop_idx = jnp.where(noop_ok, log.last + 1, 0)
    noop_term = jnp.where(noop_ok, term, 0)
    # Every term-ring write clears/overwrites the conf-ring slot too: a
    # reused ring slot must never leak a dead entry's config word into
    # the latest_conf derivation.
    noop_at = ring_span(L, log.last + 1, noop_ok.astype(I32))
    log = log.replace(
        term=jnp.where(noop_at, term[:, None], log.term),
        conf=jnp.where(noop_at, 0, log.conf),
        last=log.last + noop_ok.astype(I32))

    # ---- 4. AppendEntries requests ----------------------------------------
    # (reference Follower.appendEntries:35-88 — consistency check, conflict
    # truncation, append, passive commit.)  At most one inbound AE per group
    # passes the term check (single current-term leader), so we select it
    # with an argmax and process all groups at once.
    ae_v = inbox.ae_valid & active[None, :] & not_me_col
    ae_t_ok = ae_v & (inbox.ae_term == term[None, :])
    ae_peer, ae_any = _pick_peer(ae_t_ok)
    # A valid leader at our term: candidates/pre-candidates step down
    # (reference Candidate.appendEntries:28-41); election timer resets
    # (Follower.java:43).  A same-term leader receiving an AE is impossible
    # under election safety — guard so it never demotes itself.
    ae_any = ae_any & (role != LEADER)
    role = jnp.where(ae_any, FOLLOWER, role)
    leader_id = jnp.where(ae_any, ae_peer, leader_id)
    elect_dl = jnp.where(ae_any, now + rand_to, elect_dl)

    prev_i = take_plane(inbox.ae_prev_idx, ae_peer)
    prev_t = take_plane(inbox.ae_prev_term, ae_peer)
    n_e = take_plane(inbox.ae_n, ae_peer)
    lc = take_plane(inbox.ae_commit, ae_peer)
    ents = take_plane(inbox.ae_ents, ae_peer)                    # [G, B]
    cents = take_plane(inbox.ae_cents, ae_peer)                  # [G, B]
    # Bounded-window partial accept: the live window (base, last] must never
    # exceed the ring capacity L, or new entries would alias committed slots.
    # A follower whose compaction floor lags the leader's clamps the batch to
    # what fits; the success reply's match=tail makes the leader resume from
    # the clamped point, and the commit/compact cycle frees capacity.  (No
    # reference analog — RocksDB logs are unbounded; this is the flow-control
    # rule the HBM-resident ring requires.)
    n_e = jnp.clip(n_e, 0, jnp.maximum(log.base + L - prev_i, 0))
    # Consistency: prev entry matches, or prev is at/under our compaction
    # floor (compacted == committed == matched; reference
    # Follower.logContains:177-191 + purgeEntries:209-221).
    prev_match = ((prev_i <= log.base) |
                  ((prev_i <= log.last) & (ring_term_at(log, prev_i) == prev_t)))
    acc = ae_any & prev_match

    col = jnp.arange(B, dtype=I32)[None, :]
    idxs = prev_i[:, None] + 1 + col                             # [G, B]
    in_n = col < n_e[:, None]
    exists = (idxs <= log.last[:, None]) & (idxs > log.base[:, None])
    cur = ring_terms_batch(log, idxs)
    conflict = (acc[:, None] & in_n & exists & (cur != ents)).any(axis=1)
    wmask = acc[:, None] & in_n & (idxs > log.base[:, None])
    new_ring = ring_write_batch(log.term, idxs, ents, wmask)
    # Config adoption rides the same write mask: a follower appending a
    # config entry USES its config immediately (§6 apply-on-append via
    # the post-phase latest_conf derivation).
    new_cring = ring_write_batch(log.conf, idxs, cents, wmask)
    tail = prev_i + n_e
    # Conflict => truncate-then-append == overwrite + last = prev+n;
    # no conflict => never shrink (stale/duplicate RPC; reference
    # RocksLog.conflict:199-216 + truncate:219-225 + append:169-196).
    new_last = jnp.where(acc,
                         jnp.where(conflict, tail,
                                   jnp.maximum(log.last, tail)),
                         log.last)
    wrote = acc & (n_e > 0) & ((new_last != log.last) | conflict)
    app_from = jnp.where(wrote, prev_i + 1, jnp.zeros((G,), I32))
    app_to = jnp.where(wrote, new_last, jnp.zeros((G,), I32))
    log = log.replace(term=new_ring, conf=new_cring, last=new_last)
    # Passive commit (reference Follower.java:76-82), bounded by the
    # *verified* prefix prev+n — not our log tail, which may still hold an
    # unverified divergent suffix from a deposed leader (Raft fig. 2:
    # min(leaderCommit, index of last NEW entry)).
    commit = jnp.where(acc,
                       jnp.maximum(commit, jnp.minimum(lc, tail)),
                       commit)
    # Replies to every valid AE: the selected peer gets the real verdict;
    # stale-term senders get failure at our (newer) term.  Failure carries a
    # nextIndex hint = min(our last, prev-1) — an accelerated version of the
    # reference's log-scaled backoff (Leadership.updateIndex:75-114).
    is_sel = (peer_ids[:, None] == ae_peer[None, :]) & ae_t_ok
    out_aer_valid = ae_v
    out_aer_term = jnp.broadcast_to(term[None, :], (P, G))
    out_aer_success = is_sel & acc[None, :]
    out_aer_match = jnp.where(
        is_sel & acc[None, :], tail[None, :],
        jnp.minimum(log.last[None, :], inbox.ae_prev_idx - 1))
    # Echo whether the AE was empty (a heartbeat): its sender did not
    # charge the reply against the in-flight window (phase 9), so it must
    # not decrement it either.
    out_aer_empty = ae_v & (inbox.ae_n == 0)
    # Echo the occupancy flag (symmetric with is_probe): only replies to
    # OCCUPYING heartbeats release a sender slot — a reply to a
    # window-full exempt heartbeat must not free a slot whose real ack
    # was lost (it would disarm the RPC-timeout detector one cadence).
    out_aer_occ = ae_v & inbox.ae_occ
    # Echo the AE's send tick unconditionally (success or failure): any
    # same-term reply proves we processed the leader's AE — the read
    # plane's barrier evidence (the occupancy-echo idiom again).
    out_aer_tick = jnp.where(ae_v, inbox.ae_tick, 0)
    if strict:
        # The stamp counter's echo (6b), to a request at OUR term alone:
        # the counter starts over with every leadership, and the reply to
        # an older term's request carries our newer term, which its
        # sender may have been elected at since.
        out_aer_seq = jnp.where(ae_t_ok, inbox.ae_seq, 0)
    if hiber:
        # The sleep heartbeat this follower agrees to: the selected AE,
        # accepted, empty, flagged, and both logs and commits level.
        sleep_ok = (acc & take_plane(inbox.ae_sleep, ae_peer) & (n_e == 0)
                    & (log.last == prev_i) & (commit == lc)
                    & (commit == log.last))
        out_aer_asleep = keeps = is_sel & sleep_ok[None, :]
        # A leader's heartbeat acknowledgements, and among them those
        # that say asleep: neither counts as activity, the second does
        # not wake.
        hb_reply = (inbox.aer_valid & inbox.aer_empty & inbox.aer_success
                    & (inbox.aer_term == term[None, :]))
        q_aer = hb_reply & inbox.aer_asleep
        other = (inbox.rv_valid | inbox.rvr_valid | inbox.is_valid
                 | inbox.isr_valid | inbox.tn_valid)
        m_ok = active[None, :] & not_me_col
        msgs_busy = (m_ok & (inbox.ae_valid | other
                             | (inbox.aer_valid & ~hb_reply))).any(axis=0)
        loud = (m_ok & ((inbox.ae_valid & ~keeps) | other
                        | (inbox.aer_valid & ~q_aer))).any(axis=0)
        asleep = ((sleep_ok | asleep0) & ~loud & active
                  & ~(host.wake | (host_req & (role == LEADER))))
        woke = asleep0 & ~asleep
        # A woken follower starts a whole new timeout (a).
        elect_dl = jnp.where(woke & (role != LEADER), now + rand_to,
                             elect_dl)

    # ---- 5. InstallSnapshot ------------------------------------------------
    # Device plane: an offer merely tells the follower's host to start the
    # bulk download (side channel, reference EventNode.SnapChannel:122-267).
    # The host reports completion via HostInbox.snap_done (reference
    # RaftRoutine.restoreCheckpoint:482-541).
    is_v = inbox.is_valid & active[None, :] & not_me_col
    is_t_ok = is_v & (inbox.is_term == term[None, :])
    is_peer, is_any = _pick_peer(is_t_ok)
    is_any = is_any & (role != LEADER)
    role = jnp.where(is_any, FOLLOWER, role)
    leader_id = jnp.where(is_any, is_peer, leader_id)
    elect_dl = jnp.where(is_any, now + rand_to, elect_dl)
    off_idx = take_plane(inbox.is_idx, is_peer)
    off_term = take_plane(inbox.is_last_term, is_peer)
    off_conf = take_plane(inbox.is_conf, is_peer)
    # Success only once the milestone is covered: either our snapshot floor
    # already includes it, or we hold a matching entry at that index.  While
    # the bulk download is in flight we answer failure so the leader keeps
    # the installation pending (reference PendingSnapshot tracking,
    # SnapshotArchive.java:197-211).
    covered = ((off_idx <= log.base) |
               ((off_idx <= log.last) &
                (ring_term_at(log, off_idx) == off_term)))
    useful = is_any & ~covered
    snap_req = useful
    snap_from = jnp.where(useful, is_peer, 0)
    snap_idx_o = jnp.where(useful, off_idx, 0)
    snap_term_o = jnp.where(useful, off_term, 0)
    snap_conf_o = jnp.where(useful, off_conf, 0)
    is_sel_snap = (peer_ids[:, None] == is_peer[None, :]) & is_t_ok
    out_isr_valid = is_v
    out_isr_term = jnp.broadcast_to(term[None, :], (P, G))
    out_isr_success = is_sel_snap & covered[None, :]
    # Echo the window-exemption flag: a reply to a heartbeat-cadence
    # re-offer must not release a slot the offer never took (symmetric
    # with aer_empty).
    out_isr_probe = is_v & inbox.is_probe

    # Host finished installing a snapshot: adopt the milestone as the new
    # log floor.  InstallSnapshot receiver rule (Raft fig. 13): if we hold an
    # entry matching the snapshot's (lastIndex, lastTerm), retain the suffix
    # after it; otherwise the whole log is suspect — discard it.
    sd = host.snap_done & active & (host.snap_idx > log.base)
    tail_matches = ((host.snap_idx <= log.last) &
                    (ring_term_at(log, host.snap_idx) == host.snap_term))
    log = log.replace(
        base=jnp.where(sd, host.snap_idx, log.base),
        base_term=jnp.where(sd, host.snap_term, log.base_term),
        # The installed milestone's config becomes the derivation floor
        # (0 from a legacy host = keep the current base_conf).
        base_conf=jnp.where(sd & (host.snap_conf != 0), host.snap_conf,
                            log.base_conf),
        last=jnp.where(sd, jnp.where(tail_matches, log.last, host.snap_idx),
                       log.last),
    )
    commit = jnp.where(sd, jnp.maximum(commit, host.snap_idx), commit)

    # Compaction grant from host (snapshot taken at compact_to): raise floor,
    # but never past commit (reference compactLog gates on the snapshot
    # milestone, RaftRoutine.java:365-400).  The milestone term is read from
    # the ring *before* the floor moves — and so is the milestone CONFIG
    # (the latest config entry at/under the new floor folds into
    # base_conf before its ring slot leaves the live window).
    ct = jnp.minimum(host.compact_to, commit)
    do_c = active & (ct > log.base)
    ct_term = ring_term_at(log, ct)
    # ONE [G, L] conf sweep serves both consumers: the milestone config
    # (latest conf entry at/under the new floor, folded into base_conf)
    # and the post-compaction active view C1 — what the timers (campaign
    # eligibility), transfer intake and config-entry intake below act
    # on.  (C0 is the state cache; this is the tick's only sweep.)
    jL = jnp.arange(L, dtype=I32)[None, :]
    sw_idx = log.last[:, None] - jnp.remainder(log.last[:, None] - jL, L)
    sw_isc = (sw_idx > log.base[:, None]) & (log.conf != 0)
    cidx_all = jnp.where(sw_isc, sw_idx, 0).max(axis=1)
    w_all = take_slots(log.conf, jnp.remainder(cidx_all, L)[:, None])[:, 0]
    cidx_ct = jnp.where(sw_isc & (sw_idx <= ct[:, None]), sw_idx, 0) \
        .max(axis=1)
    w_ct = take_slots(log.conf, jnp.remainder(cidx_ct, L)[:, None])[:, 0]
    ct_conf = jnp.where(cidx_ct > 0, w_ct, log.base_conf)
    log = log.replace(base=jnp.where(do_c, ct, log.base),
                      base_term=jnp.where(do_c, ct_term, log.base_term),
                      base_conf=jnp.where(do_c, ct_conf, log.base_conf))
    live1 = cidx_all > log.base          # post-move floor
    cidx1 = jnp.where(live1, cidx_all, 0)
    w1 = jnp.where(live1, w_all, log.base_conf)
    voters1 = conf_voters_of(w1)
    vnew1 = conf_new_of(w1)
    lrn1 = conf_learners_of(w1)

    # ---- 6. AppendEntries responses (leader bookkeeping) -------------------
    # (reference Leader.java:224-243 + Leadership.State.updateIndex:75-114.)
    # Pure elementwise [G, P] updates.
    aer_r = (inbox.aer_valid & active[None, :] & (role == LEADER)[None, :] &
             (inbox.aer_term == term[None, :])).T                # [G, P]
    aer_suc = aer_r & inbox.aer_success.T
    aer_fail = aer_r & ~inbox.aer_success.T
    aer_m = inbox.aer_match.T
    m_new = jnp.maximum(match_idx, aer_m)
    match_idx = jnp.where(aer_suc, m_new, match_idx)
    nx = jnp.where(aer_suc, jnp.maximum(next_idx, m_new + 1),
                   jnp.where(aer_fail,
                             jnp.clip(aer_m + 1, 1, next_idx), next_idx))
    # Follower fell below our compaction floor -> needs a snapshot
    # (reference Leadership.java:111-113 pendingInstallation trigger).
    need_snap = jnp.where(aer_r, aer_fail & (nx <= log.base[:, None]),
                          need_snap)
    next_idx = jnp.maximum(nx, log.base[:, None] + 1)
    # Pipeline accounting: data-batch replies release a data slot;
    # heartbeat replies release a heartbeat slot ONLY when they echo the
    # occupancy flag (aer_empty & aer_occ) — the AE itself carries
    # whether it occupied a slot (ae_occ, phase 9; symmetric with
    # is_probe), so a reply to a window-full EXEMPT heartbeat can never
    # free a slot whose real ack was lost, and the window count stays
    # exact.  A rejection aborts the whole window so
    # replication resumes from the clamped next_idx (reference: nextIndex
    # rollback cancels optimistic sends, Leadership.updateIndex:75-114).
    aer_ack = aer_r & ~inbox.aer_empty.T
    aer_hb_ack = aer_r & inbox.aer_empty.T & inbox.aer_occ.T
    inflight = jnp.where(aer_ack, jnp.maximum(inflight - 1, 0), inflight)
    hb_inflight = jnp.where(aer_hb_ack, jnp.maximum(hb_inflight - 1, 0),
                            hb_inflight)
    inflight = jnp.where(aer_fail, 0, inflight)
    hb_inflight = jnp.where(aer_fail, 0, hb_inflight)
    send_next = jnp.where(aer_fail, next_idx, send_next)
    # Health evidence: any reply — grant or rejection — proves the peer
    # reachable (reference statSuccess on every response incl. rejects,
    # Leadership.java:53-63).
    ok_at = jnp.where(aer_r, now, ok_at)
    fail_streak = jnp.where(aer_r, 0, fail_streak)

    # ---- 6b. read-barrier evidence ----------------------------------------
    # A same-term AE reply proves its sender followed us when it processed
    # the AE (it reset its election timer, phase 4) — the leadership
    # confirmation the ReadIndex barrier needs.  Two anchorings, both
    # comparing only values of OUR OWN clock (stall-induced per-node
    # drift cannot skew them):
    #
    # * lease (cfg.read_lease): store the RECEIPT tick, gated by the echo
    #   freshness bound `now - aer_tick <= read_fresh_ticks`.  Receipt
    #   anchoring is stall-safe by the fault model itself: in-flight
    #   messages addressed to a stalled node are LOST, so anything in the
    #   inbox was sent one live tick ago and the follower processed our
    #   AE at most `read_fresh_ticks - 1` global ticks before receipt
    #   (duplicate-delivery chains add one tick each and require the
    #   receiver awake every hop, so the freshness bound caps them at one
    #   hop).  Term monotonicity then closes the proof: a write acked by
    #   a newer-term leader before a batch's stamp needs a majority at
    #   the newer term strictly earlier, which must intersect our
    #   same-term evidence majority — a node cannot return to an older
    #   term.  No clock-drift assumption anywhere.
    # * strict ReadIndex (etcd's ReadOnlySafe): store the ECHOED stamp
    #   counter, so release requires acks to AppendEntries that LEFT in or
    #   after the step of the stamp (the textbook dedicated confirmation
    #   round) — sound under arbitrary transport delay, with no assumption
    #   on any clock, one round trip slower.  "Ticks and steps" below has
    #   the counter and its proof.
    #
    # host.read_veto (host runtime detected a wall-clock tick gap) drops
    # stored AND same-tick evidence: a paused host's inbox may hold acks
    # queued before the pause, which receipt anchoring must not trust.
    #
    # Ticks and steps.  The proofs above speak of global ticks.  A node
    # under its own loop may take several steps in one period (a timer
    # step with host.clock 1, then steps with clock 0 as work arrives):
    # they are ONE tick of that model, delivered in pieces, all at one
    # value of `now`.
    # * lease: evidence stored in an earlier step of this `now` may
    #   release a read stamped in a later step of it (evid == stamp).
    #   That is the window a single tick always had, in wall time: an ack
    #   that reached the host early in a period waited in the inbox
    #   accumulator for the tick that stamped, beside it, a read that
    #   arrived up to a period later; both were then "this tick".  The
    #   follower side holds as before, because its vote-denying lease
    #   (phase 2 lease_open) runs on ITS `now`, which its arrival steps do
    #   not move either: no node's clock runs faster than its timer.
    #   Without a carried lease (below), evidence of an earlier `now`
    #   never releases (evid < stamp).
    # * strict: an AE sent in an earlier step of this `now` echoes
    #   aer_tick == now although it left before a read offered in a
    #   later step of it: that echo confirms nothing, so strict mode does
    #   not order by ticks at all.  It orders by STEPS: read_seq [G] moves
    #   by one in every step that stamps a batch (8b), the batch's stamp
    #   is the moved value, every AE that leaves in that step or a later
    #   one carries the counter as that step left it (phase 9 ae_seq >=
    #   the stamp) and every AE that left before it carries less.  A
    #   follower echoes the word of a request at its own term (phase 4),
    #   read_evid keeps the highest echo per peer, and the release rule is
    #   the one it always was: evidence >= stamp from a majority.  So a
    #   batch stamped in ANY step, the timer's or one that work started,
    #   is released only by acknowledgements of AEs that left in or after
    #   its step, whatever is duplicated, reordered or delayed.
    #   The counter lives within one continuous leadership at one term,
    #   beside the FIFO (8b keep_reads zeroes both and the evidence): a
    #   term has one leader and a node that loses the role cannot win it
    #   back at the same term, so the values a term's requests carry come
    #   from ONE run of the counter, and the term checks on both sides
    #   (phase 4: the echo; phase 6 aer_r: the reply) keep every other
    #   run's echoes out.  A follower that restarts echoes what it is
    #   sent and keeps nothing.  Nothing here reads a clock: the host's
    #   read_veto (which drops evidence) and read_fresh_ticks have
    #   nothing to guard in this mode.
    #
    # The carried lease (cfg.lease_carry_ticks = heartbeat_ticks - 1 > 0).
    # With a heartbeat every h ticks a lane hears acknowledgements in one
    # tick of h, so evidence of its own tick alone would leave the reads
    # of the other h - 1 to a barrier round trip each.  Evidence stored at
    # receipt tick r therefore releases batches stamped at r .. r + h - 1,
    # and none stamped later (8b; the next round is due by then).  The
    # anchoring stays the receipt: the echo bounds how old the
    # acknowledgement can be, and a lock-step cluster, whose round trip is
    # two ticks, would never see an echo-anchored lease of two.
    #
    # What the follower promised, in real time.  It processed our AE at
    # an instant p, no earlier than the instant our clock turned the
    # echoed send tick e >= r - read_fresh_ticks, and reset elect_dl to
    # ITS now + [T, 2T) (T = election_ticks).  Until its clock has
    # advanced T ticks it starts no pre-vote and grants none (phase 2),
    # and with pre_vote on a candidacy above our term begins with a
    # pre-vote majority, which must contain a member of our evidence
    # quorum.  A clock advances once a timer step, timer steps are a
    # period apart and at least half of one (a late step under
    # tick_stagger is followed by the next grid point more than half a
    # period away): k ticks take more than k - 2 periods.  The promise
    # lasts more than T - 2 periods after p.
    # What the lease covers, on our clock.  A batch stamped at s <= r +
    # h - 1 is released by an AE sent in tick e >= s - (lease_ticks - 1),
    # lease_ticks = h + read_fresh_ticks: from the turn of e to the stamp
    # our clock passes through at most lease_ticks ticks.  If our timer
    # is on time that is lease_ticks periods, and the host vetoes
    # (host.read_veto: all evidence dropped) once the wall clock says the
    # last lease_ticks ticks took more than lease_ticks + 1 periods
    # (runtime/node.py _hold_read_veto).  So a stamp lies within
    # lease_ticks + 1 periods of p, and the lease is sound when
    #
    #     lease_ticks + 1 <= T - 2, i.e. h + read_fresh_ticks + 3 <= T
    #
    # (EngineConfig.lease_carry_ticks: 0 where this fails, with its
    # arithmetic).  Four ways the lease could still outrun the promise,
    # each closed here and each a test (tests/test_lease_carry.py):
    # (a) our loop stood still, so our `now` lags the followers' clocks:
    #     the veto above (whoever steps nodes by hand is the host: a
    #     node that sat out a step is given read_veto when it wakes).
    # (b) a follower that acknowledged restarts inside the lease and comes
    #     back with leader_id NIL, which opens its vote: a node that
    #     recovers a term holds its pre-vote for lease_hold_ticks =
    #     lease_ticks + 3 ticks, more than lease_ticks + 1 periods
    #     (LeaseGuard.vote_hold; nothing on a first boot).
    # (c) a leadership transfer: TimeoutNow makes its target a candidate
    #     at once, with no pre-vote asked, so nobody's promise stands in
    #     the way.  The step that fires it drops the stored evidence
    #     (phase 9) and bars the carry for two election timeouts
    #     (LeaseGuard.carry_bar), the longest the target's candidacy can
    #     stay open on its timer: until then evidence releases its own
    #     tick only, as it always did.
    # (d) a configuration under which the inequality fails, or pre_vote
    #     off (a follower whose timer runs out bumps its term at once and
    #     asks for real votes, which carry no such refusal): no carry.
    # Two more members of an evidence quorum must keep the promise for it
    # to mean anything.  WE are one: a leader counts itself, so while it
    # hears acknowledgements it refuses pre-votes as a follower would
    # (elect_dl, which nothing else reads on a leader, is pushed T ahead
    # on every receipt below; a leader cut off from its acknowledgements
    # still opens its vote after T, which is what frees a group from an
    # outbound-only cut).  And a candidate whose election ran out asks
    # for pre-votes again instead of bumping its term unasked (phase 7).
    read_evid = s.read_evid
    if cfg.read_lease:
        evid_hit = aer_r & ~self_hot & \
            (now - inbox.aer_tick.T <= cfg.read_fresh_ticks)
        evid_val = jnp.broadcast_to(now, (G, P))
    else:
        evid_hit = aer_r & ~self_hot
        evid_val = jnp.maximum(read_evid, inbox.aer_seq.T)
    if hiber:
        # (b): nothing is stored in a step the lane entered asleep.  The
        # members' word is latched here: an acknowledgement that says
        # asleep to a heartbeat sent inside this quiet stretch.
        evid_hit = evid_hit & ~asleep0[:, None]
        slept = slept | (aer_r & q_aer.T & ~self_hot
                         & (inbox.aer_tick.T
                            >= (busy_at + cfg.election_ticks)[:, None]))
    read_evid = jnp.where(evid_hit, evid_val, read_evid)
    read_evid = jnp.where(host.read_veto, jnp.zeros_like(read_evid),
                          read_evid)
    if carry:
        elect_dl = jnp.where(evid_hit.any(axis=1),
                             now + cfg.election_ticks, elect_dl)

    # Snapshot response: success means the follower now covers our offered
    # milestone — resume log replication from just past our floor (reference
    # accomplishInstallation -> normal AppendEntries flow,
    # RaftRoutine.java:451-475).  Failure = still downloading; keep pending.
    isr_r = (inbox.isr_valid & active[None, :] & (role == LEADER)[None, :] &
             (inbox.isr_term == term[None, :])).T                # [G, P]
    isr_ok = isr_r & inbox.isr_success.T
    need_snap = jnp.where(isr_ok, False, need_snap)
    next_idx = jnp.where(isr_ok,
                         jnp.maximum(next_idx, log.base[:, None] + 1),
                         next_idx)
    match_idx = jnp.where(isr_ok, jnp.maximum(match_idx, log.base[:, None]),
                          match_idx)
    # Only replies to WINDOW-OCCUPYING offers release a slot (probe
    # re-offers are echoed as isr_probe — symmetric with aer_empty).
    isr_ack = isr_r & ~inbox.isr_probe.T
    inflight = jnp.where(isr_ack, jnp.maximum(inflight - 1, 0), inflight)
    ok_at = jnp.where(isr_r, now, ok_at)
    fail_streak = jnp.where(isr_r, 0, fail_streak)
    # The pipeline head never trails the ack base.
    send_next = jnp.maximum(send_next, next_idx)

    # ---- 6c. CheckQuorum step-down (cfg.check_quorum) ---------------------
    # "Paxos vs Raft" (arXiv:2004.05074) leader stickiness: an inbound-cut
    # leader hears no higher term — phase 1 can never depose it — yet its
    # outbound heartbeats keep suppressing every follower's election
    # timer, so the group is hostage until the cut heals.  Remedy: track
    # the last-heard tick per peer (any valid inbound RPC counts,
    # term-independent — even a stale reply proves the link alive) and
    # step down when one election timeout passes without contact from a
    # voter quorum (joint: both sets, same §6 rule as every quorum).
    # Placement before 7b/8/8b makes the containment automatic: the
    # pending transfer aborts (7b keep_x), submissions are refused
    # (phase 8 role gate), and — the safety-critical part — phase 8b's
    # keep_reads drops the pending lease reads AND zeroes read_evid, so a
    # deposed-but-unaware leader can neither strand writes nor serve
    # stale reads off a dead lease.  The stepped-down node re-arms its
    # election timer and campaigns through PreVote, which cannot disturb
    # a healthy majority's new leader (speculative terms never bump).
    qc = s.qc
    if cfg.check_quorum:
        from ..ops.quorum import contact_quorum
        heard_any = (inbox.ae_valid | inbox.aer_valid | inbox.rv_valid
                     | inbox.rvr_valid | inbox.is_valid | inbox.isr_valid
                     | inbox.tn_valid).T & active[:, None] & ~self_hot
        heard = jnp.where(heard_any, now, qc.heard)
        # The window anchors at election win; a due check that passes
        # advances it (fresh contact must then arrive within the NEXT
        # window — etcd's recent-active reset, vectorized).
        since = jnp.where(vote_win, now, qc.since)
        cq_due = active & (role == LEADER) \
            & (now - since >= cfg.election_ticks)
        if hiber:
            # No leader is deposed for the silence it agreed to: none is
            # due asleep, and a woken one gets a whole window.
            since = jnp.where(woke, now, since)
            cq_due = cq_due & ~asleep & ~woke
        cq_ok = contact_quorum(voters1, vnew1, me, heard, since)
        cq_down = cq_due & ~cq_ok
        since = jnp.where(cq_due & cq_ok, now, since)
        role = jnp.where(cq_down, FOLLOWER, role)
        leader_id = jnp.where(cq_down, NIL, leader_id)
        elect_dl = jnp.where(cq_down, now + rand_to, elect_dl)
        qc = qc.replace(heard=heard, since=since)
        # Vetoed lease reads: everything pending in the FIFO at the
        # moment of step-down (8b reads the same s.rq_* and will abort
        # them via keep_reads — this lane just counts what was saved).
        K_cq = cfg.read_slots
        jcol = jnp.arange(K_cq, dtype=I32)[None, :]
        # FIFO position of each physical slot: the pending ones are the
        # first rq_len from the head.
        pend_pos = jnp.remainder(jcol - s.rq_head[:, None], K_cq)
        pend_n = jnp.where(pend_pos < s.rq_len[:, None], s.rq_n, 0) \
            .sum(axis=1)
        cq_veto = jnp.where(cq_down, pend_n, 0)
    else:
        cq_down = None
        cq_veto = None

    # ---- 7. timers ---------------------------------------------------------
    # (reference RaftRoutine.electionTimeout:65-77 -> Follower.onTimeout:
    # 156-168: PreVote round if enabled, else direct candidacy; candidate
    # timeout restarts the election at term+1, Candidate.onTimeout:82-88.)
    # Only VOTERS campaign: learners and removed slots replicate but never
    # start elections (§6 — a server not in the newest config of its own
    # log stays quiet; it still grants votes and accepts AEs).
    voter_self = (jnp.right_shift(voters1 | vnew1, me) & 1) > 0
    expired = active & (now >= elect_dl) & (role != LEADER) & voter_self
    if hiber:
        expired = expired & ~asleep
    if cfg.pre_vote and carry:
        # The carried lease rests on pre-votes being asked (6b): a
        # candidate whose election ran out asks again, as a follower
        # would, instead of taking the next term unasked.
        start_pre = expired
        timer_cand = jnp.zeros((G,), jnp.bool_)
    elif cfg.pre_vote:
        start_pre = expired & ((role == FOLLOWER) | (role == PRE_CANDIDATE))
        timer_cand = expired & (role == CANDIDATE)
    else:
        start_pre = jnp.zeros((G,), jnp.bool_)
        timer_cand = expired
    # TimeoutNow (§3.10 leadership transfer): a caught-up voter told to
    # campaign does so IMMEDIATELY — no PreVote round, no waiting out the
    # election timer (the whole point: the old leader is alive and its
    # heartbeats would defeat PreVote's leader-stickiness check).  The
    # term check fences stale/duplicate copies: once the target bumps to
    # term+1, re-sent TimeoutNows at the old term are ignored.
    tn_cand = ((inbox.tn_valid & active[None, :] & not_me_col
                & (inbox.tn_term == term[None, :])).any(axis=0)
               & voter_self & (role != LEADER))
    start_pre = start_pre & ~tn_cand
    timer_cand = timer_cand | tn_cand
    term = jnp.where(timer_cand, term + 1, term)
    voted = jnp.where(timer_cand, me, voted)
    role = jnp.where(timer_cand, CANDIDATE, jnp.where(start_pre, PRE_CANDIDATE, role))
    leader_id = jnp.where(timer_cand | start_pre, NIL, leader_id)
    votes = jnp.where(timer_cand[:, None], self_hot, votes)
    prevotes = jnp.where(start_pre[:, None], self_hot, prevotes)
    elect_dl = jnp.where(timer_cand | start_pre, now + rand_to, elect_dl)

    became_cand = become_cand_pv | timer_cand
    last_term_v = ring_term_at(log, log.last)

    # ---- 7b. leadership-transfer intake/abort (§3.10) ---------------------
    # A pending transfer lives only within one continuous leadership at
    # one term and at most one election timeout long; anything else —
    # step-down, term bump, the deadline — aborts it (the host fails the
    # caller's future; the transfer may still have succeeded, which the
    # caller observes via the leader hint, same contract as a submit
    # abort).  While pending, client submissions and config changes are
    # FENCED so the target's catch-up condition (match == last) is a
    # stable target.
    pend0 = s.xfer_to != NIL
    keep_x = (pend0 & active & (role == LEADER) & (term == s.term)
              & (now < s.xfer_dl))
    xfer_abort = pend0 & ~keep_x
    xfer_to = jnp.where(keep_x, s.xfer_to, NIL)
    xfer_dl = jnp.where(keep_x, s.xfer_dl, 0)
    tgt = host.xfer_target
    tgt_voter = (jnp.right_shift(voters1 | vnew1,
                                 jnp.clip(tgt, 0, P - 1)) & 1) > 0
    take_x = (active & (role == LEADER) & (xfer_to == NIL)
              & (tgt >= 0) & (tgt < P) & (tgt != me) & tgt_voter)
    xfer_to = jnp.where(take_x, tgt, xfer_to)
    xfer_dl = jnp.where(take_x, now + cfg.election_ticks, xfer_dl)
    fenced = xfer_to != NIL

    # ---- 8. client submissions --------------------------------------------
    # (reference RaftStub.submit -> Leader.acceptCommand -> log.newEntry,
    # RaftStub.java:65-74, Leader.java:128-140, RocksLog.java:82-89.)
    # Capacity gate: the ring must keep (last - base) <= L.  A pending
    # leadership transfer fences intake (7b).
    free = L - (log.last - log.base)
    n_acc = jnp.where(active & (role == LEADER) & ~fenced,
                      jnp.clip(host.submit_n, 0, jnp.minimum(free, S)), 0)
    sub_start = log.last + 1
    sub_at = ring_span(L, sub_start, n_acc)                      # n_acc <= S
    log = log.replace(term=jnp.where(sub_at, term[:, None], log.term),
                      conf=jnp.where(sub_at, 0, log.conf),
                      last=log.last + n_acc)
    app_from = jnp.where((n_acc > 0) & (app_from == 0), sub_start, app_from)
    app_to = jnp.where(n_acc > 0, log.last, app_to)

    # ---- 8b. linearizable read plane: intake + barrier release ------------
    # ReadIndex (Raft dissertation §6.4), vectorized: a read batch is
    # STAMPED with the leader's current commit index and RELEASED once a
    # majority confirms our leadership at/after the stamp (evidence from
    # phase 6b) — reads never touch the log.  Stamping with the
    # pre-phase-10 commit is sound: a write acknowledged to any client
    # before this tick was committed by the end of an earlier tick, so
    # the carried-in commit already covers it.
    from ..ops.quorum import read_barrier_release
    K = cfg.read_slots
    # Pending reads live only within one continuous leadership at one
    # term: any role/term change drops them (the host fails them with
    # NotLeader; reads never enter the log, so the retry is always safe).
    keep_reads = active & (role == LEADER) & (term == s.term)
    read_abort = (s.rq_len > 0) & ~keep_reads
    rq_head = jnp.where(keep_reads, s.rq_head, 0)
    rq_len = jnp.where(keep_reads, s.rq_len, 0)
    read_evid = jnp.where(keep_reads[:, None], read_evid, 0)
    rq_idx, rq_stamp, rq_n = s.rq_idx, s.rq_stamp, s.rq_n
    # Intake: one offered batch per group per tick, accepted whole when a
    # FIFO slot is free and our §8 no-op has committed (commit >= own_from
    # — a fresh leader's commit index may lag entries committed by its
    # predecessors until its own-term entry commits, Raft §5.4.2; serving
    # before that could miss them).
    # Either mode stamps in any step (6b, "ticks and steps"): the lease
    # with the tick, strict ReadIndex with its own counter, which this
    # step moves if it stamps.  (The literal is what is left of "strict
    # stamps at the timer alone": with it the lease's program stays, equation
    # for equation, the one tests/test_read_index.py pins.)
    stamp_open = True
    n_read = jnp.where(keep_reads & (commit >= own_from) & (rq_len < K)
                       & stamp_open,
                       jnp.maximum(host.read_n, 0), 0)
    read_acc = n_read > 0
    if strict:
        read_seq = jnp.where(keep_reads, s.read_seq, 0) \
            + read_acc.astype(I32)
        stamp = read_seq[:, None]
    else:
        stamp = now
    slot_in = (jnp.arange(K, dtype=I32)[None, :]
               == jnp.remainder(rq_head + rq_len, K)[:, None]) \
        & read_acc[:, None]                                      # [G, K]
    rq_idx = jnp.where(slot_in, commit[:, None], rq_idx)
    rq_stamp = jnp.where(slot_in, stamp, rq_stamp)
    rq_n = jnp.where(slot_in, n_read[:, None], rq_n)
    rq_len = rq_len + read_acc.astype(I32)
    read_index_out = jnp.where(read_acc, commit, 0)
    # Release (ops/quorum.py): with the lease, evidence received THIS
    # tick carries receipt == now == the fresh batch's stamp, so a
    # heartbeat-ack burst releases a same-tick read with zero extra round
    # trips — the lease fast path IS the general rule at its freshness
    # limit.  Strict mode can only release on a later step's echo.  A
    # carried lease (6b) lets a receipt reach `carry` ticks further,
    # unless a transfer has barred it (case c).
    if carry:
        n_rel, n_served, n_rel_own = read_barrier_release(
            voters1, vnew1, me, read_evid, rq_stamp, rq_head, rq_len, rq_n,
            jnp.where(now >= guard.carry_bar, carry, 0))
    else:
        n_rel, n_served = read_barrier_release(
            voters1, vnew1, me, read_evid, rq_stamp, rq_head, rq_len, rq_n)
        n_rel_own = n_rel
    rq_head = jnp.remainder(rq_head + n_rel, K)
    rq_len = rq_len - n_rel
    read_lease_hit = read_acc & (n_rel > 0) & (rq_len == 0)
    # The fresh batch is the FIFO's last: evidence of this tick alone
    # would have released fewer, so it went out on an earlier tick's.
    read_carried = read_lease_hit & (n_rel_own < n_rel)
    # A batch left pending kicks an immediate barrier heartbeat (phase 9)
    # instead of waiting out the cadence: release latency is one round
    # trip, not heartbeat_ticks + one round trip.
    read_kick = read_acc & (rq_len > 0)

    # ---- 8c. membership-change intake + automatic joint leave (§6) --------
    # A change request (HostInbox.conf_voters/conf_learners, the TARGET
    # config) becomes ONE log entry: a joint C_old,new entry when the
    # voter set moves, a simple entry when only learners change.  One
    # change in flight per group: intake is fenced while the latest
    # config entry is uncommitted, while joint, and while a leadership
    # transfer is pending.  When the joint entry commits, the C_new leave
    # entry is appended AUTOMATICALLY — the leader walks §6's two-entry
    # protocol without host round-trips.  Config entries take effect on
    # append (the next latest_conf derivation sees them): the leader
    # counts the very commit that seals a joint entry under BOTH sets.
    full_bits = jnp.asarray((1 << P) - 1, I32)
    hv = host.conf_voters & full_bits
    hl = host.conf_learners & full_bits & ~hv
    joint1 = vnew1 != 0
    pending1 = cidx1 > commit
    space = log.last - log.base < L
    may_append = active & (role == LEADER) & ~pending1 & space
    # One pack covers both request kinds: a learner-only change (target
    # voters == current) packs voters_new = 0 (simple entry).
    enter_word = conf_pack(voters1, jnp.where(hv == voters1, 0, hv), hl)
    want_enter = (may_append & ~joint1 & ~fenced & (hv != 0)
                  & (enter_word != w1))
    want_leave = may_append & joint1
    leave_word = conf_pack(vnew1, 0, lrn1)
    conf_app = want_enter | want_leave
    app_word = jnp.where(want_leave, leave_word, enter_word)
    nidx = log.last + 1
    conf_at = ring_span(L, nidx, conf_app.astype(I32))
    log = log.replace(
        term=jnp.where(conf_at, term[:, None], log.term),
        conf=jnp.where(conf_at, app_word[:, None], log.conf),
        last=log.last + conf_app.astype(I32))
    conf_app_idx = jnp.where(conf_app, nidx, 0)
    conf_app_term = jnp.where(conf_app, term, 0)
    conf_app_word = jnp.where(conf_app, app_word, 0)
    app_from = jnp.where(conf_app & (app_from == 0), nidx, app_from)
    app_to = jnp.where(conf_app, log.last, app_to)

    # Membership view C2: the end-of-tick active config — replication
    # fan-out, readiness, vote-solicitation targets and the commit quorum
    # all run against it.
    cidx2 = jnp.where(conf_app, nidx, cidx1)
    w2 = jnp.where(conf_app, app_word, w1)
    voters2 = conf_voters_of(w2)
    vnew2 = conf_new_of(w2)
    lrn2 = conf_learners_of(w2)
    member2 = mask_bits(voters2 | vnew2 | lrn2, P)              # [G, P]

    # ---- 9. replication fan-out -------------------------------------------
    # (reference Leader.replicateLog:142-245 — the hot loop, now a dense
    # (group x peer) batch build straight from the HBM ring, pipelined up to
    # `inflight_limit` un-acked batches per peer, Leadership.java:10-11.)
    # Fan-out only to MEMBER slots (voters, incoming voters, learners):
    # removed/never-added slots get no AEs, no heartbeats, no snapshot
    # offers — the membership masks gate the replication plane itself.
    lead_peer = (active & (role == LEADER))[:, None] & ~self_hot & member2
    # RPC timeout: the window has been un-acked too long.  Failure evidence
    # for the health stats (reference statFailure on unreachable,
    # Leadership.java:65-73) + window reset so replication restarts from the
    # ack base (reference AsyncFuture timeout, Async.java:177-256).
    # RPC timeout — the ONLY failure-evidence source, anchored to OUR OWN
    # last send on OUR OWN tick clock (reference: per-request Async
    # timeout feeding statFailure, Async.java:177-256, Leadership.java:
    # 65-73).  Occupying heartbeats (below) keep this armed on idle
    # leaders: a dead peer accumulates un-acked heartbeats and times out
    # exactly like a lost data window.  No reply-staleness heuristics —
    # they false-positive under free-running tick drift and wedge the
    # readiness gate shut via the recovery cool-down.
    timed_out = lead_peer & (inflight + hb_inflight > 0) & \
        (now - sent_at >= cfg.rpc_timeout_ticks)
    fail_streak = jnp.where(timed_out, fail_streak + 1, fail_streak)
    fail_at = jnp.where(timed_out, now, fail_at)
    send_next = jnp.where(timed_out, next_idx, send_next)
    inflight = jnp.where(timed_out, 0, inflight)
    hb_inflight = jnp.where(timed_out, 0, hb_inflight)

    heartbeat = (role == LEADER) & ((now >= hb_due) | read_kick)
    if hiber:
        lead = active & (role == LEADER)
        others = member2 & ~self_hot
        moved = active & (msgs_busy | host_req | host.wake | woke
                          | (term != s.term) | (role != s.role)
                          | (log.last != old_last) | (commit != s.commit))
        busy_at = jnp.where(moved, now, busy_at)
        slept = jnp.where(moved[:, None], False, slept)
        quiet = (lead & (now - busy_at >= cfg.election_ticks)
                 & (~others | (match_idx == log.last[:, None])).all(axis=1)
                 & (commit == log.last) & (rq_len == 0)
                 & (cidx2 <= commit) & (vnew2 == 0) & (xfer_to == NIL)
                 & ~need_snap.any(axis=1))
        go_sleep = quiet & ~asleep & (~others | slept).all(axis=1)
        asleep = asleep | go_sleep
        # An asleep leader's cadence does not fire; a woken one sends at
        # once (unflagged: it is not quiet), which wakes its followers.
        heartbeat = (role == LEADER) & (((now >= hb_due) & ~asleep)
                                       | read_kick | (woke & lead))
    has_data = (log.last[:, None] >= send_next) & ~need_snap
    n_avail = jnp.clip(log.last[:, None] - send_next + 1, 0, B)  # [G, P]
    # Data flows whenever the window has room; empty heartbeat AEs keep
    # the follower's election timer fed on the normal cadence even while
    # acks are in flight.  Their prev = send_next - 1 assumes the in-flight
    # batches arrive first — guaranteed by the transport's per-source
    # in-order delivery (transport/inbox.py); under loss the follower
    # rejects and the window resets, same as any failed AE.
    can_send = (inflight + hb_inflight) < cfg.inflight_limit
    send_data = lead_peer & ~need_snap & has_data & can_send
    # Heartbeat capacity reservation (reference: the in-flight budget is
    # divided for heartbeats so they keep flowing, Leader.java:162,
    # Leadership.java:10-11): an empty AE goes out on the heartbeat cadence
    # on every leader lane not shipping data this tick — INCLUDING lanes
    # whose window is full of lost batches, so a wedged window can never
    # starve the followers' election timers into a spurious election (any
    # valid AE at the leader's term resets the timer, phase 4).  While the
    # window has room the heartbeat OCCUPIES a slot (in the dedicated
    # hb_inflight lane, released by its aer_empty-echoed reply), which is
    # what arms the RPC-timeout failure detector on idle leaders; when the
    # window is full it goes out slot-exempt, keeping followers fed while
    # the stuck batches carry the timeout evidence.
    send_hb = lead_peer & ~need_snap & heartbeat[:, None] & ~send_data
    hb_occupy = send_hb & can_send
    send_ae = send_data | send_hb                                # [G, P]
    n_send = jnp.where(send_data, n_avail, 0)
    prev = send_next - 1
    # One read for all peers' batches: [G, P*B] -> [P, G, B].
    flat_idx = (send_next[:, :, None] + col[None, :, :]).reshape(G, P * B)
    ents_all = ring_terms_batch(log, flat_idx).reshape(G, P, B)
    cents_all = ring_conf_batch(log, flat_idx).reshape(G, P, B)
    prev_terms = ring_terms_batch(log, prev).T                   # [P, G]
    out_ae_valid = send_ae.T
    out_ae_term = jnp.broadcast_to(term[None, :], (P, G))
    out_ae_prev_idx = prev.T
    out_ae_prev_term = prev_terms
    out_ae_commit = jnp.broadcast_to(commit[None, :], (P, G))
    out_ae_n = n_send.T
    out_ae_ents = jnp.swapaxes(ents_all, 0, 1)                   # [P, G, B]
    out_ae_cents = jnp.swapaxes(cents_all, 0, 1)                 # [P, G, B]
    out_ae_occ = hb_occupy.T
    # Send tick, echoed back as aer_tick (read-barrier evidence, 6b).
    out_ae_tick = jnp.broadcast_to(now, (P, G)).astype(I32)
    if strict:
        # The stamp counter as this step leaves it, echoed back as
        # aer_seq (6b).
        out_ae_seq = jnp.broadcast_to(read_seq[None, :], (P, G))
    if hiber:
        out_ae_sleep = (send_hb & quiet[:, None]).T
    # Snapshot offer for laggards (reference Leader.java:168-190); occupies
    # the whole window (one offer at a time), re-offered on the heartbeat
    # cadence while un-acked — the re-offer is window-exempt like a
    # heartbeat (reference: the heartbeat replicateLog pass re-enters the
    # install branch, Leader.java:162-190), so the follower's election
    # timer stays fed through a long download even if offer acks are lost.
    send_is_win = lead_peer & need_snap & (inflight + hb_inflight == 0)
    send_is = send_is_win | (lead_peer & need_snap & heartbeat[:, None])
    out_is_valid = send_is.T
    out_is_term = jnp.broadcast_to(term[None, :], (P, G))
    out_is_idx = jnp.broadcast_to(log.base[None, :], (P, G))
    out_is_last_term = jnp.broadcast_to(log.base_term[None, :], (P, G))
    out_is_probe = (send_is & ~send_is_win).T
    # The offered milestone's config rides the offer: it becomes the
    # installer's base_conf (via the host snap_conf round trip).
    out_is_conf = jnp.broadcast_to(log.base_conf[None, :], (P, G))
    # Window accounting: data batches and the first snapshot offer occupy
    # data slots; in-window heartbeats occupy heartbeat slots; window-full
    # heartbeats and snapshot re-offers are slot-exempt (see above).  Any
    # occupying send refreshes the send clock.
    occupy = send_data | send_is_win
    send_next = jnp.where(send_data, send_next + n_send, send_next)
    inflight = jnp.where(occupy, inflight + 1, inflight)
    hb_inflight = jnp.where(hb_occupy, hb_inflight + 1, hb_inflight)
    sent_at = jnp.where(occupy | hb_occupy, now, sent_at)
    hb_due = jnp.where(heartbeat, now + cfg.heartbeat_ticks, hb_due)
    if hiber:
        # Entry: every member's word says nothing is in flight, whatever
        # the counters hold; the evidence goes with the heartbeats (b).
        gs = go_sleep[:, None]
        inflight = jnp.where(gs, 0, inflight)
        hb_inflight = jnp.where(gs, 0, hb_inflight)
        read_evid = jnp.where(gs, 0, read_evid)
        slept = jnp.where(gs, False, slept)

    # Leader readiness (reference Leader.isReady, Leader.java:52-64 +
    # Leadership.isReady/isUnhealthy, Leadership.java:44-51): a follower
    # counts as healthy once it has replied this leadership (ok_at > 0), is
    # not mid-snapshot-install, its timeout streak is within the critical
    # point, and its last failure is outside the recovery cool-down.
    healthy = (ok_at > 0) & ~need_snap & ~self_hot
    if cfg.avail_crit > 0:
        healthy = healthy & (fail_streak <= cfg.avail_crit)
    if cfg.recovery_ticks > 0:
        healthy = healthy & ((fail_at == 0) |
                             (now - fail_at >= cfg.recovery_ticks))
    # Readiness is a masked quorum over the ACTIVE config (joint: both
    # sets); self counts iff self is a voter.  A pending leadership
    # transfer reports not-ready — intake is fenced anyway, and the
    # host's refusal gate should say so before the queue does.
    ready = (active & (role == LEADER) & ~fenced &
             dual_quorum((healthy & lead_peer) | self_hot, voters2, vnew2))

    # TimeoutNow dispatch (7b intake): once the target's match covers our
    # whole log, tell it to campaign.  Re-sent every tick while the
    # condition holds — duplicates are fenced by the receiver's term
    # check, and loss costs one tick, not the transfer.
    tgt_match = take_plane(match_idx.T, jnp.clip(xfer_to, 0, P - 1))
    xfer_fire = (active & (role == LEADER) & (xfer_to != NIL)
                 & (tgt_match >= log.last))
    out_tn_valid = (peer_ids[:, None] == xfer_to[None, :]) & xfer_fire[None, :]
    out_tn_term = jnp.broadcast_to(term[None, :], (P, G))
    if carry:
        # 6b case c: the target campaigns with no pre-vote asked, so what
        # is stored stops releasing now and nothing is carried until its
        # candidacy has run out on its timer.
        read_evid = jnp.where(xfer_fire[:, None], 0, read_evid)
        guard = guard.replace(carry_bar=jnp.where(
            xfer_fire, now + 2 * cfg.election_ticks, guard.carry_bar))

    # Election broadcasts (PreVote at speculative term+1 carrying our log
    # position, reference Follower.prepareElection:223-279; RequestVote at
    # the new term, Candidate.startElection:90-143).
    bcast = (became_cand | start_pre) & active
    # Solicit only VOTER slots (both sets while joint): learner grants
    # would never count, so they are not asked.
    out_rv_valid = bcast[None, :] & not_me_col \
        & mask_bits(voters2 | vnew2, P).T
    out_rv_term = jnp.broadcast_to(
        jnp.where(start_pre, term + 1, term)[None, :], (P, G))
    out_rv_last_idx = jnp.broadcast_to(log.last[None, :], (P, G))
    out_rv_last_term = jnp.broadcast_to(last_term_v[None, :], (P, G))
    out_rv_prevote = jnp.broadcast_to(start_pre[None, :], (P, G))

    # ---- 10. commit advance ------------------------------------------------
    # Quorum median over the match matrix with self = last (reference
    # Leadership.majorIndices:116-130), gated by the commit-only-own-term
    # rule (reference Leader.tryCommit:256-261, Raft §5.4.2).  Runs as the
    # Pallas scan when cfg.use_pallas (ops/quorum.py), else inline jnp —
    # identical semantics either way.
    from ..ops.quorum import quorum_commit
    # Own-match durability gate (HostInbox.durable_tail): with the
    # pipelined runtime, this scan may be executing while the PREVIOUS
    # tick's WAL fsync is still in flight — so the self column counts only
    # the fsynced prefix, never the raw device tail.  An entry therefore
    # needs (majority - 1) durable FOLLOWER acks plus OUR durable copy
    # before it can commit — the ack-after-fsync contract, enforced
    # in-kernel rather than by host-phase ordering alone.  None (every
    # fused-scan path, and the serial runtime's default) keeps the
    # classic self = log.last.
    self_match = log.last if host.durable_tail is None \
        else jnp.minimum(log.last, host.durable_tail)
    match_full = jnp.where(self_hot, self_match[:, None], match_idx)
    commit = quorum_commit(cfg, match_full, log, commit, own_from,
                           active & (role == LEADER), voters2, vnew2)
    match_idx = match_full

    # §6 epilogue: a leader whose committed SIMPLE config no longer
    # includes it steps down (it managed the cluster through the joint
    # phase and just committed the C_new that removes it — this tick's
    # AEs already carry that commit to the survivors).
    resigned = (active & (role == LEADER) & (vnew2 == 0)
                & (cidx2 <= commit)
                & ((jnp.right_shift(voters2, me) & 1) == 0))
    role = jnp.where(resigned, FOLLOWER, role)
    leader_id = jnp.where(resigned, NIL, leader_id)
    elect_dl = jnp.where(resigned, now + rand_to, elect_dl)

    # ---- flight recorder ---------------------------------------------------
    # Branchless per-group event-ring writes (cfg.trace_depth; zero cost
    # when 0 — the whole block is a trace-time branch like debug_checks).
    # Emission order within a tick is canonical and mirrors phase order:
    # the scalar oracle (testkit/oracle.py) emits the identical stream, so
    # decoded device timelines are parity-checked tick-for-tick.  All
    # records carry the END-of-tick term; TR_CRASH_RESTART is written by
    # types.crash_restart before the step runs.
    trace = s.trace
    if cfg.trace_depth:
        from .types import (
            TR_BECAME_CANDIDATE, TR_BECAME_LEADER, TR_BECAME_PRE_CANDIDATE,
            TR_COMMIT_ADVANCE, TR_CONF_CHANGE_COMMIT, TR_CONF_CHANGE_ENTER,
            TR_LEADER_TRANSFER, TR_READ_RELEASE, TR_SNAPSHOT_INSTALL,
            TR_STEPPED_DOWN, TR_TERM_BUMP,
        )
        D = cfg.trace_depth
        NE = 11
        # All of one tick's events land in ONE batched scatter per lane:
        # event e's ring slot is n + (#events of this tick that fired
        # before it), so intra-tick order IS the canonical order above.
        # Slots stay distinct within a group because at most NE events
        # fire per tick and trace_depth >= NE + 1 (EngineConfig
        # post-init).
        ev_masks = jnp.stack([                               # [G, NE]
            term != s.term,
            (s.role == LEADER) & (role != LEADER),
            start_pre,
            became_cand,
            vote_win,
            sd,
            commit > s.commit,
            n_rel > 0,
            # Membership plane: active-config change (enter/leave/learner/
            # adoption/rollback), config-entry commit, TimeoutNow sent.
            (w2 != w0) | (cidx2 != cidx0),
            (cidx2 > 0) & (s.commit < cidx2) & (commit >= cidx2),
            xfer_fire,
        ], axis=1) & active[:, None]
        ev_kinds = jnp.asarray([
            TR_TERM_BUMP, TR_STEPPED_DOWN, TR_BECAME_PRE_CANDIDATE,
            TR_BECAME_CANDIDATE, TR_BECAME_LEADER, TR_SNAPSHOT_INSTALL,
            TR_COMMIT_ADVANCE, TR_READ_RELEASE,
            TR_CONF_CHANGE_ENTER, TR_CONF_CHANGE_COMMIT,
            TR_LEADER_TRANSFER,
        ], I32)
        ev_aux = jnp.stack([                                 # [G, NE]
            s.term, leader_id, jnp.zeros((G,), I32),
            # Candidacy cause: 0 prevote majority / 1 timer / 2 TimeoutNow
            # (tn_cand implies timer_cand, so the sum is exactly 2).
            timer_cand.astype(I32) + tn_cand.astype(I32),
            noop_idx, host.snap_idx,
            commit, n_served,
            w2, cidx2, xfer_to,
        ], axis=1)
        ev_i32 = ev_masks.astype(I32)
        prior = jnp.cumsum(ev_i32, axis=1) - ev_i32          # fired before e
        n_new = ev_i32.sum(axis=1)                           # [G]
        # Ring write WITHOUT a scatter: a vmapped scatter inside the
        # fused scan lowers ~17x slower on CPU (measured; the one-hot-
        # over-D select ~3-6x).  Instead the fired events compact into a
        # dense NE-wide window ([G, NE, NE] one-hot, D-independent), and
        # the ring blends it in with one take_along_axis per varying lane
        # (the one gather left in the step; the recorder is off in every
        # served configuration, and the log rings, the read FIFO and the
        # peer planes are addressed by compare-and-select, ops/select.py).
        # Ring position d takes window offset (d - n) mod D when that
        # offset < n_new; tick/term are uniform across a tick's events,
        # so those two lanes need only the write mask.
        off_hit = (prior[:, :, None] ==
                   jnp.arange(NE, dtype=I32)[None, None, :]) \
            & ev_masks[:, :, None]                           # [G, NE, NE]
        win = lambda vals: jnp.where(
            off_hit, vals[:, :, None], 0).sum(axis=1)        # [G, NE]
        rel = jnp.remainder(jnp.arange(D, dtype=I32)[None, :]
                            - jnp.remainder(trace.n, D)[:, None], D)
        write = rel < n_new[:, None]                         # [G, D]
        rel_idx = jnp.minimum(rel, NE - 1)

        def put(ring, vals):                                 # vals [G, NE]
            return jnp.where(
                write, jnp.take_along_axis(win(vals), rel_idx, axis=1),
                ring)

        trace = trace.replace(
            tick=jnp.where(write, now, trace.tick),
            kind=put(trace.kind,
                     jnp.broadcast_to(ev_kinds[None, :], (G, NE))),
            term=jnp.where(write, term[:, None], trace.term),
            aux=put(trace.aux, ev_aux),
            n=trace.n + n_new,
        )

    # ---- heat lanes (cfg.heat) --------------------------------------------
    # Cumulative per-group activity counters for the host-side heat
    # registry: entries appended, RPCs emitted (all 7 kinds), commit
    # advance, reads served.  Branchless masked adds over lanes already
    # live at this point — the tick's outbox valid planes and the
    # append/commit/read results — so the extra work is a handful of [G]
    # sums; when off the subtree is None and nothing here traces.
    heat = s.heat
    if cfg.heat:
        sent_n = (out_ae_valid.astype(I32) + out_aer_valid.astype(I32)
                  + out_rv_valid.astype(I32) + out_rvr_valid.astype(I32)
                  + out_is_valid.astype(I32) + out_isr_valid.astype(I32)
                  + out_tn_valid.astype(I32)).sum(axis=0)
        appended_n = jnp.where(app_to > 0, app_to - app_from + 1, 0)
        heat = heat.replace(
            appended=heat.appended + appended_n,
            sent=heat.sent + sent_n,
            commits=heat.commits + (commit - s.commit),
            reads=heat.reads + n_served,
        )

    dirty = (term != old_term) | (voted != old_voted) | (log.last != old_last) \
        | (app_to > 0)

    hib = s.hib
    if hiber:
        # Phase 10 moved nothing on a lane that was quiet (its commit
        # stood at its last index); where it did, that is activity too.
        busy_at = jnp.where(active & ((commit != s.commit) | resigned), now,
                            busy_at)
        hib = Hibernate(asleep=asleep & ~resigned, busy_at=busy_at,
                        slept=slept)

    # In-kernel invariant checks (cfg.debug_checks; zero cost when off —
    # the branch is resolved at trace time).  The vectorized analog of the
    # reference's hot-path AssertionErrors (ring/log continuity
    # RocksLog.java:175-187, monotonic matchIndex Leadership.java:76-81,
    # role/ballot sanity Follower.java:48-50): a violation pinpoints the
    # faulting phase by code instead of surfacing as downstream
    # divergence.  Codes in DEBUG_CODES; the host raises on any nonzero.
    debug_viol = jnp.zeros((G,), I32)
    if cfg.debug_checks:
        def flag(viol, cond, code):
            return jnp.where(active & cond & (viol == 0),
                             jnp.asarray(code, I32), viol)
        # 1: live window exceeds ring capacity (entries would alias).
        debug_viol = flag(debug_viol, log.last - log.base > L, 1)
        # 2: commit passed the log end.
        debug_viol = flag(debug_viol, commit > jnp.maximum(log.last, log.base), 2)
        # 3: term regressed within one step.
        debug_viol = flag(debug_viol, term < s.term, 3)
        # 4: a continuing leader's matchIndex moved backwards.
        debug_viol = flag(
            debug_viol,
            (s.role == LEADER) & (role == LEADER)
            & (match_idx < s.match_idx).any(axis=1), 4)
        # 5: candidate whose ballot is not itself.
        debug_viol = flag(debug_viol, (role == CANDIDATE) & (voted != me), 5)
        # 6: commit regressed.
        debug_viol = flag(debug_viol, commit < s.commit, 6)
        # 7: pipeline head behind the ack base.
        debug_viol = flag(debug_viol, (send_next < next_idx).any(axis=1), 7)
        # 8: read FIFO length out of range.
        debug_viol = flag(debug_viol, (rq_len < 0) | (rq_len > K), 8)
        # 9: active config with an empty voter set (a config entry can
        # never be built that way; seeing one means ring corruption).
        debug_viol = flag(debug_viol, voters2 == 0, 9)

    new_state = RaftState(
        node_id=s.node_id, now=now, rng=rng, active=active,
        term=term, role=role, voted_for=voted, leader_id=leader_id,
        commit=commit, applied=s.applied, log=log,
        next_idx=next_idx, match_idx=match_idx, send_next=send_next,
        own_from=own_from,
        inflight=inflight, hb_inflight=hb_inflight, sent_at=sent_at,
        need_snap=need_snap,
        ok_at=ok_at, fail_at=fail_at, fail_streak=fail_streak,
        votes=votes, prevotes=prevotes,
        elect_deadline=elect_dl, hb_due=hb_due,
        read_evid=read_evid,
        rq_idx=rq_idx, rq_stamp=rq_stamp, rq_n=rq_n,
        rq_head=rq_head, rq_len=rq_len,
        conf_idx=cidx2, conf_word=w2,
        xfer_to=xfer_to, xfer_dl=xfer_dl,
        trace=trace,
        heat=heat,
        qc=qc,
        lease=guard,
        hib=hib,
        read_seq=read_seq if strict else None,
    )
    outbox = Messages(
        ae_valid=out_ae_valid, ae_term=out_ae_term,
        ae_prev_idx=out_ae_prev_idx, ae_prev_term=out_ae_prev_term,
        ae_commit=out_ae_commit, ae_n=out_ae_n, ae_ents=out_ae_ents,
        ae_cents=out_ae_cents, ae_occ=out_ae_occ, ae_tick=out_ae_tick,
        aer_valid=out_aer_valid, aer_term=out_aer_term,
        aer_success=out_aer_success, aer_match=out_aer_match,
        aer_empty=out_aer_empty, aer_occ=out_aer_occ,
        aer_tick=out_aer_tick,
        rv_valid=out_rv_valid, rv_term=out_rv_term,
        rv_last_idx=out_rv_last_idx, rv_last_term=out_rv_last_term,
        rv_prevote=out_rv_prevote,
        rvr_valid=out_rvr_valid, rvr_term=out_rvr_term,
        rvr_granted=out_rvr_granted, rvr_prevote=out_rvr_prevote,
        rvr_echo=out_rvr_echo,
        is_valid=out_is_valid, is_term=out_is_term, is_idx=out_is_idx,
        is_last_term=out_is_last_term, is_probe=out_is_probe,
        is_conf=out_is_conf,
        isr_valid=out_isr_valid, isr_term=out_isr_term,
        isr_success=out_isr_success, isr_probe=out_isr_probe,
        tn_valid=out_tn_valid, tn_term=out_tn_term,
        ae_sleep=out_ae_sleep if hiber else None,
        aer_asleep=out_aer_asleep if hiber else None,
        ae_seq=out_ae_seq if strict else None,
        aer_seq=out_aer_seq if strict else None,
    )
    info = StepInfo(
        submit_start=sub_start, submit_acc=n_acc, dirty=dirty,
        appended_from=app_from, appended_to=app_to, log_tail=log.last,
        commit=commit, leader=leader_id, ready=ready, snap_req=snap_req,
        snap_req_from=snap_from, snap_req_idx=snap_idx_o,
        snap_req_term=snap_term_o, snap_req_conf=snap_conf_o,
        noop_idx=noop_idx, noop_term=noop_term,
        read_acc=n_read, read_index=read_index_out,
        read_rel=n_rel, read_served=n_served,
        read_lease=read_lease_hit, read_abort=read_abort,
        read_carried=read_carried, read_kick=read_kick,
        conf_app_idx=conf_app_idx, conf_app_term=conf_app_term,
        conf_app_word=conf_app_word,
        conf_word=w2, conf_idx=cidx2, conf_pending=cidx2 > commit,
        xfer_fired=xfer_fire, xfer_abort=xfer_abort,
        debug_viol=debug_viol,
        cq_stepdown=cq_down, cq_veto=cq_veto,
        asleep=hib.asleep if hiber else None,
    )
    return new_state, outbox, info


# ---------------------------------------------------------------------------
# The packed step: what the served path calls.  A host in the loop pays per
# transfer, not per byte (some 0.3 ms a call on a TPU, for planes of a few
# bytes to a few hundred KB), so a tick's ~50 input planes go up as one
# word buffer, the flags a byte each behind the words, and everything the
# host reads back comes down as another (core/packing.py; in pieces of a
# few MB where the planes are that large).  node_step itself stays the
# entry for whatever has no host in the loop: the fused scans, the parity
# kit, the byte count.
# ---------------------------------------------------------------------------

class Readback(NamedTuple):
    """Everything the runtime pulls to the host after a step: the step's
    own outputs and the lanes of the new state that the host mirrors."""

    info: StepInfo
    outbox: Messages
    term: Array
    voted_for: Array
    role: Array
    leader_id: Array
    commit: Array
    base: Array
    base_term: Array
    heat: Any                 # Optional[HeatState]: None when cfg.heat is off
    windows: Array            # [len(WINDOW_SUMS)] int32: window_sums()


# What Readback.windows holds, in order: the leader's replication windows
# summed over the (lane, follower) pairs this node leads.
WINDOW_SUMS = ("pairs", "occupied", "full", "cooling", "timed_out")


def window_sums(cfg: EngineConfig, old: RaftState, new: RaftState) -> Array:
    """The state of the leader's windows after a step, as five sums over
    the ``[G, P]`` planes the step has just written (``WINDOW_SUMS``):
    the (lane, follower) ``pairs`` this node leads (phase 9's
    ``lead_peer``, on the step's final roles), the slots ``occupied``
    over them (``inflight + hb_inflight``), the pairs whose window is
    ``full`` (``cfg.inflight_limit``), the pairs ``cooling`` (inside
    ``cfg.recovery_ticks`` of their last RPC timeout: the follower counts
    as unhealthy) and the pairs that ``timed_out`` in THIS step: phase 9
    is ``fail_at``'s only writer beside the election's reset to 0, so
    they are where it differs from the old state's and is not 0 (not
    ``fail_at == now``: every arrival step of the period would count the
    pair again)."""
    w = new.conf_word
    member = mask_bits(
        conf_voters_of(w) | conf_new_of(w) | conf_learners_of(w), cfg.n_peers)
    self_hot = jnp.arange(cfg.n_peers, dtype=I32)[None, :] == new.node_id
    pair = (new.active & (new.role == LEADER))[:, None] & ~self_hot & member
    used = new.inflight + new.hb_inflight
    cooling = (new.fail_at != 0) & (new.now - new.fail_at < cfg.recovery_ticks)
    timed_out = (new.fail_at != old.fail_at) & (new.fail_at != 0)
    return jnp.stack([
        jnp.sum(pair, dtype=I32),
        jnp.sum(jnp.where(pair, used, 0), dtype=I32),
        jnp.sum(pair & (used >= cfg.inflight_limit), dtype=I32),
        jnp.sum(pair & cooling, dtype=I32),
        jnp.sum(pair & timed_out, dtype=I32)])


def _step_readback(cfg: EngineConfig, state: RaftState, inbox: Messages,
                   host: HostInbox) -> Tuple[RaftState, Readback]:
    old = state
    state, outbox, info = node_step(cfg, old, inbox, host)
    return state, Readback(
        info=info, outbox=outbox, term=state.term,
        voted_for=state.voted_for, role=state.role,
        leader_id=state.leader_id, commit=state.commit,
        base=state.log.base, base_term=state.log.base_term, heat=state.heat,
        windows=window_sums(cfg, old, state))


def _step_shapes(cfg: EngineConfig, durable: bool):
    """Shapes of (HostInbox, Messages, Readback) as the served step takes
    and returns them, through ``jax.eval_shape``: they follow the
    dataclasses and ``cfg``'s optional subtrees."""
    def host_inbox():
        host = HostInbox.empty(cfg)
        if durable:
            host = host.replace(durable_tail=jnp.zeros_like(host.submit_n))
        return host

    host, inbox, state = (jax.eval_shape(f) for f in (
        host_inbox, lambda: Messages.empty(cfg),
        lambda: init_state(cfg, 0)))
    _, back = jax.eval_shape(partial(_step_readback, cfg), state, inbox, host)
    return host, inbox, back


@functools.lru_cache(maxsize=None)
def step_layouts(cfg: EngineConfig, durable: bool) -> Tuple[Layout, Layout]:
    """(inputs, readback) layouts of ``node_step_packed`` for ``cfg``:
    inputs pack ``(HostInbox, Messages)``, with the ``durable_tail`` lane
    when ``durable``; readback packs a :class:`Readback`."""
    host, inbox, back = _step_shapes(cfg, durable)
    return Layout((host, inbox)), Layout(back)


@partial(jax.jit, static_argnums=(0, 1), donate_argnums=2)
def node_step_packed(cfg: EngineConfig, inputs: Layout, state: RaftState,
                     buffers: Tuple[Array, ...]
                     ) -> Tuple[RaftState, Tuple[Array, ...]]:
    """``node_step`` with packed operands and packed results: ``buffers``
    hold ``(HostInbox, Messages)`` in the layout ``inputs``
    (``step_layouts(cfg, durable)[0]``); returned beside the new state are
    the buffers of its :class:`Readback`, in ``step_layouts(cfg,
    durable)[1]``.  The values that reach the step and the values that
    come back are node_step's, bit for bit."""
    host, inbox = inputs.unpack(buffers)
    state, back = _step_readback(cfg, state, inbox, host)
    return state, Layout(back).pack(back)


# ---------------------------------------------------------------------------
# The column step: node_step_packed for a node so large that its message
# planes take several buffers.  There the planes are tens of MB each way
# and a step moves a handful of their columns and of their lanes, so three
# things cross by shape and count and are dense on the device alone: the
# Messages operand and result as COLUMNS (core/packing.py ColumnLayout),
# HostInbox as the ROWS of the lanes that have something to say and the
# Readback's [G] planes (StepInfo, the mirrored state lanes) as the rows
# of the lanes that moved (RowLayout).  A step whose messages do not fit
# the column buffers (a heartbeat round, an election storm), whose
# HostInbox holds more lanes than its row buffer or whose results moved
# more crosses that part densely, exactly as node_step_packed's does:
# decided by a count, nothing cut.  A step that fits crosses in ONE array
# each way: rows and columns are regions of one word buffer.
# ---------------------------------------------------------------------------

class ColumnLayouts(NamedTuple):
    """The layouts of ``node_step_columns`` for one ``(cfg, durable)``."""

    host: Layout            # HostInbox alone: what goes up beside columns
    inputs: Layout          # (HostInbox, Messages) dense: step_layouts' own
    back: Layout            # a Readback without its outbox
    outbox: Layout          # the dense outbox, for a step that overflowed
    columns: ColumnLayout   # Messages in column form, both directions
    rows_in: RowLayout      # HostInbox in row form
    rows_out: RowLayout     # a Readback without its outbox in row form


class RowCarry(NamedTuple):
    """What a column step leaves on the device beside the state: the
    ``durable_tail`` plane the host's rows patch (None for a layout
    without one; never a field of ``RaftState``), which the next step
    takes, and the Readback without its outbox as stacked ``[G]`` planes
    and a header (``rows_out.stack``): what ``compact_readback`` compares
    with the last step's to find the lanes that moved, and what
    ``pack_readback`` packs for a step whose rows did not fit."""

    durable: Any
    words: Array
    flags: Array
    header: Array


# The fields of StepInfo that are LEVELS (they hold from step to step: a
# lane's row comes down when one differs from the last step's) and the one
# that is carried (``submit_start`` is ``log.last + 1`` on every lane and
# read only where ``submit_acc`` is not zero: it crosses with a row and
# causes none).  Every other field of StepInfo is an event, zero unless
# something happened; every mirrored state lane of the Readback is a
# level.
INFO_LEVELS = ("log_tail", "commit", "leader", "ready", "conf_word",
               "conf_idx", "conf_pending")     # and "asleep" (cfg.hibernate)
INFO_CARRIED = ("submit_start",)


# Columns engage where the ``int32`` planes of the step's dense operand
# take at least this many buffers (core/packing.py CHUNK_BYTES each: some
# 33,000 lanes at P=3, B=8; a layout places its words as if it had no
# flags, so the flags that ride behind them since PR 42 move no shape
# across the rule).  What the column form costs does not depend on the
# lanes (K-row scatters and gathers on the device: 3 ms of a step on a TPU
# v5e at either size, with the buffer pair more each way that it then
# crossed in), what it saves does (the dense planes'
# allocation, transfer, unpacking and packing: 0.13 ms a step a thousand
# lanes): at 10,000 lanes (2 word buffers) the column step cost the
# 10,000-Region cell 3 ms a step and 4-7 ms of a 22 ms read, at 100,000 (12)
# it saves 14 ms of 50 and 30 ms of a 100 ms read; the two cross near three
# buffers (PERF.md, PR 35).
COLUMN_BUFFERS = 4


@functools.lru_cache(maxsize=None)
def column_layouts(cfg: EngineConfig, durable: bool
                   ) -> Optional[ColumnLayouts]:
    """The column step's layouts, or None for a shape that keeps
    ``node_step_packed``: a rule on the packed layout, which the shape
    alone decides (``COLUMN_BUFFERS``); below it the dense planes cross
    at little more than a transfer's fixed cost and columns lose."""
    inputs, _ = step_layouts(cfg, durable)
    if sum(w > 0 for w in inputs.words) < COLUMN_BUFFERS:
        return None
    host, inbox, back = _step_shapes(cfg, durable)
    outbox, back = back.outbox, back._replace(outbox=None)
    G = cfg.n_groups
    return ColumnLayouts(
        host=Layout(host), inputs=inputs,
        back=Layout(back), outbox=Layout(outbox),
        columns=ColumnLayout(inbox),
        rows_in=RowLayout(host, G, packing.ROWS_IN),
        rows_out=RowLayout(
            back, G, packing.ROWS_OUT,
            levels=[n for n in packing.lane_names(back, G)
                    if not n.startswith("info.")]
            + ["info." + n for n in INFO_LEVELS
               + (("asleep",) if cfg.hibernate else ())],
            carried=["info." + n for n in INFO_CARRIED]))


def first_carry(lay: ColumnLayouts) -> RowCarry:
    """The carry before a node's first column step: zero planes.  What
    that step's rows say against them means nothing, so the host takes
    the step's results whole (``pack_readback``) and uploads
    ``durable_tail`` whole (a count of -1); from then on the carry is the
    last step's."""
    rl = lay.rows_out
    durable = jnp.zeros((rl.G,), I32) \
        if "durable_tail" in lay.rows_in.at else None
    return RowCarry(durable, jnp.zeros((rl.W, rl.G), I32),
                    jnp.zeros((rl.F, rl.G), jnp.bool_),
                    jnp.zeros((rl.H,), I32))


def _host_from_rows(rl: RowLayout, base: HostInbox, rows, durable
                    ) -> Tuple[HostInbox, Any]:
    """The HostInbox a step's rows stand for: ``base``'s planes with the
    rows written over them by one K-row scatter a kind, the scalars from
    the rows' header.  ``base`` holds no event when the rows do (the
    resident zero planes) and the whole HostInbox when they do not fit
    (then the count is -1 and no row is held).  ``durable_tail`` is a
    level: the rows patch the plane the device keeps (``durable``) and a
    count of -1 replaces it with ``base``'s.  Returns the HostInbox and
    the plane to keep."""
    words, flags, _ = rl.stack(base)
    if durable is not None:
        at = rl.at["durable_tail"][1]
        words = words.at[at].set(
            jnp.where(rows[0] < 0, words[at], durable))
    host = rl.unstack(*rl.expand(rows, words, flags))
    return host, host.durable_tail


@partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=3)
def node_step_columns(cfg: EngineConfig, lay: ColumnLayouts,
                      columns_in: bool, state: RaftState, carry: RowCarry,
                      buffers: Tuple[Array, ...]):
    """``node_step`` with its messages in column form and its ``[G]``
    planes in row form.  ``buffers``: ``lay.host``'s when ``columns_in``,
    else ``lay.inputs``' (the dense operand of ``node_step_packed``); then
    ONE word buffer more, which is what a step that fits uploads:
    HostInbox's rows (``lay.rows_in``), written over the HostInbox planes
    that came before (see ``_host_from_rows``), and behind them, when
    ``columns_in``, the inbox's columns (``lay.columns``;
    ``packing.regions``).
    ``carry`` is what the last call returned (``first_carry`` for the
    first); the step reads its ``durable`` plane alone.  Returns the new
    state, the new carry (the patched ``durable_tail`` plane; the
    Readback without its outbox, stacked: left on the device for
    ``compact_readback`` to find the rows that moved, and for
    ``pack_readback`` should they not fit), the outbox's column buffer
    (whose counts say whether it fits: ``lay.columns.K``; it comes down
    behind the rows, in ``compact_readback``'s one result), and the dense
    outbox itself (``lay.columns.stack``'s few arrays, not its forty
    planes: a result is a Python object a call), left on the device for
    ``pack_outbox`` should it not.
    ``node_step`` gets the planes it always got, bit for bit: columns and
    rows are expanded into planes by K-row scatters, nothing is addressed
    G rows at a time."""
    *buffers, up = buffers
    if columns_in:
        rows, columns = packing.regions(up, lay.rows_in, lay.columns)
        host = lay.host.unpack(buffers)
        inbox = lay.columns.expand(columns)
    else:
        # The dense operand's planes are whole before the step reads one:
        # with its unpacking fused into the step, the chip's compiler
        # wrote ``now`` over two thirds of the donated ``fail_at`` plane at
        # 100,000 lanes where the CPU backend was right (PERF.md, PR 42;
        # the fault of PR 38 again, in another leaf).  What is compiled
        # into this program goes through ``tools/rows_probe.py check``.
        host, inbox = lay.inputs.unpack(buffers)
        host, inbox, rows = jax.lax.optimization_barrier((host, inbox, up))
    host, durable = _host_from_rows(lay.rows_in, host, rows, carry.durable)
    state, back = _step_readback(cfg, state, inbox, host)
    dense = lay.columns.stack(back.outbox)
    return (state,
            RowCarry(durable, *lay.rows_out.stack(back._replace(outbox=None))),
            lay.columns.compact(dense, stacked=True), dense)


@partial(jax.jit, static_argnums=0)
def compact_readback(lay: ColumnLayouts, carry: RowCarry, last: RowCarry,
                     columns: Array) -> Array:
    """What the host fetches of a column step, in ONE word buffer: the
    rows of its Readback (``lay.rows_out``) and behind them ``columns``,
    the outbox's column buffer as the step returned it, copied
    (``packing.regions``).  The rows: the lanes of ``carry`` (the step's)
    where a level differs from ``last`` (the step's before it: what the
    host's mirrors hold) or an event is not zero, their true count, the
    first K of them and every plane's value there, by a prefix sum and
    one K-row gather a kind; the header holds the leaves that are no
    planes.  A program of its own beside the step: compiled into it, the
    search and the gathers came out of the chip's compiler writing over
    the step's donated state (PERF.md, PR 38), and on its own it overlaps
    nothing the host waits for; it donates nothing."""
    rl = lay.rows_out
    moved = rl.moved(carry.words, carry.flags, last.words, last.flags)
    return jnp.concatenate(
        [rl.compact(carry.words, carry.flags, carry.header, moved), columns])


@partial(jax.jit, static_argnums=0)
def pack_outbox(lay: ColumnLayouts, dense: Tuple[Array, ...]
                ) -> Tuple[Array, ...]:
    """The dense outbox a column step left on the device, packed for the
    fetch of a step whose outbox did not fit its columns (``lay.outbox``:
    as node_step_packed's readback holds it)."""
    return lay.outbox.pack(lay.columns.unstack(dense))


@partial(jax.jit, static_argnums=0)
def pack_readback(lay: ColumnLayouts, carry: RowCarry) -> Tuple[Array, ...]:
    """The Readback without its outbox that a column step left on the
    device (the carry's planes and header), packed for the fetch of a
    step whose rows did not hold it (``lay.back``: as node_step_packed's
    readback holds it)."""
    return lay.back.pack(
        lay.rows_out.unstack(carry.words, carry.flags, carry.header))
