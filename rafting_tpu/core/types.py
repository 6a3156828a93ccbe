"""Core value types for the vectorized Multi-Raft engine.

Design inversion vs the reference (curioloop/rafting): instead of one
``RaftContext`` object + event loop per group (reference:
context/RaftContext.java:34, support/EventLoop.java:14), the consensus state of
ALL groups on a node lives in group-major JAX arrays, and a single jitted step
function advances every group at once.  Roles, terms, votes and timers are
vector lanes; "switch role" (reference: context/RaftRoutine.java:140-216) is a
masked update, not an object swap.

Index conventions
-----------------
* Log indices start at 1; index 0 is the empty sentinel.  ``base`` is the
  compaction floor (the reference's "epoch", command/RaftLog.java:25-66):
  entries in ``(base, last]`` are live, ``base`` itself carries ``base_term``
  (the snapshot milestone term).
* Peer slot p in any ``[G, P]`` / ``[P, G]`` array refers to cluster node id p.
  A node's own slot is inert (never sent to, masked everywhere).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import struct

# Role lattice (reference: context/member/Membership.java:74-108 defines the
# total order used for transitions; here roles are just lane values and the
# lattice is enforced by the masked-update order inside the step kernel).
FOLLOWER = 0
PRE_CANDIDATE = 1
CANDIDATE = 2
LEADER = 3

NIL = -1  # "no vote" / "no leader" sentinel (reference: votedFor == null)

I32 = jnp.int32

# Every index/term/clock lane is int32 BY DESIGN: the TPU vector units are
# 32-bit native (int64 is emulated as register pairs and halves throughput
# of exactly the hot lanes — match/next matrices, the log ring, the tick
# clock), and the reference's own RocksDB tier is the only 64-bit surface
# (8-byte big-endian keys, command/storage/RocksLog.java:259-280) — which
# the host WAL mirrors (u64 indices on disk).  The engine therefore bounds
# per-group log indices, terms and the tick clock at I32_SAFE_MAX; the host
# runtime checks the live maxima every tick and fails LOUDLY with
# ~2^20 ticks of headroom instead of wrapping silently.  At the design
# point (max_submit <= 32 entries/group/tick, 50 ticks/s) a single group
# crosses the bound after ~15 days of saturated writes — and the snapshot +
# lane-purge cycle (admin destroy/recreate, which resets the lane to index
# 0) is the intended long-horizon story, exactly like the reference's
# compaction floor keeps RocksDB keys bounded.
I32_SAFE_MAX = (1 << 31) - (1 << 20)

# ---------------------------------------------------------------------------
# Membership plane: packed config words (Raft §6 joint consensus).
#
# A group's configuration is a single int32 word packing three peer-slot
# bitmasks plus a marker flag:
#
#     bits  0..9   voters      — C_old while joint, else THE voter set
#     bits 10..19  voters_new  — C_new; nonzero iff the config is JOINT
#     bits 20..29  learners    — replicate but never count toward any quorum
#     bit  30      CONF_FLAG   — set on every real config word (a zero in
#                                the conf ring means "not a config entry")
#
# The packing bounds n_peers at CONF_MASK_BITS slots (asserted by
# EngineConfig); the reference's clusters are 3-9 nodes, and the Pallas
# sorting network unrolls the same range.  The layout constants are OWNED
# by utils/tracelog.py (imported below) so the engine-free dump decoder
# unpacks config words from the same single definition.
#
# §6 apply-on-append contract (see LogState.conf): config-change entries
# travel the NORMAL log, and a node uses the configuration of the LATEST
# config entry present in its log — committed or not — the moment the
# entry is appended.  Joint entries (C_old,new) require a quorum in BOTH
# voter sets for elections and commits; the C_new entry that leaves the
# joint state is auto-appended by the leader once C_old,new commits.  One
# change is in flight per group at a time (the next intake is refused
# until the previous config entry commits).  Truncation of an uncommitted
# config entry rolls the config back automatically: the active config is
# DERIVED from the log every tick, never stored separately.
# ---------------------------------------------------------------------------
from ..utils.tracelog import (  # noqa: E402  (decoder-owned layout)
    CONF_FLAG, CONF_LRN_SHIFT, CONF_MASK, CONF_MASK_BITS, CONF_NEW_SHIFT,
)


def conf_pack(voters, voters_new=0, learners=0):
    """Pack a config word (python ints or int32 arrays; CONF_FLAG set)."""
    return (CONF_FLAG | (voters & CONF_MASK)
            | ((voters_new & CONF_MASK) << CONF_NEW_SHIFT)
            | ((learners & CONF_MASK) << CONF_LRN_SHIFT))


def conf_voters_of(word):
    return (word >> 0) & CONF_MASK


def conf_new_of(word):
    return (word >> CONF_NEW_SHIFT) & CONF_MASK


def conf_learners_of(word):
    return (word >> CONF_LRN_SHIFT) & CONF_MASK


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static (hashable) engine configuration — the jit-time shape contract.

    Mirrors the semantics of the reference's RaftConfig
    (support/RaftConfig.java:27, 187-198): all timing derives from an abstract
    tick; election timeouts are randomized in [T, 2T).
    """

    n_groups: int                 # G — groups resident on this node
    n_peers: int                  # P — cluster size (incl. self); peer id == node id
    log_slots: int = 64           # L — per-group log ring capacity (power of two)
    batch: int = 8                # B — max entries per AppendEntries
                                  #     (reference REPLICATE_LIMIT=50, Leadership.java:10)
    max_submit: int = 8           # S — max client commands accepted per group per tick
    election_ticks: int = 10      # T — election timeout base, randomized [T, 2T)
                                  #     (reference RaftConfig.java:187-190)
    heartbeat_ticks: int = 3      # heartbeat interval (reference RaftConfig.java:192-194)
    rpc_timeout_ticks: int = 8    # re-send an un-acked AppendEntries after this long
                                  #     (reference: per-RPC timeout, Async.java:177-256)
    pre_vote: bool = True         # PreVote phase enabled (reference RaftConfig.java:97-100)
    use_pallas: bool = False      # quorum-commit via the Pallas TPU kernel
                                  #     (ops/quorum.py) instead of inline jnp
    inflight_limit: int = 4       # W — max un-acked AppendEntries batches per
                                  #     (group, peer) (reference IN_FLIGHT_LIMIT=20,
                                  #     Leadership.java:11)
    avail_crit: int = 3           # peer unhealthy after this many consecutive
                                  #     RPC timeouts (reference availableCriticalPoint,
                                  #     Leadership.isUnhealthy, Leadership.java:44-47)
    recovery_ticks: int = 6       # peer stays unhealthy until this long after its
                                  #     last failure (reference recoveryCoolDownMills,
                                  #     Leadership.java:45-46)
    debug_checks: bool = False    # compile in-kernel invariant checks into
                                  #     node_step (StepInfo.debug_viol codes;
                                  #     the vectorized analog of the
                                  #     reference's ~30 hot-path AssertionErrors,
                                  #     Follower.java:48-50, Leadership.java:76-81,
                                  #     RocksLog.java:175-187).  Off by default:
                                  #     zero cost when False (trace-time branch).
    # Linearizable read plane (ReadIndex + lease fast path; no reference
    # analog — curioloop/rafting routes every read through the log).
    read_slots: int = 4           # K — pending ReadIndex batches per group
                                  #     (a per-group FIFO ring of stamped read
                                  #     fences awaiting their quorum barrier)
    read_lease: bool = True       # lease fast path: barrier evidence is
                                  #     RECEIPT-anchored (a fresh same-term
                                  #     heartbeat-ack quorum in this tick's
                                  #     inbox releases a same-tick read — zero
                                  #     extra round trips).  False = strict
                                  #     ReadIndex (etcd's ReadOnlySafe):
                                  #     evidence is the ECHO of a per-lane
                                  #     counter that moves in every step that
                                  #     stamps a batch (RaftState.read_seq,
                                  #     carried as ae_seq / aer_seq), so a
                                  #     read only releases on acks to
                                  #     AppendEntries that LEFT in or after
                                  #     the step that stamped it (a dedicated
                                  #     post-stamp confirmation round; no
                                  #     assumption on clocks or delays, one
                                  #     round trip slower).  The counter and
                                  #     the two message words exist only here:
                                  #     with the lease they are None and the
                                  #     step, its pytrees and the wire are
                                  #     what they were.
    read_fresh_ticks: int = 3     # lease evidence freshness: an ack older
                                  #     than this many own-clock ticks past
                                  #     its echoed send tick is not lease
                                  #     evidence (bounds duplicate-delivery
                                  #     chains to one hop — see step.py
                                  #     read-barrier phase for the proof)
    trace_depth: int = 0          # D — flight-recorder ring depth per group
                                  #     (TraceState lanes; events written
                                  #     branchlessly at the step's phase
                                  #     boundaries).  0 disables the
                                  #     recorder entirely: the trace subtree
                                  #     is None, so the state pytree and the
                                  #     compiled step are bit-identical to a
                                  #     build without the feature.
    heat: bool = False            # per-group heat lanes (HeatState):
                                  #     cumulative appended / sent /
                                  #     committed / reads-served counters
                                  #     accumulated branchlessly each tick
                                  #     and drained by the host into the
                                  #     decaying heat registry (the active-
                                  #     set evidence feed).  False keeps
                                  #     the subtree None — the state
                                  #     pytree and compiled step are bit-
                                  #     identical to a heatless build,
                                  #     same contract as trace_depth.
    check_quorum: bool = False    # CheckQuorum step-down ("Paxos vs
                                  #     Raft", arXiv:2004.05074 §leader
                                  #     stickiness): a leader that has not
                                  #     heard from a voter quorum within
                                  #     one election timeout steps down to
                                  #     follower, closing the read-lease
                                  #     window and aborting pending lease
                                  #     reads — the gray-failure remedy
                                  #     for asymmetric inbound-only cuts,
                                  #     which a higher-term step-down can
                                  #     never reach (the cut leader hears
                                  #     no terms at all).  Adds the
                                  #     QuorumContact lanes; False keeps
                                  #     the subtree None (same zero-cost-
                                  #     when-off contract as trace/heat).
    hibernate: bool = False       # Hibernate Region (TiKV's
                                  #     raftstore.hibernate-regions): a group
                                  #     idle for an election timeout whose
                                  #     members all hold the leader's whole
                                  #     log stops ticking: its leader opens
                                  #     no heartbeat round, its followers'
                                  #     election timers do not run, and the
                                  #     first request or message wakes it
                                  #     (core/step.py "hibernation" has the
                                  #     rule and its proof).  Adds the
                                  #     Hibernate lanes, a flag on an
                                  #     AppendEntries and on its reply, and
                                  #     HostInbox.wake; False keeps all of
                                  #     them None (same zero-cost-when-off
                                  #     contract as trace/heat/qc/lease).

    def __post_init__(self):
        assert self.n_peers >= 1
        assert self.n_peers <= CONF_MASK_BITS, \
            "membership plane packs voter/learner masks into one i32 conf " \
            f"word ({CONF_MASK_BITS} bits per mask) — n_peers is bounded"
        assert self.log_slots & (self.log_slots - 1) == 0, "log_slots must be a power of 2"
        assert self.batch <= self.log_slots
        assert self.heartbeat_ticks < self.election_ticks
        assert self.rpc_timeout_ticks >= 1
        assert self.inflight_limit >= 1, "pipelining window needs >= 1 slot"
        assert self.avail_crit >= 0 and self.recovery_ticks >= 0
        assert self.read_slots >= 1, "read plane needs >= 1 pending slot"
        assert self.read_fresh_ticks >= 2, \
            "lease evidence needs the 2-tick delivery round trip"
        assert self.trace_depth == 0 or self.trace_depth >= 12, \
            "flight-recorder rings need >= 12 slots (one tick can emit " \
            "up to 11 events, batched into one scatter per lane)"

    @property
    def majority(self) -> int:
        return self.n_peers // 2 + 1

    # The carried lease (core/step.py phase 6b has the proof).  Nothing
    # here is a setting: the lease's length follows from the heartbeat
    # cadence, and whether it may be carried at all from the inequality
    # between that length and the followers' vote-denying promise.
    @property
    def lease_ticks(self) -> int:
        """The most ticks of the leader's clock that lie between the SEND
        of an AppendEntries and the last tick its acknowledgement releases
        reads in, both ends counted: the echo is at most
        ``read_fresh_ticks`` old at receipt, and the receipt covers its
        own tick and ``heartbeat_ticks - 1`` more."""
        return self.heartbeat_ticks + self.read_fresh_ticks

    @property
    def lease_carry_ticks(self) -> int:
        """How many ticks past its own lease evidence releases reads in:
        ``heartbeat_ticks - 1`` (evidence reaches as far as the next
        heartbeat round), or 0 (today's rule: its own tick only) where
        the carried lease would not be sound.  It is sound when

            heartbeat_ticks + read_fresh_ticks + 3 <= election_ticks

        (``lease_ticks`` plus the one period of lateness the leader's
        veto lets pass must fit the T - 2 periods that a follower's
        promise of T ticks is certain to last; phase 6b), when
        ``pre_vote`` is on (the promise is the refusal of PRE-votes: with
        direct candidacies there is none) and with the lease itself
        (``read_lease``).  ``RaftConfig`` as it ships (election 3) fails
        it; TiKV's timing (heartbeat 2, election 10) holds it with 2 to
        spare."""
        carry = self.heartbeat_ticks - 1
        sound = (self.read_lease and self.pre_vote
                 and self.lease_ticks + 3 <= self.election_ticks)
        return carry if sound and carry > 0 else 0

    @property
    def lease_hold_ticks(self) -> int:
        """How long a node that restarts with a term on disk grants no
        pre-vote (``LeaseGuard.vote_hold``): the lease it may have
        acknowledged just before it went down lasts ``lease_ticks + 1``
        periods at most (the veto), and ``k`` ticks of a clock take more
        than ``k - 2`` periods.  Less than ``election_ticks`` wherever the
        lease is carried at all; 0 where it is not."""
        return self.lease_ticks + 3 if self.lease_carry_ticks else 0


@struct.dataclass
class LogState:
    """Device-resident log *metadata* for all groups: entry terms in a ring.

    Payload bytes live on the host (keyed by (group, index)); the device only
    needs terms to run consistency checks, conflict scans and the
    commit-only-own-term rule (reference: RocksLog stores term-prefixed values,
    command/storage/RocksLog.java:82-89; conflict scan at 199-216).
    """

    term: jax.Array       # [G, L] int32 — term of entry at slot (index % L)
    conf: jax.Array       # [G, L] int32 — packed config word of the entry at
                          #   slot (index % L); 0 = not a config entry.  The
                          #   §6 membership plane: a group's ACTIVE config is
                          #   the word of the latest config entry in
                          #   (base, last], else ``base_conf`` (apply-on-
                          #   append — see the module-level contract above
                          #   CONF_MASK_BITS).  Travels with entries over
                          #   AppendEntries (Messages.ae_cents) so laggards
                          #   and truncation rollbacks need no special cases.
    base: jax.Array       # [G] int32 — compaction floor ("epoch"); entries (base, last] live
    base_term: jax.Array  # [G] int32 — term of the entry at `base` (snapshot milestone term)
    base_conf: jax.Array  # [G] int32 — packed config as of index ``base``
                          #   (the snapshot milestone's config; what the
                          #   derivation falls back to when no config entry
                          #   is live)
    last: jax.Array       # [G] int32 — last appended index (0 = empty)


# ---------------------------------------------------------------------------
# Flight recorder: a fixed-depth per-group ring of event records written
# branchlessly at the phase boundaries of core/step.py (the device-side
# answer to "which replica did what when" — the debugging currency
# "Paxos vs Raft" (arxiv 2004.05074) identifies as the real-world pain).
# The event-kind taxonomy is OWNED by utils/tracelog.py (numpy+stdlib
# only, so post-mortem dump decoding needs no engine import) and
# re-exported here for the kernel and oracle.  Canonical INTRA-TICK
# emission order is the numeric kind order, except TR_CRASH_RESTART,
# which crash_restart writes BEFORE the tick's step runs (its tick stamp
# is the pre-step clock).  Per-kind aux payloads:
#   TR_TERM_BUMP            aux = previous term
#   TR_STEPPED_DOWN         aux = new leader hint (NIL if unknown)
#   TR_BECAME_PRE_CANDIDATE aux = 0
#   TR_BECAME_CANDIDATE     aux = 0 prevote majority / 1 timer expiry /
#                           2 TimeoutNow (leadership transfer)
#                           ("elections by cause" decodes from this)
#   TR_BECAME_LEADER        aux = §8 no-op index (0: ring full, none)
#   TR_SNAPSHOT_INSTALL     aux = installed milestone index
#   TR_COMMIT_ADVANCE       aux = new commit index
#   TR_READ_RELEASE         aux = individual reads released
#   TR_CRASH_RESTART        aux = durable log tail survived into boot
#   TR_CONF_CHANGE_ENTER    aux = the new packed config word
#   TR_CONF_CHANGE_COMMIT   aux = the committed config entry's index
#   TR_LEADER_TRANSFER      aux = transfer target peer slot
# The scalar oracle (testkit/oracle.py) emits the identical stream, so
# the recorder itself is parity-checked; utils/tracelog.py decodes.
# ---------------------------------------------------------------------------
from ..utils.tracelog import (  # noqa: F401  (re-exported taxonomy)
    TR_BECAME_CANDIDATE, TR_BECAME_LEADER, TR_BECAME_PRE_CANDIDATE,
    TR_COMMIT_ADVANCE, TR_CONF_CHANGE_COMMIT, TR_CONF_CHANGE_ENTER,
    TR_CRASH_RESTART, TR_LEADER_TRANSFER, TR_READ_RELEASE,
    TR_SNAPSHOT_INSTALL, TR_STEPPED_DOWN, TR_TERM_BUMP, TRACE_EVENTS,
)


@struct.dataclass
class TraceState:
    """Per-group flight-recorder rings (cfg.trace_depth slots per group).

    One logical event word is the (tick, kind, term, aux) quadruple at one
    ring slot; ``n`` counts events ever written, so slot ``i % D`` holds
    event ``i`` and a host that drained through event ``m`` detects loss
    exactly when ``n - m > D`` (the ring overwrote the gap).  All lanes
    are I32 like every engine lane; the recorder is observability state,
    NOT protocol state — no step phase ever reads it back.
    """

    tick: jax.Array   # [G, D] int32 — event tick stamp (node's own clock)
    kind: jax.Array   # [G, D] int32 — TR_* event kind
    term: jax.Array   # [G, D] int32 — group term at emission
    aux: jax.Array    # [G, D] int32 — per-kind payload (see TR_* comments)
    n: jax.Array      # [G] int32 — events ever written (ring head = n % D)

    @classmethod
    def empty(cls, n_groups: int, depth: int) -> "TraceState":
        z = lambda *sh: jnp.zeros(sh, I32)
        return cls(tick=z(n_groups, depth), kind=z(n_groups, depth),
                   term=z(n_groups, depth), aux=z(n_groups, depth),
                   n=z(n_groups))


@struct.dataclass
class HeatState:
    """Per-group activity lanes (cfg.heat): cumulative event counters the
    fused step bumps branchlessly each tick, drained by the host into the
    decaying heat registry (utils/heat.py).  Observability state like
    TraceState — no step phase ever reads it back, it survives
    crash_restart (activity history is not protocol state), and the
    subtree is None when disabled so the compiled program is identical
    to a heatless build.  Cumulative (not per-tick) so the host drain is
    delta-vs-mirror and a skipped drain tick loses nothing."""

    appended: jax.Array   # [G] int32 — entries appended to the log, ever
    sent: jax.Array       # [G] int32 — RPCs emitted (all 7 kinds), ever
    commits: jax.Array    # [G] int32 — commit-index advance, ever
    reads: jax.Array      # [G] int32 — linearizable reads served, ever

    @classmethod
    def empty(cls, n_groups: int) -> "HeatState":
        # Four distinct buffers: the lanes are donated through the jitted
        # step, and donating one aliased array through several leaves is
        # an XLA error ("donate the same buffer twice").
        z = lambda: jnp.zeros((n_groups,), I32)
        return cls(appended=z(), sent=z(), commits=z(), reads=z())


@struct.dataclass
class QuorumContact:
    """Per-group quorum-contact lanes (cfg.check_quorum).

    ``heard[g, p]`` is the own-clock tick of the last VALID inbound RPC
    from peer p (any of the seven kinds, term-independent: even a stale
    reply proves the link and the peer alive).  ``since[g]`` anchors the
    contact window: set at election win, advanced each time a due check
    passes.  A leader whose window has run one election timeout without a
    voter quorum of ``heard >= since`` steps down (core/step.py phase
    6c).  Unlike trace/heat these lanes ARE read back by the step — but
    only by the CheckQuorum phase itself; they are volatile (reset by
    crash_restart like every liveness timer) and None when disabled, so a
    ``check_quorum=False`` build compiles bit-identically to the seed.
    """

    heard: jax.Array   # [G, P] int32 — own-clock tick of last contact (0 never)
    since: jax.Array   # [G] int32 — contact-window anchor (0 = not leading yet)

    @classmethod
    def empty(cls, n_groups: int, n_peers: int) -> "QuorumContact":
        # Two distinct buffers (donation: never alias donated leaves).
        return cls(heard=jnp.zeros((n_groups, n_peers), I32),
                   since=jnp.zeros((n_groups,), I32))


@struct.dataclass
class LeaseGuard:
    """Per-group lanes of the carried lease (``cfg.lease_carry_ticks`` >
    0; None otherwise, so that a configuration whose lease is not carried
    compiles the program it always did).  Both close a way in which a
    leader's stored evidence could outlive the promise behind it
    (core/step.py phase 6b, cases b and c); both are volatile.

    ``vote_hold[g]``: no pre-vote is granted before this tick.  Set by a
    restart that recovered a term (``crash_restart``, ``log/store.py
    restore_raft_state``): the node may have acknowledged a heartbeat a
    moment before it went down, and comes back with ``leader_id`` NIL,
    which would open its vote at once.  0 on a first boot.

    ``carry_bar[g]``: a leader's evidence releases reads of its own tick
    only before this tick.  Set when TimeoutNow fires: the target's
    candidacy asks no pre-vote, so no promise stands in its way.
    """

    vote_hold: jax.Array   # [G] int32 — own-clock tick (0 = none)
    carry_bar: jax.Array   # [G] int32 — own-clock tick (0 = none)

    @classmethod
    def empty(cls, n_groups: int) -> "LeaseGuard":
        # Two distinct buffers (donation: never alias donated leaves).
        return cls(vote_hold=jnp.zeros((n_groups,), I32),
                   carry_bar=jnp.zeros((n_groups,), I32))


@struct.dataclass
class Hibernate:
    """Per-group lanes of hibernation (``cfg.hibernate``; None otherwise,
    so that a configuration without it compiles the program it always
    did).  All volatile (core/step.py "hibernation").

    ``asleep[g]``: the lane does not tick.  A leader opens no heartbeat
    round, runs no CheckQuorum and holds no lease evidence; a follower's
    election timer does not expire and its vote-denying promise stands.
    ``busy_at[g]``: own-clock tick of the lane's last activity (anything
    but a heartbeat and its acknowledgement): a leader proposes sleep
    ``election_ticks`` ticks after it.
    ``slept[g, p]``: as leader, member ``p`` has said it fell asleep under
    the proposal now standing (cleared by any activity).
    """

    asleep: jax.Array      # [G] bool
    busy_at: jax.Array     # [G] int32 — own-clock tick
    slept: jax.Array       # [G, P] bool

    @classmethod
    def empty(cls, n_groups: int, n_peers: int) -> "Hibernate":
        return cls(asleep=jnp.zeros((n_groups,), jnp.bool_),
                   busy_at=jnp.zeros((n_groups,), I32),
                   slept=jnp.zeros((n_groups, n_peers), jnp.bool_))


def trace_append(tr: TraceState, mask: jax.Array, kind: int,
                 tick, term, aux) -> TraceState:
    """Branchless masked append of one event kind across all groups.

    Lanes where ``mask`` is False write nowhere (their slot compares
    equal to no ring position) and keep their count.  Compare-and-select,
    not scatter: scatters inside vmapped scan bodies lower an order of
    magnitude slower on CPU (see the fused emission block in
    core/step.py, which batches a whole tick's events the same way)."""
    G, D = tr.tick.shape
    slot = jnp.where(mask, jnp.remainder(tr.n, D), D)
    hit = slot[:, None] == jnp.arange(D, dtype=I32)[None, :]   # [G, D]
    bc = lambda v: jnp.broadcast_to(jnp.asarray(v, I32), (G,))[:, None]
    put = lambda ring, v: jnp.where(hit, bc(v), ring)
    return tr.replace(
        tick=put(tr.tick, tick),
        kind=put(tr.kind, kind),
        term=put(tr.term, term),
        aux=put(tr.aux, aux),
        n=tr.n + mask.astype(I32),
    )


@struct.dataclass
class RaftState:
    """Group-major consensus state for one node — the whole Multi-Raft node.

    Replaces the reference's per-group object graph: RaftContext fields
    (context/RaftContext.java:34-89), role objects (context/member/*.java),
    Leadership.State per-follower bookkeeping (context/member/Leadership.java)
    and TimerTicket deadlines (context/member/TimerTicket.java).
    """

    node_id: jax.Array        # scalar int32 — this node's id (== its peer slot)
    now: jax.Array            # scalar int32 — logical tick clock
    rng: jax.Array            # PRNG key for randomized election timeouts

    active: jax.Array         # [G] bool — group exists & is open (admin lifecycle)
    term: jax.Array           # [G] int32 — currentTerm
    role: jax.Array           # [G] int32 — FOLLOWER / PRE_CANDIDATE / CANDIDATE / LEADER
    voted_for: jax.Array      # [G] int32 — ballot, NIL if none
    leader_id: jax.Array      # [G] int32 — last known leader (redirect hint), NIL unknown
    commit: jax.Array         # [G] int32 — commitIndex
    applied: jax.Array        # [G] int32 — host-acknowledged apply frontier

    log: LogState

    # Leader-side replication bookkeeping (reference Leadership.State,
    # context/member/Leadership.java:30-114).
    own_from: jax.Array       # [G] int32 — as leader: first log index of OUR
                              #   current term (set at election win = the
                              #   no-op's index).  Terms are monotone along
                              #   the log, so the commit-only-own-term rule
                              #   (Raft §5.4.2) reduces to quorum_idx >=
                              #   own_from — no ring gather on the commit
                              #   hot path (ops/quorum.py).  Only meaningful
                              #   while role == LEADER.
    next_idx: jax.Array       # [G, P] int32 — ack base: first un-ACKed index
    match_idx: jax.Array      # [G, P] int32
    send_next: jax.Array      # [G, P] int32 — pipeline head: next index to ship
                              #   (>= next_idx; the window (next_idx, send_next)
                              #   is in flight — reference IN_FLIGHT_LIMIT
                              #   pipelining, Leadership.java:11)
    inflight: jax.Array       # [G, P] int32 — un-acked AppendEntries batches
    hb_inflight: jax.Array    # [G, P] int32 — un-acked OCCUPYING heartbeats
                              #   (empty AEs sent while the window had room;
                              #   aer_empty replies decrement THIS lane, so
                              #   window accounting stays exact — see step.py
                              #   phase 9)
    sent_at: jax.Array        # [G, P] int32 — tick of last send (for re-send timeout)
    need_snap: jax.Array      # [G, P] bool — follower fell behind compaction floor
                              #   (reference pendingInstallation, Leadership.java:111-113)

    # Peer-health stats (reference Leadership.State requestSuccess/
    # requestFailure/recentFailure, Leadership.java:28-73), feeding the
    # leader readiness gate (Leader.isReady, Leader.java:52-64).
    ok_at: jax.Array          # [G, P] int32 — tick of last reply since leadership
                              #   began (0 = never; reference requestSuccess != 0)
    fail_at: jax.Array        # [G, P] int32 — tick of last RPC timeout (0 = never)
    fail_streak: jax.Array    # [G, P] int32 — consecutive RPC timeouts

    # Election tallies (reference: AtomicInteger vote counts,
    # Candidate.java:112; Follower.prepareElection:241-275).
    votes: jax.Array          # [G, P] bool — RequestVote grants received this term
    prevotes: jax.Array       # [G, P] bool — PreVote grants received this round

    elect_deadline: jax.Array # [G] int32 — election timer deadline (tick)
    hb_due: jax.Array         # [G] int32 — next heartbeat tick (leader)

    # Derived-config cache (§6 membership plane): ALWAYS equal to
    # ``latest_conf(log, log.last)`` at rest — the step consumes it as
    # the tick-start view C0 (vote/PreVote tallies, campaign gating) and
    # re-derives only after the tick's log mutations, so the [G, L] conf
    # sweep runs once per tick, not twice.  Consistent across
    # crash_restart by construction (both the cache and the log are
    # durable-state functions).
    conf_idx: jax.Array       # [G] int32 — active config entry index (0 =
                              #   the config comes from log.base_conf)
    conf_word: jax.Array      # [G] int32 — active packed config word

    # Leadership transfer (TimeoutNow, Raft dissertation §3.10).  While a
    # transfer is pending the leader FENCES client submissions and config
    # changes, waits for the target's match to reach its log end, then
    # sends TimeoutNow; the target campaigns immediately, skipping
    # PreVote.  Volatile leader state: cleared on role/term change, on
    # the deadline, and by crash_restart.
    xfer_to: jax.Array        # [G] int32 — transfer target peer (NIL none)
    xfer_dl: jax.Array        # [G] int32 — abort deadline (own-clock tick)

    # Linearizable read plane (leader-only lanes; ReadIndex §6.4 of the
    # Raft dissertation, vectorized).  A read batch is STAMPED with the
    # leader's commit index at receipt and RELEASED once a majority has
    # confirmed our leadership at/after the stamp and (host-side) the
    # apply frontier covers the stamp.  All comparisons are between two
    # values of the SAME node's own clock, so per-node clock drift under
    # nemesis stalls cannot skew them (see step.py read-barrier phase).
    read_evid: jax.Array      # [G, P] int32 — barrier evidence per peer:
                              #   with cfg.read_lease, the own-clock RECEIPT
                              #   tick of the last fresh same-term AE ack;
                              #   without, the highest ECHOED stamp counter
                              #   (aer_seq) — acks to AppendEntries that left
                              #   in or after the step of a stamp.
                              #   0 = none this leadership.  A receipt
                              #   releases batches stamped in its tick and
                              #   in the cfg.lease_carry_ticks after it.
    rq_idx: jax.Array         # [G, K] int32 — pending batch read indices
    rq_stamp: jax.Array       # [G, K] int32 — pending batch stamps: the
                              #   tick with cfg.read_lease, else read_seq
                              #   as the stamping step left it
    rq_n: jax.Array           # [G, K] int32 — reads per pending batch
    rq_head: jax.Array        # [G] int32 — FIFO ring head slot
    rq_len: jax.Array         # [G] int32 — pending batch count (<= K)

    # Flight recorder (cfg.trace_depth > 0).  None when disabled: a None
    # subtree has NO leaves, so the state pytree — and therefore every
    # compiled step/scan program — is bit-identical to a traceless build
    # (the zero-cost-when-off contract, tested in test_tracelog).
    trace: Any = None         # Optional[TraceState]

    # Heat lanes (cfg.heat).  Same None-subtree contract as the recorder:
    # disabled builds compile bit-identical programs.
    heat: Any = None          # Optional[HeatState]

    # Quorum-contact lanes (cfg.check_quorum).  Same None-subtree
    # contract: a build without CheckQuorum compiles bit-identically.
    qc: Any = None            # Optional[QuorumContact]

    # The carried lease's guards (cfg.lease_carry_ticks > 0).  Same
    # None-subtree contract: a configuration whose lease is not carried
    # (a 1-tick heartbeat, a short election timeout, no pre-vote)
    # compiles bit-identically.
    lease: Any = None         # Optional[LeaseGuard]

    # Hibernation's lanes (cfg.hibernate).  Same None-subtree contract.
    hib: Any = None           # Optional[Hibernate]

    # Strict ReadIndex's stamp counter (cfg.read_lease False; None with the
    # lease, same None-subtree contract): it moves by one in every step
    # that stamps a batch, every AppendEntries that leaves in or after that
    # step carries it (ae_seq), and it lives within one continuous
    # leadership at one term, as the read FIFO does: 0 on any other lane.
    read_seq: Any = None      # Optional[[G] int32]


@struct.dataclass
class FaultSchedule:
    """A precomputed, device-resident fault plan for a fused chaos run.

    The "nemesis" plane (Jepsen terminology): every array is indexed by
    tick along its leading axis, so a whole chaos scenario — partitions,
    asymmetric/flaky links, crash-restarts, clock stalls, duplicate
    deliveries — rides through ``lax.scan`` (core/sim.py
    ``run_cluster_ticks_nemesis``) as scan inputs and the entire run
    executes inside ONE compiled program.  This is the vectorized analog
    of the reference's manual chaos procedure (kill TCP links / kill -9 a
    JVM / restart, README.md:28-33), but deterministic: the schedule is
    data, so the same seed replays bit-identically.

    Semantics per tick t (applied by the nemesis scan body):

    * ``link_up[t, s, d]`` False — messages in flight s->d are dropped at
      delivery (directed: asymmetric links are expressible).
    * ``crash[t, n]`` — node n crash-restarts BEFORE delivery: volatile
      state resets to the durable frontier (:func:`crash_restart`, the
      in-scan mirror of ``log/store.py restore_raft_state``), messages
      addressed to it this tick are lost (it was down when they arrived).
    * ``stall[t, n]`` — node n is frozen this tick (GC pause / clock
      stall): its step does not run, its clock and timers do not advance,
      it sends nothing, and inbound messages are lost.  Per-node ``now``
      clocks drift apart under stalls — by design; every timer in the
      kernel is anchored to the node's OWN clock.
    * ``dup[t, s, d]`` — every message delivered over s->d this tick is
      ALSO re-delivered next tick (unless a fresh message overwrites the
      lane), exercising duplicate/stale-RPC idempotency.
    """

    link_up: jax.Array  # [T, N, N] bool — conn[s, d] per tick (False = cut)
    crash: jax.Array    # [T, N] bool — crash-restart node n at tick t
    stall: jax.Array    # [T, N] bool — freeze node n for tick t
    dup: jax.Array      # [T, N, N] bool — duplicate deliveries on link s->d

    @property
    def n_ticks(self) -> int:
        return self.link_up.shape[0]

    @classmethod
    def healthy(cls, n_peers: int, n_ticks: int) -> "FaultSchedule":
        """The no-fault schedule: all links up, nothing crashes."""
        return cls(
            link_up=jnp.ones((n_ticks, n_peers, n_peers), jnp.bool_),
            crash=jnp.zeros((n_ticks, n_peers), jnp.bool_),
            stall=jnp.zeros((n_ticks, n_peers), jnp.bool_),
            dup=jnp.zeros((n_ticks, n_peers, n_peers), jnp.bool_),
        )


def crash_restart(cfg: EngineConfig, s: "RaftState") -> "RaftState":
    """Volatile-state reset for an in-scan crash-restart of ONE node.

    Mirrors the host recovery path exactly (``log/store.py
    restore_raft_state`` + ``runtime/node.py`` boot): durable state —
    ``term``, ``voted_for`` and the log (ring / base / base_term / last)
    — survives (the WAL persists stable records and entries before any
    RPC leaves the node); everything else is volatile.  ``commit``
    restarts at the compaction floor (entries at/below the milestone are
    committed by definition; the rest is rediscovered from leaderCommit
    traffic), leadership bookkeeping resets to boot values, and the
    election timer re-arms with a fresh randomized window like a reboot.
    The PRNG key is split ONLY on the crash path (callers select with the
    crash mask), so un-crashed nodes keep their stream bit-exactly.
    """
    G, P = cfg.n_groups, cfg.n_peers
    K = cfg.read_slots
    rng, k = jax.random.split(s.rng)
    deadline = s.now + jax.random.randint(
        k, (G,), cfg.election_ticks, 2 * cfg.election_ticks, dtype=I32)
    z = lambda *sh: jnp.zeros(sh, I32)
    f = lambda *sh: jnp.zeros(sh, jnp.bool_)
    boot_next = jnp.broadcast_to(s.log.last[:, None] + 1, (G, P))
    # The flight recorder survives a crash (it is observability, not
    # protocol state) and records the restart itself, stamped with the
    # pre-step clock — the step that follows emits at now + 1.
    trace = s.trace
    if trace is not None:
        trace = trace_append(trace, s.active, TR_CRASH_RESTART,
                             s.now, s.term, s.log.last)
    # Quorum-contact lanes are volatile like every liveness timer: a
    # rebooted node re-earns contact evidence from scratch.
    qc = s.qc
    if qc is not None:
        qc = qc.replace(heard=jnp.zeros_like(qc.heard),
                        since=jnp.zeros_like(qc.since))
    # A node that comes back with a term on disk may have acknowledged
    # a heartbeat just before it went down: it grants no pre-vote until
    # that lease has run out (phase 6b, case b).
    lease = s.lease
    if lease is not None:
        lease = LeaseGuard(
            vote_hold=jnp.where(s.term > 0, s.now + cfg.lease_hold_ticks, 0),
            carry_bar=jnp.zeros_like(lease.carry_bar))
    # A restarted node is awake, and its idle stretch starts now.
    hib = s.hib
    if hib is not None:
        hib = Hibernate(asleep=jnp.zeros_like(hib.asleep),
                        busy_at=jnp.broadcast_to(s.now, hib.busy_at.shape)
                        .astype(I32),
                        slept=jnp.zeros_like(hib.slept))
    return s.replace(
        trace=trace,
        qc=qc,
        lease=lease,
        hib=hib,
        read_seq=None if s.read_seq is None else z(G),
        rng=rng,
        role=z(G),
        leader_id=jnp.full((G,), NIL, I32),
        commit=s.log.base,
        applied=z(G),
        own_from=z(G),
        next_idx=boot_next,
        match_idx=z(G, P),
        send_next=boot_next,
        inflight=z(G, P),
        hb_inflight=z(G, P),
        sent_at=z(G, P),
        need_snap=f(G, P),
        ok_at=z(G, P),
        fail_at=z(G, P),
        fail_streak=z(G, P),
        votes=f(G, P),
        prevotes=f(G, P),
        elect_deadline=deadline,
        hb_due=z(G),
        # Pending reads are volatile leader state: a restart drops them
        # (clients retry — reads never enter the log, so the retry is
        # always safe) and barrier evidence must be re-earned.
        read_evid=z(G, P),
        rq_idx=z(G, K), rq_stamp=z(G, K), rq_n=z(G, K),
        rq_head=z(G), rq_len=z(G),
        # A pending leadership transfer is volatile leader state.  The
        # CONFIG (conf_idx/conf_word cache) is not reset: it derives from
        # the log (conf ring + base_conf), which survives like
        # term/ballot — the §6 voter set is durable across
        # crash-restarts by construction.
        xfer_to=jnp.full((G,), NIL, I32),
        xfer_dl=z(G),
    )


@struct.dataclass
class Messages:
    """One tick's worth of RPC traffic, dense over (peer, group).

    Axis 0 is the *sender* for an inbox and the *destination* for an outbox.
    At most one RPC of each kind per (peer, group) per tick — the dense analog
    of the reference's scope-multiplexed single connection per peer
    (transport/NettyNode.java:54-74).

    Covers the reference's full 4-RPC wire interface (RaftService.java:22-61):
    appendEntries, preVote, requestVote, installSnapshot (+ replies).
    """

    # AppendEntries request (reference Leader.replicateLog → Follower.appendEntries)
    ae_valid: jax.Array      # [P, G] bool
    ae_term: jax.Array       # [P, G] int32
    ae_prev_idx: jax.Array   # [P, G] int32
    ae_prev_term: jax.Array  # [P, G] int32
    ae_commit: jax.Array     # [P, G] int32 — leaderCommit
    ae_n: jax.Array          # [P, G] int32 — entry count (<= B)
    ae_ents: jax.Array       # [P, G, B] int32 — entry terms
    ae_occ: jax.Array        # [P, G] bool — this (empty) AE OCCUPIES a
                             #   heartbeat window slot on its sender; echoed
                             #   back as aer_occ so only replies to occupying
                             #   heartbeats release hb_inflight (a reply to a
                             #   window-full EXEMPT heartbeat must not free a
                             #   slot whose own ack was lost)
    ae_cents: jax.Array      # [P, G, B] int32 — per-entry packed config
                             #   words (0 = not a config entry): the §6
                             #   membership plane rides the log, so every
                             #   shipped entry carries its config word and
                             #   followers adopt configs apply-on-append
                             #   exactly as they adopt terms
    ae_tick: jax.Array       # [P, G] int32 — sender's own clock at send,
                             #   echoed back as aer_tick: the lease's
                             #   freshness bound on duplicate-delivery
                             #   chains (strict ReadIndex orders by ae_seq,
                             #   below), hibernation's and the runtime's
                             #   heartbeat-round anchor

    # AppendEntries response (reference RaftResponse + match bookkeeping)
    aer_valid: jax.Array     # [P, G] bool
    aer_term: jax.Array      # [P, G] int32
    aer_success: jax.Array   # [P, G] bool
    aer_match: jax.Array     # [P, G] int32 — match index on success, nextIndex-1 hint on failure
    aer_empty: jax.Array     # [P, G] bool — reply to an EMPTY AE (heartbeat):
                             #   window-exempt on the sender, so the leader
                             #   skips the inflight decrement (exact window
                             #   accounting; see step.py phase 9)
    aer_occ: jax.Array       # [P, G] bool — echo of the AE's ae_occ flag
                             #   (meaningful with aer_empty; symmetric with
                             #   is_probe/isr_probe)
    aer_tick: jax.Array      # [P, G] int32 — echo of ae_tick (read barrier)

    # RequestVote / PreVote request (reference Follower.prepareElection,
    # Candidate.startElection)
    rv_valid: jax.Array      # [P, G] bool
    rv_term: jax.Array       # [P, G] int32 (PreVote carries term+1 speculatively)
    rv_last_idx: jax.Array   # [P, G] int32
    rv_last_term: jax.Array  # [P, G] int32
    rv_prevote: jax.Array    # [P, G] bool

    # Vote response
    rvr_valid: jax.Array     # [P, G] bool
    rvr_term: jax.Array      # [P, G] int32 — responder's current term
    rvr_granted: jax.Array   # [P, G] bool
    rvr_prevote: jax.Array   # [P, G] bool
    rvr_echo: jax.Array      # [P, G] int32 — echo of the requested term (staleness fence,
                             #   the vectorized analog of AsyncHead request-group
                             #   cancellation, transport/rpc/Async.java:70-172)

    # InstallSnapshot request/response (reference Leader.java:168-190,
    # Follower.installSnapshot:130-153).  Device plane carries only the
    # milestone (index, term); bulk bytes move on the host side channel.
    is_valid: jax.Array      # [P, G] bool
    is_term: jax.Array       # [P, G] int32
    is_idx: jax.Array        # [P, G] int32 — snapshot last index
    is_last_term: jax.Array  # [P, G] int32 — snapshot last term
    is_probe: jax.Array      # [P, G] bool — window-exempt re-offer (heartbeat
                             #   cadence): echoed back so the reply does not
                             #   release a slot the offer never took
    is_conf: jax.Array       # [P, G] int32 — packed config as of the offered
                             #   milestone (the sender's base_conf): the
                             #   installing follower's new base_conf, round-
                             #   tripped through the host via
                             #   StepInfo.snap_req_conf / HostInbox.snap_conf
    isr_valid: jax.Array     # [P, G] bool
    isr_term: jax.Array      # [P, G] int32
    isr_success: jax.Array   # [P, G] bool
    isr_probe: jax.Array     # [P, G] bool — echo of is_probe

    # TimeoutNow (leadership transfer, §3.10): the leader tells a caught-up
    # voter to campaign immediately, skipping PreVote and the leader-
    # stickiness lease.  Stale-term copies are ignored by the term check.
    tn_valid: jax.Array      # [P, G] bool
    tn_term: jax.Array       # [P, G] int32 — sender's term (receiver must match)

    # Hibernation (cfg.hibernate; None otherwise: the pytree, the packed
    # layouts and the wire sections are then what they always were).
    ae_sleep: Any = None     # Optional[[P, G] bool] — this heartbeat proposes
                             #   sleep: its sender has been idle for an
                             #   election timeout and holds every member's
                             #   acknowledgement of its whole log
    aer_asleep: Any = None   # Optional[[P, G] bool] — the replier fell (or
                             #   stays) asleep on that heartbeat
    # Strict ReadIndex (cfg.read_lease False; None with the lease, same
    # contract): the order of stamps and AppendEntries by STEPS.
    ae_seq: Any = None       # Optional[[P, G] int32] — the sender's
                             #   RaftState.read_seq as this step left it:
                             #   every batch stamped up to and in the step
                             #   this AppendEntries leaves in has a stamp
                             #   <= it
    aer_seq: Any = None      # Optional[[P, G] int32] — echo of ae_seq by a
                             #   replier AT THE REQUEST'S TERM (0 otherwise:
                             #   the counter starts over with every
                             #   leadership, so the echo of an older term's
                             #   request must never reach a newer one)

    @classmethod
    def empty(cls, cfg: EngineConfig) -> "Messages":
        P, G, B = cfg.n_peers, cfg.n_groups, cfg.batch
        z = lambda *s: jnp.zeros(s, I32)
        f = lambda *s: jnp.zeros(s, jnp.bool_)
        return cls(
            ae_valid=f(P, G), ae_term=z(P, G), ae_prev_idx=z(P, G),
            ae_prev_term=z(P, G), ae_commit=z(P, G), ae_n=z(P, G),
            ae_ents=z(P, G, B), ae_cents=z(P, G, B), ae_occ=f(P, G),
            ae_tick=z(P, G),
            aer_valid=f(P, G), aer_term=z(P, G), aer_success=f(P, G),
            aer_match=z(P, G), aer_empty=f(P, G), aer_occ=f(P, G),
            aer_tick=z(P, G),
            rv_valid=f(P, G), rv_term=z(P, G), rv_last_idx=z(P, G),
            rv_last_term=z(P, G), rv_prevote=f(P, G),
            rvr_valid=f(P, G), rvr_term=z(P, G), rvr_granted=f(P, G),
            rvr_prevote=f(P, G), rvr_echo=z(P, G),
            is_valid=f(P, G), is_term=z(P, G), is_idx=z(P, G),
            is_last_term=z(P, G), is_probe=f(P, G), is_conf=z(P, G),
            isr_valid=f(P, G), isr_term=z(P, G), isr_success=f(P, G),
            isr_probe=f(P, G),
            tn_valid=f(P, G), tn_term=z(P, G),
            ae_sleep=f(P, G) if cfg.hibernate else None,
            aer_asleep=f(P, G) if cfg.hibernate else None,
            ae_seq=None if cfg.read_lease else z(P, G),
            aer_seq=None if cfg.read_lease else z(P, G),
        )


@struct.dataclass
class HostInbox:
    """Host → device inputs for one tick (beyond peer RPC traffic)."""

    submit_n: jax.Array        # [G] int32 — new client commands offered (<= S)
    # Snapshot-install completion events (host finished downloading/restoring
    # a snapshot; reference RaftRoutine.restoreCheckpoint:482-541).
    snap_done: jax.Array       # [G] bool
    snap_idx: jax.Array        # [G] int32
    snap_term: jax.Array       # [G] int32
    # Compaction grants: host took a snapshot at this index, device may raise
    # the log floor (reference RaftRoutine.compactLog:365-400).  The milestone
    # term is read from the device-side ring, so only the index is needed.
    compact_to: jax.Array      # [G] int32 (0 = no-op)
    # Membership plane (§6): the TARGET configuration a client asked for.
    # 0 in ``conf_voters`` = no request (a voter set can never be empty).
    # The leader turns a request into ONE config entry: a joint C_old,new
    # entry when the voter set changes, a simple entry when only the
    # learner set moves; the C_new leave entry is auto-appended when the
    # joint entry commits.  Intake is refused (silently — the host
    # re-offers) while another change is in flight, while a leadership
    # transfer is pending, or when the request equals the active config.
    conf_voters: jax.Array     # [G] int32 — target voter bitmask (0 = none)
    conf_learners: jax.Array   # [G] int32 — target learner bitmask
    # Leadership transfer request: target peer slot (NIL = none).  The
    # device latches it into RaftState.xfer_to when this node leads.
    xfer_target: jax.Array     # [G] int32
    # Config at an installed snapshot's milestone (0 = keep current
    # base_conf; paired with snap_done/snap_idx/snap_term — round-tripped
    # from the leader's InstallSnapshot offer, StepInfo.snap_req_conf).
    snap_conf: jax.Array       # [G] int32
    # Linearizable read plane.
    read_n: jax.Array          # [G] int32 — linearizable reads offered this
                               #   tick (one batch; stamped together when a
                               #   pending slot is free and the lane leads)
    read_veto: jax.Array       # scalar bool — host detected a wall-clock
                               #   tick gap (process pause): discard stored
                               #   and same-tick lease evidence so a pause
                               #   cannot stretch the lease window (the host
                               #   analog of the device model's
                               #   stall-loses-inbound rule)
    # The engine's clock (``RaftState.now``) advances by this much in the
    # step: 1 on a step the period's timer started, 0 on a step that work
    # started between two timer ticks (the runtime's loop steps when a
    # slice, a submission or a read arrives; runtime/node.py _run).  Every
    # timer of the engine compares against ``now``, so the steps of one
    # period are ONE tick of the protocol's clock, delivered in pieces:
    # none of them can expire an election, RPC, CheckQuorum or transfer
    # deadline, none sends a cadence heartbeat (``now >= hb_due`` stays
    # false; a read's barrier heartbeat and data AppendEntries leave),
    # and everything stamped in them (``sent_at``, ``ok_at``, ``ae_tick``,
    # the lease's ``rq_stamp``) carries the period's value.  Whoever steps
    # with no loop advances the clock every step (``empty()`` gives 1).  A
    # node's first step is a timer step: stamps are >= 1, evidence 0 means
    # none.
    clock: jax.Array           # scalar int32 — 0 or 1
    # Durable-tail feedback (the pipelined runtime's safety lane): the
    # highest log index per group the host has FSYNCED.  When present, the
    # commit quorum counts this node's own match only up to it — an entry
    # is never self-acked ahead of its durability barrier, so a scan
    # dispatched concurrently with the previous tick's WAL fsync cannot
    # commit (and hence the host cannot ack) an un-fsynced range.  None
    # (the default, and what every fused-scan path feeds) = the device
    # log tail is durable the moment it is written — the serial runtime's
    # invariant, unchanged.
    durable_tail: Optional[jax.Array] = None   # [G] int32, or None
    # Hibernation (cfg.hibernate; None otherwise): the host's peer-lost
    # signal.  The node this lane's leader lives on has been silent for an
    # election timeout (runtime/node.py: the node-level beat), so an asleep
    # follower wakes and starts a whole new election timeout.  Every other
    # wake the step reads off the fields above and off its messages.
    wake: Optional[jax.Array] = None           # [G] bool, or None

    @classmethod
    def empty(cls, cfg: EngineConfig) -> "HostInbox":
        G = cfg.n_groups
        return cls(
            submit_n=jnp.zeros((G,), I32),
            snap_done=jnp.zeros((G,), jnp.bool_),
            snap_idx=jnp.zeros((G,), I32),
            snap_term=jnp.zeros((G,), I32),
            compact_to=jnp.zeros((G,), I32),
            conf_voters=jnp.zeros((G,), I32),
            conf_learners=jnp.zeros((G,), I32),
            xfer_target=jnp.full((G,), NIL, I32),
            snap_conf=jnp.zeros((G,), I32),
            read_n=jnp.zeros((G,), I32),
            read_veto=jnp.asarray(False),
            clock=jnp.asarray(1, I32),
            durable_tail=None,
            wake=jnp.zeros((G,), jnp.bool_) if cfg.hibernate else None,
        )


@struct.dataclass
class StepInfo:
    """Device → host outputs for one tick (beyond peer RPC traffic)."""

    submit_start: jax.Array   # [G] int32 — first index assigned to accepted commands
    submit_acc: jax.Array     # [G] int32 — how many offered commands were accepted
    dirty: jax.Array          # [G] bool — (term, votedFor) or log tail changed; the
                              #   host must fsync stable records / WAL before
                              #   releasing this tick's outbox (the reference
                              #   persists before replying, RaftMember.java:25)
    appended_from: jax.Array  # [G] int32 — first index (re)written this tick (0 none)
    appended_to: jax.Array    # [G] int32 — last index written this tick
    log_tail: jax.Array       # [G] int32 — post-step log end: the host WAL's
                              #   validity watermark.  Entries beyond it were
                              #   truncated (conflict or snapshot discard) and
                              #   must not survive recovery.
    commit: jax.Array         # [G] int32 — post-step commitIndex (apply frontier)
    leader: jax.Array         # [G] int32 — leader hint for client redirect
    ready: jax.Array          # [G] bool — leading AND a majority of peers healthy
                              #   (reference Leader.isReady, Leader.java:52-64;
                              #   the host refuses submissions when False)
    snap_req: jax.Array       # [G] bool — follower should start a snapshot download
    snap_req_from: jax.Array  # [G] int32 — peer to download from
    snap_req_idx: jax.Array   # [G] int32
    snap_req_term: jax.Array  # [G] int32
    snap_req_conf: jax.Array  # [G] int32 — config at the offered milestone
                              #   (the offer's is_conf; feed back as
                              #   HostInbox.snap_conf on completion)
    noop_idx: jax.Array       # [G] int32 — index of the own-term NO-OP a fresh
                              #   leader appended this tick (0 = none; Raft §8
                              #   liveness — the host stages it with an empty
                              #   payload so it is durable like any entry)
    noop_term: jax.Array      # [G] int32 — the no-op's term (the election-win
                              #   term; carried explicitly so a later-phase
                              #   term bump in the same tick cannot skew the
                              #   staged record)
    # Linearizable read plane (host pairs these with its own FIFO mirror
    # of offered read batches — acceptance and release are reported as
    # counts, in FIFO order).
    read_acc: jax.Array       # [G] int32 — reads accepted into the batch
                              #   stamped this tick (0 = offer not taken)
    read_index: jax.Array     # [G] int32 — the stamped batch's ReadIndex
                              #   (meaningful when read_acc > 0): serve once
                              #   applied >= read_index
    read_rel: jax.Array       # [G] int32 — batches RELEASED this tick
                              #   (leadership confirmed at/after their stamp;
                              #   FIFO from the oldest pending)
    read_served: jax.Array    # [G] int32 — individual reads in those batches
    read_lease: jax.Array     # [G] bool — the batch stamped THIS tick was
                              #   released same-tick by the lease fast path
                              #   (zero extra round trips)
    read_abort: jax.Array     # [G] bool — pending read batches dropped
                              #   (leadership/term changed); the host fails
                              #   them with NotLeader — clients retry safely
                              #   (reads never enter the log)
    read_carried: jax.Array   # [G] bool — that same-step release
                              #   (read_lease) needed evidence of an EARLIER
                              #   tick: the lease was carried
                              #   (cfg.lease_carry_ticks); never set
                              #   without read_lease
    read_kick: jax.Array      # [G] bool — the batch stamped this step was
                              #   left pending and asked for a barrier
                              #   heartbeat (phase 9 sends it at once)
    # Membership plane outputs.
    conf_app_idx: jax.Array   # [G] int32 — index of the config entry THIS
                              #   node appended as leader this tick (0 =
                              #   none; intake accept or the automatic
                              #   joint-leave).  The host stages it durably
                              #   with an empty payload, like the §8 no-op.
    conf_app_term: jax.Array  # [G] int32 — that entry's term
    conf_app_word: jax.Array  # [G] int32 — that entry's packed config word
    conf_word: jax.Array      # [G] int32 — the ACTIVE config after this
                              #   tick (latest config entry in the log, else
                              #   base_conf) — the host mirror's source
    conf_idx: jax.Array       # [G] int32 — that entry's log index (0 = the
                              #   config comes from base_conf)
    conf_pending: jax.Array   # [G] bool — a config entry is in flight
                              #   (conf_idx > commit): intake is fenced
    xfer_fired: jax.Array     # [G] bool — TimeoutNow sent to the transfer
                              #   target this tick (its match reached our
                              #   log end)
    xfer_abort: jax.Array     # [G] bool — a pending transfer was dropped
                              #   (deadline passed or leadership/term moved)
    debug_viol: jax.Array     # [G] int32 — in-kernel invariant violation code
                              #   (0 = ok; codes in step.py DEBUG_CODES).
                              #   Always zeros unless cfg.debug_checks.
    # CheckQuorum outputs (cfg.check_quorum; None-subtree when off so the
    # info pytree matches a build without the feature).
    cq_stepdown: Any = None   # Optional[[G] bool] — leader stepped down
                              #   this tick for lack of voter-quorum
                              #   contact within one election timeout
    cq_veto: Any = None       # Optional[[G] int32] — individual pending
                              #   lease reads vetoed by that step-down
                              #   (the reads a deposed-but-unaware leader
                              #   would otherwise have served stale)
    # Hibernation (cfg.hibernate; None-subtree when off).
    asleep: Any = None        # Optional[[G] bool] — the lane is asleep
                              #   after this step (a level: the host's
                              #   mirror; what woke or fell asleep is its
                              #   difference from the last step's)

    @classmethod
    def empty(cls, cfg: EngineConfig) -> "StepInfo":
        G = cfg.n_groups
        z = lambda: jnp.zeros((G,), I32)
        return cls(
            submit_start=z(), submit_acc=z(),
            dirty=jnp.zeros((G,), jnp.bool_),
            appended_from=z(), appended_to=z(), log_tail=z(),
            commit=z(), leader=jnp.full((G,), NIL, I32),
            ready=jnp.zeros((G,), jnp.bool_),
            snap_req=jnp.zeros((G,), jnp.bool_),
            snap_req_from=z(), snap_req_idx=z(), snap_req_term=z(),
            snap_req_conf=z(),
            noop_idx=z(), noop_term=z(),
            read_acc=z(), read_index=z(), read_rel=z(), read_served=z(),
            read_lease=jnp.zeros((G,), jnp.bool_),
            read_abort=jnp.zeros((G,), jnp.bool_),
            read_carried=jnp.zeros((G,), jnp.bool_),
            read_kick=jnp.zeros((G,), jnp.bool_),
            conf_app_idx=z(), conf_app_term=z(), conf_app_word=z(),
            conf_word=z(), conf_idx=z(),
            conf_pending=jnp.zeros((G,), jnp.bool_),
            xfer_fired=jnp.zeros((G,), jnp.bool_),
            xfer_abort=jnp.zeros((G,), jnp.bool_),
            debug_viol=z(),
            # Present iff the feature is on: the scan carry's pytree
            # structure must match node_step's output structure.
            cq_stepdown=(jnp.zeros((G,), jnp.bool_)
                         if cfg.check_quorum else None),
            cq_veto=(z() if cfg.check_quorum else None),
            asleep=(jnp.zeros((G,), jnp.bool_) if cfg.hibernate else None),
        )


def boot_conf_word(cfg: EngineConfig, n_voters: int | None = None) -> int:
    """The boot configuration word: the first ``n_voters`` slots (default
    all P) are voters, no joint set, no learners."""
    nv = cfg.n_peers if n_voters is None else n_voters
    assert 1 <= nv <= cfg.n_peers
    return int(conf_pack((1 << nv) - 1))


def init_state(cfg: EngineConfig, node_id: int, seed: int = 0,
               n_active: int | None = None,
               n_voters: int | None = None) -> RaftState:
    """Fresh boot state: every group a follower at term 0 with an empty log.

    The staggered election deadlines come from the per-group randomized
    timeout, seeded per node — the vectorized analog of the reference's
    randomized election window (support/RaftConfig.java:187-190).

    ``n_voters`` bounds the BOOT voter set to the first n slots (default:
    all P).  Slots outside it are spare capacity the membership plane can
    add later (learner catch-up -> promote), the shape rebalance walks
    start from.
    """
    G, P, K = cfg.n_groups, cfg.n_peers, cfg.read_slots
    key = jax.random.PRNGKey(seed * 7919 + node_id)
    key, sub = jax.random.split(key)
    first_deadline = jax.random.randint(
        sub, (G,), cfg.election_ticks, 2 * cfg.election_ticks, dtype=I32)
    active = jnp.arange(G) < (G if n_active is None else n_active)
    z = lambda *s: jnp.zeros(s, I32)
    return RaftState(
        node_id=jnp.asarray(node_id, I32),
        now=jnp.asarray(0, I32),
        rng=key,
        active=active,
        term=z(G),
        role=z(G),
        voted_for=jnp.full((G,), NIL, I32),
        leader_id=jnp.full((G,), NIL, I32),
        commit=z(G),
        applied=z(G),
        log=LogState(term=z(G, cfg.log_slots), conf=z(G, cfg.log_slots),
                     base=z(G), base_term=z(G),
                     base_conf=jnp.full((G,), boot_conf_word(cfg, n_voters),
                                        I32),
                     last=z(G)),
        next_idx=jnp.ones((G, P), I32),
        match_idx=z(G, P),
        send_next=jnp.ones((G, P), I32),
        inflight=z(G, P),
        own_from=z(G),
        hb_inflight=z(G, P),
        sent_at=z(G, P),
        need_snap=jnp.zeros((G, P), jnp.bool_),
        ok_at=z(G, P),
        fail_at=z(G, P),
        fail_streak=z(G, P),
        votes=jnp.zeros((G, P), jnp.bool_),
        prevotes=jnp.zeros((G, P), jnp.bool_),
        elect_deadline=first_deadline,
        hb_due=z(G),
        read_evid=z(G, P),
        rq_idx=z(G, K), rq_stamp=z(G, K), rq_n=z(G, K),
        rq_head=z(G), rq_len=z(G),
        conf_idx=z(G),
        conf_word=jnp.full((G,), boot_conf_word(cfg, n_voters), I32),
        xfer_to=jnp.full((G,), NIL, I32),
        xfer_dl=z(G),
        trace=(TraceState.empty(G, cfg.trace_depth)
               if cfg.trace_depth else None),
        heat=(HeatState.empty(G) if cfg.heat else None),
        qc=(QuorumContact.empty(G, P) if cfg.check_quorum else None),
        lease=(LeaseGuard.empty(G) if cfg.lease_carry_ticks else None),
        hib=(Hibernate.empty(G, P) if cfg.hibernate else None),
        read_seq=(None if cfg.read_lease else z(G)),
    )
