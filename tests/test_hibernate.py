"""Hibernation (core/step.py "hibernation"; RaftConfig.hibernate_regions):
a group nobody has asked anything of for an election timeout stops ticking,
the first request or message wakes it, and sleep only ever LENGTHENS a
follower's vote-denying promise and ENDS a leader's lease.

The engine's cases step three nodes by hand, a ROUND at a time (what a
round sends the next one delivers; tests/test_lease_carry.py's way), and
reach into the messages in flight where a case needs one lost, kept back or
delivered twice.  The runtime's cases step a ``LocalCluster`` in lock step:
the node-level beat, the peer-lost signal, the counters, and a
linearizability history over lanes that sleep and wake between operations.
"""

import functools
import hashlib
import json
import os
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rafting_tpu.core.cluster import (
    DeviceCluster, auto_host_inbox, cluster_step_nemesis,
)
from rafting_tpu.core.types import (
    FOLLOWER, LEADER, NIL, EngineConfig, FaultSchedule, Messages,
)

N = 3
T, H = 10, 2
BASE = dict(n_peers=N, log_slots=32, batch=4, max_submit=4,
            election_ticks=T, heartbeat_ticks=H, rpc_timeout_ticks=5,
            hibernate=True)


def cfg_of(G=1, **kw):
    return EngineConfig(n_groups=G, **{**BASE, **kw})


@functools.lru_cache(maxsize=None)
def _stepper(cfg):
    return jax.jit(partial(cluster_step_nemesis, cfg))


class Rounds:
    """Three nodes, one lane (``G`` lanes where a case wants more), a
    round at a time.  ``inflight`` (what the next round delivers, [sender,
    destination, lane] planes) is the case's to tamper with."""

    def __init__(self, cfg, seed=0):
        self.cfg, self.G = cfg, cfg.n_groups
        c = DeviceCluster(cfg, seed=seed)
        self.states, self.inflight, self.info = \
            c.states, c.inflight, c.last_info
        self.link = np.ones((N, N), bool)
        self.acked = 0              # highest index committed anywhere
        self.fifo = [[] for _ in range(N)]
        self.stale = []             # (round, node, read index, frontier)
        self.served = [0] * N
        self.flagged = 0            # sleep heartbeats sent, all rounds
        self.n = 0

    # ---------------------------------------------------------------- views
    def lane(self, name, node=None, g=0):
        a = np.asarray(functools.reduce(getattr, name.split("."),
                                        self.states))
        return a[:, g] if node is None else a[node, g]

    def asleep(self, g=0):
        return self.lane("hib.asleep", g=g)

    @property
    def now(self):
        return np.asarray(self.states.now)

    def leader(self, g=0):
        lead = np.nonzero(self.lane("role", g=g) == LEADER)[0]
        terms = self.lane("term", g=g)
        return int(max(lead, key=lambda i: terms[i])) if len(lead) else None

    def cut(self, a, b=None):
        for o in range(N) if b is None else (b,):
            if o != a:
                self.link[a, o] = self.link[o, a] = False

    def mend(self):
        self.link[:] = True

    def patch(self, **planes):
        """State surgery: ``name=(node, value)`` writes lane 0 of that
        node's plane (a [G] or [G, P] leaf of RaftState)."""
        kw = {}
        for name, (node, value) in planes.items():
            a = np.array(getattr(self.states, name))
            a[node, 0] = value
            kw[name] = jnp.asarray(a)
        self.states = self.states.replace(**kw)

    def sent(self, name, src, dst, g=0):
        return np.asarray(getattr(self.inflight, name))[src, dst, g]

    def drop(self, kind, src, dst, g=0):
        """Lose the ``kind`` message in flight from src to dst; returns
        its fields (to deliver later: ``inject``)."""
        kept = {}
        for f in Messages.__dataclass_fields__:
            if f.split("_", 1)[0] == kind \
                    and getattr(self.inflight, f) is not None:
                a = np.array(getattr(self.inflight, f))
                kept[f] = a[src, dst, g].copy()
                if f.endswith("_valid"):
                    a[src, dst, g] = False
                    self.inflight = self.inflight.replace(
                        **{f: jnp.asarray(a)})
        return kept

    def inject(self, kept, src, dst, g=0, **over):
        """Put a message into what the next round delivers."""
        kw = {}
        for f, v in {**kept, **over}.items():
            a = np.array(getattr(self.inflight, f))
            a[src, dst, g] = v
            kw[f] = jnp.asarray(a)
        self.inflight = self.inflight.replace(**kw)

    # ---------------------------------------------------------------- steps
    def round(self, clock=1, reads=None, writes=None, wake=(), xfer=None,
              g=0):
        """One round.  ``reads`` / ``writes``: {node: n} offered on lane
        ``g``; ``wake``: nodes whose host gives the peer-lost signal on
        it.  Returns the round's StepInfo (numpy, [N, G] leaves)."""
        cfg, G = self.cfg, self.G
        def per(d):
            a = np.zeros((N, G), np.int32)
            for i, n in (d or {}).items():
                a[i, g] = n
            return jnp.asarray(a)
        host = auto_host_inbox(cfg, self.states, per(writes), False,
                               self.info, per(reads))
        w = np.zeros((N, G), bool)
        for i in wake:
            w[i, g] = True
        clock = np.broadcast_to(np.asarray(clock, np.int32), (N,))
        host = host.replace(clock=jnp.asarray(clock), wake=jnp.asarray(w))
        if xfer is not None:
            node, target = xfer
            x = np.full((N, G), NIL, np.int32)
            x[node, g] = target
            host = host.replace(xfer_target=jnp.asarray(x))
        off = jnp.zeros((N,), jnp.bool_)
        fault = FaultSchedule(link_up=jnp.asarray(self.link), crash=off,
                              stall=off, dup=jnp.zeros((N, N), jnp.bool_))
        self.states, self.inflight, self.info = _stepper(cfg)(
            self.states, self.inflight, host, self.info, fault)
        info = jax.tree.map(np.asarray, self.info)
        for n in range(N):
            q = self.fifo[n]
            if info.read_abort[n, g]:
                q.clear()
            if info.read_acc[n, g] > 0:
                q.append((int(info.read_index[n, g]), self.acked))
            for _ in range(int(info.read_rel[n, g])):
                ridx, frontier = q.pop(0)
                if ridx < frontier:
                    self.stale.append((self.n, n, ridx, frontier))
            self.served[n] += int(info.read_served[n, g])
        self.acked = max(self.acked, int(self.lane("commit", g=g).max()))
        self.flagged += int((np.asarray(self.inflight.ae_valid)
                             & np.asarray(self.inflight.ae_sleep)).sum())
        self.n += 1
        return info

    def tick(self, n=1, **first):
        info = self.round(**first)
        for _ in range(n - 1):
            info = self.round()
        return info

    def until(self, pred, limit=120, what="condition"):
        for _ in range(limit):
            if pred():
                return
            self.round()
        raise AssertionError(f"{what} not reached in {limit} rounds")

    def sleep(self):
        """Elect, settle and idle until every member's lane 0 sleeps;
        returns the leader."""
        self.until(lambda: self.asleep().all(), what="the group asleep")
        lead = self.leader()
        assert lead is not None
        return lead

    def idle(self):
        """Elect and idle until the leader is one round short of
        proposing sleep (nothing flagged yet, everything level)."""
        self.until(lambda: self.leader() is not None
                   and self.lane("commit").min() >= 1, what="a leader")
        lead = self.leader()
        self.until(lambda: int(self.now[lead])
                   - int(self.lane("hib.busy_at", lead)) >= T - 2,
                   what="an idle stretch")
        assert self.flagged == 0 and not self.asleep().any()
        return lead


def followers(lead):
    return [i for i in range(N) if i != lead]


# ------------------------------------------------- entry, sleep and wake ----


def test_an_idle_group_stops_ticking_and_holds_its_terms():
    """Nothing asked for an election timeout: the leader proposes, both
    followers agree, all three sleep; from then on no message leaves any
    of them, no timer expires and no term moves, for as long as one
    likes."""
    r = Rounds(cfg_of())
    lead = r.sleep()
    assert r.flagged >= 2               # one proposal a follower at least
    terms, roles = r.lane("term").copy(), r.lane("role").copy()
    for _ in range(6 * T):
        r.round()
        assert not any(np.asarray(getattr(r.inflight, f)).any()
                       for f in Messages.__dataclass_fields__
                       if f.endswith("_valid")), "a sleeper sent something"
    assert r.asleep().all()
    assert (r.lane("term") == terms).all()
    assert (r.lane("role") == roles).all() and roles[lead] == LEADER
    # The device still steps the lane: its clock went on.
    assert int(r.now[lead]) - int(r.lane("hib.busy_at", lead)) > 6 * T


def test_the_leader_sleeps_only_when_every_member_has_said_so():
    """One follower's word is not enough: with the other cut off the
    leader keeps heartbeating (flagged), the follower that agreed sleeps
    under it with its timer off, and nobody campaigns."""
    r = Rounds(cfg_of())
    lead = r.idle()
    a, b = followers(lead)
    r.cut(lead, b)
    r.tick(3 * T)
    assert r.asleep()[a] and not r.asleep()[lead]
    assert r.lane("hib.slept", lead)[a] and not r.lane("hib.slept", lead)[b]
    assert r.lane("role", a) == FOLLOWER and r.lane("term", a) \
        == r.lane("term", lead)
    r.mend()
    r.until(lambda: r.asleep().all(), what="all asleep after the mend")


# What refuses entry, each alone.  A case returns a callable to run every
# round while the refusal should hold.
def _a_write_since(r, lead):
    r.round(writes={lead: 1})
    return lambda: None


def _a_member_behind(r, lead):
    r.cut(lead, followers(lead)[1])
    r.round(writes={lead: 1})       # commits with the other one
    return lambda: None


def _an_uncommitted_tail(r, lead):
    r.cut(lead)
    r.round(writes={lead: 1})
    return lambda: None


def _a_pending_read(r, lead):
    r.cut(lead)
    info = r.round(reads={lead: 1})
    assert info.read_acc[lead, 0] == 1 and not info.read_lease[lead, 0]
    return lambda: None


# Strict ReadIndex: the batch waits for acknowledgements of heartbeats sent
# after its stamp, which the cut keeps away.
_a_pending_read.cfg = dict(read_lease=False)


def _a_pending_transfer(r, lead):
    target = followers(lead)[0]
    return lambda: r.patch(xfer_to=(lead, target),
                           xfer_dl=(lead, int(r.now[lead]) + T))


@pytest.mark.parametrize("refusal", [
    _a_write_since, _a_member_behind, _an_uncommitted_tail,
    _a_pending_read, _a_pending_transfer],
    ids=lambda f: f.__name__.lstrip("_"))
def test_entry_is_refused_by_each_precondition(refusal):
    """A leader one round short of proposing, and then one thing in the
    way: for a whole election timeout (which the idle stretch alone would
    have outlasted by far) no heartbeat carries the flag and nobody
    sleeps."""
    r = Rounds(cfg_of(**getattr(refusal, "cfg", {})))
    lead = r.idle()
    hold = refusal(r, lead)
    for _ in range(T - 1):
        hold()
        r.round()
        assert r.flagged == 0, refusal.__name__
        assert not r.asleep().any()


def test_wake_by_submit():
    """A write offered to a sleeping leader is accepted in that step and
    its AppendEntries (unflagged) wakes both followers; it commits as
    fast as on a lane that never slept."""
    r = Rounds(cfg_of())
    lead = r.sleep()
    before = int(r.lane("commit", lead))
    info = r.round(writes={lead: 1})
    assert info.submit_acc[lead, 0] == 1 and not r.asleep()[lead]
    assert all(r.sent("ae_valid", lead, f) and r.sent("ae_n", lead, f) == 1
               and not r.sent("ae_sleep", lead, f) for f in followers(lead))
    r.round()
    assert not r.asleep().any()
    r.round()
    assert int(r.lane("commit", lead)) == before + 1


def test_wake_by_read():
    """A read offered to a sleeping leader is stamped in that step, finds
    no evidence, and its barrier heartbeat goes out at once: the existing
    read_kick path is the wake."""
    r = Rounds(cfg_of())
    lead = r.sleep()
    info = r.round(reads={lead: 1})
    assert info.read_acc[lead, 0] == 1 and info.read_kick[lead, 0]
    assert not info.read_lease[lead, 0] and not r.asleep()[lead]
    assert all(r.sent("ae_valid", lead, f) and not r.sent("ae_sleep", lead, f)
               for f in followers(lead))
    r.round()
    assert not r.asleep().any()
    info = r.round()
    assert info.read_served[lead, 0] == 1 and not r.stale


def test_wake_by_message():
    """Any message but the two that keep a lane asleep wakes it: a
    pre-vote request wakes a sleeping follower (which refuses it and
    starts a whole new timeout) and a sleeping leader (which heartbeats
    at once, unflagged, and so wakes the rest)."""
    r = Rounds(cfg_of())
    lead = r.sleep()
    a, b = followers(lead)
    term = int(r.lane("term", a))
    ask = dict(rv_valid=True, rv_term=term + 1, rv_prevote=True,
               rv_last_idx=int(r.lane("log.last", a)), rv_last_term=term)
    r.inject(ask, b, a)
    r.inject(ask, b, lead)
    r.round()
    assert not r.asleep()[a] and not r.asleep()[lead] and r.asleep()[b]
    assert r.sent("rvr_valid", a, b) and not r.sent("rvr_granted", a, b)
    assert int(r.lane("elect_deadline", a)) >= int(r.now[a]) + T
    assert r.sent("ae_valid", lead, b) and not r.sent("ae_sleep", lead, b)
    r.round()
    assert not r.asleep().any()
    assert (r.lane("term") == term).all() and r.leader() == lead


def test_wake_by_peer_loss():
    """The host's peer-lost signal wakes a sleeping follower with a whole
    new timeout; the leader being gone for good, the followers elect one
    of themselves within two election timeouts of the signal."""
    r = Rounds(cfg_of())
    lead = r.sleep()
    r.cut(lead)
    r.tick(3 * T)
    assert r.asleep().all()             # silence alone wakes nobody
    r.round(wake=followers(lead))
    for f in followers(lead):
        assert not r.asleep()[f]
        assert int(r.lane("elect_deadline", f)) >= int(r.now[f]) + T
    since = r.n
    r.until(lambda: r.leader() in followers(lead)
            and r.lane("term").max() > r.lane("term", lead),
            limit=2 * T + 8, what="a new leader")
    assert r.n - since <= 2 * T + 8


def test_entry_clears_a_phantom_window_slot():
    """A led lane that carries window slots nothing will ever release
    (acknowledgements merged away in a storm: PERF.md window_leaked_pct)
    still sleeps, because entry asks what the acknowledgements prove and
    not what the counters say, and it sleeps with them cleared: nothing
    times out in its sleep or at its wake."""
    r = Rounds(cfg_of())
    lead = r.idle()
    phantom = np.zeros(N, np.int32)
    phantom[followers(lead)] = 2
    r.patch(hb_inflight=(lead, phantom), inflight=(lead, phantom // 2))
    r.until(lambda: r.asleep().all(), what="asleep over phantom slots")
    assert not r.lane("hb_inflight", lead).any()
    assert not r.lane("inflight", lead).any()
    r.tick(3 * T)
    r.round(writes={lead: 1})
    r.tick(4)
    assert not r.lane("fail_at", lead).any()
    assert not r.lane("fail_streak", lead).any()
    assert int(r.lane("commit", lead)) == int(r.lane("log.last", lead)) \
        == int(r.lane("log.last").min())


# ----------------------------------------------------------- the proof ----


def test_a_sleep_never_shortens_a_promise():
    """(a).  A follower's vote-denying promise as lease_open sees it is
    never earlier for having slept: long after the deadline its last
    heartbeat left it (so an awake follower's timer would have fired and
    it would grant) a sleeper refuses a pre-vote, in the step that wakes
    it and for a whole election timeout after it."""
    r = Rounds(cfg_of())
    lead = r.sleep()
    a, b = followers(lead)
    r.cut(lead)                         # the leader's wake reaches nobody
    r.tick(3 * T)
    assert int(r.now[a]) > int(r.lane("elect_deadline", a)) + T
    term = int(r.lane("term", a))
    ask = dict(rv_valid=True, rv_term=term + 1, rv_prevote=True,
               rv_last_idx=int(r.lane("log.last", a)), rv_last_term=term)
    woken_at = int(r.now[a]) + 1
    for k in range(T):
        r.inject(ask, b, a)
        r.round()
        assert r.sent("rvr_valid", a, b)
        assert not r.sent("rvr_granted", a, b), f"granted {k} ticks in"
        if k == 0:
            assert int(r.lane("elect_deadline", a)) >= woken_at + T
    # The promise does end: a whole timeout after the wake it grants (or
    # campaigns itself), as an awake follower of a silent leader does.
    r.until(lambda: r.lane("role", a) != FOLLOWER
            or r.lane("term", a) > term, limit=2 * T,
            what="the woken follower's own timeout")


def test_a_leader_serves_no_read_from_evidence_of_before_its_sleep():
    """(b).  Entry drops the lease evidence, nothing is stored in a step
    the lane entered asleep, so the first read after a sleep is stamped
    against nothing and waits for acknowledgements of a heartbeat sent
    after the wake; an acknowledgement from before that arrives with the
    read releases nothing."""
    r = Rounds(cfg_of())
    r.until(lambda: r.leader() is not None
            and (r.lane("read_evid", r.leader()) > 0).sum() == N - 1,
            what="a leader with evidence")
    lead = r.sleep()
    assert not r.lane("read_evid", lead).any()
    a = followers(lead)[0]
    # An acknowledgement of the last sleep heartbeat, delivered late and
    # beside the read: fresh by its echo, and worth nothing.
    late = dict(aer_valid=True, aer_term=int(r.lane("term", lead)),
                aer_success=True, aer_empty=True, aer_occ=False,
                aer_match=int(r.lane("log.last", lead)),
                aer_tick=int(r.now[lead]), aer_asleep=True)
    for f in followers(lead):
        r.inject(late, f, lead)
    info = r.round(reads={lead: 1})
    assert info.read_acc[lead, 0] == 1 and info.read_kick[lead, 0]
    assert info.read_rel[lead, 0] == 0
    assert not r.lane("read_evid", lead).any()
    r.round()
    info = r.round()
    assert info.read_served[lead, 0] == 1 and not r.stale


def test_a_leader_deposed_in_its_sleep_answers_with_a_failed_barrier():
    """(c).  Cut off asleep, its followers woken by the peer-lost signal
    elect another and commit a write.  A read on the old leader wakes it,
    is stamped and never released; once the cut mends its barrier
    heartbeat is answered at the higher term and the read is aborted:
    never a value."""
    r = Rounds(cfg_of())
    old = r.sleep()
    r.cut(old)
    r.round(wake=followers(old))
    r.until(lambda: r.leader() in followers(old)
            and r.lane("term").max() > r.lane("term", old),
            limit=3 * T, what="a new leader")
    new = r.leader()
    r.until(lambda: r.info.ready[new, 0], what="the new leader ready")
    r.round(writes={new: 1})
    r.tick(4)
    assert r.acked > int(r.lane("commit", old))
    assert r.asleep()[old] and r.lane("role", old) == LEADER
    info = r.round(reads={old: 1})
    assert info.read_acc[old, 0] == 1 and info.read_kick[old, 0]
    r.tick(T)
    assert r.served[old] == 0 and r.fifo[old]
    r.mend()
    aborted = False
    for _ in range(4):
        aborted |= bool(r.round().read_abort[old, 0])
    assert aborted and r.served[old] == 0 and not r.stale
    assert r.lane("role", old) == FOLLOWER


def _lost(r, lead, a, b):
    """The proposal never reaches b: the leader keeps heartbeating, b's
    timer stays fed, a sleeps, nobody campaigns; once it gets through,
    all sleep."""
    term = r.lane("term").copy()
    for _ in range(3 * T):
        r.drop("ae", lead, b) if r.sent("ae_sleep", lead, b) else None
        r.round()
    assert r.asleep()[a] and not r.asleep()[lead]
    assert (r.lane("term") == term).all() and r.leader() == lead
    r.until(lambda: r.asleep().all(), what="asleep once it gets through")


def _twice(r, lead, a, b):
    """Delivered again after the lane woke for a write: the follower's
    log no longer ends where the heartbeat says, so it stays awake."""
    r.until(lambda: r.sent("ae_sleep", lead, a), what="a proposal")
    kept = r.drop("ae", lead, a)
    r.inject(kept, lead, a)
    r.round()
    r.round(writes={lead: 1})
    r.round()                           # a appends, awake
    assert not r.asleep()[a]
    r.inject(kept, lead, a)
    r.round()
    assert not r.asleep()[a], "slept on a heartbeat from before the write"


def _behind_a_heartbeat(r, lead, a, b):
    """Overtaken by the unflagged heartbeat of a read: a may fall asleep
    on it under an awake leader, whose latch ignores the word and whose
    next cadence heartbeat wakes it within one heartbeat period."""
    r.until(lambda: r.sent("ae_sleep", lead, a), what="a proposal")
    kept = r.drop("ae", lead, a)
    r.round(reads={lead: 1})            # busy: the proposal is withdrawn
    r.round()
    r.inject(kept, lead, a)
    r.round()
    slept_at = r.n
    assert r.asleep()[a] and not r.asleep()[lead]
    r.until(lambda: not r.asleep()[a], limit=H + 2,
            what="woken by the cadence")
    assert r.n - slept_at <= H + 1
    assert not r.lane("hib.slept", lead).any() and not r.asleep()[lead]


def _at_a_stale_term(r, lead, a, b):
    """A sleep heartbeat of an older term puts nobody to sleep and wakes
    a sleeper; and a follower that slept through an election wakes at the
    new leader's first AppendEntries, into its term."""
    r.until(lambda: r.sent("ae_sleep", lead, a), what="a proposal")
    kept = r.drop("ae", lead, a)
    r.inject(kept, lead, a)
    r.until(lambda: r.asleep().all(), what="all asleep")
    term = int(r.lane("term", a))
    r.inject(kept, lead, a, ae_term=term - 1)
    r.round()
    assert not r.asleep()[a], "asleep on a deposed leader's heartbeat"
    r.until(lambda: r.asleep().all(), what="all asleep again")
    # a sleeps on, cut off, while the other two move to a new term (a
    # transfer: its target campaigns at once).
    r.cut(a)
    r.round(xfer=(lead, b))
    r.until(lambda: r.leader() == b and r.lane("term", b) > term,
            limit=2 * T, what="a new term without a")
    assert r.asleep()[a] and r.lane("term", a) == term
    r.mend()
    r.until(lambda: not r.asleep()[a], limit=8,
            what="a woken by the new leader")
    assert r.lane("term", a) == r.lane("term").max() > term


@pytest.mark.parametrize("fate", [
    _lost, _twice, _behind_a_heartbeat, _at_a_stale_term],
    ids=lambda f: f.__name__.lstrip("_"))
def test_a_sleep_heartbeat_gone_astray_leaves_nobody_asleep_wrongly(fate):
    """(d).  Lost, duplicated, overtaken, or of a stale term: no follower
    stays asleep under an awake, unproposing leader for longer than one
    heartbeat period, none sleeps at a stale term, and no read is stale
    for any of it."""
    r = Rounds(cfg_of())
    lead = r.idle()
    a, b = followers(lead)
    fate(r, lead, a, b)
    assert not r.stale


# The final states of the run below on the parent of this change (the commit
# before hibernation): sha256 over every leaf of every node's state.
PARENT_DIGEST = \
    "15bdfde0777b3e3edccf5240dc7a8aadade07138180b9f91805d4bb0b789ac7a"


def test_the_field_off_is_the_program_it_was():
    """(e).  Off, the state holds no hibernation lane, the messages no
    flag, HostInbox no signal and StepInfo no level, and a cluster's run
    (elections, writes, reads, a cut) ends in the states the parent ended
    in, bit for bit.  tests/test_oracle_parity.py holds the oracle to the
    step at both settings."""
    cfg = EngineConfig(n_groups=8, **{**BASE, "hibernate": False})
    c = DeviceCluster(cfg, seed=3)
    for t in range(110):
        if t in (30, 60):
            c.isolate(t // 30 - 1)
        if t in (50, 85):
            c.heal()
        c.tick(submit_n=int(t % 7 == 0), read_n=int(t % 5 == 0))
    assert c.states.hib is None and c.last_info.asleep is None
    assert c.inflight.ae_sleep is None and c.inflight.aer_asleep is None
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(c.states):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    assert h.hexdigest() == PARENT_DIGEST


# --------------------------------------------------------- the runtime ----


def _cluster(tmp_path, G, **kw):
    from rafting_tpu.testkit.fixtures import NullProvider
    from rafting_tpu.testkit.harness import LocalCluster
    cfg = EngineConfig(n_groups=G, n_peers=N, log_slots=16, batch=4,
                       max_submit=4, election_ticks=T, heartbeat_ticks=H,
                       rpc_timeout_ticks=6, hibernate=True, **kw)
    return LocalCluster(cfg, str(tmp_path), seed=5,
                        provider_factory=lambda i: NullProvider())


def _period(c, arrivals=5):
    """A period of every node's clock: the timer's round, then rounds in
    which no clock moves, so that an election fits inside a period as it
    does under a started loop."""
    c.tick()
    for _ in range(arrivals):
        for node in c.nodes.values():
            node.tick(arrival=True)


def test_counters_spans_and_the_beat_on_a_lock_step_cluster(tmp_path):
    """Lock step, 8 lanes: every lane falls asleep on every node, the
    gauge says so, and from then on each node sends each peer exactly one
    empty frame a period and nothing else; a write and a read each wake
    one lane, counted by cause on each member; /metrics lists the new
    series."""
    from rafting_tpu.utils.metrics import validate_exposition
    c = _cluster(tmp_path, 8)
    try:
        nodes = list(c.nodes.values())
        c.tick_until(lambda: all(n.h_asleep.all() for n in nodes),
                     what="every lane asleep")
        for n in nodes:
            assert n.metrics["lane_sleeps"] == 8
            assert n.metrics["lane_wakes"] == 0
        c.tick()
        beats = [n.metrics["node_beats_sent"] for n in nodes]
        c.tick(5)
        for n, b in zip(nodes, beats):
            assert n.metrics["node_beats_sent"] - b == 5 * (N - 1)
            assert n.metrics._gauges["lanes_asleep"] == 8
        lead = c.leader_of(3)
        f = c.nodes[lead].submit(3, b"w")
        c.tick(4)
        assert f.done() and f.exception() is None
        c.tick_until(lambda: all(n.h_asleep.all() for n in nodes),
                     what="asleep again")
        q = c.nodes[lead].read(3, b"q")
        c.tick(4)
        assert q.done() and q.exception() is None
        for i, n in c.nodes.items():
            m = n.metrics
            assert m["lane_wakes"] == 2 == m["lane_sleeps"] - 8 + (
                0 if n.h_asleep[3] else 1)
            cause = "wake_request" if i == lead else "wake_message"
            assert m[cause] == 2 and m["wake_peer_lost"] == 0
        srv = c.nodes[lead].start_observability()
        import urllib.request
        get = lambda path: urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}{path}", timeout=5).read().decode()
        page = get("/metrics")
        assert json.loads(get("/healthz"))["groups_asleep"] \
            == int(c.nodes[lead].h_asleep.sum())
        validate_exposition(page)
        for name in ("lanes_asleep", "lane_sleeps_total", "lane_wakes_total",
                     "wake_request_total", "wake_message_total",
                     "wake_peer_lost_total", "node_beats_sent_total"):
            assert f"raft_{name}" in page, name
    finally:
        c.close()


def test_the_beat_is_heard_over_tcp_and_a_stopped_node_is_missed(tmp_path):
    """Real sockets: with every lane asleep the only frames on the wire
    are the beats, each reader counts its source's (``heard``), and a node
    that stops stepping is taken as lost by the two others election_ticks
    of THEIR timer steps later: the lanes that followed it wake."""
    from rafting_tpu.testkit.fixtures import NullProvider
    from rafting_tpu.testkit.harness import LocalCluster
    cfg = EngineConfig(n_groups=8, n_peers=N, log_slots=16, batch=4,
                       max_submit=4, election_ticks=T, heartbeat_ticks=H,
                       rpc_timeout_ticks=6, hibernate=True)
    c = LocalCluster(cfg, str(tmp_path), seed=5, transport="tcp",
                     provider_factory=lambda i: NullProvider())
    try:
        nodes = c.nodes

        def rounds(n, who):
            for _ in range(n):
                for i in who:
                    nodes[i].tick()
                time.sleep(0.01)        # the readers' turn

        for _ in range(40):
            rounds(10, nodes)
            if all(n.h_asleep.all() for n in nodes.values()):
                break
        assert all(n.h_asleep.all() for n in nodes.values())
        heard = {i: dict(n.transport.heard) for i, n in nodes.items()}
        beats = {i: n.metrics["node_beats_sent"] for i, n in nodes.items()}
        rounds(6, nodes)
        for i, n in nodes.items():
            assert n.metrics["node_beats_sent"] - beats[i] == 6 * (N - 1)
            for p in nodes:
                if p != i:
                    assert n.transport.heard[p] - heard[i][p] >= 5, (i, p)
            assert n.metrics["wake_peer_lost"] == 0
        stopped = max(nodes, key=lambda i: (nodes[i].h_role == LEADER).sum())
        left = [i for i in nodes if i != stopped]
        followed = {i: int((nodes[i].h_leader == stopped).sum())
                    for i in left}
        assert all(followed.values())
        rounds(T + 2, left)
        for i in left:
            assert nodes[i].metrics["wake_peer_lost"] == followed[i]
    finally:
        c.close()


def test_asleep_woken_and_woke_ride_the_spans_that_are_there(tmp_path):
    """Under a profiler session ``raft.mirrors`` carries ``asleep`` (this
    node's open lanes asleep after the step) and ``woken`` on every timer
    step, and ``raft.reads`` carries ``woke`` beside ``lease_hits`` where
    a step served a query: a read on a sleeping lane is one barrier that
    woke it, a read right behind it rides the lease, and ``woke +
    lease_hits <= queries``.  No span is new."""
    import glob

    from jax.profiler import ProfileData
    c = _cluster(tmp_path / "data", 8)
    trace_dir = str(tmp_path / "trace")
    try:
        nodes = list(c.nodes.values())
        c.tick_until(lambda: all(n.h_asleep.all() for n in nodes),
                     what="every lane asleep")
        lead = c.leader_of(2)
        node = c.nodes[lead]
        with jax.profiler.trace(trace_dir):
            c.tick(2)
            first = node.read(2, b"q")
            c.tick(4)
            second = node.read(2, b"q")
            c.tick(3)
            assert first.done() and second.done()
            assert first.exception() is None and second.exception() is None
    finally:
        c.close()
    (path,) = glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True)
    spans = [(e.name, dict(e.stats))
             for p in ProfileData.from_file(path).planes
             if p.name == "/host:CPU" for ln in p.lines for e in ln.events
             if e.name.startswith("raft.")]
    assert {n for n, _ in spans} <= {
        "raft." + k for k in (
            "dispatch_intake", "dispatch_upload", "dispatch_enqueue", "wal",
            "fsync", "send", "apply", "reads", "maintain", "scan_device",
            "scan_fetch", "mirrors", "tail", "wait")}
    mine = lambda name: [s for n, s in spans
                         if n == name and s["node"] == lead]
    mirrors = mine("raft.mirrors")
    assert all("asleep" in s and "woken" in s for s in mirrors)
    assert [s["asleep"] for s in mirrors[:2]] == [8, 8]
    assert min(s["asleep"] for s in mirrors) == 7
    assert sum(s["woken"] for s in mirrors) == 1
    served = [s for s in mine("raft.reads") if "queries" in s]
    assert sum(s["queries"] for s in served) == 2
    assert sum(s["woke"] for s in served) == 1
    assert sum(s["lease_hits"] for s in served) == 1
    assert all(s["woke"] + s["lease_hits"] <= s["queries"] for s in served)


def test_a_lost_node_with_every_lane_asleep_is_replaced_within_the_bound(
        tmp_path):
    """Three nodes, 64 lanes, every lane asleep; the node that leads the
    most is cut off.  Its silence is noticed election_ticks periods later
    (the beat's count), the lanes that followed it wake with a randomised
    timeout in [T, 2T), and every lane has a ready leader among the two
    that are left within election_ticks + 2 x election_ticks periods of
    the cut: the bound the configuration's file states."""
    c = _cluster(tmp_path, 64)
    try:
        nodes = c.nodes
        for _ in range(200):
            _period(c)
            if all(n.h_asleep.all() for n in nodes.values()):
                break
        else:
            raise AssertionError("not every lane fell asleep")
        led = {i: int((n.h_role == LEADER).sum()) for i, n in nodes.items()}
        lost = max(led, key=led.get)
        assert led[lost] > 0
        orphans = np.flatnonzero(nodes[lost].h_role == LEADER)
        left = [i for i in nodes if i != lost]
        c.net.partition([[lost], left])
        for period in range(1, 3 * T + 2):
            _period(c)
            ready = np.zeros(64, bool)
            for i in left:
                n = nodes[i]
                ready |= (n.h_role == LEADER) & n.h_ready
            if ready.all():
                break
        assert ready.all(), f"{int((~ready).sum())} lanes still leaderless"
        assert period <= 3 * T, period
        assert period > T, "led again before the silence could be noticed"
        for i in left:
            m = nodes[i].metrics
            assert m["wake_peer_lost"] == len(orphans)
        # The lanes the two survivors led among themselves slept through it.
        assert sum(int(nodes[i].h_asleep.sum()) for i in left) \
            == 2 * (64 - len(orphans))
    finally:
        c.close()


def test_linearizable_over_lanes_that_sleep_and_wake(tmp_path):
    """Started loops, three clients (one a member, so reads and writes
    enter on leaders and on followers and are forwarded) in bursts with
    pauses longer than the idle threshold between them: the lanes sleep
    between bursts and every burst's first operations wake them.  The
    history is linearizable."""
    from rafting_tpu.machine.kv_machine import KVMachineProvider
    from rafting_tpu.testkit import linz
    from rafting_tpu.testkit.chaos import KVWorkload
    from rafting_tpu.testkit.harness import LocalCluster
    from rafting_tpu.testkit.history import History

    cfg = EngineConfig(n_groups=3, n_peers=N, log_slots=64, batch=8,
                       max_submit=8, election_ticks=T, heartbeat_ticks=H,
                       rpc_timeout_ticks=8, check_quorum=True,
                       hibernate=True)
    root = str(tmp_path)
    cluster = LocalCluster(
        cfg, root, seed=17,
        provider_factory=lambda i: KVMachineProvider(
            os.path.join(root, f"node{i}", "kv")))
    try:
        for g in range(cfg.n_groups):
            cluster.wait_leader(g)
        history = History()
        period = 0.03
        cluster.start_loops(period)
        wakes = lambda: sum(int(n.metrics["lane_wakes"])
                            for n in cluster.nodes.values())
        for burst in range(3):
            deadline = time.monotonic() + 200 * period
            asleep = lambda: all(n.h_asleep[1]
                                 for n in cluster.nodes.values())
            while not asleep() and time.monotonic() < deadline:
                time.sleep(period)
            assert asleep(), "the lane did not fall asleep"
            load = KVWorkload(cluster, history, group=1, clients=3,
                              seed=17 + burst)
            load.start()
            time.sleep(12 * period)
            load.stop()
            load.join()
        cluster.stop_loops()
        assert wakes() >= 3 * N
        assert sum(int(n.metrics["wake_request"])
                   for n in cluster.nodes.values()) >= 3
        counts = history.counts()
        assert counts["ok"] >= 10, f"workload starved: {counts}"
        res = linz.check(history)
        assert res.ok, res.render()
    finally:
        cluster.close()
