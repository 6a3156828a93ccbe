"""The engine's clock is the host's to advance (``HostInbox.clock``).

A node under its own loop steps when the period's timer fires (clock 1)
and when work arrives in between (clock 0).  The steps of one period are
one tick of the protocol's clock, delivered in pieces; these tests hold
the engine to what that means:

* I1  no run of clock-0 steps moves ``now`` or expires a timer;
* I2  strict ReadIndex stamps a read in the step it arrives in and never
      releases it on the echo of an AppendEntries that left before that
      step (tests/test_read_index.py has the rest); the lease releases
      on evidence of the same ``now`` and never on evidence of an earlier;
* I3  a clock-0 step that brings nothing emits nothing;
* kernel and scalar oracle agree over random mixes of the two kinds of
  step under the nemesis regimes, lease on and off; and whoever sets no
  clock steps the program it always stepped.
"""

import jax
import numpy as np
import pytest

from rafting_tpu.core.step import node_step
from rafting_tpu.core.types import (
    CANDIDATE, LEADER, PRE_CANDIDATE, EngineConfig, HostInbox, Messages,
    init_state,
)
from rafting_tpu.testkit.parity import MSG_GROUPS, route_numpy, run_parity

ARRIVAL = np.asarray(0, np.int32)


def small_cfg(**kw):
    base = dict(n_groups=4, n_peers=3, log_slots=16, batch=4, max_submit=4,
                election_ticks=6, heartbeat_ticks=2, rpc_timeout_ticks=5,
                pre_vote=True)
    base.update(kw)
    return EngineConfig(**base)


class Trio:
    """Three engines stepped by hand: who steps, with which clock, and
    which of the messages in flight reach it are the test's to say."""

    def __init__(self, cfg, seed=0):
        self.cfg = cfg
        self.N = cfg.n_peers
        self.states = [init_state(cfg, i, seed=seed) for i in range(self.N)]
        self.out = [Messages.empty(cfg) for _ in range(self.N)]
        self.info = [None] * self.N

    def inbox_of(self, n, senders=None):
        """What node ``n`` would receive now from ``senders`` (default:
        everyone)."""
        conn = np.zeros((self.N, self.N), bool)
        for s in (range(self.N) if senders is None else senders):
            conn[s, n] = True
        return route_numpy(self.out, conn)[n]

    def step(self, n, inbox=None, arrival=False, **host):
        """One step of node ``n``; its outbox replaces what it had in
        flight.  ``inbox`` None = nothing arrives."""
        h = HostInbox.empty(self.cfg)
        if arrival:
            h = h.replace(clock=ARRIVAL)
        if host:
            h = h.replace(**{k: np.asarray(v, np.int32)
                             for k, v in host.items()})
        if inbox is None:
            inbox = Messages.empty(self.cfg)
        self.states[n], self.out[n], self.info[n] = node_step(
            self.cfg, self.states[n], inbox, h)
        return self.info[n]

    def round(self, arrival=False):
        """Everyone steps on what everyone sent last round."""
        inboxes = route_numpy(self.out, np.ones((self.N, self.N), bool))
        for n in range(self.N):
            self.step(n, inboxes[n], arrival=arrival)

    def now(self, n):
        return int(self.states[n].now)

    def leader(self, g=0):
        roles = [int(np.asarray(s.role)[g]) for s in self.states]
        return roles.index(LEADER) if roles.count(LEADER) == 1 else None

    def settle(self, g=0, max_rounds=200):
        """Timer rounds until group ``g`` has one leader whose own-term
        no-op is committed everywhere and nothing is in flight but the
        heartbeat cadence.  Returns the leader."""
        for _ in range(max_rounds):
            self.round()
            lead = self.leader(g)
            if lead is None:
                continue
            s = self.states[lead]
            own = int(np.asarray(s.own_from)[g])
            if all(int(np.asarray(t.commit)[g]) >= own > 0
                   for t in self.states) \
                    and all(int(np.asarray(t.leader_id)[g]) == lead
                            for t in self.states):
                return lead
        raise AssertionError("no settled leader")


def valid_lanes(out: Messages):
    return {v: int(np.asarray(getattr(out, v)).sum()) for v in MSG_GROUPS}


# ---------------------------------------------------------------- parity

REGIMES = {
    "drops": dict(drop_p=0.2, part_p=0.12),
    "crash_stall": dict(crash_p=0.04, stall_p=0.06),
    "membership": dict(conf_p=0.05, xfer_p=0.03, drop_p=0.1),
}


@pytest.mark.parametrize("lease", [True, False], ids=["lease", "strict"])
@pytest.mark.parametrize("regime", sorted(REGIMES))
def test_parity_over_mixed_clocks(regime, lease):
    """Kernel and oracle agree, state, outbox and info, when each node's
    step advances the clock or not at random, under every nemesis
    regime.  The clock still runs (elections happen, entries commit)."""
    cfg = small_cfg(n_groups=8, read_lease=lease)
    states, stats = run_parity(23, n_ticks=110, cfg=cfg, arrival_p=0.55,
                               **REGIMES[regime])
    assert stats["arrival_steps"] > 60
    assert all(0 < int(s.now) < 110 for s in states)


def test_no_clock_said_is_a_tick_every_step():
    """``HostInbox.empty()`` advances the clock: a caller with no loop
    steps the program it stepped before, and saying 1 is saying nothing."""
    cfg = small_cfg()
    a, b = Trio(cfg, seed=5), Trio(cfg, seed=5)
    for _ in range(25):
        a.round()
        inboxes = route_numpy(b.out, np.ones((3, 3), bool))
        for n in range(3):
            b.step(n, inboxes[n], clock=1)
    for n in range(3):
        assert a.now(n) == 25
        jax.tree.map(np.testing.assert_array_equal,
                     (a.states[n], a.out[n], a.info[n]),
                     (b.states[n], b.out[n], b.info[n]))


def test_fused_scan_counts_every_tick():
    """The fused drivers say no clock either: T ticks move ``now`` by T."""
    from rafting_tpu.core.cluster import DeviceCluster
    c = DeviceCluster(small_cfg(), seed=1)
    for _ in range(7):
        c.tick(submit_n=1)
    assert (np.asarray(c.states.now) == 7).all()


# ------------------------------------------------------------- I1: timers

@pytest.mark.parametrize("check_quorum", [False, True], ids=["", "cq"])
def test_arrival_steps_expire_no_timer(check_quorum):
    """However many clock-0 steps a node takes, alone and unheard, its
    clock stands and nothing that waits on the clock happens: no
    follower campaigns, no leader counts an RPC timeout or loses its
    CheckQuorum window.  The same silence under the timer's steps does
    all of that (the control)."""
    cfg = small_cfg(check_quorum=check_quorum)
    t = Trio(cfg, seed=2)
    lead = t.settle()
    many = 4 * cfg.election_ticks
    before = [(t.now(n), np.asarray(t.states[n].term).copy(),
               np.asarray(t.states[n].role).copy(),
               np.asarray(t.states[n].fail_streak).copy(),
               np.asarray(t.states[n].elect_deadline).copy())
              for n in range(3)]
    for _ in range(many):
        for n in range(3):
            info = t.step(n, arrival=True)
            assert valid_lanes(t.out[n])["rv_valid"] == 0
            if check_quorum:
                assert not np.asarray(info.cq_stepdown).any()
    for n in range(3):
        now, term, role, streak, dl = before[n]
        assert t.now(n) == now
        np.testing.assert_array_equal(np.asarray(t.states[n].term), term)
        np.testing.assert_array_equal(np.asarray(t.states[n].role), role)
        np.testing.assert_array_equal(
            np.asarray(t.states[n].fail_streak), streak)
        np.testing.assert_array_equal(
            np.asarray(t.states[n].elect_deadline), dl)
    # Control: the same silence, clock running.
    campaigned = timed_out = False
    for _ in range(many):
        for n in range(3):
            t.step(n)
            role = np.asarray(t.states[n].role)
            if n != lead and np.isin(role, (PRE_CANDIDATE, CANDIDATE)).any():
                campaigned = True
        if (np.asarray(t.states[lead].fail_streak) > 0).any() \
                or (np.asarray(t.states[lead].role) != LEADER).any():
            timed_out = True
    assert campaigned and timed_out


def test_clock_advances_by_the_timer_steps_alone():
    """Any mix: ``now`` moves by the number of clock-1 steps."""
    cfg = small_cfg()
    t = Trio(cfg, seed=3)
    t.settle()
    rng = np.random.default_rng(0)
    start = [t.now(n) for n in range(3)]
    timer_steps = [0, 0, 0]
    for _ in range(60):
        inboxes = route_numpy(t.out, np.ones((3, 3), bool))
        for n in range(3):
            arrival = bool(rng.random() < 0.8)
            timer_steps[n] += not arrival
            t.step(n, inboxes[n], arrival=arrival,
                   submit_n=np.full(cfg.n_groups, 1))
    assert [t.now(n) - start[n] for n in range(3)] == timer_steps
    assert t.leader() is not None


# ------------------------------------------------------ I2: read evidence

def _followers(lead):
    return [n for n in range(3) if n != lead]


def test_strict_read_is_stamped_where_it_arrives_and_waits_for_a_later_echo():
    """read_lease off.  A heartbeat leaves in the timer's step at ``now``
    N; a read is offered in a later step of the same N.  It is stamped in
    that step (strict ReadIndex orders stamps and AppendEntries by steps,
    not by ticks).  The echo of the earlier heartbeat carries N, the very
    tick of the stamp, and must not release the read: only the echo of an
    AppendEntries that left in or after the stamping step does."""
    cfg = small_cfg(read_lease=False, heartbeat_ticks=1)
    t = Trio(cfg, seed=4)
    lead = t.settle()
    fol = _followers(lead)
    G = cfg.n_groups
    offer = np.zeros(G, np.int32)
    offer[0] = 1
    # The timer's step at N: the cadence heartbeat leaves.
    t.step(lead, t.inbox_of(lead))
    N = t.now(lead)
    seq0 = int(np.asarray(t.states[lead].read_seq)[0])
    assert np.asarray(t.out[lead].ae_valid)[fol, 0].all()
    assert (np.asarray(t.out[lead].ae_tick)[fol, 0] == N).all()
    assert (np.asarray(t.out[lead].ae_seq)[fol, 0] == seq0).all()
    # The followers answer it (their own arrival steps).
    for f in fol:
        t.step(f, t.inbox_of(f, senders=[lead]), arrival=True)
        assert int(np.asarray(t.out[f].aer_tick)[lead, 0]) == N
        assert int(np.asarray(t.out[f].aer_seq)[lead, 0]) == seq0
    old_echoes = t.inbox_of(lead, senders=fol)
    # The read arrives AFTER that heartbeat left, in a step of the same N
    # that brings nothing else: stamped there, and its own barrier
    # heartbeat leaves, at the same tick and one stamp later.
    info = t.step(lead, arrival=True, read_n=offer)
    assert t.now(lead) == N
    assert int(np.asarray(info.read_acc)[0]) == 1
    assert int(np.asarray(info.read_rel)[0]) == 0
    assert bool(np.asarray(info.read_kick)[0])
    assert (np.asarray(t.out[lead].ae_tick)[fol, 0] == N).all()
    assert (np.asarray(t.out[lead].ae_seq)[fol, 0] == seq0 + 1).all()
    kicks = {f: t.inbox_of(f, senders=[lead]) for f in fol}
    # The echoes of the EARLIER heartbeat arrive: they carry the stamp's
    # own tick and release nothing, in this step or the timer's next.
    info = t.step(lead, old_echoes, arrival=True)
    assert int(np.asarray(info.read_rel)[0]) == 0
    info = t.step(lead, old_echoes)
    assert t.now(lead) == N + 1
    assert int(np.asarray(info.read_rel)[0]) == 0
    # The barrier heartbeat's echo does.
    for f in fol:
        t.step(f, kicks[f], arrival=True)
        assert int(np.asarray(t.out[f].aer_seq)[lead, 0]) == seq0 + 1
    info = t.step(lead, t.inbox_of(lead, senders=fol), arrival=True)
    assert int(np.asarray(info.read_rel)[0]) == 1
    assert int(np.asarray(info.read_served)[0]) == 1
    assert not bool(np.asarray(info.read_lease)[0])


def test_lease_read_takes_evidence_of_its_own_now_and_no_older():
    """read_lease on.  Acknowledgements stored in an earlier step of
    ``now`` N release a read stamped in a later step of N at once (what
    one tick always did with an ack that waited in the inbox beside the
    read); once the clock has advanced they release nothing."""
    cfg = small_cfg(read_lease=True, heartbeat_ticks=1)
    t = Trio(cfg, seed=4)
    lead = t.settle()
    fol = _followers(lead)
    offer = np.zeros(cfg.n_groups, np.int32)
    offer[0] = 1
    t.step(lead, t.inbox_of(lead))
    N = t.now(lead)
    for f in fol:
        t.step(f, t.inbox_of(f, senders=[lead]), arrival=True)
    # The acks arrive in one step of N ...
    t.step(lead, t.inbox_of(lead, senders=fol), arrival=True)
    assert (np.asarray(t.states[lead].read_evid)[0, fol] == N).all()
    # ... the read in a later one: stamped N, released in the same step.
    info = t.step(lead, arrival=True, read_n=offer)
    assert t.now(lead) == N
    assert int(np.asarray(info.read_acc)[0]) == 1
    assert int(np.asarray(info.read_rel)[0]) == 1
    assert bool(np.asarray(info.read_lease)[0])
    # The clock advances and nobody answers: a read stamped at N + 1 is
    # not released by the evidence of N, in this step or in later steps
    # of N + 1 that bring nothing.
    info = t.step(lead, read_n=offer)
    assert t.now(lead) == N + 1
    assert int(np.asarray(info.read_acc)[0]) == 1
    assert int(np.asarray(info.read_rel)[0]) == 0
    for _ in range(3):
        info = t.step(lead, arrival=True)
        assert int(np.asarray(info.read_rel)[0]) == 0
    # Fresh acknowledgements (the barrier heartbeat's) do release it.
    # (The leader's outbox of the stamping step was overwritten by the
    # empty steps above; the followers answer the next heartbeat.)
    t.step(lead)
    for f in fol:
        t.step(f, t.inbox_of(f, senders=[lead]), arrival=True)
    info = t.step(lead, t.inbox_of(lead, senders=fol), arrival=True)
    assert int(np.asarray(info.read_rel)[0]) == 1


# --------------------------------------------------------- I3: no echoes

@pytest.mark.parametrize("lease", [True, False], ids=["lease", "strict"])
def test_an_arrival_step_that_brings_nothing_sends_nothing(lease):
    """Leader or follower, settled or fresh from a replicated write: a
    clock-0 step with an empty inbox and empty host planes has an empty
    outbox, so steps cannot feed on each other's messages."""
    cfg = small_cfg(read_lease=lease)
    t = Trio(cfg, seed=6)
    t.settle()
    for n in range(3):
        t.step(n, arrival=True)
        assert not any(valid_lanes(t.out[n]).values()), valid_lanes(t.out[n])
    # A write goes round in arrival steps alone: leader, followers,
    # leader; then nobody has anything left to say.
    lead = t.leader()
    fol = _followers(lead)
    t.step(lead, arrival=True, submit_n=np.full(cfg.n_groups, 2))
    assert valid_lanes(t.out[lead])["ae_valid"] > 0
    for f in fol:
        t.step(f, t.inbox_of(f, senders=[lead]), arrival=True)
        assert valid_lanes(t.out[f])["aer_valid"] > 0
    commit0 = int(np.asarray(t.states[lead].commit)[0])
    t.step(lead, t.inbox_of(lead, senders=fol), arrival=True)
    assert int(np.asarray(t.states[lead].commit)[0]) == commit0 + 2
    # The acknowledgement step itself answers nobody (no cadence
    # heartbeat without the clock): the round ends here.
    assert not any(valid_lanes(t.out[lead]).values())
    for n in range(3):
        t.step(n, arrival=True)
        assert not any(valid_lanes(t.out[n]).values())
