"""The carried lease (core/step.py phase 6b): with a heartbeat every h > 1
ticks a same-term acknowledgement quorum releases reads stamped in its own
tick and in the h - 1 after it, and in no later one, and each way in which
that lease could outlive the followers' promise is closed.

Three nodes are stepped by hand, a ROUND at a time: what a round sends the
next one delivers, and a node's clock advances only in the rounds the
caller says (``HostInbox.clock``), as under a started loop, whose arrival
steps carry a whole election inside one period.  Every read is held to the
invariant of tests/test_read_plane.py: a released batch's ReadIndex covers
every index committed anywhere before the batch was stamped.

Each counter-example runs twice: as the engine is (no stale read), and with
its closure taken out by hand (the history then serves one), so that the
test fails if the closure goes."""

import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rafting_tpu.core.cluster import (
    DeviceCluster, auto_host_inbox, cluster_step_nemesis,
)
from rafting_tpu.core.types import (
    LEADER, NIL, EngineConfig, FaultSchedule,
)
from rafting_tpu.testkit import nemesis

N, G = 3, 1
BASE = dict(n_groups=G, n_peers=N, log_slots=32, batch=4, max_submit=4,
            election_ticks=10, heartbeat_ticks=2, rpc_timeout_ticks=5)
CFG = EngineConfig(**BASE)


class CarryAnyway(EngineConfig):
    """A configuration that carries its lease whether or not it may: what
    case (d) would be without ``EngineConfig.lease_carry_ticks``'s
    conditions."""

    @property
    def lease_carry_ticks(self) -> int:
        return self.heartbeat_ticks - 1


@functools.lru_cache(maxsize=None)
def _stepper(cfg):
    return jax.jit(partial(cluster_step_nemesis, cfg))


class Rounds:
    def __init__(self, cfg, seed=0):
        self.cfg = cfg
        c = DeviceCluster(cfg, seed=seed)
        self.states, self.inflight, self.info = \
            c.states, c.inflight, c.last_info
        self.link = np.ones((N, N), bool)
        self.acked = 0              # highest index committed anywhere
        self.fifo = [[] for _ in range(N)]
        self.stale = []             # (round, node, read index, frontier)
        self.served = 0
        self.n = 0                  # rounds so far

    # ---------------------------------------------------------------- views
    def lane(self, name, node=None):
        a = np.asarray(getattr(self.states, name))
        return a[:, 0] if node is None else a[node, 0]

    @property
    def now(self):
        return np.asarray(self.states.now)

    def leader(self):
        lead = np.nonzero(self.lane("role") == LEADER)[0]
        terms = self.lane("term")
        return int(max(lead, key=lambda i: terms[i])) if len(lead) else None

    def cut(self, a, b=None):
        """Both directions between a and b (or a and everyone)."""
        for o in range(N) if b is None else (b,):
            if o != a:
                self.link[a, o] = self.link[o, a] = False

    def mend(self, a, b):
        self.link[a, b] = self.link[b, a] = True

    # ---------------------------------------------------------------- steps
    def round(self, clock=1, reads=None, writes=None, veto=(), stall=(),
              crash=(), xfer=None):
        """One round.  ``clock``: 1/0 for all, or per node.  ``reads`` /
        ``writes``: {node: n} offered on the lane.  Returns the round's
        StepInfo (numpy, [N, G] leaves)."""
        cfg = self.cfg
        per = lambda d: jnp.asarray(
            [[(d or {}).get(i, 0)] * G for i in range(N)], jnp.int32)
        host = auto_host_inbox(cfg, self.states, per(writes), False,
                               self.info, per(reads))
        clock = np.broadcast_to(np.asarray(clock, np.int32), (N,))
        host = host.replace(
            clock=jnp.asarray(clock),
            read_veto=jnp.asarray([i in veto for i in range(N)]))
        if xfer is not None:
            node, target = xfer
            host = host.replace(xfer_target=jnp.asarray(
                [[target if i == node else NIL] * G for i in range(N)],
                jnp.int32))
        mask = lambda which: jnp.asarray([i in which for i in range(N)])
        fault = FaultSchedule(link_up=jnp.asarray(self.link),
                              crash=mask(crash), stall=mask(stall),
                              dup=jnp.zeros((N, N), jnp.bool_))
        self.states, self.inflight, self.info = _stepper(cfg)(
            self.states, self.inflight, host, self.info, fault)
        info = jax.tree.map(np.asarray, self.info)
        for n in range(N):
            if n in stall:
                continue            # a frozen StepInfo is no event
            q = self.fifo[n]
            if n in crash or info.read_abort[n, 0]:
                q.clear()
            if info.read_acc[n, 0] > 0:
                q.append((int(info.read_index[n, 0]), self.acked))
            for _ in range(int(info.read_rel[n, 0])):
                ridx, frontier = q.pop(0)
                if ridx < frontier:
                    self.stale.append((self.n, n, ridx, frontier))
            self.served += int(info.read_served[n, 0])
        self.acked = max(self.acked, int(self.lane("commit").max()))
        self.n += 1
        return info

    def tick(self, rounds=1, **first):
        """A period: the timer's round, then ``rounds - 1`` arrival rounds
        in which no clock moves."""
        info = self.round(clock=1, **first)
        for _ in range(rounds - 1):
            self.round(clock=0)
        return info

    def settle(self):
        """A leader whose no-op has committed, evidence flowing."""
        for _ in range(80):
            self.tick()
            lead = self.leader()
            if lead is not None and \
                    (self.lane("read_evid", lead) > 0).sum() == N - 1 and \
                    self.lane("commit", lead) >= self.lane("own_from", lead):
                return lead
        raise AssertionError("no settled leader")

    def evidence_tick(self, lead):
        """Lock-step ticks until one in which the leader took evidence
        from both followers; returns its number."""
        for _ in range(8):
            self.tick()
            now = int(self.now[lead])
            if (np.delete(self.lane("read_evid", lead), lead) == now).all():
                return now
        raise AssertionError("no heartbeat round")


def test_the_inequality_decides_whether_the_lease_is_carried():
    """6b case (d), the arithmetic: TiKV's timing carries one tick with
    room, RaftConfig's shipped election timeout does not, nor does a
    configuration without pre-votes or without the lease."""
    assert (CFG.lease_carry_ticks, CFG.lease_ticks,
            CFG.lease_hold_ticks) == (1, 5, 8)
    assert CFG.lease_hold_ticks < CFG.election_ticks
    cfg = lambda **kw: EngineConfig(**{**BASE, **kw})
    assert cfg(election_ticks=8).lease_carry_ticks == 1      # 2+3+3 == 8
    assert cfg(election_ticks=7).lease_carry_ticks == 0
    assert cfg(election_ticks=3).lease_carry_ticks == 0      # as shipped
    assert cfg(heartbeat_ticks=1).lease_carry_ticks == 0     # today's rule
    assert cfg(heartbeat_ticks=3).lease_carry_ticks == 2
    assert cfg(heartbeat_ticks=5).lease_carry_ticks == 0     # 5+3+3 > 10
    assert cfg(pre_vote=False).lease_carry_ticks == 0
    assert cfg(read_lease=False).lease_carry_ticks == 0
    for c in (cfg(election_ticks=7), cfg(pre_vote=False)):
        assert c.lease_hold_ticks == 0
    from rafting_tpu.core.types import init_state
    assert init_state(cfg(election_ticks=7), 0).lease is None
    assert init_state(CFG, 0).lease is not None
    from rafting_tpu.api.config import RaftConfig
    tikv = RaftConfig(local="raft://h:1", peers=("raft://h:2", "raft://h:3"),
                      tick_ms=1000, heartbeat_mul=2.0, election_mul=10.0)
    assert tikv.engine_config().lease_carry_ticks == 1
    shipped = RaftConfig(local="raft://h:1",
                         peers=("raft://h:2", "raft://h:3"),
                         heartbeat_mul=2.0)
    assert shipped.engine_config().lease_carry_ticks == 0


def test_a_read_in_the_tick_after_the_evidence_needs_no_round():
    """Evidence of tick r releases a batch stamped at r + 1 in the step
    that stamps it, with no heartbeat sent; one stamped at r + 2 is left
    pending and asks for a barrier heartbeat, and so does one at r + 3,
    when no heartbeat is due."""
    r = Rounds(CFG)
    lead = r.settle()
    at = r.evidence_tick(lead)
    r.cut(lead)                       # no acknowledgement comes back now
    info = r.tick(reads={lead: 2})    # r + 1
    assert int(r.now[lead]) == at + 1
    assert info.read_lease[lead, 0] and info.read_carried[lead, 0]
    assert info.read_served[lead, 0] == 2 and not info.read_kick[lead, 0]
    assert not np.asarray(r.inflight.ae_valid)[lead].any()
    info = r.tick(reads={lead: 1})    # r + 2: out of reach
    assert info.read_acc[lead, 0] == 1 and info.read_rel[lead, 0] == 0
    assert info.read_kick[lead, 0] and not info.read_lease[lead, 0]
    assert int(r.lane("hb_due", lead)) == at + 4
    info = r.tick(reads={lead: 1})    # r + 3: no heartbeat due, one leaves
    assert info.read_kick[lead, 0] and info.read_rel[lead, 0] == 0
    assert np.asarray(r.inflight.ae_valid)[lead].any()
    assert not r.stale and r.served == 2


def test_evidence_of_its_own_tick_is_not_counted_as_carried():
    r = Rounds(CFG)
    lead = r.settle()
    r.evidence_tick(lead)
    r.tick()
    info = r.tick(reads={lead: 1})    # the next round's receipts
    assert info.read_lease[lead, 0] and not info.read_carried[lead, 0]


def _to_the_eve_of_an_expiry(r, lead, waiter, rounds):
    """Lock step until the tick X at which ``waiter``'s election timer
    runs out follows a tick in which the leader sends heartbeats (so that
    under ``rounds`` rounds a tick it takes evidence at X - 1 and sends
    nothing at X), then ``rounds``-round ticks up to X - 1.  Returns X."""
    for _ in range(400):
        x = int(r.lane("elect_deadline", waiter))
        due = int(r.lane("hb_due", lead))
        now = int(r.now[lead])
        if x - now >= 4 and (x - 1 - due) % 2 == 0:
            break
        r.tick()
    else:
        raise AssertionError("no expiry on the off tick")
    while int(r.now[lead]) < x - 1:
        r.tick(rounds)
    assert (np.delete(r.lane("read_evid", lead), [lead, waiter])
            == x - 1).all(), "the leader holds no evidence of X - 1"
    return x


def _restarted_follower(hold: bool):
    """6b case (b).  C, cut off since long, asks for pre-votes the moment F,
    which acknowledged L's heartbeat a tick ago, comes back from a crash."""
    r = Rounds(CFG)
    lead = r.settle()
    f, c = [i for i in range(N) if i != lead]
    r.cut(c)
    x = _to_the_eve_of_an_expiry(r, lead, c, 12)
    r.mend(c, f)
    r.round(clock=1, crash=(f,))                  # tick X: F is back
    held = np.asarray(r.states.lease.vote_hold)[f, 0]
    assert held == x - 1 + CFG.lease_hold_ticks   # it recovered a term
    if not hold:
        # core/types.py crash_restart without its vote_hold line
        r.states = r.states.replace(lease=r.states.lease.replace(
            vote_hold=jnp.zeros_like(r.states.lease.vote_hold)))
    for k in range(1, 12):
        r.round(clock=0, writes={c: 1} if k >= 5 else None,
                reads={lead: 1} if k >= 9 else None)
    assert int(r.now[lead]) == x
    return r, lead, c


def test_a_restarted_follower_holds_its_pre_vote_for_the_lease():
    r, lead, c = _restarted_follower(hold=True)
    assert r.lane("role", c) != LEADER and r.lane("role", lead) == LEADER
    assert not r.stale and r.served > 0
    r, lead, c = _restarted_follower(hold=False)
    assert r.lane("role", c) == LEADER
    assert r.stale, "without the hold the carried lease serves a stale read"


def _transfer(closed: bool):
    """6b case (c).  L hands the lane to a caught-up target and is cut off
    the moment TimeoutNow is on its way; the target is elected and commits
    inside the tick in which L still holds last tick's evidence."""
    r = Rounds(CFG)
    lead = r.settle()
    tgt, f = [i for i in range(N) if i != lead]
    r.evidence_tick(lead)
    while int(r.lane("hb_due", lead)) != int(r.now[lead]) + 1:
        r.tick()                                  # next: a heartbeat tick
    r.tick(12)                                    # evidence of X - 1
    x = int(r.now[lead]) + 1
    evid = r.states.read_evid
    assert (np.delete(np.asarray(evid)[lead, 0], lead) == x - 1).all()
    info = r.round(clock=1, xfer=(lead, tgt))     # tick X: it fires
    assert info.xfer_fired[lead, 0]
    assert (np.asarray(r.states.read_evid)[lead] == 0).all()
    assert np.asarray(r.states.lease.carry_bar)[lead, 0] \
        == x + 2 * CFG.election_ticks

    def reopen():
        # core/step.py phase 9 without its `if carry:` block (TimeoutNow
        # goes out again in every step that still finds the target
        # caught up, so the block runs in each)
        if not closed:
            r.states = r.states.replace(
                read_evid=evid, lease=r.states.lease.replace(
                    carry_bar=jnp.zeros_like(r.states.lease.carry_bar)))
    r.cut(lead)
    r.link[lead, tgt] = True                      # TimeoutNow arrives
    r.round(clock=0)
    r.cut(lead)
    for k in range(2, 12):
        reopen()
        r.round(clock=0, writes={tgt: 1} if k >= 4 else None,
                reads={lead: 1} if k >= 8 else None)
    assert int(r.now[lead]) == x and r.lane("role", tgt) == LEADER
    return r


def test_a_transfer_ends_the_carried_lease_when_it_fires():
    r = _transfer(closed=True)
    assert not r.stale
    r = _transfer(closed=False)
    assert r.stale, "stored evidence outlived a TimeoutNow"


def _paused_leader(veto: bool):
    """6b case (a).  L takes evidence, then its loop stands still for two
    election timeouts while the others elect and commit; it wakes, still
    cut off, one tick of ITS clock later."""
    r = Rounds(CFG)
    lead = r.settle()
    at = r.evidence_tick(lead)
    others = [i for i in range(N) if i != lead]
    for _ in range(3 * CFG.election_ticks):
        r.round(clock=1, stall=(lead,))
    new = r.leader()
    assert new in others
    for _ in range(6):
        r.round(clock=1, stall=(lead,), writes={new: 1})
    r.cut(lead)
    r.round(clock=1, reads={lead: 1}, veto=(lead,) if veto else ())
    assert int(r.now[lead]) == at + 1             # its clock stood still
    return r


def test_a_leader_that_stood_still_is_vetoed_when_it_wakes():
    r = _paused_leader(veto=True)
    assert not r.stale
    # runtime/node.py _hold_read_veto (and note_pause) without the veto
    r = _paused_leader(veto=False)
    assert r.stale, "a lagging clock carried the lease past the promise"


def _no_pre_vote(cfg):
    """6b case (d).  Without pre-votes a follower whose timer runs out
    takes the next term and asks for real votes, which nobody's lease
    refuses: it is elected and commits inside the leader's tick."""
    r = Rounds(cfg)
    lead = r.settle()
    f, c = [i for i in range(N) if i != lead]
    r.cut(c)
    x = _to_the_eve_of_an_expiry(r, lead, c, 12)
    r.mend(c, f)
    r.round(clock=1)
    for k in range(1, 12):
        r.round(clock=0, writes={c: 1} if k >= 3 else None,
                reads={lead: 1} if k >= 7 else None)
    assert int(r.now[lead]) == x and r.lane("role", c) == LEADER
    return r


def test_without_pre_votes_evidence_releases_its_own_tick_only():
    kw = dict(BASE, pre_vote=False, log_slots=16)
    r = _no_pre_vote(EngineConfig(**kw))
    assert not r.stale
    # core/types.py EngineConfig.lease_carry_ticks without its conditions
    r = _no_pre_vote(CarryAnyway(**kw))
    assert r.stale, "a lease carried with no promise behind it"


# ------------------------------------------------------------------ nemesis

_SCENARIOS = {
    "partition": lambda T: nemesis.concat(
        nemesis.split_brain(N, 2 * T // 3, start=5, stop=2 * T // 3 - 10,
                            seed=3),
        nemesis.healthy(N, T - 2 * T // 3)),
    "crash_restart": lambda T: nemesis.concat(
        nemesis.crash_storm(N, 2 * T // 3, rate=0.05, seed=4),
        nemesis.healthy(N, T - 2 * T // 3)),
    "clock_stall": lambda T: nemesis.concat(
        nemesis.clock_stalls(N, 2 * T // 3, rate=0.06, max_len=14, seed=5),
        nemesis.healthy(N, T - 2 * T // 3)),
    "lossy_dup": lambda T: nemesis.concat(
        nemesis.lossy_links(N, 2 * T // 3, drop_p=0.15, dup_p=0.3, seed=6),
        nemesis.healthy(N, T - 2 * T // 3)),
}


@pytest.mark.parametrize("scenario", sorted(_SCENARIOS))
def test_reads_stay_linearizable_under_nemesis_on_a_carried_lease(scenario):
    """tests/test_read_plane.py's adversary at a heartbeat of 2 and an
    election timeout of 10: reads and writes on every node every tick, a
    transfer asked for every 25 ticks, a woken node vetoed by its host."""
    sched = _SCENARIOS[scenario](120)
    crash, stall = np.asarray(sched.crash), np.asarray(sched.stall)
    r = Rounds(CFG, seed=1)
    carried = 0
    for t in range(sched.n_ticks):
        r.link = np.asarray(sched.link_up[t]).copy()
        down = tuple(np.nonzero(stall[t])[0].tolist())
        woke = tuple(n for n in range(N)
                     if t and stall[t - 1, n] and not stall[t, n])
        lead = r.leader()
        xfer = (lead, (lead + 1) % N) if t % 25 == 24 and lead is not None \
            else None
        info = r.round(clock=1, stall=down, veto=woke, xfer=xfer,
                       crash=tuple(np.nonzero(crash[t])[0].tolist()),
                       reads={n: 2 for n in range(N)},
                       writes={n: 1 for n in range(N)})
        carried += int(sum(info.read_carried[n, 0] for n in range(N)
                           if n not in down))
    assert not r.stale, r.stale[:3]
    assert r.served > 0 and carried > 0
