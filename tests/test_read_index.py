"""Strict ReadIndex (``read_lease`` off, etcd raft's ReadOnlySafe) orders
stamps and AppendEntries by STEPS (core/step.py phase 6b, "ticks and
steps"): a per-lane counter moves in every step that stamps a batch, every
AppendEntries carries it, followers echo it, and a batch is released by a
majority of echoes no older than its stamp.  The invariant, at the grain of
steps:

    a strict batch is never released on the echo of an AppendEntries that
    left before the step that stamped it,

whatever is duplicated, reordered, delayed, deposed, re-elected or
restarted; and with the lease on, the step programs and the packed layouts
are the ones they were before the counter existed.
"""

import hashlib
import json
import os
import time

import jax
import numpy as np
import pytest

from rafting_tpu.api.config import RaftConfig, load_xml_config
from rafting_tpu.core import packing
from rafting_tpu.core import step as step_mod
from rafting_tpu.core.types import (
    LEADER, EngineConfig, HostInbox, Messages, conf_new_of, crash_restart,
    init_state,
)
from rafting_tpu.machine.kv_machine import KVMachineProvider
from rafting_tpu.testkit.harness import LocalCluster
from rafting_tpu.testkit.parity import route_numpy, run_parity
from rafting_tpu.transport import codec

from test_clock_plane import Trio, small_cfg


def lane(x, g=0):
    return np.asarray(x)[..., g]


def offer(cfg, n=1, g=0):
    a = np.zeros(cfg.n_groups, np.int32)
    a[g] = n
    return a


class Strict(Trio):
    """A settled strict trio, and the moves the cases below are made of.
    Messages in flight are values: ``kick`` / ``echo`` return them, and
    the case decides when, how often and in which order they land."""

    def __init__(self, seed=4, **kw):
        super().__init__(small_cfg(read_lease=False, heartbeat_ticks=1,
                                   **kw), seed=seed)
        self.lead = self.settle()
        self.fol = [n for n in range(self.N) if n != self.lead]

    @property
    def seq(self):
        return int(lane(self.states[self.lead].read_seq))

    def heartbeat(self):
        """The leader's timer step: its cadence heartbeat, per follower."""
        self.step(self.lead)
        return {f: self.inbox_of(f, senders=[self.lead]) for f in self.fol}

    def read(self, arrival=True, n=1):
        """The leader steps with a read offered and nothing delivered;
        returns (info, its outbox per follower)."""
        info = self.step(self.lead, arrival=arrival,
                         read_n=offer(self.cfg, n))
        return info, {f: self.inbox_of(f, senders=[self.lead])
                      for f in self.fol}

    def echo(self, f, ae, arrival=True):
        """Follower ``f`` processes ``ae``; its reply as the leader's
        inbox."""
        self.step(f, ae, arrival=arrival)
        return self.inbox_of(self.lead, senders=[f])

    def deliver(self, inbox, arrival=True):
        """The leader steps on ``inbox``; batches released."""
        return int(lane(self.step(self.lead, inbox,
                                  arrival=arrival).read_rel))


# ------------------------------------------------------------ I2, by steps

def test_a_read_stamped_on_arrival_ignores_an_older_echo_of_its_own_tick():
    """The issue's case.  A heartbeat leaves in an arrival step; the read is
    offered in a LATER arrival step of the same ``now``; the old echo
    arrives: no release; the kick's echo: release."""
    t = Strict()
    t.step(t.lead)                                   # the period's timer
    N = t.now(t.lead)
    # An arrival step in which a heartbeat leaves: a first read's kick.
    info, first = t.read()
    assert int(lane(info.read_acc)) == 1 and t.seq == 1
    old = {f: t.echo(f, first[f]) for f in t.fol}
    for f in t.fol:
        assert int(lane(t.out[f].aer_seq)[t.lead]) == 1
    # The first read is released by them; the second is offered later, in
    # another arrival step of the same N.
    assert t.deliver(old[t.fol[0]]) == 1
    info, kick = t.read()
    assert t.now(t.lead) == N
    assert int(lane(info.read_acc)) == 1 and t.seq == 2
    assert bool(lane(info.read_kick))
    assert (lane(t.out[t.lead].ae_seq)[t.fol] == 2).all()
    assert (lane(t.out[t.lead].ae_tick)[t.fol] == N).all()
    # The old echoes (same tick N, one stamp older): nothing, from either
    # follower, however often.
    for f in t.fol:
        assert t.deliver(old[f]) == 0
        assert t.deliver(old[f]) == 0
    # The kick's echo, from ONE follower (a majority of three with self).
    assert t.deliver(t.echo(t.fol[1], kick[t.fol[1]])) == 1
    assert not bool(lane(t.info[t.lead].read_lease))


@pytest.mark.parametrize("arrival", [True, False], ids=["arrival", "timer"])
def test_a_read_is_stamped_by_whatever_step_takes_it(arrival):
    """No loop, a timer step, an arrival step: the step that takes the
    offer stamps it, kicks, and no lease releases it in that step even
    with fresh acknowledgements beside it in the inbox."""
    t = Strict()
    hb = t.heartbeat()
    for f in t.fol:
        t.echo(f, hb[f])
    both = t.inbox_of(t.lead, senders=t.fol)
    info = t.step(t.lead, both, arrival=arrival,
                  read_n=offer(t.cfg, 3))
    assert int(lane(info.read_acc)) == 3
    assert int(lane(info.read_rel)) == 0
    assert not bool(lane(info.read_lease))
    assert bool(lane(info.read_kick))


def test_release_needs_a_majority():
    """Five voters: the leader and ONE echo are two of five; a second
    echo makes the majority."""
    t = Strict(n_peers=5, seed=9)
    _, kick = t.read()
    a, b = t.fol[:2]
    assert t.deliver(t.echo(a, kick[a])) == 0
    assert t.deliver(t.echo(a, kick[a])) == 0        # the same peer again
    assert t.deliver(t.echo(b, kick[b])) == 1


def test_release_while_joint_needs_both_majorities():
    """C_old = {0, 1, 2}, C_new = {leader, 3, 4}: the echo of an old
    member confirms C_old alone; one of a new member's, even a refusal
    (it holds no log yet), is a reply at the leader's term and makes the
    second majority."""
    cfg = small_cfg(read_lease=False, heartbeat_ticks=1, n_peers=5)
    t = Trio(cfg, seed=2)
    t.states = [init_state(cfg, i, seed=2, n_voters=3) for i in range(5)]
    for _ in range(200):
        t.round()
        lead = t.leader()
        if lead is not None and all(
                int(lane(t.states[n].commit))
                >= int(lane(t.states[lead].own_from)) > 0
                and int(lane(t.states[n].leader_id)) == lead
                for n in range(3)):
            break
    else:
        raise AssertionError("no settled leader among the three voters")
    old = [n for n in range(3) if n != lead]
    voters_new = (1 << lead) | (1 << 3) | (1 << 4)
    # The joint entry takes effect on append; nobody has acknowledged it.
    t.step(lead, arrival=True,
           conf_voters=np.full(cfg.n_groups, voters_new, np.int32))
    assert int(lane(conf_new_of(t.states[lead].conf_word))) == voters_new
    info = t.step(lead, arrival=True, read_n=offer(cfg))
    assert int(lane(info.read_acc)) == 1
    kick = {f: t.inbox_of(f, senders=[lead]) for f in old + [3, 4]}
    for f in old:
        t.step(f, kick[f], arrival=True)
    info = t.step(lead, t.inbox_of(lead, senders=old), arrival=True)
    assert int(lane(info.read_rel)) == 0
    t.step(3, kick[3], arrival=True)
    assert not bool(lane(t.out[3].aer_success)[lead])
    info = t.step(lead, t.inbox_of(lead, senders=[3]), arrival=True)
    assert int(lane(info.read_rel)) == 1


def test_duplicate_and_reordered_echoes():
    """Two batches, stamps 1 and 2.  The echo of the second kick arrives
    first and releases both (evidence is a maximum: an AE that left after
    stamp 2 left after stamp 1); the echo of the first, late and twice,
    changes nothing; and had it come first it would have released one."""
    t = Strict()
    _, k1 = t.read()
    _, k2 = t.read()
    f = t.fol[0]
    e1 = t.echo(f, k1[f])
    e2 = t.echo(f, k2[f])
    assert t.deliver(e2) == 2
    assert t.deliver(e1) == 0 and t.deliver(e1) == 0
    assert int(lane(t.states[t.lead].read_evid)[f]) == 2
    # The other order, on the other follower's echoes, for two new reads.
    _, k3 = t.read()
    _, k4 = t.read()
    g = t.fol[1]
    e3 = t.echo(g, k3[g])
    e4 = t.echo(g, k4[g])
    assert t.deliver(e3) == 1
    assert t.deliver(e3) == 0
    assert t.deliver(e4) == 1


def test_a_delayed_echo_of_an_earlier_period_never_counts():
    """An echo may be as late as it likes: periods pass, the read comes,
    and the echoes of every heartbeat that left before it, delivered then,
    release nothing."""
    t = Strict()
    late = []
    for _ in range(4):
        hb = t.heartbeat()
        late += [t.echo(f, hb[f]) for f in t.fol]
    info, kick = t.read()
    assert int(lane(info.read_acc)) == 1
    for e in late:
        assert t.deliver(e) == 0
    assert t.deliver(t.echo(t.fol[0], kick[t.fol[0]])) == 1


def _depose_and_reelect(t):
    """The leader hears a higher term (steps down, its counter and FIFO
    go), then wins the election it starts when its timer runs out.  The
    followers vote for it.  Returns the new term, or None where another
    node won the election."""
    lead = t.lead
    term0 = int(lane(t.states[lead].term))
    poison = Messages.empty(t.cfg)
    rv_valid = np.array(poison.rv_valid)
    rv_term = np.array(poison.rv_term)
    rv_valid[t.fol[0], 0] = True
    rv_term[t.fol[0], 0] = term0 + 1        # a real vote request, stale log
    t.step(lead, poison.replace(rv_valid=rv_valid, rv_term=rv_term,
                                rv_last_idx=np.zeros_like(rv_term),
                                rv_last_term=np.zeros_like(rv_term)))
    assert int(lane(t.states[lead].role)) != LEADER
    assert int(lane(t.states[lead].read_seq)) == 0
    for _ in range(200):
        t.round()
        if t.leader() == lead \
                and int(lane(t.states[lead].term)) > term0:
            break
    else:
        return None                 # another node won: no case here
    t.settle()
    return int(lane(t.states[lead].term))


def test_an_echo_from_before_a_reelection_never_counts():
    """The leader stamps (counter 3), is deposed and re-elected at a
    higher term, where the counter starts over.  The echoes of the OLD
    term's kicks, which carry 3, arrive while a read of the new term
    waits at stamp 1: dropped by the term check, whichever side's.  And a
    follower that has moved on to the new term does not echo the old
    term's request at all (its reply carries the new term and no
    counter), so even the reply that the term check would let through
    confirms nothing."""
    for seed in range(8):       # the first under which the old leader wins
        t = Strict(seed=seed)
        for _ in range(3):
            _, kick = t.read()
        assert t.seq == 3
        f = t.fol[0]
        stale_kick = kick[f]                 # an AE of the old term, seq 3
        stale_echo = t.echo(f, stale_kick)   # its echo, at the old term
        old_term = int(lane(t.states[t.lead].term))
        assert int(lane(t.out[f].aer_seq)[t.lead]) == 3
        new_term = _depose_and_reelect(t)
        if new_term is not None:
            break
    assert new_term > old_term and t.seq == 0
    info, kick = t.read()
    assert int(lane(info.read_acc)) == 1 and t.seq == 1
    # The old term's echo (term old, seq 3).
    assert t.deliver(stale_echo) == 0
    # The old term's REQUEST reaches a follower now at the new term: its
    # reply is at the new term, which the leader would accept, and echoes
    # no counter.
    reply = t.echo(f, stale_kick)
    assert int(lane(t.out[f].aer_term)[t.lead]) == new_term
    assert int(lane(t.out[f].aer_seq)[t.lead]) == 0
    assert t.deliver(reply) == 0
    assert int(lane(t.states[t.lead].rq_len)) == 1
    # The new term's own round releases.
    _, kick = t.read()
    assert t.deliver(t.echo(f, kick[f])) == 2


def test_a_deposed_leader_drops_its_pending_reads_and_counter():
    t = Strict()
    t.read()
    assert int(lane(t.states[t.lead].rq_len)) == 1 and t.seq == 1
    higher = Messages.empty(t.cfg)
    v, term = np.array(higher.aer_valid), np.array(higher.aer_term)
    v[t.fol[0], 0] = True
    term[t.fol[0], 0] = int(lane(t.states[t.lead].term)) + 1
    info = t.step(t.lead, higher.replace(aer_valid=v, aer_term=term),
                  arrival=True)
    assert bool(lane(info.read_abort))
    s = t.states[t.lead]
    assert int(lane(s.rq_len)) == 0 and int(lane(s.read_seq)) == 0
    assert not lane(s.read_evid).any()


def test_a_restarted_follower_echoes_what_it_is_sent_and_no_more():
    """A follower restarts between the heartbeat and the read: it keeps
    nothing, so its echo of the OLD heartbeat (delivered late) carries the
    old counter and releases nothing, and its echo of the kick releases."""
    t = Strict()
    hb = t.heartbeat()
    f = t.fol[0]
    t.states[f] = jax.tree.map(np.array, crash_restart(t.cfg, t.states[f]))
    info, kick = t.read()
    assert int(lane(info.read_acc)) == 1
    assert t.deliver(t.echo(f, hb[f])) == 0
    assert t.deliver(t.echo(f, kick[f])) == 1


def test_a_restarted_leader_starts_over_and_trusts_nothing_older():
    """The leader restarts with reads pending (its counter at 2): FIFO,
    evidence and counter are volatile and gone; echoes of its former life,
    delivered to the restarted node, release nothing (it leads nothing)."""
    t = Strict()
    t.read()
    _, k2 = t.read()
    echoes = [t.echo(f, k2[f]) for f in t.fol]
    t.states[t.lead] = jax.tree.map(
        np.array, crash_restart(t.cfg, t.states[t.lead]))
    s = t.states[t.lead]
    assert int(lane(s.read_seq)) == 0 and int(lane(s.rq_len)) == 0
    for e in echoes:
        assert t.deliver(e) == 0
    assert not lane(t.states[t.lead].read_evid).any()


def test_no_lease_ever_releases_a_strict_read():
    """Forty rounds of reads on every lane, heartbeats and echoes flowing:
    every batch is released in a later step than its stamp."""
    cfg = small_cfg(read_lease=False, heartbeat_ticks=1)
    t = Trio(cfg, seed=3)
    t.settle()
    released = 0
    for r in range(40):
        boxes = route_numpy(t.out, np.ones((3, 3), bool))
        for n in range(3):
            info = t.step(n, boxes[n], arrival=bool(r % 3),
                          read_n=np.full(cfg.n_groups, 2, np.int32))
            assert not np.asarray(info.read_lease).any()
            released += int(np.asarray(info.read_rel).sum())
    assert released > 40


# ------------------------------------------------- kernel against oracle

REGIMES = {
    "drops": dict(drop_p=0.2, part_p=0.12),
    "crash_stall": dict(crash_p=0.04, stall_p=0.06),
    "membership": dict(conf_p=0.05, xfer_p=0.03, drop_p=0.1),
    "calm": dict(drop_p=0.0, part_p=0.0),
}


@pytest.mark.parametrize("regime", sorted(REGIMES))
def test_strict_parity_over_random_timer_and_arrival_mixes(regime):
    """Kernel and scalar oracle agree on state (the counter too), outbox
    (``ae_seq`` / ``aer_seq`` too) and info over random mixes of timer and
    arrival steps, and reads stamped in arrival steps are released."""
    cfg = small_cfg(n_groups=8, read_lease=False, heartbeat_ticks=1)
    states, stats = run_parity(31, n_ticks=120, cfg=cfg, arrival_p=0.6,
                               **REGIMES[regime])
    assert stats["arrival_steps"] > 60
    # (A lane whose only voter is its leader confirms itself: membership
    # chaos makes such lanes, and their reads are released where stamped.)
    assert stats["lease_reads"] == 0 or regime == "membership"
    assert stats["stamps_on_arrival"] > 0
    assert stats["reads_released"] > 0
    assert all(s.read_seq is not None for s in states)


def test_strict_parity_with_hibernation():
    cfg = small_cfg(n_groups=4, read_lease=False, heartbeat_ticks=2,
                    election_ticks=10, hibernate=True)
    _, stats = run_parity(7, n_ticks=160, cfg=cfg, arrival_p=0.4,
                          drop_p=0.05, part_p=0.02, calm=((30, 80),),
                          wake_p=0.01)
    assert stats["asleep_steps"] > 0 and stats["lease_reads"] == 0
    assert stats["reads_released"] > 0


# ----------------------------------------------------- the served path

def _kv(op, k, v=None) -> bytes:
    cmd = {"op": op, "k": k}
    if v is not None:
        cmd["v"] = v
    return json.dumps(cmd).encode()


def test_a_strict_write_and_read_are_served_while_the_timer_stands(tmp_path):
    """Real loops at a period of seconds, ``read_lease`` off: a write and
    then reads are answered while the leader's timer count stands still,
    every read by a round of its own (``read_rounds``), none by a lease,
    all stamped in arrival steps; the wall-clock veto is never raised."""
    root = str(tmp_path)
    cfg = EngineConfig(n_groups=3, n_peers=3, log_slots=32, batch=4,
                       max_submit=4, election_ticks=10, heartbeat_ticks=1,
                       rpc_timeout_ticks=8, pre_vote=True, read_lease=False)
    lc = LocalCluster(cfg, root, seed=11,
                      provider_factory=lambda i: KVMachineProvider(
                          os.path.join(root, f"kv{i}")))
    try:
        for g in range(cfg.n_groups):
            lc.wait_leader(g)
        lc.tick_until(lambda: all(lc.nodes[lc.leader_of(g)].is_ready(g)
                                  for g in range(cfg.n_groups)),
                      what="ready leaders")
        lc.tick(3)
        lead = lc.nodes[lc.leader_of(1)]
        # With no loop: the step that takes a strict read stamps it.
        fut = lead.read(1, _kv("get", "none"))
        lc.tick(4)
        assert fut.result(0) is None
        lc.start_loops(3.0)
        m = lead.metrics
        for attempt in range(4):
            t0, end = lead.timer_ticks, time.monotonic() + 60
            while lead.timer_ticks == t0:
                assert time.monotonic() < end
                time.sleep(0.001)
            timer0 = lead.timer_ticks
            base = {k: int(m[k]) for k in (
                "read_rounds", "read_stamps_on_arrival", "read_lease_hits",
                "read_kicks", "read_vetoes")}
            lead.submit(1, _kv("set", "k", attempt)).result(60)
            got = [lead.read(1, _kv("get", "k")).result(60)
                   for _ in range(3)]
            if lead.timer_ticks == timer0:
                break
        else:
            raise AssertionError("never inside one period in 4 attempts")
        lc.stop_loops()
        assert got == [attempt] * 3
        moved = {k: int(m[k]) - v for k, v in base.items()}
        assert moved == {"read_rounds": 3, "read_stamps_on_arrival": 3,
                         "read_lease_hits": 0, "read_kicks": 3,
                         "read_vetoes": 0}
    finally:
        lc.close()


# --------------------------------------------------------- configuration

def test_raft_config_read_lease_reaches_the_engine():
    base = dict(local="raft://127.0.0.1:6001",
                peers=("raft://127.0.0.1:6002", "raft://127.0.0.1:6003"))
    assert RaftConfig(**base).read_lease is True
    assert RaftConfig(**base).engine_config().read_lease is True
    cfg = RaftConfig(read_lease=False, **base).engine_config()
    assert cfg.read_lease is False and cfg.lease_carry_ticks == 0
    state = init_state(cfg, 0)
    assert state.read_seq is not None and state.lease is None
    assert init_state(RaftConfig(**base).engine_config(), 0).read_seq is None


@pytest.mark.parametrize("text,want", [
    ('read-lease="false"', False), ('read-lease="true"', True), ("", True)])
def test_read_lease_from_xml(tmp_path, text, want):
    path = tmp_path / "raft.xml"
    path.write_text(f"""<raft>
      <cluster><local>raft://127.0.0.1:6001</local>
        <remote>raft://127.0.0.1:6002</remote>
        <remote>raft://127.0.0.1:6003</remote></cluster>
      <timing tick="200" heartbeat="1" election="10" {text}/>
    </raft>""")
    cfg = load_xml_config(str(path))
    assert cfg.read_lease is want
    assert cfg.engine_config().read_lease is want


def test_the_wire_carries_the_word_only_where_strict():
    lease = Messages.empty(small_cfg())
    strict = Messages.empty(small_cfg(read_lease=False))
    assert lease.ae_seq is None and lease.aer_seq is None
    assert strict.ae_seq.shape == strict.ae_tick.shape
    have = lambda m: {f for f in m.__dataclass_fields__
                      if getattr(m, f) is not None}
    assert codec.kind_fields("ae", have(lease)) == codec.KIND_FIELDS["ae"]
    assert codec.kind_fields("ae", have(strict))[1][-1] == "ae_seq"
    assert codec.kind_fields("aer", have(strict))[1][-1] == "aer_seq"
    # A strict node and a lease node are refused at the handshake.
    assert codec.schema_tag(have(lease)) == codec.SCHEMA_TAG
    assert codec.schema_tag(have(strict)) != codec.SCHEMA_TAG


# ------------------------------------- with the lease on, nothing moved

def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _layout_text(lay) -> str:
    if lay is None or isinstance(lay, (int, bool)):
        return repr(lay)
    if isinstance(lay, tuple):
        return "(" + ",".join(_layout_text(x) for x in lay) + ")"
    return type(lay).__name__ + ":" + ";".join(
        f"{k}={getattr(lay, k)!r}" for k in lay.__slots__
        if not k.startswith("_") and k != "treedef") \
        + f";leaves={lay.treedef.num_leaves}"


def program_digests(cfg, small):
    """Digests of the three step programs' jaxprs and of the packed
    layouts (every slot's place, shape and kind; not the treedef's text,
    which names the None fields too) for ``cfg`` at 16 lanes, the column
    step at the sizes of ``small()``."""
    S = step_mod
    state = init_state(cfg, 0)
    inbox, host = Messages.empty(cfg), HostInbox.empty(cfg)
    out = {"node_step": _sha(str(jax.make_jaxpr(
        lambda s, i, h: S.node_step(cfg, s, i, h))(state, inbox, host)))}
    inputs, back = S.step_layouts(cfg, True)
    out["step_layouts"] = _sha(_layout_text((inputs, back)))
    bufs = tuple(inputs.alloc())
    out["node_step_packed"] = _sha(str(jax.make_jaxpr(
        lambda s, b: S.node_step_packed(cfg, inputs, s, b))(state, bufs)))
    small()
    lay = S.column_layouts(cfg, True)
    out["column_layouts"] = _sha(_layout_text(tuple(lay)))
    up, _ = packing.alloc_regions(lay.rows_in, lay.columns)
    bufs = tuple(lay.host.alloc()) + (up,)
    out["node_step_columns"] = _sha(str(jax.make_jaxpr(
        lambda s, c, b: S.node_step_columns(cfg, lay, True, s, c, b))(
        state, S.first_carry(lay), bufs)))
    return out


# Taken on the parent commit (4c3d862, PR 44) by this very function.
PARENT = {
    "default": (dict(), {
        "node_step": "8a741b9b144b4224",
        "step_layouts": "0b407c683610862b",
        "node_step_packed": "f73f8a622309b6b3",
        "column_layouts": "47b84a56b60e5fa3",
        "node_step_columns": "3bc272f53d8e5883"}),
    "coord": (dict(election_ticks=10, heartbeat_ticks=1), {
        "node_step": "e202505c4335afe2",
        "step_layouts": "0b407c683610862b",
        "node_step_packed": "400154135e1c3258",
        "column_layouts": "47b84a56b60e5fa3",
        "node_step_columns": "24fd73e2743a8851"}),
    "hibernate": (dict(election_ticks=10, heartbeat_ticks=2,
                       hibernate=True), {
        "node_step": "ff8a542162feb889",
        "step_layouts": "d51e00f22b48f2ec",
        "node_step_packed": "5a86ff9c463dc095",
        "column_layouts": "c0b6b4af60dca12c",
        "node_step_columns": "6256dc26e9dc6e2d"}),
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_with_the_lease_on_the_programs_and_layouts_are_the_parents(
        name, small):
    kw, want = PARENT[name]
    cfg = EngineConfig(n_groups=16, n_peers=3, **kw)
    assert cfg.read_lease
    assert program_digests(cfg, small) == want


def test_strict_adds_one_lane_and_one_word_each_way(small):
    """What strict mode costs: one [G] state lane, one [P, G] word on an
    AppendEntries and one on its reply, in every layout."""
    lease = EngineConfig(n_groups=16, n_peers=3)
    strict = EngineConfig(n_groups=16, n_peers=3, read_lease=False)
    n = lambda tree: len(jax.tree.leaves(tree))
    assert n(init_state(strict, 0)) == n(init_state(lease, 0)) + 1 \
        - n(init_state(lease, 0).lease)
    assert n(Messages.empty(strict)) == n(Messages.empty(lease)) + 2
    words = lambda cfg: sum(step_mod.step_layouts(cfg, True)[0].words)
    assert words(strict) - words(lease) == 2 * 3 * 16
    small()
    cols = lambda cfg: step_mod.column_layouts(cfg, True).columns
    assert cols(strict).W == cols(lease).W + 2
    assert cols(strict).F == cols(lease).F
