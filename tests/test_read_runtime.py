"""Read plane, host runtime tier: RaftNode.read end to end over a live
LocalCluster (real WAL, state machines, codec round-trips), the
follower->leader read forward, the stub's bounded NotLeader redirect cap,
the read-veto pause guard, and Prometheus metrics exposition.
"""

import json
import os
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from rafting_tpu.api.anomaly import (
    BatchAbortedError, BusyLoopError, NotLeaderError, is_refusal,
)
from rafting_tpu.api.serial import JsonSerializer
from rafting_tpu.api.stub import RaftStub
from rafting_tpu.core.types import EngineConfig
from rafting_tpu.machine.kv_machine import KVMachineProvider
from rafting_tpu.testkit.harness import LocalCluster
from rafting_tpu.utils.metrics import Metrics


def _cfg(**kw) -> EngineConfig:
    base = dict(n_groups=2, n_peers=3, log_slots=32, batch=4, max_submit=4,
                election_ticks=6, heartbeat_ticks=2, rpc_timeout_ticks=5,
                pre_vote=True)
    base.update(kw)
    return EngineConfig(**base)


@pytest.fixture
def kv_cluster(tmp_path):
    root = str(tmp_path)
    lc = LocalCluster(
        _cfg(), root,
        provider_factory=lambda i: KVMachineProvider(
            os.path.join(root, f"kv{i}")))
    try:
        yield lc
    finally:
        lc.close()


def _kv(op, k, v=None) -> bytes:
    cmd = {"op": op, "k": k}
    if v is not None:
        cmd["v"] = v
    return json.dumps(cmd).encode()


def _ready_leader(lc, group=0):
    leader = lc.wait_leader(group)
    node = lc.nodes[leader]
    lc.tick_until(lambda: node.is_ready(group), what="leader ready")
    return leader, node


# --------------------------------------------------------------- end to end --

def test_read_after_write_linearizable(kv_cluster):
    lc = kv_cluster
    _, node = _ready_leader(lc)
    wf = node.submit(0, _kv("set", "a", 42))
    lc.tick_until(wf.done, what="write applied")
    assert wf.result() == 42
    rf = node.read(0, _kv("get", "a"))
    lc.tick_until(rf.done, what="read served")
    assert rf.result() == 42
    # A batch shares one barrier and resolves in order.
    bf = node.read_batch(0, [_kv("get", "a"), _kv("get", "missing")])
    lc.tick_until(bf.done, what="read batch served")
    assert bf.result() == [42, None]
    assert node.metrics["reads_served"] >= 3
    # Reads never grew the log: durable tail is untouched by the reads.
    tail_after = node.store.tail(0)
    rf2 = node.read(0, _kv("get", "a"))
    lc.tick_until(rf2.done, what="second read")
    assert node.store.tail(0) == tail_after


def test_follower_read_refused_with_hint(kv_cluster):
    lc = kv_cluster
    leader, _ = _ready_leader(lc)
    follower = lc.nodes[(leader + 1) % 3]
    fut = follower.read(0, _kv("get", "a"))
    assert fut.done()
    exc = fut.exception()
    assert isinstance(exc, NotLeaderError)
    assert exc.leader == leader
    # Reads never enter the log -> ALWAYS a marked retry-safe refusal.
    assert is_refusal(exc)


def test_forward_read_follower_to_leader(kv_cluster):
    """The FWD_READ channel: a follower relays the read to the leader and
    returns the query result — reads work from any node."""
    lc = kv_cluster
    leader, node = _ready_leader(lc)
    wf = node.submit(0, _kv("set", "k", "v1"))
    lc.tick_until(wf.done, what="write applied")
    follower = lc.nodes[(leader + 1) % 3]
    box = {}

    def relay():
        box["res"] = follower.transport.forward_read(
            leader, 0, _kv("get", "k"), timeout=10.0)

    th = threading.Thread(target=relay, daemon=True)
    th.start()
    lc.tick_until(lambda: "res" in box, what="forwarded read",
                  max_rounds=2000)
    th.join(timeout=5)
    ok, raw = box["res"]
    assert ok, raw
    assert json.loads(raw) == "v1"


def test_read_survives_veto_pause(kv_cluster):
    """A detected wall-clock pause (HostInbox.read_veto) drops lease
    evidence — the pending read is NOT served on stale evidence, but the
    barrier re-earns fresh acks and the read still completes."""
    lc = kv_cluster
    _, node = _ready_leader(lc)
    wf = node.submit(0, _kv("set", "p", 7))
    lc.tick_until(wf.done, what="write applied")
    # Simulate a long process pause right before the next tick.
    node._tick_interval = 0.02
    node._last_tick_wall = time.monotonic() - 10.0
    rf = node.read(0, _kv("get", "p"))
    node.tick()
    # The veto is HELD for read_fresh_ticks consecutive ticks, not one:
    # pause-era acks can drain from socket buffers over several ticks,
    # and a single-tick veto would let lease evidence resurrect from
    # them (the tick clock did not advance during the wall pause).
    assert node.metrics["read_vetoes"] >= 1
    assert node._read_veto_hold == max(node.cfg.read_fresh_ticks, 2) - 1
    lc.tick_until(rf.done, what="read after pause")
    assert rf.result() == 7
    node._tick_interval = None


def test_read_veto_lasts_periods_not_steps(kv_cluster):
    """The veto after a pause is a length of time: it is counted down by
    the timer's ticks (``read_fresh_ticks`` of them), however many steps
    arriving work starts in between, and a read waits it out."""
    lc = kv_cluster
    _, node = _ready_leader(lc)
    wf = node.submit(0, _kv("set", "p", 7))
    lc.tick_until(wf.done, what="write applied")
    hold = max(node.cfg.read_fresh_ticks, 2)
    node._tick_interval = 0.02
    node._last_tick_wall = time.monotonic() - 10.0
    rf = node.read(0, _kv("get", "p"))
    node.tick(arrival=True)         # the step that notices the pause
    assert node._read_veto_hold == hold
    timer0, steps0 = node.timer_ticks, node.ticks
    for _ in range(3 * hold):       # steps a few milliseconds apart
        node._last_tick_wall = time.monotonic()
        for n in lc.nodes.values():
            n.tick(arrival=True)
        assert node._read_veto_hold == hold
    assert not rf.done(), "served on evidence the veto should have dropped"
    assert node.timer_ticks == timer0 and node.ticks == steps0 + 3 * hold
    assert node.metrics["ticks_on_arrival"] >= 3 * hold + 1
    for k in range(1, hold + 1):    # the timer's ticks count it down
        node._last_tick_wall = time.monotonic()
        lc.tick(1)
        assert node._read_veto_hold == hold - k
    lc.tick_until(rf.done, what="read after the veto")
    assert rf.result() == 7
    node._tick_interval = None


def test_host_cadences_count_the_timer(kv_cluster):
    """``ticks`` names a step, ``timer_ticks`` counts periods: arrival
    steps move the first and leave alone the second and everything that
    keeps time by it (the engine's clock, the transaction sweep, the
    health plane), while a caller that says nothing moves both together."""
    lc = kv_cluster
    _, node = _ready_leader(lc)
    assert node.ticks == node.timer_ticks > 0
    now0 = int(node.state.now)
    timer0, txn0 = node.timer_ticks, node.txn._tick_n
    health0 = node.health.tick if node.health is not None else None
    for _ in range(7):
        for n in lc.nodes.values():
            n.tick(arrival=True)
    assert node.timer_ticks == timer0 and int(node.state.now) == now0
    assert node.ticks == timer0 + 7
    assert node.txn._tick_n == txn0
    if health0 is not None:
        assert node.health.tick == health0
    lc.tick(2)
    assert node.timer_ticks == timer0 + 2 and int(node.state.now) == now0 + 2
    assert node.txn._tick_n == txn0 + 2


# ------------------------------------------- one barrier for waiting reads --

def _write(lc, node, k, v):
    wf = node.submit(0, _kv("set", k, v))
    lc.tick_until(wf.done, what="write applied")


@pytest.mark.parametrize("n", [2, 7])
def test_waiting_single_reads_ride_one_barrier(kv_cluster, n):
    """N ``read`` calls queued between two ticks are promoted into the
    group's offer slot together: one stamp, one barrier, each future its
    own result, in the order they came."""
    lc = kv_cluster
    _, node = _ready_leader(lc)
    for i in range(n):
        _write(lc, node, f"k{i}", i)
    m = node.metrics
    barriers, served, joined = (m["read_barriers"], m["reads_served"],
                                m["reads_coalesced"])
    futs = [node.read(0, _kv("get", f"k{i}")) for i in range(n)]
    lc.tick_until(lambda: all(f.done() for f in futs), what="reads served")
    assert [f.result() for f in futs] == list(range(n))
    assert m["read_barriers"] == barriers + 1
    assert m["reads_served"] == served + n
    assert m["reads_coalesced"] == joined + n - 1
    assert m.histogram("read_batch_queries").max == n


def test_read_behind_an_offer_in_flight_waits_for_the_next_slot(tmp_path):
    """The invariant of the merge: only batches WAITING when the slot is
    won share its stamp.  A strict ReadIndex offer (no lease) is stamped
    in one step and waits a round of acknowledgements for its release; a
    read that arrives meanwhile joins no stamped offer and gets a barrier
    of its own, one slot later."""
    root = str(tmp_path)
    lc = LocalCluster(
        _cfg(read_lease=False), root,
        provider_factory=lambda i: KVMachineProvider(
            os.path.join(root, f"kv{i}")))
    try:
        _, node = _ready_leader(lc)
        _write(lc, node, "a", 1)
        barriers = node.metrics["read_barriers"]
        first = node.read(0, _kv("get", "a"))
        lc.tick()               # offered and stamped: the round is out
        assert 0 in node._reads_offered or node._reads_pending.get(0)
        late = node.read(0, _kv("get", "a"))
        # It waits: it is in no offer, stamped or not.
        assert [b.sink.future for b in node._reads_waiting[0]] == [late]
        lc.tick_until(lambda: first.done() and late.done(),
                      what="both reads served")
        assert first.result() == late.result() == 1
        assert node.metrics["read_barriers"] == barriers + 2
    finally:
        lc.close()


def test_a_raising_query_fails_only_its_own_call(kv_cluster):
    lc = kv_cluster
    _, node = _ready_leader(lc)
    _write(lc, node, "a", 1)
    barriers = node.metrics["read_barriers"]
    good1 = node.read(0, _kv("get", "a"))
    bad = node.read(0, _kv("set", "a", 2))       # not a query: raises
    good2 = node.read(0, _kv("get", "missing"))
    lc.tick_until(lambda: good1.done() and bad.done() and good2.done(),
                  what="reads settled")
    assert node.metrics["read_barriers"] == barriers + 1
    assert good1.result() == 1 and good2.result() is None
    assert isinstance(bad.exception(), ValueError)
    assert is_refusal(bad.exception())            # a read: always retry-safe


def test_explicit_read_batch_keeps_its_atomic_list(kv_cluster):
    """``read_batch`` under a barrier shared with single reads: one future,
    one list in order; a query of it that raises fails that call whole
    (with the per-slot outcomes), and nobody else's."""
    lc = kv_cluster
    _, node = _ready_leader(lc)
    _write(lc, node, "a", 1)
    _write(lc, node, "b", 2)
    barriers = node.metrics["read_barriers"]
    single = node.read(0, _kv("get", "a"))
    batch = node.read_batch(0, [_kv("get", "b"), _kv("get", "a"),
                                _kv("get", "missing")])
    broken = node.read_batch(0, [_kv("get", "a"), _kv("add", "l", 1)])
    after = node.read(0, _kv("get", "b"))
    futs = (single, batch, broken, after)
    lc.tick_until(lambda: all(f.done() for f in futs), what="reads settled")
    assert node.metrics["read_barriers"] == barriers + 1
    assert single.result() == 1 and after.result() == 2
    assert batch.result() == [2, 1, None]
    exc = broken.exception()
    assert isinstance(exc, BatchAbortedError)
    assert exc.completed == [True, False] and exc.results[0] == 1


def test_leadership_loss_fails_every_part_as_a_marked_refusal(kv_cluster):
    """An offer whose barrier can no longer be earned (the leader is cut
    off and loses its term) fails part by part: every future of it, and
    of the batches still waiting, gets the retry-safe refusal."""
    lc = kv_cluster
    leader, node = _ready_leader(lc)
    _write(lc, node, "a", 1)
    lc.faults.isolate(leader)
    lc.tick(node.cfg.read_fresh_ticks + 2)   # lease evidence goes stale
    aborted = node.metrics["read_batches_aborted"]
    parts = [node.read(0, _kv("get", "a")) for _ in range(3)]
    lc.tick()                                # one offer, three parts
    waiting = [node.read(0, _kv("get", "a")),
               node.read_batch(0, [_kv("get", "a"), _kv("get", "a")])]
    # The majority elects behind the cut; healed, the old leader meets
    # the higher term and steps down with its reads unconfirmed.
    lc.tick_until(lambda: any(n.is_leader(0) for i, n in lc.nodes.items()
                              if i != leader),
                  what="a new leader behind the cut", max_rounds=400)
    assert not any(f.done() for f in parts + waiting)
    lc.faults.heal()
    lc.tick_until(lambda: all(f.done() for f in parts + waiting),
                  what="reads refused", max_rounds=400)
    for f in parts + waiting:
        exc = f.exception()
        # (a read_batch's failure carries the refusal as its cause)
        exc = getattr(exc, "cause", exc)
        assert isinstance(exc, NotLeaderError) and is_refusal(exc), exc
    assert node.metrics["read_batches_aborted"] >= aborted + 5


def test_group_queue_cap_still_refuses(tmp_path):
    """The cap counts waiting QUERIES, merged or not: the read that would
    pass it is refused at the door, and the slot's next win empties it."""
    from rafting_tpu.runtime.node import RaftNode

    root = str(tmp_path)
    lc = LocalCluster(
        _cfg(), root,
        provider_factory=lambda i: KVMachineProvider(
            os.path.join(root, f"kv{i}")))
    try:
        _, node = _ready_leader(lc)
        assert isinstance(node, RaftNode)
        node.group_queue_cap = 4
        futs = [node.read(0, _kv("get", "a")) for _ in range(4)]
        over = node.read(0, _kv("get", "a"))
        assert isinstance(over.exception(), BusyLoopError)
        assert is_refusal(over.exception())
        over2 = node.read_batch(0, [_kv("get", "a")] * 2)
        assert isinstance(over2.exception(), BusyLoopError)
        lc.tick_until(lambda: all(f.done() for f in futs),
                      what="queued reads served")
        assert all(f.exception() is None for f in futs)
        assert node._read_queued_n[0] == 0
        again = node.read(0, _kv("get", "a"))
        lc.tick_until(again.done, what="read after the queue emptied")
        assert again.exception() is None
    finally:
        lc.close()


# ------------------------------------------------------- stub redirect cap --

class _StuckFollowerNode:
    """A node that never leads and never learns a hint — the worst-case
    election ping-pong from the stub's point of view."""

    node_id = 0
    serializer = JsonSerializer()

    def __init__(self):
        class _T:
            def forward_submit(self, peer, lane, payload, timeout=None):
                raise AssertionError("no hint -> no forward expected")

            forward_read = forward_submit

        self.transport = _T()

    def is_leader(self, lane):
        return False

    def leader_hint(self, lane):
        return None

    def submit(self, lane, payload):
        raise AssertionError("not leader -> no local submit expected")

    read = submit


class _HintPingPongNode(_StuckFollowerNode):
    """Always hints at peer 1, whose serve side refuses NotLeader back —
    the two ex-leaders pointing at each other."""

    def __init__(self):
        super().__init__()
        self.forwards = 0
        node = self

        class _T:
            def forward_submit(self, peer, lane, payload, timeout=None):
                node.forwards += 1
                return False, b"REFUSED:NotLeaderError: group 0: not leader"

            forward_read = forward_submit

        self.transport = _T()

    def leader_hint(self, lane):
        return 1


class _FakeContainer:
    def __init__(self, node):
        self._node = node

    def _lookup(self, name):
        return 0


@pytest.mark.parametrize("op", ["submit", "read"])
def test_stub_redirect_cap_no_hint(op):
    """max_redirects bounds the retry loop: with a huge budget left, a
    hintless election still fails fast after the capped retries instead
    of burning the whole budget."""
    stub = RaftStub(_FakeContainer(_StuckFollowerNode()), "g", 0,
                    forward=True, forward_budget=300.0, max_redirects=3)
    t0 = time.monotonic()
    fut = getattr(stub, op)(b"x")
    with pytest.raises(NotLeaderError):
        fut.result(timeout=30)
    assert time.monotonic() - t0 < 10.0, "redirect cap did not bound the loop"


@pytest.mark.parametrize("op", ["submit", "read"])
def test_stub_redirect_cap_ping_pong(op):
    """Ex-leaders hinting at each other: the forward channel keeps
    answering REFUSED:NotLeader — the cap bounds the ping-pong COUNT."""
    node = _HintPingPongNode()
    stub = RaftStub(_FakeContainer(node), "g", 0,
                    forward=True, forward_budget=300.0, max_redirects=4)
    fut = getattr(stub, op)(b"x")
    with pytest.raises(NotLeaderError):
        fut.result(timeout=30)
    assert node.forwards <= 5, f"{node.forwards} forwards despite cap 4"


# -------------------------------------------------------------- prometheus --

def test_render_prometheus_format():
    m = Metrics()
    m["reads_served"] += 5
    m.gauge("groups_led", 3)
    m.observe("read_barrier_latency_s", 0.004)
    m.observe("read_barrier_latency_s", 0.2)
    text = m.render_prometheus()
    assert "# TYPE raft_reads_served_total counter" in text
    assert "raft_reads_served_total 5" in text
    assert "# TYPE raft_groups_led gauge" in text
    assert "raft_groups_led 3" in text
    assert "# TYPE raft_read_barrier_latency_s histogram" in text
    assert 'raft_read_barrier_latency_s_bucket{le="+Inf"} 2' in text
    assert "raft_read_barrier_latency_s_count 2" in text
    # Cumulative buckets are monotone.
    counts = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
              if line.startswith("raft_read_barrier_latency_s_bucket")]
    assert counts == sorted(counts)
    assert text.endswith("\n")


def test_node_metrics_expose_read_counters(kv_cluster):
    lc = kv_cluster
    _, node = _ready_leader(lc)
    wf = node.submit(0, _kv("set", "m", 1))
    lc.tick_until(wf.done, what="write applied")
    rf = node.read(0, _kv("get", "m"))
    lc.tick_until(rf.done, what="read served")
    text = node.metrics.render_prometheus()
    assert "raft_reads_served_total" in text
    assert "raft_read_barrier_latency_s_count" in text
    assert "raft_read_lease_hits_total" in text
