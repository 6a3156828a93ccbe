"""Unit tests for the history model (testkit/history.py) and the Wing &
Gong linearizability checker (testkit/linz.py).

The load-bearing cases are the Jepsen classification corners: ``info``
(outcome unknown) writes may linearize or vanish, marked-refusal
``fail`` writes must NOT appear, and a client retry of an
unknown-outcome append is legal exactly when the first attempt was
recorded ``info`` — the retry duplicate-safety contract documented on
RaftStub.execute."""

import json
import math

import pytest

from rafting_tpu.api.anomaly import (
    NotLeaderError, WaitTimeoutError, as_refusal)
from rafting_tpu.testkit import linz
from rafting_tpu.testkit.history import History, Op, StubRecorder


def _op(i, kind, key, value=None, status="ok", result=None, inv=0,
        resp=None, proc="p"):
    if resp is None:
        resp = math.inf if status == "info" else inv + 0.5
    return Op(id=i, proc=proc, kind=kind, key=key, value=value,
              status=status, result=result, invoke_seq=inv, resp_seq=resp)


# ------------------------------------------------------------- the model --

def test_sequential_register_reads():
    ops = [_op(0, "w", "x", 1, inv=0, resp=1),
           _op(1, "r", "x", result=1, inv=2, resp=3),
           _op(2, "w", "x", 2, inv=4, resp=5),
           _op(3, "r", "x", result=2, inv=6, resp=7)]
    assert linz.check(ops).ok


def test_stale_read_is_flagged():
    """Two writes complete strictly before the read is invoked: real-time
    order pins w1 < w2 < r, so r returning the OLD value is the classic
    stale read — exactly the defect the KV machine's test knob injects."""
    ops = [_op(0, "w", "x", 1, inv=0, resp=1),
           _op(1, "w", "x", 2, inv=2, resp=3),
           _op(2, "r", "x", result=1, inv=4, resp=5)]
    res = linz.check(ops)
    assert not res.ok and res.key == "x"
    assert "NON-LINEARIZABLE" in res.render()


def test_concurrent_write_read_may_see_either():
    # Read overlaps the write: old and new value are both legal.
    base = [_op(0, "w", "x", 1, inv=0, resp=1),
            _op(1, "w", "x", 2, inv=2, resp=6)]
    assert linz.check(base + [_op(2, "r", "x", result=1, inv=3,
                                  resp=4)]).ok
    assert linz.check(base + [_op(2, "r", "x", result=2, inv=3,
                                  resp=4)]).ok
    assert not linz.check(base + [_op(2, "r", "x", result=7, inv=3,
                                      resp=4)]).ok


def test_info_write_may_happen_or_not():
    """An unknown-outcome write is forever-concurrent: a later read may
    see it (it committed eventually) or never see it (it was lost)."""
    base = [_op(0, "w", "x", 1, inv=0, resp=1),
            _op(1, "w", "x", 2, status="info", inv=2)]
    assert linz.check(base + [_op(2, "r", "x", result=1, inv=4,
                                  resp=5)]).ok
    assert linz.check(base + [_op(2, "r", "x", result=2, inv=4,
                                  resp=5)]).ok
    # ...and it may even take effect AFTER a read that missed it.
    assert linz.check(base + [_op(2, "r", "x", result=1, inv=4, resp=5),
                              _op(3, "r", "x", result=2, inv=6,
                                  resp=7)]).ok


def test_failed_write_must_not_appear():
    """A MARKED refusal is a promise the command never entered any log;
    a read observing it anyway is a soundness violation."""
    ops = [_op(0, "w", "x", 1, inv=0, resp=1),
           _op(1, "w", "x", 2, status="fail", inv=2, resp=3),
           _op(2, "r", "x", result=2, inv=4, resp=5)]
    assert not linz.check(ops).ok


def test_info_write_before_invoke_is_illegal():
    # Even an info op cannot take effect BEFORE its invocation.
    ops = [_op(0, "r", "x", result=5, inv=0, resp=1),
           _op(1, "w", "x", 5, status="info", inv=2)]
    assert not linz.check(ops).ok


# ----------------------------------------------- retry duplicate-safety --

def test_duplicate_append_legal_iff_first_attempt_was_info():
    """The at-most-once contract (RaftStub.execute docstring): a client
    that resubmits after an UNKNOWN outcome may double-apply.  The
    history stays sound because the first attempt is ``info``: a read
    seeing the value once or twice both verify.  Recording that same
    first attempt as ``fail`` (as if it provably never happened) makes
    the double-apply a checker violation — a duplicate apply is always
    surfaced, never silently accepted."""
    retry = [_op(1, "a", "l", "v", status="info", inv=1),
             _op(2, "a", "l", "v", inv=3, resp=4)]
    once = [_op(3, "r", "l", result=["v"], inv=5, resp=6)]
    twice = [_op(3, "r", "l", result=["v", "v"], inv=5, resp=6)]
    assert linz.check(retry + once).ok      # first attempt lost
    assert linz.check(retry + twice).ok     # first attempt committed too
    misrecorded = [_op(1, "a", "l", "v", status="fail", inv=1, resp=2),
                   _op(2, "a", "l", "v", inv=3, resp=4)]
    assert linz.check(misrecorded + once).ok
    assert not linz.check(misrecorded + twice).ok   # duplicate surfaced
    thrice = [_op(3, "r", "l", result=["v", "v", "v"], inv=5, resp=6)]
    assert not linz.check(retry + thrice).ok        # 2 attempts, 3 applies


def test_append_order_must_match_observed_list():
    ops = [_op(0, "a", "l", "a", inv=0, resp=1),
           _op(1, "a", "l", "b", inv=2, resp=3),
           _op(2, "r", "l", result=["b", "a"], inv=4, resp=5)]
    assert not linz.check(ops).ok
    ops[2] = _op(2, "r", "l", result=["a", "b"], inv=4, resp=5)
    assert linz.check(ops).ok


# -------------------------------------------- counterexamples & locality --

def test_counterexample_is_minimal_prefix():
    """Shrinking keeps only the shortest failing response-prefix: noise
    appended after the witness read must not appear."""
    ops = [_op(0, "w", "x", 1, inv=0, resp=1),
           _op(1, "w", "x", 2, inv=2, resp=3),
           _op(2, "r", "x", result=1, inv=4, resp=5)]   # the witness
    noise = [_op(10 + i, "w", "x", 100 + i, inv=10 + 2 * i,
                 resp=11 + 2 * i) for i in range(8)]
    res = linz.check(ops + noise)
    assert not res.ok
    assert {o.id for o in res.counterexample} <= {0, 1, 2}
    assert any(o.id == 2 for o in res.counterexample)


def test_per_key_compositionality():
    good = [_op(0, "w", "x", 1, inv=0, resp=1),
            _op(1, "r", "x", result=1, inv=2, resp=3)]
    bad = [_op(2, "w", "y", 1, inv=4, resp=5),
           _op(3, "r", "y", result=9, inv=6, resp=7)]
    res = linz.check(good + bad)
    assert not res.ok and res.key == "y"
    assert res.checked_keys == 2 and res.n_ops == 4


def test_vacuous_histories_pass():
    assert linz.check([]).ok
    assert linz.check([_op(0, "w", "x", 1, status="info", inv=0)]).ok
    assert linz.check([_op(0, "w", "x", 1, status="fail", inv=0,
                           resp=1)]).ok


# ----------------------------------------------------- history recording --

class _FakeStub:
    """Duck-typed stand-in exposing the renamed raw paths the recorder
    wraps (api/stub.py: execute -> _execute under the history gate)."""

    def __init__(self, behavior):
        self._behavior = behavior

    def _execute(self, command, timeout):
        return self._behavior(command)

    def _execute_read(self, query, timeout):
        return self._behavior(query)


def test_recorder_classification_rule():
    h = History()
    rec = StubRecorder(h, "c0")
    set_cmd = json.dumps({"op": "set", "k": "x", "v": 1})
    # ok
    assert rec.execute(_FakeStub(lambda c: 1), set_cmd, None) == 1
    # MARKED refusal -> fail (provably never happened)
    with pytest.raises(NotLeaderError):
        rec.execute(_FakeStub(
            lambda c: (_ for _ in ()).throw(
                as_refusal(NotLeaderError("hint")))), set_cmd, None)
    # unmarked NotLeader (accept-then-abort) -> info, NOT fail
    with pytest.raises(NotLeaderError):
        rec.execute(_FakeStub(
            lambda c: (_ for _ in ()).throw(NotLeaderError("late"))),
            set_cmd, None)
    # timeout -> info (still in flight)
    with pytest.raises(WaitTimeoutError):
        rec.execute(_FakeStub(
            lambda c: (_ for _ in ()).throw(WaitTimeoutError("t"))),
            set_cmd, None)
    ops = {o.id: o for o in h.ops()}
    assert [ops[i].status for i in range(4)] == \
        ["ok", "fail", "info", "info"]
    assert ops[1].error == "NotLeaderError"
    assert math.isinf(ops[2].resp_seq) and math.isinf(ops[3].resp_seq)
    assert h.counts() == {"ok": 1, "fail": 1, "info": 2}


def test_recorder_parses_kv_vocabulary_and_fallback():
    h = History()
    rec = StubRecorder(h, "c1")
    rec.execute(_FakeStub(lambda c: 2),
                json.dumps({"op": "add", "k": "l", "v": "e"}), None)
    rec.execute_read(_FakeStub(lambda c: ["e"]),
                     json.dumps({"op": "get", "k": "l"}), None)
    rec.execute(_FakeStub(lambda c: None), b"\x00raw-bytes", None)
    ops = h.ops()
    assert (ops[0].kind, ops[0].key, ops[0].value) == ("a", "l", "e")
    assert (ops[1].kind, ops[1].key, ops[1].result) == ("r", "l", ["e"])
    assert (ops[2].kind, ops[2].key) == ("w", "__cmd__")


def test_recorded_result_is_snapshotted():
    """A read returning a LIVE machine object (the KV machine hands out
    its actual list) must be recorded by value: later mutation of the
    returned object cannot rewrite what the read saw."""
    h = History()
    rec = StubRecorder(h, "c0")
    live = ["a"]
    rec.execute_read(_FakeStub(lambda c: live),
                     json.dumps({"op": "get", "k": "l"}), None)
    live.append("b")
    assert h.ops()[0].result == ["a"]


def test_history_unpaired_invoke_is_info_forever():
    h = History()
    h.invoke("c0", "w", "x", 1)   # the client thread died mid-call
    (op,) = h.ops()
    assert op.status == "info" and math.isinf(op.resp_seq)
    assert linz.check(h).ok


# ----------------------------------------- the gray-failure nemesis --
#
# Integration tier: the leader_isolate nemesis (testkit/chaos.py) cuts
# every link INTO a group's leader while its outbound heartbeats keep
# suppressing follower timers — the fault CheckQuorum exists for.  One
# honest note on what the lease CAN'T do wrong here: this engine's
# lease evidence is ACK-RECEIPT based (a leader extends its lease only
# from acks it actually hears), so an inbound cut starves the lease
# rather than letting it serve stale reads — the CQ-off failure mode
# is UNAVAILABILITY (a hostage group), not a linearizability
# violation.  The CQ-on run is therefore the load-bearing safety
# proof for the new 6c transition: step-down + cq_veto + re-election
# under concurrent lease reads must leave a linearizable history, and
# the group must keep committing.  tools/chaos_run.py carries the
# matching soak + committed counterexample artifact.

def test_leader_isolate_lease_linearizable_and_live(tmp_path):
    """check_quorum=True under repeated inbound-only leader cuts: the
    6c step-down fires (counter proof), the healthy majority re-elects,
    lease-read clients see a linearizable history, and goodput survives
    the cuts (ok ops keep landing)."""
    import os as _os
    from rafting_tpu.core.types import EngineConfig as _EC
    from rafting_tpu.machine.kv_machine import KVMachineProvider
    from rafting_tpu.testkit.chaos import (
        ChaosConductor, KVWorkload, plan_leader_isolate)
    from rafting_tpu.testkit.harness import LocalCluster
    from rafting_tpu.testkit.history import History

    cfg = _EC(n_groups=3, n_peers=3, log_slots=64, batch=8, max_submit=8,
              election_ticks=10, heartbeat_ticks=3, rpc_timeout_ticks=8,
              read_lease=True, check_quorum=True)
    root = str(tmp_path)
    cluster = LocalCluster(
        cfg, root, seed=13,
        provider_factory=lambda i: KVMachineProvider(
            _os.path.join(root, f"node{i}", "kv")))
    try:
        for g in range(cfg.n_groups):
            cluster.wait_leader(g)
        history = History()
        # dur=25 > 2 election timeouts: every cut outlives the step-down
        # bound, so a surviving leader would be a real regression.
        events = plan_leader_isolate(160, seed=13, group=1,
                                     period=50, dur=25)
        conductor = ChaosConductor(cluster, events)
        load = KVWorkload(cluster, history, group=1, clients=3, seed=13)
        load.start()
        conductor.run(extra_ticks=40, tick_sleep=0.002)
        load.stop()
        load.join(tick_fn=conductor.step)
        conductor.finish()
        hits = [ev for ev in conductor.applied
                if ev["kind"] == "leader_isolate" and "victim" in ev]
        assert hits, f"nemesis never landed: {conductor.applied}"
        stepdowns = sum(
            n.metrics._counters.get("checkquorum_stepdowns", 0)
            for n in cluster.nodes.values())
        assert stepdowns >= 1, \
            "no CheckQuorum step-down under an inbound-only leader cut"
        counts = history.counts()
        assert counts["ok"] >= 10, f"workload starved: {counts}"
        res = linz.check(history)
        assert res.ok, res.render()
    finally:
        cluster.close()


def test_leader_isolate_hostage_when_check_quorum_off(tmp_path):
    """The counterexample run (check_quorum=False): the same inbound
    cut leaves the half-dead leader in charge for 4+ election timeouts
    — its heartbeats suppress every follower timer, no higher term ever
    reaches it, and a command submitted to it can never commit (the
    quorum's acks are on the severed inbound path).  This is the
    availability hole the tentpole closes; the artifact twin lives in
    tools/chaos_run.py (--nemesis leader-isolate --no-check-quorum)."""
    from rafting_tpu.core.types import EngineConfig as _EC, LEADER
    from rafting_tpu.testkit.harness import LocalCluster

    cfg = _EC(n_groups=3, n_peers=3, log_slots=64, batch=8, max_submit=8,
              election_ticks=10, heartbeat_ticks=3, rpc_timeout_ticks=8,
              read_lease=True, check_quorum=False)
    cluster = LocalCluster(cfg, str(tmp_path), seed=13)
    try:
        for g in range(cfg.n_groups):
            cluster.wait_leader(g)
        lead = cluster.leader_of(1)
        victim = cluster.nodes[lead]
        elections0 = sum(n.metrics._counters.get("elections", 0)
                         for n in cluster.nodes.values())
        for o in range(cfg.n_peers):
            if o != lead:
                cluster.faults.set_link(o, lead, False)
        fut = victim.submit(1, b"hostage-probe")
        cluster.tick(4 * cfg.election_ticks)
        assert cluster.leader_of(1) == lead, \
            "leader lost the group without CheckQuorum (unexpected)"
        # The probe must NOT commit.  It either hangs (no quorum ack can
        # arrive on the severed inbound path) or the leader's quorum-
        # health gate already refused it (NotReady: no healthy majority
        # heard) — both are the unavailability; commitment would be the
        # bug.  And no follower can take over either: their election
        # timers are suppressed by the victim's still-flowing
        # heartbeats, so they refuse with NotLeader pointing AT the
        # hostage-taker.
        if fut.done():
            from rafting_tpu.api.anomaly import NotReadyError
            assert isinstance(fut.exception(), NotReadyError), \
                f"probe resolved oddly: {fut.exception()!r}"
        for o in range(cfg.n_peers):
            if o == lead:
                continue
            f2 = cluster.nodes[o].submit(1, b"follower-probe")
            assert isinstance(f2.exception(), NotLeaderError)
        elections1 = sum(n.metrics._counters.get("elections", 0)
                         for n in cluster.nodes.values())
        assert elections1 == elections0, \
            "a follower re-elected despite suppressed timers"
        # Heal and the world recovers — the hole is the WINDOW, which
        # without CheckQuorum is unbounded (as long as the gray fault).
        cluster.faults.heal()
        cluster.net.flush_held()
        probe = [None]

        def committed():
            if probe[0] is None and cluster.nodes[lead].is_ready(1):
                probe[0] = cluster.nodes[lead].submit(1, b"post-heal")
            return (probe[0] is not None and probe[0].done()
                    and probe[0].exception() is None)
        cluster.tick_until(committed, 800, "post-heal commit")
    finally:
        cluster.close()


# ------------------------------------ info ops that nobody observed --

def _made_up_history(rng, kind, n):
    """``n`` ops of up to three overlapping clients on one key, results
    from a real register (``w``) or list (``a``) stepped at each op's
    linearization point; then some mutations lose their reply (info: took
    effect or never ran) and now and then a read is made stale."""
    t, state, ops, open_until = 0, None, [], [0, 0, 0]
    for i in range(n):
        c = rng.randrange(3)
        inv = max(t, open_until[c]) + 1
        resp = inv + rng.choice((1, 1, 3, 6))
        t, open_until[c] = inv, resp
        if rng.random() < 0.5:
            ops.append([inv + rng.random() * (resp - inv),
                        _op(i, "r", "k", inv=inv, resp=resp, proc=f"c{c}")])
        else:
            ops.append([inv + rng.random() * (resp - inv),
                        _op(i, kind, "k", f"v{i}", inv=inv, resp=resp,
                            proc=f"c{c}")])
    lost = set()
    for at, o in sorted(ops, key=lambda p: p[0]):
        if o.kind == "r":
            o.result = state
        elif rng.random() < 0.35:
            o.status, o.resp_seq = "info", math.inf
            if rng.random() < 0.5:
                lost.add(o.id)          # never ran
                continue
        if o.kind == "w":
            state = o.value
        elif o.kind == "a":
            state = (state or ()) + (o.value,)
    reads = [o for _, o in ops if o.kind == "r"]
    if reads and rng.random() < 0.4:
        stale = rng.choice(reads)
        stale.result = rng.choice([None, "v0", ("v0",), ("v1", "v0")])
    return [o for _, o in ops]


@pytest.mark.parametrize("kind", ["w", "a"], ids=["register", "list"])
@pytest.mark.parametrize("seed", range(6))
def test_dropping_unobserved_info_ops_changes_no_verdict(monkeypatch, kind,
                                                         seed):
    """``linz._observable`` drops the info writes and appends whose value
    no ok read returned before the search; the search over every live op
    (what the checker did before) gives the same verdict on made-up
    histories of both kinds, legal and illegal."""
    import random

    rng = random.Random(1000 * seed + ord(kind))
    histories = [_made_up_history(rng, kind, rng.randrange(4, 10))
                 for _ in range(60)]
    pruned = [linz.check_ops(h) for h in histories]
    dropped = sum(
        len(h) - len(linz._observable(h)) for h in histories)
    monkeypatch.setattr(linz, "_observable", lambda live: live)
    assert pruned == [linz.check_ops(h) for h in histories]
    assert dropped > 0 and True in pruned and False in pruned


def test_info_appends_nobody_read_cost_no_search():
    """Eight appends of unknown outcome early in a list key's history of
    ninety ops, none of them ever read: each could take effect at any later
    point or never, which the search once tried one by one (a run of the
    loop test below grew past 30 GB on such a history); dropped, the
    verdict is the ok ops' own, at once.  An observed one still counts:
    a read that holds it where it cannot be is flagged."""
    ops, held = [], ()
    for i in range(8):
        ops.append(_op(i, "a", "l", f"lost{i}", status="info", inv=i))
    for i in range(8, 90, 2):
        held += (f"v{i}",)
        ops.append(_op(i, "a", "l", f"v{i}", inv=10 * i, resp=10 * i + 1))
        ops.append(_op(i + 1, "r", "l", result=list(held), inv=10 * i + 2,
                       resp=10 * i + 3))
    assert linz.check(ops).ok
    seen = list(held) + ["lost3"]
    ok = ops + [_op(90, "r", "l", result=seen, inv=1000, resp=1001)]
    assert linz.check(ok).ok
    early = ops[:10] + [_op(91, "r", "l", result=["lost3", "v8"], inv=85,
                            resp=86)] + ops[10:]
    assert not linz.check(early).ok


# ------------------------------------------- loops that step on arrival --
#
# The same judgment with the nodes under their own loops
# (runtime/node.py _run): a step when the period's timer fires and a step
# whenever a slice, a write or a read arrives in between, the engine's
# clock advanced by the timer's steps alone (HostInbox.clock).  Most steps
# of this run are arrival steps; the leader of the loaded group is cut off
# half way, so an election, a step-down and the read plane's aborts all
# happen between and across them.  The period is twenty of the cluster's
# own steps as it measures them here and now (never under 50 ms): a loop
# starts an arrival step only where twice a step fits before its timer
# (runtime/node.py arrival_step_at), so on a host where a step is slow (a
# suite's other workers) a fixed period left no room, the work rode the
# timer and the share of arrival steps, which follows the wall clock, read
# under a half (0.375 on a quarter of a core at 50 ms; 0.73-0.83 there at
# twenty steps).

@pytest.mark.parametrize("lease", [True, False], ids=["lease", "strict"])
def test_linearizable_with_loops_stepping_on_arrival(tmp_path, lease):
    import os as _os
    import time as _time
    from rafting_tpu.core.types import EngineConfig as _EC
    from rafting_tpu.machine.kv_machine import KVMachineProvider
    from rafting_tpu.testkit.chaos import KVWorkload
    from rafting_tpu.testkit.harness import LocalCluster
    from rafting_tpu.testkit.history import History

    cfg = _EC(n_groups=3, n_peers=3, log_slots=64, batch=8, max_submit=8,
              election_ticks=10, heartbeat_ticks=1, rpc_timeout_ticks=8,
              read_lease=lease, check_quorum=True)
    root = str(tmp_path)
    cluster = LocalCluster(
        cfg, root, seed=17,
        provider_factory=lambda i: KVMachineProvider(
            _os.path.join(root, f"node{i}", "kv")))
    try:
        for g in range(cfg.n_groups):
            cluster.wait_leader(g)
        rounds = 5
        t0 = _time.perf_counter()
        cluster.tick(rounds)
        step = (_time.perf_counter() - t0) / (rounds * len(cluster.nodes))
        period = max(0.05, 20 * step)
        now0 = {i: int(n.state.now) for i, n in cluster.nodes.items()}
        timer0 = {i: n.timer_ticks for i, n in cluster.nodes.items()}
        history = History()
        load = KVWorkload(cluster, history, group=1, clients=3, seed=17)
        cluster.start_loops(period)
        load.start()
        _time.sleep(30 * period)
        victim = cluster.leader_of(1)
        if victim is not None:
            cluster.faults.isolate(victim)
        _time.sleep(40 * period)
        cluster.faults.heal()
        _time.sleep(40 * period)
        load.stop()
        load.join()
        cluster.stop_loops()
        steps = sum(int(n.metrics["ticks"]) for n in cluster.nodes.values())
        arrival = sum(int(n.metrics["ticks_on_arrival"])
                      for n in cluster.nodes.values())
        assert arrival > steps - arrival, \
            f"the loops hardly woke: {arrival} of {steps} steps"
        for i, n in cluster.nodes.items():
            assert int(n.state.now) - now0[i] == n.timer_ticks - timer0[i]
        counts = history.counts()
        assert counts["ok"] >= 10, f"workload starved: {counts}"
        res = linz.check(history)
        assert res.ok, res.render()
    finally:
        cluster.close()
