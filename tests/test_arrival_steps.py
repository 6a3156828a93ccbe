"""A started loop steps when work arrives, not only when its timer fires
(``RaftNode._run``, ``arrival_step_at``), and the period stays the
engine's clock.  Real loops over a LocalCluster; everything is asserted
by counts (steps, timer ticks, elections), never by a wall-clock limit.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from rafting_tpu.core.types import EngineConfig
from rafting_tpu.machine.kv_machine import KVMachineProvider
from rafting_tpu.runtime.node import SETTLE_MARGIN, arrival_step_at
from rafting_tpu.testkit.harness import LocalCluster


def _cfg(**kw) -> EngineConfig:
    base = dict(n_groups=3, n_peers=3, log_slots=32, batch=4, max_submit=4,
                election_ticks=10, heartbeat_ticks=1, rpc_timeout_ticks=8,
                pre_vote=True)
    base.update(kw)
    return EngineConfig(**base)


def _kv(op, k, v=None) -> bytes:
    cmd = {"op": op, "k": k}
    if v is not None:
        cmd["v"] = v
    return json.dumps(cmd).encode()


def _cluster(tmp_path, **kw) -> LocalCluster:
    root = str(tmp_path)
    lc = LocalCluster(_cfg(**kw), root, seed=11,
                      provider_factory=lambda i: KVMachineProvider(
                          os.path.join(root, f"kv{i}")))
    for g in range(lc.cfg.n_groups):
        lc.wait_leader(g)
    lc.tick_until(lambda: all(lc.nodes[lc.leader_of(g)].is_ready(g)
                              for g in range(lc.cfg.n_groups)),
                  what="ready leaders")
    lc.tick(3)
    return lc


def _counts(lc):
    """Per node: (steps, timer ticks, arrival steps, elections)."""
    return {i: (n.ticks, n.timer_ticks,
                int(n.metrics["ticks_on_arrival"]),
                int(n.metrics["elections"]))
            for i, n in lc.nodes.items()}


def _after_timer_tick(node, timeout=60.0):
    """Return right after ``node``'s loop has run a timer tick."""
    t0, end = node.timer_ticks, time.monotonic() + timeout
    while node.timer_ticks == t0:
        assert time.monotonic() < end, "the loop's timer never fired"
        time.sleep(0.001)
    return node.timer_ticks


@pytest.mark.parametrize("lease", [True, False], ids=["lease", "strict"])
def test_write_and_read_resolve_before_the_next_timer_tick(tmp_path, lease):
    """At a period of seconds, a write is acknowledged and a linearizable
    read served while the leader's timer count stands still: steps that
    the arrivals started did the work, on the leader and on the followers
    that acknowledged.  That holds for the lease and for strict ReadIndex
    alike: a strict read is stamped in the step it arrives in, its
    barrier heartbeat leaves in that step and the followers' arrival
    steps acknowledge it (core/step.py 6b); no lease ever releases it."""
    lc = _cluster(tmp_path, read_lease=lease)
    try:
        lc.start_loops(2.0)
        lead = lc.nodes[lc.leader_of(1)]
        m0 = {k: int(lead.metrics[k]) for k in (
            "read_rounds", "read_stamps_on_arrival", "read_lease_hits")}
        for attempt in range(4):
            timer0 = _after_timer_tick(lead)
            before = _counts(lc)
            lead.submit(1, _kv("set", "k", attempt)).result(60)
            assert lead.read(1, _kv("get", "k")).result(60) == attempt
            if lead.timer_ticks == timer0:
                break
        else:
            raise AssertionError("never inside one period in 4 attempts")
        after = _counts(lc)
        assert after[lead.node_id][2] > before[lead.node_id][2]
        assert sum(after[i][2] > before[i][2] for i in after
                   if i != lead.node_id) >= 1
        lc.stop_loops()
        moved = {k: int(lead.metrics[k]) - v for k, v in m0.items()}
        assert moved["read_stamps_on_arrival"] >= 1
        if not lease:
            # Every strict read paid a round of its own.
            assert moved["read_lease_hits"] == 0
            assert moved["read_rounds"] == attempt + 1
    finally:
        lc.close()


def test_idle_cluster_steps_a_bounded_number_of_times_a_period(tmp_path):
    """Twenty idle periods.  A node steps once for its timer, and on
    arrival only for what that period's one heartbeat round brings it:
    a slice from each other node that leads something, an
    acknowledgement slice from each follower of what it leads; nothing
    answers an acknowledgement, so the round ends.  No step moves the
    clock but the timer's, and nobody campaigns."""
    lc = _cluster(tmp_path)
    try:
        now0 = {i: int(n.state.now) for i, n in lc.nodes.items()}
        c0 = _counts(lc)
        lc.start_loops(0.1)
        for n in lc.nodes.values():
            t0 = n.timer_ticks
            while n.timer_ticks < t0 + 20:
                time.sleep(0.01)
        lc.stop_loops()
        c1 = _counts(lc)
        for i, n in lc.nodes.items():
            steps, timer, arrival, elections = (
                a - b for a, b in zip(c1[i], c0[i]))
            assert timer >= 20 and steps == timer + arrival
            # 2 heartbeat slices + 2 acknowledgement slices a period (the
            # nodes' windows of 20 periods end apart: a few more).
            assert arrival <= 4 * timer + 8, (i, c0[i], c1[i])
            assert elections == 0
            assert int(n.state.now) - now0[i] == timer
    finally:
        lc.close()


def test_a_storm_of_reads_moves_no_clock(tmp_path):
    """Reads into one node as fast as they are served: its arrival steps
    outnumber its timer ticks ten to one, and still ``state.now`` moved
    by the timer count on every node, no follower of any group
    campaigned, every term stands."""
    lc = _cluster(tmp_path)
    try:
        lead = lc.nodes[lc.leader_of(1)]
        wf = lead.submit(1, _kv("set", "k", 1))
        lc.tick_until(wf.done, what="write applied")
        now0 = {i: int(n.state.now) for i, n in lc.nodes.items()}
        terms0 = {i: n.h_term.copy() for i, n in lc.nodes.items()}
        c0 = _counts(lc)
        lc.start_loops(0.5)
        _after_timer_tick(lead)
        stop = threading.Event()
        served = [0]

        def storm():
            while not stop.is_set():
                assert lead.read(1, _kv("get", "k")).result(60) == 1
                served[0] += 1

        th = threading.Thread(target=storm, daemon=True)
        th.start()
        t0 = lead.timer_ticks
        while lead.timer_ticks < t0 + 6:
            time.sleep(0.01)
        stop.set()
        th.join(timeout=60)
        lc.stop_loops()
        c1 = _counts(lc)
        steps, timer, arrival, _ = (a - b for a, b in
                                    zip(c1[lead.node_id], c0[lead.node_id]))
        assert arrival > 10 * timer, (served[0], c0, c1)
        for i, n in lc.nodes.items():
            d_timer = c1[i][1] - c0[i][1]
            assert int(n.state.now) - now0[i] == d_timer
            assert c1[i][3] == c0[i][3], "an election under the storm"
            np.testing.assert_array_equal(n.h_term, terms0[i])
    finally:
        lc.close()


def test_a_backlog_is_worked_off_in_a_few_steps(tmp_path):
    """One node's tick takes some three periods, once (more, and the
    drain collapses the queue, which this is not about).  Its peers'
    heartbeat slices queue up behind it (a slice per source per step is
    all a drain pops).  The loop keeps stepping while a drain leaves slices
    queued, so the depth is back at 0 a few steps later and stays
    there: nothing stands from period to period."""
    lc = _cluster(tmp_path)
    try:
        period = 0.1
        victim = lc.nodes[(lc.leader_of(1) + 1) % 3]
        depths = []
        fold = victim._fold_inbox_stats

        def spy():
            before = victim.metrics.histogram("inbox_backlog").n
            fold()
            h = victim.metrics.histogram("inbox_backlog")
            assert h.n == before + 1
            depths.append(max(
                (int(v) for k, v in victim.metrics._gauges.items()
                 if k.startswith("inbox_backlog_src")), default=0))

        victim._fold_inbox_stats = spy
        tick, stalled = victim.tick, []

        def late_tick(arrival=False):
            if not stalled and victim.timer_ticks >= t_stall:
                stalled.append(len(depths))
                time.sleep(2.7 * period)
            return tick(arrival=arrival)

        t_stall = victim.timer_ticks + 5
        victim.tick = late_tick
        lc.start_loops(period)
        while victim.timer_ticks < t_stall + 12:
            time.sleep(0.01)
        lc.stop_loops()
        assert stalled, "the late tick never ran"
        after = depths[stalled[0]:]
        assert max(after) >= 1, "the stall queued nothing"
        first = next(k for k, d in enumerate(after) if d)
        cleared = next(k for k, d in enumerate(after)
                       if k > first and d == 0)
        assert cleared - first <= 6, after
        assert not any(after[cleared + 3:]), after
    finally:
        lc.close()


def test_every_step_says_on_its_span_who_started_it(tmp_path, monkeypatch):
    """Under a profiler session each step's ``raft.dispatch_intake`` span
    carries ``arrival`` (0: the timer's, 1: started for work), the
    benchmark's reader (``benchmark/layer_metrics/arrival_tick_share.py``)
    turns them into the share the counters give, and a trace whose spans
    carry no such statistic, as the parent's, reads as nothing."""
    import glob
    import types

    import jax

    from benchmark import spanstats
    from benchmark.layer_metrics import arrival_tick_share

    lc = _cluster(tmp_path / "data")
    trace_dir = str(tmp_path / "trace")
    try:
        lead = lc.nodes[lc.leader_of(1)]
        c0 = _counts(lc)
        with jax.profiler.trace(trace_dir):
            lc.tick(2)                      # manual ticks: the timer's
            lc.start_loops(0.25)
            _after_timer_tick(lead)
            for k in range(10):
                lead.submit(1, _kv("set", "k", k)).result(60)
                assert lead.read(1, _kv("get", "k")).result(60) == k
            lc.stop_loops()
        c1 = _counts(lc)
        (path,) = glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True)
        spanstats.reduce_file.cache_clear()
        stats = spanstats.reduce_file(path)
        rows = spanstats.rows(stats, "dispatch_intake", "arrival")
        assert set(rows) == set(lc.nodes)
        for i in lc.nodes:
            steps, _, arrival, _ = (a - b for a, b in zip(c1[i], c0[i]))
            assert len(rows[i]) == steps
            assert sum(s["arrival"] for s in rows[i]) == arrival
            assert {s["arrival"] for s in rows[i]} <= {0.0, 1.0}
        share = arrival_tick_share.read(types.SimpleNamespace(xplane=path))
        total = sum(c1[i][0] - c0[i][0] for i in lc.nodes)
        assert share == pytest.approx(
            sum(c1[i][2] - c0[i][2] for i in lc.nodes) / total)
        assert 0.5 < share < 1.0
        # The parent's spans: node and tick, nothing else on the intake.
        for ticks in stats["dispatch_intake"].values():
            for s in ticks.values():
                del s["arrival"]
        assert spanstats.rows(stats, "dispatch_intake", "arrival") == {}
        monkeypatch.setattr(spanstats, "reduce_file", lambda p: stats)
        assert arrival_tick_share.read(
            types.SimpleNamespace(xplane=path)) is None
    finally:
        lc.close()


# ---------------------------------------------------------------- the gap

# Made-up readings of a loop between two steps: (now, ended, took, due,
# cost).  A 100,000-lane step and a small one; inside the gap, past it,
# with room before the timer and without.
READINGS = [(now, 10.0, took, due, cost)
            for took, cost in ((0.0145, 0.0145), (0.008, 0.006),
                               (0.19, 0.05))
            for now in (10.0, 10.004, 10.02, 10.3)
            for due in (10.03, 10.25, 11.0)]
WAITS = (0.0, 0.001, 0.0067, 0.0145, 0.1, 1.0)


def _room(at, due, cost):
    """The room rule by itself: a whole step fits SETTLE_MARGIN times
    between ``at`` and the timer."""
    return at + SETTLE_MARGIN * cost <= due


def _parent(now, ended, took, due, cost):
    """The rule as it stood: the gap is the last step's whole duration."""
    at = max(now, ended + took)
    return at if _room(at, due, cost) else None


def _waited_0_is_the_parents_instant(r):
    assert arrival_step_at(*r) == _parent(*r)
    assert arrival_step_at(*r, 0.0) == _parent(*r)


def _never_later_than_the_parent_never_before_now(r):
    old = _parent(*r)
    for w in WAITS:
        at = arrival_step_at(*r, w)
        if old is not None:
            assert at is not None and r[0] <= at <= old


def _monotone_in_waited(r):
    ats = [arrival_step_at(*r, w) for w in WAITS]
    for sooner, later in zip(ats[1:], ats):
        # more of the step spent blocked: the same instant or an earlier
        # one, and a step that was let start stays let start
        assert later is None or (sooner is not None and sooner <= later)


def _all_of_it_waited_starts_now(r):
    now, _, took, due, cost = r
    for w in (took, took + 0.5):
        at = arrival_step_at(*r, w)
        assert at == (now if _room(now, due, cost) else None)


def _the_room_rule_is_untouched(r):
    now, _, _, due, cost = r
    for w in WAITS:
        at = arrival_step_at(*r, w)
        # No room at ``now`` is no room, however short the gap; and a step
        # that is let start has room for the WHOLE step's cost, waits
        # and all: no arrival step makes a timer tick late.
        if not _room(now, due, cost):
            assert at is None
        if at is not None:
            assert _room(at, due, cost)


@pytest.mark.parametrize("holds", [
    _waited_0_is_the_parents_instant,
    _never_later_than_the_parent_never_before_now,
    _monotone_in_waited,
    _all_of_it_waited_starts_now,
    _the_room_rule_is_untouched,
], ids=lambda f: f.__name__.strip("_"))
def test_the_gap_is_what_the_step_held_of_the_interpreter(holds):
    """``arrival_step_at`` with one more observed quantity, ``waited``:
    the seconds of the last step its thread spent blocked.  A pure
    function of readings; no clock is read, no node is built."""
    assert any(_parent(*r) is None for r in READINGS)
    assert any(_parent(*r) not in (None, r[0]) for r in READINGS)
    for r in READINGS:
        holds(r)


class _Standing(threading.Event):
    """Work that always waits: an event nobody clears."""

    def clear(self):
        pass


BLOCKED_S = 0.03


@pytest.fixture(scope="module")
def blocked_loop(tmp_path_factory):
    """Real loops, traced, in which one node's step sleeps ``BLOCKED_S``
    inside its ``scan_device`` stage (a device that takes that long) under
    a standing ``_wake``.  Yields the trace, the counts around it and what
    that node's loop did over thirty of its steps."""
    import glob

    import jax

    tmp = tmp_path_factory.mktemp("blocked")
    lc = _cluster(tmp / "data")
    trace_dir = str(tmp / "trace")
    try:
        node = lc.nodes[lc.leader_of(1)]
        fetch, st = node._fetch, node._stages

        def slow_fetch(ctx):
            st.enter("scan_device")
            time.sleep(BLOCKED_S)
            return fetch(ctx)

        node._fetch = slow_fetch
        node._wake = _Standing()
        node._wake.set()
        m = node.metrics

        def busy_s():
            return (m.histogram("tick_latency_s").total
                    + m.histogram("tick_stage_tail_s").total)

        c0 = _counts(lc)
        held0 = {i: int(n.metrics["steps_held"])
                 for i, n in lc.nodes.items()}
        with jax.profiler.trace(trace_dir):
            lc.tick(1)
            lc.start_loops(2.0)
            _after_timer_tick(node)
            while node.ticks < c0[node.node_id][0] + 5:
                time.sleep(0.001)
            t0, b0, n0 = time.perf_counter(), busy_s(), node.ticks
            while node.ticks < n0 + 30:
                time.sleep(0.001)
            t1, b1, n1 = time.perf_counter(), busy_s(), node.ticks
            lc.stop_loops()
        (path,) = glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True)
        yield dict(lc=lc, node=node, path=path, c0=c0, c1=_counts(lc),
                   held0=held0, wall_s=t1 - t0, busy_s=b1 - b0,
                   steps=n1 - n0)
    finally:
        lc.close()


def test_a_loop_that_waits_for_its_device_steps_more_than_half_the_time(
        blocked_loop):
    """Under the parent's rule a loop with work always waiting took a
    step, waited the step's length, took the next: ``1 / (2 x took)``
    steps a second, its steps half of the time.  The seconds the step
    slept in ``scan_device`` held no interpreter and leave no gap behind
    them, so the steps fill more than half of the time."""
    b = blocked_loop
    assert b["steps"] >= 30
    took = b["busy_s"] / b["steps"]
    assert took > BLOCKED_S
    assert b["steps"] / b["wall_s"] > 1.0 / (2.0 * took), b


def test_each_step_says_what_gap_it_was_given(blocked_loop):
    """Every step a loop starts carries ``held``, ``gap_ms`` and
    ``waited_ms`` on its ``raft.dispatch_intake`` span beside ``arrival``
    (a step a caller takes by hand carries ``arrival`` alone); the
    counter ``steps_held`` and the histogram ``arrival_gap_s`` are the
    same over the arrival steps."""
    from benchmark import spanstats

    b = blocked_loop
    spanstats.reduce_file.cache_clear()
    stats = spanstats.reduce_file(b["path"])
    arrivals = spanstats.rows(stats, "dispatch_intake", "arrival")
    rows = spanstats.rows(stats, "dispatch_intake", "held")
    for i, n in b["lc"].nodes.items():
        steps, _, arrival, _ = (x - y for x, y in zip(b["c1"][i],
                                                      b["c0"][i]))
        assert len(arrivals[i]) == steps
        # the manual tick and the loop's first step were given no gap
        assert len(rows[i]) == steps - 2
        assert all({"gap_ms", "waited_ms"} <= set(s) for s in rows[i])
        on_arrival = [s for s in rows[i] if s["arrival"]]
        assert len(on_arrival) == arrival
        assert {s["held"] for s in rows[i]} <= {0.0, 1.0}
        assert not any(s["held"] for s in rows[i] if not s["arrival"])
        assert sum(s["held"] for s in on_arrival) == \
            int(n.metrics["steps_held"]) - b["held0"][i]
        gaps = n.metrics.histogram("arrival_gap_s")
        assert gaps.n == arrival
        assert 1e3 * gaps.total == pytest.approx(
            sum(s["gap_ms"] for s in on_arrival))
    slow = rows[b["node"].node_id]
    # Work always waited, so the gap held every arrival step back, and
    # the gap is the step less its sleep: shorter than the sleep was.
    assert all(s["held"] for s in slow if s["arrival"])
    assert all(s["waited_ms"] >= 1e3 * BLOCKED_S for s in slow)
    assert sum(s["gap_ms"] for s in slow) < sum(s["waited_ms"] for s in slow)


@pytest.mark.parametrize("metric,stat", [("step_held_share", "held"),
                                         ("arrival_gap_ms", "gap_ms")])
def test_the_gap_s_two_readers(blocked_loop, monkeypatch, metric, stat):
    """``benchmark/layer_metrics/step_held_share.py`` and
    ``arrival_gap_ms.py`` over a traced loop's spans: the mean of the
    statistic over the arrival steps of all nodes; a trace whose spans
    carry no such statistic, as the parent's, reads as nothing."""
    import copy
    import importlib
    import types

    from benchmark import spanstats

    reader = importlib.import_module(f"benchmark.layer_metrics.{metric}")
    r = types.SimpleNamespace(xplane=blocked_loop["path"])
    spanstats.reduce_file.cache_clear()
    stats = copy.deepcopy(spanstats.reduce_file(r.xplane))
    on_arrival = [s for ticks in stats["dispatch_intake"].values()
                  for s in ticks.values() if s.get("arrival") and stat in s]
    assert len(on_arrival) == sum(
        blocked_loop["c1"][i][2] - blocked_loop["c0"][i][2]
        for i in blocked_loop["lc"].nodes)
    want = sum(s[stat] for s in on_arrival) / len(on_arrival)
    assert reader.read(r) == pytest.approx(want)
    assert want > 0.5 if stat == "held" else 0.0 < want < 1e3 * BLOCKED_S
    # The parent's spans: node, tick and arrival, nothing of the gap.
    for ticks in stats["dispatch_intake"].values():
        for s in ticks.values():
            for k in ("held", "gap_ms", "waited_ms"):
                s.pop(k, None)
    monkeypatch.setattr(spanstats, "reduce_file", lambda p: stats)
    assert reader.read(r) is None
