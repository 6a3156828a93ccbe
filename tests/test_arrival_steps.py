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
def test_write_and_lease_read_resolve_before_the_next_timer_tick(
        tmp_path, lease):
    """At a period of seconds, a write is acknowledged and a lease read
    served while the leader's timer count stands still: steps that the
    arrivals started did the work, on the leader and on the followers
    that acknowledged.  A strict read is stamped by the timer's step
    (core/step.py 6b) and so takes the timer; its write does not."""
    lc = _cluster(tmp_path, read_lease=lease)
    try:
        lc.start_loops(2.0)
        lead = lc.nodes[lc.leader_of(1)]
        for attempt in range(4):
            timer0 = _after_timer_tick(lead)
            before = _counts(lc)
            lead.submit(1, _kv("set", "k", attempt)).result(60)
            if lease:
                assert lead.read(1, _kv("get", "k")).result(60) == attempt
            if lead.timer_ticks == timer0:
                break
        else:
            raise AssertionError("never inside one period in 4 attempts")
        after = _counts(lc)
        assert after[lead.node_id][2] > before[lead.node_id][2]
        assert sum(after[i][2] > before[i][2] for i in after
                   if i != lead.node_id) >= 1
        if not lease:
            timer0 = lead.timer_ticks
            assert lead.read(1, _kv("get", "k")).result(60) == attempt
            assert lead.timer_ticks > timer0
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
