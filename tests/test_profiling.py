"""JAX profiler hooks (SURVEY §5: the reference ships zero tracing; the TPU
build integrates the device profiler from the start): the tick loop's
stage spans (utils/profiling.py StageSpans) — a ``tick_stage_<name>_s`` sample per phase per tick and a
``raft.<name>`` span in whatever ``jax.profiler`` session is running.
All assertions are on counts or on one thread's own clock."""

import glob
import time

import pytest

from rafting_tpu.core.types import LEADER, EngineConfig
from rafting_tpu.log import native_available
from rafting_tpu.testkit.harness import LocalCluster, wal_store_factory
from rafting_tpu.utils import profiling
from rafting_tpu.utils.metrics import Metrics
from rafting_tpu.utils.profiling import StageSpans

# The top-level stages: every instant of tick() is in exactly one of them
# (dispatch = intake + upload + enqueue, scan_wait = device + fetch).
TOP_STAGES = ("dispatch", "wal", "fsync", "send", "apply", "reads",
              "maintain", "scan_wait", "mirrors", "tail")


def _raft_spans(trace_dir):
    """(name, statistics) of every ``raft*`` span on /host:CPU of the one
    session written under ``trace_dir``."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True)
    planes = [p for p in ProfileData.from_file(path).planes
              if p.name == "/host:CPU"]
    assert planes
    return [(e.name, dict(e.stats)) for p in planes for ln in p.lines
            for e in ln.events if e.name.startswith("raft")]


def _stage_total(node) -> float:
    h = node.metrics._histograms
    return sum(h[f"tick_stage_{s}_s"].total for s in TOP_STAGES
               if f"tick_stage_{s}_s" in h)


@pytest.mark.parametrize("engine", [
    "python", pytest.param("native", marks=pytest.mark.skipif(
        not native_available(), reason="native WAL engine unavailable"))])
@pytest.mark.parametrize("shape", ["packed", "columns"])
def test_stages_cover_the_tick(tmp_path, take_shape, shape, engine):
    """Over 50 manual ticks of a 16-lane node the stage totals sum to at
    least 95% of the time spent inside tick(), on the ticking thread's
    own clock, and the composite stages are the sums of their parts —
    whichever step the shape takes and under either persist step: the
    Python one enters ``wal`` and ``fsync``, the native one ``wal``
    alone, and both leave one sample a host phase in each of the two
    histograms."""
    cfg = EngineConfig(n_groups=16, n_peers=3)
    take_shape(cfg, shape)
    c = LocalCluster(
        cfg, str(tmp_path), seed=1,
        store_factory=wal_store_factory(str(tmp_path), engine))
    try:
        c.wait_leader(0)
        node = c.nodes[0]
        assert node.store.can_stage_native == (engine == "native")
        before, loop = _stage_total(node), 0.0
        n0 = node.metrics.histogram("tick_latency_s").n
        for _ in range(50):
            t0 = time.perf_counter()
            node.tick()
            loop += time.perf_counter() - t0
            for other in (c.nodes[1], c.nodes[2]):
                other.tick()
        covered = _stage_total(node) - before
        assert node.metrics.histogram("tick_latency_s").n - n0 == 50
        assert 0.95 * loop <= covered <= loop
        h = node.metrics._histograms
        parts = sum(h[f"tick_stage_dispatch_{p}_s"].total
                    for p in ("intake", "upload", "enqueue"))
        assert h["tick_stage_dispatch_s"].total == pytest.approx(parts)
        assert h["tick_stage_scan_wait_s"].total == pytest.approx(
            h["tick_stage_scan_device_s"].total
            + h["tick_stage_scan_fetch_s"].total)
        # tick_latency_s still ends where the tail begins.
        assert h["tick_latency_s"].total + h["tick_stage_tail_s"].total \
            == pytest.approx(_stage_total(node), rel=1e-6)
        assert h["tick_stage_wal_s"].n == h["tick_stage_fsync_s"].n \
            == h["tick_stage_send_s"].n
        assert ("fsync" in node._stages.spent) == (engine == "python")
    finally:
        c.close()


def test_any_profiler_session_holds_the_stage_spans(tmp_path):
    """A jax.profiler trace started by the TEST (not by a node) around
    eight ticks holds the tick phases on /host:CPU, each with ``node`` and
    ``tick``, for every node of the process, and no parent span."""
    import jax

    cfg = EngineConfig(n_groups=16, n_peers=3)
    c = LocalCluster(cfg, str(tmp_path / "data"), seed=1)
    trace_dir = str(tmp_path / "trace")
    try:
        c.wait_leader(0)
        first = c.nodes[0].ticks
        with jax.profiler.trace(trace_dir):
            c.tick(8)
    finally:
        c.close()
    seen = {}
    for name, stats in _raft_spans(trace_dir):
        seen.setdefault(name, set()).add((stats["node"], stats["tick"]))
    for name in ("dispatch_intake", "dispatch_upload", "dispatch_enqueue",
                 "scan_device", "scan_fetch", "mirrors",
                 "tail", "reads", "maintain"):
        ids = seen["raft." + name]
        assert {n for n, _ in ids} == {0, 1, 2}, name
        assert {t for n, t in ids if n == 0} == set(range(first, first + 8))
    assert not {"raft.tick", "raft.dispatch", "raft.fetch",
                "raft.scan_wait"} & set(seen)


def test_read_and_maintain_spans_carry_what_the_tick_did(tmp_path):
    """``raft.reads`` carries ``queries`` and ``barriers`` for what that
    tick served; ``raft.maintain`` carries ``ring_used``, ``ring_slots``,
    ``led``, ``checkpoints`` and ``by_pressure``: written when the phase
    is done (StageSpans.note), read back from the session's xplane."""
    import jax

    cfg = EngineConfig(n_groups=4, n_peers=3)
    c = LocalCluster(cfg, str(tmp_path / "data"), seed=1)
    trace_dir = str(tmp_path / "trace")
    try:
        lead = c.wait_leader(0)
        node = c.nodes[lead]
        c.tick_until(lambda: node.is_ready(0), what="leader ready")
        with jax.profiler.trace(trace_dir):
            futs = [node.read(0, b"q%d" % i) for i in range(3)]
            for _ in range(20):
                futs.append(node.submit(0, b"w"))
                c.tick()
            assert all(f.done() for f in futs[:3])
    finally:
        c.close()
    reads, maintain = [], []
    for name, stats in _raft_spans(trace_dir):
        if name == "raft.reads" and "barriers" in stats:
            reads.append(stats)
        elif name == "raft.maintain" and stats["node"] == lead:
            maintain.append(stats)
    # Three reads waited together: one tick served them under one barrier.
    assert [(s["queries"], s["barriers"]) for s in reads] == [(3, 1)]
    assert reads[0]["node"] == lead
    assert len(maintain) == 20
    assert all(s["ring_slots"] == cfg.log_slots and s["led"] >= 1
               for s in maintain)
    used = [s["ring_used"] for s in maintain]
    assert used == sorted(used) and used[-1] >= 19   # one entry a tick
    assert all(s["checkpoints"] == s["by_pressure"] == 0 for s in maintain)


def test_a_tick_crosses_to_the_device_in_two_transfers_each_way(tmp_path):
    """A served tick uploads its packed buffer and fetches the step's:
    ``raft.dispatch_upload`` and ``raft.scan_fetch`` note ``transfers`` of
    exactly 1 on every tick of every node (2 while the flags had a buffer
    of their own, whence the name: two a tick in all now), and the
    counters ``h2d_transfers`` / ``d2h_transfers`` advance by 1 a tick."""
    import jax

    cfg = EngineConfig(n_groups=16, n_peers=3)
    c = LocalCluster(cfg, str(tmp_path / "data"), seed=1)
    trace_dir = str(tmp_path / "trace")
    counters = ("h2d_transfers", "d2h_transfers")
    try:
        lead = c.wait_leader(0)
        node = c.nodes[lead]
        c.tick_until(lambda: node.is_ready(0), what="leader ready")
        before = {i: [n.metrics[k] for k in counters]
                  for i, n in c.nodes.items()}
        with jax.profiler.trace(trace_dir):
            for _ in range(8):
                node.submit(0, b"w")
                node.read(0, b"q")
                c.tick()
        for i, n in c.nodes.items():
            for k, was in zip(counters, before[i]):
                assert n.metrics[k] - was == 8, (i, k)
    finally:
        c.close()
    noted = {"raft.dispatch_upload": [], "raft.scan_fetch": []}
    for name, stats in _raft_spans(trace_dir):
        if name in noted:
            noted[name].append(stats["transfers"])
    for name, transfers in noted.items():
        assert len(transfers) == 3 * 8, name
        assert transfers == [1] * (3 * 8), (name, transfers)


def test_lease_hits_lanes_and_leaderless_ride_the_spans(tmp_path):
    """PR 31's statistics.  ``raft.reads``: ``lease_hits`` (barriers the
    lease released in the step that stamped them, where they served a
    query) never passes ``queries`` and sums over a run to the counter
    ``read_lease_hits``.  The host phase's spans carry ``lanes`` (what the
    phase walked one by one: a step that moved one group walks a few lanes,
    not every lane the node holds); ``raft.mirrors`` carries ``leaderless``
    and ``open``, 0 of them leaderless once every group is led."""
    import jax

    cfg = EngineConfig(n_groups=64, n_peers=3)
    c = LocalCluster(cfg, str(tmp_path / "data"), seed=1)
    trace_dir = str(tmp_path / "trace")
    try:
        for g in range(cfg.n_groups):
            c.wait_leader(g)
        lead = c.leader_of(0)
        node = c.nodes[lead]
        c.tick_until(lambda: node.is_ready(0), what="leader ready")
        c.tick(3)
        hits0 = node.metrics["read_lease_hits"]
        served0 = node.metrics["reads_served"]
        with jax.profiler.trace(trace_dir):
            futs = []
            for i in range(12):
                if i == 5:
                    # A vetoed step drops the lease evidence: the read it
                    # stamps pays a ReadIndex round trip (no lease hit).
                    node._read_veto_hold = 1
                futs.append(node.read(0, b"q%d" % i))
                if i % 3 == 0:
                    futs.append(node.read(0, b"r%d" % i))   # shares a barrier
                futs.append(node.submit(0, b"w%d" % i))
                c.tick()
            c.tick(4)
            assert all(f.done() and f.exception() is None for f in futs)
        hits = node.metrics["read_lease_hits"] - hits0
        served = node.metrics["reads_served"] - served0
    finally:
        c.close()
    reads, lanes, mirrors = [], {}, []
    for name, stats in _raft_spans(trace_dir):
        if name == "raft.reads" and stats["node"] == lead \
                and "queries" in stats:
            reads.append(stats)
        if "lanes" in stats:
            lanes.setdefault(name, []).append(stats["lanes"])
        if name == "raft.mirrors":
            mirrors.append(stats)
    assert served == 16 and sum(s["queries"] for s in reads) == 16
    assert all(0 <= s["lease_hits"] <= s["barriers"] <= s["queries"]
               for s in reads)
    assert sum(s["lease_hits"] for s in reads) == hits
    assert 0 < hits < sum(s["barriers"] for s in reads)     # the vetoed one
    assert set(lanes) == {"raft.wal", "raft.send", "raft.apply",
                          "raft.reads", "raft.maintain"}
    # One loaded group of 64: no phase of any step walks a tenth of the
    # lanes, and the sends are the heartbeat columns (lane by peer).
    for name in ("raft.wal", "raft.apply", "raft.reads", "raft.maintain"):
        assert max(lanes[name]) <= 6, (name, max(lanes[name]))
    assert max(lanes["raft.apply"]) >= 1 and max(lanes["raft.wal"]) >= 1
    assert mirrors and all(s["open"] == cfg.n_groups
                           and s["leaderless"] == 0 for s in mirrors)


def test_scanned_rides_the_host_spans_in_whole_planes_on_a_packed_shape(
        tmp_path):
    """PR 40's statistic.  ``raft.wal``, ``raft.apply``, ``raft.reads`` and
    ``raft.maintain`` carry ``scanned``: the lanes the stage's selection
    passes ran over.  On a shape that keeps ``node_step_packed`` every pass
    looks at every lane, so every stage of every step reads a multiple of
    ``n_groups`` (the timer's policy pass on top), whatever moved; over a
    run the four sum to the counter ``host_lanes_scanned``."""
    import jax

    cfg = EngineConfig(n_groups=64, n_peers=3)
    c = LocalCluster(cfg, str(tmp_path / "data"), seed=1)
    trace_dir = str(tmp_path / "trace")
    try:
        lead = c.wait_leader(0)
        node = c.nodes[lead]
        c.tick_until(lambda: node.is_ready(0), what="leader ready")
        before = {i: n.metrics["host_lanes_scanned"]
                  for i, n in c.nodes.items()}
        steps = {i: n.ticks for i, n in c.nodes.items()}
        with jax.profiler.trace(trace_dir):
            futs = []
            for i in range(8):
                futs += [node.submit(0, b"w%d" % i), node.read(0, b"q%d" % i)]
                c.tick()
            for n in c.nodes.values():      # steps between two timer ticks
                n.tick(arrival=True)
            c.tick(3)
            assert all(f.done() and f.exception() is None for f in futs)
        counted = {i: n.metrics["host_lanes_scanned"] - before[i]
                   for i, n in c.nodes.items()}
        steps = {i: n.ticks - steps[i] for i, n in c.nodes.items()}
    finally:
        c.close()
    G = cfg.n_groups
    scanned = {}
    for name, stats in _raft_spans(trace_dir):
        if "scanned" in stats:
            scanned.setdefault(stats["node"], {}).setdefault(
                name, []).append(stats["scanned"])
    for i, by_span in scanned.items():
        assert set(by_span) == {"raft.wal", "raft.apply", "raft.reads",
                                "raft.maintain"}
        for name, values in by_span.items():
            assert len(values) == steps[i], (i, name)
            assert all(v > 0 and v % G == 0 for v in values), (i, name)
        assert sum(map(sum, by_span.values())) == counted[i]
        # The arrival step skipped the policy pass that every timer step
        # makes: it scanned the least.
        assert min(by_span["raft.maintain"]) < max(by_span["raft.maintain"])
    assert sorted(scanned) == [0, 1, 2]


def test_windows_unready_and_merged_replies_ride_the_spans(tmp_path):
    """PR 37's statistics.  ``raft.mirrors`` carries ``led`` and
    ``unready`` beside ``leaderless`` / ``open``, and the five window sums
    of the step's readback with the slots they are a share of
    (``win_slots`` = ``win_pairs`` x ``inflight_limit``);
    ``raft.dispatch_intake`` carries ``collapsed`` and ``merged`` beside
    ``arrival``: on every step of every node, 0 where nothing happened."""
    import jax

    cfg = EngineConfig(n_groups=16, n_peers=3)
    c = LocalCluster(cfg, str(tmp_path / "data"), seed=1)
    trace_dir = str(tmp_path / "trace")
    try:
        for g in range(cfg.n_groups):
            c.wait_leader(g)
        c.tick_until(lambda: all(
            n.h_ready[n.h_role == LEADER].all() for n in c.nodes.values()),
            what="every leader ready")
        with jax.profiler.trace(trace_dir):
            c.tick(6)
        led = {i: int((n.h_role == LEADER).sum()) for i, n in c.nodes.items()}
    finally:
        c.close()
    mirrors, intake = [], []
    for name, stats in _raft_spans(trace_dir):
        if name == "raft.mirrors":
            mirrors.append(stats)
        elif name == "raft.dispatch_intake":
            intake.append(stats)
    assert len(mirrors) == len(intake) == 3 * 6
    assert sum(led.values()) == cfg.n_groups
    for s in mirrors:
        assert s["led"] == led[s["node"]] and s["unready"] == 0
        assert s["win_pairs"] == 2 * s["led"]
        assert s["win_slots"] == s["win_pairs"] * cfg.inflight_limit
        assert 0 <= s["win_occupied"] <= s["win_slots"]
        assert s["win_full"] == s["win_cooling"] == s["win_timeouts"] == 0
    assert all(s["arrival"] == s["collapsed"] == s["merged"] == 0
               for s in intake)


def test_a_stage_that_outlasts_the_period_is_a_stall(caplog):
    """``stage_stalls`` and ONE warning (node, tick, stage, seconds) for a
    stage longer than the loop's period, from the instants the boundary
    takes anyway; nothing for ``wait``, nothing for a stage inside the
    period, nothing under a caller that has no period."""
    m = Metrics()
    st = StageSpans(m, 2)
    st.begin(41)
    for period, name, slow in ((None, "apply", True), (0.01, "apply", False),
                               (0.01, "wait", True), (0.01, "send", True)):
        st.period = period
        st.enter(name)
        if slow:
            time.sleep(0.02)
        st.leave()
    assert m["stage_stalls"] == 1
    (line,) = [r.getMessage() for r in caplog.records]
    assert line.startswith("node 2 tick 41: stage send took 0.0")
    assert line.endswith("(period 0.010 s)")


def test_a_loops_first_step_is_never_a_stall(tmp_path, caplog):
    """``_run`` names the period to the stage spans after the loop's first
    step (which loads or compiles the program): that step may take many
    periods and says nothing, a later one that does is one line, and the
    period goes when the loop does."""
    c = LocalCluster(EngineConfig(n_groups=4, n_peers=3), str(tmp_path))
    try:
        node, period, calls = c.nodes[0], 0.02, []
        st = node._stages

        def tick(arrival=False):
            st.begin(len(calls))
            st.enter("apply")
            calls.append(st.period)
            if len(calls) in (1, 3):        # the first step, and a later
                time.sleep(3 * period)
            if len(calls) == 5:
                node._stop.set()
            st.leave()

        node.tick = tick
        node._run(period)
        assert calls == [None] + [period] * 4 and st.period is None
        assert node.metrics["stage_stalls"] == 1
        (line,) = [r.getMessage() for r in caplog.records
                   if "stage" in r.getMessage()]
        assert line.startswith("node 0 tick 2: stage apply took")
    finally:
        c.close()


def test_note_without_a_session_is_nothing():
    st = StageSpans(Metrics(), 0)
    st.begin(1)
    st.enter("reads")
    st.note(queries=3, barriers=1)      # no span open: no error, no effect
    st.leave()


def test_no_profiler_session_allocates_no_annotation(monkeypatch):
    """With no session a boundary is the flag test and the histogram
    sample: no annotation object is ever built."""
    class Never:
        @staticmethod
        def is_enabled():
            return False

        def __init__(self, *a, **kw):
            raise AssertionError("annotation allocated with no session")

    monkeypatch.setattr(profiling, "TraceAnnotation", Never)
    m = Metrics()
    st = StageSpans(m, 3)
    st.begin(7)
    a = st.enter("dispatch_intake")
    b = st.enter("wal", observe=False)
    end = st.leave()
    assert a <= b <= end
    assert m.histogram("tick_stage_dispatch_intake_s").n == 1
    assert "tick_stage_wal_s" not in m._histograms      # left to the caller
    assert st.spent["dispatch_intake"] == pytest.approx(b - a)
    assert st.spent["wal"] == pytest.approx(end - b)
    assert st.leave() >= end and len(st.spent) == 2     # nothing open
    assert st.period is None and "stage_stalls" not in m._counters


def test_tick_starts_lie_on_a_staggered_grid(tmp_path):
    """Node i of P is due a share i/P of a period after node 0, on one
    clock: the next start is the first grid instant more than half a
    period away, so a tick that starts on time is followed one period
    later, one that overran rejoins the grid at the next instant, and no
    two nodes of a host are ever due together."""
    c = LocalCluster(EngineConfig(n_groups=4, n_peers=3), str(tmp_path))
    try:
        period = 0.2
        n0, n1, n2 = (c.nodes[i] for i in range(3))
        for n in (n0, n1, n2):
            n._tick_stagger = True            # what start(stagger=True) sets
        assert n0._next_start(10.0, period) == pytest.approx(10.2)
        assert n1._next_start(10.0, period) == pytest.approx(10.2 + period / 3)
        assert n2._next_start(10.0, period) == pytest.approx(10.2 - period / 3)
        # On time (a hair after its slot): one period on.
        assert n1._next_start(10.0 + period / 3 + 1e-4, period) \
            == pytest.approx(10.2 + period / 3)
        # Started 0.3 of a period late: still the next slot, so the grid
        # holds and the lateness does not carry over.
        assert n0._next_start(10.06, period) == pytest.approx(10.2)
        # Started 0.7 late (it overran): the slot after the next, never a
        # start less than half a period after this one.
        assert n0._next_start(10.14, period) == pytest.approx(10.4)
        for t in (10.0, 10.013, 10.077, 10.19):
            due = sorted(n._next_start(t, period) % period
                         for n in (n0, n1, n2))
            gaps = [b - a for a, b in zip(due, due[1:] + [due[0] + period])]
            assert gaps == pytest.approx([period / 3] * 3)
        # The loop hands the grid's instant to the deadline test.
        n0._note_tick_start(10.0, period, n0._next_start(10.0, period))
        assert n0._tick_due == pytest.approx(10.2)
    finally:
        c.close()


@pytest.mark.parametrize("node_id", [0, 1, 2])
@pytest.mark.parametrize("started", [10.0, 10.013, 10.14])
def test_unstaggered_starts_are_one_period_apart(tmp_path, node_id, started):
    """Nobody said the nodes share a host (RaftConfig.tick_stagger off,
    the default): the next start is one period after this one, for every
    node and wherever in a period the start fell, as before the grid."""
    c = LocalCluster(EngineConfig(n_groups=4, n_peers=3), str(tmp_path))
    try:
        n = c.nodes[node_id]
        assert n._tick_stagger is False
        assert n._next_start(started, 0.2) == pytest.approx(started + 0.2)
    finally:
        c.close()


def test_tick_stagger_reaches_the_loop_from_the_config(tmp_path,
                                                       monkeypatch):
    """RaftConfig.tick_stagger (off unless the deployer sets it) is what
    the container hands the node's loop."""
    from rafting_tpu.api import RaftConfig
    from rafting_tpu.api.container import RaftContainer
    from rafting_tpu.runtime.node import RaftNode
    seen = []
    monkeypatch.setattr(
        RaftNode, "start",
        lambda self, tick_interval=0.02, stagger=False:
            seen.append((tick_interval, stagger)))
    assert RaftConfig(local="raft://127.0.0.1:1",
                      peers=()).tick_stagger is False
    for i, flag in enumerate((False, True)):
        rc = RaftConfig(local="raft://127.0.0.1:1", peers=(),
                        data_dir=str(tmp_path / f"n{i}"), tick_ms=200,
                        n_groups=4, tick_stagger=flag)
        c = RaftContainer(rc, admin=False).create()
        c.destroy()
    assert seen == [(0.2, False), (0.2, True)]


def test_late_ticks_from_a_fake_clock(tmp_path):
    """tick_late_s = start - due, the due instant being what the loop
    named at the previous start (here one interval on); ticks_late counts starts more than half a
    period late: a tick of 1.2 periods is not counted, one of 1.8 is."""
    c = LocalCluster(EngineConfig(n_groups=4, n_peers=3), str(tmp_path))
    try:
        node = c.nodes[0]
        period = 0.5
        starts = [10.0]
        for periods in (1.0, 1.2, 1.8, 1.0, 0.999):
            starts.append(starts[-1] + periods * period)
        for now in starts:
            node._note_tick_start(now, period, now + period)
        h = node.metrics.histogram("tick_late_s")
        assert h.n == 5                       # the first start has no due
        assert h.total == pytest.approx((0.2 + 0.8) * period)
        assert h.max == pytest.approx(0.8 * period)
        assert node.metrics["ticks_late"] == 1
    finally:
        c.close()


def test_leaderless_gauge_counts_open_lanes_until_they_are_led(tmp_path):
    """``groups_leaderless``: every open lane before the first election,
    none once every group is led and the followers know by whom; a store
    that is electing reads differently from one that is sick."""
    cfg = EngineConfig(n_groups=16, n_peers=3)
    c = LocalCluster(cfg, str(tmp_path / "data"), seed=2)
    try:
        c.tick(1)
        for node in c.nodes.values():
            g = node.metrics._gauges
            assert g["groups_leaderless"] == g["groups_active"] \
                == cfg.n_groups
        for g in range(cfg.n_groups):
            c.wait_leader(g)
        c.tick_until(lambda: all(
            n.metrics._gauges["groups_leaderless"] == 0
            for n in c.nodes.values()),
            what="every lane led and known")
    finally:
        c.close()


def test_lease_carried_and_kicks_ride_the_reads_span(tmp_path):
    """PR 39's statistics.  ``raft.reads`` carries ``lease_carried``
    (barriers released in the step that stamped them on evidence of an
    EARLIER tick: never more than ``lease_hits``) on every step that
    served a query and ``kicks`` (batches left pending, which ask for a
    barrier heartbeat) on every step that stamped one; over a window both
    sum to the counters ``read_lease_carried`` and ``read_kicks``.  At the
    engine's shipped timing (heartbeat 3, election 10) a lane hears
    acknowledgements one tick in three and the lease reaches the two
    after it."""
    import jax

    cfg = EngineConfig(n_groups=16, n_peers=3)
    assert cfg.lease_carry_ticks == 2
    c = LocalCluster(cfg, str(tmp_path / "data"), seed=1)
    trace_dir = str(tmp_path / "trace")
    names = ("read_lease_hits", "read_lease_carried", "read_kicks",
             "reads_served")
    try:
        lead = c.wait_leader(0)
        node = c.nodes[lead]
        c.tick_until(lambda: node.is_ready(0), what="leader ready")
        c.tick(6)                      # the election's own traffic is over
        before = [node.metrics[k] for k in names]
        with jax.profiler.trace(trace_dir):
            futs = []
            for i in range(18):
                if i == 9:
                    # A vetoed step drops the evidence: until the next
                    # round's acknowledgements the reads are left pending
                    # and each kicks a heartbeat of its own.
                    node.note_pause()
                futs.append(node.read(0, b"q%d" % i))
                c.tick()
            c.tick(4)
            assert all(f.done() and f.exception() is None for f in futs)
        hits, carried, kicks, served = (
            node.metrics[k] - was for k, was in zip(names, before))
    finally:
        c.close()
    reads = [stats for name, stats in _raft_spans(trace_dir)
             if name == "raft.reads" and stats["node"] == lead]
    served_in = [s for s in reads if "queries" in s]
    assert served == 18 and sum(s["queries"] for s in served_in) == 18
    assert all(0 <= s["lease_carried"] <= s["lease_hits"] <= s["barriers"]
               for s in served_in)
    assert sum(s["lease_carried"] for s in served_in) == carried
    assert sum(s["lease_hits"] for s in served_in) == hits
    assert sum(s.get("kicks", 0) for s in reads) == kicks
    assert 0 < carried < hits and kicks >= 1
