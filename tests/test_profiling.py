"""JAX profiler hooks (SURVEY §5: the reference ships zero tracing; the TPU
build integrates the device profiler from the start): ``device_trace``
around a region, and the tick loop's stage spans (utils/profiling.py
StageSpans) — a ``tick_stage_<name>_s`` sample per phase per tick and a
``raft.<name>`` span in whatever ``jax.profiler`` session is running.
All assertions are on counts or on one thread's own clock."""

import glob
import time

import pytest

from rafting_tpu.core.types import EngineConfig
from rafting_tpu.testkit.harness import LocalCluster
from rafting_tpu.utils import profiling
from rafting_tpu.utils.metrics import Metrics
from rafting_tpu.utils.profiling import StageSpans, device_trace

# The top-level stages: every instant of tick() is in exactly one of them
# (dispatch = intake + upload + enqueue, scan_wait = device + fetch).
TOP_STAGES = ("dispatch", "wal", "fsync", "send", "apply", "reads",
              "maintain", "scan_wait", "mirrors", "eager_send", "tail")


def test_device_trace_context(tmp_path):
    import jax.numpy as jnp
    d = str(tmp_path / "t")
    with device_trace(d):
        jnp.ones((8, 8)).sum().block_until_ready()
    assert glob.glob(d + "/**/*.xplane.pb", recursive=True)
    with device_trace(""):   # falsy -> no-op
        pass


def _stage_total(node) -> float:
    h = node.metrics._histograms
    return sum(h[f"tick_stage_{s}_s"].total for s in TOP_STAGES
               if f"tick_stage_{s}_s" in h)


@pytest.mark.parametrize("pipeline", [False, True],
                         ids=["serial", "pipelined"])
def test_stages_cover_the_tick(tmp_path, pipeline):
    """Over 50 manual ticks of a 16-lane node the stage totals sum to at
    least 95% of the time spent inside tick(), on the ticking thread's
    own clock, and the composite stages are the sums of their parts."""
    cfg = EngineConfig(n_groups=16, n_peers=3)
    c = LocalCluster(cfg, str(tmp_path), seed=1, pipeline=pipeline)
    try:
        c.wait_leader(0)
        node = c.nodes[0]
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
        assert ("tick_stage_eager_send_s" in h) == pipeline
    finally:
        c.close()


def test_any_profiler_session_holds_the_stage_spans(tmp_path):
    """A jax.profiler trace started by the TEST (not by a node) around
    eight ticks holds the tick phases on /host:CPU, each with ``node`` and
    ``tick``, for every node of the process, and no parent span."""
    import jax
    from jax.profiler import ProfileData

    cfg = EngineConfig(n_groups=16, n_peers=3)
    c = LocalCluster(cfg, str(tmp_path / "data"), seed=1, pipeline=True)
    trace_dir = str(tmp_path / "trace")
    try:
        c.wait_leader(0)
        first = c.nodes[0].ticks
        with jax.profiler.trace(trace_dir):
            c.tick(8)
    finally:
        c.close()
    (path,) = glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True)
    seen = {}
    planes = [p for p in ProfileData.from_file(path).planes
              if p.name == "/host:CPU"]
    assert planes
    for ln in planes[0].lines:
        for e in ln.events:
            if e.name.startswith("raft"):
                stats = dict(e.stats)
                seen.setdefault(e.name, set()).add(
                    (stats["node"], stats["tick"]))
    for name in ("dispatch_intake", "dispatch_upload", "dispatch_enqueue",
                 "scan_device", "scan_fetch", "mirrors", "eager_send",
                 "tail", "reads", "maintain"):
        ids = seen["raft." + name]
        assert {n for n, _ in ids} == {0, 1, 2}, name
        assert {t for n, t in ids if n == 0} == set(range(first, first + 8))
    assert not {"raft.tick", "raft.dispatch", "raft.fetch",
                "raft.scan_wait"} & set(seen)


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


def test_host_cost_is_the_stage_spans_own_sum(tmp_path):
    """What the loop weighs against its deadline (runtime/node.py
    settles_now) is read from the stage spans, per host phase: the six
    host stages of a tick over the host phases it ran, the costliest of
    the last HOST_COST_MEMORY ticks."""
    from rafting_tpu.runtime.node import HOST_COST_MEMORY, HOST_STAGES
    c = LocalCluster(EngineConfig(n_groups=4, n_peers=3), str(tmp_path),
                     seed=1, pipeline=True)
    try:
        node = c.nodes[0]
        node.tick()
        assert not node._host_costs           # fetched, no host phase yet
        node.tick()
        assert len(node._host_costs) == node._host_runs == 1
        assert node._host_costs[-1] == pytest.approx(
            node._stages.total(*HOST_STAGES))
        node._tick_due = float("inf")         # room: this tick runs two
        node.tick()
        assert node._host_runs == 2
        assert node._host_costs[-1] == pytest.approx(
            node._stages.total(*HOST_STAGES) / 2)
        for _ in range(HOST_COST_MEMORY + 3):
            node.tick()
        assert len(node._host_costs) == HOST_COST_MEMORY
    finally:
        c.close()


def test_late_ticks_from_a_fake_clock(tmp_path):
    """tick_late_s = start - due, the due instant being the previous
    start plus the interval; ticks_late counts starts more than half a
    period late: a tick of 1.2 periods is not counted, one of 1.8 is."""
    c = LocalCluster(EngineConfig(n_groups=4, n_peers=3), str(tmp_path))
    try:
        node = c.nodes[0]
        period = 0.5
        starts = [10.0]
        for periods in (1.0, 1.2, 1.8, 1.0, 0.999):
            starts.append(starts[-1] + periods * period)
        for now in starts:
            node._note_tick_start(now, period)
        h = node.metrics.histogram("tick_late_s")
        assert h.n == 5                       # the first start has no due
        assert h.total == pytest.approx((0.2 + 0.8) * period)
        assert h.max == pytest.approx(0.8 * period)
        assert node.metrics["ticks_late"] == 1
    finally:
        c.close()
