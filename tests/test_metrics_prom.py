"""Prometheus exposition hygiene: non-finite guards, label escaping, and
the strict round-trip validator (ISSUE 3 satellites)."""

import math
import time

import pytest

from rafting_tpu.utils.metrics import (
    Metrics, escape_label_value, validate_exposition,
)


def _registry():
    m = Metrics()
    m.inc("commits", 5)
    m.inc("weird name-with.chars", 2)
    m.gauge("groups_led", 3)
    for v in (1e-6, 0.5, 2.0, 130.0):
        m.observe("tick_latency_s", v)
    return m


def test_render_round_trips_strict_validator():
    text = _registry().render_prometheus()
    validate_exposition(text)   # raises on any malformation
    assert "raft_commits_total 5" in text
    assert "raft_weird_name_with_chars_total 2" in text
    assert 'le="+Inf"' in text


def test_nonfinite_gauges_render_canonically():
    m = _registry()
    m.gauge("rate", float("nan"))
    m.gauge("hi", float("inf"))
    m.gauge("lo", float("-inf"))
    text = m.render_prometheus()
    # Python's spellings would be 'nan'/'inf' — the format wants these:
    assert "raft_rate NaN" in text
    assert "raft_hi +Inf" in text
    assert "raft_lo -Inf" in text
    validate_exposition(text)


def test_nonfinite_histogram_sum_guarded():
    m = Metrics()
    m.observe("h", float("inf"))
    text = m.render_prometheus()
    assert "raft_h_sum +Inf" in text
    validate_exposition(text)


def test_validator_rejects_malformations():
    good = _registry().render_prometheus()
    # Duplicate TYPE line.
    dup = good + "# TYPE raft_commits_total counter\n"
    with pytest.raises(ValueError, match="duplicate TYPE"):
        validate_exposition(dup)
    # Bad charset in a metric name.
    with pytest.raises(ValueError, match="malformed"):
        validate_exposition("bad-name 1\n")
    # Python float spellings are not valid exposition values.
    with pytest.raises(ValueError, match="malformed"):
        validate_exposition("raft_x nan\n")
    # Unsorted le buckets.
    bad = ('# TYPE h histogram\n'
           'h_bucket{le="2"} 1\n'
           'h_bucket{le="1"} 2\n'
           'h_bucket{le="+Inf"} 2\n')
    with pytest.raises(ValueError, match="not ascending"):
        validate_exposition(bad)
    # Bucket series missing its +Inf terminator.
    with pytest.raises(ValueError, match=r"missing \+Inf"):
        validate_exposition('h_bucket{le="1"} 1\n')
    # Missing trailing newline.
    with pytest.raises(ValueError, match="newline"):
        validate_exposition("x 1")


def test_escape_label_value():
    assert escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
    assert escape_label_value("plain") == "plain"


def test_windowed_rates_report_current_not_historical():
    m = Metrics()
    m._t0 -= 100.0          # pretend the node has been up 100 s
    m.inc("commits", 1000)  # ancient history
    m.checkpoint()
    m.inc("commits", 10)    # the current window
    life = m.rates()["commits_per_sec"]
    cur = m.rates(since_last=True)["commits_per_sec"]
    assert life < 11        # lifetime average diluted by the 100 s
    assert cur > 100        # windowed rate sees only the fresh 10
    # checkpoint() moves the baseline forward.
    m.checkpoint()
    assert m.rates(since_last=True)["commits_per_sec"] < 1e6
    time.sleep(0.01)
    m.inc("commits", 1)
    assert 0 < m.rates(since_last=True)["commits_per_sec"] < 1000


def test_windowed_rates_cover_absolute_set_counters():
    """The runtime sets some counters absolutely (m['commits'] = total);
    the windowed delta must still be the in-window movement."""
    m = Metrics()
    m["frontier"] = 500
    m.checkpoint()
    m["frontier"] = 530
    r = m.rates(since_last=True)["frontier_per_sec"]
    assert r > 0
    # Lifetime rate would have counted all 530.
    assert m.rates()["frontier_per_sec"] > r * 0  # both defined
    assert not math.isnan(r)


def test_host_tier_metrics_on_exposition(tmp_path):
    """The host phase's observability — the host_workers gauge (the
    native WAL engine's effective thread width; 1 under the Python
    engine) and the native_host gauge — appear on /metrics and the page
    passes the strict validator."""
    from rafting_tpu.core.types import EngineConfig
    from rafting_tpu.log import native_available
    from rafting_tpu.testkit.harness import LocalCluster

    native = native_available()
    cfg = EngineConfig(n_groups=4, n_peers=3, log_slots=16, batch=4,
                       max_submit=4, election_ticks=6, heartbeat_ticks=2,
                       rpc_timeout_ticks=5)
    c = LocalCluster(cfg, str(tmp_path), wal_shards=2, host_workers=2)
    try:
        c.wait_leader(0)
        c.tick(3)
        node = c.nodes[c.leader_of(0)]
        text = node.metrics.render_prometheus()
        validate_exposition(text)
        assert f"raft_host_workers {2 if native else 1}" in text
        assert f"raft_native_host {int(native)}" in text
    finally:
        c.close()


def test_ticks_on_exposition(tmp_path):
    """How many steps a node took, and how many of them were started for
    arriving work, is on /metrics from boot: ``ticks`` and
    ``ticks_on_arrival``, both counters.  Nothing on the page speaks of a
    second tick order."""
    from rafting_tpu.core.types import EngineConfig
    from rafting_tpu.testkit.harness import LocalCluster

    cfg = EngineConfig(n_groups=4, n_peers=3, log_slots=16, batch=4,
                       max_submit=4, election_ticks=6, heartbeat_ticks=2,
                       rpc_timeout_ticks=5)
    c = LocalCluster(cfg, str(tmp_path))
    try:
        node = c.nodes[0]
        boot = node.metrics.render_prometheus()
        validate_exposition(boot)
        assert "raft_ticks_total 0" in boot
        assert "raft_ticks_on_arrival_total 0" in boot
        c.tick(10)
        for _ in range(3):
            node.tick(arrival=True)
        text = node.metrics.render_prometheus()
        validate_exposition(text)
        assert "raft_ticks_total 13" in text
        assert "raft_ticks_on_arrival_total 3" in text
        assert node.metrics["ticks"] == node.ticks == 13
        assert node.timer_ticks == 10
        for gone in ("settled", "eager", "pipeline"):
            assert gone not in text
    finally:
        c.close()


def test_membership_counters_on_metrics(tmp_path):
    """ISSUE 7 satellite: the membership-change and leadership-transfer
    counters render on /metrics from boot (zeros included), move with a
    real §6 change, and the page still passes the strict validator."""
    from rafting_tpu.core.types import EngineConfig
    from rafting_tpu.testkit.harness import LocalCluster

    cfg = EngineConfig(n_groups=1, n_peers=3, log_slots=16, batch=4,
                       max_submit=4, election_ticks=6, heartbeat_ticks=2,
                       rpc_timeout_ticks=5)
    c = LocalCluster(cfg, str(tmp_path))
    try:
        c.wait_leader(0)
        c.submit_via_leader(0, b"x")   # waits out the readiness gate too
        node = c.nodes[c.leader_of(0)]
        text = node.metrics.render_prometheus()
        validate_exposition(text)
        for name in ("raft_membership_changes_entered_total",
                     "raft_membership_changes_committed_total",
                     "raft_membership_changes_aborted_total",
                     "raft_leadership_transfers_attempted_total",
                     "raft_leadership_transfers_succeeded_total",
                     "raft_timeout_now_sent_total"):
            assert name in text, f"{name} missing from exposition"
        # A real change moves entered/committed (joint + auto-leave).
        fut = node.change_membership(0, 0b011)
        for _ in range(400):
            if fut.done():
                break
            c.tick()
        fut.result()
        text = node.metrics.render_prometheus()
        validate_exposition(text)
        assert node.metrics["membership_changes_entered"] >= 2
        assert node.metrics["membership_changes_committed"] >= 2
    finally:
        c.close()


def test_heat_and_hop_metrics_on_exposition(tmp_path, monkeypatch):
    """ISSUE 18 satellite: the fleet-attribution counters, the
    heat_active_set gauge and the per-segment hop histograms all render
    on /metrics and the page passes the strict round-trip validator."""
    from rafting_tpu.core.types import EngineConfig
    from rafting_tpu.testkit.harness import LocalCluster

    monkeypatch.setenv("RAFT_LAT_SAMPLE", "1")
    cfg = EngineConfig(n_groups=4, n_peers=3, log_slots=32, batch=4,
                       max_submit=4, election_ticks=6, heartbeat_ticks=2,
                       rpc_timeout_ticks=5, heat=True)
    c = LocalCluster(cfg, str(tmp_path))
    try:
        c.wait_leader(0)
        for i in range(4):
            c.submit_via_leader(0, b"prom-%d" % i)
        c.tick(8)
        node = c.nodes[c.leader_of(0)]
        text = node.metrics.render_prometheus()
        validate_exposition(text)
        for name in ("raft_heat_appended_total", "raft_heat_sent_total",
                     "raft_heat_commits_total", "raft_heat_reads_total",
                     "raft_heat_active_set",
                     "raft_hop_tracked_total",
                     "raft_hop_requests_sent_total",
                     "raft_hop_echoes_total", "raft_hop_finalized_total",
                     "raft_hop_dropped_unknown_total"):
            assert name in text, f"{name} missing from exposition"
        for seg in ("leader_pack", "wire", "follower_fsync",
                    "ack_return", "quorum_wait"):
            assert f"raft_hop_{seg}_s_bucket" in text
        assert node.metrics["heat_appended"] >= 4
        assert node.metrics["hop_finalized"] >= 1
    finally:
        c.close()


def test_hop_metric_cardinality_bounded(tmp_path, monkeypatch):
    """Cardinality lint: per-peer hop histograms embed the peer in the
    metric NAME (the strict validator admits only the le label), so the
    hop family must stay at exactly 5 segments x (1 aggregate + at most
    P peer series) — a leaked per-span or per-group series would blow
    the scrape."""
    from rafting_tpu.core.types import EngineConfig
    from rafting_tpu.testkit.harness import LocalCluster

    monkeypatch.setenv("RAFT_LAT_SAMPLE", "1")
    cfg = EngineConfig(n_groups=4, n_peers=3, log_slots=32, batch=4,
                       max_submit=4, election_ticks=6, heartbeat_ticks=2,
                       rpc_timeout_ticks=5)
    c = LocalCluster(cfg, str(tmp_path))
    try:
        c.wait_leader(0)
        for i in range(6):
            c.submit_via_leader(0, b"card-%d" % i)
        c.tick(8)
        node = c.nodes[c.leader_of(0)]
        assert node._hops.counts["finalized"] >= 1
        segs = ("leader_pack", "wire", "follower_fsync", "ack_return",
                "quorum_wait")
        hop_hists = [n for n in node.metrics._histograms
                     if n.startswith("hop_")]
        assert hop_hists, "no hop histograms observed"
        P = cfg.n_peers
        allowed = {f"hop_{s}_s" for s in segs} | {
            f"hop_{s}_p{p}_s" for s in segs for p in range(P)}
        assert set(hop_hists) <= allowed
        assert len(hop_hists) <= len(segs) * (P + 1)
        # Aggregate + at least one peer series per segment exist.
        for s in segs:
            assert f"hop_{s}_s" in hop_hists
        assert any("_p" in n for n in hop_hists)
        validate_exposition(node.metrics.render_prometheus())
    finally:
        c.close()


def test_self_healing_counters_on_exposition(tmp_path):
    """ISSUE 20 satellite: the gray-failure plane's three counters —
    checkquorum step-downs, leadership evacuations, lease vetoes — are
    visible at ZERO from boot (an absent counter is indistinguishable
    from a disabled plane to an alerting rule), round-trip the strict
    validator, and the health gauges ride along when the plane is on.
    Cardinality lint: the plane adds exactly 3 counters + 3 gauges —
    nothing per-peer or per-group leaks into the registry."""
    from rafting_tpu.core.types import EngineConfig
    from rafting_tpu.testkit.harness import LocalCluster

    cfg = EngineConfig(n_groups=2, n_peers=3, log_slots=16, batch=4,
                       max_submit=4, election_ticks=6, heartbeat_ticks=2,
                       rpc_timeout_ticks=5)
    c = LocalCluster(cfg, str(tmp_path))
    try:
        c.wait_leader(0)
        c.tick(3)
        for node in c.nodes.values():
            text = node.metrics.render_prometheus()
            validate_exposition(text)
            assert "raft_checkquorum_stepdowns_total 0" in text
            assert "raft_leader_evacuations_total 0" in text
            assert "raft_lease_vetoes_total 0" in text
            # Health plane on by default: the three gauges exist.
            assert node.health is not None
            assert "raft_health_self_score" in text
            assert "raft_health_self_degraded" in text
            assert "raft_health_degraded_peers" in text
            # Cardinality lint: one series per name, no per-peer fanout.
            health_names = [n for n in node.metrics._counters
                            if n in ("checkquorum_stepdowns",
                                     "leader_evacuations",
                                     "lease_vetoes")]
            assert len(health_names) == 3
            fanout = [n for n in list(node.metrics._counters)
                      + list(node.metrics._gauges)
                      if n.startswith("health_") and any(
                          ch.isdigit() for ch in n)]
            assert not fanout, f"per-entity health series leaked: {fanout}"
    finally:
        c.close()


def test_read_barrier_and_ring_pressure_series_are_on_the_page(tmp_path):
    """PR 26's series from boot (0 before anything happened), and moving:
    counters ``read_barriers``, ``reads_coalesced``, ``ckpt_by_pressure``,
    ``compactions_by_pressure``; histogram ``read_batch_queries``; gauge
    ``log_ring_used_max``."""
    from rafting_tpu.core.types import EngineConfig
    from rafting_tpu.testkit.harness import LocalCluster

    cfg = EngineConfig(n_groups=1, n_peers=3, log_slots=16, batch=4,
                       max_submit=4, election_ticks=6, heartbeat_ticks=1)
    c = LocalCluster(cfg, str(tmp_path))
    try:
        text = c.nodes[0].metrics.render_prometheus()
        validate_exposition(text)
        for name in ("read_barriers", "reads_coalesced", "ckpt_by_pressure",
                     "compactions_by_pressure"):
            assert f"raft_{name}_total 0" in text, name
        node = c.nodes[c.wait_leader(0)]
        c.tick_until(lambda: node.is_ready(0), what="leader ready")
        reads = [node.read(0, b"q") for _ in range(4)]
        writes = []
        for _ in range(30):     # a 16-slot ring at one entry a tick
            writes.append(node.submit(0, b"w"))
            c.tick()
        assert all(f.done() and f.exception() is None
                   for f in reads + writes[:20])
        text = node.metrics.render_prometheus()
        validate_exposition(text)
        assert "raft_read_barriers_total 1" in text
        assert "raft_reads_coalesced_total 3" in text
        assert "raft_read_batch_queries_count 1" in text
        assert "# TYPE raft_log_ring_used_max gauge" in text
        assert node.metrics["ckpt_by_pressure"] >= 1
        assert node.metrics["compactions_by_pressure"] >= 1
        assert 0 < node.metrics._gauges["log_ring_used_max"] < 16
    finally:
        c.close()


def test_health_disabled_suppresses_gauges(tmp_path, monkeypatch):
    """RAFT_HEALTH=0 turns the scorecard plane off: no health gauges on
    the page (the counters stay — device 6c still steps down), and the
    node reports the plane disabled."""
    from rafting_tpu.core.types import EngineConfig
    from rafting_tpu.testkit.harness import LocalCluster

    monkeypatch.setenv("RAFT_HEALTH", "0")
    cfg = EngineConfig(n_groups=1, n_peers=3, log_slots=16, batch=4,
                       max_submit=4, election_ticks=6, heartbeat_ticks=2,
                       rpc_timeout_ticks=5)
    c = LocalCluster(cfg, str(tmp_path))
    try:
        c.wait_leader(0)
        c.tick(2)
        node = c.nodes[c.leader_of(0)]
        assert node.health is None
        assert node.health_snapshot() == {"enabled": False}
        text = node.metrics.render_prometheus()
        validate_exposition(text)
        assert "raft_checkquorum_stepdowns_total 0" in text
        assert "raft_health_self_score" not in text
    finally:
        c.close()
