"""Gray-failure scorecards + leadership evacuation (ISSUE 20 host tier).

Unit coverage for utils/health.py (windowed delta-quantile peer scoring,
decay-heal, stale-contact and self penalties, the env gate) and runtime
coverage for the evacuation loop: a self-degraded leader hands its
groups to the healthiest voter, refuses routed traffic with the typed
LeadershipEvacuatedError (carrying the target hint), and reports the
whole story on /healthz.
"""

import json

import numpy as np
import pytest

from rafting_tpu.core.types import LEADER, EngineConfig
from rafting_tpu.utils.health import (
    HealthRegistry, PEER_SEGMENTS, health_from_env,
)
from rafting_tpu.utils.metrics import Metrics


# --------------------------------------------------------------- unit tier --


def _feed(metrics: Metrics, seg: str, peer: int, v: float, n: int) -> None:
    for _ in range(n):
        metrics.observe(f"hop_{seg}_p{peer}_s", v)


def test_slow_peer_scored_against_fleet_median():
    """One peer whose windowed hop p50 sits >= slow_ratio x the fleet
    median accrues penalty; fleet-typical peers stay clean.  Needs >= 3
    remote peers — with two, the median IS the midpoint and no single
    peer can sit 4x above it."""
    m = Metrics()
    h = HealthRegistry(4, 0, half_life_ticks=1000.0)
    for p in (1, 2):
        _feed(m, "wire", p, 0.001, 10)
    _feed(m, "wire", 3, 2.0, 10)        # ~2000x slower than the fleet
    h.ingest(1, m)                      # baseline window (discarded)
    for p in (1, 2):
        _feed(m, "wire", p, 0.001, 10)
    _feed(m, "wire", 3, 2.0, 10)
    h.ingest(2, m)
    assert h.score[3] > 0.0
    assert h.score[1] == 0.0 and h.score[2] == 0.0
    # Repeated slow windows accumulate to degraded.
    for t in range(3, 10):
        for p in (1, 2):
            _feed(m, "wire", p, 0.001, 10)
        _feed(m, "wire", 3, 2.0, 10)
        h.ingest(t, m)
    assert 3 in h.degraded_peers()
    assert h.degraded_peers() == {3}


def test_scores_decay_back_to_healthy():
    h = HealthRegistry(3, 0, half_life_ticks=16.0, degraded_after=4.0)
    h.score[1] = 8.0
    h.self_score = 8.0
    h._score_tick = 0
    h.tick = 0
    assert 1 in h.degraded_peers() and h.self_degraded()
    # Two half-lives with no fresh penalties: 8 -> 2, under threshold.
    h.ingest(32, Metrics())
    assert h.degraded_peers() == set()
    assert not h.self_degraded()


def test_stale_contact_penalty_from_quorum_lanes():
    h = HealthRegistry(3, 0, half_life_ticks=1e6,
                       contact_stale_ticks=10)
    h.note_contact(np.array([0, 50, 50], np.int64))
    h.ingest(55, Metrics())             # ages 5: fresh, no penalty
    assert h.score[1] == 0.0
    h.ingest(90, Metrics())             # ages 40: both peers stale
    assert h.score[1] > 0.0 and h.score[2] > 0.0
    # note_contact only moves forward (max-fold), never backward.
    h.note_contact(np.array([0, 10, 95], np.int64))
    assert int(h.last_contact[1]) == 50
    assert int(h.last_contact[2]) == 95


def test_self_penalties_fold_storage_and_admission_signals():
    h = HealthRegistry(3, 1, half_life_ticks=1e6)
    h.ingest(1, Metrics(), io_slow=True, backpressure=True,
             poisoned_stripes=2, admission_level=0.5)
    # 1 (io) + 1 (backpressure) + 2*2 (new stripes) + 0.5 (admission)
    assert h.self_score == pytest.approx(6.5)
    # Stripe count is a high-water mark: re-reporting the same two
    # poisoned stripes adds nothing.
    h.ingest(2, Metrics(), poisoned_stripes=2)
    assert h.self_score == pytest.approx(6.5)
    assert h.self_degraded()


def test_a_peer_never_reached_scores_nothing_and_a_flapping_link_does():
    """The transport tells a peer that is not up yet
    (``connects_refused_total``: a node that boots before its peers) from a
    link that flaps (``reconnects_total``); only the second is a sickness
    of this node's own.  A cold boot of 10,000 lanes held the first member
    alone for 20 s and was evacuated on its refused connects (PERF.md,
    PR 31)."""
    h = HealthRegistry(3, 0, half_life_ticks=1e6)
    m = Metrics()
    m["connects_refused_total"] += 40
    h.ingest(1, m)
    assert h.self_score == 0.0 and not h.self_degraded()
    m["reconnects_total"] += 8
    h.ingest(2, m)
    assert h.self_score == pytest.approx(4.0)
    assert h.self_degraded()


def test_snapshot_shape_and_evacuation_audit():
    h = HealthRegistry(3, 0)
    h.note_contact(np.array([0, 7, 0], np.int64))
    h.tick = 12
    h.note_evacuation(4, 2)
    s = h.snapshot()
    assert s["self_degraded"] is False
    assert len(s["peers"]) == 3
    assert s["peers"][0]["self"] is True
    assert s["peers"][1]["last_contact_tick"] == 7
    assert s["peers"][1]["contact_age_ticks"] == 5
    assert s["peers"][2]["last_contact_tick"] is None
    assert s["evacuations"] == 1
    assert s["recent_evacuations"][0] == {"tick": 12, "group": 4,
                                          "target": 2}
    json.dumps(s)                       # HTTP-safe: plain JSON types


def test_health_env_gate(monkeypatch):
    for off in ("0", "false", "no", "off"):
        monkeypatch.setenv("RAFT_HEALTH", off)
        assert health_from_env(3, 0) is None
    monkeypatch.setenv("RAFT_HEALTH", "1")
    monkeypatch.setenv("RAFT_HEALTH_HALF_LIFE", "64")
    monkeypatch.setenv("RAFT_HEALTH_DEGRADED", "2.5")
    monkeypatch.setenv("RAFT_HEALTH_SLOW_RATIO", "8")
    monkeypatch.setenv("RAFT_HEALTH_STALE_TICKS", "32")
    h = health_from_env(3, 1)
    assert (h.half_life, h.degraded_after, h.slow_ratio,
            h.contact_stale_ticks) == (64.0, 2.5, 8.0, 32)


def test_peer_segments_exclude_self_blame():
    # leader_pack is our own packing time and quorum_wait blames the
    # quorum — neither may indict a single peer.
    assert "leader_pack" not in PEER_SEGMENTS
    assert "quorum_wait" not in PEER_SEGMENTS


# ------------------------------------------------------------ runtime tier --


def _cfg(**kw):
    base = dict(n_groups=3, n_peers=3, log_slots=32, batch=8,
                max_submit=8, election_ticks=8, heartbeat_ticks=2,
                rpc_timeout_ticks=6)
    base.update(kw)
    return EngineConfig(**base)


def test_degraded_leader_evacuates_and_refuses_typed(tmp_path):
    """The whole host tier end to end: force one leader self-degraded,
    watch the evacuation loop transfer its groups away, the counter and
    audit move, routed traffic bounce with LeadershipEvacuatedError
    carrying the landing target, and /healthz carry the peers block."""
    from rafting_tpu.api.anomaly import (
        LeadershipEvacuatedError, evac_target_of,
    )
    from rafting_tpu.testkit.harness import LocalCluster

    c = LocalCluster(_cfg(), str(tmp_path), seed=3)
    try:
        for g in range(3):
            c.wait_leader(g)
        victim_id = c.leader_of(0)
        victim = c.nodes[victim_id]
        assert victim.health is not None
        # Poison the self scorecard hard enough that decay is moot.
        victim.health.self_score = 1e6
        victim._evac_next_ok = 0
        for _ in range(300):
            c.tick()
            if victim._evacuated:
                break
        assert victim._evacuated, "degraded leader never evacuated"
        assert victim.metrics._counters["leader_evacuations"] >= 1
        g, (target, expiry) = next(iter(victim._evacuated.items()))
        assert target != victim_id
        assert expiry > victim.ticks
        # Routed traffic during the re-point window: typed refusal with
        # the landing target as hint.
        c.tick(3)
        if victim.h_role[g] != LEADER:
            fut = victim.submit(g, b"bounce")
            assert fut.done()
            exc = fut.exception()
            assert isinstance(exc, LeadershipEvacuatedError)
            assert evac_target_of(exc) == target
        # Audit trail: registry + snapshot + healthz peers block.
        snap = victim.health_snapshot()
        assert snap["evacuations"] >= 1
        assert str(g) in {str(k) for k in snap["evacuated_groups"]}
        from rafting_tpu.runtime.obsrv import ObservabilityServer
        srv = ObservabilityServer(victim)
        try:
            hz = srv.healthz()
            assert hz["peers"]["self_degraded"] is True
            assert hz["peers"]["evacuations"] >= 1
        finally:
            srv.close()
    finally:
        c.close()


def test_evacuation_never_lands_on_degraded_peer(tmp_path):
    """The target choice skips peers the scorecard marks degraded: with
    one of the two candidate voters branded, the evacuation must land on
    the other."""
    from rafting_tpu.testkit.harness import LocalCluster

    c = LocalCluster(_cfg(), str(tmp_path), seed=5)
    try:
        for g in range(3):
            c.wait_leader(g)
        victim_id = c.leader_of(0)
        victim = c.nodes[victim_id]
        others = [i for i in range(3) if i != victim_id]
        branded, clean = others[0], others[1]
        victim.health.score[branded] = 1e6
        victim.health.self_score = 1e6
        victim._evac_next_ok = 0
        for _ in range(300):
            c.tick()
            if victim._evacuated:
                break
        assert victim._evacuated
        targets = {t for (t, _) in victim._evacuated.values()}
        assert targets == {clean}
    finally:
        c.close()


def test_rebalancer_evacuate_skips_degraded(tmp_path):
    """The admin-driven twin (admin/rebalance.py evacuate): consults
    every node's scorecard and never hands a group to a branded peer."""
    from rafting_tpu.admin.rebalance import Rebalancer
    from rafting_tpu.testkit.harness import LocalCluster

    c = LocalCluster(_cfg(), str(tmp_path), seed=9)
    try:
        for g in range(3):
            c.wait_leader(g)
        source = c.leader_of(1)
        others = [i for i in range(3) if i != source]
        branded, clean = others[0], others[1]
        c.nodes[source].health.score[branded] = 1e6
        # The transfer preflight refuses until the readiness gate warms
        # (quorum recently heard); give the fresh leader a few ticks.
        for _ in range(200):
            if bool(c.nodes[source].h_ready[1]):
                break
            c.tick()
        rb = Rebalancer(c.nodes, step=c.tick)
        moved = rb.evacuate(source, groups=[1])
        assert moved == [1]
        c.tick(3)
        assert c.leader_of(1) == clean
    finally:
        c.close()


# --------------------------------------------------------- post-mortem CLI --


def _snapshot_with_timeline():
    m = Metrics()
    h = HealthRegistry(3, 0, half_life_ticks=1e6)
    h.sample_every = 1
    h.ingest(1, m)
    h.ingest(2, m, io_slow=True, backpressure=True)
    h.ingest(3, m, io_slow=True, backpressure=True, poisoned_stripes=1)
    h.note_contact(np.array([0, 3, 3], np.int64))
    h.note_evacuation(2, 1)
    return h.snapshot()


def test_health_report_cli_renders_all_shapes(tmp_path, capsys):
    """tools/health_report.py is the engine-free post-mortem half: it
    accepts a bare snapshot, a /healthz capture and a save_dump-style
    meta wrapper, gzip-transparent, and renders peers + timeline +
    evacuation audit."""
    import sys as _sys
    _sys.path.insert(0, "tools")
    import health_report

    snap = _snapshot_with_timeline()
    assert snap["timeline"], "registry recorded no timeline samples"

    bare = tmp_path / "health.json"
    bare.write_text(json.dumps(snap))
    assert health_report.main([str(bare)]) == 0
    out = capsys.readouterr().out
    assert "peer 1" in out and "evacuations: 1" in out
    assert "timeline" in out and "group 2" in out and "-> peer 1" in out
    # The self-degraded marker fires once the score crosses threshold.
    assert "DEGRADED" in out

    # /healthz capture (health under "peers") + gzip + sibling lookup.
    import gzip as _gzip
    hz = tmp_path / "healthz.json.gz"
    with _gzip.open(hz, "wt") as f:
        json.dump({"ok": True, "node_id": 0, "peers": snap}, f)
    assert health_report.main([str(hz)]) == 0
    assert "evacuations: 1" in capsys.readouterr().out
    assert health_report.main([str(hz)[:-3]]) == 0   # bare -> .gz sibling
    capsys.readouterr()

    # save_dump-style wrapper (health under _meta.health) + --json.
    dump = tmp_path / "dump.json"
    dump.write_text(json.dumps({"_meta": {"health": snap}, "lanes": {}}))
    assert health_report.main([str(dump), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["evacuations"] == 1 and doc["timeline"]

    # A document with no scorecards is a typed failure, not a traceback.
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"ok": True}))
    assert health_report.main([str(empty)]) == 2


def test_health_report_peer_filter(tmp_path, capsys):
    import sys as _sys
    _sys.path.insert(0, "tools")
    import health_report

    snap = _snapshot_with_timeline()
    p = tmp_path / "h.json"
    p.write_text(json.dumps(snap))
    assert health_report.main([str(p), "--peer", "1"]) == 0
    out = capsys.readouterr().out
    assert "p1" in out and "p2" not in out
