"""HTTP observability plane: /metrics, /healthz, /timeline (ISSUE 3).

Acceptance: all three endpoints served in-process, the metrics page
passes the strict exposition validator, and group timelines agree with
the Metrics counters the same drain derived them from.
"""

import json
import urllib.request

import numpy as np
import pytest

from rafting_tpu.core.types import EngineConfig, LEADER
from rafting_tpu.testkit.harness import LocalCluster
from rafting_tpu.utils.metrics import validate_exposition

CFG = EngineConfig(n_groups=4, n_peers=3, log_slots=32, batch=4,
                   max_submit=4, election_ticks=6, heartbeat_ticks=2,
                   rpc_timeout_ticks=5, trace_depth=32)


def _get(port: int, path: str):
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                    timeout=5) as r:
            return r.status, r.headers.get("Content-Type", ""), r.read()
    except urllib.error.HTTPError as e:   # 4xx/5xx still carry a body
        return e.code, e.headers.get("Content-Type", ""), e.read()


@pytest.fixture
def cluster(tmp_path):
    c = LocalCluster(CFG, str(tmp_path))
    try:
        c.wait_leader(0)
        c.tick(10)
        for g in range(CFG.n_groups):
            c.wait_leader(g)
        c.submit_via_leader(0, b"obsrv-probe")
        yield c
    finally:
        c.close()


def test_endpoints_serve_and_validate(cluster):
    c = cluster
    lead = c.leader_of(0)
    node = c.nodes[lead]
    srv = node.start_observability()
    assert srv.port > 0
    # Idempotent attach: a second call returns the same server.
    assert node.start_observability() is srv

    # /metrics: strict exposition-format validity + live counters.
    status, ctype, body = _get(srv.port, "/metrics")
    assert status == 200 and ctype.startswith("text/plain")
    text = body.decode()
    validate_exposition(text)
    assert "raft_elections_total" in text
    assert "raft_tick_latency_s_bucket" in text

    # /healthz: the peer-health gate state.
    status, ctype, body = _get(srv.port, "/healthz")
    assert status == 200 and ctype.startswith("application/json")
    doc = json.loads(body)
    assert doc["ok"] is True
    assert doc["node_id"] == lead
    assert doc["groups_active"] == CFG.n_groups
    assert doc["groups_led"] == int((node.h_role == LEADER).sum())
    assert doc["groups_led"] >= 1
    assert 0 <= doc["groups_ready"] <= doc["groups_led"]
    assert doc["ticks"] == node.ticks

    # /timeline: decoded flight-recorder events, consistent with the
    # labeled metrics the same drain produced.
    won = 0
    for g in range(CFG.n_groups):
        status, _, body = _get(srv.port, f"/timeline?group={g}")
        assert status == 200
        doc = json.loads(body)
        assert doc["group"] == g and doc["trace_depth"] == 32
        for ev in doc["events"]:
            assert set(ev) == {"seq", "tick", "event", "kind", "term",
                               "aux"}
        won += sum(ev["event"] == "BECAME_LEADER"
                   for ev in doc["events"])
    assert won == node.metrics["elections_won"]
    assert won >= 1
    # The timeline-derived election count agrees with the cause split.
    assert (node.metrics["elections_cause_timer"]
            + node.metrics["elections_cause_prevote"]) >= won

    # Error paths.
    status, _, body = _get(srv.port, "/timeline?group=999")
    assert status == 400
    status, _, body = _get(srv.port, "/nope")
    assert status == 404
    assert "/metrics" in json.loads(body)["paths"]


def test_close_shuts_server_down(tmp_path):
    c = LocalCluster(CFG, str(tmp_path))
    try:
        node = c.nodes[0]
        srv = node.start_observability()
        port = srv.port
        _get(port, "/healthz")
    finally:
        c.close()
    with pytest.raises(OSError):
        _get(port, "/healthz")


def test_timeline_matches_leader_churn_under_partition(tmp_path):
    """Leader churn derived from the timeline equals the labeled metric,
    and a forced re-election shows up as decoded events."""
    c = LocalCluster(CFG, str(tmp_path))
    try:
        lead = c.wait_leader(0)
        # Isolate the leader so another node wins group 0.
        c.net.partition([[lead], [i for i in c.nodes if i != lead]])
        c.tick_until(
            lambda: any(i != lead and c.nodes[i].h_role[0] == LEADER
                        for i in c.nodes),
            300, "re-election after isolating the leader")
        c.net.heal()
        c.tick(10)
        total_wins = 0
        total_churn = 0
        for i, n in c.nodes.items():
            srv = n.start_observability()
            wins = {}
            for g in range(CFG.n_groups):
                _, _, body = _get(srv.port, f"/timeline?group={g}")
                evs = json.loads(body)["events"]
                wins[g] = sum(e["event"] == "BECAME_LEADER" for e in evs)
            assert sum(wins.values()) == n.metrics["elections_won"]
            total_wins += sum(wins.values())
            total_churn += int(n.metrics["leader_churn"])
        # Group 0 elected at least twice across the cluster.
        assert total_wins >= 2
        assert total_churn >= 0
    finally:
        c.close()


CFG_HEAT = EngineConfig(n_groups=4, n_peers=3, log_slots=32, batch=4,
                        max_submit=4, election_ticks=6,
                        heartbeat_ticks=2, rpc_timeout_ticks=5,
                        trace_depth=32, heat=True)


def test_heatmap_and_hops_endpoints(tmp_path, monkeypatch):
    """The fleet-attribution endpoints (ISSUE 18): /heatmap serves the
    decaying registry document, /hops the hop tracer's, and /latency
    carries the hops subdocument when tracing is live."""
    monkeypatch.setenv("RAFT_LAT_SAMPLE", "1")
    c = LocalCluster(CFG_HEAT, str(tmp_path))
    try:
        c.wait_leader(0)
        for i in range(4):
            c.submit_via_leader(0, b"attr-%d" % i)
        c.tick(8)
        node = c.nodes[c.leader_of(0)]
        srv = node.start_observability()

        status, ctype, body = _get(srv.port, "/heatmap")
        assert status == 200 and ctype.startswith("application/json")
        doc = json.loads(body)
        assert doc["enabled"] is True
        assert doc["groups"] == CFG_HEAT.n_groups
        assert doc["totals"]["appended"] >= 4
        assert doc["active_set"] >= 1
        assert any(t["group"] == 0 for t in doc["top"])
        # k caps the top list.
        _, _, body = _get(srv.port, "/heatmap?k=1")
        assert len(json.loads(body)["top"]) == 1

        status, ctype, body = _get(srv.port, "/hops")
        assert status == 200 and ctype.startswith("application/json")
        doc = json.loads(body)
        assert doc["enabled"] is True
        assert doc["counts"]["finalized"] >= 1
        assert doc["segments"]

        # /latency embeds the same hops document.
        status, _, body = _get(srv.port, "/latency")
        assert status == 200
        assert json.loads(body)["hops"]["counts"]["finalized"] >= 1
    finally:
        c.close()


def test_typed_4xx_errors(cluster):
    """Hardened error paths (ISSUE 18 satellite): malformed params and
    unknown paths answer with typed JSON, never a traceback or a bare
    status line."""
    srv = cluster.nodes[cluster.leader_of(0)].start_observability()

    # Non-integer param → 400 bad_param.
    for path in ("/timeline?group=abc", "/heatmap?k=abc"):
        status, ctype, body = _get(srv.port, path)
        assert status == 400 and ctype.startswith("application/json")
        doc = json.loads(body)
        assert doc["error"] == "bad_param" and "detail" in doc

    # Out-of-range param → 400 param_out_of_range.
    for path in ("/timeline?group=999", "/timeline?group=-1",
                 "/heatmap?k=0", "/heatmap?k=99999"):
        status, _, body = _get(srv.port, path)
        assert status == 400
        assert json.loads(body)["error"] == "param_out_of_range"

    # Unknown path → 404 unknown_path listing the served paths.
    status, _, body = _get(srv.port, "/nope")
    assert status == 404
    doc = json.loads(body)
    assert doc["error"] == "unknown_path"
    assert "/heatmap?k=N" in doc["paths"] and "/hops" in doc["paths"]


def test_heatmap_disabled_document(cluster):
    """A heatless config still serves /heatmap — enabled: false, so
    dashboards can probe capability without a 404."""
    srv = cluster.nodes[cluster.leader_of(0)].start_observability()
    status, _, body = _get(srv.port, "/heatmap")
    assert status == 200
    assert json.loads(body) == {"enabled": False}
