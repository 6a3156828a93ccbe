"""Cross-node hop attribution plane (ISSUE 18).

The hop tracer decomposes a sampled entry's ``send_commit`` phase into
per-peer segments (leader_pack / wire / follower_fsync / ack_return /
quorum_wait) using only durations measured on a single clock.  Checked
here: the HOPS wire codec round-trips, coverage scanning queues exactly
one request per (span, peer), follower durability stamping refuses
un-fsynced tails, crashed / outcome-unknown spans NEVER fabricate hop
latency (they drop, counted), and through a live serial-mode cluster
the per-hop segments reconcile with the span's end-to-end send→commit.
"""

import time

import numpy as np
import pytest

from rafting_tpu.core.types import EngineConfig
from rafting_tpu.transport import codec
from rafting_tpu.testkit.harness import LocalCluster
from rafting_tpu.utils.latency import (
    COMMITTED, HOP_ECHO, HOP_REQUEST, HOP_SEGMENTS, SENT, HopTracer,
    Span, hops_from_env,
)
from rafting_tpu.utils.metrics import Metrics

CFG = EngineConfig(n_groups=4, n_peers=3, log_slots=32, batch=4,
                   max_submit=4, election_ticks=6, heartbeat_ticks=2,
                   rpc_timeout_ticks=5)


def _span(seq=0, group=1, idx=3):
    sp = Span(seq, "w", 0)
    sp.group, sp.idx, sp.tick = group, idx, 7
    return sp


# ------------------------------------------------------- wire codec --


def test_pack_hops_roundtrip():
    reqs = [(1, 0, 5, 123456789), (2, 3, 1, 987654321)]
    frames = list(codec.FrameReader().feed(
        codec.pack_hops(HOP_REQUEST, 2, reqs)))
    assert len(frames) == 1 and frames[0][0] == codec.HOPS
    direction, origin, records = codec.unpack_hops(frames[0][1])
    assert (direction, origin) == (HOP_REQUEST, 2)
    assert records == reqs

    echoes = [(7, 111, 222, 333, 444)]
    _, body = next(iter(codec.FrameReader().feed(
        codec.pack_hops(HOP_ECHO, 1, echoes))))
    direction, origin, records = codec.unpack_hops(body)
    assert (direction, origin, records) == (HOP_ECHO, 1, echoes)

    # Truncated body → typed IOError, not a struct traceback.
    with pytest.raises(IOError):
        codec.unpack_hops(codec.pack_hops(HOP_REQUEST, 0, reqs)[
            codec._HDR.size:-3])


def test_hops_frames_concatenate_with_msgs():
    """HOPS frames ride the same blob as a MSGS frame; the reader
    yields both (the piggyback contract _flush_sends relies on)."""
    blob = codec.pack_hops(HOP_REQUEST, 0, [(1, 2, 3, 4)]) \
        + codec.pack_hops(HOP_ECHO, 0, [(1, 4, 5, 6, 7)])
    kinds = [ftype for ftype, _ in codec.FrameReader().feed(blob)]
    assert kinds == [codec.HOPS, codec.HOPS]


# -------------------------------------------------- tracer mechanics --


def test_scan_outbox_queues_once_per_peer():
    tr = HopTracer(node_id=0, n_peers=3)
    sp = _span(group=1, idx=3)
    tr.track(sp)
    P, G = 3, 4
    valid = np.zeros((P, G), bool)
    prev = np.zeros((P, G), np.int32)
    n = np.zeros((P, G), np.int32)
    # Peer 1 covers idx 3 (prev=2, n=2 → (2, 4]); peer 2 does not
    # (prev=3 means idx 3 already replicated — not in this frame).
    valid[1, 1] = valid[2, 1] = True
    prev[1, 1], n[1, 1] = 2, 2
    prev[2, 1], n[2, 1] = 3, 1
    tr.scan_outbox(valid, prev, n)
    assert set(tr._live[1].sent) == {1}
    assert tr._live[1].t_pack > 0
    # Self-coverage never queues (peer 0 IS the leader).
    valid[0, 1], prev[0, 1], n[0, 1] = True, 0, 8
    tr.scan_outbox(valid, prev, n)
    assert 0 not in tr._live[1].sent
    # Retransmit coverage does not re-request: first coverage wins.
    tr.scan_outbox(valid, prev, n)
    out = tr.take_out(1)
    assert out is not None
    reqs, echoes = out
    assert len(reqs) == 1 and echoes == []
    assert reqs[0][:3] == (1, 1, 3)
    assert tr._live[1].sent[1] > 0   # send time stamped at take_out
    assert tr.take_out(1) is None


def test_fold_foreign_stamps_only_durable_tails():
    tr = HopTracer(node_id=1, n_peers=3)
    t0 = time.perf_counter_ns()
    tr.recv_requests(0, [(9, 2, 5, t0)], t0)
    # Tail below idx: neither staged nor echoed.
    tr.fold_foreign(np.asarray([0, 0, 4, 0]), fsynced=True)
    assert tr._out_echo == {} and len(tr._foreign) == 1
    # Tail covers idx but only staged (pre-barrier): still no echo.
    tr.fold_foreign(np.asarray([0, 0, 5, 0]), fsynced=False)
    assert tr._out_echo == {} and tr._foreign[0].d_staged > 0
    assert tr._foreign[0].d_fsync == 0
    # Post-barrier: fsync stamped, echo queued to the origin.
    tr.fold_foreign(np.asarray([0, 0, 5, 0]), fsynced=True)
    assert len(tr._out_echo[0]) == 1 and not tr._foreign
    f = tr._out_echo[0][0]
    assert f.d_fsync >= f.d_staged > 0
    reqs, echoes = tr.take_out(0)
    assert reqs == [] and len(echoes) == 1
    hop_id, t_send, d_staged, d_fsync, d_echo = echoes[0]
    assert hop_id == 9 and t_send == t0
    assert d_echo >= d_fsync >= d_staged > 0


def test_foreign_hop_expires_never_fabricates():
    """A context whose entry never becomes durable here (conflict
    truncation, lane purge) expires by TTL — no echo, counted."""
    tr = HopTracer(node_id=1, n_peers=3, ttl_s=1.0)
    tr.recv_requests(0, [(5, 0, 99, 1)],
                     time.perf_counter_ns() - int(2e9))
    tr.fold_foreign(np.asarray([0, 0, 0, 0]), fsynced=True)
    assert not tr._foreign and tr._out_echo == {}
    assert tr.counts["foreign_expired"] == 1


def test_crashed_and_unknown_spans_drop_without_latency():
    """The no-fabrication rule: a span that settled with any outcome
    other than ok-with-commit-stamp drops its hop context unobserved,
    and an orphan echo (leader crash forgot the context) only counts."""
    m = Metrics()
    tr = HopTracer(node_id=0, n_peers=3)
    dead = _span(seq=1, group=0, idx=2)
    tr.track(dead)
    # Give it full coverage + an echo so only the outcome gate stands
    # between it and the histograms.
    valid = np.ones((3, 4), bool)
    prev = np.zeros((3, 4), np.int32)
    n = np.full((3, 4), 8, np.int32)
    tr.scan_outbox(valid, prev, n)
    tr.take_out(1)
    tr.recv_echoes(1, [(1, 1, 10, 20, 30)], time.perf_counter_ns())
    dead.outcome = "unknown"          # crashed in the fsync window
    tr.fold(m)
    assert tr.counts["dropped_unknown"] == 1
    assert tr.counts["finalized"] == 0
    assert not tr._live
    for seg in HOP_SEGMENTS:
        assert f"hop_{seg}_s" not in m._histograms
    # Orphan echo: no context → counted, never observed.
    tr.recv_echoes(1, [(777, 1, 10, 20, 30)], time.perf_counter_ns())
    tr.fold(m)
    assert tr.counts["echo_orphan"] == 1
    assert m["hop_dropped_unknown"] == 1


def test_ok_span_without_commit_stamp_drops():
    m = Metrics()
    tr = HopTracer(node_id=0, n_peers=3)
    sp = _span(seq=2)
    tr.track(sp)
    sp.outcome = "ok"                 # settled, but COMMITTED never hit
    tr.fold(m)
    assert tr.counts["dropped_unknown"] == 1
    for seg in HOP_SEGMENTS:
        assert f"hop_{seg}_s" not in m._histograms


def test_hops_from_env(monkeypatch):
    monkeypatch.setenv("RAFT_HOP_TRACE", "0")
    assert hops_from_env(0, 3) is None
    monkeypatch.setenv("RAFT_HOP_TRACE", "off")
    assert hops_from_env(0, 3) is None
    monkeypatch.delenv("RAFT_HOP_TRACE")
    tr = hops_from_env(2, 5)
    assert tr is not None and tr.node_id == 2 and tr.n_peers == 5
    monkeypatch.setenv("RAFT_HOP_TTL_S", "7")
    assert hops_from_env(0, 3)._ttl_ns == int(7e9)


# ------------------------------------------- live reconciliation ----


def test_cluster_hop_reconciliation_serial(tmp_path, monkeypatch):
    """Rate-1 sampling through a serial-mode cluster: every committed
    span finalizes a hop decomposition whose per-peer segment sum
    reconciles with the span's end-to-end send→commit.  Serial mode
    keeps pack and flush in the same host phase, so the only slack is
    the intra-tick t_pack→SENT sliver (the WAL stage+fsync)."""
    monkeypatch.setenv("RAFT_LAT_SAMPLE", "1")
    c = LocalCluster(CFG, str(tmp_path))
    try:
        c.wait_leader(0)
        for i in range(6):
            c.submit_via_leader(0, b"hop-%d" % i)
        c.tick(8)
        node = c.nodes[c.leader_of(0)]
        hops = node._hops
        assert hops is not None
        assert hops.counts["finalized"] >= 6
        assert hops.counts["dropped_unknown"] == 0
        traces = [t for t in hops.recent if t["group"] == 0]
        assert len(traces) >= 6
        for t in traces:
            sc = t["send_commit_s"]
            assert sc > 0.0
            assert len(t["peers"]) >= 1
            for p, segs in t["peers"].items():
                assert p != node.node_id
                assert set(segs) == set(HOP_SEGMENTS)
                assert all(v >= 0.0 for v in segs.values())
                total = sum(segs.values())
                # total telescopes to commit−pack; send_commit is
                # commit−send with pack ≤ send in the same host phase,
                # so total ≥ sc −ε and within the slack of one tick's
                # stage+fsync.
                assert total == pytest.approx(
                    sc, rel=0.05, abs=0.025), (t, total)
        # Followers stamped and echoed: foreign bookkeeping drained.
        for i, n in c.nodes.items():
            h = n._hops
            assert not h._foreign or True
            assert h.counts["foreign_expired"] == 0
        # The /hops document renders from the same registry.
        doc = node.hops_snapshot()
        assert doc["enabled"] is True
        assert doc["counts"]["finalized"] >= 6
        for seg in HOP_SEGMENTS:
            assert doc["segments"][seg]["all"]["count"] >= 6
            assert doc["segments"][seg]["peers"]
    finally:
        c.close()


def test_hop_blind_receiver_ignores_hops_frames(tmp_path, monkeypatch):
    """RAFT_HOP_TRACE=0 on the whole cluster: no tracer exists, HOPS
    frames are never sent, and the run commits normally (the sideband
    is strictly additive)."""
    monkeypatch.setenv("RAFT_HOP_TRACE", "0")
    monkeypatch.setenv("RAFT_LAT_SAMPLE", "1")
    c = LocalCluster(CFG, str(tmp_path))
    try:
        c.wait_leader(0)
        for n in c.nodes.values():
            assert n._hops is None
        for i in range(3):
            c.submit_via_leader(0, b"blind-%d" % i)
        node = c.nodes[c.leader_of(0)]
        assert node.latency_snapshot().get("hops") is None
        assert node.hops_snapshot() == {"enabled": False}
    finally:
        c.close()
