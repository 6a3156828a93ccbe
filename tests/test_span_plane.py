"""The operation span plane read end to end (utils/latency.py): reads
stamped submitted -> offered -> served with the wait for the group's
offer slot split out, every stamp carrying the node's tick number, the
write phases grouped as the benchmark reads them, and the instruments'
own reproduction of the standing inbox backlog (PERF.md section 6, PR 23
third session).  Manual ticks; assertions on tick numbers and on sums
over the same spans, none on a wall-clock gate."""

import pytest

from rafting_tpu.core.types import EngineConfig, LEADER
from rafting_tpu.testkit.harness import LocalCluster
from rafting_tpu.utils.latency import (
    ACKED, COMMITTED, OFFERED, SENT, SERVED, SUBMITTED,
)

CFG = EngineConfig(n_groups=8, n_peers=3, heartbeat_ticks=1,
                   election_ticks=10)

# The four groups benchmark/layer_metrics/commit_*_ms.py read.
WRITE_GROUPS = {
    "queue": ("submit_offer",),
    "persist": ("offer_stage", "stage_fsync"),
    "replicate": ("fsync_send", "send_commit"),
    "release": ("commit_apply", "apply_ack"),
}


def _settle(c, futs, rounds=60):
    for _ in range(rounds):
        if all(f.done() for f in futs):
            break
        c.tick()
    assert all(f.done() and f.exception() is None for f in futs)
    c.tick(2)       # the retired rings are harvested at the tick's tail


@pytest.mark.parametrize("shape", ["packed", "columns"])
def test_read_spans_split_queue_from_confirm(tmp_path, monkeypatch,
                                             take_shape, shape):
    monkeypatch.setenv("RAFT_LAT_SAMPLE", "1")
    take_shape(CFG, shape)
    c = LocalCluster(CFG, str(tmp_path), seed=3)
    try:
        lead = c.wait_leader(0)
        c.submit_via_leader(0, b"w")
        c.tick(4)
        node = c.nodes[lead]
        # Two batches of one group between two ticks: the group's offer
        # slot takes every batch that waits when it is won, so both are
        # offered by the same tick, under one barrier.
        barriers = node.metrics["read_barriers"]
        _settle(c, [node.read(0, b"q1"), node.read(0, b"q2")])
        assert node.metrics["read_barriers"] == barriers + 1
        first, second = sorted(
            (sp for sp in node._lat.recent if sp.kind == "r"),
            key=lambda sp: sp.seq)
        for sp in (first, second):
            assert sp.outcome == "ok"
            assert 0.0 < sp.t[SUBMITTED] <= sp.t[OFFERED] <= sp.t[SERVED]
            assert 0 <= sp.n[SUBMITTED] <= sp.n[OFFERED] <= sp.n[SERVED]
            assert sp.to_dict()["ticks"]["offered"] == sp.n[OFFERED]
        assert first.n[SUBMITTED] == second.n[SUBMITTED]
        assert second.n[OFFERED] == first.n[OFFERED]
        h = node.metrics._histograms
        assert h["lat_read_queue_s"].n == h["lat_read_confirm_s"].n \
            == h["lat_read_e2e_s"].n == 2
        assert h["lat_read_queue_s"].total + h["lat_read_confirm_s"].total \
            == pytest.approx(h["lat_read_e2e_s"].total, rel=1e-9)
        doc = node.latency_snapshot()
        assert doc["lat_read_queue"]["count"] == 2
    finally:
        c.close()


def test_write_groups_telescope_to_e2e(tmp_path, monkeypatch):
    """Queue + persist + replicate + release, as means over the same
    spans, are the mean of lat_e2e_s (to 1%), every stamp in order with
    its tick number."""
    monkeypatch.setenv("RAFT_LAT_SAMPLE", "1")
    c = LocalCluster(CFG, str(tmp_path), seed=3)
    try:
        lead = c.wait_leader(0)
        node = c.nodes[lead]
        c.tick(10)      # a fresh leader refuses until its majority is ready
        for i in range(8):
            _settle(c, [node.submit(0, b"w%d" % i)])
        h = node.metrics._histograms
        e2e = h["lat_e2e_s"]
        assert e2e.n == 8 and node.metrics["lat_span_overflow"] == 0
        parts = 0.0
        for pairs in WRITE_GROUPS.values():
            for pair in pairs:
                assert h[f"lat_{pair}_s"].n == e2e.n
                parts += h[f"lat_{pair}_s"].total / e2e.n
        assert parts == pytest.approx(e2e.total / e2e.n, rel=0.01)
        for sp in node._lat.recent:
            if sp.kind == "w":
                ticks = sp.n[SUBMITTED:ACKED + 1]
                assert ticks == sorted(ticks) and ticks[0] >= 0
    finally:
        c.close()


def test_skipped_round_leaves_backlog_and_one_more_round_per_commit(
        tmp_path, monkeypatch):
    """Three nodes ticked by hand, each leading lanes of its own (so each
    sends the others a slice every tick: a node that only answers sends
    nothing unasked, and its queue drains by itself).  One node misses
    one round (its peers tick, it does not): from then on its inbox holds
    one slice per source beyond the one it pops, and the writes it leads
    need one more of its ticks from sent to committed (every
    acknowledgement waits a tick in the queue), for the next 20 rounds
    and more."""
    monkeypatch.setenv("RAFT_LAT_SAMPLE", "1")
    c = LocalCluster(CFG, str(tmp_path), seed=1)
    try:
        lead = c.wait_leader(0)
        node = c.nodes[lead]
        c.tick(10)
        assert all((n.h_role == LEADER).any() for n in c.nodes.values())

        def commit_ticks(n):
            node._lat.recent.clear()
            for i in range(n):
                _settle(c, [node.submit(0, b"w%d" % i)])
            return [sp.n[COMMITTED] - sp.n[SENT]
                    for sp in node._lat.recent if sp.kind == "w"]

        def backlog_over(rounds):
            h = node.metrics.histogram("inbox_backlog")
            n0, t0 = h.n, h.total
            c.tick(rounds)
            return (h.total - t0) / (h.n - n0)

        before = commit_ticks(3)
        assert len(set(before)) == 1
        assert backlog_over(5) == 0.0
        for i, other in c.nodes.items():
            if i != lead:
                other.tick()                    # the round `lead` misses
        assert backlog_over(20) == 1.0
        after = commit_ticks(3)
        assert after == [before[0] + 1] * 3
        assert backlog_over(5) == 1.0           # it never comes back
        g = node.metrics._gauges
        assert {g[f"inbox_backlog_src{p}"] for p in range(3) if p != lead} \
            == {1}
        assert node.metrics["inbox_collapsed"] == 0
    finally:
        c.close()
