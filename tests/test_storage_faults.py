"""Storage-fault nemesis, node tier: the failure-response policy end to
end on full RaftNode clusters (ISSUE 12 acceptance scenarios).

* Injected fsync failure on a leader's WAL stripe: FAIL-STOP.  No future
  for the affected range ever completes successfully on that node (the
  in-flight promise fails with StorageFaultError, outcome-unknown), the
  stripe is never fsynced again, its groups go silent, and a healthy
  replica takes over — while groups on healthy stripes keep committing
  with byte-parity across replicas.
* Injected ENOSPC: DEGRADE, don't wedge.  The barrier failure engages
  admission backpressure (fresh submissions refuse with BusyLoopError),
  the engine keeps its staged buffer, and the retried barrier lands the
  very same record — durable across restart.
* At-rest bit flip in the newest archived snapshot: caught by CRC on
  recovery (fall back to the previous milestone + WAL replay, full
  parity) and by the background scrubber (quarantined to ``*.corrupt``
  before any reader trusts it).

Parametrized over both WAL engines, the native one at thread widths
W ∈ {1, 2, 4} (the Python one stages on the tick thread, width 1).
"""

import errno
import glob
import os

import numpy as np
import pytest

from rafting_tpu.api import BusyLoopError, StorageFaultError
from rafting_tpu.core.types import EngineConfig
from rafting_tpu.log import native_available
from rafting_tpu.snapshot.policy import MaintainAgreement
from rafting_tpu.testkit import faultfs
from rafting_tpu.testkit.harness import LocalCluster, wal_store_factory

CFG = EngineConfig(n_groups=4, n_peers=3, log_slots=32, batch=4,
                   max_submit=4, election_ticks=10, heartbeat_ticks=3,
                   rpc_timeout_ticks=8)

TIERS = [("python", 1)] + (
    [("native", 1), ("native", 2), ("native", 4)]
    if native_available() else [])


def make_cluster(root, tier, workers, maintain_factory=None):
    return LocalCluster(CFG, root,
                        store_factory=wal_store_factory(root, tier),
                        host_workers=workers,
                        maintain_factory=maintain_factory)


@pytest.fixture(params=TIERS, ids=[f"{t}-w{w}" for t, w in TIERS])
def tier_cluster(request, tmp_path):
    tier, workers = request.param
    c = make_cluster(str(tmp_path), tier, workers)
    yield c
    c.close()


def test_fsync_failstop_quarantines_stripe(tier_cluster):
    c = tier_cluster
    lead = c.wait_leader(0)
    c.wait_leader(1)
    c.submit_via_leader(0, b"pre-fault")
    c.submit_via_leader(1, b"healthy-pre")
    node = c.nodes[lead]

    # Groups stripe g % 4 over 4 shards: group 0 lives alone on stripe 0.
    node.store.set_fault("fsync", value=errno.EIO, shard=0)
    fut = node.submit(0, b"doomed")
    for _ in range(100):
        if fut.done():
            break
        c.tick()
    assert fut.done(), "future neither completed nor failed under fsync fault"
    # Ack-after-fsync: the future must NOT complete for a range whose
    # fsync failed — it fails outcome-unknown (the entry may have been
    # replicated by an eager send and can legally commit cluster-wide).
    assert isinstance(fut.exception(), StorageFaultError)
    assert 0 in node._poisoned_stripes
    assert node.metrics["fsync_failures"] >= 1
    assert node._healthy_groups is not None and not node._healthy_groups[0]

    # Fail-stop: fresh submissions for the quarantined group refuse
    # immediately with the same taxonomy (marked retry-safe).
    fut2 = node.submit(0, b"refused")
    assert isinstance(fut2.exception(timeout=1), StorageFaultError)

    # Healthy stripes on the SAME node keep committing with parity.
    c.submit_via_leader(1, b"healthy-post")
    c.tick(10)
    c.assert_file_parity(1)

    # The quarantined lane went silent: the healthy replicas elect a new
    # leader for group 0 and accept traffic again.
    for _ in range(300):
        l = c.leader_of(0)
        if l is not None and l != lead:
            break
        c.tick()
    new_lead = c.leader_of(0)
    assert new_lead is not None and new_lead != lead
    c.submit_via_leader(0, b"after-failover")
    c.tick(10)

    # The stripe is never reused: still poisoned at the end of the run,
    # and replica output files agree on their common prefix everywhere.
    assert 0 in node._poisoned_stripes
    c.assert_file_parity(0)
    for g in (1, 2, 3):
        c.assert_file_parity(g, require_progress=False)


def test_enospc_backpressure_not_wedge(tier_cluster):
    c = tier_cluster
    lead = c.wait_leader(0)
    c.submit_via_leader(0, b"pre-nospace")
    node = c.nodes[lead]

    node.store.set_fault("write", value=errno.ENOSPC, shard=0)
    fut = node.submit(0, b"kept-through-enospc")
    saw_backpressure = False
    for _ in range(100):
        c.tick()
        if node._io_backpressure:
            saw_backpressure = True
            # Degraded, not wedged: fresh admissions refuse with
            # BusyLoopError while the barrier retry is pending.
            fut2 = node.submit(0, b"shed")
            assert isinstance(fut2.exception(timeout=1), BusyLoopError)
            break
    assert saw_backpressure, "ENOSPC never surfaced as backpressure"
    assert node.metrics["enospc_backpressure"] >= 1
    assert not node._poisoned_stripes   # ENOSPC must not quarantine

    # The engine kept its staged buffer: the retried barrier lands the
    # SAME record and the in-flight future completes successfully.
    for _ in range(200):
        if fut.done():
            break
        c.tick()
    assert fut.done() and fut.exception() is None
    assert not node._io_backpressure
    c.tick(10)
    c.assert_file_parity(0)
    assert "kept-through-enospc" in c.command_payloads(lead, 0)

    # Durable, not just applied: the record survives a crash-restart.
    c.kill_node(lead)
    c.restart_node(lead)
    c.tick_until(lambda: "kept-through-enospc"
                 in c.command_payloads(lead, 0), 300, "restart catch-up")


def aggressive_no_compact():
    """Checkpoint eagerly but never compact: the WAL floor stays at 0,
    so recovery can fall back to ANY older milestone and replay."""
    return MaintainAgreement(CFG.n_groups, state_change_threshold=1,
                             dirty_log_tolerance=1, snap_min_interval=2,
                             compact_min_interval=1 << 30)


@pytest.mark.parametrize("tier", ["python"] + (
    ["native"] if native_available() else []))
def test_corrupt_newest_snapshot_falls_back_on_recovery(tmp_path, tier):
    c = make_cluster(str(tmp_path), tier, 1,
                     maintain_factory=aggressive_no_compact)
    try:
        c.wait_leader(0)
        for k in range(8):
            c.submit_via_leader(0, f"cmd-{k}".encode())
            c.tick(3)   # space the commits so several milestones land
        victim = next(
            (i for i in c.nodes
             if len(c.nodes[i].archive.list_snapshots(0)) >= 2), None)
        for _ in range(200):
            if victim is not None:
                break
            c.tick()
            victim = next(
                (i for i in c.nodes
                 if len(c.nodes[i].archive.list_snapshots(0)) >= 2), None)
        assert victim is not None, "no node accumulated two snapshots"
        want = c.command_payloads(victim, 0)
        newest = c.nodes[victim].archive.list_snapshots(0)[-1].path
        c.kill_node(victim)

        # At-rest corruption of the newest milestone while the node is
        # down (the scrub never saw it): recovery must catch it by CRC,
        # quarantine it, fall back to the previous milestone and replay
        # the WAL above it — full state, zero trust in corrupt bytes.
        faultfs.flip_bits(newest, seed=42)
        n = c.restart_node(victim)
        assert os.path.exists(newest + ".corrupt")
        assert not os.path.exists(newest)
        assert all(s.path != newest
                   for s in n.archive.list_snapshots(0))
        c.tick_until(lambda: c.command_payloads(victim, 0)[:len(want)]
                     == want, 300, "post-corruption catch-up")
        c.tick(10)
        c.assert_file_parity(0)
    finally:
        c.close()


def test_scrubber_quarantines_live_corruption(tmp_path):
    c = make_cluster(str(tmp_path), "python", 1,
                     maintain_factory=aggressive_no_compact)
    try:
        c.wait_leader(0)
        for k in range(6):
            c.submit_via_leader(0, f"cmd-{k}".encode())
            c.tick(3)
        victim = None
        for _ in range(200):
            victim = next(
                (i for i in c.nodes
                 if len(c.nodes[i].archive.list_snapshots(0)) >= 1), None)
            if victim is not None:
                break
            c.tick()
        assert victim is not None
        node = c.nodes[victim]
        snap = node.archive.list_snapshots(0)[-1]
        faultfs.flip_bits(snap.path, seed=7)
        # Drive the scrubber directly (its tick cadence is hundreds of
        # ticks by default — the policy, not the cadence, is under test).
        before = node.metrics["scrub_corrupt"]
        for _ in range(4):   # round-robin cursor: cover every group
            node._scrub_archive()
        assert node.metrics["scrub_corrupt"] == before + 1
        assert os.path.exists(snap.path + ".corrupt")
        assert all(s.path != snap.path
                   for s in node.archive.list_snapshots(0))
        # A later checkpoint re-archives a good snapshot in its place.
        c.tick(40)
        assert node.metrics["scrub_ok"] >= 1 or \
            len(node.archive.list_snapshots(0)) >= 1
    finally:
        c.close()


def test_healthz_and_metrics_surface_storage_state(tmp_path):
    c = make_cluster(str(tmp_path), "python", 1)
    try:
        c.wait_leader(0)
        c.submit_via_leader(0, b"warm0")
        node = c.nodes[c.leader_of(0)]
        srv = node.start_observability()
        import json
        import urllib.request

        def healthz():
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/healthz", timeout=5) as r:
                return json.load(r)

        h = healthz()
        assert h["storage"] == {"poisoned_stripes": [],
                                "backpressure": False, "io_slow": False}
        node.store.set_fault("fsync", shard=0)
        fut = node.submit(0, b"doomed")
        for _ in range(100):
            if fut.done():
                break
            c.tick()
        h = healthz()
        assert h["storage"]["poisoned_stripes"] == [0]
        assert h["ok"] is True   # liveness bit: healthy groups still serve
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=5) as r:
            text = r.read().decode()
        assert "fsync_failures" in text
        assert "stripes_poisoned 1" in text
    finally:
        c.close()
