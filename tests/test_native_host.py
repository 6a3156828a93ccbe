"""Native host tier (runtime/node.py _host_phase_native + log/native/
wal.cpp wal_stage_and_sync / wal_pack_ae): tick-for-tick scalar-oracle
parity with the C staging path under partition + crash + stall nemesis,
byte-identical WAL segments between the native and Python staging
backends (recovery interchangeable in BOTH directions, torn tails
included), the crash-in-the-stage-window durability contract, and
native/Python outcome convergence.

The whole module skips cleanly when the toolchain / .so is unavailable —
the pure-Python paths (tested by test_host_striped.py and the serial
suites) are the portable fallback."""

import os
import shutil

import numpy as np
import pytest

from rafting_tpu.core.types import EngineConfig, LEADER
from rafting_tpu.log import wal as wal_mod
from rafting_tpu.log.store import LogStore, restore_raft_state
from rafting_tpu.testkit import nemesis
from rafting_tpu.testkit.fixtures import NullProvider
from rafting_tpu.testkit.harness import LocalCluster

from test_host_striped import oracle_checked_step  # noqa: F401  (fixture)
from test_host_striped import (
    test_eager_window_crash_completes_nothing as _eager_window_crash,
)

pytestmark = pytest.mark.skipif(
    not wal_mod.native_available(),
    reason="native WAL host tier unavailable (no toolchain/.so)")

CFG = EngineConfig(n_groups=8, n_peers=3, log_slots=16, batch=4,
                   max_submit=4, election_ticks=8, heartbeat_ticks=2,
                   rpc_timeout_ticks=6, pre_vote=True)


@pytest.fixture(autouse=True)
def _native_host_tier(monkeypatch):
    """Force the native route — auto-selection already picks it when the
    .so loads, but the pin makes the subject of this module explicit and
    keeps it that way if the default ever changes."""
    monkeypatch.setenv("RAFT_NATIVE_HOST", "1")


# ------------------------------------------------ oracle parity x W ----


@pytest.mark.parametrize("workers", [1, 2, 4])
def test_native_oracle_parity_under_nemesis(tmp_path, workers,
                                            oracle_checked_step):
    """W ∈ {1,2,4} native host tiers drive identical device-visible
    semantics under a partition + crash-restart + clock-stall schedule
    with submit and linearizable-read load offered throughout — every
    tick of every node is oracle-checked, and every durable write goes
    through wal_stage_and_sync."""
    sched = nemesis.compose(
        nemesis.split_brain(3, 36, start=8, stop=20, seed=21),
        nemesis.crash_storm(3, 36, rate=0.02, seed=22),
        nemesis.clock_stalls(3, 36, rate=0.03, seed=23),
    )
    c = LocalCluster(CFG, str(tmp_path), provider_factory=NullProvider,
                     seed=5, pipeline=False, wal_shards=4,
                     host_workers=workers)
    try:
        assert all(n._native_host for n in c.nodes.values()), \
            "native host tier not selected — suite is vacuous"
        assert all(n._w_native == workers for n in c.nodes.values())

        def audit(t):
            for g in range(CFG.n_groups):
                c.leader_of(g)   # raises on same-term split brain
            for n in c.nodes.values():
                for g in np.nonzero((n.h_role == LEADER) & n.h_ready)[0]:
                    n.submit_batch(int(g), [b"s%d-%d" % (t, g)])
                    n.read(int(g), b"r%d-%d" % (t, g))

        c.replay_schedule(sched, audit=audit)
        for _ in range(50):
            c.tick()
            if all(c.leader_of(g) is not None
                   for g in range(CFG.n_groups)):
                break
        for g in range(CFG.n_groups):
            assert c.wait_leader(g, max_rounds=100) is not None
        assert oracle_checked_step["n"] > 36 * 2
        total = sum(int(n.h_commit.astype(np.int64).sum())
                    for n in c.nodes.values())
        assert total > 0, "schedule never committed anything"
    finally:
        c.close()


# ------------------------------------------------ crash windows --------


def test_native_eager_window_crash_completes_nothing(tmp_path):
    """The eager-send crash window contract (acks/futures never precede
    the tick's own fsync) holds identically when the fsync is issued by
    the native stage_and_sync call."""
    _eager_window_crash(tmp_path)


def test_native_crash_in_stage_window(tmp_path):
    """Crash INSIDE the native stage window: entries staged with
    do_sync=0 live only in the engine's userspace buffers — a crash
    image taken there recovers the pre-stage durable tail; after the
    sync they are durable."""
    d = str(tmp_path / "wal")
    s = LogStore(d, shards=2)
    assert s.can_stage_native
    base = [(g, 1, memoryview(b"abc" * (g + 1)), np.array([3 * (g + 1)],
            np.uint32), 1) for g in range(4)]
    s.stage_and_sync(base, *[np.array([], np.int64)] * 5,
                     workers=2, sync=True)
    tails = {g: s.tail(g) for g in range(4)}

    spans = [(g, 2, memoryview(b"zz" * (g + 2)), np.array([2 * (g + 2)],
             np.uint32), 2) for g in range(4)]
    s.stage_and_sync(spans, *[np.array([], np.int64)] * 5,
                     workers=2, sync=False)   # the stage window

    img = str(tmp_path / "crash-img")
    shutil.copytree(d, img)
    r = LogStore(img, shards=2)
    try:
        for g in range(4):
            assert r.tail(g) == tails[g], \
                "un-fsynced stage leaked into the crash image"
            assert r.payload(g, 2) is None
    finally:
        r.close()

    s.sync()
    s.close()
    r = LogStore(d, shards=2)
    try:
        for g in range(4):
            assert r.tail(g) == 2
            assert r.payload(g, 2) == b"zz" * (g + 2)
    finally:
        r.close()


# ----------------------------------- cross-backend recovery parity ----


def _drive(s: LogStore, native: bool) -> None:
    """One op sequence through either backend: appends, an overwrite, a
    stable record, a truncation, and a compaction floor."""
    def spans_of(rows):
        out = []
        for g, start, payloads, term in rows:
            buf = b"".join(payloads)
            lens = np.array([len(p) for p in payloads], np.uint32)
            out.append((g, start, memoryview(buf), lens, term))
        return out

    tick1 = spans_of([(g, 1, [bytes([g]) * (4 + k) for k in range(3)], 1)
                      for g in range(6)])
    tick2 = spans_of([(0, 2, [b"overwrite-0"], 2),
                      (3, 4, [b"x3", b"y3"], 2)])
    if native:
        s.stage_and_sync(tick1, *[np.array([], np.int64)] * 5, sync=True)
        s.put_stable_batch([1, 2], [5, 6], [0, 1])
        s.stage_and_sync(tick2, np.array([5]), np.array([1]),
                         np.array([4]), np.array([2]), np.array([1]),
                         workers=2, sync=True)
    else:
        s.append_spans(tick1)
        s.sync()
        s.put_stable_batch([1, 2], [5, 6], [0, 1])
        s.append_spans(tick2)
        s.truncate_to(5, 1)
        s.set_floor(4, 2, 1)
        s.sync()


def _state_of(s: LogStore) -> dict:
    out = {}
    for g in range(6):
        out[g] = (s.tail(g), s.wal.floor(g),
                  [s.payload(g, i) for i in range(1, 6)])
    return out


def _seg_bytes(d: str) -> dict:
    out = {}
    for root, _dirs, files in os.walk(d):
        for f in files:
            p = os.path.join(root, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, d)] = fh.read()
    return out


def test_cross_backend_recovery_and_byte_identity(tmp_path):
    """The same op sequence through the native stage_and_sync and the
    Python staging path yields BYTE-IDENTICAL segment files, and each
    backend's output recovers correctly under the other (both
    directions)."""
    d_nat = str(tmp_path / "nat")
    d_py = str(tmp_path / "py")
    s = LogStore(d_nat, shards=4)
    _drive(s, native=True)
    s.close()
    s = LogStore(d_py, shards=4)
    _drive(s, native=False)
    s.close()

    a, b = _seg_bytes(d_nat), _seg_bytes(d_py)
    assert sorted(a) == sorted(b)
    for k in a:
        assert a[k] == b[k], f"segment {k} diverges between backends"

    # native-written → Python-engine recovery
    r = LogStore(d_nat, shards=4, force_python=True)
    try:
        ref = _state_of(r)
        assert r.payload(0, 2) == b"overwrite-0"
        assert r.tail(5) == 1 and r.wal.floor(4) == 2
    finally:
        r.close()
    # Python-written → native-engine recovery
    r = LogStore(d_py, shards=4)
    try:
        assert _state_of(r) == ref
    finally:
        r.close()


def test_torn_tail_cross_backend_parity(tmp_path):
    """A torn tail (partial frame at the end of a shard segment) is
    truncated to the same recovered state by the native and Python
    readers."""
    d = str(tmp_path / "wal")
    s = LogStore(d, shards=2)
    _drive(s, native=True)
    s.close()
    # Tear the newest segment of shard 0: chop off the last 5 bytes.
    shard0 = os.path.join(d, "shard00")
    seg = sorted(f for f in os.listdir(shard0) if f.endswith(".wal"))[-1]
    segp = os.path.join(shard0, seg)
    size = os.path.getsize(segp)
    with open(segp, "r+b") as f:
        f.truncate(size - 5)

    img = str(tmp_path / "img")
    shutil.copytree(d, img)
    r_nat = LogStore(d, shards=2)
    r_py = LogStore(img, shards=2, force_python=True)
    try:
        assert _state_of(r_nat) == _state_of(r_py)
    finally:
        r_nat.close()
        r_py.close()


# ----------------------------------------- native/Python convergence --


def test_native_python_convergence(tmp_path, monkeypatch):
    """Native and pure-Python host tiers drive the same workload to the
    same applied outcome — the backend repartitions WORK, never
    effects."""
    results = {}
    for tag, env in (("nat", "1"), ("py", "0")):
        monkeypatch.setenv("RAFT_NATIVE_HOST", env)
        c = LocalCluster(CFG, str(tmp_path / tag),
                         provider_factory=NullProvider, seed=3,
                         pipeline=True, wal_shards=4, host_workers=2)
        try:
            assert all(n._native_host == (env == "1")
                       for n in c.nodes.values())
            lead = c.wait_leader(0)
            c.tick_until(lambda: c.nodes[lead].is_ready(0),
                         what="leader ready")
            futs = [c.nodes[lead].submit_batch(0, [b"c%d" % k])
                    for k in range(8)]
            for _ in range(60):
                c.tick(1)
                if all(f.done() for f in futs):
                    break
            results[tag] = [f.result(timeout=1) for f in futs]
        finally:
            c.close()
    assert results["nat"] == results["py"]


def test_native_env_off_and_fallback(tmp_path, monkeypatch):
    """RAFT_NATIVE_HOST=0 pins the Python tier even with the .so loaded;
    a store without the native surface (force_python engines) degrades
    to the Python tier automatically with no env involved."""
    monkeypatch.setenv("RAFT_NATIVE_HOST", "0")
    c = LocalCluster(CFG, str(tmp_path / "off"),
                     provider_factory=NullProvider, wal_shards=2,
                     host_workers=2)
    try:
        assert all(not n._native_host for n in c.nodes.values())
        assert all(n._w_eff == 2 for n in c.nodes.values())
    finally:
        c.close()
    monkeypatch.delenv("RAFT_NATIVE_HOST")
    s = LogStore(str(tmp_path / "pystore"), shards=2, force_python=True)
    try:
        assert not s.can_stage_native
        assert s.pack_ae_blob(np.array([0], np.uint32),
                              np.array([1], np.int64),
                              np.array([0], np.uint32)) is None
    finally:
        s.close()
