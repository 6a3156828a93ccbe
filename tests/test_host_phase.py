"""The host phase (runtime/node.py _host_phase) under both persist steps
— the native WAL engine's one stage-and-fsync call (log/native/wal.cpp
wal_stage_and_sync / wal_pack_ae) at thread widths 1, 2 and 4, and the
Python engine's stage and barrier: tick-for-tick scalar-oracle parity
under partition + crash + stall nemesis, one step program through a
failed barrier, the crash-in-the-stage-window durability
contract, byte-identical WAL segments between the two engines (recovery
interchangeable in BOTH directions, torn tails included), outcome
convergence, and which step a node takes: the store decides, a
membership-config tick takes the Python one.

The parity tests monkeypatch the runtime's ``node_step_packed`` with a
wrapper that also runs the scalar oracle on the SAME inputs (unpacked from
the tick's upload buffers) every tick, so a persist step that corrupts
what it feeds the device (WAL staging, submission arenas, inbox routing)
diverges at the exact offending tick — the host phase sits between two
oracle-checked device steps."""

import errno
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rafting_tpu.runtime.node as node_mod
from rafting_tpu.core.step import column_layouts, step_layouts
from rafting_tpu.core.types import EngineConfig, LEADER, conf_voters_of
from rafting_tpu.log import wal as wal_mod
from rafting_tpu.log.store import LogStore, restore_raft_state
from rafting_tpu.testkit import nemesis
from rafting_tpu.testkit.fixtures import NullProvider
from rafting_tpu.testkit.harness import LocalCluster, wal_store_factory
from rafting_tpu.testkit.oracle import oracle_step

from rafting_tpu.testkit.parity import (
    assert_info_equal, assert_messages_equal, assert_state_equal,
)

CFG = EngineConfig(n_groups=8, n_peers=3, log_slots=16, batch=4,
                   max_submit=4, election_ticks=8, heartbeat_ticks=2,
                   rpc_timeout_ticks=6, pre_vote=True)

needs_native = pytest.mark.skipif(
    not wal_mod.native_available(),
    reason="native WAL engine unavailable (no toolchain/.so)")


def engine_param(engine, *rest):
    """One parametrize case on an engine; native ones skip without it."""
    return pytest.param(engine, *rest, id="-".join(map(str, (engine,) + rest)),
                        marks=[needs_native] if engine == "native" else [])


def make_cluster(cfg, root, engine, workers, shards=4, **kw):
    """A LocalCluster whose nodes' stores run ``engine`` — the engine is
    a property of the store, and the node takes its persist step from
    it."""
    c = LocalCluster(cfg, root,
                     store_factory=wal_store_factory(root, engine, shards),
                     host_workers=workers, **kw)
    for n in c.nodes.values():
        assert n.store.can_stage_native == (engine == "native"), \
            "the store runs another engine — the case is vacuous"
    return c


@pytest.fixture
def oracle_checked_step(monkeypatch):
    """Cross-check every runtime node_step_packed call against the scalar
    oracle: the oracle steps what the tick's upload buffers hold, and the
    packed step's readback must unpack to the oracle's outputs (oracle
    FIRST: the step donates its state buffers), the ``durable_tail`` clamp
    every node feeds included."""
    real = node_mod.node_step_packed
    calls = {"n": 0}

    def checked(cfg, inputs, state, buffers):
        host, inbox = jax.tree.map(
            jnp.asarray, inputs.unpack(jax.device_get(buffers)))
        o_state, o_out, o_info = oracle_step(cfg, state, inbox, host)
        k_state, packed = real(cfg, inputs, state, buffers)
        assert host.durable_tail is not None
        _, readback = step_layouts(cfg, True)
        back = readback.unpack(jax.device_get(packed))
        tag = f"oracle-checked step #{calls['n']}"
        assert_state_equal(k_state, o_state, tag)
        assert_messages_equal(back.outbox, o_out, tag)
        assert_info_equal(back.info, o_info, tag)
        calls["n"] += 1
        return k_state, packed

    monkeypatch.setattr(node_mod, "node_step_packed", checked)
    return calls


# ------------------------------------- oracle parity x engine x width ----


@pytest.mark.parametrize("engine,workers,lease", [
    engine_param(e, w, lease)
    for lease in (True, False)
    for e, w in (("python", 1), ("native", 1), ("native", 2), ("native", 4))
])
def test_oracle_parity_under_nemesis(tmp_path, engine, workers, lease,
                                     oracle_checked_step):
    """Either persist step, the native one at W ∈ {1,2,4}, drives the
    identical device-visible semantics under a partition + crash-restart
    + clock-stall schedule with submit and linearizable-read load offered
    throughout — every tick of every node is oracle-checked."""
    cfg = EngineConfig(n_groups=8, n_peers=3, log_slots=16, batch=4,
                       max_submit=4, election_ticks=8, heartbeat_ticks=2,
                       rpc_timeout_ticks=6, pre_vote=True, read_lease=lease)
    sched = nemesis.compose(
        nemesis.split_brain(3, 36, start=8, stop=20, seed=21),
        nemesis.crash_storm(3, 36, rate=0.02, seed=22),
        nemesis.clock_stalls(3, 36, rate=0.03, seed=23),
    )
    c = make_cluster(cfg, str(tmp_path), engine, workers,
                     provider_factory=NullProvider, seed=5)
    try:
        assert all(n.host_workers == workers for n in c.nodes.values())

        def audit(t):
            for g in range(cfg.n_groups):
                c.leader_of(g)   # raises on same-term split brain
            # Offered load through the chaos: the persist/apply/send
            # path must carry real entries and reads, not just
            # heartbeats.
            for n in c.nodes.values():
                for g in np.nonzero((n.h_role == LEADER) & n.h_ready)[0]:
                    n.submit_batch(int(g), [b"s%d-%d" % (t, g)])
                    n.read(int(g), b"r%d-%d" % (t, g))

        c.replay_schedule(sched, audit=audit)
        for _ in range(50):
            c.tick()
            if all(c.leader_of(g) is not None
                   for g in range(cfg.n_groups)):
                break
        for g in range(cfg.n_groups):
            assert c.wait_leader(g, max_rounds=100) is not None
        assert oracle_checked_step["n"] > 36 * 2, \
            "oracle wrapper never saw the replayed ticks"
        total = sum(int(n.h_commit.astype(np.int64).sum())
                    for n in c.nodes.values())
        assert total > 0, "schedule never committed anything"
    finally:
        c.close()


# ------------------------------------------------------- crash windows ----


@pytest.mark.parametrize("shape", ["packed", "columns"])
def test_a_failed_barrier_keeps_the_one_program(tmp_path, monkeypatch,
                                                take_shape, shape):
    """A node compiles ONE step program and keeps it through a failed
    barrier and its recovery: every step is fed ``durable_tail`` (the
    fsynced mirror; the confirmed tail ``_acked_tail`` while a barrier
    stands failed), so the clamp changes what the program is fed, never
    which program runs.  (A node that fed a tail only while clamped
    compiled a second program in the middle of a storage fault.)"""
    cfg = EngineConfig(n_groups=4, n_peers=3, log_slots=32, batch=4,
                       max_submit=4, election_ticks=10, heartbeat_ticks=3,
                       rpc_timeout_ticks=8)
    take_shape(cfg, shape)
    lay = column_layouts(cfg, True)
    name = "node_step_columns" if lay is not None else "node_step_packed"
    step = getattr(node_mod, name)
    keys = set()

    def spy(cfg_, layout, *rest):
        # The step's static arguments beside cfg: (layout,) of the packed
        # step, (layout, columns_in) of the column step.
        keys.add((layout,) + (rest[:1] if lay is not None else ()))
        return step(cfg_, layout, *rest)

    monkeypatch.setattr(node_mod, name, spy)
    c = LocalCluster(cfg, str(tmp_path), provider_factory=NullProvider,
                     seed=5, wal_shards=2)
    try:
        lead = c.wait_leader(0)
        node = c.nodes[lead]
        c.tick_until(lambda: node.is_ready(0), what="leader ready")
        fut = node.submit(0, b"warm")
        c.tick_until(fut.done, what="warm write")
        c.tick(10)
        compiled, before = step._cache_size(), set(keys)
        node.store.set_fault("write", value=errno.ENOSPC, shard=0)
        fut = node.submit(0, b"kept-through-enospc")
        clamped = 0
        for _ in range(200):
            c.tick()
            clamped += node._acked_tail is not None
            if fut.done() and node._acked_tail is None:
                break
        assert clamped and fut.done() and fut.exception() is None
        c.tick(5)
        want = lay if lay is not None else step_layouts(cfg, True)[0]
        assert {k[0] for k in keys} == {want}, "a second layout was stepped"
        # No compile but for a static variant of the column step first met
        # after the fault (columns in / dense in).
        assert step._cache_size() - compiled == len(keys - before)
    finally:
        c.close()


@needs_native
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


@needs_native
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


@needs_native
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


# ------------------------------------------- native/Python convergence --


@needs_native
@pytest.mark.parametrize("shape", ["packed", "columns"])
@pytest.mark.parametrize("workers", [2, 4])
def test_native_python_convergence(tmp_path, take_shape, workers, shape):
    """The native step at width W and the Python step drive the same
    workload to the same applied outcome — the engine and its threads
    repartition WORK, never effects — whichever step the shape takes."""
    take_shape(CFG, shape)
    results = {}
    for engine in ("native", "python"):
        c = make_cluster(CFG, str(tmp_path / engine), engine, workers,
                         provider_factory=NullProvider, seed=3)
        try:
            lead = c.wait_leader(0)
            c.tick_until(lambda: c.nodes[lead].is_ready(0),
                         what="leader ready")
            futs = [c.nodes[lead].submit_batch(0, [b"c%d" % k])
                    for k in range(8)]
            for _ in range(60):
                c.tick(1)
                if all(f.done() for f in futs):
                    break
            results[engine] = [f.result(timeout=1) for f in futs]
        finally:
            c.close()
    assert results["native"] == results["python"]


# ------------------------------------------------ which step, how wide ----


@needs_native
def test_native_width_clamps_to_stripes(tmp_path):
    """host_workers beyond the WAL stripe count clamps to it (a thread
    without a stripe would idle every tick); the gauge reports the
    effective width."""
    c = LocalCluster(CFG, str(tmp_path / "a"), provider_factory=NullProvider,
                     wal_shards=2, host_workers=8)
    try:
        for n in c.nodes.values():
            assert n.store.can_stage_native and n.host_workers == 2
            assert n.metrics._gauges["host_workers"] == 2
    finally:
        c.close()
    c = LocalCluster(CFG, str(tmp_path / "b"), provider_factory=NullProvider,
                     wal_shards=1, host_workers=4)
    try:
        assert all(n.host_workers == 1 for n in c.nodes.values())
        c.wait_leader(0)
    finally:
        c.close()


def _count_steps(node):
    """Count which persist step each host phase of ``node`` takes, and
    whether the tick it persisted carried a membership-config entry."""
    seen = {"python": 0, "native": 0, "python_conf": 0, "native_conf": 0}

    def carries_conf(prep):
        if (prep.conf_app > 0).any():
            return True
        if prep.fr_cents is None or not len(prep.wrote):
            return False
        return bool(prep.fr_cents.any())      # rows of the wrote set

    def wrap(name, fn):
        def counted(prep):
            seen[name] += 1
            seen[name + "_conf"] += carries_conf(prep)
            return fn(prep)
        return counted

    node._persist_stage = wrap("python", node._persist_stage)
    node._persist_stage_native = wrap("native", node._persist_stage_native)
    return seen


def test_force_python_store_takes_the_python_step_at_width_one(tmp_path):
    """The store decides: a ``force_python`` store's node stages on the
    tick thread at width 1 whatever ``host_workers`` asks for, never
    calls the native entry point, and offers no native payload pack."""
    c = make_cluster(CFG, str(tmp_path), "python", 4, shards=2,
                     provider_factory=NullProvider)
    try:
        assert all(n.host_workers == 1 for n in c.nodes.values())
        seen = [_count_steps(n) for n in c.nodes.values()]
        c.wait_leader(0)
        c.submit_via_leader(0, b"x")
        assert all(s["native"] == 0 and s["python"] > 0 for s in seen)
        s = c.nodes[0].store
        assert s.pack_ae_blob(np.array([0], np.uint32),
                              np.array([1], np.int64),
                              np.array([0], np.uint32)) is None
        gauges = c.nodes[0].metrics._gauges
        assert gauges["host_workers"] == 1 and gauges["native_host"] == 0
    finally:
        c.close()


@needs_native
def test_conf_tick_takes_the_python_step_on_a_native_store(tmp_path):
    """A tick that carries a membership-config entry (a leader's conf
    append, a follower's adopted conf word) takes the Python persist
    step on a native store — the conf sidecar is one global document —
    and every other tick the native one.  What they leave on disk
    between them a ``force_python`` store recovers exactly as the native
    engine does, voter set included."""
    cfg = EngineConfig(n_groups=2, n_peers=4, log_slots=16, batch=4,
                       max_submit=4, election_ticks=8, heartbeat_ticks=2,
                       rpc_timeout_ticks=6, pre_vote=True)
    root = str(tmp_path)
    c = make_cluster(cfg, root, "native", 2)
    try:
        seen = {i: _count_steps(n) for i, n in c.nodes.items()}
        c.wait_leader(0)
        c.submit_via_leader(0, b"before")
        lead = c.leader_of(0)
        fut = c.nodes[lead].change_membership(0, 0b0111)
        for _ in range(400):
            if fut.done():
                break
            c.tick()
        assert fut.result() == {"voters": 0b0111, "learners": 0}
        c.submit_via_leader(0, b"after")
        c.tick(10)
        for i, s in seen.items():
            assert s["native_conf"] == 0, \
                f"node {i}: a conf-bearing tick reached the native engine"
            assert s["native"] > 0, f"node {i}: the native step never ran"
        assert seen[lead]["python_conf"] >= 2      # joint + leave appends
        followers = [i for i in seen if i != lead]
        assert any(seen[i]["python_conf"] >= 2 for i in followers), \
            "no follower adopted a conf word through the Python step"
    finally:
        c.close()
    for i in range(cfg.n_peers):
        d = os.path.join(root, f"node{i}", "wal")
        img = os.path.join(root, f"img{i}")
        shutil.copytree(d, img)
        r_nat = LogStore(d, shards=4)
        r_py = LogStore(img, shards=4, force_python=True)
        try:
            assert not r_py.can_stage_native
            s_nat = restore_raft_state(cfg, i, r_nat)
            s_py = restore_raft_state(cfg, i, r_py)
            for a, b in zip(jax.tree.leaves(s_nat), jax.tree.leaves(s_py)):
                np.testing.assert_array_equal(
                    a, b, err_msg=f"node {i}: recovery differs")
            if i < 3:
                assert int(conf_voters_of(
                    int(np.asarray(s_py.conf_word)[0]))) == 0b0111
            for g in range(cfg.n_groups):
                assert r_nat.tail(g) == r_py.tail(g)
                for idx in range(1, r_nat.tail(g) + 1):
                    assert r_nat.payload(g, idx) == r_py.payload(g, idx)
        finally:
            r_nat.close()
            r_py.close()
