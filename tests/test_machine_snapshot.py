"""Tests for the state-machine SPI, apply dispatcher, snapshot archive and
maintain policy (reference parity: SURVEY.md §2 L2a + §5 checkpoint/resume,
test model: command/SnapshotTest.java + cluster/cmd/FileMachine.java)."""

import os
from concurrent.futures import Future

import numpy as np
import pytest

from rafting_tpu.machine import (
    ApplyDispatcher, FileMachine, FileMachineProvider, KVMachine,
    KVMachineProvider,
)
from rafting_tpu.snapshot import MaintainAgreement, SnapshotArchive


# ---------------------------------------------------------------- machines

def test_file_machine_roundtrip(tmp_path):
    m = FileMachine(str(tmp_path / "m.txt"))
    assert m.last_applied() == 0
    m.apply(1, b"alpha")
    m.apply(2, b"beta")
    assert m.last_applied() == 2
    ck = m.checkpoint(1)
    assert ck.index == 2
    m.apply(3, b"gamma")
    # Recover to the checkpoint: prefix-compatible, rolls back to index 2.
    m.recover(ck)
    assert m.last_applied() == 2
    assert m.lines() == ["1:alpha\n", "2:beta\n"]
    m.close()
    # Reopen recounts last_applied from the file.
    m2 = FileMachine(str(tmp_path / "m.txt"))
    assert m2.last_applied() == 2
    m2.close()


def test_file_machine_detects_divergence(tmp_path):
    a = FileMachine(str(tmp_path / "a.txt"))
    a.apply(1, b"x")
    ck = a.checkpoint(1)
    b = FileMachine(str(tmp_path / "b.txt"))
    b.apply(1, b"DIFFERENT")
    with pytest.raises(AssertionError):
        b.recover(ck)
    a.close()
    b.close()


def test_kv_machine(tmp_path):
    m = KVMachine(str(tmp_path / "kv.json"))
    m.apply(1, b'{"op": "set", "k": "a", "v": 1}')
    m.apply(2, b'{"op": "set", "k": "b", "v": [2, 3]}')
    assert m.apply(3, b'{"op": "get", "k": "a"}') == 1
    ck = m.checkpoint(2)
    m.apply(4, b'{"op": "del", "k": "a"}')
    m.recover(ck)
    assert m.data == {"a": 1, "b": [2, 3]}
    assert m.last_applied() == 3
    m.close()
    m2 = KVMachine(str(tmp_path / "kv.json"))
    assert m2.last_applied() == 3 and m2.data["a"] == 1
    m2.close()


# ---------------------------------------------------------------- dispatcher

def test_dispatcher_applies_in_order_and_completes_promises(tmp_path):
    store = {}
    for i in range(1, 6):
        store[(0, i)] = f"cmd{i}".encode()
        store[(2, i)] = f"two{i}".encode()
    d = ApplyDispatcher(FileMachineProvider(str(tmp_path)),
                        lambda g, i: store.get((g, i)))
    f3 = Future()
    d.register_promise(0, 3, f3)
    commit = np.array([3, 0, 5], np.int32)
    d.advance(commit)
    assert d.applied(0) == 3 and d.applied(2) == 5
    assert f3.result(timeout=0) == 3
    # Frontier moves; only the delta is applied.
    commit[0] = 5
    d.advance(commit)
    assert d.applied(0) == 5
    assert d.machine(0).lines() == [f"{i}:cmd{i}\n" for i in range(1, 6)]
    d.close()


def test_dispatcher_halt_resume(tmp_path):
    store = {(0, i): b"x%d" % i for i in range(1, 10)}
    d = ApplyDispatcher(FileMachineProvider(str(tmp_path)),
                        lambda g, i: store.get((g, i)))
    d.advance(np.array([2], np.int32))
    assert d.applied(0) == 2
    d.halt(0)
    d.advance(np.array([6], np.int32))
    assert d.applied(0) == 2, "halted group must not apply"
    # Simulate snapshot install at index 6 from a donor machine.
    donor = FileMachine(str(tmp_path / "donor.txt"))
    for i in range(1, 7):
        donor.apply(i, b"x%d" % i)
    ck = donor.checkpoint(6)
    d.resume_from(0, ck)
    assert d.applied(0) == 6
    d.advance(np.array([8], np.int32))
    assert d.applied(0) == 8
    donor.close()
    d.close()


def test_dispatcher_abort_promises(tmp_path):
    d = ApplyDispatcher(FileMachineProvider(str(tmp_path)), lambda g, i: None)
    f = Future()
    d.register_promise(1, 7, f)
    d.abort_promises(1, RuntimeError("not leader"))
    with pytest.raises(RuntimeError):
        f.result(timeout=0)
    d.close()


def test_dispatcher_missing_payload_stops(tmp_path):
    """Frontier ahead of stored entries (snapshot commit) must not crash."""
    d = ApplyDispatcher(FileMachineProvider(str(tmp_path)),
                        lambda g, i: b"p" if i <= 2 else None)
    d.advance(np.array([5], np.int32))
    assert d.applied(0) == 2
    d.close()


# ---------------------------------------------------------------- archive

def test_archive_save_retention_order(tmp_path):
    a = SnapshotArchive(str(tmp_path / "arch"), retain=3)
    src = tmp_path / "state"
    for i in range(1, 6):
        src.write_text(f"state-{i}")
        a.save_checkpoint(0, str(src), index=i * 10, term=1)
    snaps = a.list_snapshots(0)
    assert len(snaps) == 3, "retention must prune to last 3"
    assert [s.index for s in snaps] == [30, 40, 50]
    last = a.last_snapshot(0)
    assert last.index == 50
    with open(last.path) as f:
        assert f.read() == "state-5"
    # Ordering violation rejected.
    src.write_text("old")
    with pytest.raises(AssertionError):
        a.save_checkpoint(0, str(src), index=5, term=0)


def test_archive_pending_lifecycle(tmp_path):
    a = SnapshotArchive(str(tmp_path / "arch"))
    p = a.pend_snapshot(0, index=100, term=3, from_peer=1)
    assert p is not None
    # Duplicate/older offers don't replace it.
    assert a.pend_snapshot(0, index=100, term=3, from_peer=2) is None
    assert a.pend_snapshot(0, index=90, term=3, from_peer=2) is None
    # A newer offer supersedes.
    p2 = a.pend_snapshot(0, index=120, term=4, from_peer=2)
    assert p2 is not None and p2.from_peer == 2
    data = tmp_path / "dl"
    data.write_text("snapshot-bytes")
    snap = a.install_pending(0, str(data))
    assert (snap.index, snap.term) == (120, 4)
    assert a.pending(0) is None
    assert a.last_snapshot(0).index == 120
    # Failed pending can be replaced by a same-milestone retry.
    a.pend_snapshot(0, index=130, term=4, from_peer=1)
    a.fail_pending(0)
    assert a.pend_snapshot(0, index=130, term=4, from_peer=2) is not None


def test_archive_sweeps_temps(tmp_path):
    root = tmp_path / "arch"
    g0 = root / "g0"
    g0.mkdir(parents=True)
    (g0 / "snapshot_0000000000000064_0000000000000001").write_text("ok")
    (g0 / "junk.tmp").write_text("torn")
    a = SnapshotArchive(str(root))
    assert not (g0 / "junk.tmp").exists()
    assert a.last_snapshot(0).index == 0x64


# ---------------------------------------------------------------- policy

def test_maintain_policy_thresholds():
    ma = MaintainAgreement(3, state_change_threshold=10,
                           dirty_log_tolerance=5, snap_min_interval=4,
                           compact_min_interval=2, compact_slack=2)
    applied = np.array([12, 3, 12], np.int64)
    base = np.array([0, 0, 10], np.int64)
    need = ma.need_checkpoint(now=10, applied=applied, log_base=base)
    # g0: changed=12>=10, dirty=12>=5 -> yes. g1: changed 3 -> no.
    # g2: dirty=2 < 5 -> no.
    assert list(need) == [True, False, False]
    ma.note_checkpoint(0, now=10, index=12)
    # Too soon after the last snapshot.
    assert not ma.need_checkpoint(11, applied + 20, base)[0] or \
        ma.need_checkpoint(11, applied + 20, base)[0] == (11 - 10 >= 4)
    # After the interval, more changes retrigger.
    assert ma.need_checkpoint(20, np.array([30, 3, 12], np.int64), base)[0]


def test_maintain_policy_compaction_gated_on_snapshot():
    ma = MaintainAgreement(2, compact_min_interval=1, compact_slack=2)
    commit = np.array([50, 50], np.int64)
    base = np.array([0, 0], np.int64)
    # No snapshot yet -> no compaction.
    assert list(ma.compact_targets(5, commit, base)) == [0, 0]
    ma.note_checkpoint(0, now=5, index=40)
    t = ma.compact_targets(10, commit, base)
    assert t[0] == 40 and t[1] == 0  # min(snap=40, commit-slack=48)
    ma.note_checkpoint(1, now=10, index=49)
    t = ma.compact_targets(15, commit, base)
    assert t[1] == 48  # min(snap=49, commit-slack=48)


def _watched(G, **kw):
    ma = MaintainAgreement(G, **kw)
    ma.watch_ring(log_slots=64, max_submit=8)
    return ma


def test_watch_ring_states_the_pressure_point():
    # RaftConfig's shape: room for RELEASE_TICKS ticks of full intake.
    assert _watched(1).pressure_at == 64 - 5 * 8 == 24
    # max_submit large against the ring: never under a quarter of it.
    ma = MaintainAgreement(1)
    ma.watch_ring(log_slots=64, max_submit=32)
    assert ma.pressure_at == 16
    # A policy nobody told its ring sees no pressure at all.
    assert MaintainAgreement(1).pressure_at is None


def test_pressure_makes_a_group_due_before_its_interval():
    """g0's ring is past the pressure point one tick after its last
    checkpoint, with 30 entries changed (under the 64-entry threshold):
    due now.  g1 is as full but has applied nothing since its snapshot
    (a checkpoint would release nothing): not due.  g2 is under no
    pressure and waits for the cadence as it always has."""
    ma = _watched(3)
    for g, idx in enumerate((10, 40, 10)):
        ma.note_checkpoint(g, now=100, index=idx)
    applied = np.array([40, 40, 30], np.int64)
    base = np.array([10, 10, 10], np.int64)
    need = ma.need_checkpoint(101, applied, base)
    assert list(need) == [True, False, False]
    assert list(ma.ckpt_pressed) == [True, False, False]
    # The same group by the calendar: due by cadence, not counted as
    # pressure.
    need = ma.need_checkpoint(200, np.array([80, 40, 30], np.int64), base)
    assert need[0] and not ma.ckpt_pressed[0]


def test_pressure_compacts_at_once_but_never_past_the_snapshot():
    ma = _watched(3, compact_min_interval=10, compact_slack=8)
    ma.last_compact_tick[:] = 100
    ma.note_checkpoint(0, now=100, index=30)      # snapshot behind commit
    ma.note_checkpoint(1, now=100, index=60)      # snapshot ahead of slack
    commit = np.array([50, 50, 50], np.int64)     # g2: no snapshot at all
    base = np.array([10, 10, 10], np.int64)
    t = ma.compact_targets(101, commit, base)     # one tick in: not "due"
    assert list(t) == [30, 42, 0]     # min(snapshot, commit - slack); none
    assert list(ma.compact_pressed) == [True, True, False]
    assert ma.last_compact_tick[0] == 101 and ma.last_compact_tick[2] == 100
    # Nothing to release (the target is the base): no grant, no count.
    t = ma.compact_targets(102, commit, np.array([30, 42, 10], np.int64))
    assert list(t) == [0, 0, 0] and not ma.compact_pressed.any()


@pytest.mark.parametrize("watched", [False, True])
def test_no_pressure_gives_the_cadence_arrays(watched):
    """With no ring past the pressure point the policy's outputs are the
    arrays it gave before it knew about rings, tick for tick."""
    rng = np.random.default_rng(5)
    kw = dict(state_change_threshold=12, dirty_log_tolerance=4,
              snap_min_interval=5, compact_min_interval=3, compact_slack=2)
    ma, ref = MaintainAgreement(8, **kw), MaintainAgreement(8, **kw)
    if watched:
        ma.watch_ring(log_slots=64, max_submit=8)
    applied = np.zeros(8, np.int64)
    base = np.zeros(8, np.int64)
    for now in range(1, 120):
        applied += rng.integers(0, 3, 8)
        commit = applied + rng.integers(0, 2, 8)
        a, b = (m.need_checkpoint(now, applied, base) for m in (ma, ref))
        np.testing.assert_array_equal(a, b)
        for g in np.nonzero(a)[0]:
            for m in (ma, ref):
                m.note_checkpoint(int(g), now, int(applied[g]))
        a, b = (m.compact_targets(now, commit, base) for m in (ma, ref))
        np.testing.assert_array_equal(a, b)
        base = np.maximum(base, a)
        assert (applied - base <= 24).all()     # the premise: no pressure
        assert not ma.ckpt_pressed.any() and not ma.compact_pressed.any()


def test_apply_batch_partial_failure_resolves_promises(tmp_path):
    """apply_batch that RAISES mid-batch after partially applying: the
    raise discards every result the batch would have returned, so the
    dispatcher must fail the applied entries' promises loudly ("result
    unavailable", never a hang), resync from the machine's own frontier,
    and resume the remainder normally (machine/dispatch.py batch fast
    path; the lossless alternative is the short-return contract)."""
    from rafting_tpu.testkit.fixtures import NullMachine, NullProvider

    class PartialBatchMachine(NullMachine):
        def __init__(self):
            super().__init__()
            self.fail_once_at = 3

        def apply_batch(self, start_index, payloads):
            out = []
            for k, p in enumerate(payloads):
                idx = start_index + k
                if idx == self.fail_once_at:
                    self.fail_once_at = None
                    # Contract breach on purpose: the entry APPLIED but
                    # the exception loses its result.
                    self._applied = idx
                    raise RuntimeError("burp after applying")
                out.append(self.apply(idx, p))
            return out

    class Prov(NullProvider):
        def bootstrap(self, group):
            return PartialBatchMachine()

    store = {(0, i): b"p%d" % i for i in range(1, 7)}
    d = ApplyDispatcher(Prov(), lambda g, i: store.get((g, i)),
                        payload_window_fn=lambda g, s, n:
                        [store.get((g, s + k)) for k in range(n)])
    futs = {i: Future() for i in range(1, 7)}
    for i, f in futs.items():
        d.register_promise(0, i, f)
    d.advance(np.array([6], np.int32))
    # A RAISING apply_batch discards every result it would have returned
    # (Python loses the return value), so entries 1..3 — all applied per
    # the machine's own frontier — fail LOUDLY with "result unavailable"
    # instead of hanging forever.
    for i in (1, 2, 3):
        assert futs[i].done(), f"promise {i} left hanging"
        with pytest.raises(RuntimeError, match="result unavailable"):
            futs[i].result(timeout=0)
    # The remainder resumes (same tick or the next advance) with results.
    d.advance(np.array([6], np.int32))
    assert d.applied(0) == 6
    for i in (4, 5, 6):
        assert futs[i].result(timeout=0) == i
    d.close()
