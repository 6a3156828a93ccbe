"""The reader of the inbox backlog (transport/inbox.py InboxStats): the
accumulator stamps slices at merge() and records, at drain(), the wait of
every slice popped, the depth left per source, and the slices collapsed
or dropped; the node's tick thread — and no other — folds the record into
its registry (runtime/node.py _fold_inbox_stats).  All on counts."""

import threading

from rafting_tpu.core.types import EngineConfig
from rafting_tpu.testkit.harness import LocalCluster
from rafting_tpu.transport import InboxAccumulator, messages_template

CFG = EngineConfig(n_groups=4, n_peers=3)


def _acc() -> InboxAccumulator:
    return InboxAccumulator(CFG, messages_template(CFG))


def _merge(acc: InboxAccumulator, src: int, n: int = 1) -> None:
    for _ in range(n):
        acc.merge(src, {}, {})      # an empty slice queues like any other


def test_three_slices_one_drain_leave_depth_two_and_one_wait():
    acc = _acc()
    _merge(acc, 1, 3)
    acc.drain()
    st = acc.take_stats()
    assert st.depth == {1: 2}
    assert len(st.waits_s) == 1 and st.waits_s[0] >= 0.0
    assert (st.collapsed, st.dropped) == (0, 0)
    again = acc.take_stats()            # the caller owns what it took
    assert again.waits_s == [] and again.depth == {}


def test_slice_per_tick_stream_keeps_a_standing_backlog():
    """One pop per source per tick never drains a queue that is fed one
    slice per tick: depth 2 stands for 20 drains (PERF.md's defect, as
    counts), and every slice waited behind the two before it."""
    acc = _acc()
    _merge(acc, 2, 2)
    for _ in range(20):
        _merge(acc, 2)
        acc.drain()
        st = acc.take_stats()
        assert st.depth == {2: 2} and len(st.waits_s) == 1
        assert st.collapsed == 0
    assert acc.has_traffic


def test_collapse_is_counted():
    acc = _acc()
    k = InboxAccumulator.COLLAPSE_BACKLOG
    _merge(acc, 1, k)
    acc.drain()
    assert acc.take_stats().collapsed == 0          # k queued: one popped
    _merge(acc, 1, 2)                               # k + 1 queued again
    acc.drain()
    st = acc.take_stats()
    assert st.collapsed == k + 1 and len(st.waits_s) == k + 1
    assert st.depth == {1: 0} and not acc.has_traffic


def test_drop_at_the_bound_is_counted():
    acc = _acc()
    cap = InboxAccumulator.MAX_QUEUED_SLICES
    _merge(acc, 0, cap)
    assert acc.take_stats().dropped == 0
    _merge(acc, 0)                                  # the 65th
    st = acc.take_stats()
    assert st.dropped == 1 and st.waits_s == []
    acc.drain()
    assert acc.take_stats().collapsed == cap


def test_fold_happens_on_the_draining_thread_only(tmp_path):
    """Over real TCP the reader threads call merge(); every inbox_* write
    to the registry still comes from the thread that ticks."""
    writers = {}
    c = LocalCluster(CFG, str(tmp_path), seed=2, transport="tcp")
    try:
        node = c.nodes[0]
        m = node.metrics
        observe, gauge = m.observe, m.gauge

        def rec_observe(name, v):
            writers.setdefault(name, set()).add(threading.get_ident())
            observe(name, v)

        def rec_gauge(name, v):
            writers.setdefault(name, set()).add(threading.get_ident())
            gauge(name, v)

        m.observe, m.gauge = rec_observe, rec_gauge
        c.wait_leader(0)
        c.tick(30)
        assert m.histogram("inbox_wait_s").n > 0
        assert m.histogram("inbox_backlog").n >= 30   # one sample a tick
        me = {threading.get_ident()}
        for name in ("inbox_wait_s", "inbox_backlog", "inbox_backlog_src1",
                     "inbox_backlog_src2"):
            assert writers[name] == me, name
        assert all(ids == me for ids in writers.values())
    finally:
        c.close()
