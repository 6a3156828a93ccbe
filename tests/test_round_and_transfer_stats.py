"""The statistics PR 34 adds to the step's spans and registry, by counts.

``hb_round_s`` (histogram; ``hb_rounds_closed`` counts it; under a profiler
session it rides the ``raft.tail`` span of the step that closes a round):
per period of the engine's clock, the time from the start of the timer's
step that sent the period's heartbeats to the end of the first step whose
drained slices held acknowledgements of that period from every peer the node
leads lanes against.  ``bytes`` beside ``transfers`` on ``raft.dispatch_upload``
and ``raft.scan_fetch``: the packed buffers' sizes.  And the inbox's drain as
the round sees it: a source's queued slices reach the engine one a step in
arrival order, the sources side by side.
"""

import numpy as np
import pytest

from rafting_tpu.core import packing
from rafting_tpu.core.packing import DenseView
from rafting_tpu.core.step import column_layouts, step_layouts
from rafting_tpu.core.types import EngineConfig, LEADER
from rafting_tpu.testkit.harness import LocalCluster
from rafting_tpu.transport import InboxAccumulator, messages_template
from rafting_tpu.utils.metrics import validate_exposition
from rafting_tpu.utils.profiling import StageSpans


def _cfg(**kw) -> EngineConfig:
    base = dict(n_groups=8, n_peers=3, log_slots=32, batch=4, max_submit=4,
                election_ticks=10, heartbeat_ticks=1, rpc_timeout_ticks=8,
                pre_vote=True)
    base.update(kw)
    return EngineConfig(**base)


def _led(node) -> int:
    return int((node.h_role == LEADER).sum())


def _closed(lc):
    return {i: int(n.metrics["hb_rounds_closed"])
            for i, n in lc.nodes.items()}


@pytest.fixture
def one_leader(tmp_path):
    """Three nodes, every group led by node 0 (the others hear of it
    before their own timers run out: node 0 is ticked alone first)."""
    lc = LocalCluster(_cfg(), str(tmp_path), seed=7)
    lc.tick_until(lambda: all(lc.leader_of(g) is not None
                              for g in range(lc.cfg.n_groups)),
                  what="leaders")
    leaders = {lc.leader_of(g) for g in range(lc.cfg.n_groups)}
    if len(leaders) > 1:
        keep = min(leaders)
        for g in range(lc.cfg.n_groups):
            src = lc.leader_of(g)
            if src != keep:
                lc.nodes[src].transfer_leadership(g, keep)
        lc.tick_until(lambda: all(lc.leader_of(g) == keep
                                  for g in range(lc.cfg.n_groups)),
                      what="one leader")
    lc.tick(6)
    yield lc, lc.leader_of(0)
    lc.close()


def test_hb_round_closes_once_a_period_on_a_node_that_leads(one_leader):
    lc, lead = one_leader
    before, t0 = _closed(lc), lc.nodes[lead].timer_ticks
    lc.tick(20)
    after = _closed(lc)
    periods = lc.nodes[lead].timer_ticks - t0
    assert periods == 20
    # In lock step every step is a period and a round is heard of two
    # steps later: twenty periods close twenty rounds, one each.
    assert after[lead] - before[lead] == periods
    h = lc.nodes[lead].metrics.histogram("hb_round_s")
    assert h.n == after[lead] and h.total > 0.0
    # Both are on the /metrics page, from boot on every node.
    for node in lc.nodes.values():
        page = node.metrics.render_prometheus()
        validate_exposition(page)
        assert "raft_hb_rounds_closed_total" in page
    assert "raft_hb_round_s" in lc.nodes[lead].metrics.render_prometheus()


def test_a_timer_step_with_no_heartbeat_due_opens_no_round(tmp_path):
    """A heartbeat every second period (TiKV's cadence): the periods in
    between open no round and close none, whatever a lane sends of its own
    in them (a write's entries here), so twenty periods close ten rounds
    and ``hb_round_s`` stays a reading per round."""
    lc = LocalCluster(_cfg(n_groups=1, heartbeat_ticks=2), str(tmp_path),
                      seed=7)
    try:
        lead = lc.wait_leader(0)
        node = lc.nodes[lead]
        lc.tick_until(lambda: node.is_ready(0), what="leader ready")
        lc.tick(6)
        before = _closed(lc)[lead]
        samples = node.metrics.histogram("hb_round_s").n
        futs, off = [], 0
        for t in range(20):
            if int(node.state.now) + 1 < int(np.asarray(node.state.hb_due)[0]):
                futs.append(node.submit(0, b"w%d" % t))     # no heartbeat due
                off += 1
            lc.tick()
        assert off == 10
        assert _closed(lc)[lead] - before == 10
        assert node.metrics.histogram("hb_round_s").n - samples == 10
        lc.tick(3)
        assert all(f.done() and f.exception() is None for f in futs)
    finally:
        lc.close()


def test_hb_round_is_absent_on_a_node_that_leads_nothing(one_leader):
    lc, lead = one_leader
    before = _closed(lc)        # a node may have led before the fixture's
    samples = {i: n.metrics.histogram("hb_round_s").n   # transfers
               for i, n in lc.nodes.items()}
    lc.tick(10)
    for i, node in lc.nodes.items():
        if i == lead:
            continue
        assert _led(node) == 0
        assert int(node.metrics["hb_rounds_closed"]) == before[i]
        assert node.metrics.histogram("hb_round_s").n == samples[i]
        assert not node._hb_rounds


def test_hb_round_waits_for_both_peers_acknowledgements(one_leader):
    lc, lead = one_leader
    a, b = (i for i in lc.nodes if i != lead)
    lc.faults.set_link(b, lead, False)      # b hears, its replies are lost
    lc.tick(4)                              # rounds in flight run out
    before = _closed(lc)[lead]
    lc.tick(12)
    assert _led(lc.nodes[lead]) == lc.cfg.n_groups      # a's acks: a quorum
    assert _closed(lc)[lead] == before      # a alone closes no round
    lc.faults.set_link(b, lead, True)
    lc.tick(6)
    assert _closed(lc)[lead] > before


def test_hb_acknowledged_matches_the_period_and_the_peer(tmp_path):
    """The two helpers on planes made by hand: only an acknowledgement
    that echoes the round's own clock, from a peer the round is open
    against, strikes that peer."""
    lc = LocalCluster(_cfg(), str(tmp_path), seed=3)
    try:
        node = lc.nodes[0]
        P, G = node.cfg.n_peers, node.cfg.n_groups

        out = DenseView({"ae_valid": np.zeros((P, G), bool),
                         "ae_tick": np.full((P, G), 41, np.int32)})
        out.planes["ae_valid"][1, 2] = out.planes["ae_valid"][2, 5] = True
        node._hb_rounds.clear()
        node._hb_open(out, started=1.5)
        assert [r[:2] + [sorted(r[2])] for r in node._hb_rounds] == \
            [[41, 1.5, [1, 2]]]

        def acks(rows):
            arrays = {"aer_valid": np.zeros((P, G), bool),
                      "aer_tick": np.zeros((P, G), np.int32)}
            for p, g, tick in rows:
                arrays["aer_valid"][p, g] = True
                arrays["aer_tick"][p, g] = tick
            return DenseView(arrays)

        node._hb_acknowledged(acks([(1, 2, 40), (2, 5, 40)]))   # last period
        assert node._hb_closed is None and len(node._hb_rounds) == 1
        node._hb_acknowledged(acks([(1, 2, 41)]))
        assert node._hb_closed is None
        assert node._hb_rounds[0][2] == {2}
        node._hb_acknowledged(acks([(1, 3, 41)]))               # peer 1 again
        assert node._hb_closed is None
        node._hb_acknowledged(acks([(2, 5, 41)]))
        assert node._hb_closed == 1.5 and not node._hb_rounds
        # A node that addresses no AppendEntries opens nothing.
        node._hb_closed = None
        out.planes["ae_valid"][:] = False
        node._hb_open(out, started=2.5)
        assert not node._hb_rounds
    finally:
        lc.close()


@pytest.mark.parametrize("columns", [False, True], ids=["packed", "columns"])
def test_transfer_spans_carry_the_packed_layouts_bytes(tmp_path, monkeypatch,
                                                       small, columns):
    """Every ``st.note`` of a step, caught where it is written: the upload
    and the fetch say how many buffers crossed and how many bytes, and
    those are the layouts' own sizes; on a shape whose messages cross as
    columns (forced here by a small ``CHUNK_BYTES``; ``COLUMNS`` = G, so
    nothing overflows) they are one column pair and one row pair each
    way, beside the [G] planes' buffers only where those crossed whole, and
    ``columns`` and ``rows`` are the counts that crossed."""
    if columns:
        small(None, None, columns=_cfg().n_groups)
    lc = LocalCluster(_cfg(), str(tmp_path), seed=5)
    try:
        lc.tick(3)
        node = lc.nodes[1]
        notes = []
        real = StageSpans.note

        def spy(self, **stats):
            if self is node._stages:
                notes.append((self._name, stats))
            return real(self, **stats)

        monkeypatch.setattr(StageSpans, "note", spy)
        outboxes, host_phase = [], node._host_phase
        node._host_phase = lambda ctx: (outboxes.append(ctx.outbox),
                                        host_phase(ctx))[1]
        node.tick()
        (fetched,) = outboxes
        inputs, readback = step_layouts(node.cfg, True)
        lay = column_layouts(node.cfg, True)
    finally:
        monkeypatch.undo()
        step_layouts.cache_clear()
        column_layouts.cache_clear()
        lc.close()

    def size(layout):
        return sum(n * np.dtype(dt).itemsize for dt, n in layout.buffers)

    by_phase = {name: kw for name, kw in notes if "bytes" in kw}
    assert set(by_phase) == {"dispatch_upload", "scan_fetch"}
    up, down = by_phase["dispatch_upload"], by_phase["scan_fetch"]
    assert (lay is not None) == columns
    if columns:
        # ONE buffer each way, the rows and behind them the columns, and
        # the [G] planes' buffers beside it only where a span says they
        # crossed whole (``planes_dense``: rows is then 0).
        for span, planes, rows in ((up, lay.host, lay.rows_in),
                                   (down, lay.back, lay.rows_out)):
            whole = span["planes_dense"]
            assert whole in (0, 1) and span["rows"] <= rows.K * (1 - whole)
            assert span["transfers"] == 1 + len(planes.buffers) * whole
            assert span["bytes"] == lay.columns.nbytes + rows.nbytes \
                + size(planes) * whole > 0
        assert up["dense"] == down["dense"] == 0
        assert down["columns"] == fetched.columns
    else:
        assert up["transfers"] == len(inputs.buffers)
        assert down["transfers"] == len(readback.buffers)
        assert up["bytes"] == size(inputs) > 0
        assert down["bytes"] == size(readback) > 0
        assert (up["dense"], up["columns"]) == (1, 0)
        assert (down["dense"], down["columns"]) == (1, 0)
        assert (up["planes_dense"], up["rows"]) == (1, 0)
        assert (down["planes_dense"], down["rows"]) == (1, 0)
        assert packing.CHUNK_BYTES >= max(up["bytes"], down["bytes"]) // max(
            up["transfers"], down["transfers"]) > 0


# ------------------------------------------------------------------ inbox

ACC_CFG = EngineConfig(n_groups=8, n_peers=3)


def _slice(kind_valid: str, groups, **planes):
    cols = np.asarray(groups, np.int64)
    fields = {kind_valid: (cols, np.ones(len(cols), bool))}
    for name, vals in planes.items():
        fields[name] = (cols, np.asarray(vals, np.int32))
    return fields


def test_slices_from_one_source_drain_one_a_step_in_arrival_order():
    """The drain as it stands (ISSUE 34's many-slices drain was measured
    and left to a ``perf_opt``, PERF.md PR 34): a source's queued slices
    reach the engine one a step, oldest first, whatever lanes they fill,
    so no (kind, group) stream is ever reordered."""
    acc = InboxAccumulator(ACC_CFG, messages_template(ACC_CFG))
    acc.merge(2, _slice("ae_valid", [4], ae_term=[1]), {})
    acc.merge(2, _slice("ae_valid", [4, 5], ae_term=[2, 2]), {})    # lane 4
    acc.merge(2, _slice("aer_valid", [6], aer_term=[3]), {})    # disjoint
    seen = []
    for _ in range(4):
        arrays, _ = acc.drain()
        seen.append((arrays["ae_valid"][2].nonzero()[0].tolist(),
                     arrays["ae_term"][2, 4:6].tolist(),
                     arrays["aer_valid"][2].nonzero()[0].tolist()))
    assert seen == [([4], [1, 0], []),
                    ([4, 5], [2, 2], []),
                    ([], [0, 0], [6]),
                    ([], [0, 0], [])]
    st = acc.take_stats()
    assert st.collapsed == 0 and len(st.waits_s) == 3


def test_slices_of_different_sources_drain_in_one_step():
    acc = InboxAccumulator(ACC_CFG, messages_template(ACC_CFG))
    acc.merge(1, _slice("aer_valid", [1, 2], aer_tick=[7, 7]), {})
    acc.merge(2, _slice("aer_valid", [1, 3], aer_tick=[7, 7]), {})
    arrays, _ = acc.drain()
    st = acc.take_stats()
    assert st.depth == {1: 0, 2: 0} and not acc.has_traffic
    assert arrays["aer_valid"][1].nonzero()[0].tolist() == [1, 2]
    assert arrays["aer_valid"][2].nonzero()[0].tolist() == [1, 3]
    assert not arrays["aer_valid"][0].any()
