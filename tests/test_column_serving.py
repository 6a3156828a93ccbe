"""Serving with the step's messages in column form (core/packing.py
ColumnLayout, core/step.py node_step_columns, PERF.md PR 35).

At 10,000 lanes and more the dense message planes do not fit one packed
buffer, and there a step's messages cross as the columns that moved; a step
whose messages do not fit the column buffers (a heartbeat round, an
election) crosses densely, decided by a count.  These tests force both at
a size a CPU holds, by a small ``CHUNK_BYTES`` (the shape rule engages) and
a small ``COLUMNS`` (rounds and elections overflow): (a) a lock-step
cluster whose every step is cross-checked against the scalar oracle while
it elects, serves single operations and runs its heartbeat rounds; (b)
three served containers that take writes and linearizable reads through
``RaftStub`` over such steps, pass ``testkit/linz.py``, and whose four
counters say what the spans' ``columns`` / ``dense`` say.
"""

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rafting_tpu.runtime.node as node_mod
from rafting_tpu.api import RaftConfig, RaftContainer
from rafting_tpu.core import packing
from rafting_tpu.core.step import (
    _host_from_rows, column_layouts, pack_readback)
from rafting_tpu.core.types import EngineConfig, LEADER
from rafting_tpu.testkit import linz
from rafting_tpu.testkit.fixtures import NullProvider
from rafting_tpu.testkit.harness import (
    LocalCluster, free_ports, kv_factory, scaled_election_mul)
from rafting_tpu.testkit.history import History
from rafting_tpu.testkit.oracle import oracle_step
from rafting_tpu.testkit.parity import (
    assert_info_equal, assert_messages_equal, assert_state_equal)
from rafting_tpu.transport import InboxAccumulator, messages_template
from rafting_tpu.transport.codec import KIND_FIELDS
from rafting_tpu.transport.inbox import fill_columns, scatter_dense
from rafting_tpu.utils.profiling import StageSpans

K = 3                       # columns a peer row: four led lanes overflow
ROWS = 3                    # rows each way: four lanes at once overflow
COUNTERS = ("steps_columns_in", "column_overflows_in",
            "steps_columns_out", "column_overflows_out",
            "steps_rows_in", "row_overflows_in",
            "steps_rows_out", "row_overflows_out")


@pytest.fixture
def small_columns(small):
    """This file's sizes of ``small`` (tests/conftest.py): a buffer closed
    every 2 KB, K columns a row, ROWS rows a step."""
    small(ROWS, ROWS, columns=K, chunk_bytes=2048)


@pytest.fixture
def noted(monkeypatch):
    """Every ``st.note`` of the upload and the fetch, and every ``scanned``
    of the host phase, caught where it is written: node -> phase ->
    [statistics]."""
    seen = {}
    real = StageSpans.note

    def spy(self, **stats):
        if "dense" in stats or "scanned" in stats:
            seen.setdefault(self._node, {}).setdefault(
                self._name, []).append(stats)
        return real(self, **stats)

    monkeypatch.setattr(StageSpans, "note", spy)
    return seen


def assert_scanned_follows_the_rows(node, notes):
    """``scanned`` on the four host-phase spans sums to the counter
    ``host_lanes_scanned``.  The persist stage makes six selections: a
    step that looked at every lane scanned ``6 * n_groups`` there (every
    selection a pass over the planes), and on a node whose Readback comes
    down as rows most steps scanned less than that, down to nothing: what
    they looked at are the rows that moved."""
    G = node.cfg.n_groups
    assert set(notes) >= {"wal", "apply", "reads", "maintain"}
    spans = [s["scanned"] for phase in ("wal", "apply", "reads", "maintain")
             for s in notes[phase]]
    assert sum(spans) == node.metrics["host_lanes_scanned"] > 0
    wal = [s["scanned"] for s in notes["wal"]]
    whole = [n for n in wal if n >= 6 * G]
    assert whole and all(n % G == 0 for n in whole), whole[:5]
    by_rows = [n for n in wal if n < 6 * G]
    assert len(by_rows) > len(wal) // 2, (len(by_rows), len(wal))
    assert any(by_rows), "no row step ever had a row to look at"
    assert all(n % 6 == 0 for n in by_rows)     # six selections, one id set
    # ... and far less in all than looking at every lane every step would.
    assert sum(wal) < len(wal) * 6 * G // 2


def assert_counters_match_spans(node, notes, maybe=()):
    """The eight counters are the spans' ``dense`` and ``planes_dense``
    counted, a dense step carries no column count and a step whose [G]
    planes crossed whole no row count; every form was taken, and a span's
    bytes and transfers are those of the forms it says."""
    m = node.metrics
    up, down = notes["dispatch_upload"], notes["scan_fetch"]
    assert m["steps_columns_in"] == sum(not s["dense"] for s in up)
    assert m["column_overflows_in"] == sum(s["dense"] for s in up)
    assert m["steps_columns_out"] == sum(not s["dense"] for s in down)
    assert m["column_overflows_out"] == sum(s["dense"] for s in down)
    assert m["steps_rows_in"] == sum(not s["planes_dense"] for s in up)
    assert m["row_overflows_in"] == sum(s["planes_dense"] for s in up)
    assert m["steps_rows_out"] == sum(not s["planes_dense"] for s in down)
    assert m["row_overflows_out"] == sum(s["planes_dense"] for s in down)
    assert all(s["columns"] == 0 for s in up + down if s["dense"])
    assert all(s["rows"] == 0 for s in up + down if s["planes_dense"])
    assert all(s["rows"] <= ROWS for s in up + down)
    assert all(m[name] > 0 for name in COUNTERS if name not in maybe), \
        {name: m[name] for name in COUNTERS}
    assert sum(s["columns"] for s in up) > 0
    assert sum(s["columns"] for s in down) > 0
    assert sum(s["rows"] for s in up) > 0
    assert sum(s["rows"] for s in down) > 0
    lay = column_layouts(node.cfg, True)
    # Up: ONE buffer, the rows and behind them the messages' columns, or
    # the rows alone beside the dense operand (whose buffers hold
    # HostInbox's planes too); HostInbox's planes beside the one buffer
    # only when they cross whole.  Down: ONE buffer, the rows and the
    # outbox's columns, and what either says it does not hold.
    size = lambda layout: sum(b.nbytes for b in layout.alloc())
    for s in up:
        planes = lay.inputs if s["dense"] else \
            lay.host if s["planes_dense"] else None
        assert s["bytes"] == lay.rows_in.nbytes \
            + (0 if s["dense"] else lay.columns.nbytes) \
            + (size(planes) if planes else 0), s
        assert s["transfers"] == 1 + (len(planes.buffers) if planes else 0), s
    for s in down:
        assert s["transfers"] == 1 \
            + len(lay.back.buffers) * s["planes_dense"] \
            + len(lay.outbox.buffers) * s["dense"], s
        assert s["bytes"] == lay.rows_out.nbytes + lay.columns.nbytes \
            + size(lay.back) * s["planes_dense"] \
            + size(lay.outbox) * s["dense"], s
    # A step that fits crosses in one array each way.
    assert any(s["transfers"] == 1 for s in up)
    assert any(s["transfers"] == 1 for s in down)


# ------------------------------------------- (a) lock step, oracle-checked ----


@pytest.fixture
def oracle_checked_columns(monkeypatch):
    """Cross-check every runtime node_step_columns call against the scalar
    oracle, whichever way the messages went in: the oracle steps what the
    upload buffers stand for, and state, dense outbox and StepInfo must be
    the oracle's (oracle FIRST: the step donates its state), the
    ``durable_tail`` clamp every node feeds included."""
    real = node_mod.node_step_columns
    calls = {True: 0, False: 0}

    def checked(cfg, lay, columns_in, state, carry, buffers):
        *bufs, rows = jax.device_get(buffers)
        if columns_in:
            rows, columns = packing.regions(rows, lay.rows_in, lay.columns)
            host = lay.host.unpack(bufs)
            inbox = lay.columns.expand(columns)
        else:
            host, inbox = lay.inputs.unpack(bufs)
        # The HostInbox the rows stand for, over the planes they ride
        # beside (the resident zero planes, or the planes whole).
        host, _ = _host_from_rows(
            lay.rows_in, *jax.tree.map(jnp.asarray, (host, rows)),
            carry.durable)
        assert host.durable_tail is not None
        host, inbox = jax.tree.map(jnp.asarray, (host, inbox))
        o_state, o_out, o_info = oracle_step(cfg, state, inbox, host)
        out = real(cfg, lay, columns_in, state, carry, buffers)
        k_state, k_carry, pair, dense = out
        tag = f"oracle-checked column step #{sum(calls.values())}"
        assert_state_equal(k_state, o_state, tag)
        assert_messages_equal(lay.columns.unstack(dense), o_out, tag)
        assert_info_equal(lay.back.unpack(jax.device_get(pack_readback(
            lay, k_carry))).info, o_info, tag)
        calls[bool(columns_in)] += 1
        return out

    monkeypatch.setattr(node_mod, "node_step_columns", checked)
    return calls


# Reads by the lease, and by strict ReadIndex (read_lease off: the step
# and the layouts gain read_seq / ae_seq / aer_seq, PR 45).
READS = [pytest.param(True, id="lease"), pytest.param(False, id="strict")]


@pytest.mark.parametrize("lease", READS)
def test_column_steps_match_the_oracle_through_election_rounds_and_traffic(
        tmp_path, small_columns, oracle_checked_columns, noted, lease):
    cfg = EngineConfig(n_groups=16, n_peers=3, log_slots=16, batch=4,
                       max_submit=4, election_ticks=8, heartbeat_ticks=4,
                       rpc_timeout_ticks=6, pre_vote=True, read_lease=lease)
    assert column_layouts(cfg, True) is not None
    c = LocalCluster(cfg, str(tmp_path), provider_factory=NullProvider,
                     seed=5)
    try:
        for g in range(cfg.n_groups):       # the election: 16 lanes at once
            assert c.wait_leader(g, max_rounds=200) is not None
        for t in range(60):                 # rounds every fourth tick, and
            n = c.nodes[t % 3]              # single operations between them
            led = np.nonzero((n.h_role == LEADER) & n.h_ready)[0]
            if len(led):
                g = int(led[t % len(led)])
                n.submit_batch(g, [b"w%d" % t])
                n.read(g, b"r%d" % t)
            if t % 20 == 10:                # a write to every led lane at
                for g in led.tolist():      # once: more than ROWS of them
                    n.submit_batch(g, [b"burst%d" % t])
            c.tick()
        assert min(oracle_checked_columns.values()) > 20, \
            oracle_checked_columns
        assert sum(int(n.h_commit.astype(np.int64).sum())
                   for n in c.nodes.values()) > 0
        for i, n in c.nodes.items():
            # HostInbox overflows its rows on every node: the first step
            # uploads the durable tail whole.
            assert_counters_match_spans(n, noted[i])
        assert sum(n.metrics["row_overflows_in"]
                   for n in c.nodes.values()) > 0
    finally:
        c.close()


# ------------------------------------------------ (b) served, linearizable ----


@pytest.fixture(params=READS)
def served(request, tmp_path, small_columns):
    ports = free_ports(3)
    uris = [f"raft://127.0.0.1:{p}" for p in ports]
    cs = [RaftContainer(RaftConfig(
        local=u, peers=tuple(p for p in uris if p != u), n_groups=16,
        log_slots=32, batch=4, max_submit=4, tick_ms=50, seed=3,
        read_lease=request.param, data_dir=str(tmp_path / f"node{i}"),
        election_mul=scaled_election_mul(10)), kv_factory()).create()
        for i, u in enumerate(uris)]
    yield cs
    for c in cs:
        c.destroy()


def _cmd(op, k, v=None):
    d = {"op": op, "k": k}
    if v is not None:
        d["v"] = v
    return json.dumps(d)


def test_served_cluster_over_columns_is_linearizable(noted, served):
    cs = served
    names = [f"kv{i}" for i in range(12)]   # twelve groups: their election
    for c in cs:                            # and their rounds overflow K
        for i, name in enumerate(names):
            assert c.open_context(name) == i + 1
    assert column_layouts(cs[0].node.cfg, True) is not None
    stubs = [[c.get_stub(name) for name in names] for c in cs]
    model = {}
    for i in range(12):
        k, v = f"s{i % 4}", f"seq-{i}"
        assert stubs[i % 3][i].execute(_cmd("set", k, v), timeout=30) == v
        model[(i, k)] = v
        got = stubs[(i + 1) % 3][i].execute_read(_cmd("get", k), timeout=30)
        assert got == v, (i, got)
    # A write to every group at once, through each member in turn: the
    # member that leads four lanes or more (one does) finds more queued
    # writes than HostInbox's rows hold and uploads its planes whole, and
    # their commits move more lanes than the Readback's rows hold.
    for r in range(6):
        futs = [stubs[r % 3][i].submit(_cmd("set", "burst", f"b{r}-{i}"))
                for i in range(12)]
        for i, fut in enumerate(futs):
            assert fut.result(timeout=30) == f"b{r}-{i}"
            model[(i, "burst")] = f"b{r}-{i}"
    # Concurrent phase on one group: three recording clients, one a member.
    history = History()
    rec = [c.get_stub(names[0]).attach_history(history, f"c{i}")
           for i, c in enumerate(cs)]

    def client(i):
        rng = np.random.default_rng(200 + i)
        for seq in range(25):
            k = f"r{rng.integers(3)}"
            try:
                if rng.random() < 0.5:
                    rec[i].execute_read(_cmd("get", k), timeout=10)
                else:
                    rec[i].execute(_cmd("set", k, f"c{i}-{seq}"), timeout=10)
            except Exception:
                pass        # recorded as fail or info by the stub

    threads = [threading.Thread(target=client, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    counts = history.counts()
    assert counts["ok"] >= 40, counts
    res = linz.check(history)
    assert res.ok, res.render()
    # The three replicas end identical, group by group.
    deadline = time.monotonic() + 20
    for g in range(1, 13):
        machines = [c.node.dispatcher.machine(g) for c in cs]
        while time.monotonic() < deadline and len(
                {m.last_applied() for m in machines}) != 1:
            time.sleep(0.05)
        assert machines[0].data == machines[1].data == machines[2].data
    for (i, k), v in model.items():
        assert cs[0].node.dispatcher.machine(i + 1).data[k] == v
    for row in stubs:
        for s in row:
            s.close()
    for s in rec:
        s.close()
    # Stop the loops, then read what they counted against what they noted.
    for c in cs:
        c.node._stop.set()
        c.node._wake.set()
    for c in cs:
        if c.node._thread is not None:
            c.node._thread.join(timeout=30)
    for i, c in enumerate(cs):
        assert_counters_match_spans(c.node, noted[c.node.node_id],
                                    maybe=("row_overflows_in",))
        assert_scanned_follows_the_rows(c.node, noted[c.node.node_id])
    # (A serial node uploads no durable tail, so only the bursts overflow
    # HostInbox's rows, on the member that leads most.)
    assert sum(c.node.metrics["row_overflows_in"] for c in cs) > 0
    assert all(c.node.metrics["row_overflows_out"] > 1 for c in cs)


# --------------------------------------- (c) the drain's two destinations ----


def _random_slice(rng, template, G, n_max):
    """One unpacked slice as codec.unpack_slice hands it over: per kind
    one lanes array shared by its fields; now and then a kind twice (the
    eager / deferred split), its lanes concatenated and so not shared."""
    fields = {}
    for kind, (vfield, dfields) in KIND_FIELDS.items():
        if rng.random() < 0.5:
            continue
        parts = []
        for _ in range(1 + (rng.random() < 0.3)):
            cols = np.sort(rng.choice(G, rng.integers(1, n_max + 1),
                                      replace=False)).astype(np.int64)
            parts.append((cols, {f: rng.integers(
                1, 50, (len(cols),) + template[f][1]).astype(template[f][0])
                for f in dfields}))
        if len(parts) == 1:
            cols, vals = parts[0]
            fields[vfield] = (cols, np.ones(len(cols), bool))
            fields.update({f: (cols, v) for f, v in vals.items()})
        else:
            cat = lambda xs: np.concatenate(xs)
            fields[vfield] = (cat([c for c, _ in parts]),
                              np.ones(sum(len(c) for c, _ in parts), bool))
            for f in dfields:
                fields[f] = (cat([c for c, _ in parts]),
                             cat([v[f] for _, v in parts]))
    return fields


@pytest.mark.parametrize("seed", range(6))
def test_columns_are_filled_as_dense_planes_are(seed, small_columns,
                                                monkeypatch):
    """What a drain pops lands in the column buffers exactly as it lands
    in dense planes: one slice a source, a collapsed backlog of several
    (the newest wins a lane, kind by kind), a kind in two sections; and a
    source with more columns than the buffers take fills nothing."""
    cfg = EngineConfig(n_groups=128, n_peers=3)
    monkeypatch.setattr(packing, "COLUMNS", cfg.n_groups)
    column_layouts.cache_clear()
    lay = column_layouts(cfg, True).columns
    template = messages_template(cfg)
    rng = np.random.default_rng(seed)
    acc = InboxAccumulator(cfg, template)
    for src in (1, 2):
        for _ in range(1 if (seed + src) % 2 else 5):     # 5: a collapse
            acc.merge(src, _random_slice(rng, template, cfg.n_groups, 3), {})
    batches, _ = acc.pop()
    assert {len(b) for b in batches.values()} == {1, 5}
    dense = {name: np.zeros((3, cfg.n_groups) + trail, dt)
             for name, (dt, trail) in template.items()}
    scatter_dense(batches, dense)
    pair = lay.alloc()
    view = lay.view(pair)
    assert fill_columns(batches, view)
    back = lay.expand(pair)
    for name, plane in dense.items():
        np.testing.assert_array_equal(getattr(back, name), plane, name)
        np.testing.assert_array_equal(view.dense(name), plane, name)
    for p in range(3):
        lanes = view.cols[p, :view.n[p]]
        assert (np.diff(lanes) > 0).all() and (view.cols[p, view.n[p]:]
                                               == cfg.n_groups).all()
    # One source past K: nothing is written, the step goes dense.
    monkeypatch.setattr(packing, "COLUMNS", max(view.n) - 1)
    column_layouts.cache_clear()
    lay = column_layouts(cfg, True).columns
    empty = lay.alloc()
    assert not fill_columns(batches, lay.view(empty))
    np.testing.assert_array_equal(empty, lay.alloc())
    # ... also when no single kind of it is: the union counts.
    monkeypatch.setattr(packing, "COLUMNS", 4)
    column_layouts.cache_clear()
    lay = column_layouts(cfg, True).columns
    wide = np.arange(lay.K + 1, dtype=np.int64)
    batches[1].append({"tn_valid": (wide, np.ones(len(wide), bool)),
                       "tn_term": (wide, np.ones(len(wide), np.int32))})
    empty = lay.alloc()
    assert not fill_columns(batches, lay.view(empty))
    np.testing.assert_array_equal(empty, lay.alloc())
