"""The [G] planes of a column step as rows (core/packing.py RowLayout,
core/step.py node_step_columns, PERF.md PR 38): HostInbox goes up as the rows
of the lanes that have something to say and the Readback comes down as the
rows of the lanes that moved, and the step, the mirrors a host patches from
the rows, the device's own ``durable_tail`` plane and the node's running
gauges are what the dense planes give, bit for bit."""

import errno

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rafting_tpu.runtime.node as node_mod
from rafting_tpu.core import packing
from rafting_tpu.core.cluster import route
from rafting_tpu.core.step import (
    Readback, column_layouts, compact_readback, first_carry, node_step,
    node_step_columns, pack_readback, step_layouts)
from rafting_tpu.core.types import (
    EngineConfig, HostInbox, I32_SAFE_MAX, LEADER, Messages, NIL, init_state)
from rafting_tpu.testkit.fixtures import NullProvider
from rafting_tpu.log.wal import native_available
from rafting_tpu.testkit.harness import LocalCluster, wal_store_factory

BASE = dict(n_groups=16, log_slots=16, batch=4, max_submit=4,
            election_ticks=8, heartbeat_ticks=3, rpc_timeout_ticks=6)


def assert_trees_equal(a, b, tag=""):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb, tag
    for i, (x, y) in enumerate(zip(la, lb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      f"{tag} leaf {i}")


def _on_columns(msgs):
    """``msgs`` with everything outside the columns that hold a valid
    message zeroed: what a drain delivers, in either form."""
    occ = np.any([np.asarray(getattr(msgs, f))
                  for f in msgs.__dataclass_fields__
                  if f.endswith("_valid")], axis=0)
    return jax.tree.map(
        lambda a: np.where(occ.reshape(occ.shape + (1,) * (a.ndim - 2)),
                           a, np.zeros((), a.dtype)), msgs)


# ------------------------------------------ the host's side, as a model ----


class RowHost:
    """What runtime/node.py does with the rows, written the plain way: the
    upload of one HostInbox as rows (or whole, by the count), and a mirror
    of the Readback patched from the rows that come down (or taken whole,
    by the count)."""

    def __init__(self, lay):
        self.lay = lay
        self.carry = first_carry(lay)
        self.sent = None                # the durable plane the device holds
        self.words, self.flags = lay.rows_out.planes()
        self.first = True
        self.seen = dict(rows_in=0, whole_in=0, rows_out=0, whole_out=0)

    def upload(self, host: HostInbox) -> tuple:
        """(HostInbox's planes or None, the row buffer) for one step."""
        rl = self.lay.rows_in
        said = (host.submit_n != 0) | host.snap_done | (host.compact_to != 0) \
            | (host.conf_voters != 0) | (host.xfer_target != NIL) \
            | (host.read_n != 0)
        durable = host.durable_tail
        if durable is not None and self.sent is not None:
            said = said | (durable != self.sent)
        ids = np.nonzero(said)[0]
        if len(ids) > rl.K or (durable is not None and self.sent is None):
            self.seen["whole_in"] += 1
            self.sent = None if durable is None else durable.copy()
            return host, rl.whole(host)
        self.seen["rows_in"] += 1
        buf = rl.alloc()
        view = rl.view(buf)
        view.set_n(len(ids))
        view.ids[:len(ids)] = ids
        view.set_head("read_veto", host.read_veto)
        view.set_head("clock", host.clock)
        for name in packing.lane_names(host, rl.G):
            view.field(name)[:len(ids)] = getattr(host, name)[ids]
        if durable is not None:
            self.sent[ids] = durable[ids]
        return None, buf

    def fetch(self, rows, tag) -> Readback:
        """The mirror after one step's results, patched from the fetched
        row buffer or taken whole (``pack_readback``) when they do not
        fit; the events are the step's own until :meth:`done`."""
        rl = self.lay.rows_out
        view = rl.view(rows)
        n = view.n
        if n > rl.K or self.first:
            self.first = False
            self.seen["whole_out"] += 1
            whole = self.lay.back.unpack(jax.device_get(
                pack_readback(self.lay, self.carry)))
            rl.copy_levels(whole, self.words, self.flags)
            self.moved = None
            return whole
        self.seen["rows_out"] += 1
        ids = self.moved = view.ids[:n]
        assert (np.diff(ids) > 0).all() and (view.ids[n:] == rl.G).all(), tag
        self.flags[:, ids] = view.flags[:, :n]
        self.words[:, ids] = view.words[:, :n]
        return rl.unstack(self.words, self.flags, view.header)

    def done(self):
        """The step's host phase is over: its events are cleared at the
        rows that wrote them."""
        if self.moved is not None:
            rl = self.lay.rows_out
            self.words[rl.Lw:, self.moved] = 0
            self.flags[rl.Lf:, self.moved] = False


def one_up(lay, rows, inbox) -> tuple:
    """What a step that fits uploads: ONE word buffer, HostInbox's row
    buffer and behind it the inbox's columns, allocated once and filled
    through the views the runtime fills (``alloc_regions``); the regions
    read back what was written, count for count and field for field."""
    up, (r, c) = packing.alloc_regions(lay.rows_in, lay.columns)
    assert up.dtype == np.int32 and up.shape == (
        lay.rows_in.size + lay.columns.size,)
    assert lay.rows_in.view(r).n == 0 and (lay.columns.view(c).n == 0).all()
    r[...] = rows
    c[...] = lay.columns.compact(inbox)
    held = _on_columns(inbox)
    view = lay.columns.view(packing.regions(up, lay.rows_in, lay.columns)[1])
    for name in inbox.__dataclass_fields__:
        if getattr(inbox, name) is not None and (view.n <= lay.columns.K).all():
            np.testing.assert_array_equal(view.dense(name),
                                          getattr(held, name), name)
    for a, b in zip(lay.rows_in._parts(up[:lay.rows_in.size]),
                    lay.rows_in._parts(rows)):
        np.testing.assert_array_equal(a, b)
    return (up,)


def one_down(lay, down, columns, p_out, tag) -> tuple:
    """What a column step's fetch brings down: ONE word buffer, the
    Readback's row buffer and behind it the outbox's columns as the step
    returned them, which are the dense outbox compacted.  Returns the two
    regions."""
    down = jax.device_get(down)
    assert down.dtype == np.int32 and down.shape == (
        lay.rows_out.size + lay.columns.size,), tag
    rows, cols = packing.regions(down, lay.rows_out, lay.columns)
    assert np.shares_memory(rows, down) and np.shares_memory(cols, down)
    np.testing.assert_array_equal(cols, jax.device_get(columns), tag)
    np.testing.assert_array_equal(
        cols, lay.columns.compact(jax.device_get(p_out)), tag)
    return rows, cols


def assert_mirror_is(back: Readback, want: Readback, tag: str):
    """Every leaf the dense planes give; ``submit_start`` (``log.last + 1``
    on every lane) where the step accepted something, which is where it is
    read."""
    took = np.asarray(want.info.submit_acc) > 0
    fix = lambda b: b._replace(info=b.info.replace(
        submit_start=np.where(took, np.asarray(b.info.submit_start), 0)))
    assert_trees_equal(fix(back), fix(jax.device_get(want)), tag)


def _sparse_host(cfg, rng, t, commit, durable):
    """A HostInbox as a step of a served node builds it: a few lanes with
    writes and reads, now and then a snapshot done, a membership change,
    a transfer or a compaction grant on one lane."""
    G = cfg.n_groups
    host = jax.device_get(HostInbox.empty(cfg))
    z = lambda dt=np.int32: np.zeros(G, dt)
    f = dict(submit_n=z(), read_n=z(), snap_done=z(bool), snap_idx=z(),
             snap_term=z(), snap_conf=z(), compact_to=z(), conf_voters=z(),
             conf_learners=z(), xfer_target=np.full(G, NIL, np.int32))
    busy = rng.choice(G, rng.integers(0, 4), replace=False)
    f["submit_n"][busy] = rng.integers(1, cfg.max_submit + 1, len(busy))
    busy = rng.choice(G, rng.integers(0, 3), replace=False)
    f["read_n"][busy] = rng.integers(1, 3, len(busy))
    g = int(rng.integers(G))
    if t % 11 == 5:                 # a snapshot installed at the commit
        f["snap_done"][g] = True
        f["snap_idx"][g] = commit[g] + 2
        f["snap_term"][g] = 1
    if t % 13 == 7:                 # drop peer 2, then take it back
        f["conf_voters"][g] = 0b011 if (t // 13) % 2 else 0b111
    if t % 17 == 3:
        f["xfer_target"][g] = (t // 17) % cfg.n_peers
    if t % 7 == 2:
        f["compact_to"][g] = max(int(commit[g]) - 1, 0)
    if t == 20:                     # a burst: more lanes than rows hold
        f["submit_n"][:] = 1
    return host.replace(durable_tail=durable, read_veto=np.asarray(t % 19 == 0),
                        clock=np.asarray(int(t % 3 != 1), np.int32), **f)


@pytest.mark.parametrize("k_in, k_out", [(16, 16), (4, 5), (1, 2)],
                         ids=["roomy", "tight", "cramped"])
@pytest.mark.parametrize("columns_in", [True, False],
                         ids=["columns-in", "dense-in"])
@pytest.mark.parametrize("durable", [True, False], ids=["durable", "serial"])
def test_rows_in_and_out_are_the_dense_planes_bit_for_bit(
        small, columns_in, k_in, k_out, durable):
    """Three nodes over cut links, stepped through ``node_step`` on dense
    planes and through ``node_step_columns`` with HostInbox as rows and
    the Readback as rows, the messages as columns or densely: state,
    outbox, every mirror the host patches and the device's durable plane
    agree after every one of 60 steps, whichever way each part crossed."""
    small(k_in, k_out, columns=16)
    cfg = EngineConfig(n_peers=3, **BASE)
    N, G = cfg.n_peers, cfg.n_groups
    lay = column_layouts(cfg, durable)
    assert lay is not None
    assert (lay.rows_in.K, lay.rows_out.K) == (k_in, k_out)
    rng = np.random.default_rng(7)
    plain = [init_state(cfg, n, seed=3) for n in range(N)]
    rows = [init_state(cfg, n, seed=3) for n in range(N)]
    hosts = [RowHost(lay) for _ in range(N)]
    resident = lay.host.pack(jax.device_get(HostInbox.empty(cfg)).replace(
        durable_tail=np.zeros(G, np.int32) if durable else None))
    outboxes = [jax.device_get(Messages.empty(cfg))] * N
    tails = [np.zeros(G, np.int32)] * N
    commits = [np.zeros(G, np.int32)] * N
    for t in range(60):
        inflight = jax.tree.map(lambda *a: np.stack(a), *outboxes)
        inboxes = jax.device_get(
            route(inflight, jnp.asarray(rng.random((N, N)) > 0.3)))
        outboxes = []
        for n in range(N):
            tag = f"step {t} node {n}"
            inbox = _on_columns(jax.tree.map(lambda a: a[n], inboxes))
            host = _sparse_host(cfg, rng, t, commits[n],
                                tails[n] if durable else None)
            plain[n], p_out, p_info = node_step(
                cfg, plain[n], *jax.tree.map(jnp.asarray, (inbox, host)))
            h = hosts[n]
            planes, up = h.upload(host)
            base = resident if planes is None else lay.host.pack(planes)
            if columns_in:
                bufs = base + one_up(lay, up, inbox)
            else:
                bufs = lay.inputs.pack((lay.host.unpack(base), inbox)) + (up,)
            last = h.carry
            rows[n], h.carry, c_out, dense = node_step_columns(
                cfg, lay, columns_in, rows[n], last, bufs)
            r_down, _ = one_down(lay, compact_readback(
                lay, h.carry, last, c_out), c_out, p_out, tag)
            assert_trees_equal(rows[n], plain[n], tag)
            assert_trees_equal(lay.columns.unstack(dense), p_out, tag)
            if durable:         # what the commit clamp of phase 10 read
                np.testing.assert_array_equal(
                    np.asarray(h.carry.durable), tails[n], tag)
            s = plain[n]
            want = Readback(
                info=p_info, outbox=None, term=s.term,
                voted_for=s.voted_for, role=s.role, leader_id=s.leader_id,
                commit=s.commit, base=s.log.base,
                base_term=s.log.base_term, heat=s.heat, windows=None)
            back = h.fetch(r_down, tag)
            assert_mirror_is(back._replace(windows=None), want, tag)
            h.done()
            outboxes.append(jax.device_get(p_out))
            tails[n] = np.asarray(p_info.log_tail)
            commits[n] = np.asarray(p_info.commit)
    seen = {k: sum(h.seen[k] for h in hosts) for k in hosts[0].seen}
    assert sum(int(c.sum()) for c in commits) > 0, "nothing committed"
    assert seen["rows_in"] > 10 and seen["rows_out"] > 10, seen
    assert seen["whole_out"] >= N, seen         # every node's first step
    assert seen["whole_in"] >= (N if durable else 0), seen
    if k_in < G:                                # ... and the burst
        assert seen["whole_in"] > (N if durable else 0), seen
    if k_out < G:
        assert seen["whole_out"] > N, seen


@pytest.mark.parametrize("lanes", [5, 6], ids=["fits", "one-over"])
def test_one_lane_over_either_capacity_falls_back_by_count(small, lanes):
    """A single node that leads every lane is offered a write on exactly
    ``lanes`` of them: with room for five rows each way the step with
    five goes up and comes down as rows, the step with six says so in its
    counts (the TRUE ones) and crosses whole, and both are the dense
    step."""
    small(5, 5, columns=16)
    cfg = EngineConfig(n_peers=1, **BASE)
    G = cfg.n_groups
    lay = column_layouts(cfg, False)
    assert lay is not None and lay.rows_in.K == lay.rows_out.K == 5
    empty = jax.device_get(HostInbox.empty(cfg))
    inbox = jax.device_get(Messages.empty(cfg))
    resident = lay.host.pack(empty)
    plain = init_state(cfg, 0, seed=1)
    rows = init_state(cfg, 0, seed=1)
    h = RowHost(lay)

    def step(host, tag):
        nonlocal plain, rows
        plain, p_out, p_info = node_step(
            cfg, plain, *jax.tree.map(jnp.asarray, (inbox, host)))
        planes, up = h.upload(host)
        base = resident if planes is None else lay.host.pack(planes)
        last = h.carry
        rows, h.carry, c_out, dense = node_step_columns(
            cfg, lay, True, rows, last, base + one_up(lay, up, inbox))
        r_down, _ = one_down(lay, compact_readback(
            lay, h.carry, last, c_out), c_out, p_out, tag)
        assert_trees_equal(rows, plain, tag)
        s = plain
        back = h.fetch(r_down, tag)
        assert_mirror_is(back._replace(windows=None), Readback(
            info=p_info, outbox=None, term=s.term, voted_for=s.voted_for,
            role=s.role, leader_id=s.leader_id, commit=s.commit,
            base=s.log.base, base_term=s.log.base_term, heat=s.heat,
            windows=None), tag)
        h.done()
        return lay.rows_out.view(r_down).n, p_info

    for t in range(40):             # elect: every lane led and quiet
        step(empty, f"boot {t}")
    assert (np.asarray(plain.role) == LEADER).all()
    before = dict(h.seen)
    host = empty.replace(submit_n=np.where(np.arange(G) < lanes, 1, 0)
                         .astype(np.int32))
    count, info = step(host, "the step")
    assert int(np.asarray(info.submit_acc).sum()) == lanes
    assert count == lanes           # the true count, also beyond K
    fits = lanes <= 5
    assert h.seen["rows_in"] - before["rows_in"] == int(fits)
    assert h.seen["whole_in"] - before["whole_in"] == int(not fits)
    assert h.seen["rows_out"] - before["rows_out"] == int(fits)
    assert h.seen["whole_out"] - before["whole_out"] == int(not fits)


# -------------------------------------------- the runtime, in lock step ----


@pytest.fixture
def checked_rows(monkeypatch):
    """Every runtime column step, as it is called: the ``durable_tail``
    plane the device keeps after the step is the plane the host would
    have uploaded whole at that dispatch, lane for lane."""
    real = node_mod.node_step_columns
    nodes, calls = {}, []

    def checked(cfg, lay, columns_in, state, carry, buffers):
        node = nodes[int(state.node_id)]
        out = real(cfg, lay, columns_in, state, carry, buffers)
        assert "durable_tail" in lay.rows_in.at     # every step is fed one
        src = node._durable_tail_m if node._acked_tail is None \
            else node._acked_tail
        np.testing.assert_array_equal(
            np.asarray(out[1].durable),
            np.minimum(src, I32_SAFE_MAX).astype(np.int32),
            f"node {node.node_id} tick {node.ticks}")
        calls.append(node._acked_tail is not None)
        return out

    monkeypatch.setattr(node_mod, "node_step_columns", checked)
    return nodes, calls


def assert_mirrors_and_gauges(c):
    """After a tick: every mirror is the device's lane (the step has been
    fetched) and the running counts behind the gauges are a recount."""
    for n in c.nodes.values():
        s = n.state
        for mirror, lane in ((n.h_term, s.term), (n.h_role, s.role),
                             (n.h_leader, s.leader_id),
                             (n.h_commit, s.commit), (n.h_base, s.log.base),
                             (n.h_conf_word, s.conf_word),
                             (n.h_conf_idx, s.conf_idx)):
            np.testing.assert_array_equal(mirror, np.asarray(lane))
        assert n._lane_counts == n.count_lanes(), n.node_id
        n_open, n_led, n_unready, n_lost = n.count_lanes()
        assert n.metrics._gauges["groups_active"] == n_open
        assert n.metrics._gauges["groups_led"] == n_led
        assert n.metrics._gauges["groups_led_unready"] == n_unready
        assert n.metrics._gauges["groups_leaderless"] == n_unready + n_lost


def _lose_a_barrier(c, payload: bytes) -> int:
    """ENOSPC under the write of lane 0's leader: the host feeds the
    confirmed tail (``_acked_tail``) until the retried barrier lands.
    Returns the node."""
    lead = c.leader_of(0)
    node = c.nodes[lead]
    node.store.set_fault("write", value=errno.ENOSPC, shard=0)
    fut = node.submit(0, payload)
    clamped = False
    for _ in range(200):
        c.tick()
        assert_mirrors_and_gauges(c)
        clamped = clamped or node._acked_tail is not None
        if fut.done() and node._acked_tail is None:
            break
    assert clamped and fut.done() and fut.exception() is None
    return lead


@pytest.mark.parametrize("engine", ["native", "python"])
def test_device_durable_tail_and_gauges_through_a_storm_a_failed_barrier_a_purge_and_a_reopen(
        tmp_path, small, checked_rows, engine):
    """A cluster whose every lane elects at once (a storm: both row forms
    overflow), serves writes as rows, loses a barrier to ENOSPC (the host
    then feeds ``_acked_tail``) and a second one later, after steps that
    were fed the fsynced mirror again, purges a lane, reopens it, and
    restarts a node: in every step the device's ``durable_tail`` is the
    host's plane, after every tick the mirrors are the device's lanes and
    the running gauges a full recount, and both row forms and both
    fallbacks were taken.  Under either persist step."""
    if engine == "native" and not native_available():
        pytest.skip("no native WAL toolchain")
    small(4, 4, columns=3)
    nodes, calls = checked_rows
    cfg = EngineConfig(n_peers=3, pre_vote=True, **BASE)
    assert column_layouts(cfg, True) is not None
    c = LocalCluster(cfg, str(tmp_path), provider_factory=NullProvider,
                     seed=5,
                     store_factory=wal_store_factory(str(tmp_path), engine))
    try:
        nodes.update(c.nodes)
        for _ in range(60):                     # the storm
            c.tick()
            assert_mirrors_and_gauges(c)
        assert all(c.leader_of(g) is not None for g in range(cfg.n_groups))
        futs = []
        for t in range(40):                     # single operations: rows
            n = c.nodes[t % 3]
            led = np.nonzero((n.h_role == LEADER) & n.h_ready)[0]
            if len(led):
                g = int(led[t % len(led)])
                futs.append(n.submit_batch(g, [b"w%d" % t]))
                futs.append(n.read(g, b"r%d" % t))
            if t in (18, 19, 20):               # more writes than rows
                for g in led.tolist():
                    futs.append(n.submit_batch(g, [b"burst"]))
            c.tick()
            assert_mirrors_and_gauges(c)
        assert all(n.store.can_stage_native == (engine == "native")
                   for n in c.nodes.values())
        lead = _lose_a_barrier(c, b"kept-through-enospc")
        assert any(calls) and not calls[-1]
        clamped = sum(calls)
        c.tick(5)                       # steps fed the fsynced mirror
        assert sum(calls) == clamped
        lead = _lose_a_barrier(c, b"kept-again")
        assert sum(calls) > clamped and not calls[-1]
        # A purge (the lane's durable tail and mirrors drop to zero under
        # the rows) and a reopen.
        victim = c.nodes[(lead + 1) % 3]
        victim.set_active(2, False, purge=True)
        for _ in range(5):
            c.tick()
            assert_mirrors_and_gauges(c)
        assert not victim.h_active[2] and victim._durable_tail_m[2] == 0
        victim.set_active(2, True)
        for _ in range(40):
            c.tick()
            assert_mirrors_and_gauges(c)
        assert victim.h_commit[2] > 0           # caught up again
        assert sum(n.metrics["row_overflows_in"]
                   for n in c.nodes.values()) > 0
        # A node comes back from its WAL: its first step crosses whole.
        c.kill_node(lead)
        nodes[lead] = c.restart_node(lead)
        for _ in range(60):
            c.tick()
            assert_mirrors_and_gauges(c)
        assert sum(f.done() for f in futs) >= len(futs) - 4
        for n in c.nodes.values():
            m = n.metrics
            for name in ("steps_rows_in", "steps_rows_out",
                         "row_overflows_out"):
                assert m[name] > 0, (n.node_id, name)
    finally:
        c.close()


# name -> (rows a step holds each way, columns a peer row holds, lanes
# written in one step, whether the Readback and the outbox then overflow)
CROSSINGS = {
    "fits": (8, 8, 3, False, False),
    "rows-over": (4, 8, 6, True, False),
    "columns-over": (8, 3, 6, False, True),
    "both-over": (4, 3, 6, True, True),
}


@pytest.mark.parametrize("case", list(CROSSINGS))
def test_a_column_step_is_one_array_each_way_and_an_overflow_is_fetched_whole(
        tmp_path, small, monkeypatch, case):
    """One step of a node that leads six lanes or more accepts a write on
    ``lanes`` of them at once.  Where rows and columns hold it, the step
    uploads ONE array (``transfers`` 1 on ``raft.dispatch_upload``) and
    fetches ONE (1 on ``raft.scan_fetch``); where the Readback moved more
    lanes than its rows hold, where a row of the outbox holds more
    columns than the buffer, and where both do, that part comes down
    whole in the dense layout's buffers beside the one array, counted as
    what really crossed; and after every step the mirrors are the
    device's lanes and every write is acknowledged."""
    from rafting_tpu.utils.profiling import StageSpans
    k_rows, k_columns, lanes, rows_over, columns_over = CROSSINGS[case]
    small(k_rows, k_rows, columns=k_columns)
    cfg = EngineConfig(n_peers=3, pre_vote=True, **BASE)
    lay = column_layouts(cfg, True)
    assert lay is not None
    c = LocalCluster(cfg, str(tmp_path), provider_factory=NullProvider,
                     seed=5)
    try:
        for _ in range(60):
            c.tick()
        node = max(c.nodes.values(), key=lambda n: int(
            ((n.h_role == LEADER) & n.h_ready).sum()))
        led = np.nonzero((node.h_role == LEADER) & node.h_ready)[0]
        assert len(led) >= 6
        notes = []
        real = StageSpans.note

        def spy(self, **stats):
            if self is node._stages and "transfers" in stats:
                notes.append((self._name, node.ticks, stats))
            return real(self, **stats)

        monkeypatch.setattr(StageSpans, "note", spy)
        futs = []
        for _ in range(3):          # in each phase of the heartbeat's three
            futs += [node.submit_batch(int(g), [b"w"]) for g in led[:lanes]]
            for _ in range(4):
                c.tick()
                assert_mirrors_and_gauges(c)
        c.tick(10)
        assert all(f.done() and f.exception() is None for f in futs)
    finally:
        monkeypatch.undo()
        step_layouts.cache_clear()
        column_layouts.cache_clear()
        c.close()
    size = lambda layout: sum(4 * n for _, n in layout.buffers)
    up = {t: s for name, t, s in notes if name == "dispatch_upload"}
    down = {t: s for name, t, s in notes if name == "scan_fetch"}
    assert up.keys() == down.keys() and len(up) >= 22
    for s in up.values():       # the rows, the columns behind them
        planes = lay.inputs if s["dense"] else \
            lay.host if s["planes_dense"] else None
        assert s["transfers"] == 1 + (len(planes.buffers) if planes else 0)
        assert s["bytes"] == lay.rows_in.nbytes \
            + (0 if s["dense"] else lay.columns.nbytes) \
            + (size(planes) if planes else 0)
    for s in down.values():     # the rows and the columns, and what
        assert s["transfers"] == 1 \
            + len(lay.back.buffers) * s["planes_dense"] \
            + len(lay.outbox.buffers) * s["dense"]   # either does not hold
        assert s["bytes"] == lay.rows_out.nbytes + lay.columns.nbytes \
            + size(lay.back) * s["planes_dense"] \
            + size(lay.outbox) * s["dense"]
    # The step that took the writes: it offered ``lanes`` rows ...
    wrote = [t for t, s in up.items() if not s["planes_dense"]
             and s["rows"] >= lanes] if lanes <= k_rows else \
        [t for t, s in up.items() if s["planes_dense"]]
    assert wrote, up
    # ... and came down as the case says, each part by its own count.
    assert any((down[t]["planes_dense"], down[t]["dense"])
               == (int(rows_over), int(columns_over)) for t in wrote), \
        [down[t] for t in wrote]
    if case == "fits":
        assert any(up[t]["transfers"] == down[t]["transfers"] == 1
                   and down[t]["rows"] >= lanes and down[t]["columns"] > 0
                   for t in wrote), [(up[t], down[t]) for t in wrote]
