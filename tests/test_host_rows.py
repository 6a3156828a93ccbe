"""A row step's host phase walks the rows that moved (runtime/node.py
``_host_lanes``, PERF.md PR 40): persist, the rejection sweep, apply, reads
and maintain select their work among the lanes the fetch handed down and
the lanes the host itself is holding work for, and every selection is what
the same expression gives over whole planes.

Two clusters of three nodes are stepped side by side through the same
operations: one as it ships, one whose every host phase is forced to look
at every lane (``_host_lanes`` -> None: the parent's passes).  After every
round each selection either made (``_where``, the lanes ``advance`` visited
and left behind) is the other's, call for call; every plane and counter the
host keeps agrees; after every host phase that keeps them, the sets are a
recount over the planes; and at the end the WAL files are the same bytes."""

import errno
import os
import time

import numpy as np
import pytest

from rafting_tpu.core.step import column_layouts
from rafting_tpu.core.types import EngineConfig, LEADER
from rafting_tpu.machine.dispatch import ApplyDispatcher
from rafting_tpu.runtime.node import RaftNode
from rafting_tpu.snapshot.policy import MaintainAgreement
from rafting_tpu.testkit.fixtures import NullProvider
from rafting_tpu.log.wal import native_available
from rafting_tpu.testkit.harness import LocalCluster, wal_store_factory

BASE = dict(n_groups=16, n_peers=3, log_slots=16, batch=4, max_submit=4,
            election_ticks=8, heartbeat_ticks=3, rpc_timeout_ticks=6,
            pre_vote=True)
ROWS = 6
SENTINEL = np.iinfo(np.int64).max
# Counters both clusters must agree on after every round.
COUNTERS = (
    "applies", "commits", "elections", "reads_served", "read_barriers",
    "read_lease_hits", "read_lease_carried", "read_kicks",
    "read_batches_aborted", "snapshots_taken", "snapshots_installed",
    "ckpt_by_pressure", "compactions_by_pressure",
    "membership_changes_entered", "membership_changes_committed",
    "steps_rows_out", "row_overflows_out", "storage_transient_errors",
    "enospc_backpressure")
PLANES = ("_durable_tail_m", "_wal_floor", "_stable_term_m",
          "_stable_voted_m", "_rel_min", "h_commit", "h_base", "h_term",
          "h_active")


def recount(node: RaftNode, ctx) -> None:
    """What a host phase that ran to its end keeps for the next, against
    the plain expressions over the planes it ended on."""
    cfg = node.cfg
    G, L = cfg.n_groups, cfg.log_slots
    tag = f"node {node.node_id} tick {node.ticks}"
    commit, base = np.asarray(ctx.commit), np.asarray(ctx.base)
    mirror = node.dispatcher.applied_view(G)
    np.testing.assert_array_equal(
        node.dispatcher.backlog, np.flatnonzero(commit > mirror), tag)
    assert node.metrics["commits"] == int(commit.astype(np.int64).sum()), tag
    pressed = node.maintain.pressed(node.h_commit, base)
    np.testing.assert_array_equal(node._pressed_m, pressed, tag)
    assert node._pressed_n == int(pressed.sum()), tag
    fill = node._durable_tail_m - base
    assert node.metrics._gauges["log_ring_used_max"] == \
        int(fill[node.h_active].max(initial=0)), tag
    np.testing.assert_array_equal(
        node._fill_hist,
        np.bincount(np.clip(fill, 0, L + 1)[node.h_active],
                    minlength=L + 2), tag)
    np.testing.assert_array_equal(
        node._fill_m, np.where(node.h_active, np.clip(fill, 0, L + 1), -1),
        tag)
    assert node._led_open == int(
        ((node.h_role == LEADER) & node.h_active).sum()), tag
    assert node._lane_counts == node.count_lanes(), tag
    assert sorted(node._reads_released) == \
        np.flatnonzero(node._rel_min < SENTINEL).tolist(), tag
    assert (base <= node._wal_floor).all(), tag


class Twins:
    """The cluster as it ships (``rows``) and one whose host phases all
    look at every lane (``whole``), stepped through the same operations."""

    def __init__(self, tmp_path, monkeypatch, wal, **cfg):
        self.cfg = EngineConfig(**{**BASE, **cfg.pop("engine", {})})
        # The two planes that act on the wall clock (health evacuation on
        # a slow fsync, admission shedding on queue delay) would tell the
        # twins apart by chance.
        monkeypatch.setenv("RAFT_HEALTH", "0")
        monkeypatch.setenv("RAFT_ADMISSION", "0")
        assert column_layouts(self.cfg, True) is not None
        roots = {k: str(tmp_path / k) for k in ("rows", "whole")}
        self.seen = {k: {} for k in roots}      # (kind, node) -> [selections]
        self.with_ids = self.phases = 0
        twins = self

        def kind(node):
            return "whole" if node.data_dir.startswith(roots["whole"]) \
                else "rows"

        def note(node, what):
            twins.seen[kind(node)].setdefault(node.node_id, []).append(what)

        real_lanes = RaftNode._host_lanes
        real_where = RaftNode._where
        real_phase = RaftNode._host_phase
        real_advance = ApplyDispatcher.advance
        by_dispatcher = {}

        def lanes(node, ctx):
            ids = real_lanes(node, ctx)
            if kind(node) == "whole":
                return None
            twins.phases += 1
            twins.with_ids += ids is not None
            if ids is not None:     # ascending, and the offers among them
                assert (np.diff(ids) > 0).all()
                assert np.isin(ctx.rows.sub_ids, ids).all()
                assert np.isin(ctx.rows.read_ids, ids).all()
            return ids

        def where(node, among, mask):
            hit = real_where(node, among, mask)
            note(node, hit.tolist())
            return hit

        def advance(disp, commit, max_per_group=0, lanes=None):
            n = real_advance(disp, commit, max_per_group, lanes)
            note(by_dispatcher[id(disp)], ("advance", n,
                                           disp.backlog.tolist()))
            return n

        def phase(node, ctx):
            by_dispatcher[id(node.dispatcher)] = node
            real_phase(node, ctx)
            if node._host_sets_ok:
                recount(node, ctx)

        monkeypatch.setattr(RaftNode, "_host_lanes", lanes)
        monkeypatch.setattr(RaftNode, "_where", where)
        monkeypatch.setattr(RaftNode, "_host_phase", phase)
        monkeypatch.setattr(ApplyDispatcher, "advance", advance)
        kw = dict(provider_factory=NullProvider, seed=5, **cfg)
        self.rows, self.whole = (
            LocalCluster(self.cfg, roots[k], store_factory=wal_store_factory(
                roots[k], wal), **kw) for k in ("rows", "whole"))
        assert all(n.store.can_stage_native == (wal == "native")
                   for c in self.both for n in c.nodes.values())
        self.roots = roots

    @property
    def both(self):
        return (self.rows, self.whole)

    def close(self):
        for c in self.both:
            c.close()

    # -- stepping ---------------------------------------------------------

    @staticmethod
    def settle(c):
        """Wait out the worker threads (checkpoint saves, snapshot
        downloads), so that both clusters harvest them in the same step."""
        deadline = time.monotonic() + 20
        for n in c.nodes.values():
            while True:
                with n._ckpt_cv:
                    saved = {d[0] for d in n._ckpt_done}
                    busy = n._ckpt_queue or n._ckpt_inflight - saved
                with n._snap_cv:
                    busy = busy or n._snap_queue or n._snap_inflight
                if not busy:
                    break
                assert time.monotonic() < deadline, "workers never settled"
                time.sleep(0.002)

    def tick(self, rounds=1):
        for _ in range(rounds):
            for c in self.both:
                c.tick()
                self.settle(c)
            self.compare()

    def tick_until(self, pred, max_rounds=400, what="condition"):
        for _ in range(max_rounds):
            if pred():
                return
            self.tick()
        raise AssertionError(f"{what} not reached in {max_rounds} rounds")

    def compare(self):
        """Every selection of the round, call for call, and everything the
        host keeps."""
        a, b = self.seen["rows"], self.seen["whole"]
        assert a == b, {n: [(x, y) for x, y in zip(a[n], b.get(n, []))
                            if x != y][:3] for n in a}
        a.clear(), b.clear()
        G = self.cfg.n_groups
        assert sorted(self.rows.nodes) == sorted(self.whole.nodes)
        for i, n in self.rows.nodes.items():
            w = self.whole.nodes[i]
            tag = f"node {i} tick {n.ticks}"
            for name in PLANES:
                np.testing.assert_array_equal(
                    getattr(n, name), getattr(w, name), f"{tag} {name}")
            np.testing.assert_array_equal(
                n.dispatcher.applied_view(G), w.dispatcher.applied_view(G),
                tag)
            for name in COUNTERS:
                assert n.metrics[name] == w.metrics[name], (tag, name)
            assert n.metrics._gauges.get("log_ring_used_max") == \
                w.metrics._gauges.get("log_ring_used_max"), tag

    def wal_bytes(self, kind):
        out = {}
        root = self.roots[kind]
        for r, _dirs, files in os.walk(root):
            if os.sep + "wal" not in r:
                continue
            for f in files:
                path = os.path.join(r, f)
                with open(path, "rb") as fh:
                    out[os.path.relpath(path, root)] = fh.read()
        return out

    # -- the same operation on both ---------------------------------------

    def elect(self):
        self.tick_until(
            lambda: all(c.leader_of(g) is not None and
                        c.nodes[c.leader_of(g)].h_ready[g]
                        for c in self.both for g in range(self.cfg.n_groups)),
            what="every lane led and ready")

    def leader(self, g):
        lead = self.rows.leader_of(g)
        assert lead == self.whole.leader_of(g)
        return lead

    def on(self, i, fn):
        """``fn(node)`` on node ``i`` of both clusters; the results."""
        return [fn(c.nodes[i]) for c in self.both]

    def write(self, g, payload):
        return self.on(self.leader(g), lambda n: n.submit(g, payload))

    def read(self, g, payload=b"q"):
        return self.on(self.leader(g), lambda n: n.read(g, payload))

    def done(self, futs, rounds=200):
        self.tick_until(lambda: all(f.done() for f in futs), rounds,
                        "futures resolved")
        for f in futs:
            assert f.exception() is None, f.exception()


# ------------------------------------------------------------- scenarios ----


def traffic(t: Twins):
    """Elections (a storm: the rows overflow and the phase runs whole),
    then single writes and reads on every node's lanes: rows."""
    t.elect()
    futs = []
    for k in range(30):
        g = (5 * k + 1) % t.cfg.n_groups
        futs += t.write(g, b"w%d" % k)
        if k % 2:
            futs += t.read((g + 3) % t.cfg.n_groups)
        t.tick()
    t.done(futs)
    served = sum(n.metrics["reads_served"] for n in t.rows.nodes.values())
    assert served == 15


def lease_reads(t: Twins):
    traffic(t)
    assert sum(n.metrics["read_lease_hits"]
               for n in t.rows.nodes.values()) > 0


def readindex_reads(t: Twins):
    traffic(t)
    assert sum(n.metrics["read_lease_hits"]
               for n in t.rows.nodes.values()) == 0


def halted_machine(t: Twins):
    """A machine halted under a committed write: the lane stays in the
    apply backlog through steps in which its row does not move, a read
    released behind the write waits for the apply frontier, and both go
    through when the machine is resumed."""
    t.elect()
    g = 3
    lead = t.leader(g)
    t.done(t.write(g, b"before"))
    t.on(lead, lambda n: n.dispatcher.halt(g))
    w = t.write(g, b"held")
    t.tick(6)
    r = t.read(g)
    t.tick(12)                      # quiet steps: the row does not move
    node = t.rows.nodes[lead]
    assert g in node.dispatcher.backlog.tolist()
    assert g in node._reads_released and not any(f.done() for f in w + r)
    other = t.write(g + 1, b"elsewhere")    # other lanes go on
    t.done(other)
    assert not any(f.done() for f in w + r)
    t.on(lead, lambda n: n.dispatcher.unhalt(g))
    t.done(w + r, 20)
    assert g not in node.dispatcher.backlog.tolist()
    assert g not in node._reads_released


def ring_pressure(t: Twins):
    """One lane fed ``max_submit`` entries a step under the policy as it
    ships: the ring comes under pressure, is checkpointed and compacted by
    it, and the fullest ring's fill is read off the counts per fill."""
    t.elect()
    g = 2
    lead = t.leader(g)
    futs, fills = [], set()
    for _ in range(60):
        futs += t.on(lead, lambda n: n.submit_batch(
            g, [b"x"] * t.cfg.max_submit))
        t.tick()
        fills.add(t.rows.nodes[lead].metrics._gauges["log_ring_used_max"])
    t.done(futs, 400)
    m = t.rows.nodes[lead].metrics
    assert m["ckpt_by_pressure"] > 0 and m["compactions_by_pressure"] > 0
    assert max(fills) > t.rows.nodes[lead].maintain.pressure_at
    assert int(t.rows.nodes[lead]._wal_floor[g]) > 0    # a floor was pushed


def snapshot_install(t: Twins):
    """A follower cut off until the leader has compacted past it catches
    up by an installed snapshot: durable tail, floor and apply frontier
    move at a dispatch, and the next phase looks at every lane."""
    t.elect()
    g = 1
    lead = t.leader(g)
    victim = (lead + 1) % 3
    for c in t.both:
        c.faults.isolate(victim)
    futs = []
    for k in range(40):
        futs += t.on(lead, lambda n: n.submit_batch(g, [b"deep"] * 2))
        t.tick(2)
    t.tick_until(lambda: t.rows.nodes[lead].h_base[g]
                 > t.rows.nodes[victim].h_commit[g], 200,
                 "the leader compacted past the cut follower")
    for c in t.both:
        c.faults.heal()
    t.tick_until(lambda: t.rows.nodes[victim].metrics["snapshots_installed"]
                 > 0, 300, "snapshot installed")
    t.tick(30)
    assert t.rows.nodes[victim].h_commit[g] == t.rows.nodes[lead].h_commit[g]


def membership_change(t: Twins):
    """A voter dropped and taken back through the joint walk: config
    entries take the Python persist step."""
    t.elect()
    g = 4
    lead = t.leader(g)
    drop = (lead + 1) % 3
    for voters in (0b111 & ~(1 << drop), 0b111):
        futs = t.on(lead, lambda n: n.change_membership(g, voters))
        t.done(futs, 300)
        t.done(t.write(g, b"after-%d" % voters))
    assert t.rows.nodes[lead].metrics["membership_changes_committed"] >= 4


def failed_barrier(t: Twins):
    """ENOSPC under a write: the phase is cut at its barrier
    (``_sync_pending``), the confirmed-tail clamp stands until the retried
    barrier lands, and every phase looks at every lane meanwhile."""
    t.elect()
    g = 0
    lead = t.leader(g)
    t.done(t.write(g, b"warm"))
    t.on(lead, lambda n: n.store.set_fault("write", value=errno.ENOSPC,
                                           shard=0))
    futs = t.write(g, b"kept-through-enospc")
    clamped = False
    for _ in range(200):
        t.tick()
        node = t.rows.nodes[lead]
        clamped = clamped or node._acked_tail is not None
        if all(f.done() for f in futs) and node._acked_tail is None:
            break
    assert clamped and t.rows.nodes[lead].metrics["enospc_backpressure"] > 0
    t.done(futs)
    before = t.with_ids
    t.done(t.write(g, b"after") + t.read(g))
    assert t.with_ids > before          # ... and by rows again after it


def purge_and_reopen(t: Twins):
    """A lane purged and reopened, then a node back from its WAL."""
    t.elect()
    g = 2
    lead = t.leader(g)
    victim = (lead + 1) % 3
    t.done(t.write(g, b"doomed"))
    t.on(victim, lambda n: n.set_active(g, False, purge=True))
    t.tick(5)
    assert not t.rows.nodes[victim].h_active[g]
    assert t.rows.nodes[victim]._durable_tail_m[g] == 0
    t.on(victim, lambda n: n.set_active(g, True))
    t.done(t.write(g, b"again"))
    t.tick_until(lambda: t.rows.nodes[victim].h_commit[g]
                 == t.rows.nodes[lead].h_commit[g], 100, "caught up")
    for c in t.both:
        c.kill_node(lead)
        c.restart_node(lead)
    t.tick(40)
    t.elect()
    t.done(t.write(g, b"after-restart") + t.read(g))


def overflow(t: Twins):
    """More lanes move than the row buffer holds: the Readback comes down
    whole, the phase looks at every lane and rebuilds what it keeps, and
    the next quiet step is worked from its rows again."""
    t.elect()
    over = {i: n.metrics["row_overflows_out"]
            for i, n in t.rows.nodes.items()}
    futs = []
    for g in range(t.cfg.n_groups):
        futs += t.write(g, b"burst")
    t.done(futs)
    assert all(n.metrics["row_overflows_out"] > over[i]
               for i, n in t.rows.nodes.items())
    before = t.with_ids
    t.done(t.write(7, b"single") + t.read(9))
    assert t.with_ids > before


def one_array(t: Twins):
    """A step whose phase is worked from its rows also crossed in ONE
    array each way (the rows, the columns behind them; column buffers
    wide enough here for a heartbeat's messages): the counters
    ``h2d_transfers`` / ``d2h_transfers`` move by one each over some step
    of every node of both clusters, and by no less over any (a storm's
    rows: more, the dense part whole beside the one array)."""
    t.elect()
    names = ("h2d_transfers", "d2h_transfers")
    nodes = [n for c in t.both for n in c.nodes.values()]
    steps = {id(n): set() for n in nodes}
    futs = []
    for k in range(12):
        if k % 4 == 0:
            futs += t.write(3 + k, b"one") + t.read(5 + k)
        before = [[n.metrics[name] for name in names] for n in nodes]
        t.tick()
        for n, was in zip(nodes, before):
            steps[id(n)].add(tuple(n.metrics[name] - w
                                   for name, w in zip(names, was)))
    t.done(futs)
    for moved in steps.values():
        assert (1, 1) in moved and min(min(m) for m in moved) == 1, moved


SCENARIOS = {
    "one_array": (one_array, dict(packing=dict(columns=16))),
    "lease_reads": (lease_reads, {}),
    "readindex_reads": (readindex_reads, dict(engine=dict(read_lease=False))),
    "halted_machine": (halted_machine, {}),
    "ring_pressure": (ring_pressure, {}),
    "snapshot_install": (snapshot_install, dict(
        maintain_factory=lambda: MaintainAgreement(
            BASE["n_groups"], state_change_threshold=2,
            dirty_log_tolerance=1, snap_min_interval=2,
            compact_min_interval=2, compact_slack=2))),
    "membership_change": (membership_change, {}),
    "failed_barrier": (failed_barrier, {}),
    "purge_and_reopen": (purge_and_reopen, {}),
    "overflow": (overflow, {}),
}


@pytest.mark.parametrize("wal", ["native", "python"])
@pytest.mark.parametrize("scenario", list(SCENARIOS))
def test_selections_over_the_rows_are_the_selections_over_whole_planes(
        tmp_path, monkeypatch, small, scenario, wal):
    """Under either persist step (the native engine's one call walks the
    rows' spans, the Python stage and barrier the same)."""
    if wal == "native" and not native_available():
        pytest.skip("no native WAL toolchain")
    run, kw = SCENARIOS[scenario]
    kw = dict(kw)
    small(ROWS, ROWS, **{"columns": 3, **kw.pop("packing", {})})
    t = Twins(tmp_path, monkeypatch, wal, **kw)
    try:
        run(t)
        t.tick(10)
        scanned = {k: sum(n.metrics["host_lanes_scanned"]
                          for n in c.nodes.values())
                   for k, c in zip(("rows", "whole"), t.both)}
    finally:
        t.close()
    # The mechanism engaged (most phases of a quiet cluster are worked from
    # their rows), it scanned less, and it wrote the same WAL.
    assert t.with_ids > t.phases // 4, (t.with_ids, t.phases)
    assert scanned["rows"] < scanned["whole"], scanned
    rows, whole = t.wal_bytes("rows"), t.wal_bytes("whole")
    assert rows and sorted(rows) == sorted(whole)
    for name in rows:
        assert rows[name] == whole[name], name
