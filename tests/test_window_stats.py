"""From a lost reply to a refused operation, every link counted (PERF.md
section 7 "Since PR 34" (k), section 3's table of the seven links).

A leader counts its un-acknowledged sends per (lane, follower) and releases
ONE slot per reply (core/step.py phases 6 and 9).  An inbox collapse
(transport/inbox.py: a source more than COLLAPSE_BACKLOG slices behind has
its queue merged newest-wins per lane) keeps a lane's newest reply and
loses the older, and with each of those a slot of the window, for good;
the loss that fills the window stops the occupying heartbeats, the window
times out, the follower cools down, and a leader with a majority cooling
is not ready and refuses.  (a) drives that chain in lock step and reads
every link where the program counts it: ``InboxStats.merged`` /
``inbox_replies_merged``, the five window sums of the step's readback
(``window_slots_occupied``, ``window_pairs_cooling``, ``window_timeouts``),
``groups_led_unready``, ``lane_unready_s``, the refusal's text.  (b) checks
the sums against the states on either side of a step, in both served forms.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rafting_tpu.api.anomaly import NotReadyError
from rafting_tpu.core.cluster import route
from rafting_tpu.core.step import (
    WINDOW_SUMS, column_layouts, first_carry, node_step_columns,
    node_step_packed, pack_readback, step_layouts)
from rafting_tpu.core.types import (
    EngineConfig, HostInbox, LEADER, Messages, conf_learners_of, conf_new_of,
    conf_voters_of, init_state)
from rafting_tpu.testkit.harness import LocalCluster
from rafting_tpu.transport import InboxAccumulator, messages_template

# ------------------------------------------------ (a) the chain, lock step ----

# The 100,000-Region cell's timing (a heartbeat a period, an RPC timeout of
# three, a cool-down of ten) on the default window of four.
CHAIN = EngineConfig(n_groups=4, n_peers=3, heartbeat_ticks=1,
                     rpc_timeout_ticks=3, recovery_ticks=10)
HELD = InboxAccumulator.COLLAPSE_BACKLOG + 1    # slices that collapse


class Chain:
    """One open lane, its leader stepped first in every round, and a gate
    on what the leader's inbox is handed: pass, hold (until ``release``)
    or lose."""

    def __init__(self, root, sources):
        self.c = c = LocalCluster(CHAIN, root, seed=1)
        for node in c.nodes.values():
            for g in range(1, CHAIN.n_groups):
                node.set_active(g, False)
        lead = c.wait_leader(0)
        self.lead = node = c.nodes[lead]
        self.followers = [n for i, n in c.nodes.items() if i != lead]
        self.sources = [n.node_id for n in self.followers][:sources]
        c.tick_until(lambda: node.is_ready(0), what="leader ready")
        self.mode, self.held = "pass", []
        self._merge = node.acc.merge
        node.acc.merge = self._gate
        # From here the leader steps first.  The change of order leaves a
        # follower one slice behind for good (a slice a source a step):
        # one step of the followers alone takes it.
        for f in self.followers:
            f.tick()
        self.round(4)

    def _gate(self, src, fields, payloads):
        if self.mode == "pass" or src not in self.sources:
            self._merge(src, fields, payloads)
        elif self.mode == "hold":
            self.held.append((src, fields, payloads))

    def release(self):
        self.mode = "pass"
        for slice_ in self.held:
            self._merge(*slice_)
        self.held = []

    def round(self, n=1):
        """The leader's step, then the followers': a heartbeat and its
        acknowledgements in one round, drained by the leader's next."""
        for _ in range(n):
            self.lead.tick()
            for f in self.followers:
                f.tick()

    def gauge(self, name):
        return self.lead.metrics._gauges[name]


@pytest.mark.parametrize("sources", [1, 2],
                         ids=["one-follower-held", "both-followers-held"])
def test_lost_replies_leak_slots_until_the_leader_refuses(
        tmp_path, caplog, sources):
    """This test characterises a KNOWN DEFECT (PERF.md section 7 "Since PR
    34" (k); ROADMAP queue 1 item 2) and flips with its repair: once a
    merged reply no longer costs a slot, the standing occupancy below
    stays where it was and nothing times out.

    ``HELD`` slices of acknowledgements held back, then handed to the
    leader's inbox at once: its queue collapses, ``merged`` counts the
    replies overwritten, and the standing occupancy of the windows rises
    by exactly that many and stays.  One more lost slice fills the window:
    no heartbeat occupies a slot any more, the window times out
    ``rpc_timeout_ticks`` periods later (``window_timeouts``) and cools
    down for ``recovery_ticks``.  With one follower held the other still
    makes a majority: the lane serves throughout.  With both (a leader
    whose own late step collapsed both queues) the lane is unready for
    exactly the cool-down: one ``lane_unready_s`` sample, the gauges in
    step with ``h_ready``, and a refusal that names the episode."""
    x = Chain(str(tmp_path), sources)
    node, m, W = x.lead, x.lead.metrics, CHAIN.inflight_limit
    try:
        # Undisturbed: one heartbeat in flight to each follower.
        assert x.gauge("window_slots_occupied") == 2
        assert m["inbox_replies_merged"] == m["window_timeouts"] == 0

        # -- links 2 and 3: the collapse, and the slots it costs ----------
        x.mode = "hold"
        x.round(HELD)
        assert len(x.held) == HELD * sources
        assert x.gauge("window_slots_occupied") == 2 + (HELD - 1) * sources
        x.release()
        x.round()
        merged = (HELD - 1) * sources       # a lane keeps its newest
        assert m["inbox_collapsed"] == HELD * sources
        assert m["inbox_replies_merged"] == merged
        for _ in range(2 * CHAIN.recovery_ticks):
            x.round()
            assert x.gauge("window_slots_occupied") == 2 + merged   # stays
        assert m["window_timeouts"] == 0 and node.is_ready(0)
        assert W - 1 == HELD - 1    # three of four slots gone for good

        # -- link 4: the loss that fills the window, and its timeout ------
        x.mode = "lose"
        x.round()
        x.mode = "pass"
        lost_at = node.timer_ticks
        x.round(CHAIN.rpc_timeout_ticks - 1)
        assert m["window_timeouts"] == 0 and node.is_ready(0)
        x.round()
        assert node.timer_ticks == lost_at + CHAIN.rpc_timeout_ticks
        assert m["window_timeouts"] == sources      # one a window
        since = node.timer_ticks

        # -- links 5 to 7: cool-down, unready lane, refusal ---------------
        unready = sources == 2              # a majority cooling, or not
        for k in range(CHAIN.recovery_ticks):
            assert x.gauge("window_pairs_cooling") == sources
            assert node.is_ready(0) == (not unready)
            assert x.gauge("groups_led_unready") == int(unready)
            assert x.gauge("groups_leaderless") == int(unready)
            err = node._refusal(0)
            if unready:
                assert isinstance(err, NotReadyError)
                assert str(err) == (
                    "group 0: leader lacks a healthy majority "
                    f"(unready for {k} periods, since tick {since})")
            else:
                assert err is None
            x.round()
        assert x.gauge("window_pairs_cooling") == 0
        assert x.gauge("groups_led_unready") == 0 and node.is_ready(0)
        assert node._refusal(0) is None and not node._unready_since.any()
        episodes = m.histogram("lane_unready_s")
        if unready:
            # A caller that steps the node has no period: one counts 1 s.
            assert (episodes.n, episodes.total) == (1, CHAIN.recovery_ticks)
            assert m["refused_not_ready"] == CHAIN.recovery_ticks
            warned = [r.getMessage() for r in caplog.records
                      if "led lane(s) unready" in r.getMessage()]
            # One line a period once the episode is older than a timeout.
            assert len(warned) == CHAIN.recovery_ticks \
                - CHAIN.rpc_timeout_ticks - 1
            assert f"[(0, {since})]" in warned[0] and "'cooling': 2" \
                in warned[0]
        else:
            assert episodes.n == 0 and m["refused_not_ready"] == 0
        # The timeout gave the slots back: the windows stand where they
        # started, and the lane serves.
        assert x.gauge("window_slots_occupied") == 2 + merged - \
            (HELD - 1) * sources
        fut = node.submit(0, b"after")
        x.round(4)
        assert fut.done() and fut.exception() is None
    finally:
        x.c.close()


def test_a_fresh_leader_counts_as_unready_and_opens_no_episode(tmp_path):
    """An election is not an episode: the leader of a new term is led and
    unready until a majority has answered (``groups_led_unready``), its
    refusal says no more than it did, and nothing reaches
    ``lane_unready_s``."""
    c = LocalCluster(EngineConfig(n_groups=4, n_peers=3), str(tmp_path),
                     seed=1)
    try:
        seen = 0
        for _ in range(200):
            c.tick()
            for node in c.nodes.values():
                fresh = (node.h_role == LEADER) & ~node.h_ready
                assert node.metrics._gauges["groups_led_unready"] \
                    == fresh.sum()
                for g in np.nonzero(fresh)[0]:
                    seen += 1
                    assert str(node._refusal(int(g))) == \
                        f"group {g}: leader lacks a healthy majority"
            if seen and all(n.h_ready[n.h_role == LEADER].all()
                            for n in c.nodes.values()) \
                    and sum((n.h_role == LEADER).sum()
                            for n in c.nodes.values()) == 4:
                break
        assert seen, "no step showed a leader before its first majority"
        for node in c.nodes.values():
            assert not node._unready_since.any()
            assert node.metrics.histogram("lane_unready_s").n == 0
    finally:
        c.close()


def test_merged_counts_the_replies_a_collapse_overwrites():
    """Per counted kind (AppendEntries and InstallSnapshot replies): the
    lanes over the collapsed slices less the distinct lanes.  Requests
    and votes are not counted, and a drain that collapses nothing counts
    nothing."""
    cfg = EngineConfig(n_groups=8, n_peers=3)
    acc = InboxAccumulator(cfg, messages_template(cfg))

    def slice_(**kinds):
        return {f"{k}_valid": (np.asarray(lanes, np.int64),
                               np.ones(len(lanes), bool))
                for k, lanes in kinds.items()}

    for fields in (slice_(aer=[0, 1, 2], ae=[5]), slice_(aer=[1, 2]),
                   slice_(aer=[2], isr=[7], rv=[3])):
        acc.merge(1, fields, {})
    acc.pop()
    st = acc.take_stats()
    assert (st.depth, st.collapsed, st.merged) == ({1: 2}, 0, 0)
    acc.merge(1, slice_(isr=[7], ae=[5], rv=[3]), {})
    acc.merge(1, slice_(aer=[2, 6]), {})
    batches, _ = acc.pop()              # four queued: they collapse
    st = acc.take_stats()
    assert len(batches[1]) == st.collapsed == 4
    # aer: lanes 1,2 | 2 | 2,6 hold 5 replies on 3 lanes; isr: 7 | 7.
    assert st.merged == (5 - 3) + (2 - 1)


# --------------------------- (b) the sums are the states' own, both forms ----

SUMS = dict(n_groups=8, n_peers=3, log_slots=16, batch=4, max_submit=4,
            election_ticks=6, heartbeat_ticks=1, rpc_timeout_ticks=2,
            recovery_ticks=3, inflight_limit=2)


def np_window_sums(cfg, old, new):
    """``window_sums`` over fetched states, in numpy."""
    w = new.conf_word
    bits = conf_voters_of(w) | conf_new_of(w) | conf_learners_of(w)
    peers = np.arange(cfg.n_peers)
    pair = (new.active & (new.role == LEADER))[:, None] \
        & (peers[None, :] != new.node_id) \
        & (((bits[:, None] >> peers[None, :]) & 1) > 0)
    used = new.inflight + new.hb_inflight
    cooling = (new.fail_at != 0) \
        & (new.now - new.fail_at < cfg.recovery_ticks)
    timed_out = (new.fail_at != old.fail_at) & (new.fail_at != 0)
    return [int(pair.sum()), int(used[pair].sum()),
            int((pair & (used >= cfg.inflight_limit)).sum()),
            int((pair & cooling).sum()), int((pair & timed_out).sum())]


def _packed_step(cfg):
    inputs, readback = step_layouts(cfg, True)

    def step(state, host, inbox):
        state, bufs = node_step_packed(cfg, inputs, state,
                                       inputs.pack((host, inbox)))
        return state, readback.unpack(jax.device_get(bufs))
    return step


def _column_step(cfg):
    lay = column_layouts(cfg, True)
    assert lay is not None
    # HostInbox and the Readback cross whole here (a row buffer that
    # holds none; pack_readback), so one carry serves every node of the
    # test.
    carry = [first_carry(lay)]

    def step(state, host, inbox):
        held = lay.columns.compact(inbox)
        fits = bool((lay.columns.view(held).n <= lay.columns.K).all())
        rows = lay.rows_in.whole(host)
        bufs = lay.host.pack(host) + (np.concatenate([rows, held]),) \
            if fits else lay.inputs.pack((host, inbox)) + (rows,)
        state, carry[0], _, dense = node_step_columns(
            cfg, lay, fits, state, carry[0], bufs)
        back = lay.back.unpack(jax.device_get(pack_readback(lay, carry[0])))
        return state, back._replace(outbox=lay.columns.unstack(dense))
    return step


@pytest.mark.parametrize("form", ["packed", "columns"])
def test_window_sums_are_a_recomputation_from_the_states(small, form):
    """A cluster stepped 80 periods through the served program over links
    cut at random, so that windows fill, time out and cool down: after
    every step the readback's five sums equal a numpy recomputation from
    the states fetched on either side of it, and each of the five was
    non-zero somewhere."""
    if form == "columns":       # 8 lanes: columns that hold every lane
        small(None, None, columns=8, chunk_bytes=256)
    cfg = EngineConfig(**SUMS)
    N, G = cfg.n_peers, cfg.n_groups
    step = (_packed_step if form == "packed" else _column_step)(cfg)
    rng = np.random.default_rng(5)
    states = [init_state(cfg, n, seed=3) for n in range(N)]
    outboxes = [jax.device_get(Messages.empty(cfg))] * N
    tails = [np.zeros(G, np.int32)] * N
    peak = np.zeros(len(WINDOW_SUMS), np.int64)
    for t in range(80):
        # Calm, then a partition's worth of loss, then calm again.
        cut = 0.5 if 30 <= t < 50 else 0.05
        inflight = jax.tree.map(lambda *a: np.stack(a), *outboxes)
        inboxes = jax.device_get(
            route(inflight, jnp.asarray(rng.random((N, N)) > cut)))
        outboxes = []
        for n in range(N):
            inbox = jax.tree.map(lambda a: a[n], inboxes)
            host = jax.device_get(HostInbox.empty(cfg)).replace(
                submit_n=rng.integers(0, 2, G, dtype=np.int32),
                durable_tail=tails[n])
            old = jax.device_get(states[n])
            states[n], back = step(states[n], host, inbox)
            want = np_window_sums(cfg, old, jax.device_get(states[n]))
            assert back.windows.dtype == np.int32
            assert list(back.windows) == want, (t, n, WINDOW_SUMS)
            peak = np.maximum(peak, want)
            outboxes.append(jax.device_get(back.outbox))
            tails[n] = np.asarray(back.info.log_tail)
    assert (peak > 0).all(), dict(zip(WINDOW_SUMS, peak))
