"""Whole-cluster execution: N vectorized nodes in one SPMD program.

The reference runs one JVM per node and moves RPCs over per-peer TCP
connections (transport/EventBus.java, transport/EventNode.java).  Here an
entire N-node cluster is ``vmap(node_step)`` over a leading node axis, and
message routing is a pure array permutation: ``inbox[dst, src] =
outbox[src, dst]`` — a transpose of the first two axes.  Under a
``Mesh('node', 'group')`` sharding, that transpose lowers to an XLA
all-to-all over ICI, which is exactly the multi-chip deployment story: one
Raft node per device, consensus traffic riding the interconnect.

Fault injection (network partitions, message drops) is a boolean
connectivity matrix ANDed into every ``*_valid`` mask — the vectorized
analog of killing TCP links in the reference's manual chaos procedure
(README.md:28-33).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .step import node_step, ring_term_at
from .types import (
    LEADER, EngineConfig, FaultSchedule, HostInbox, Messages, RaftState,
    StepInfo, crash_restart, init_state,
)

_VALID_FIELDS = tuple(f.name for f in dataclasses.fields(Messages)
                      if f.name.endswith("_valid"))
# Message kinds (ae/aer/rv/rvr/is/isr) -> all fields of that RPC.  The
# leading underscore token of a field name is its kind; the nemesis
# duplicate-delivery merge replaces whole RPCs, so it must move every
# field of a kind together (a dup'd AE with a fresh reply's term lanes
# would be a frankenmessage).
_KIND_FIELDS = {}
for _f in dataclasses.fields(Messages):
    _KIND_FIELDS.setdefault(_f.name.split("_", 1)[0], []).append(_f.name)


def route(outboxes: Messages, conn: Optional[jax.Array] = None) -> Messages:
    """Deliver every node's outbox as next tick's inboxes.

    ``outboxes`` arrays are [N, P, G, ...] with axis 0 = sender, axis 1 =
    destination; the delivered inboxes are [N, P, G, ...] with axis 0 =
    destination, axis 1 = sender — a pure transpose.  ``conn[s, d]`` masks
    link s->d (False = partitioned / dropped).
    """
    swapped = jax.tree.map(lambda a: jnp.swapaxes(a, 0, 1), outboxes)
    if conn is None:
        return swapped
    # After the swap an element at [d, s] traveled s->d: mask with conn.T.
    mask = jnp.swapaxes(conn, 0, 1)
    reps = {}
    for name in _VALID_FIELDS:
        arr = getattr(swapped, name)
        reps[name] = arr & mask[..., None]
    return swapped.replace(**reps)


@partial(jax.jit, static_argnums=0, donate_argnums=(1, 2))
def cluster_step(cfg: EngineConfig, states: RaftState, inflight: Messages,
                 host: HostInbox, conn: jax.Array
                 ) -> Tuple[RaftState, Messages, StepInfo]:
    """One lockstep tick of the whole cluster.

    ``states``/``host``/returned ``StepInfo`` carry a leading node axis [N];
    ``inflight`` is the messages currently traveling (delivered this tick).
    """
    inboxes = route(inflight, conn)
    new_states, outboxes, infos = jax.vmap(partial(node_step, cfg))(
        states, inboxes, host)
    return new_states, outboxes, infos


def _node_bcast(mask: jax.Array, like: jax.Array) -> jax.Array:
    """Broadcast a [N] node mask against a leading-node-axis array."""
    return mask.reshape(mask.shape + (1,) * (like.ndim - 1))


def _select_nodes(mask: jax.Array, on_true, on_false):
    """Per-node pytree select: leaf[n] <- on_true[n] where mask[n]."""
    return jax.tree.map(
        lambda a, b: jnp.where(_node_bcast(mask, a), a, b), on_true, on_false)


def cluster_step_nemesis(cfg: EngineConfig, states: RaftState,
                         inflight: Messages, host: HostInbox,
                         prev_info: StepInfo, fault: FaultSchedule
                         ) -> Tuple[RaftState, Messages, StepInfo]:
    """One lockstep tick under one tick-slice of a :class:`FaultSchedule`.

    ``fault`` holds the per-tick arrays (``link_up`` [N, N], ``crash`` [N],
    ``stall`` [N], ``dup`` [N, N] — a scanned slice of the [T, ...]
    schedule).  Order of operations (the fault model of
    ``types.FaultSchedule``):

    1. crashed nodes reset volatile state to the durable frontier
       (:func:`crash_restart`) BEFORE delivery;
    2. in-flight messages deliver through ``link_up``; anything addressed
       to a crashed or stalled node is lost (it was down on arrival);
    3. live nodes step; stalled nodes are frozen wholesale — state, clock,
       timers, StepInfo — and send nothing;
    4. messages delivered over a ``dup`` link this tick are queued again
       for next tick unless the sender wrote a fresh RPC of the same kind
       over the lane (at-least-once delivery, exercising stale/duplicate
       RPC idempotency).

    Not jitted standalone: the nemesis path is always driven through the
    fused scan (core/sim.py ``run_cluster_ticks_nemesis``).
    """
    down = fault.crash | fault.stall                               # [N]

    # 1. crash-restart.  crash_restart splits each node's PRNG key; the
    # select keeps un-crashed nodes' streams bit-exact (types.py).
    restarted = jax.vmap(partial(crash_restart, cfg))(states)
    states = _select_nodes(fault.crash, restarted, states)

    # 2. delivery: link masks AND down-destination loss.  After route()'s
    # transpose conn[s, d] gates the s->d lane, so down destinations are
    # a column mask.
    inboxes = route(inflight, fault.link_up & ~down[None, :])

    # 3. step, then freeze stalled nodes (their pre-step state INCLUDES a
    # same-tick crash reset: a node both crashed and stalled restarts but
    # does not run).  StepInfo freezes too, so the self-driving host inbox
    # (auto_host_inbox snapshot echo) does not act for a stalled node.
    stepped, outboxes, infos = jax.vmap(partial(node_step, cfg))(
        states, inboxes, host)
    new_states = _select_nodes(fault.stall, states, stepped)
    infos = _select_nodes(fault.stall, prev_info, infos)
    sender_up = ~fault.stall
    outboxes = outboxes.replace(**{
        name: getattr(outboxes, name) & _node_bcast(
            sender_up, getattr(outboxes, name))
        for name in _VALID_FIELDS})

    # 4. duplicate delivery: re-queue this tick's DELIVERED messages on
    # dup'd links, whole-RPC, wherever the fresh outbox left the lane
    # empty.  The copy rides ``inflight`` and is subject to next tick's
    # masks like any message.
    delivered = fault.link_up & ~down[None, :]                     # [N, N]
    dup_lane = (fault.dup & delivered)[:, :, None]                 # [N, N, 1]
    reps = {}
    for kind, names in _KIND_FIELDS.items():
        vname = f"{kind}_valid"
        keep = dup_lane & getattr(inflight, vname) \
            & ~getattr(outboxes, vname)                            # [N, P, G]
        for name in names:
            old = getattr(inflight, name)
            new = getattr(outboxes, name)
            if new is None:     # a flag of a feature that is off
                continue
            k = keep if old.ndim == keep.ndim else keep[..., None]
            reps[name] = jnp.where(k, old, new)
        reps[vname] = getattr(outboxes, vname) | keep
    outboxes = outboxes.replace(**reps)
    return new_states, outboxes, infos


@partial(jax.jit, static_argnums=(0, 3, 6))
def auto_host_inbox(cfg: EngineConfig, states: RaftState, submit_n: jax.Array,
                    compact, prev_info: StepInfo,
                    read_n: Optional[jax.Array] = None,
                    durable_lag: bool = False) -> HostInbox:
    """Build a HostInbox batch [N, ...] for the self-driving harness.

    Policy (the steady-state behavior of a host runtime whose state machines
    keep pace — reference MaintainAgreement, command/MaintainAgreement.java):

    * offer ``submit_n`` client commands per group (leaders accept);
    * compact with slack: raise the log floor only up to ``commit - L/4``,
      keeping a tail of committed entries so briefly-lagging followers catch
      up from the log instead of tripping snapshot installation;
    * service snapshot downloads instantly: last tick's ``snap_req`` comes
      back as this tick's ``snap_done`` (the payload-less analog of the
      reference's out-of-band snapshot channel, EventNode.java:122-267).

    ``read_n`` ([N, G] int32, optional): linearizable reads offered per
    group per tick (the read-plane analog of ``submit_n``; only leaders
    with a free ReadIndex slot stamp them — unstamped offers are simply
    re-offered next tick by this self-driving policy).

    ``durable_lag``: feed each node's PREVIOUS-tick log tail
    (``prev_info.log_tail``) as ``HostInbox.durable_tail`` — the fused-scan
    model of the pipelined runtime's one-tick durability barrier (a tick's
    appends fsync while the next scan runs, so own-match counts only the
    prior tick's tail).  Default False: writes are durable instantly, the
    classic simulation assumption.

    ``compact``: False = never; True = every tick (the bench steady state);
    int K > 1 = every K ticks.  The cadence matters for laggard catch-up
    under SUSTAINED load: an every-tick floor advances continuously and
    outruns any snapshot install (each installed milestone is already
    below the floor by adoption time — a pursuit that never converges),
    whereas real compaction is gated on discrete checkpoints with minimum
    intervals (snapshot/policy.py, reference MaintainAgreement.java:
    85-130), giving laggards a stable window to install and then drain
    the live log.  Use a cadence when simulating catch-up scenarios.
    """
    G = cfg.n_groups
    slack = cfg.log_slots // 4
    if read_n is None:
        read_n = jnp.zeros(submit_n.shape, jnp.int32)

    def one(st, sub, rd, info):
        hi = HostInbox.empty(cfg)
        if compact is True:
            ct = jnp.maximum(st.commit - slack, 0)
        elif compact:
            ct = jnp.where(st.now % int(compact) == 0,
                           jnp.maximum(st.commit - slack, 0),
                           jnp.zeros((G,), jnp.int32))
        else:
            ct = jnp.zeros((G,), jnp.int32)
        return hi.replace(
            submit_n=sub,
            read_n=rd,
            compact_to=ct,
            snap_done=info.snap_req,
            snap_idx=info.snap_req_idx,
            snap_term=info.snap_req_term,
            snap_conf=info.snap_req_conf,
            durable_tail=info.log_tail if durable_lag else None,
        )
    return jax.vmap(one)(states, submit_n, read_n, prev_info)


def cluster_snapshot(states: RaftState) -> dict:
    """Host snapshot dict from a stacked [N, ...] RaftState — the ONE
    definition of the audit currency, shared by ``DeviceCluster.snapshot``
    and the fused-scan audit paths (testkit/invariants.py ClusterChecker,
    testkit/nemesis.py), so raw scan outputs audit without a DeviceCluster
    wrapper and the two paths cannot drift."""
    return {
        "term": np.asarray(states.term),
        "role": np.asarray(states.role),
        "voted_for": np.asarray(states.voted_for),
        "leader_id": np.asarray(states.leader_id),
        "commit": np.asarray(states.commit),
        "last": np.asarray(states.log.last),
        "base": np.asarray(states.log.base),
        "log_term": np.asarray(states.log.term),
        "now": np.asarray(states.now),
    }


class DeviceCluster:
    """Host-side driver for an all-on-device N-node Multi-Raft cluster.

    The in-process many-node harness — the generalization of the reference's
    loopback test trick (transport/EventClusterTest.java:81-83) — used by the
    test suite, the chaos/parity oracle and the benchmark.
    """

    def __init__(self, cfg: EngineConfig, seed: int = 0,
                 n_active: int | None = None, n_voters: int | None = None):
        self.cfg = cfg
        # Compaction policy for the self-driving inbox (see
        # auto_host_inbox): True = every tick, int K = every K ticks,
        # False = never.  Set a cadence when simulating laggard catch-up.
        self.compact = True
        N = cfg.n_peers
        states = [init_state(cfg, i, seed=seed, n_active=n_active,
                             n_voters=n_voters)
                  for i in range(N)]
        self.states: RaftState = jax.tree.map(
            lambda *xs: jnp.stack(xs), *states)
        self.inflight: Messages = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (N,) + a.shape).copy(),
            Messages.empty(cfg))
        self.conn = jnp.ones((N, N), jnp.bool_)
        self.last_info: StepInfo = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (N,) + a.shape).copy(),
            StepInfo.empty(cfg))

    # -- fault injection ----------------------------------------------------
    def set_partition(self, groups_of_nodes) -> None:
        """Partition the cluster: nodes can only reach their own side."""
        N = self.cfg.n_peers
        conn = np.zeros((N, N), bool)
        for side in groups_of_nodes:
            for a in side:
                for b in side:
                    conn[a, b] = True
        self.conn = jnp.asarray(conn)

    def heal(self) -> None:
        self.conn = jnp.ones((self.cfg.n_peers,) * 2, jnp.bool_)

    def isolate(self, node: int) -> None:
        N = self.cfg.n_peers
        self.set_partition([[n for n in range(N) if n != node], [node]])

    # -- stepping -----------------------------------------------------------
    def tick(self, submit_n=None, host: Optional[HostInbox] = None,
             read_n=None) -> StepInfo:
        N, G = self.cfg.n_peers, self.cfg.n_groups
        if host is None:
            def dense(v):
                if v is None:
                    return jnp.zeros((N, G), jnp.int32)
                v = jnp.asarray(v, jnp.int32)
                return jnp.broadcast_to(v, (N, G)) if v.ndim == 0 else v
            host = auto_host_inbox(self.cfg, self.states, dense(submit_n),
                                   self.compact, self.last_info,
                                   dense(read_n))
        self.states, self.inflight, info = cluster_step(
            self.cfg, self.states, self.inflight, host, self.conn)
        self.last_info = info
        if self.cfg.debug_checks:
            self._debug_check(info)
        return info

    def _debug_check(self, info: StepInfo) -> None:
        """cfg.debug_checks: surface in-kernel violations (per-node lanes)
        plus the one cross-node invariant a single node cannot see —
        at most one leader per (group, term), the election-safety assert
        of the reference (Follower.java:48-50, Leader.java:79-81)."""
        from .step import raise_debug_violations
        raise_debug_violations(info, "cluster tick")
        role = np.asarray(self.states.role)
        term = np.asarray(self.states.term)
        N = role.shape[0]
        for i in range(N):
            for j in range(i + 1, N):
                both = ((role[i] == LEADER) & (role[j] == LEADER)
                        & (term[i] == term[j]))
                if both.any():
                    g = int(np.nonzero(both)[0][0])
                    raise AssertionError(
                        f"election safety violated: nodes {i} and {j} both "
                        f"lead group {g} at term {int(term[i, g])}")

    def run(self, n_ticks: int, submit_n=None) -> None:
        for _ in range(n_ticks):
            self.tick(submit_n)

    # -- membership ---------------------------------------------------------
    def request_membership(self, voters: int, learners: int = 0,
                           groups=None, submit_n=None) -> StepInfo:
        """One tick with a membership-change request offered to EVERY node
        for the selected groups (only the leader's intake takes it; §6,
        core/step.py phase 8c).  ``voters``/``learners`` are peer
        bitmasks; ``groups`` (None = all) selects lanes.  The request is
        a single-tick offer — drive further ticks until
        ``StepInfo.conf_pending`` clears and the active ``conf_word``
        matches (the joint walk's leave entry auto-appends)."""
        import jax.numpy as jnp

        N, G = self.cfg.n_peers, self.cfg.n_groups
        sel = np.zeros(G, bool)
        sel[np.asarray(list(range(G)) if groups is None else groups)] = True
        hv = jnp.asarray(np.where(sel, voters, 0).astype(np.int32))
        hl = jnp.asarray(np.where(sel, learners, 0).astype(np.int32))
        return self._tick_with(conf_voters=hv, conf_learners=hl,
                               submit_n=submit_n)

    def request_transfer(self, target, groups=None) -> StepInfo:
        """One tick with a leadership-transfer request (TimeoutNow walk,
        core/step.py phase 7b/9) offered to every node for the selected
        groups.  ``target`` is a peer id (or [G] vector)."""
        import jax.numpy as jnp

        G = self.cfg.n_groups
        sel = np.zeros(G, bool)
        sel[np.asarray(list(range(G)) if groups is None else groups)] = True
        tgt = np.broadcast_to(np.asarray(target, np.int32), (G,))
        tgt = np.where(sel, tgt, -1).astype(np.int32)
        return self._tick_with(xfer_target=jnp.asarray(tgt))

    def _tick_with(self, submit_n=None, **host_lanes) -> StepInfo:
        """Tick once with extra per-group HostInbox lanes broadcast to
        every node on top of the self-driving policy."""
        import jax.numpy as jnp

        N, G = self.cfg.n_peers, self.cfg.n_groups
        sub = jnp.zeros((N, G), jnp.int32) if submit_n is None else \
            jnp.broadcast_to(jnp.asarray(submit_n, jnp.int32), (N, G))
        host = auto_host_inbox(self.cfg, self.states, sub, self.compact,
                               self.last_info)
        host = host.replace(**{
            k: jnp.broadcast_to(v, (N,) + v.shape) for k, v in
            host_lanes.items()})
        return self.tick(host=host)

    def membership(self, group: int, node: int = 0) -> dict:
        """Decoded active config of one group as one node sees it (the
        state's conf_word cache; configs converge with the log)."""
        from .types import conf_learners_of, conf_new_of, conf_voters_of

        w = int(self.states.conf_word[node, group])
        return {"voters": int(conf_voters_of(w)),
                "voters_new": int(conf_new_of(w)),
                "learners": int(conf_learners_of(w)),
                "joint": bool(conf_new_of(w))}

    # -- inspection ---------------------------------------------------------
    def snapshot(self) -> dict:
        """Pull the whole cluster state to host numpy for assertions."""
        return cluster_snapshot(self.states)

    def leaders(self, group: int = 0) -> list[int]:
        role = np.asarray(self.states.role[:, group])
        return [int(n) for n in np.nonzero(role == LEADER)[0]]

    def log_terms(self, node: int, group: int, lo: int, hi: int) -> list[int]:
        """Entry terms for indices [lo, hi] on one node (host-side read)."""
        L = self.cfg.log_slots
        ring = np.asarray(self.states.log.term[node, group])
        base = int(self.states.log.base[node, group])
        last = int(self.states.log.last[node, group])
        out = []
        for i in range(lo, hi + 1):
            if i <= base or i > last:
                out.append(None)
            else:
                out.append(int(ring[i % L]))
        return out
