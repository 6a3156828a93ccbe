"""Explicit multi-chip sharding specs for the stacked cluster pytrees.

A whole N-node cluster stacks every per-node pytree along a leading ``node``
axis (core/cluster.py), and each node's state is group-major.  Under a
``Mesh('node', 'group')`` the natural layout is therefore fixed by *meaning*,
not by array sizes: the specs below are declared per field, so a group count
that happens to collide with another dimension (P, L, B, S) can never change
the sharding (the failure mode of size-based inference).

The reference has no analog — its "mesh" is one JVM per node and a TCP mesh
between them (transport/NettyCluster.java:42-50); here the node axis is a
real device-mesh axis and the inter-node ``route()`` transpose lowers to an
XLA all-to-all over it.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from .types import (
    EngineConfig, FaultSchedule, HeatState, HostInbox, LogState, Messages,
    LeaseGuard, QuorumContact, RaftState, StepInfo, TraceState,
)

# RaftState fields with no group axis: per-node scalars and the PRNG key.
_STATE_NODE_ONLY = ("node_id", "now", "rng")

_NODE = PS("node")
_NODE_GROUP = PS("node", "group")          # [N, G, ...] — trailing dims replicated
_NODE_PEER_GROUP = PS("node", None, "group")  # [N, P, G, ...] message planes


def state_pspecs(trace: bool = False, heat: bool = False,
                 qc: bool = False, lease: bool = False) -> RaftState:
    """A RaftState-shaped pytree of PartitionSpecs for stacked [N, ...] state.

    ``trace`` must match whether the state carries flight-recorder lanes
    (cfg.trace_depth > 0): a None subtree in the state needs a None in the
    spec tree, and recorder lanes are [N, G, D] group-major like every
    per-group lane.  ``heat`` likewise matches cfg.heat — heat lanes are
    plain [N, G] group-major counters — and ``qc`` matches
    cfg.check_quorum (contact lanes are [N, G, P] / [N, G], group-major
    like the match matrix), ``lease`` whether the lease is carried
    (cfg.lease_carry_ticks > 0: two [N, G] guard lanes)."""
    kw = {f.name: _NODE_GROUP for f in dataclasses.fields(RaftState)}
    for name in _STATE_NODE_ONLY:
        kw[name] = _NODE
    kw["log"] = LogState(term=_NODE_GROUP, conf=_NODE_GROUP,
                         base=_NODE_GROUP, base_term=_NODE_GROUP,
                         base_conf=_NODE_GROUP, last=_NODE_GROUP)
    kw["trace"] = TraceState(
        tick=_NODE_GROUP, kind=_NODE_GROUP, term=_NODE_GROUP,
        aux=_NODE_GROUP, n=_NODE_GROUP) if trace else None
    kw["heat"] = HeatState(
        appended=_NODE_GROUP, sent=_NODE_GROUP, commits=_NODE_GROUP,
        reads=_NODE_GROUP) if heat else None
    kw["qc"] = QuorumContact(
        heard=_NODE_GROUP, since=_NODE_GROUP) if qc else None
    kw["lease"] = LeaseGuard(
        vote_hold=_NODE_GROUP, carry_bar=_NODE_GROUP) if lease else None
    # Hibernation (cfg.hibernate) and strict ReadIndex's counter
    # (cfg.read_lease off) are a served deployment's: the sharded harness
    # runs without them.
    kw["hib"] = kw["read_seq"] = None
    return RaftState(**kw)


def messages_pspecs() -> Messages:
    """Specs for stacked [N, P, G, ...] message planes (axis 2 = group)."""
    kw = {f.name: _NODE_PEER_GROUP for f in dataclasses.fields(Messages)}
    kw["ae_sleep"] = kw["aer_asleep"] = None
    kw["ae_seq"] = kw["aer_seq"] = None
    return Messages(**kw)


def info_pspecs(qc: bool = False) -> StepInfo:
    """``qc`` must match whether the info carries the CheckQuorum lanes
    (cfg.check_quorum) — None-subtree pairing like :func:`state_pspecs`."""
    kw = {f.name: _NODE_GROUP for f in dataclasses.fields(StepInfo)}
    if not qc:
        kw["cq_stepdown"] = None
        kw["cq_veto"] = None
    kw["asleep"] = None
    return StepInfo(**kw)


def host_pspecs(durable: bool = False) -> HostInbox:
    """Specs for a stacked [N, ...] HostInbox (callers that device_put a
    pre-built inbox instead of folding ``auto_host_inbox`` into the scan).
    ``read_veto`` and ``clock`` are per-node scalars; ``durable`` must
    match whether the inbox carries the durable-tail feedback lane (a None subtree needs a
    None spec, exactly like the trace lanes in :func:`state_pspecs`)."""
    kw = {f.name: _NODE_GROUP for f in dataclasses.fields(HostInbox)}
    kw["read_veto"] = kw["clock"] = _NODE
    kw["durable_tail"] = _NODE_GROUP if durable else None
    kw["wake"] = None
    return HostInbox(**kw)


# Non-pytree cluster inputs.
CONN_PSPEC = PS("node")        # [N, N] connectivity — rows ride the node axis
SUBMIT_PSPEC = PS("node", "group")  # [N, G] offered load


def fault_schedule_pspecs() -> FaultSchedule:
    """Specs for a [T, ...] FaultSchedule: the tick axis is scanned (never
    sharded); the first NODE axis rides the mesh's node dimension, exactly
    like CONN_PSPEC's rows — so each device holds its own node's fault
    lanes and the scan consumes them without cross-chip gathers."""
    return FaultSchedule(
        link_up=PS(None, "node"),   # [T, N, N] — sender rows per device
        crash=PS(None, "node"),     # [T, N]
        stall=PS(None, "node"),     # [T, N]
        dup=PS(None, "node"),       # [T, N, N]
    )


def shard_fault_schedule(mesh: Mesh, sched: FaultSchedule) -> FaultSchedule:
    """device_put a fault schedule with its per-field specs (the nemesis
    analog of :func:`shard_cluster`)."""
    T, N = sched.crash.shape
    assert sched.link_up.shape == (T, N, N), sched.link_up.shape
    assert sched.stall.shape == (T, N), sched.stall.shape
    assert sched.dup.shape == (T, N, N), sched.dup.shape
    return jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        sched, fault_schedule_pspecs())


def validate_cluster_shapes(cfg: EngineConfig, states: RaftState,
                            inflight: Messages, info: StepInfo,
                            conn: jax.Array | None = None,
                            submit: jax.Array | None = None) -> None:
    """Assert the declared group axes actually hold G — the guard that makes
    the per-field specs safe regardless of dimension-size collisions."""
    G, P = cfg.n_groups, cfg.n_peers
    N = states.term.shape[0]
    assert states.term.ndim == 2 and states.term.shape[1] == G, states.term.shape
    assert states.next_idx.shape[1:] == (G, P), states.next_idx.shape
    assert states.log.term.shape[1] == G, states.log.term.shape
    if states.trace is not None:
        assert states.trace.tick.shape[1] == G, states.trace.tick.shape
        assert states.trace.n.shape[1:] == (G,), states.trace.n.shape
    if states.heat is not None:
        assert states.heat.appended.shape[1:] == (G,), \
            states.heat.appended.shape
    if states.qc is not None:
        assert states.qc.heard.shape[1:] == (G, P), states.qc.heard.shape
        assert states.qc.since.shape[1:] == (G,), states.qc.since.shape
    assert inflight.ae_valid.ndim == 3 and inflight.ae_valid.shape[2] == G, \
        inflight.ae_valid.shape
    assert info.commit.shape[1] == G, info.commit.shape
    if conn is not None:
        assert conn.shape == (N, N), conn.shape
    if submit is not None:
        assert submit.shape == (N, G), submit.shape


def shard_cluster(mesh: Mesh, cfg: EngineConfig, states: RaftState,
                  inflight: Messages, info: StepInfo, conn: jax.Array,
                  submit: jax.Array) -> Tuple[RaftState, Messages, StepInfo,
                                              jax.Array, jax.Array]:
    """device_put every cluster input with its explicit per-field spec."""
    validate_cluster_shapes(cfg, states, inflight, info, conn, submit)

    def put(tree, specs):
        # The arrays tree leads: specs are flattened only up to its
        # structure, so each PartitionSpec stays atomic at a leaf position.
        return jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            tree, specs)

    states = put(states, state_pspecs(trace=states.trace is not None,
                                      heat=states.heat is not None,
                                      qc=states.qc is not None,
                                      lease=states.lease is not None))
    inflight = put(inflight, messages_pspecs())
    info = put(info, info_pspecs(qc=info.cq_stepdown is not None))
    conn = jax.device_put(conn, NamedSharding(mesh, CONN_PSPEC))
    submit = jax.device_put(submit, NamedSharding(mesh, SUBMIT_PSPEC))
    return states, inflight, info, conn, submit
