"""Fused multi-tick cluster simulation: a `lax.scan` over whole-cluster steps.

One compiled program advances an N-node, G-group Multi-Raft cluster by many
ticks without touching the host — the measurement core for the benchmark and
the fast path for large-scale tests.  The host-policy loop (submissions,
slack compaction, instant snapshot service) is folded into the scan body via
``auto_host_inbox``.

``run_cluster_ticks_blocked`` tiles the group axis: groups are independent
(no cross-group dataflow anywhere in the step), so a ``lax.map`` over blocks
of <= ``group_block`` groups — each block running the WHOLE tick scan — is
semantically exact while keeping every compiled program inside the working
envelope the TPU has been proven to handle (r1: the single fused program ran
at 32k groups and faulted at >= 65k).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from .cluster import auto_host_inbox, cluster_step, cluster_step_nemesis
from .shard import info_pspecs, messages_pspecs, state_pspecs, SUBMIT_PSPEC
from .types import EngineConfig, FaultSchedule, Messages, RaftState, StepInfo


def _scan_ticks(cfg: EngineConfig, n_ticks: int, states: RaftState,
                inflight: Messages, prev_info: StepInfo, conn: jax.Array,
                submit_n: jax.Array, read_n=None, durable_lag: bool = False
                ) -> Tuple[RaftState, Messages, StepInfo]:
    def body(carry, _):
        states, inflight, info = carry
        host = auto_host_inbox(cfg, states, submit_n, True, info, read_n,
                               durable_lag)
        states, inflight, info = cluster_step(cfg, states, inflight, host,
                                              conn)
        return (states, inflight, info), ()

    (states, inflight, info), _ = jax.lax.scan(
        body, (states, inflight, prev_info), None, length=n_ticks)
    return states, inflight, info


@partial(jax.jit, static_argnums=(0, 1, 8), donate_argnums=(2, 3, 4))
def run_cluster_ticks(cfg: EngineConfig, n_ticks: int, states: RaftState,
                      inflight: Messages, prev_info: StepInfo,
                      conn: jax.Array, submit_n: jax.Array,
                      read_n=None, durable_lag: bool = False
                      ) -> Tuple[RaftState, Messages, StepInfo]:
    """Advance the cluster `n_ticks` ticks under a constant offered load.

    ``submit_n`` is [N, G]: commands offered to every node each tick (only
    leaders accept).  ``read_n`` (optional, [N, G]) additionally offers
    linearizable read batches each tick (read plane, core/step.py phase
    8b; reads never touch the log).  ``durable_lag`` (static) feeds each
    tick's ``HostInbox.durable_tail`` from the previous tick's log tail —
    the in-scan model of the pipelined runtime's one-tick durability
    barrier (see ``auto_host_inbox``).  Returns the final carry; per-tick
    outputs are not materialized (the benchmark reads commit deltas from
    the state — for read-plane accounting use
    :func:`run_cluster_ticks_reads`).
    """
    return _scan_ticks(cfg, n_ticks, states, inflight, prev_info, conn,
                       submit_n, read_n, durable_lag)


@partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3, 4))
def run_cluster_ticks_reads(cfg: EngineConfig, n_ticks: int,
                            states: RaftState, inflight: Messages,
                            prev_info: StepInfo, conn: jax.Array,
                            submit_n: jax.Array, read_n: jax.Array
                            ) -> Tuple[RaftState, Messages, StepInfo,
                                       jax.Array, jax.Array, jax.Array]:
    """`run_cluster_ticks` with read-plane accounting in the carry.

    Offers ``read_n`` [N, G] linearizable read batches per node per tick on
    top of ``submit_n`` writes and accumulates, across the whole fused
    scan: total individual reads served, total batches released by the
    same-tick lease fast path, and total log entries appended (the bench's
    zero-log-growth / mixed-load evidence).  Returns ``(states, inflight,
    info, reads_served, lease_hits, appended)``.  The counters are i32
    scalars like every engine lane (core/types.py I32 design): one scan
    must keep ``n_ticks * N * G * reads_per_batch`` under ~2^31 — the
    bench drives bounded chunks, so chunk totals never approach it (the
    host sums chunks in Python ints).
    """
    from .types import I32

    def body(carry, _):
        states, inflight, info, served, lease, appended = carry
        host = auto_host_inbox(cfg, states, submit_n, True, info, read_n)
        states, inflight, info = cluster_step(cfg, states, inflight, host,
                                              conn)
        served = served + info.read_served.sum()
        lease = lease + info.read_lease.astype(I32).sum()
        appended = appended + jnp.where(
            info.appended_to > 0,
            info.appended_to - info.appended_from + 1, 0).sum()
        return (states, inflight, info, served, lease, appended), ()

    zero = jnp.zeros((), I32)
    (states, inflight, info, served, lease, appended), _ = jax.lax.scan(
        body, (states, inflight, prev_info, zero, zero, zero), None,
        length=n_ticks)
    return states, inflight, info, served, lease, appended


@partial(jax.jit, static_argnums=0, donate_argnums=(1, 2, 3))
def run_cluster_ticks_nemesis(cfg: EngineConfig, states: RaftState,
                              inflight: Messages, prev_info: StepInfo,
                              sched: FaultSchedule, submit_n: jax.Array,
                              read_n=None
                              ) -> Tuple[RaftState, Messages, StepInfo]:
    """Advance the cluster ``sched.n_ticks`` ticks under a fault schedule.

    The device-side nemesis: the whole chaos scenario — per-tick directed
    link masks, crash-restarts, clock stalls, duplicate deliveries — is
    data riding ``lax.scan`` as scan inputs, so the run executes inside
    ONE compiled program with zero per-tick host round-trips (the
    requirement that lets chaos run at the benchmark's 10k-100k-group
    scale instead of `DeviceCluster.tick`'s host-loop pace).  Tick count
    comes from the schedule's leading axis.  Fully deterministic: same
    seed + same schedule replays bit-identically (every lane is integer /
    counter-mode PRNG — there is no order-dependent float math to drift).

    ``submit_n`` is [N, G] constant offered load, as in
    :func:`run_cluster_ticks`; ``read_n`` (optional, [N, G]) offers
    linearizable read batches under the same faults — the adversary run
    the read plane's lease safety argument is tested against.  The
    self-driving host policy (``auto_host_inbox``: slack compaction +
    instant snapshot service) is folded into the scan body, with a
    stalled node's StepInfo frozen so its host half stalls with it, and
    ``HostInbox.read_veto`` on the step it wakes in.
    """
    def body(carry, fault):
        states, inflight, info, slept = carry
        host = auto_host_inbox(cfg, states, submit_n, True, info, read_n)
        # A node that sat out the last tick wakes with a clock that lags
        # its peers': its host reads the pause off the wall clock and
        # vetoes the lease evidence it holds (core/step.py phase 6b a).
        host = host.replace(read_veto=slept & ~fault.stall)
        states, inflight, info = cluster_step_nemesis(
            cfg, states, inflight, host, info, fault)
        return (states, inflight, info, fault.stall), ()

    (states, inflight, info, _), _ = jax.lax.scan(
        body, (states, inflight, prev_info,
               jnp.zeros_like(sched.stall[0])), sched)
    return states, inflight, info


def _group_axis(spec) -> int | None:
    entries = tuple(spec)
    return entries.index("group") if "group" in entries else None


def _to_blocks(tree, specs, nb: int, gb: int):
    """Split every group axis into [nb, gb] and move the block axis front.
    Leaves without a group axis are broadcast (shared by every block)."""
    def f(a, spec):
        ax = _group_axis(spec)
        if ax is None:
            return jnp.broadcast_to(a, (nb,) + a.shape)
        pad = nb * gb - a.shape[ax]
        if pad:
            width = [(0, 0)] * a.ndim
            width[ax] = (0, pad)
            a = jnp.pad(a, width)  # zero pad == inactive lanes (active=False)
        a = a.reshape(a.shape[:ax] + (nb, gb) + a.shape[ax + 1:])
        return jnp.moveaxis(a, ax, 0)
    return jax.tree.map(f, tree, specs)


def _from_blocks(tree, specs, G: int):
    """Invert ``_to_blocks``: merge [nb, gb] back into the group axis and
    strip padding.  Block-invariant leaves take block 0's value."""
    def f(a, spec):
        ax = _group_axis(spec)
        if ax is None:
            return a[0]
        a = jnp.moveaxis(a, 0, ax)
        a = a.reshape(a.shape[:ax] + (-1,) + a.shape[ax + 2:])
        return jax.lax.slice_in_dim(a, 0, G, axis=ax)
    return jax.tree.map(f, tree, specs)


@partial(jax.jit, static_argnums=(0, 1, 7), static_argnames=("group_block",),
         donate_argnums=(2, 3, 4))
def run_cluster_ticks_blocked(cfg: EngineConfig, n_ticks: int,
                              states: RaftState, inflight: Messages,
                              prev_info: StepInfo, conn: jax.Array,
                              submit_n: jax.Array, group_block: int
                              ) -> Tuple[RaftState, Messages, StepInfo]:
    """`run_cluster_ticks`, tiled over the group axis.

    Groups never interact, so each block of <= ``group_block`` groups runs
    the whole ``n_ticks`` scan as its own program under ``lax.map``; the
    group count is padded up to a block multiple with inert lanes
    (``active=False`` — zero-padded lanes never elect, accept, or send).
    Per-block PRNG keys are folded with the block index so election jitter
    stays decorrelated across blocks.  Not bit-identical to the unblocked
    run (randomized timeouts are drawn per-block), but protocol-equivalent;
    use the unblocked path when exact parity matters.  The returned state's
    ``rng`` is block 0's folded key (block-invariant leaves collapse to
    block 0), so chaining blocked and unblocked runs changes the
    randomized-timeout stream — fine for throughput runs, not for
    reproducibility-sensitive callers.
    """
    G = cfg.n_groups
    if group_block >= G:
        return _scan_ticks(cfg, n_ticks, states, inflight, prev_info, conn,
                           submit_n)
    nb = -(-G // group_block)
    gb = group_block
    cfg_blk = dataclasses.replace(cfg, n_groups=gb)

    st_specs, msg_specs, inf_specs = (
        state_pspecs(trace=states.trace is not None,
                     heat=states.heat is not None,
                     qc=states.qc is not None,
                     lease=states.lease is not None), messages_pspecs(),
        info_pspecs(qc=prev_info.cq_stepdown is not None))
    states_b = _to_blocks(states, st_specs, nb, gb)
    inflight_b = _to_blocks(inflight, msg_specs, nb, gb)
    info_b = _to_blocks(prev_info, inf_specs, nb, gb)
    submit_b = _to_blocks(submit_n, SUBMIT_PSPEC, nb, gb)
    # Decorrelate the per-node keys across blocks.
    rng_b = jax.vmap(lambda b: jax.vmap(
        lambda k: jax.random.fold_in(k, b))(states.rng))(
            jnp.arange(nb, dtype=jnp.uint32))
    states_b = states_b.replace(rng=rng_b)

    def one_block(blk):
        st, infl, inf, sub = blk
        return _scan_ticks(cfg_blk, n_ticks, st, infl, inf, conn, sub)

    states_o, inflight_o, info_o = jax.lax.map(
        one_block, (states_b, inflight_b, info_b, submit_b))
    return (_from_blocks(states_o, st_specs, G),
            _from_blocks(inflight_o, msg_specs, G),
            _from_blocks(info_o, inf_specs, G))


def committed_entries(states: RaftState) -> jax.Array:
    """Total entries committed across all groups (scalar int64-ish).

    Each group's commit point is counted once, at the furthest node (commit
    indices are identical across nodes once converged)."""
    return jnp.sum(states.commit.max(axis=0).astype(jnp.int64))
