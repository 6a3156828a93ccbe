"""Linearizable read plane: kernel semantics + nemesis linearizability.

The read plane (core/step.py phases 6b/8b, ops/quorum.read_barrier_release)
serves reads off the log: a leader stamps a batch with its commit index
(ReadIndex, Raft dissertation §6.4) and releases it once a majority's
barrier evidence postdates the stamp.  These tests pin its three core
claims:

* reads bypass the append path entirely — a read-only load produces ZERO
  log growth while still being served;
* the served ReadIndex is LINEARIZABLE under adversity: for every released
  batch, its read index covers every write acked (committed anywhere)
  before the batch was stamped — checked tick-by-tick under the standard
  nemesis regimes (partition, crash-restart storm, clock stalls, lossy +
  duplicating links), with the lease fast path both on and off (clock
  stalls are the lease's designated adversary — per-node clocks drift
  apart by design — and duplicate delivery attacks its freshness bound).
"""

import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rafting_tpu.core.cluster import (
    DeviceCluster, auto_host_inbox, cluster_step_nemesis,
)
from rafting_tpu.core.sim import run_cluster_ticks, run_cluster_ticks_reads
from rafting_tpu.core.types import EngineConfig
from rafting_tpu.testkit import nemesis


def _cfg(**kw) -> EngineConfig:
    base = dict(n_groups=4, n_peers=3, log_slots=32, batch=4, max_submit=4,
                election_ticks=6, heartbeat_ticks=2, rpc_timeout_ticks=5,
                pre_vote=True)
    base.update(kw)
    return EngineConfig(**base)


# ------------------------------------------------------------ zero growth --

def _settled(cfg, seed=0, ticks=50):
    c = DeviceCluster(cfg, seed=seed)
    N, G = cfg.n_peers, cfg.n_groups
    zero = jnp.zeros((N, G), jnp.int32)
    states, inflight, info = run_cluster_ticks(
        cfg, ticks, c.states, c.inflight, c.last_info, c.conn, zero)
    return c, states, inflight, info


@pytest.mark.parametrize("lease", [True, False])
def test_read_only_load_zero_log_growth(lease):
    """The acceptance headline: a pure read load is served (ReadIndex
    batches flow, individual reads complete) while the log does not grow
    by a single entry — reads never enter the append path."""
    cfg = _cfg(read_lease=lease)
    c, states, inflight, info = _settled(cfg)
    N, G = cfg.n_peers, cfg.n_groups
    last0 = np.asarray(states.log.last).copy()
    zero = jnp.zeros((N, G), jnp.int32)
    reads = jnp.full((N, G), 4, jnp.int32)
    states, inflight, info, served, lease_hits, appended = \
        run_cluster_ticks_reads(cfg, 50, states, inflight, info, c.conn,
                                zero, reads)
    assert int(served) > 0, "read-only load served nothing"
    assert int(appended) == 0, "reads grew the log"
    np.testing.assert_array_equal(np.asarray(states.log.last), last0)
    if lease:
        # With fresh heartbeat-ack evidence in steady state, at least some
        # batches must release same-tick (zero extra round trips).
        assert int(lease_hits) > 0, "lease fast path never fired"


def test_mixed_load_reads_ride_alongside_writes():
    cfg = _cfg()
    c, states, inflight, info = _settled(cfg)
    N, G = cfg.n_peers, cfg.n_groups
    sub = jnp.full((N, G), 2, jnp.int32)
    reads = jnp.full((N, G), 6, jnp.int32)
    # 50 ticks on purpose: shares the (cfg, n_ticks=50) compiled reads
    # scan with the zero-growth test above (tier-1 time budget).
    states, inflight, info, served, _, appended = run_cluster_ticks_reads(
        cfg, 50, states, inflight, info, c.conn, sub, reads)
    assert int(served) > 0 and int(appended) > 0
    # Only writes append: growth is bounded by the write stream (counted
    # per node — followers append their adopted replicas too).
    assert int(appended) <= 50 * G * cfg.max_submit * N


def test_device_cluster_tick_read_path():
    """DeviceCluster.tick(read_n=...) — the host-loop entry the chaos and
    debug tests drive — stamps and releases reads too."""
    cfg = _cfg()
    c = DeviceCluster(cfg, seed=0)
    for _ in range(40):
        c.tick(submit_n=1)
    for _ in range(10):
        c.tick()   # drain in-flight replication before the tail snapshot
    served = 0
    last0 = np.asarray(c.states.log.last).copy()
    for _ in range(20):
        info = c.tick(read_n=3)
        served += int(np.asarray(info.read_served).sum())
    assert served > 0
    np.testing.assert_array_equal(np.asarray(c.states.log.last), last0)


# -------------------------------------------------- nemesis linearizability --

@functools.lru_cache(maxsize=None)
def _stepper(cfg: EngineConfig):
    """One compiled nemesis stepper per config — the three scenario runs
    of a lease mode share it (compile once, run thrice)."""
    return jax.jit(partial(cluster_step_nemesis, cfg))


def _linearizability_run(cfg: EngineConfig, sched, *, seed=0, submit=2,
                         reads=2) -> int:
    """Drive a FaultSchedule tick-by-tick from the host, asserting the
    read-plane linearizability invariant at every release:

        every released batch's ReadIndex >= the ACKED FRONTIER (max commit
        index across all nodes) as of the tick BEFORE the batch was
        stamped — i.e. no read can ever be served older than a write that
        was acked before the read was issued.

    The acked frontier is exactly the could-have-been-acked set: a commit
    advance requires a quorum at the leader's own term, which a minority
    (stale) leader can never assemble.  Host FIFOs mirror the device's
    rq_* lanes batch-for-batch; a crash or device abort drops them, a
    stalled node's frozen StepInfo replay is skipped (core/sim.py
    freezes StepInfo with the node).  Returns total reads served.
    """
    c = DeviceCluster(cfg, seed=seed)
    N, G = cfg.n_peers, cfg.n_groups
    sub = jnp.full((N, G), submit, jnp.int32)
    rd = jnp.full((N, G), reads, jnp.int32)
    step_fn = _stepper(cfg)
    states, inflight, info = c.states, c.inflight, c.last_info
    crash = np.asarray(sched.crash)
    stall = np.asarray(sched.stall)
    T = sched.n_ticks
    acked = np.zeros(G, np.int64)
    fifos = [[[] for _ in range(G)] for _ in range(N)]
    served = 0
    for t in range(T):
        fault = jax.tree.map(lambda a: a[t], sched)
        host = auto_host_inbox(cfg, states, sub, True, info, rd)
        states, inflight, info = step_fn(states, inflight, host, info, fault)
        h_acc = np.asarray(info.read_acc)
        h_idx = np.asarray(info.read_index)
        h_rel = np.asarray(info.read_rel)
        h_abort = np.asarray(info.read_abort)
        h_srv = np.asarray(info.read_served)
        for n in range(N):
            if stall[t, n]:
                continue   # frozen StepInfo: a replay, not fresh events
            for g in range(G):
                q = fifos[n][g]
                if crash[t, n] or h_abort[n, g]:
                    # Pending reads are volatile: restart/step-down drops
                    # them (clients retry — reads never entered the log).
                    q.clear()
                if h_acc[n, g] > 0:
                    # Stamped this tick: pair the ReadIndex with the acked
                    # frontier as of the END OF THE PREVIOUS tick (writes
                    # acked before this read could have been issued).
                    q.append((int(h_idx[n, g]), int(acked[g])))
                for _ in range(int(h_rel[n, g])):
                    assert q, (f"t={t} n={n} g={g}: device released a "
                               "batch the host FIFO does not hold")
                    ridx, acked_at_stamp = q.pop(0)
                    assert ridx >= acked_at_stamp, (
                        f"t={t} n={n} g={g}: STALE READ — released "
                        f"ReadIndex {ridx} < acked frontier "
                        f"{acked_at_stamp} at stamp time (lease="
                        f"{cfg.read_lease})")
                served += int(h_srv[n, g])
        acked = np.maximum(acked,
                           np.asarray(states.commit).max(axis=0)
                           .astype(np.int64))
    return served


_SCENARIOS = {
    "partition": lambda N, T: nemesis.concat(
        nemesis.split_brain(N, 2 * T // 3, start=5, stop=2 * T // 3 - 10,
                            seed=3),
        nemesis.healthy(N, T - 2 * T // 3)),
    "crash_restart": lambda N, T: nemesis.concat(
        nemesis.crash_storm(N, 2 * T // 3, rate=0.05, seed=4),
        nemesis.healthy(N, T - 2 * T // 3)),
    "clock_stall": lambda N, T: nemesis.concat(
        nemesis.clock_stalls(N, 2 * T // 3, rate=0.06, max_len=6, seed=5),
        nemesis.healthy(N, T - 2 * T // 3)),
    # Lossy + DUPLICATING links: the lease's freshness bound claims a
    # re-delivered ack chain can stretch receipt anchoring by at most one
    # hop (core/step.py phase 6b) — this regime is that claim's adversary.
    "lossy_dup": lambda N, T: nemesis.concat(
        nemesis.lossy_links(N, 2 * T // 3, drop_p=0.15, dup_p=0.3, seed=6),
        nemesis.healthy(N, T - 2 * T // 3)),
}


@pytest.mark.parametrize("lease", [True, False])
def test_read_linearizability_under_nemesis(lease):
    """No read is ever served older than a previously acked write — under
    partitions, crash-restarts and clock stalls, lease on AND off.  The
    clock-stall x lease combination is the designated adversary: stalls
    drift per-node clocks apart, and the lease's receipt-anchored
    evidence must stay sound anyway (its freshness bound compares only
    same-node clock values; see core/step.py phase 6b).  One test per
    lease mode runs all the scenarios so they share one compiled
    nemesis stepper (tier-1 time budget)."""
    cfg = _cfg(read_lease=lease)
    T = 64
    for scenario, build in sorted(_SCENARIOS.items()):
        sched = build(cfg.n_peers, T)
        served = _linearizability_run(cfg, sched)
        assert served > 0, f"{scenario}: no reads served — scenario too harsh"
