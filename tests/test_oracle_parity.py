"""Kernel ↔ scalar-oracle parity under randomized chaos schedules.

Every tick, each node's (state, inbox, host inbox) is fed both to the
vectorized kernel (`node_step`) and to the loop-based scalar oracle
(`testkit.oracle.oracle_step`); the resulting state, every outbound message
(masked by its validity lane) and the step info must agree exactly.  The
kernel's outputs carry the simulation forward, so each tick is an
independent check and divergence cannot compound silently.

This is the election-safety/semantics parity requirement from BASELINE.md
("election-safety parity vs CPU event-loop path") made mechanical — the
vectorized analog of the reference's manual 3-process kill/restart oracle
(README.md:28-33).
"""

import numpy as np
import pytest

from rafting_tpu.core.types import EngineConfig
from rafting_tpu.testkit.parity import run_parity


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_parity_prevote(seed):
    cfg = EngineConfig(n_groups=8, n_peers=3, log_slots=16, batch=4,
                       max_submit=4, election_ticks=6, heartbeat_ticks=2,
                       rpc_timeout_ticks=5, pre_vote=True)
    run_parity(seed, n_ticks=60, cfg=cfg)


def test_parity_no_prevote():
    cfg = EngineConfig(n_groups=8, n_peers=3, log_slots=16, batch=4,
                       max_submit=4, election_ticks=6, heartbeat_ticks=2,
                       rpc_timeout_ticks=5, pre_vote=False)
    run_parity(7, n_ticks=60, cfg=cfg)


def test_parity_strict_read_index():
    """Lease fast path OFF: barrier evidence is the echoed send tick (the
    textbook dedicated-confirmation-round ReadIndex) — the read plane's
    other mode must hold kernel<->oracle parity too, under the full
    partition + crash-restart + clock-stall chaos mix."""
    cfg = EngineConfig(n_groups=8, n_peers=3, log_slots=16, batch=4,
                       max_submit=4, election_ticks=6, heartbeat_ticks=2,
                       rpc_timeout_ticks=5, pre_vote=True, read_lease=False)
    run_parity(13, n_ticks=60, cfg=cfg, crash_p=0.04, stall_p=0.06)


def test_parity_small_read_fifo():
    """K=1 pending slot: intake backpressure (offers refused while a batch
    is pending) and same-tick lease release both exercised at the ring's
    smallest size — with crash-restarts dropping the FIFO and clock
    stalls drifting the lease evidence clocks (the lease adversary)."""
    cfg = EngineConfig(n_groups=8, n_peers=3, log_slots=16, batch=4,
                       max_submit=4, election_ticks=6, heartbeat_ticks=2,
                       rpc_timeout_ticks=5, pre_vote=True, read_slots=1)
    run_parity(17, n_ticks=60, cfg=cfg, crash_p=0.04, stall_p=0.06)


def test_parity_five_nodes():
    cfg = EngineConfig(n_groups=4, n_peers=5, log_slots=16, batch=2,
                       max_submit=2, election_ticks=8, heartbeat_ticks=2,
                       rpc_timeout_ticks=6, pre_vote=True)
    run_parity(11, n_ticks=50, cfg=cfg, drop_p=0.25, part_p=0.15)


def test_parity_heat_lanes_under_chaos():
    """cfg.heat on: the scalar oracle mirrors the device heat lanes
    (appended / sent / commits / reads) tick-for-tick — under the full
    drop + partition + crash-restart + clock-stall mix, since activity
    history is observability state that must survive crash_restart
    untouched.  assert_state_equal covers every heat.* field; on top of
    that the lanes must actually accumulate (a run that never moved a
    counter proves nothing)."""
    cfg = EngineConfig(n_groups=8, n_peers=3, log_slots=16, batch=4,
                       max_submit=4, election_ticks=6, heartbeat_ticks=2,
                       rpc_timeout_ticks=5, pre_vote=True, heat=True)
    states, _ = run_parity(19, n_ticks=60, cfg=cfg,
                           crash_p=0.04, stall_p=0.06)
    final = states[-1]
    assert final.heat is not None
    assert int(np.asarray(final.heat.appended).sum()) > 0
    assert int(np.asarray(final.heat.sent).sum()) > 0
    assert int(np.asarray(final.heat.commits).sum()) > 0


# The final states of the heartbeat_ticks=1 case below, as the parent of
# PR 39 (the commit before the carried lease) left them: sha256 over every
# leaf of every node's state, in tree order.
PARENT_DIGEST_HB1 = \
    "d562be343027e877ccceb4b3a8616ebd48946f8b94a5c0ff082de6d4801bfc2e"


@pytest.mark.parametrize("hb", [1, 2])
def test_parity_tikv_timing_with_reads(hb):
    """TiKV's timing (election 10) with reads in the schedule, restarts,
    stalls, transfers and steps that do not advance the clock.  At a
    1-tick heartbeat the lease is not carried and the run ends in the
    states the parent ended in, bit for bit; at 2 it is carried (core/
    step.py phase 6b), the oracle's read plane moves in lock step, leaf
    for leaf, and some reads really ride carried evidence."""
    import hashlib

    import jax

    cfg = EngineConfig(n_groups=8, n_peers=3, log_slots=16, batch=4,
                       max_submit=4, election_ticks=10, heartbeat_ticks=hb,
                       rpc_timeout_ticks=5)
    assert cfg.lease_carry_ticks == hb - 1
    states, stats = run_parity(23, n_ticks=90, cfg=cfg, crash_p=0.03,
                               stall_p=0.04, xfer_p=0.02, arrival_p=0.3)
    assert stats["lease_reads"] > 0 and stats["crashes"] > 0
    if hb == 1:
        assert stats["lease_carried"] == 0
        assert all(s.lease is None for s in states)
        h = hashlib.sha256()
        for st in states:
            for leaf in jax.tree.leaves(st):
                h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
        assert h.hexdigest() == PARENT_DIGEST_HB1
    else:
        assert stats["lease_carried"] > 0
        assert all(s.lease is not None for s in states)


@pytest.mark.parametrize("hibernate", [False, True])
@pytest.mark.parametrize("seed", [23, 5])
def test_parity_hibernation(seed, hibernate):
    """TiKV's timing with calm stretches in the schedule, long enough
    for a cluster with ``hibernate`` to fall asleep, and chaos (drops,
    cuts, restarts, stalls, transfers, config changes, the host's
    peer-lost signal, steps that do not advance the clock) to wake it:
    the oracle's timers, heartbeats and read plane move in lock step at
    both settings, leaf for leaf.  On, lanes really sleep and wake; off,
    the state holds no hibernation lane and the wire no flag (core/
    step.py "hibernation", case e; the digest above pins the program
    itself)."""
    cfg = EngineConfig(n_groups=8, n_peers=3, log_slots=16, batch=4,
                       max_submit=4, election_ticks=10, heartbeat_ticks=2,
                       rpc_timeout_ticks=5, hibernate=hibernate)
    states, stats = run_parity(
        seed, n_ticks=150, cfg=cfg, crash_p=0.03, stall_p=0.04,
        xfer_p=0.02, conf_p=0.01, arrival_p=0.3,
        calm=((30, 70), (95, 130)), wake_p=0.01 if hibernate else 0.0)
    assert stats["crashes"] > 0 and stats["lease_reads"] > 0
    if hibernate:
        assert stats["asleep_steps"] > 200 and stats["wakes"] > 10
        assert all(s.hib is not None for s in states)
    else:
        assert stats["asleep_steps"] == 0
        assert all(s.hib is None for s in states)


def test_parity_hibernation_five_nodes_check_quorum():
    """Five members, CheckQuorum on: a leader asleep is not deposed for
    the silence it agreed to, and the oracle agrees step for step."""
    cfg = EngineConfig(n_groups=4, n_peers=5, log_slots=16, batch=2,
                       max_submit=2, election_ticks=8, heartbeat_ticks=2,
                       rpc_timeout_ticks=6, check_quorum=True,
                       hibernate=True)
    _, stats = run_parity(11, n_ticks=120, cfg=cfg, drop_p=0.25,
                          part_p=0.15, calm=((40, 80),))
    assert stats["asleep_steps"] > 100 and stats["wakes"] > 0
