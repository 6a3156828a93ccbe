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
