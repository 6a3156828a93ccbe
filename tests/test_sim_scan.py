"""The fused scan path (`run_cluster_ticks`) under test — the exact program
__graft_entry__.dryrun_multichip runs.

These tests pin (a) bit-parity between the fused scan and the per-tick
`DeviceCluster.tick` path and (b) the group-blocked runner's protocol
invariants.  The same program on the chip is chip_smoke.py's engine phase.
"""

import jax.numpy as jnp
import numpy as np

from rafting_tpu import DeviceCluster, EngineConfig
from rafting_tpu.core.sim import (
    committed_entries, run_cluster_ticks, run_cluster_ticks_blocked,
)


def _cfg(G=48):
    return EngineConfig(n_groups=G, n_peers=3, log_slots=32, batch=4,
                        max_submit=4, election_ticks=10, heartbeat_ticks=3)


def test_scan_bit_identical_to_per_tick_path():
    """One fused 64-tick scan == 64 individual DeviceCluster.tick calls."""
    cfg = _cfg()
    a = DeviceCluster(cfg, seed=3)
    b = DeviceCluster(cfg, seed=3)
    for _ in range(64):
        a.tick(submit_n=2)
    sub = jnp.full((cfg.n_peers, cfg.n_groups), 2, jnp.int32)
    s, inflight, info = run_cluster_ticks(
        cfg, 64, b.states, b.inflight, b.last_info, b.conn, sub)

    for name in ("term", "role", "voted_for", "leader_id", "commit",
                 "next_idx", "match_idx", "inflight", "elect_deadline"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a.states, name)), np.asarray(getattr(s, name)),
            err_msg=name)
    np.testing.assert_array_equal(np.asarray(a.states.log.term),
                                  np.asarray(s.log.term))
    np.testing.assert_array_equal(np.asarray(a.states.log.last),
                                  np.asarray(s.log.last))
    np.testing.assert_array_equal(np.asarray(a.last_info.commit),
                                  np.asarray(info.commit))
    assert int(committed_entries(s)) > 0


def test_blocked_runner_invariants():
    """Group-tiled execution (4 blocks of 32, padded from 100) preserves the
    protocol invariants; padding lanes stay inert."""
    cfg = _cfg(G=100)
    c = DeviceCluster(cfg, seed=0)
    sub = jnp.full((cfg.n_peers, cfg.n_groups), 3, jnp.int32)
    s, inflight, info = run_cluster_ticks_blocked(
        cfg, 96, c.states, c.inflight, c.last_info, c.conn, sub, 32)

    roles = np.asarray(s.role)
    commit = np.asarray(s.commit)
    last = np.asarray(s.log.last)
    term = np.asarray(s.term)
    assert roles.shape == (3, 100)
    assert ((roles == 3).sum(axis=0) == 1).all(), "one leader per group"
    assert (commit.max(axis=0) > 0).all(), "every group commits"
    assert (commit <= last).all(), "commit never passes the log tail"
    # Leader completeness: the leader's term is the max across the cluster.
    lead_term = (term * (roles == 3)).max(axis=0)
    assert (lead_term == term.max(axis=0)).all()


def test_blocked_equals_unblocked_when_block_covers_all():
    cfg = _cfg(G=40)
    a = DeviceCluster(cfg, seed=1)
    b = DeviceCluster(cfg, seed=1)
    sub = jnp.full((cfg.n_peers, cfg.n_groups), 2, jnp.int32)
    s1, _, _ = run_cluster_ticks(
        cfg, 48, a.states, a.inflight, a.last_info, a.conn, sub)
    s2, _, _ = run_cluster_ticks_blocked(
        cfg, 48, b.states, b.inflight, b.last_info, b.conn, sub, 64)
    np.testing.assert_array_equal(np.asarray(s1.commit), np.asarray(s2.commit))
    np.testing.assert_array_equal(np.asarray(s1.term), np.asarray(s2.term))
