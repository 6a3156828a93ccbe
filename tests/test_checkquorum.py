"""CheckQuorum: vectorized gray-failure step-down (core/step.py phase 6c).

The classic gray failure CheckQuorum exists for: an inbound-only cut leaves
a leader able to SEND heartbeats (suppressing every follower's election
timer) but unable to HEAR acks — phase 1's higher-term step-down never
fires, and without CheckQuorum the group is hostage to a half-dead leader
forever.  arXiv:2004.05074 ("Paxos vs. Raft") names this the practical
liveness gap of leader leases; etcd's CheckQuorum is the standard remedy.

Covered here:
* kernel <-> scalar-oracle parity tick-for-tick with ``check_quorum`` on,
  under the full drop + partition + crash-restart + clock-stall (+
  membership/transfer) chaos mix, lease fast path on AND off;
* the hostage contrast: under an asymmetric inbound cut the leader steps
  down within two election timeouts with CheckQuorum on, and provably
  does NOT with it off;
* post-stepdown liveness: the rest of the fleet re-elects and commits;
* zero-cost-when-off: ``check_quorum=False`` carries no qc lanes and the
  step emits the seed's exact pytree structure.
"""

import dataclasses

import jax
import numpy as np
import pytest

from rafting_tpu.core.cluster import DeviceCluster
from rafting_tpu.core.types import (
    EngineConfig, HostInbox, LEADER, Messages, StepInfo, crash_restart,
    init_state,
)
from rafting_tpu.testkit.parity import run_parity

CFG = dict(n_groups=8, n_peers=3, log_slots=16, batch=4, max_submit=4,
           election_ticks=6, heartbeat_ticks=2, rpc_timeout_ticks=5,
           pre_vote=True, check_quorum=True)


@pytest.mark.parametrize("seed", [23, 31])
def test_parity_check_quorum_lease(seed):
    """Full chaos mix with the lease fast path on: the qc lanes (heard /
    since), the 6c step-down, and the step-down's lease-veto accounting
    (StepInfo.cq_stepdown / cq_veto) all mirror in the scalar oracle."""
    cfg = EngineConfig(**CFG)
    run_parity(seed, n_ticks=60, cfg=cfg, crash_p=0.04, stall_p=0.06)


def test_parity_check_quorum_strict_read_index():
    """Lease off: a 6c step-down must still abort pending ReadIndex
    barriers (phase 8b keep_reads) identically in kernel and oracle."""
    cfg = EngineConfig(**dict(CFG, read_lease=False))
    run_parity(29, n_ticks=60, cfg=cfg, crash_p=0.04, stall_p=0.06)


def test_parity_check_quorum_membership():
    """Joint-config quorums: contact_quorum needs majority contact in BOTH
    C_old and C_new while a §6 walk is in flight — chaos membership
    changes and transfers exercise that against the oracle."""
    cfg = EngineConfig(**CFG)
    run_parity(37, n_ticks=60, cfg=cfg, crash_p=0.03, stall_p=0.04,
               conf_p=0.05, xfer_p=0.05)


def _settle(cfg, seed=1, ticks=60):
    c = DeviceCluster(cfg, seed=seed)
    for _ in range(ticks):
        c.tick(submit_n=1)
    return c


def _inbound_cut(c, node):
    """Cut every link INTO ``node`` while its outbound links stay up — the
    asymmetric gray failure (LinkFaults.isolate cuts both directions and
    would let phase 1 handle it; the whole point is that it can't here)."""
    N = c.cfg.n_peers
    conn = np.ones((N, N), bool)
    for o in range(N):
        if o != node:
            conn[o, node] = False  # conn[src, dst]
    import jax.numpy as jnp
    c.conn = jnp.asarray(conn)


def test_stepdown_within_two_timeouts():
    cfg = EngineConfig(n_groups=4, n_peers=3, check_quorum=True)
    c = _settle(cfg)
    lead = c.leaders(0)[0]
    _inbound_cut(c, lead)
    down_at = None
    for t in range(1, 2 * cfg.election_ticks + 1):
        c.tick(submit_n=1)
        if not (np.asarray(c.states.role[lead]) == LEADER).any():
            down_at = t
            break
    assert down_at is not None, \
        "isolated leader still leading after 2 election timeouts"
    # Liveness after the cut: the healthy majority re-elects and commits.
    before = int(np.asarray(c.states.commit).max(axis=0).sum())
    for _ in range(6 * cfg.election_ticks):
        c.tick(submit_n=1)
    for g in range(cfg.n_groups):
        ls = c.leaders(g)
        assert ls and ls[0] != lead, f"group {g} not re-elected: {ls}"
    after = int(np.asarray(c.states.commit).max(axis=0).sum())
    assert after > before, "no commits after re-election"


def test_hostage_without_check_quorum():
    """The counterfactual: same cut, check_quorum off — the half-dead
    leader keeps leading every group it led (its heartbeats still reach
    the followers, so nobody ever times out)."""
    cfg = EngineConfig(n_groups=4, n_peers=3, check_quorum=False)
    c = _settle(cfg)
    lead = c.leaders(0)[0]
    led = np.asarray(c.states.role[lead]) == LEADER
    _inbound_cut(c, lead)
    for _ in range(4 * cfg.election_ticks):
        c.tick(submit_n=1)
    still = np.asarray(c.states.role[lead]) == LEADER
    assert (still & led).sum() == led.sum(), \
        "leader lost groups without CheckQuorum under an inbound-only cut"


def test_check_quorum_off_prunes_lanes():
    """Zero-cost-when-off: the off build carries None qc subtrees in state
    and info — the seed's exact pytree structure, so the compiled program
    is the seed's program (the None-subtree contract of trace/heat)."""
    cfg_off = EngineConfig(n_groups=4, n_peers=3, check_quorum=False)
    cfg_on = EngineConfig(n_groups=4, n_peers=3, check_quorum=True)
    s_off = init_state(cfg_off, 0)
    assert s_off.qc is None
    assert StepInfo.empty(cfg_off).cq_stepdown is None
    assert StepInfo.empty(cfg_off).cq_veto is None
    s_on = init_state(cfg_on, 0)
    assert s_on.qc is not None
    assert s_on.qc.heard.shape == (4, 3)
    assert s_on.qc.since.shape == (4,)
    assert StepInfo.empty(cfg_on).cq_stepdown is not None
    # The off structure is exactly the on structure minus the qc leaves
    # (field set identical, optional subtrees None) — i.e. the seed tree.
    off_leaves = {p for p, _ in
                  jax.tree_util.tree_leaves_with_path(s_off)}
    on_leaves = {p for p, _ in jax.tree_util.tree_leaves_with_path(s_on)}
    extra = {jax.tree_util.keystr(p) for p in on_leaves - off_leaves}
    assert extra == {".qc.heard", ".qc.since"}, extra


def test_qc_lanes_volatile_across_crash():
    """Contact history is volatile: a crash-restart must zero heard/since
    (a restarted node has heard nothing), like every in-memory lane."""
    cfg = EngineConfig(n_groups=4, n_peers=3, check_quorum=True)
    c = _settle(cfg, ticks=40)
    assert int(np.asarray(c.states.qc.heard).max()) > 0
    s0 = jax.tree.map(lambda a: a[0], c.states)
    r = crash_restart(cfg, s0)
    assert int(np.asarray(r.qc.heard).sum()) == 0
    assert int(np.asarray(r.qc.since).sum()) == 0


def test_quiet_leader_stays_up():
    """No false positives: in a healthy, completely idle cluster (no load)
    heartbeat acks alone refresh contact, and no leader ever steps down
    across many election timeouts."""
    cfg = EngineConfig(n_groups=4, n_peers=3, check_quorum=True)
    c = _settle(cfg)
    leads = {g: c.leaders(g) for g in range(cfg.n_groups)}
    for _ in range(8 * cfg.election_ticks):
        info = c.tick()  # zero offered load
        assert not bool(np.asarray(info.cq_stepdown).any())
    assert {g: c.leaders(g) for g in range(cfg.n_groups)} == leads
