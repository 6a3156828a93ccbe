#!/usr/bin/env python
"""BASELINE config 5 validation: 100k groups with InstallSnapshot
lagging-follower catch-up, on the backend JAX finds, with in-kernel invariant
checks compiled in.

Scenario: one node is isolated while the majority keeps committing and
COMPACTING until every group's log floor has passed the victim's frozen
tail — at that point log replication alone cannot catch it up anywhere
(reference Leadership.java:111-113 pendingInstallation trigger).  After
heal, the leader's InstallSnapshot offers drive the victim's snapshot
plane (device phases 5/9; the sim's host inbox services the bulk
transfer instantly — the payload-free analog of the out-of-band snapshot
channel), and every group must converge via a FLOOR JUMP, not log replay.

Usage: python tools/validate_config5.py [n_groups]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import numpy as np
    import jax
    from rafting_tpu import DeviceCluster, EngineConfig, LEADER

    from _artifact import PhaseLog

    G = int(sys.argv[1]) if len(sys.argv) > 1 else 100_000
    cfg = EngineConfig(n_groups=G, n_peers=3, log_slots=64, batch=8,
                       max_submit=8, election_ticks=10, heartbeat_ticks=3,
                       rpc_timeout_ticks=8, debug_checks=True)
    plog = PhaseLog("config5", seed=5,
                    config={"n_groups": G, "n_peers": 3, "log_slots": 64,
                            "batch": 8, "max_submit": 8, "submit_n": 4,
                            "compact_every": 16, "debug_checks": True})
    c = DeviceCluster(cfg, seed=5)
    # Discrete compaction cadence (every 16 ticks), matching real
    # checkpoint-gated compaction: a floor advancing EVERY tick outruns
    # any snapshot install under sustained load and no laggard could ever
    # converge (see auto_host_inbox).
    c.compact = 16
    t0 = time.time()
    for _ in range(60):
        c.tick(submit_n=4)
    roles = np.asarray(c.states.role)
    assert ((roles == LEADER).sum(axis=0) == 1).all()
    plog.phase("elect+replicate", groups=G,
               elapsed_s=round(time.time() - t0, 1))

    victim = 2
    victim_tail = np.asarray(c.states.log.last)[victim].copy()
    c.isolate(victim)
    # Majority commits + compacts until every group's floor passes the
    # victim's frozen tail (floor chases commit - L/4 via the sim's
    # maintain policy, so ~L more commits per group suffice).
    for k in range(12):
        for _ in range(30):
            c.tick(submit_n=4)
        floors = np.asarray(c.states.log.base)[:2].min(axis=0)
        frac = float((floors > victim_tail).mean())
        plog.phase("isolated", ticks=30 * (k + 1),
                   floors_past_victim_pct=round(frac * 100, 2))
        if frac == 1.0:
            break
    assert (np.asarray(c.states.log.base)[:2].min(axis=0)
            > victim_tail).all(), "compaction never passed the victim"

    c.heal()
    commit_majority = np.asarray(c.states.commit)[:2].max(axis=0)
    for k in range(10):
        for _ in range(30):
            c.tick(submit_n=4)
        v_commit = np.asarray(c.states.commit)[victim]
        frac = float((v_commit >= commit_majority).mean())
        plog.phase("healed", ticks=30 * (k + 1),
                   caught_up_pct=round(frac * 100, 2))
        if frac == 1.0:
            break
    v_commit = np.asarray(c.states.commit)[victim]
    assert (v_commit >= commit_majority).all(), \
        f"victim stuck on {int((v_commit < commit_majority).sum())} groups"
    # Drain without load so in-flight installs/replication settle before
    # the lane checks (flags mid-clear at the convergence instant are
    # normal operation, not stuck state).
    for _ in range(40):
        c.tick()
    # The catch-up must have been via snapshot installation: the victim's
    # floor jumped past its pre-heal tail on every group.
    v_base = np.asarray(c.states.log.base)[victim]
    assert (v_base > victim_tail).all(), "catch-up without a floor jump"
    # Pending installations must be gone on LIVE leader lanes (deposed
    # leaders keep stale need_snap bookkeeping by design — it is inert
    # and reset on the next election win).
    lead_lanes = (np.asarray(c.states.role) == LEADER)[:, :, None]
    assert not (np.asarray(c.states.need_snap) & lead_lanes).any(), \
        "pending installations remain on live leaders after convergence"
    platform = jax.devices()[0].platform
    plog.phase("converged", floor_jump_groups=G)
    plog.save(platform)
    print(f"config-5 OK on {platform}: all {G} groups "
          f"caught up via snapshot floor jump; total {time.time() - t0:.0f}s",
          flush=True)


if __name__ == "__main__":
    main()
