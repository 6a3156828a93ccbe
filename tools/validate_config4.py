#!/usr/bin/env python
"""BASELINE config 4 validation: 100k groups x 5 peers, mixed
AppendEntries + RequestVote traffic under partition, on the backend JAX finds,
with in-kernel invariant checks compiled in (EngineConfig.debug_checks).

Runs on the backend JAX finds and says which.  On a TPU: not measured by
any recorded run.

Usage: python tools/validate_config4.py [n_groups]
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
    cfg = EngineConfig(n_groups=G, n_peers=5, log_slots=64, batch=8,
                       max_submit=8, election_ticks=10, heartbeat_ticks=3,
                       rpc_timeout_ticks=8, debug_checks=True)
    plog = PhaseLog("config4", seed=4,
                    config={"n_groups": G, "n_peers": 5, "log_slots": 64,
                            "batch": 8, "max_submit": 8, "submit_n": 4,
                            "debug_checks": True})
    c = DeviceCluster(cfg, seed=4)
    t0 = time.time()
    for _ in range(60):
        c.tick(submit_n=4)
    roles = np.asarray(c.states.role)
    assert ((roles == LEADER).sum(axis=0) == 1).all(), "one leader per group"
    commit0 = np.asarray(c.states.commit).max(axis=0)
    assert (commit0 > 0).all()
    plog.phase("elect+replicate", groups=G, peers=5,
               elapsed_s=round(time.time() - t0, 1),
               committed=int(commit0.astype(np.int64).sum()))

    # Partition: isolate a 2-node minority; the 3-node majority must keep
    # committing (deposed-leader groups re-elect behind the partition).
    c.set_partition([[0, 1, 2], [3, 4]])
    commit1 = commit0
    for k in range(6):
        for _ in range(30):
            c.tick(submit_n=4)
        commit1 = np.asarray(c.states.commit)[:3].max(axis=0)
        frac = float((commit1 > commit0).mean())
        plog.phase("partitioned", ticks=30 * (k + 1),
                   progressed_pct=round(frac * 100, 3))
        if frac == 1.0:
            break
    assert (commit1 > commit0).all(), \
        f"stuck groups: {int((commit1 <= commit0).sum())}"

    c.heal()
    # Same-term split brain is checked EVERY tick by the harness itself
    # (debug_checks=True -> DeviceCluster._debug_check's cross-node
    # election-safety scan) — any violation raises from tick(), so
    # reaching the end of this run IS the safety result.
    for _ in range(60):
        c.tick(submit_n=4)
    for _ in range(15):
        c.tick()
    commit2 = np.asarray(c.states.commit).max(axis=0)
    assert (commit2 > commit1).all()
    platform = jax.devices()[0].platform
    plog.phase("healed", committed=int(commit2.astype(np.int64).sum()),
               split_brain=0)
    plog.save(platform)
    print(f"config-4 OK on {platform}: no same-term split "
          f"brain, all {G} groups progressed; total {time.time() - t0:.0f}s, "
          f"committed={int(commit2.astype(np.int64).sum())}", flush=True)


if __name__ == "__main__":
    main()
