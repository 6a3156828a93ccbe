"""Kernel <-> scalar-oracle parity harness under randomized chaos schedules.

Every tick, each node's (state, inbox, host inbox) is fed both to the
vectorized kernel (``node_step``) and to the loop-based scalar oracle
(``testkit.oracle.oracle_step``); the resulting state, every outbound
message (masked by its validity lane) and the step info must agree
exactly.  The kernel's outputs carry the simulation forward, so each tick
is an independent check and divergence cannot compound silently.

Shared by tests/test_oracle_parity.py (CPU backend) and chip_smoke.py
(the same schedules with the kernel on the chip).
"""

import dataclasses

import jax
import numpy as np

from ..core.step import node_step
from ..core.types import (
    EngineConfig, HostInbox, Messages, crash_restart, init_state,
)
from .oracle import _np, oracle_step

# (validity lane, dependent fields) per RPC kind: fields are only
# meaningful where the lane is set; the kernel leaves arbitrary broadcast
# values elsewhere.
MSG_GROUPS = {
    "ae_valid": ["ae_term", "ae_prev_idx", "ae_prev_term", "ae_commit",
                 "ae_n", "ae_ents", "ae_cents", "ae_tick"],
    "aer_valid": ["aer_term", "aer_success", "aer_match", "aer_tick"],
    "rv_valid": ["rv_term", "rv_last_idx", "rv_last_term", "rv_prevote"],
    "rvr_valid": ["rvr_term", "rvr_granted", "rvr_prevote", "rvr_echo"],
    "is_valid": ["is_term", "is_idx", "is_last_term", "is_conf"],
    "isr_valid": ["isr_term", "isr_success"],
    "tn_valid": ["tn_term"],
}


# Fields of a mode that is on (hibernation; strict ReadIndex) ride their
# kind's lane.
OPTIONAL_FIELDS = {"ae_valid": ["ae_sleep", "ae_seq"],
                   "aer_valid": ["aer_asleep", "aer_seq"]}


def assert_messages_equal(kernel_out: Messages, oracle_out: dict, tag: str):
    k = _np(kernel_out)
    for vfield, deps in MSG_GROUPS.items():
        kv, ov = k[vfield], oracle_out[vfield]
        np.testing.assert_array_equal(
            kv, ov, err_msg=f"{tag}: {vfield} mismatch")
        mask = kv
        deps = deps + [f for f in OPTIONAL_FIELDS.get(vfield, ()) if f in k]
        for f in deps:
            a, b = k[f], oracle_out[f]
            m = mask[..., None] if a.ndim == 3 else mask
            np.testing.assert_array_equal(
                np.where(m, a, 0), np.where(m, b, 0),
                err_msg=f"{tag}: {f} mismatch (masked by {vfield})")


def assert_state_equal(kernel_state, oracle_state: dict, tag: str):
    k = _np(kernel_state)
    for f, ov in oracle_state.items():
        np.testing.assert_array_equal(
            k[f], ov, err_msg=f"{tag}: state.{f} mismatch")


def assert_info_equal(kernel_info, oracle_info: dict, tag: str):
    k = _np(kernel_info)
    for f, ov in oracle_info.items():
        np.testing.assert_array_equal(
            k[f], ov, err_msg=f"{tag}: info.{f} mismatch")


def route_numpy(outboxes, conn):
    """inbox[dst].field[src] = outbox[src].field[dst], masked by conn."""
    fields = [f.name for f in dataclasses.fields(Messages)
              if getattr(outboxes[0], f.name) is not None]
    raw = {f: np.stack([np.asarray(getattr(ob, f)) for ob in outboxes])
           for f in fields}  # [N(src), P(dst), G, ...]
    inboxes = []
    N = len(outboxes)
    for d in range(N):
        kw = {}
        for f in fields:
            arr = raw[f][:, d].copy()  # [N(src), G, ...]
            if f.endswith("_valid"):
                m = conn[:, d]
                arr = arr & m[:, None]
            kw[f] = arr
        inboxes.append(Messages(**{f: np.asarray(v) for f, v in kw.items()}))
    return inboxes


def run_parity(seed: int, n_ticks: int, cfg: EngineConfig,
               drop_p: float = 0.15, part_p: float = 0.1,
               crash_p: float = 0.0, stall_p: float = 0.0,
               conf_p: float = 0.0, xfer_p: float = 0.0,
               n_voters=None, arrival_p: float = 0.0,
               calm=(), wake_p: float = 0.0):
    """``conf_p``/``xfer_p``: per-group per-tick probability of offering a
    random membership-change / leadership-transfer request through the
    host inbox (the §6 plane's chaos input — only leaders take them, and
    the one-in-flight gate drops the rest, all of which is part of the
    checked semantics).  ``n_voters`` bounds the boot voter set.
    ``arrival_p``: per-node per-round probability that the step does NOT
    advance the engine's clock (``HostInbox.clock`` 0: a step the runtime
    starts for arriving work between two timer ticks); each node's first
    step always advances it, as a loop's does.  At 0.0 (the default) no
    draw is made and the schedule is the one it always was.
    ``calm``: ``(start, end)`` tick ranges in which nothing is dropped,
    cut, crashed or stalled and the hosts offer nothing: long enough, a
    cluster with ``cfg.hibernate`` falls asleep in them and the chaos
    after them wakes it.  ``wake_p``: per-group per-tick probability of
    the host's peer-lost signal (``HostInbox.wake``; only with
    ``cfg.hibernate``).  Neither draws anything at its default."""
    N, G = cfg.n_peers, cfg.n_groups
    rng = np.random.default_rng(seed)
    states = [init_state(cfg, i, seed=seed, n_voters=n_voters)
              for i in range(N)]
    outboxes = [Messages.empty(cfg) for _ in range(N)]
    infos = [None] * N
    was_asleep = [np.zeros(G, bool) for _ in range(N)]
    partition_left = 0
    partition = None
    stats = {"partitions": 0, "crashes": 0, "stalls": 0, "arrival_steps": 0,
             "lease_reads": 0, "lease_carried": 0, "asleep_steps": 0,
             "wakes": 0, "stamps_on_arrival": 0, "reads_released": 0}

    for t in range(n_ticks):
        quiet = any(a <= t < b for a, b in calm)
        # --- chaos schedule: random drops plus occasional partitions -----
        if quiet:
            partition_left = 0
        elif partition_left == 0 and rng.random() < part_p:
            stats["partitions"] += 1
            k = rng.integers(1, N)
            side = rng.permutation(N)[:k]
            partition = np.zeros((N, N), bool)
            for a in range(N):
                for b in range(N):
                    partition[a, b] = (a in side) == (b in side)
            partition_left = int(rng.integers(3, 12))
        if partition_left > 0:
            conn = partition.copy()
            partition_left -= 1
        else:
            conn = np.ones((N, N), bool)
        if not quiet:
            conn &= rng.random((N, N)) > drop_p
        np.fill_diagonal(conn, True)

        # Crash-restarts and clock stalls (the device nemesis fault model,
        # host-orchestrated): a crashed node resets volatile state to the
        # durable frontier BEFORE the tick (types.crash_restart — the
        # kernel and oracle then both step the restarted state, so parity
        # covers the post-crash lanes, read FIFO drop included); a stalled
        # node does not step at all and loses inbound + sends nothing,
        # drifting its clock from its peers' (the lease's adversary).
        crashed = rng.random(N) < (0.0 if quiet else crash_p)
        stalled = rng.random(N) < (0.0 if quiet else stall_p)
        stats["crashes"] += int(crashed.sum())
        stats["stalls"] += int(stalled.sum())
        for n in range(N):
            if crashed[n]:
                # Leaf-copy: eager crash_restart aliases jnp.zeros constant
                # buffers across fields, and the donating node_step rejects
                # a buffer donated twice (inside the fused scan the vmap
                # body never materializes the aliases, so only this eager
                # harness needs the copy).
                states[n] = jax.tree.map(lambda a: a.copy(),
                                         crash_restart(cfg, states[n]))
            if crashed[n] or stalled[n]:
                conn[:, n] = False
                conn[n, n] = True

        inboxes = route_numpy(outboxes, conn)
        new_outboxes = []
        for n in range(N):
            if stalled[n]:
                new_outboxes.append(Messages.empty(cfg))
                continue
            sub = rng.integers(0, cfg.max_submit + 1, size=G).astype(np.int32)
            # Linearizable read offers ride the same chaos schedule (the
            # read plane is part of the checked semantics), plus an
            # occasional host read-veto (process-pause detection).
            reads = rng.integers(0, 4, size=G).astype(np.int32)
            veto = bool(rng.random() < 0.05)
            # Membership chaos (conf_p/xfer_p): random target configs and
            # transfer targets through the host lanes.
            full = (1 << N) - 1
            cv = np.where(rng.random(G) < conf_p,
                          rng.integers(1, full + 1, size=G),
                          0).astype(np.int32)
            cl = (np.where(rng.random(G) < 0.5,
                           rng.integers(0, full + 1, size=G), 0)
                  .astype(np.int32) & ~cv).astype(np.int32) \
                if conf_p else np.zeros(G, np.int32)
            xt = np.where(rng.random(G) < xfer_p,
                          rng.integers(0, N, size=G),
                          -1).astype(np.int32)
            host = HostInbox.empty(cfg)
            if arrival_p and rng.random() < arrival_p \
                    and int(states[n].now) > 0:
                host = host.replace(clock=np.asarray(0, np.int32))
                stats["arrival_steps"] += 1
            if (conf_p or xfer_p) and not quiet:
                host = host.replace(conf_voters=cv, conf_learners=cl,
                                    xfer_target=xt)
            if wake_p:
                host = host.replace(wake=rng.random(G) < wake_p)
            if quiet:
                pass
            elif infos[n] is not None:
                prev = infos[n]
                compact = np.where(
                    rng.random(G) < 0.3,
                    np.maximum(np.asarray(states[n].commit)
                               - cfg.log_slots // 4, 0),
                    0).astype(np.int32)
                host = host.replace(
                    submit_n=sub,
                    read_n=reads,
                    read_veto=np.asarray(veto),
                    snap_done=np.asarray(prev.snap_req),
                    snap_idx=np.asarray(prev.snap_req_idx),
                    snap_term=np.asarray(prev.snap_req_term),
                    snap_conf=np.asarray(prev.snap_req_conf),
                    compact_to=compact)
            else:
                host = host.replace(submit_n=sub, read_n=reads,
                                    read_veto=np.asarray(veto))

            # Oracle FIRST: node_step donates the state buffers.
            o_state, o_out, o_info = oracle_step(cfg, states[n], inboxes[n],
                                                 host)
            k_state, k_out, k_info = node_step(cfg, states[n], inboxes[n],
                                               host)
            tag = f"seed={seed} tick={t} node={n}"
            assert_state_equal(k_state, o_state, tag)
            assert_messages_equal(k_out, o_out, tag)
            assert_info_equal(k_info, o_info, tag)
            states[n] = k_state
            new_outboxes.append(k_out)
            infos[n] = k_info
            stats["lease_reads"] += int(np.asarray(k_info.read_lease).sum())
            stats["lease_carried"] += int(
                np.asarray(k_info.read_carried).sum())
            if int(host.clock) == 0:
                stats["stamps_on_arrival"] += int(
                    (np.asarray(k_info.read_acc) > 0).sum())
            stats["reads_released"] += int(np.asarray(k_info.read_rel).sum())
            if cfg.hibernate:
                now_asleep = np.asarray(k_info.asleep)
                stats["asleep_steps"] += int(now_asleep.sum())
                stats["wakes"] += int((was_asleep[n] & ~now_asleep).sum())
                was_asleep[n] = now_asleep
        outboxes = new_outboxes

    # The schedule must have actually elected leaders / committed entries.
    total_commit = sum(int(np.asarray(s.commit).sum()) for s in states)
    assert total_commit > 0, "chaos schedule never committed anything"
    return states, stats
