#!/usr/bin/env python
"""Headline benchmark: AppendEntries commits/sec across 100k Raft groups.

Runs the full consensus loop — leader election, AppendEntries fan-out over a
3-node cluster, quorum-median commit, slack compaction — entirely on device,
with every node's engine vectorized over all groups (BASELINE.json north
star: 100k groups, >1M commits/sec on one TPU v5e-1).

Smoke-first harness.  Structure:

* every scale runs in its OWN subprocess under a hard timeout, so a fault at
  one scale costs that scale, not the whole run.  A chip belongs to one
  process at a time: the parent never touches JAX, so each child in turn
  gets the device;
* scales escalate 1k (smoke) → 4k → 16k → 32k → 65k → 100k and a
  fully-formed headline JSON line is printed and flushed after EVERY
  successful scale — whatever kills the parent later, a parseable number is
  already on stdout;
* children enable ``faulthandler`` with a watchdog dump so a hang leaves a
  traceback on stderr instead of silence;
* every child runs on the backend JAX finds and every line names it.  There
  is no fallback: when the smoke scale fails nothing is printed and the exit
  code is non-zero.  A CPU number is what a caller gets by setting
  ``JAX_PLATFORMS=cpu``, and its line says ``cpu``.

The final stdout line is the headline result at the largest surviving scale:
``{"metric", "value", "unit", "vs_baseline"}``.
"""

import json
import math
import os
import subprocess
import sys
import time

ARTIFACT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "artifacts")

SCALES = (1_024, 4_096, 16_384, 32_768, 65_536, 100_000)
BASELINE_CPS = 1_000_000  # BASELINE.md: >1M commits/sec @100k groups, v5e-1
FALSY = ("", "0", "false", "no", "off")
# The tuned pipeline budget (32k-group sweep; see git log).
TUNED_ENV = {"BENCH_MAX_SUBMIT": "32", "BENCH_BATCH": "32",
             "BENCH_LOG_SLOTS": "256"}
TUNED_TAG = " [tuned budget S=32/B=32/L=256]"


def env_flag(name: str) -> bool:
    return os.environ.get(name, "").strip().lower() not in FALSY


def _platform() -> str:
    """The backend an in-process stage ran on (the stages that replace the
    ladder run in this process, on whatever JAX found)."""
    import jax
    return jax.devices()[0].platform


def child_run(n_groups: int, measure_ticks: int, warmup_ticks: int,
              profile_dir: str = "") -> dict:
    """One scale, in-process.  Prints nothing; returns the result dict."""
    import faulthandler
    faulthandler.enable()
    # If anything (backend init, compile, device exec) wedges, dump every
    # thread's stack to stderr before the parent's timeout fires.
    timeout_s = float(os.environ.get("BENCH_CHILD_WATCHDOG", "240"))
    faulthandler.dump_traceback_later(timeout_s, exit=False)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from functools import partial as _partial
    from rafting_tpu import DeviceCluster, EngineConfig
    from rafting_tpu.core.sim import run_cluster_ticks, run_cluster_ticks_blocked

    t_init = time.perf_counter()
    dev = jax.devices()[0]
    init_s = time.perf_counter() - t_init

    n_peers = 3
    # BENCH_NEMESIS=1: measure commits/sec UNDER the standard three-regime
    # fault schedule (testkit/nemesis.chaos_mix, seed 0: partitions ->
    # crash/stall storm -> lossy+duplicating links) instead of a healthy
    # network — the honest number behind the BASELINE config-4 "under
    # partition" target.  Warm-up stays healthy (elect + reach steady
    # state); only the measured window runs the schedule, entirely inside
    # fused scans.
    nemesis_on = env_flag("BENCH_NEMESIS")
    # BENCH_READS=1: measure the LINEARIZABLE READ PLANE instead of pure
    # append throughput — a warm-compiled mixed 90/10 read/write load
    # (per tick per group: one ReadIndex batch of 9*max_submit queries +
    # max_submit log writes), entirely inside the fused scan
    # (core/sim.py run_cluster_ticks_reads).  Reads never touch the log,
    # so the headline is reads/sec on top of a still-live write stream.
    reads_on = env_flag("BENCH_READS")
    if reads_on and nemesis_on:
        # The reads scan measures the HEALTHY path; silently honoring both
        # flags would label a fault-free measurement as a chaos number.
        raise SystemExit("BENCH_READS and BENCH_NEMESIS are mutually "
                         "exclusive: the read stage measures the healthy "
                         "path (a faults-on reads scan does not exist yet)")
    # Pipeline budget knobs.  Defaults are L=64/B=8; TUNED_ENV is the point
    # from the 32k-group CPU sweep (S=32/B=32/L=256 — the reference itself
    # ships up to 50 entries per AppendEntries, Leadership.java
    # REPLICATE_LIMIT).
    # BENCH_TRACE=1: compile the flight recorder into the scan
    # (cfg.trace_depth event-ring slots per group, BENCH_TRACE_DEPTH
    # overrides the default 16) — the recorder-overhead A/B: same load,
    # same schedule, commits/sec with the trace lanes vs without.
    trace_on = env_flag("BENCH_TRACE")
    cfg = EngineConfig(
        n_groups=n_groups, n_peers=n_peers,
        log_slots=int(os.environ.get("BENCH_LOG_SLOTS", "64")),
        batch=int(os.environ.get("BENCH_BATCH", "8")),
        max_submit=int(os.environ.get("BENCH_MAX_SUBMIT", "8")),
        election_ticks=10, heartbeat_ticks=3, rpc_timeout_ticks=8,
        pre_vote=True,
        # BENCH_USE_PALLAS=1: quorum commit through the Pallas kernel
        # (ops/quorum.py) instead of inline jnp — the A/B the TPU decision
        # needs is then one env var per run.
        use_pallas=env_flag("BENCH_USE_PALLAS"),
        # Default matches the engine's floor (>= 12 slots now that a tick
        # can emit up to 11 events — membership added three kinds).
        trace_depth=(int(os.environ.get("BENCH_TRACE_DEPTH", "16"))
                     if trace_on else 0),
    )
    # Group-axis tiling (groups are independent; run_cluster_ticks_blocked).
    # An UNBLOCKED 100k program runs on a v5e chip (chip_smoke.py drives
    # it); blocked against unblocked has not been measured there.
    max_block = int(os.environ.get("BENCH_GROUP_BLOCK", "32768"))
    if n_groups > max_block:
        n_blocks = -(-n_groups // max_block)
        block = -(-n_groups // n_blocks)  # equal blocks, minimal padding
        run_ticks = _partial(run_cluster_ticks_blocked, group_block=block)
    else:
        block = 0
        run_ticks = run_cluster_ticks
    c = DeviceCluster(cfg, seed=0)
    submit = jnp.full((n_peers, n_groups), cfg.max_submit, jnp.int32)

    # Ticks per device execution: the scan length is static, so warm-up
    # and measure run the SAME compiled program in chunks of their common
    # divisor (one compile, about a minute at 100k groups, instead of
    # two).  Nothing else limits an execution: on a v5e chip one 512-tick
    # execution at 100k groups ran for 242 s and completed, and
    # jax.block_until_ready fenced it (chip_smoke.py, PR 21).
    chunk = math.gcd(warmup_ticks, measure_ticks)

    def run_chunks(n_ticks, states, inflight, info):
        done = 0
        while done < n_ticks:
            step = min(chunk, n_ticks - done)
            states, inflight, info = run_ticks(
                cfg, step, states, inflight, info, c.conn, submit)
            done += step
        return states, inflight, info

    if reads_on:
        from rafting_tpu.core.sim import run_cluster_ticks_reads
        # 90/10 offered mix: 9*S reads per group-tick ride one ReadIndex
        # batch; S writes flow beside them.
        read_load = jnp.full((n_peers, n_groups), 9 * cfg.max_submit,
                             jnp.int32)
        read_totals = {"served": 0, "lease": 0, "appended": 0}

        def run_chunks_reads(n_ticks, states, inflight, info):
            done = 0
            served = lease = appended = 0
            while done < n_ticks:
                step = min(chunk, n_ticks - done)
                states, inflight, info, sv, lh, ap = run_cluster_ticks_reads(
                    cfg, step, states, inflight, info, c.conn, submit,
                    read_load)
                # Lazy device scalars: summed on device, pulled once after
                # the measured window (the commit read is the fence).
                served, lease, appended = served + sv, lease + lh, appended + ap
                done += step
            return states, inflight, info, served, lease, appended

    if nemesis_on:
        from rafting_tpu.core.sim import run_cluster_ticks_nemesis
        from rafting_tpu.testkit import nemesis as _nem
        sched = _nem.chaos_mix(n_peers, measure_ticks, seed=0)

        def run_chunks_faulted(states, inflight, info):
            done = 0
            while done < measure_ticks:
                step = min(chunk, measure_ticks - done)
                states, inflight, info = run_cluster_ticks_nemesis(
                    cfg, states, inflight, info,
                    jax.tree.map(lambda a: a[done:done + step], sched),
                    submit)
                done += step
            return states, inflight, info

    def commit_sum(states):
        # The committed total is the measurement; reading it to the host
        # also fences the executions behind it.
        return int(np.asarray(states.commit).max(axis=0)
                   .astype(np.int64).sum())

    # Warm-up: compile + elect leaders + reach steady-state replication.
    t0 = time.perf_counter()
    states, inflight, info = run_chunks(warmup_ticks, c.states, c.inflight,
                                        c.last_info)
    if nemesis_on:
        # Compile the nemesis scan during warm-up, NOT inside measure():
        # one execution per distinct step size of the measured chunk
        # sequence, driven by an all-healthy schedule (the compiled
        # program is identical — the fault schedule is data), so the
        # faults-on headline times pure execution like the healthy one.
        for step in sorted({min(chunk, measure_ticks - d)
                            for d in range(0, measure_ticks, chunk)}):
            states, inflight, info = run_cluster_ticks_nemesis(
                cfg, states, inflight, info,
                _nem.healthy(n_peers, step), submit)
    if reads_on:
        # Same warm-compile discipline for the reads scan (the read load
        # is data; only the per-step-size programs need building).
        for step in sorted({min(chunk, measure_ticks - d)
                            for d in range(0, measure_ticks, chunk)}):
            states, inflight, info, *_ = run_cluster_ticks_reads(
                cfg, step, states, inflight, info, c.conn, submit,
                read_load)
    start_commit = commit_sum(states)
    warm_s = time.perf_counter() - t0

    def measure():
        nonlocal states, inflight, info
        t0 = time.perf_counter()
        if reads_on:
            states, inflight, info, sv, lh, ap = run_chunks_reads(
                measure_ticks, states, inflight, info)
        elif nemesis_on:
            states, inflight, info = run_chunks_faulted(states, inflight,
                                                        info)
        else:
            states, inflight, info = run_chunks(measure_ticks, states,
                                                inflight, info)
        # The commit read fences the elapsed time; its cost ([N, G] i32
        # pull) is part of the measurement and negligible at every scale.
        commit_sum(states)
        if reads_on:
            read_totals["served"] = int(np.asarray(sv))
            read_totals["lease"] = int(np.asarray(lh))
            read_totals["appended"] = int(np.asarray(ap))
        return time.perf_counter() - t0

    from rafting_tpu.utils.profiling import device_trace
    with device_trace(profile_dir):   # no-op when unset
        elapsed = measure()

    end_commit = int(np.asarray(states.commit).max(axis=0).astype(np.int64).sum())
    commits = end_commit - start_commit

    # Sanity: nonzero commits always; exactly one leader per group only on
    # the healthy path (mid-chaos a deposed minority leader may linger at
    # a lower term — legal Raft, so the faulted run asserts AT LEAST one).
    roles = np.asarray(states.role)
    n_lead = (roles == 3).sum(axis=0)
    if nemesis_on:
        assert (n_lead >= 1).any(), "no leaders anywhere after chaos"
    else:
        assert (n_lead == 1).all(), f"leaders per group: {np.unique(n_lead)}"
    assert commits > 0

    faulthandler.cancel_dump_traceback_later()
    res = {
        "scale": n_groups,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "cps": commits / elapsed,
        "commits": commits,
        "ticks": measure_ticks,
        "elapsed_s": round(elapsed, 4),
        "warmup_s": round(warm_s, 2),
        "init_s": round(init_s, 2),
        "nemesis": nemesis_on,
        "trace_depth": cfg.trace_depth,
    }
    if trace_on:
        # The recorder must have actually recorded (elections at minimum).
        ev = int(np.asarray(states.trace.n).astype(np.int64).sum())
        assert ev > 0, "BENCH_TRACE run recorded zero events"
        res["trace_events"] = ev
    if reads_on:
        assert read_totals["served"] > 0, "read stage served nothing"
        res.update(
            reads=read_totals["served"],
            rps=read_totals["served"] / elapsed,
            lease_hits=read_totals["lease"],
            appended=read_totals["appended"],
            read_mix="90/10",
        )
    return res


def member_child(n_groups: int) -> dict:
    """BENCH_MEMBER stage, in-process: (1) the masked-quorum commit
    kernel A/B'd against the legacy fixed-majority baseline at P=3 —
    asserting the membership-aware kernel stays within noise (>= 0.95x);
    (2) reconfig walk-through throughput: every group walks the full
    3 -> 3-disjoint rebalance (add learners {3,4,5} -> catch up ->
    joint switch to {3,4,5} -> auto-leave) at P=6, reported as groups
    reconfigured per second with zero committed-entry loss asserted."""
    import faulthandler
    faulthandler.enable()
    timeout_s = float(os.environ.get("BENCH_CHILD_WATCHDOG", "240"))
    faulthandler.dump_traceback_later(timeout_s, exit=False)

    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from rafting_tpu import DeviceCluster, EngineConfig
    from rafting_tpu.core.cluster import cluster_snapshot
    from rafting_tpu.core.sim import run_cluster_ticks
    from rafting_tpu.core.types import conf_new_of, conf_voters_of

    dev = jax.devices()[0]
    # ONE scan length everywhere: run_cluster_ticks compiles per static
    # tick count, so warm-up must execute the exact program the measured
    # window re-runs (a 32-tick warmup before a 64-tick measure times the
    # 64-tick compile INSIDE the measurement).
    CHUNK = 16

    def scan_chunks(cfg, c, n_ticks, submit):
        for _ in range(n_ticks // CHUNK):
            c.states, c.inflight, c.last_info = run_cluster_ticks(
                cfg, CHUNK, c.states, c.inflight, c.last_info, c.conn,
                submit)

    def commits_per_sec(cfg, reps=2) -> float:
        c = DeviceCluster(cfg, seed=0)
        submit = jnp.full((cfg.n_peers, cfg.n_groups), cfg.max_submit,
                          jnp.int32)
        scan_chunks(cfg, c, 32, submit)   # compile + elect + steady state
        best = 0.0
        for _ in range(reps):
            start = int(np.asarray(c.states.commit).max(axis=0)
                        .astype(np.int64).sum())
            t0 = time.perf_counter()
            scan_chunks(cfg, c, 64, submit)
            end = int(np.asarray(c.states.commit).max(axis=0)
                      .astype(np.int64).sum())
            best = max(best, (end - start) / (time.perf_counter() - t0))
        return best

    # -- (1) masked vs fixed-majority commit kernel, P=3 ------------------
    base_cfg = EngineConfig(
        n_groups=n_groups, n_peers=3,
        log_slots=int(os.environ.get("BENCH_LOG_SLOTS", "64")),
        batch=int(os.environ.get("BENCH_BATCH", "8")),
        max_submit=int(os.environ.get("BENCH_MAX_SUBMIT", "8")),
        election_ticks=10, heartbeat_ticks=3, rpc_timeout_ticks=8,
        pre_vote=True)
    cps_fixed = commits_per_sec(
        dataclasses.replace(base_cfg, quorum_fixed=True))
    cps_masked = commits_per_sec(base_cfg)
    ratio = cps_masked / max(cps_fixed, 1e-9)
    assert ratio >= 0.95, \
        (f"masked-quorum kernel regressed commit throughput beyond noise "
         f"at P=3: {cps_masked:,.0f} vs fixed {cps_fixed:,.0f} "
         f"({ratio:.3f}x)")

    # -- (2) reconfig walk-through throughput, P=6 3->3-disjoint ----------
    cfg6 = dataclasses.replace(base_cfg, n_peers=6)
    c = DeviceCluster(cfg6, seed=0, n_voters=3)
    submit = jnp.full((6, n_groups), cfg6.max_submit, jnp.int32)
    scan_chunks(cfg6, c, 64, submit)   # compile + elect + steady state
    pre_commit = cluster_snapshot(c.states)["commit"].max(axis=0).copy()
    assert (pre_commit > 0).all(), "warm-up never committed"
    target = 0b111000
    new_nodes = (3, 4, 5)

    def walk_done() -> bool:
        w = np.asarray(c.last_info.conf_word)[new_nodes, :]
        ok = ((conf_voters_of(w) == target) & (conf_new_of(w) == 0)).all()
        roles = np.asarray(c.states.role)[new_nodes, :]
        return bool(ok and ((roles == 3).sum(axis=0) == 1).all())

    # The walk runs under LIGHT live traffic (1 command/group/tick): the
    # self-driving scan policy compacts every tick, and at full offered
    # load the floor outruns any learner snapshot install (the documented
    # pursuit-never-converges regime, core/cluster.py auto_host_inbox) —
    # real deployments gate compaction on checkpoint cadences instead.
    submit_walk = jnp.ones((6, n_groups), jnp.int32)
    scan_chunks(cfg6, c, CHUNK, submit_walk)   # compile the walk program
    t0 = time.perf_counter()
    c.request_membership(voters=0b000111, learners=target)   # learners in
    scan_chunks(cfg6, c, 48, submit_walk)
    c.request_membership(voters=target, learners=0)          # joint switch
    chunks = 0
    while not walk_done():
        scan_chunks(cfg6, c, CHUNK, submit_walk)
        chunks += 1
        assert chunks < 64, "rebalance walk did not converge"
    elapsed = time.perf_counter() - t0
    # Zero committed-entry loss: the new set's commit frontier covers the
    # pre-walk frontier and keeps advancing under the new voters.
    snap = cluster_snapshot(c.states)
    post = snap["commit"][new_nodes, :].max(axis=0)
    assert (post >= pre_commit).all(), "committed entries lost in the walk"
    scan_chunks(cfg6, c, CHUNK, submit)
    post2 = cluster_snapshot(c.states)["commit"][new_nodes, :].max(axis=0)
    assert (post2 > post).all(), "commits stalled after the walk"

    faulthandler.cancel_dump_traceback_later()
    return {
        "scale": n_groups,
        "platform": dev.platform,
        "member_stage": True,
        "walk_groups_per_sec": n_groups / elapsed,
        "walk_elapsed_s": round(elapsed, 3),
        "cps_masked": cps_masked,
        "cps_fixed": cps_fixed,
        "masked_vs_fixed": round(ratio, 4),
    }


def run_member_ladder(profile_unused: str = "") -> None:
    """BENCH_MEMBER=1: the membership stage replaces the normal ladder —
    reconfig walk-through throughput at 1k/32k/100k plus the
    masked-vs-fixed commit A/B at P=3, one subprocess per scale."""
    timeout_s = float(os.environ.get("BENCH_MEMBER_TIMEOUT", "420"))
    any_ok = False
    for g in (1_024, 32_768, 100_000):
        cmd = [sys.executable, os.path.abspath(__file__),
               "--member-child", str(g)]
        env = dict(os.environ)
        try:
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=timeout_s, env=env)
        except subprocess.TimeoutExpired:
            sys.stderr.write(f"[bench] member scale {g}: TIMEOUT\n")
            continue
        if r.returncode != 0:
            tail = "\n".join(r.stderr.strip().splitlines()[-10:])
            sys.stderr.write(f"[bench] member scale {g}: rc="
                             f"{r.returncode}\n{tail}\n")
            continue
        try:
            res = json.loads(r.stdout.strip().splitlines()[-1])
        except (json.JSONDecodeError, IndexError):
            continue
        save_artifact(res, child_env=env, note="BENCH_MEMBER stage")
        any_ok = True
        emit({
            "metric": f"membership rebalance walk-throughs/sec "
                      f"@{g // 1000}k Raft groups (3->3-disjoint walk: "
                      f"add-learner -> catch-up -> joint switch -> "
                      f"auto-leave, P=6, {res['platform']}) "
                      f"[masked-quorum commit kernel "
                      f"{res['masked_vs_fixed']}x of fixed-majority @P=3]",
            "value": round(res["walk_groups_per_sec"]),
            "unit": "groups/sec",
            "vs_baseline": res["masked_vs_fixed"],
        })
    if not any_ok:
        emit({"metric": "membership rebalance stage (no scale survived)",
              "value": 0, "unit": "groups/sec", "vs_baseline": 0.0})
        sys.exit(1)


def run_openloop_stage() -> None:
    """BENCH_OPENLOOP=1: the overload stage replaces the ladder — an
    OPEN-LOOP rate sweep (testkit/openloop.py) against a small durable
    3-node cluster, with the admission-control plane ON and then
    force-disabled (RAFT_ADMISSION=0), emitting offered-vs-goodput +
    shed-rate + admitted-percentile curves per sweep point.  The
    headline is the NO-COLLAPSE property: past the measured capacity,
    goodput with admission on plateaus (>= 85% of its peak) and the
    admitted p999 stays bounded, while the admission-off control run is
    free to collapse (unbounded standing queues -> every completion
    lands past its deadline).  Closed-loop ladders cannot see any of
    this — the driver's politeness hides the overload (ROADMAP item 5).

    Scale knobs: BENCH_OPENLOOP_GROUPS (default 8), BENCH_OPENLOOP_DUR
    (seconds per sweep point, default 2), BENCH_OPENLOOP_MULTS (offered
    load as x capacity, default "0.5,1.0,2.0,3.0")."""
    import shutil
    import tempfile

    from rafting_tpu.core.types import EngineConfig
    from rafting_tpu.testkit.harness import LocalCluster
    from rafting_tpu.testkit.openloop import (
        OpenLoopSpec, no_collapse_check, run_open_loop)

    n_groups = int(os.environ.get("BENCH_OPENLOOP_GROUPS", "8"))
    dur = float(os.environ.get("BENCH_OPENLOOP_DUR", "2"))
    mults = [float(x) for x in os.environ.get(
        "BENCH_OPENLOOP_MULTS", "0.5,1.0,2.0,3.0").split(",")]
    deadline_s = float(os.environ.get("BENCH_OPENLOOP_DEADLINE_S", "1.0"))
    cfg = EngineConfig(
        n_groups=n_groups, n_peers=3, log_slots=64, batch=8, max_submit=8,
        election_ticks=10, heartbeat_ticks=3, rpc_timeout_ticks=8)

    def build(root: str) -> LocalCluster:
        c = LocalCluster(cfg, root, seed=7)
        for g in range(n_groups):
            c.wait_leader(g)
        return c

    def submit_fn(c: LocalCluster):
        leaders = {g: c.leader_of(g) for g in range(n_groups)}

        def submit(grp: int, tenant: str, seq: int):
            g = grp % n_groups
            ld = leaders.get(g)
            if ld is None or not c.nodes[ld].is_leader(g):
                leaders[g] = ld = c.leader_of(g)
            if ld is None:
                return None
            return c.nodes[ld].submit(g, b"ol-%d" % seq, tenant=tenant)
        return submit

    def probe_capacity(c: LocalCluster) -> float:
        """Closed-loop throughput at this scale: burst-submit to every
        leader, tick until drained, repeat — the politeness the open
        loop then discards."""
        t0 = time.monotonic()
        done = 0
        for _ in range(16):
            futs = []
            for g in range(n_groups):
                ld = c.leader_of(g)
                if ld is not None:
                    futs.append(c.nodes[ld].submit_batch(
                        g, [b"cap"] * 8))
            for _ in range(200):
                if all(f.done() for f in futs):
                    break
                c.tick(1)
            done += sum(8 for f in futs
                        if f.done() and f.exception() is None)
        return done / max(time.monotonic() - t0, 1e-9)

    def sweep(c: LocalCluster, cap: float, label: str) -> list:
        out = []
        for m in mults:
            spec = OpenLoopSpec(
                rate=max(1.0, cap * m), duration_s=dur, n_tenants=4,
                n_groups=n_groups, deadline_s=deadline_s,
                seed=int(m * 100))
            r = run_open_loop(spec, submit_fn(c),
                              step=lambda: c.tick(1), drain_s=2.0)
            d = r.to_dict()
            d["offered_x_capacity"] = m
            adms = [n.admission for n in c.nodes.values()]
            d["admission"] = {
                "enabled": adms[0].enabled,
                "level": round(max(a.level for a in adms), 4),
                "shed_total": sum(a.shed for a in adms)}
            out.append((m, r, d))
            emit({"metric": f"open-loop goodput @{n_groups} groups, "
                            f"admission={label}, offered={m:g}x capacity",
                  "value": round(r.goodput, 1), "unit": "ops/sec",
                  "vs_baseline": None, **d})
        return out

    results = {}
    for label, env_admission in (("on", None), ("off", "0")):
        root = tempfile.mkdtemp(prefix=f"openloop-{label}-")
        old = os.environ.get("RAFT_ADMISSION")
        try:
            if env_admission is not None:
                os.environ["RAFT_ADMISSION"] = env_admission
            else:
                os.environ.pop("RAFT_ADMISSION", None)
            c = build(root)
            try:
                cap = probe_capacity(c)
                emit({"metric": f"closed-loop capacity probe "
                                f"@{n_groups} groups (admission={label})",
                      "value": round(cap, 1), "unit": "ops/sec",
                      "vs_baseline": None})
                results[label] = (cap, sweep(c, cap, label))
            finally:
                c.close()
        finally:
            if old is None:
                os.environ.pop("RAFT_ADMISSION", None)
            else:
                os.environ["RAFT_ADMISSION"] = old
            shutil.rmtree(root, ignore_errors=True)

    on = [r for _m, r, _d in results["on"][1]]
    ok, why = no_collapse_check(on, slo_s=deadline_s)
    emit({"metric": "open-loop no-collapse verdict (admission on)",
          "value": 1 if ok else 0, "unit": "pass", "vs_baseline": None,
          "why": why,
          "capacity_ops_per_sec": round(results["on"][0], 1)})
    save_artifact(
        {"platform": _platform(), "scale": n_groups,
         "capacity": {k: round(v[0], 1) for k, v in results.items()},
         "sweep": {k: [d for _m, _r, d in v[1]]
                   for k, v in results.items()},
         "no_collapse": {"ok": ok, "why": why}},
        note="BENCH_OPENLOOP stage: open-loop overload sweep")
    assert ok, f"no-collapse property failed: {why}"


def run_txn_stage() -> None:
    """BENCH_TXN=1: the cross-group transaction stage replaces the
    ladder — closed-loop 2-key Zipf bank transfers through the 2PC
    plane (runtime/txn.py) on a durable 3-node cluster, A/B'd against
    the SAME key traffic issued as two independent single-group writes
    (the no-atomicity upper bound: what the cluster does when nobody
    asks for cross-group all-or-nothing).  Emits txn/sec + abort rate
    per scale point plus the atomicity-tax ratio vs that bound; the
    tax is real and bounded — one transfer is five sequential quorum
    commits (begin, 2x prepare, decide, finalize) against the bound's
    two independent ones, so the honest ceiling is ~0.4x before lock
    conflicts subtract their share.

    Scale knobs: BENCH_TXN_GROUPS (comma ladder of total group counts,
    coordinator + N-1 participants, default "3,5"), BENCH_TXN_CLIENTS
    (default 8), BENCH_TXN_DUR (seconds per phase, default 4),
    BENCH_TXN_ZIPF (account skew, default 1.0)."""
    import itertools
    import shutil
    import tempfile
    import threading

    from rafting_tpu.api.stub import RaftStub
    from rafting_tpu.core.types import EngineConfig
    from rafting_tpu.machine.kv_machine import KVMachineProvider
    from rafting_tpu.testkit.chaos import StubHost
    from rafting_tpu.testkit.harness import LocalCluster
    from rafting_tpu.testkit.openloop import OpenLoopSpec, gen_transfers

    ladder = [int(x) for x in os.environ.get(
        "BENCH_TXN_GROUPS", "3,5").split(",")]
    clients = int(os.environ.get("BENCH_TXN_CLIENTS", "8"))
    dur = float(os.environ.get("BENCH_TXN_DUR", "4"))
    zipf = float(os.environ.get("BENCH_TXN_ZIPF", "1.0"))
    n_accounts = 16

    for n_groups in ladder:
        participants = list(range(1, n_groups))
        cfg = EngineConfig(n_groups=n_groups, n_peers=3, log_slots=64,
                           batch=8, max_submit=8, election_ticks=10,
                           heartbeat_ticks=3, rpc_timeout_ticks=8,
                           read_lease=True)
        root = tempfile.mkdtemp(prefix=f"txnbench-{n_groups}-")
        cluster = LocalCluster(
            cfg, root, seed=5,
            provider_factory=lambda i: KVMachineProvider(
                os.path.join(root, f"node{i}", "kv")))
        stop = threading.Event()

        def tick_loop():
            while not stop.is_set():
                for node in list(cluster.nodes.values()):
                    node.tick()
                time.sleep(0.002)

        try:
            for g in range(n_groups):
                cluster.wait_leader(g)
            threading.Thread(target=tick_loop, daemon=True).start()
            hosts = [StubHost(cluster, c % cfg.n_peers)
                     for c in range(clients)]
            seeder = StubHost(cluster, 0)
            for g in participants:
                s = RaftStub(seeder, str(g), g, forward=True,
                             forward_budget=10.0)
                for a in range(n_accounts):
                    s.execute(json.dumps({"op": "set", "k": f"acct{a}",
                                          "v": 10_000}), timeout=10)
            # One seeded plan feeds BOTH phases: same keys, same skew,
            # same amounts — the A/B differs only in atomicity.
            spec = OpenLoopSpec(rate=500.0, duration_s=dur * 8,
                                n_tenants=4, n_groups=len(participants),
                                seed=5)
            plan = gen_transfers(spec, n_accounts=n_accounts,
                                 account_zipf=zipf)

            def phase(body) -> tuple:
                idx = itertools.count()
                outs = [{"ok": 0, "aborted": 0, "failed": 0}
                        for _ in range(clients)]

                def worker(c):
                    host = hosts[c]
                    parts = {g: RaftStub(host, str(g), g, forward=True,
                                         forward_budget=8.0)
                             for g in participants}
                    coord = RaftStub(host, "0", 0, forward=True,
                                     forward_budget=8.0)
                    end = time.monotonic() + dur
                    while time.monotonic() < end:
                        step = plan[next(idx) % len(plan)]
                        body(coord, parts, step, outs[c])
                threads = [threading.Thread(target=worker, args=(c,))
                           for c in range(clients)]
                t0 = time.monotonic()
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                el = time.monotonic() - t0
                tot = {k: sum(o[k] for o in outs) for k in outs[0]}
                return tot, el

            def txn_body(coord, parts, step, out):
                _t, _tn, src, dst, ka, kb, amt = step
                sg, dg = participants[src], participants[dst]
                try:
                    r = (coord.txn(deadline_s=2.0)
                         .transfer(parts[sg], ka, parts[dg], kb, amt)
                         .execute(timeout=6.0))
                    out["ok" if r.committed else "aborted"] += 1
                except Exception:
                    out["failed"] += 1

            def write_body(coord, parts, step, out):
                _t, _tn, src, dst, ka, kb, amt = step
                sg, dg = participants[src], participants[dst]
                try:
                    parts[sg].execute(json.dumps(
                        {"op": "incr", "k": ka, "v": -amt}), timeout=6.0)
                    parts[dg].execute(json.dumps(
                        {"op": "incr", "k": kb, "v": amt}), timeout=6.0)
                    out["ok"] += 1
                except Exception:
                    out["failed"] += 1

            txn_tot, txn_el = phase(txn_body)
            wr_tot, wr_el = phase(write_body)
        finally:
            stop.set()
            time.sleep(0.05)
            cluster.close()
            shutil.rmtree(root, ignore_errors=True)

        attempted = txn_tot["ok"] + txn_tot["aborted"] + txn_tot["failed"]
        txn_rate = txn_tot["ok"] / max(txn_el, 1e-9)
        abort_rate = txn_tot["aborted"] / max(attempted, 1)
        wr_rate = wr_tot["ok"] / max(wr_el, 1e-9)
        ratio = txn_rate / max(wr_rate, 1e-9)
        res = {
            "platform": _platform(), "scale": n_groups,
            "participants": len(participants), "clients": clients,
            "duration_s": dur, "account_zipf": zipf,
            "txn": {**txn_tot, "attempted": attempted,
                    "elapsed_s": round(txn_el, 3)},
            "independent_writes": {**wr_tot,
                                   "elapsed_s": round(wr_el, 3)},
            "txn_per_sec": round(txn_rate, 1),
            "abort_rate": round(abort_rate, 4),
            "independent_pairs_per_sec": round(wr_rate, 1),
            "atomicity_tax": round(ratio, 3),
        }
        save_artifact(res, note="BENCH_TXN stage: cross-group 2PC "
                                "transfers vs independent-writes bound")
        emit({"metric": f"cross-group 2PC transfers/sec @{n_groups} "
                        f"groups (1 coordinator + "
                        f"{len(participants)} participants, 2-key "
                        f"Zipf({zipf:g}) transfers, {clients} closed-"
                        f"loop clients, durable 3-node cluster) "
                        f"[abort rate {abort_rate:.1%}; independent-"
                        f"writes bound {wr_rate:.0f} pairs/sec]",
              "value": round(txn_rate, 1), "unit": "txn/sec",
              "vs_baseline": round(ratio, 3)})
        assert txn_tot["ok"] > 0, "txn stage committed nothing"


def run_latency_ab() -> None:
    """BENCH_LAT=1: the latency-plane overhead A/B replaces the ladder —
    durable commits/sec through bench_runtime.run() with span sampling
    ON (1/64, the default rate) vs OFF (RAFT_LAT_SAMPLE=0) at the same
    scale (default 100k groups, BENCH_LAT_SCALE overrides), in one
    process so all runs share jit caches and the comparison is
    load-for-load fair.  Mirrored ABBA order (off, on, on, off): on a
    shared host, back-to-back in-process runs drift — the second of two
    IDENTICAL unsampled runs measured ~10% slower on a single-vCPU
    container — and ABBA cancels linear drift exactly, where a naive
    off-then-on pair books the entire drift as "sampling overhead".
    Asserts the sampled pair keeps >98% of the unsampled pair's
    throughput — the plane's whole admission design (seeded stride
    selection, bounded in-flight spans, single-writer harvest) exists to
    make observation cheaper than 2%.  The ON runs' results carry the
    per-entry e2e + per-phase distributions."""
    import bench_runtime
    scale = int(os.environ.get("BENCH_LAT_SCALE", "100000"))
    off1 = bench_runtime.run(n_groups=scale, lat_sample=0)
    on1 = bench_runtime.run(n_groups=scale, lat_sample=64)
    on2 = bench_runtime.run(n_groups=scale, lat_sample=64)
    off2 = bench_runtime.run(n_groups=scale, lat_sample=0)
    assert on1["latency"]["sample_rate"] == 64 and \
        off1["latency"]["sample_rate"] == 0, "A/B pins did not take"
    on_cps = (on1["value"] + on2["value"]) / 2
    off_cps = (off1["value"] + off2["value"]) / 2
    overhead = 1.0 - on_cps / max(off_cps, 1)
    res = {
        "scale": scale,
        "platform": _platform(),
        "lat_overhead": round(overhead, 4),
        "sampled_commits_per_sec": round(on_cps),
        "unsampled_commits_per_sec": round(off_cps),
        "order": "ABBA (off, on, on, off)",
        "sampled": [on1, on2],
        "unsampled": [off1, off2],
    }
    save_artifact(res, note="BENCH_LAT stage: span-sampling overhead A/B")
    emit({
        "metric": f"latency-plane sampling overhead @{scale // 1000}k "
                  f"groups (durable runtime, 1/64 sampling vs off, "
                  f"loopback)",
        "value": round(overhead * 100, 2),
        "unit": "% durable commits/sec regression (target <2%)",
        "vs_baseline": None,
        "sampled_commits_per_sec": round(on_cps),
        "unsampled_commits_per_sec": round(off_cps),
        "sampled_e2e": on1["latency"].get("e2e"),
        "sampled_counts": on1["latency"].get("counts"),
    })
    assert overhead < 0.02, (
        f"latency plane costs {overhead * 100:.2f}% durable throughput "
        f"(budget: 2%) — sampled {on_cps:.0f} vs unsampled "
        f"{off_cps:.0f} commits/sec")


def run_heat_ab() -> None:
    """BENCH_HEAT=1: the fleet-attribution overhead A/B replaces the
    ladder — durable commits/sec with the FULL attribution plane on
    (heat lanes compiled in + 1/64 span sampling + cross-node hop
    tracing) vs everything off, at the same scale (default 100k groups,
    BENCH_HEAT_SCALE overrides), in one process so all runs share jit
    caches.  Mirrored ABBA order (off, on, on, off) for the same
    drift-cancellation reason as BENCH_LAT.  Asserts the attributed
    pair keeps >98% of the bare pair's throughput: the heat lanes are
    four branchless [G] adds folded into the existing scan, the drain
    is one vectorized delta per tick, and hop records ride existing
    flushes — observation must stay cheaper than 2%."""
    import bench_runtime
    scale = int(os.environ.get("BENCH_HEAT_SCALE", "100000"))
    off1 = bench_runtime.run(n_groups=scale, lat_sample=0, heat=False,
                             hops=False)
    on1 = bench_runtime.run(n_groups=scale, lat_sample=64, heat=True,
                            hops=True)
    on2 = bench_runtime.run(n_groups=scale, lat_sample=64, heat=True,
                            hops=True)
    off2 = bench_runtime.run(n_groups=scale, lat_sample=0, heat=False,
                             hops=False)
    assert on1["heat"]["enabled"] and not off1["heat"]["enabled"], \
        "A/B heat pins did not take"
    on_cps = (on1["value"] + on2["value"]) / 2
    off_cps = (off1["value"] + off2["value"]) / 2
    overhead = 1.0 - on_cps / max(off_cps, 1)
    res = {
        "scale": scale,
        "platform": _platform(),
        "heat_overhead": round(overhead, 4),
        "attributed_commits_per_sec": round(on_cps),
        "bare_commits_per_sec": round(off_cps),
        "order": "ABBA (off, on, on, off)",
        "active_set": on1["heat"].get("active_set"),
        "attributed": [on1, on2],
        "bare": [off1, off2],
    }
    save_artifact(res, note="BENCH_HEAT stage: fleet-attribution "
                            "overhead A/B")
    emit({
        "metric": f"fleet-attribution overhead @{scale // 1000}k groups "
                  f"(heat lanes + 1/64 sampling + hop tracing vs all "
                  f"off, durable runtime, loopback)",
        "value": round(overhead * 100, 2),
        "unit": "% durable commits/sec regression (target <2%)",
        "vs_baseline": None,
        "attributed_commits_per_sec": round(on_cps),
        "bare_commits_per_sec": round(off_cps),
        "active_set": on1["heat"].get("active_set"),
    })
    assert overhead < 0.02, (
        f"attribution plane costs {overhead * 100:.2f}% durable "
        f"throughput (budget: 2%) — attributed {on_cps:.0f} vs bare "
        f"{off_cps:.0f} commits/sec")


def _ran_on(res: dict) -> str:
    """Every line names the device it ran on."""
    return f"{res['platform']} {res.get('device_kind', '')}".strip()


def headline(res: dict, tuned: bool = False, extra_note: str = "") -> dict:
    note = TUNED_TAG if tuned else ""
    if res.get("nemesis"):
        note += " [NEMESIS: three-regime fault schedule on]"
    if res.get("trace_depth"):
        note += f" [TRACE: flight recorder on, depth {res['trace_depth']}]"
    note += f" [{extra_note}]" if extra_note else ""
    return {
        # "device engine, payload-free": the full consensus protocol
        # (elections, replication fan-out, quorum commit) but no WAL, no
        # payload bytes, no transport — the durable product path is
        # bench_runtime.py's separate metric; the two are NOT comparable.
        "metric": f"AppendEntries commits/sec @{res['scale'] // 1000}k Raft "
                  f"groups (3-node cluster, device engine, "
                  f"payload-free, {_ran_on(res)}){note}",
        "value": round(res["cps"]),
        "unit": "commits/sec",
        "vs_baseline": round(res["cps"] / BASELINE_CPS, 3),
    }


def headline_reads(res: dict) -> dict:
    """The read-plane headline: linearizable reads/sec under a mixed
    90/10 read/write load.  A SEPARATE metric from the commits/sec
    ladder — reads bypass the log, so the two are not directly
    comparable; its baseline is the mix-implied read throughput AT the
    commits baseline (90/10 mix at BASELINE_CPS writes = 9x reads), so
    vs_baseline == 1.0 means the read plane keeps pace with a
    baseline-rate write stream, not a unit-mismatched commits ratio."""
    return {
        "metric": f"linearizable reads/sec @{res['scale'] // 1000}k Raft "
                  f"groups (ReadIndex+lease, mixed {res['read_mix']} "
                  f"read/write, 3-node cluster, device engine, "
                  f"{_ran_on(res)}) "
                  f"[writes rode along at {round(res['cps'])} commits/sec]",
        "value": round(res["rps"]),
        "unit": "reads/sec",
        "vs_baseline": round(res["rps"] / (9 * BASELINE_CPS), 3),
    }


def emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


def save_artifact(res: dict, child_env: dict | None = None,
                  extra_env: dict | None = None, note: str = "") -> None:
    """Persist one successful scale's raw result as a committed-to-repo
    artifact: artifacts/bench_<platform>_<scale>_<seq>.json — the raw
    result + config + env knobs in a file, so a number never lives only in
    prose (the reference's verification ethos is artifact-driven,
    /root/reference/README.md:28-33).
    Best-effort: artifact IO must never kill the bench itself."""
    try:
        os.makedirs(ARTIFACT_DIR, exist_ok=True)
        stem = f"bench_{res.get('platform', 'unknown')}_{res.get('scale', 0)}"
        seq = 0
        while os.path.exists(
                os.path.join(ARTIFACT_DIR, f"{stem}_{seq:03d}.json")):
            seq += 1
        doc = {
            "result": res,
            "note": note,
            "seed": 0,                       # DeviceCluster(cfg, seed=0)
            "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            # The CHILD's effective environment (extra_env applied).
            "env": {k: v for k, v in (child_env or os.environ).items()
                    if k.startswith("BENCH_") or k == "JAX_PLATFORMS"},
            "extra_env": extra_env or {},
            "argv": sys.argv[1:],
        }
        path = os.path.join(ARTIFACT_DIR, f"{stem}_{seq:03d}.json")
        with open(path, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        sys.stderr.write(f"[bench] artifact saved: {path}\n")
    except OSError as e:
        sys.stderr.write(f"[bench] artifact save failed: {e}\n")


def run_scale(n_groups: int, measure_ticks: int, warmup_ticks: int,
              timeout_s: float, profile_dir: str = "",
              extra_env: dict | None = None) -> dict | None:
    """Run one scale in a subprocess; return its result dict or None.
    The child inherits this environment as it is: it runs on the backend
    JAX finds there."""
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           str(n_groups), str(measure_ticks), str(warmup_ticks), profile_dir]
    env = dict(os.environ)
    if extra_env:
        env.update(extra_env)
    try:
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=timeout_s, env=env)
    except subprocess.TimeoutExpired as e:
        # Keep the child's faulthandler watchdog dump — it is the only
        # evidence of WHERE the hang was.
        tail = ""
        if isinstance(e.stderr, (bytes, str)):
            s = e.stderr.decode(errors="replace") if isinstance(e.stderr, bytes) else e.stderr
            tail = "\n".join(s.splitlines()[-25:])
        sys.stderr.write(f"[bench] scale {n_groups}: TIMEOUT after "
                         f"{timeout_s:.0f}s\n{tail}\n")
        return None
    if r.returncode != 0:
        tail = r.stderr.strip().splitlines()[-12:]
        sys.stderr.write(f"[bench] scale {n_groups}: rc={r.returncode}\n" +
                         "\n".join(tail) + "\n")
        return None
    try:
        res = json.loads(r.stdout.strip().splitlines()[-1])
    except (json.JSONDecodeError, IndexError):
        sys.stderr.write(f"[bench] scale {n_groups}: unparseable output: "
                         f"{r.stdout[-500:]!r}\n")
        return None
    save_artifact(res, child_env=env, extra_env=extra_env)
    return res


def main() -> None:
    if len(sys.argv) > 1 and sys.argv[1] in ("--child", "--member-child"):
        # A child is the process that compiles: its cache outlives it.
        from rafting_tpu.utils.compile_cache import enable_compile_cache
        enable_compile_cache()
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        n_groups, ticks, warmup = map(int, sys.argv[2:5])
        profile_dir = sys.argv[5] if len(sys.argv) > 5 else ""
        print(json.dumps(child_run(n_groups, ticks, warmup, profile_dir)))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--member-child":
        print(json.dumps(member_child(int(sys.argv[2]))))
        return
    if env_flag("BENCH_MEMBER"):
        # The membership stage replaces the ladder (like a pinned
        # BENCH_READS run measures reads): reconfig walk-through
        # throughput + the masked-vs-fixed commit kernel A/B.
        run_member_ladder()
        return
    if env_flag("BENCH_LAT"):
        # The latency-plane overhead A/B replaces the ladder: durable
        # commits/sec with 1/64 span sampling vs off (<2% budget).
        run_latency_ab()
        return
    if env_flag("BENCH_HEAT"):
        # The fleet-attribution overhead A/B replaces the ladder:
        # durable commits/sec with heat lanes + sampling + hop tracing
        # vs all off (<2% budget).
        run_heat_ab()
        return
    if env_flag("BENCH_OPENLOOP"):
        # The overload stage replaces the ladder: open-loop rate sweep
        # with admission control on vs force-disabled (no-collapse A/B).
        run_openloop_stage()
        return
    if env_flag("BENCH_TXN"):
        # The transaction stage replaces the ladder: cross-group 2PC
        # transfers/sec + abort rate vs the independent-writes bound.
        run_txn_stage()
        return

    profile_dir = os.environ.get("BENCH_PROFILE_DIR", "")
    only = int(sys.argv[1]) if len(sys.argv) > 1 else None
    scales = [only] if only else list(SCALES)
    smoke_timeout = float(os.environ.get("BENCH_SMOKE_TIMEOUT", "420"))
    scale_timeout = float(os.environ.get("BENCH_SCALE_TIMEOUT", "300"))
    # Global wall budget: keep the whole ladder inside the driver's window
    # even if several scales burn their full timeout.
    budget = float(os.environ.get("BENCH_TOTAL_BUDGET", "2200"))
    t_start = time.monotonic()

    best = None
    # The extra env AND run shape (ticks, warmup) that produced `best` —
    # any later stage whose number is COMPARED against best (the
    # flight-recorder A/B) must re-run identically, or the ratio
    # conflates config / run-length effects with stage overhead.
    best_env: dict = {}
    best_shape = (512, 128)
    for i, g in enumerate(scales):
        is_smoke = (i == 0 and only is None)
        timeout_s = smoke_timeout if i == 0 else scale_timeout
        remaining = budget - (time.monotonic() - t_start)
        if remaining < timeout_s * 0.5:
            sys.stderr.write(f"[bench] budget exhausted before scale {g}\n")
            break
        ticks, warmup = (64, 32) if is_smoke else (512, 128)
        res = run_scale(g, ticks, warmup, min(timeout_s, remaining),
                        profile_dir="" if is_smoke else profile_dir)
        if res is None:
            if best is None and i == 0:
                break   # smoke failed: nothing to report
            # A mid-ladder failure costs that scale only (bounded by its
            # timeout): larger scales may still succeed.
            continue
        best = res
        best_shape = (ticks, warmup)
        sys.stderr.write(f"[bench] scale {g}: {res['cps']:,.0f} commits/s "
                         f"({res['platform']}, warmup {res['warmup_s']}s)\n")
        emit(headline(best))

    if best is None:
        sys.stderr.write("[bench] the smoke scale failed on the backend JAX "
                         "found: no result\n")
        sys.exit(1)

    # Bonus stages: the conservative number is banked; if the top scale
    # passed, try better configurations and publish whichever wins, tagged
    # so the artifact records which config produced it.
    #
    # 1. Pallas quorum kernel — same per-tick cost as the main ladder
    #    (fits the normal scale timeout).  Device only: on CPU the kernel
    #    runs interpret-mode at 1000x cost.
    # 2. Tuned pipeline budget (S=32/B=32/L=256) — 2x+ on CPU.  CPU-only:
    #    4x the per-tick work of the ladder's budget; not measured on a
    #    chip.
    def bonus(extra_env, tag, ticks, warmup, timeout_s):
        nonlocal best, best_env
        remaining = budget - (time.monotonic() - t_start)
        if remaining < timeout_s * 0.4:
            return
        res = run_scale(best["scale"], ticks, warmup,
                        min(timeout_s, remaining),
                        profile_dir=profile_dir, extra_env=extra_env)
        if res is not None and res["cps"] > best["cps"]:
            sys.stderr.write(f"[bench] {tag}: {res['cps']:,.0f} commits/s\n")
            emit(headline(res, tuned=(extra_env is TUNED_ENV),
                          extra_note="" if extra_env is TUNED_ENV else tag))
            best = res
            best_env = dict(extra_env)
            best_shape = (ticks, warmup)

    if scales and best["scale"] == scales[-1] and only is None:
        bonus_timeout = float(os.environ.get("BENCH_BONUS_TIMEOUT", "420"))
        if (best["platform"] != "cpu"
                and "BENCH_USE_PALLAS" not in os.environ):
            bonus({"BENCH_USE_PALLAS": "1"}, "pallas quorum kernel",
                  512, 128, scale_timeout)
        if (best["platform"] == "cpu"
                and not any(k in os.environ for k in TUNED_ENV)):
            bonus(TUNED_ENV, "tuned budget", 96, 48, bonus_timeout)

    # Read-plane stage: linearizable reads/sec (mixed 90/10 read/write,
    # ReadIndex + lease) at the best surviving scale — a SEPARATE headline
    # that never replaces the commits/sec number.  Skipped when the
    # operator pinned BENCH_READS (then the whole ladder measured reads)
    # or BENCH_NEMESIS (the flags are mutually exclusive in the child).
    if (best is not None and "BENCH_READS" not in os.environ
            and "BENCH_NEMESIS" not in os.environ):
        remaining = budget - (time.monotonic() - t_start)
        rd_timeout = float(os.environ.get("BENCH_READS_TIMEOUT", "300"))
        if remaining >= rd_timeout * 0.4:
            ticks, warmup = ((512, 128) if best["platform"] != "cpu"
                             else (96, 48))
            res = run_scale(best["scale"], ticks, warmup,
                            min(rd_timeout, remaining),
                            extra_env={"BENCH_READS": "1"})
            if res is not None and "rps" in res:
                sys.stderr.write(f"[bench] read plane: "
                                 f"{res['rps']:,.0f} reads/s "
                                 f"({res['lease_hits']} lease hits)\n")
                emit(headline_reads(res))
    elif best is not None and "rps" in best:
        # Operator-pinned BENCH_READS ladder: the banked headline above
        # was commits/sec — emit the reads/sec number it was run for.
        emit(headline_reads(best))

    # Faults-on stage: commits/sec under the standard nemesis schedule at
    # the best surviving scale — a SEPARATE headline (chaos throughput is
    # not comparable to the healthy number, so it never replaces `best`).
    # Skipped when the operator already pinned BENCH_NEMESIS (then the
    # whole ladder above was the faults-on run) or BENCH_READS (the child
    # refuses the flag combination).
    if (best is not None and "BENCH_NEMESIS" not in os.environ
            and "BENCH_READS" not in os.environ):
        remaining = budget - (time.monotonic() - t_start)
        nem_timeout = float(os.environ.get("BENCH_NEMESIS_TIMEOUT", "300"))
        if remaining >= nem_timeout * 0.4:
            ticks, warmup = ((512, 128) if best["platform"] != "cpu"
                             else (96, 48))
            res = run_scale(best["scale"], ticks, warmup,
                            min(nem_timeout, remaining),
                            extra_env={"BENCH_NEMESIS": "1"})
            if res is not None:
                sys.stderr.write(f"[bench] nemesis faults-on: "
                                 f"{res['cps']:,.0f} commits/s\n")
                emit(headline(res))

    # Flight-recorder overhead stage (BENCH_TRACE=1 in the child): the
    # same ladder load with cfg.trace_depth event rings compiled into the
    # scan, compared against the banked traceless number — the "tracing
    # is cheap enough to leave on" evidence (acceptance: <= 5% commits/sec
    # regression).  vs_baseline here is with-trace / without-trace, so
    # 0.95+ passes.  Skipped when the operator pinned any stage flag (a
    # pinned ladder already measured what they asked for).
    if (best is not None and "BENCH_TRACE" not in os.environ
            and "BENCH_READS" not in os.environ
            and "BENCH_NEMESIS" not in os.environ):
        remaining = budget - (time.monotonic() - t_start)
        tr_timeout = float(os.environ.get("BENCH_TRACE_TIMEOUT", "300"))
        if remaining >= tr_timeout * 0.4:
            ticks, warmup = best_shape
            res = run_scale(best["scale"], ticks, warmup,
                            min(tr_timeout, remaining),
                            # Same config AND run shape that produced
                            # `best`, plus the recorder — the ratio
                            # isolates trace cost.
                            extra_env={**best_env, "BENCH_TRACE": "1"})
            if res is not None:
                ratio = res["cps"] / best["cps"]
                sys.stderr.write(
                    f"[bench] flight recorder on: {res['cps']:,.0f} "
                    f"commits/s ({(1 - ratio) * 100:+.1f}% overhead, "
                    f"{res.get('trace_events', 0)} events)\n")
                emit({
                    "metric": f"flight-recorder overhead "
                              f"@{res['scale'] // 1000}k Raft groups: "
                              f"commits/sec with trace_depth="
                              f"{res['trace_depth']} vs "
                              f"{round(best['cps'])} without "
                              f"({res['platform']})",
                    "value": round(res["cps"]),
                    "unit": "commits/sec",
                    "vs_baseline": round(ratio, 3),
                })


if __name__ == "__main__":
    main()
