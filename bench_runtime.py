#!/usr/bin/env python
"""Durable-runtime benchmark: commits/sec through the FULL node stack.

Unlike ``bench.py`` (the headline device-engine number, payload-free), this
drives the product path users actually run: real RaftNodes with WAL
durability (persist-before-send barrier), state-machine applies,
snapshot/compaction maintenance and the loopback transport, across a
3-node in-process cluster.  Nodes tick sequentially in one thread —
threading them was measured 2x SLOWER (three jax host programs sharing
one GIL + oversubscribed XLA threadpools); a real deployment runs one
process per node, so the honest single-process number is per-node cost x
3, not a thread-contended mess.  The output carries the slowest node's
tick-latency histogram so host-path stalls are visible, not averaged
away.

Offered load is shaped like the BASELINE scale story: dense per group at
small group counts, aggregate-heavy / per-group-light at 32k-100k (the
100k-group regime is many quiet groups, not 100k firehoses — per-group
rate at the 1M/s target is ~10 commits/s/group).

Prints one JSON line per scale, each naming the backend the engine ran
on: the one JAX finds.  For the CPU backend set ``JAX_PLATFORMS=cpu``.

Usage: bench_runtime.py [n_groups ...] [--tcp]
"""

import json
import shutil
import sys
import tempfile
import time

import numpy as np


def _shape(n_groups: int):
    """(per-group burst, measured rounds, log_slots) per scale: dense at
    small G, aggregate-heavy at large G (the 100k regime is many quiet
    groups — per-group rate at the 1M/s aggregate target is ~10
    commits/s).  log_slots grows with scale because sustained acceptance
    is bounded by checkpoint-throughput x ring-capacity / n_groups
    (see RaftNode.max_checkpoints_per_tick): a 256-slot ring at 100k
    groups caps the drain far below the offered load no matter how fast
    the host tier gets.  Device-ring cost of L=1024 at 100k groups is
    ~400MB per node — HBM-realistic for the v5e target."""
    if n_groups <= 8_192:
        return 32, 40, 1024
    if n_groups <= 32_768:
        return 8, 25, 512
    return 8, 12, 1024


def run(n_groups: int = 1024, rounds: int = 0, burst_n: int = 0,
        transport: str = "loopback", pipeline=None,
        host_workers=None, native=None, lat_sample=None,
        heat=None, hops=None) -> dict:
    """``pipeline``: True/False forces the durable pipeline on/off for
    every node; None uses the runtime default (RAFT_PIPELINE env if set,
    else on exactly when the engine's backend is not the CPU — see
    RaftNode).
    ``host_workers``: striped host tier width per node (None = the
    runtime default, env RAFT_HOST_WORKERS else 1 = serial).
    ``native``: True/False pins the C++ stage_and_sync host tier on/off
    via RAFT_NATIVE_HOST for the run; None = runtime auto-selection
    (native whenever the native WAL engine built).
    ``lat_sample``: pins RAFT_LAT_SAMPLE (1/N span sampling; 0 disables
    the latency plane entirely) for the run; None = env default.  When
    the plane is on, the result carries per-entry commit-path latency
    distributions (e2e + per-phase), not just throughput.
    ``heat``: True/False compiles the per-group heat lanes
    (EngineConfig.heat — device activity counters + host heat registry)
    in/out; None = off (the config default).
    ``hops``: True/False pins RAFT_HOP_TRACE (cross-node hop tracing)
    on/off for the run; None = env default (on)."""
    from rafting_tpu.core.types import EngineConfig, LEADER
    from rafting_tpu.testkit.fixtures import NullProvider
    from rafting_tpu.testkit.harness import LocalCluster

    d_burst, d_rounds, d_slots = _shape(n_groups)
    burst_n = burst_n or d_burst
    rounds = rounds or d_rounds

    # The tuned pipeline budget (S=32/B=32/L=256, the 32k-group sweep from
    # bench.py's bonus stage): more commits per Python-visited group per
    # tick, which is exactly what the host tier's O(groups-visited) cost
    # structure wants.  (L=1024 was measured and does NOT help — the cap
    # is host per-entry work, not ring/compaction coupling.)  BENCH_RT_*
    # env knobs override.
    import os
    slots = int(os.environ.get("BENCH_RT_SLOTS", str(d_slots)))
    cfg = EngineConfig(
        n_groups=n_groups, n_peers=3, log_slots=slots,
        batch=int(os.environ.get("BENCH_RT_BATCH", "32")),
        max_submit=int(os.environ.get("BENCH_RT_SUBMIT", "32")),
        election_ticks=10, heartbeat_ticks=3, rpc_timeout_ticks=8,
        heat=bool(heat))
    root = tempfile.mkdtemp(prefix="bench-runtime-")
    pins = {}
    if native is not None:
        pins["RAFT_NATIVE_HOST"] = "1" if native else "0"
    if lat_sample is not None:
        pins["RAFT_LAT_SAMPLE"] = str(lat_sample)
    if hops is not None:
        pins["RAFT_HOP_TRACE"] = "1" if hops else "0"
    env_prev = {k: os.environ.get(k) for k in pins}
    os.environ.update(pins)
    try:
        c = LocalCluster(cfg, root, provider_factory=NullProvider, seed=0,
                         transport=transport, pipeline=pipeline,
                         host_workers=host_workers)
    finally:
        for k, v in env_prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    payload = b"x" * 64
    burst = [payload] * burst_n

    def tick_round():
        for n in c.nodes.values():
            n.tick()

    def offer():
        # Fill every led+ready group's per-round budget through the BULK
        # batch API: one arena build + one lock acquisition per node for
        # the whole fan-out (the per-group submit_batch loop was ~100k
        # calls/round at the top scale — ~30% of the durable tick).
        for n in c.nodes.values():
            mask = (n.h_role == LEADER) & n.h_ready
            n.submit_batch_many(np.nonzero(mask)[0], burst)

    try:
        c.wait_leader(0, max_rounds=300)
        # Settle until EVERY group elected (condition-driven: the
        # pipelined runtime adds one tick of message latency, so a fixed
        # settle count that worked serially under-waits at 32k+ groups).
        for _ in range(40):
            c.tick(5)
            roles = np.stack([m.h_role for m in c.nodes.values()])
            if (roles == LEADER).any(axis=0).all():
                break
        leaders = np.array([c.leader_of(g) if c.leader_of(g) is not None
                            else -1 for g in range(n_groups)])
        assert (leaders >= 0).all()

        # Warmup (also compiles every jit variant the loop will hit).
        for _ in range(5):
            offer()
            tick_round()
        # The reported latency histogram covers the MEASURE phase only:
        # election warmup + first-tick XLA compiles are one-time costs
        # (tens of seconds on CPU at 100k groups) that otherwise own the
        # p99 of a 15-round run and bury the steady-state number the
        # durable tier is actually judged on.
        for n in c.nodes.values():
            n.metrics.histogram("tick_latency_s").reset()
            for stage in n.metrics.breakdown():
                n.metrics.histogram(f"tick_stage_{stage}").reset()
            # Per-entry latency distributions are measure-phase only too
            # (a warmup span that waited out an election would own p999).
            for name in list(n.metrics._histograms):
                if name.startswith("lat_"):
                    n.metrics.histogram(name).reset()
            # Windowed-rate baseline: rates(since_last=True) below then
            # reports measure-phase throughput, not a lifetime average
            # diluted by election warmup + compile ticks.
            n.metrics.checkpoint()
        start = sum(int(n.h_commit.astype(np.int64).sum())
                    for n in c.nodes.values()) / len(c.nodes)
        t0 = time.perf_counter()
        for _ in range(rounds):
            offer()
            tick_round()
        elapsed = time.perf_counter() - t0
        end = sum(int(n.h_commit.astype(np.int64).sum())
                  for n in c.nodes.values()) / len(c.nodes)
        commits = end - start
        lat = {}
        for n in c.nodes.values():
            h = n.metrics.histogram("tick_latency_s")
            if h.n and (not lat or h.quantile(0.5) > lat.get("p50_s", 0)):
                lat = {"p50_s": round(h.quantile(0.5), 5),
                       "p99_s": round(h.quantile(0.99), 5),
                       "max_s": round(h.max, 4),
                       "ticks": h.n}
        # Measure-window rates from the checkpointed registries (the
        # "commits" counter is the absolute frontier, so its windowed
        # delta/sec is a per-node commits/sec cross-check of the headline;
        # applies/sec is the state-machine drain the aggregate hides).
        applies_ps = max((n.metrics.rates(since_last=True)
                          .get("applies_per_sec", 0.0))
                         for n in c.nodes.values())
        # Per-stage tick breakdown (scan-wait / wal / fsync / send / apply
        # / maintain) from the slowest node — measure-phase only, mean
        # seconds per tick — so a regression shows WHERE the tick went,
        # not just that it got slower.  The same histograms back the
        # /metrics exposition (runtime/obsrv.py).
        slow = max(c.nodes.values(),
                   key=lambda n: n.metrics.histogram("tick_latency_s").total)
        stages = {k: round(v["mean"], 6)
                  for k, v in slow.metrics.breakdown().items()}
        # Per-entry commit-path latency distributions (the sampled span
        # plane, utils/latency.py) from the node with the most completed
        # spans — leadership is spread across nodes, so any single node
        # sees ~1/3 of the sampled population.
        latency = {"sample_rate": 0}
        lat_node = max(c.nodes.values(),
                       key=lambda n: n.metrics.histogram("lat_e2e_s").n)
        if lat_node._lat is not None:
            def _summ(name):
                h = lat_node.metrics._histograms.get(name)
                if h is None or not h.n:
                    return None
                s = h.summary()
                return {"count": s["count"], "mean_s": round(s["mean"], 6),
                        "p50_s": round(s["p50"], 6),
                        "p99_s": round(s["p99"], 6),
                        "p999_s": round(h.quantile(0.999), 6),
                        "max_s": round(s["max"], 6)}
            latency = {
                "sample_rate": lat_node._lat.rate,
                "counts": dict(lat_node._lat.counts),
                "e2e": _summ("lat_e2e_s"),
                "phases": {name: s for name in (
                    "submit_offer", "offer_stage", "stage_fsync",
                    "fsync_send", "send_commit", "commit_apply",
                    "apply_ack")
                    if (s := _summ(f"lat_{name}_s")) is not None},
            }
        import jax
        dev = jax.devices()[0]
        return {
            "metric": f"durable-runtime commits/sec @{n_groups} groups "
                      f"(3 nodes, WAL fsync barrier, applies, {transport}, "
                      f"engine on {dev.platform} {dev.device_kind})",
            "platform": dev.platform,
            "value": round(commits / elapsed),
            "unit": "commits/sec",
            "vs_baseline": None,
            "burst_per_group": burst_n,
            "rounds": rounds,
            "pipeline": bool(slow.pipeline),
            "host_workers": int(slow._w_eff),
            "native_host": bool(slow._native_host),
            "native_workers": int(slow._w_native) if slow._native_host
                              else 0,
            "wal_shards": getattr(getattr(slow.store, "wal", None),
                                  "n_shards", 1),
            "tick_latency": lat,
            "tick_stages_mean_s": stages,
            "applies_per_sec_windowed": round(applies_ps),
            "latency": latency,
            "heat": ({"enabled": True,
                      "active_set": slow.heatmap_snapshot(8)
                      .get("active_set")}
                     if slow.heat is not None else {"enabled": False}),
        }
    finally:
        c.close()
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    from rafting_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    args = sys.argv[1:]
    transport = "loopback"
    if "--tcp" in args:
        # Real localhost sockets: measures the transport plane's framing,
        # sender queues, reader threads and accumulator under durable
        # load (the reference system test's topology,
        # test/resources/raft1.xml:3-7).
        args.remove("--tcp")
        transport = "tcp"
    scales = [int(a) for a in args] or [1024]
    import os
    for n in scales:
        out = run(n_groups=n, transport=transport)
        print(json.dumps(out), flush=True)
        if os.environ.get("BENCH_PIPELINE", "") == "1":
            # Serial-vs-pipelined A/B at the same scale: the headline run
            # above used the backend-aware default, so only the OTHER
            # mode is re-run (forced explicitly — on a CPU host the
            # default is serial, and a None-vs-False comparison would
            # silently measure serial against itself).  The comparison
            # line reports the speedup plus both runs' per-stage tick
            # breakdowns.
            other = run(n_groups=n, transport=transport,
                        pipeline=not out["pipeline"])
            print(json.dumps(other), flush=True)
            piped, serial = ((out, other) if out["pipeline"]
                             else (other, out))
            print(json.dumps({
                "metric": f"durable pipeline speedup @{n} groups "
                          f"({transport})",
                "value": round(piped["value"] / max(serial["value"], 1), 3),
                "unit": "x (pipelined / serial commits/sec)",
                "pipelined_commits_per_sec": piped["value"],
                "serial_commits_per_sec": serial["value"],
                "pipelined_stages_mean_s": piped["tick_stages_mean_s"],
                "serial_stages_mean_s": serial["tick_stages_mean_s"],
            }), flush=True)
        if os.environ.get("BENCH_HOSTPAR", "") == "1":
            # Serial-vs-striped host tier A/B at the same scale: re-run
            # with host_workers forced to 1 (serial orchestration, the
            # pre-stripe behaviour), then W=2 and W=4 striped.  Each run
            # prints its own JSON line (per-stage tick breakdown included
            # — the striped runs report the max-across-workers stage
            # times, so stage sums can exceed wall tick time); the
            # comparison line is striped-vs-serial commits/sec.
            base = run(n_groups=n, transport=transport, host_workers=1)
            print(json.dumps(base), flush=True)
            for w in (2, 4):
                striped = run(n_groups=n, transport=transport,
                              host_workers=w)
                print(json.dumps(striped), flush=True)
                print(json.dumps({
                    "metric": f"striped host tier speedup @{n} groups "
                              f"(W={striped['host_workers']}, {transport})",
                    "value": round(striped["value"] /
                                   max(base["value"], 1), 3),
                    "unit": "x (striped / serial commits/sec)",
                    "striped_commits_per_sec": striped["value"],
                    "serial_commits_per_sec": base["value"],
                    "striped_stages_mean_s": striped["tick_stages_mean_s"],
                    "serial_stages_mean_s": base["tick_stages_mean_s"],
                }), flush=True)
        if os.environ.get("BENCH_NATIVE", "") == "1":
            # Native-vs-Python host tier A/B at the same scale: the C++
            # stage_and_sync path (GIL released, real OS threads) against
            # the pure-Python serial staging loop.  Both runs print their
            # own JSON line; the comparison line carries the per-backend
            # wal/fsync/send stage means — the tentpole's acceptance
            # metric is mean wal_s, not just the commits/sec headline
            # (which also folds in scan-wait and apply cost that the
            # native tier doesn't touch).
            py = run(n_groups=n, transport=transport, native=False,
                     host_workers=1)
            print(json.dumps(py), flush=True)
            nat = run(n_groups=n, transport=transport, native=True,
                      host_workers=4)
            print(json.dumps(nat), flush=True)

            def _st(d, k):
                return d["tick_stages_mean_s"].get(k, 0.0)
            print(json.dumps({
                "metric": f"native host tier wal speedup @{n} groups "
                          f"(W={nat['native_workers']}, {transport})",
                "value": round(_st(py, "wal_s") /
                               max(_st(nat, "wal_s"), 1e-9), 3),
                "unit": "x (python wal_s / native wal_s, mean per tick)",
                "native_commits_per_sec": nat["value"],
                "python_commits_per_sec": py["value"],
                "native": {k: _st(nat, k)
                           for k in ("wal_s", "fsync_s", "send_s")},
                "python": {k: _st(py, k)
                           for k in ("wal_s", "fsync_s", "send_s")},
            }), flush=True)
