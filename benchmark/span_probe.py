#!/usr/bin/env python3
"""Chip-side probes of the tracing plane (PR 24), not runs of the benchmark:

    python3 benchmark/span_probe.py traced    --workload W --seed N --seconds S
    python3 benchmark/span_probe.py late-tick --workload W --seed N --seconds 20

``traced`` is ``run.py --trace 1`` with every operation sampled
(``RAFT_LAT_SAMPLE=1``, set here before the cluster is built) and the
program's registry marked around the window (``program_marks.install``): the
same ``harness.run_cell``, the same result line, with the span and counter
metrics of ``span_metrics.json`` in it, and each node's /latency, /hops and
registry documents written to ``benchmark_out/spans_<workload>.json``.

``late-tick`` is PERF.md's call 25 read by the program's own instruments:
one process boots the cell's cluster, drives a window undisturbed, then makes
ONE tick of one node last two periods through its public ``tick()`` and
drives further windows, printing per window and per node ``ticks_late``,
the inbox backlog and wait, and the commit's replicate part of the writes
that node leads.
"""

import time

T_PROCESS = time.time()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402
import traceback    # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def traced(a) -> None:
    from benchmark import harness, program_marks
    from benchmark import readings as rd
    got = program_marks.install(harness, rd)
    result = harness.run_cell(a.workload, a.seed, a.seconds, True,
                              T_PROCESS, on_chip=not a.cpu_lanes,
                              overrides=rehearsal(a))
    if got.program:
        # Every stage of the busiest node as the histograms have it over
        # window + drain (the listed readers read the traced slice).
        busiest = max(got.program, key=lambda p: mean_s(p, rd.TICK))
        harness.say("stages", **{
            k[len("tick_stage_"):-2]: round(1e3 * mean_s(busiest, k), 3)
            for k in sorted(busiest["histograms"])
            if k.startswith("tick_stage_")})
    if got.documents is not None:
        os.makedirs(harness.OUT_DIR, exist_ok=True)
        path = os.path.join(harness.OUT_DIR, f"spans_{a.workload}.json")
        with open(path, "w") as f:
            json.dump(got.documents, f)
        harness.say("spans", file=path, bytes=os.path.getsize(path))
    harness.finish(result)


def mean_s(program: dict, name: str) -> float:
    n, total = program["histograms"].get(name, (0, 0.0))
    return total / n if n else 0.0


def rehearsal(a):
    """CPU rehearsal of the probe's control flow at a tiny size; its
    numbers are never device numbers."""
    if not a.cpu_lanes:
        return None
    return {"raft_config": {"n_groups": a.cpu_lanes, "tick_ms": 100},
            "open_groups": a.cpu_lanes - 1, "traffic": {"rate_ops_s": 40},
            "latency_limit_ms": 5000, "trace_slice_s": 1}


def late_tick(a) -> None:
    from benchmark import harness as h
    from benchmark import program_marks as pm
    from benchmark import readings as rd
    from benchmark.cluster import Cluster, load_config
    from benchmark.reference import OK
    from benchmark.traffic import load_traffic, make_schedule

    bench = h.load_benchmark()
    _, config_path, traffic_path = h.find_cell(bench, a.workload)
    config, traffic = load_config(config_path), load_traffic(traffic_path)
    ov = rehearsal(a)
    if ov:
        config["raft_config"].update(ov["raft_config"])
        config.update(open_groups=ov["open_groups"],
                      latency_limit_ms=ov["latency_limit_ms"])
        traffic.update(ov["traffic"])
    import jax
    from rafting_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    d = jax.devices()[0]
    h.say("device", platform=d.platform, kind=repr(d.device_kind))
    if d.platform != "tpu" and not a.cpu_lanes:
        raise SystemExit(2)
    tick_s = config["raft_config"]["tick_ms"] / 1e3
    limit_s = config["latency_limit_ms"] / 1e3
    cluster = Cluster(config, h.data_root(), a.seed, h.say)
    cluster.boot(timeout_s=max(300.0, 400 * tick_s))
    cluster.warm_up(timeout_s=max(120.0, 200 * tick_s))
    nodes = [c.node for c in cluster.containers]
    h.say("setup", seconds=round(time.time() - T_PROCESS, 2))

    def stretch_one_tick(node, periods: float) -> None:
        """The node's next tick lasts ``periods`` periods: its public
        ``tick()`` wrapped for one call."""
        tick = node.tick

        def slow():
            node.tick = tick
            t0 = time.perf_counter()
            out = tick()
            time.sleep(max(0.0, periods * tick_s
                           - (time.perf_counter() - t0)))
            return out
        node.tick = slow

    def one(label: str, seed: int) -> None:
        sched = make_schedule(traffic, seed, a.seconds,
                              config["open_groups"])
        before = [pm.marks(n) for n in nodes]
        comps, _, ticks, _, _ = h.window(cluster, sched, a.seconds, limit_s)
        prog = [pm.delta(b, pm.marks(n)) for b, n in zip(before, nodes)]
        writes = [c.latency_s for c in comps
                  if c.op.kind == "w" and c.record.outcome == OK]
        reads = [c.latency_s for c in comps
                 if c.op.kind == "r" and c.record.outcome == OK]
        h.say("window", label=label, attempted=len(comps),
              failed=sum(c.record.outcome != OK for c in comps),
              commit_p50_ms=round(1e3 * rd.percentile(writes, 50), 1),
              commit_p95_ms=round(1e3 * rd.percentile(writes, 95), 1),
              read_p50_ms=round(1e3 * rd.percentile(reads, 50), 1),
              ticks=ticks)
        for i, p in enumerate(prog):
            def mean_ms(*names):
                return round(1e3 * sum(mean_s(p, n) for n in names), 1)
            h.say("node", label=label, node=i,
                  ticks_late=p["counters"].get("ticks_late", 0),
                  inbox_backlog_slices=round(mean_s(p, "inbox_backlog"), 3),
                  inbox_wait_ms=mean_ms("inbox_wait_s"),
                  commit_replicate_ms=mean_ms("lat_fsync_send_s",
                                              "lat_send_commit_s"),
                  commit_e2e_ms=mean_ms("lat_e2e_s"),
                  spans=p["histograms"].get("lat_e2e_s", (0, 0.0))[0],
                  inbox_collapsed=p["counters"].get("inbox_collapsed", 0),
                  inbox_dropped=p["counters"].get("inbox_dropped", 0))

    one("undisturbed", a.seed)
    stretch_one_tick(nodes[a.node], 2.0)
    one(f"node{a.node}-late-once", a.seed + 1)
    one("untouched-after", a.seed + 2)
    h.cluster_done(cluster, cluster.data_root)
    sys.stdout.flush()
    os._exit(0)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=("traced", "late-tick"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--node", type=int, default=2)
    ap.add_argument("--cpu-lanes", type=int, default=0,
                    help="CPU rehearsal at this many lanes (no chip)")
    a = ap.parse_args()
    # Every operation sampled; read once, when a node is built.
    os.environ["RAFT_LAT_SAMPLE"] = "1"
    (traced if a.mode == "traced" else late_tick)(a)


if __name__ == "__main__":
    try:
        main()
    except SystemExit:
        raise
    except BaseException:
        traceback.print_exc()
        sys.stdout.flush()
        os._exit(1)
