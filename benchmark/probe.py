#!/usr/bin/env python3
"""Chip-side probes that are not runs of the benchmark: the knee sweep and
the controls.  One process boots a cell's cluster once and drives several
short windows through the harness's own window, drain and comparison.

    python3 benchmark/probe.py sweep   --workload W --rates 5,10,20 --seconds 20
    python3 benchmark/probe.py control --workload W --seeds 1,2,3 --seconds 15

``sweep`` prints, per rate, failures, commit p50/p95 of each half of the
window, read p95 and goodput: the knee is the highest rate at which nothing
fails and the second half's commit p95 is not above the first half's.
``control`` builds the cluster with the machines that can be broken
(``cluster.FaultyKVMachine``) and drives, per seed, a sound window
(``correct`` must be true), then one with ``stale_reads`` and one with
``drop_apply`` (must be false).
"""

import time

T_PROCESS = time.time()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402
import traceback    # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=("sweep", "control"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="2147483659")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--drain", type=float, default=None)
    a = ap.parse_args()
    from benchmark import harness as h
    from benchmark import readings as rd
    from benchmark.cluster import Cluster, load_config
    from benchmark.reference import OK
    from benchmark.traffic import load_traffic, make_schedule

    bench = h.load_benchmark()
    cell, config_path, traffic_path = h.find_cell(bench, a.workload)
    config, traffic = load_config(config_path), load_traffic(traffic_path)
    import jax
    from rafting_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    watch = h.CompileWatch()
    d = jax.devices()[0]
    h.say("device", platform=d.platform, kind=repr(d.device_kind))
    if d.platform != "tpu":
        raise SystemExit(2)
    tick_s = config["raft_config"]["tick_ms"] / 1e3
    drain = a.drain if a.drain is not None \
        else config["latency_limit_ms"] / 1e3
    seeds = [int(s) for s in a.seeds.split(",")]
    cluster = Cluster(config, h.data_root(), seeds[0], h.say,
                      faults=a.mode == "control")
    cluster.boot(timeout_s=max(300.0, 400 * tick_s))
    cluster.warm_up(timeout_s=max(120.0, 200 * tick_s))
    h.say("setup", seconds=round(time.time() - T_PROCESS, 2))

    def one(seed: int, rate, fault):
        sched = make_schedule(traffic, seed, a.seconds, config["open_groups"],
                              rate_ops_s=rate)
        touched = {}
        for op in sched:
            touched.setdefault(op.group, set()).add(op.key)
        initial = cluster.replica_states(touched)[0]
        cluster.set_fault(fault)
        mark = watch.mark()
        jax.config.update("jax_log_compiles", True)
        comps, hist, ticks, fsyncs, elapsed = h.window(
            cluster, sched, a.seconds, drain)
        jax.config.update("jax_log_compiles", False)
        h.say("compiles", **watch.since(mark))
        cluster.set_fault(None)
        verdict = h.settle_and_check(cluster, comps, initial,
                                     timeout_s=max(30.0, 30 * tick_s))
        return comps, verdict, ticks, hist

    def pct(xs, q):
        return round(1e3 * rd.percentile(xs, q), 1) if xs else None

    if a.mode == "sweep":
        for i, rate in enumerate(float(r) for r in a.rates.split(",")):
            comps, verdict, ticks, hist = one(seeds[0] + i, rate, None)
            half = a.seconds / 2
            w1 = [c.latency_s for c in comps if c.op.kind == "w"
                  and c.op.due_s < half and c.record.outcome == OK]
            w2 = [c.latency_s for c in comps if c.op.kind == "w"
                  and c.op.due_s >= half and c.record.outcome == OK]
            rs = [c.latency_s for c in comps if c.op.kind == "r"
                  and c.record.outcome == OK]
            r1 = [c.latency_s for c in comps if c.op.kind == "r"
                  and c.op.due_s < half and c.record.outcome == OK]
            r2 = [c.latency_s for c in comps if c.op.kind == "r"
                  and c.op.due_s >= half and c.record.outcome == OK]
            e2e = h.end_to_end(comps, a.seconds,
                               config["latency_limit_ms"] / 1e3)
            r = rd.Readings(a.seconds, hist, ticks, 0, 0, [], [], [])
            h.say("sweep", rate=rate, attempted=len(comps),
                  failed=sum(c.record.outcome != OK for c in comps),
                  commit_p50_ms=pct(w1 + w2, 50),
                  commit_p95_first_half_ms=pct(w1, 95),
                  commit_p95_second_half_ms=pct(w2, 95),
                  read_p50_ms=pct(rs, 50), read_p95_ms=pct(rs, 95),
                  read_p95_first_half_ms=pct(r1, 95),
                  read_p95_second_half_ms=pct(r2, 95),
                  goodput=round(e2e["goodput"], 2), correct=verdict.correct,
                  ticks=ticks, tick_work_ms=r.mean_ms(rd.TICK),
                  errors=sorted({c.record.error.split(":")[0]
                                 for c in comps if c.record.error}))
    else:
        # Each mode gets seeds of its own: the same seed would write the
        # same values to the same keys again, and a dropped or stale write
        # would be hidden by its twin from the earlier window.
        for k, fault in enumerate((None, "stale_reads", "drop_apply")):
            for seed in (s + 1_000_003 * k for s in seeds):
                comps, verdict, _, _ = one(seed, None, fault)
                h.say("control", fault=fault, seed=seed,
                      attempted=len(comps),
                      failed=sum(c.record.outcome != OK for c in comps),
                      correct=verdict.correct,
                      numbers=json.dumps(verdict.numbers))
    h.cluster_done(cluster, cluster.data_root)
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    try:
        main()
    except SystemExit:
        raise
    except BaseException:
        traceback.print_exc()
        sys.stdout.flush()
        os._exit(1)
