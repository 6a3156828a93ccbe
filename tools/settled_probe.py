#!/usr/bin/env python3
"""How often the tick loop settles a tick in its own period (PR 25): one run
of a benchmark cell through the benchmark's own ``harness.run_cell`` (the
same set-up, window, drain and comparison as ``benchmark/run.py``), with each
node's ``ticks``, ``ticks_settled`` and ``ticks_on_arrival`` counters read
around the window (PR 29: a step is the timer's, or one the loop started for
arriving work; the engine's clock ``state.now`` moves by the timer's alone,
so ``engine_now`` equals ``timer_ticks`` on a node booted from an empty
directory, give or take the step in flight when the two were read), and
beside them (PR 43) ``steps_held``, the arrival steps that started at the end
of the gap ``arrival_step_at`` left behind the step before, with work
already waiting, and ``arrival_gap_ms``, the mean gap an arrival step was
given (histogram ``arrival_gap_s``):

    python3 tools/settled_probe.py --workload W --seed N --seconds S [--trace 1]
        [--tick-ms T] [--rate R] [--cpu-lanes L]

Prints the run's own lines, one ``[settled]`` line per node (window + drain,
and the whole process) and the result line last.  ``--tick-ms`` runs the
cell's cluster at another period: at one shorter than a node's tick work the
loop has no room and the line shows the fallback (ticks overlapped as the
pipeline was built); ``--rate`` offers another rate than the traffic file's.  ``--cpu-lanes`` rehearses the control flow on the CPU
at a tiny size; its numbers are counts of ticks, never device numbers.
"""

import time

T_PROCESS = time.time()

import argparse     # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402
import traceback    # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def engine_now(node) -> int:
    """``state.now`` of a node whose loop is running: the step in flight
    donates the state it was handed, so a reading may have to be taken
    again."""
    for _ in range(20):
        try:
            return int(node.state.now)
        except RuntimeError:
            time.sleep(0.001)
    return -1


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tick-ms", type=int, default=0)
    ap.add_argument("--rate", type=float, default=0.0)
    ap.add_argument("--cpu-lanes", type=int, default=0)
    a = ap.parse_args()
    from benchmark import harness, program_marks
    from benchmark import readings as rd
    got = program_marks.install(harness, rd)
    ov = {"raft_config": {}}
    if a.cpu_lanes:
        from benchmark.cluster import load_config
        from benchmark.rehearse import overrides_for
        _, config_path, _ = harness.find_cell(harness.load_benchmark(),
                                              a.workload)
        ov = overrides_for(load_config(config_path), a.cpu_lanes)
    if a.tick_ms:
        ov["raft_config"]["tick_ms"] = a.tick_ms
    if a.rate:
        ov.setdefault("traffic", {})["rate_ops_s"] = a.rate
    result = harness.run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                              T_PROCESS, on_chip=not a.cpu_lanes,
                              overrides=ov)
    for node, p in zip(got.nodes, got.program or ()):
        w, whole = p["counters"], node.metrics
        gaps, gap_s = p["histograms"].get("arrival_gap_s", (0, 0.0))
        harness.say("settled", node=node.node_id,
                    window_ticks=w.get("ticks", 0),
                    window_settled=w.get("ticks_settled", 0),
                    window_share=round(w.get("ticks_settled", 0)
                                       / max(1, w.get("ticks", 0)), 4),
                    window_on_arrival=w.get("ticks_on_arrival", 0),
                    arrival_share=round(w.get("ticks_on_arrival", 0)
                                        / max(1, w.get("ticks", 0)), 4),
                    steps_held=w.get("steps_held", 0),
                    arrival_gap_ms=round(1e3 * gap_s / max(1, gaps), 3),
                    process_ticks=whole["ticks"],
                    process_settled=whole["ticks_settled"],
                    process_on_arrival=whole["ticks_on_arrival"],
                    timer_ticks=node.timer_ticks,
                    engine_now=engine_now(node),
                    ticks_late=w.get("ticks_late", 0))
    harness.finish(result)


if __name__ == "__main__":
    try:
        main()
    except SystemExit as e:
        sys.stdout.flush()
        os._exit(e.code if isinstance(e.code, int) else 1)
    except BaseException:
        traceback.print_exc()
        sys.stdout.flush()
        os._exit(1)
