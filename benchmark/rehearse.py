#!/usr/bin/env python3
"""CPU rehearsal of the harness, never a measurement: drives every cell of
BENCHMARK.json end to end at 16 and 64 lanes on ``JAX_PLATFORMS=cpu`` (set-up,
warm-up, window, drain, comparison, per-layer readers) and prints counts and
``correct`` only: no time, rate or device metric leaves it.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py [--lanes 16,64] [--workload NAME]
"""

import time

T_PROCESS = time.time()

import argparse     # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def overrides_for(config: dict, lanes: int) -> dict:
    """The configuration cut to ``lanes`` lanes and a tick a busy CPU keeps."""
    return {"raft_config": {"n_groups": lanes, "tick_ms": 100},
            "open_groups": min(lanes - 1, config["open_groups"]),
            "traffic": {"rate_ops_s": 40},
            "latency_limit_ms": 5000, "trace_slice_s": 1}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lanes", default="16,64")
    ap.add_argument("--workload", default=None)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--seed", type=int, default=3_000_000_019)
    a = ap.parse_args()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from benchmark import harness
    from benchmark.cluster import load_config
    bench = harness.load_benchmark()
    ok = True
    for cell in bench["workloads"]:
        if a.workload and cell["name"] != a.workload:
            continue
        _, config_path, _ = harness.find_cell(bench, cell["name"])
        for lanes in (int(x) for x in a.lanes.split(",")):
            for trace in (False, True):
                res = harness.run_cell(
                    cell["name"], a.seed + lanes, a.seconds, trace,
                    time.time(), on_chip=False,
                    overrides=overrides_for(load_config(config_path), lanes))
                names = sorted(res["metrics"])
                print(f"REHEARSED {cell['name']} lanes={lanes} "
                      f"trace={int(trace)} correct={res['correct']} "
                      f"attempted={res['attempted']} failed={res['failed']} "
                      f"metrics_present={names}", flush=True)
                ok &= res["correct"] and res["failed"] == 0
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
