#!/usr/bin/env python3
"""One run of one cell, on the chip:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time.  No CPU fallback: when JAX's first device is not a
TPU, or there are fewer chips than the cell asks for, it says so, prints no
result and exits non-zero.  The last line of standard output is the result
object; everything else is on earlier lines.
"""

import time

T_PROCESS = time.time()

import argparse     # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402
import traceback    # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args()
    try:
        from benchmark import harness
        result = harness.run_cell(a.workload, a.seed, a.seconds,
                                  bool(a.trace), T_PROCESS, on_chip=True)
    except SystemExit as e:
        sys.stdout.flush()
        os._exit(e.code if isinstance(e.code, int) else 1)
    except BaseException:
        traceback.print_exc()
        sys.stdout.flush()
        os._exit(1)     # never the per-group teardown of a half-built cluster
    harness.finish(result)


if __name__ == "__main__":
    main()
