#!/usr/bin/env python3
"""Spread of each end-to-end metric over sets of runs, by the builder's
rule: the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median, the wider
of the sets, and five times the widest as the bound it suggests.

    python3 benchmark/spread.py FILE [FILE ...]

Each FILE holds the result lines (the last stdout line of ``run.py``) of one
set of runs of one cell, one JSON object per line; other lines are skipped.
"""

import json
import statistics
import sys


def result_lines(path):
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line.startswith('{"correct"'):
                out.append(json.loads(line))
    return out


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(paths):
    sets = [result_lines(p) for p in paths]
    names = sorted({k for s in sets for r in s for k in r["metrics"]})
    for p, s in zip(paths, sets):
        print(f"{p}: runs={len(s)} correct={[r['correct'] for r in s]} "
              f"failed={[r['failed'] for r in s]} "
              f"attempted={[r['attempted'] for r in s]}")
    for name in names:
        rows = [[r["metrics"][name]["value"] for r in s
                 if name in r["metrics"]] for s in sets]
        # each set's first run compiles or fills the cache: setup_s leaves
        # it out, as the driver does
        if name == "setup_s":
            rows = [v[1:] for v in rows]
        meds = [statistics.median(v) for v in rows if v]
        sp = [spread(v) for v in rows if len(v) >= 2]
        print(f"{name}: medians={[round(m, 4) for m in meds]} "
              f"spreads={[round(x, 4) for x in sp]} "
              f"suggested_bound={round(max(0.01, 5 * max(sp)), 3) if sp else None}"
              + (f" second_vs_first={round(meds[1] / meds[0] - 1, 4)}"
                 if len(meds) == 2 else ""))


if __name__ == "__main__":
    main(sys.argv[1:])
