"""``coord-1g-3v.mixed-steady`` exists to measure reads that share a
barrier, so the comparison that decides ``correct`` has to see them: the
cell's own keys (``scrambled`` uniform over 10,000: drawn with replacement)
make some reads of a run meet a key the run wrote before, on every seed,
and with the timed path's reads broken underneath the cell's own traffic
comes out NOT correct (no smaller key space laid over it, as
``test_end_to_end.py`` does for its controls)."""

import time

import numpy as np
import pytest

from benchmark import harness
from benchmark.cluster import load_config
from benchmark.rehearse import overrides_for
from benchmark.traffic import load_traffic, make_schedule

CELL = "coord-1g-3v.mixed-steady"
BENCH = harness.load_benchmark()
_, CONFIG_PATH, TRAFFIC_PATH = harness.find_cell(BENCH, CELL)


def rereads(sched, gap_s):
    """Reads due more than ``gap_s`` after the first write to their key."""
    first, n = {}, 0
    for op in sched:
        if op.kind == "w":
            first.setdefault(op.key, op.due_s)
        elif op.due_s - first.get(op.key, float("inf")) > gap_s:
            n += 1
    return n


def test_every_seed_rereads_keys_it_wrote():
    traffic, config = load_traffic(TRAFFIC_PATH), load_config(CONFIG_PATH)
    assert traffic["key_dist"] == {"kind": "uniform", "scrambled": True}
    gap = config["latency_limit_ms"] / 1e3     # the write was acknowledged
    rng = np.random.default_rng(26)
    seeds = [1, 2 ** 31 + 12345] + [int(s) for s in rng.integers(
        0, 2 ** 31 + 1000, 200)]
    counts = [rereads(make_schedule(traffic, s, BENCH["run_seconds"], 1), gap)
              for s in seeds]
    assert min(counts) >= 5, min(counts)


@pytest.mark.parametrize("seed", [2_500_000_001, 4_300_000_079])
def test_stale_reads_are_caught_on_the_cells_own_keys(seed):
    ov = overrides_for(load_config(CONFIG_PATH), 16)
    ov["traffic"]["rate_ops_s"] = 30
    seconds = float(BENCH["run_seconds"])       # the cell's own window
    sched = make_schedule(load_traffic(TRAFFIC_PATH), seed, seconds, 1,
                          rate_ops_s=30)
    assert rereads(sched, 2.0) >= 5
    res = harness.run_cell(CELL, seed, seconds, False, time.time(),
                           on_chip=False, overrides=ov, fault="stale_reads")
    assert res["attempted"] == len(sched)
    assert res["correct"] is False
