"""What a traced run hands to the per-layer readers: the program's counters
and histograms as deltas over the window, the driver's own clock, and the
trace's reduction.  One reader per metric lives in ``layer_metrics/<name>.py``
as ``read(r: Readings) -> float | None``; a reader that finds nothing to read
returns None and the metric is left out of the line.
"""

from __future__ import annotations

import importlib
import math
import statistics
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from .tracered import Reduction

STAGES = ("scan_wait", "wal", "fsync", "send", "apply", "reads", "maintain")
TICK = "tick_latency_s"


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile of all the values (0 < q <= 100)."""
    s = sorted(values)
    return s[max(0, min(len(s) - 1, math.ceil(len(s) * q / 100) - 1))]


def histogram_marks(node) -> Dict[str, Tuple[int, float]]:
    """(count, total) of the tick histograms of one node, to be subtracted
    from a later reading: the histograms have one writer, the tick thread,
    so the benchmark reads deltas instead of resetting them."""
    names = (TICK,) + tuple(f"tick_stage_{s}_s" for s in STAGES)
    out = {}
    for n in names:
        h = node.metrics.histogram(n)
        out[n] = (h.n, h.total)
    return out


@dataclass
class Readings:
    window_s: float
    # per node: histogram name -> (count, total seconds) over the window
    histograms: List[Dict[str, Tuple[int, float]]]
    ticks: List[int]                       # per node, over the window
    fsync_calls: int                       # all nodes, over the window
    acked_writes: int
    commit_latencies_s: List[float]        # acknowledged writes
    read_latencies_s: List[float]          # answered reads
    gen_late_s: List[float]                # fired - due, every operation
    step_bytes: Optional[int] = None
    peak_bytes_per_s: Optional[float] = None
    trace: Optional[Reduction] = None

    @property
    def busiest(self) -> int:
        """The node whose ticks cost most: the one a commit waits for."""
        means = [(h[TICK][1] / h[TICK][0]) if h[TICK][0] else 0.0
                 for h in self.histograms]
        return means.index(max(means))

    def mean_ms(self, *names: str) -> Optional[float]:
        """Sum of the busiest node's per-tick means of these histograms."""
        h = self.histograms[self.busiest]
        if any(h[n][0] == 0 for n in names):
            return None
        return 1e3 * sum(h[n][1] / h[n][0] for n in names)

    def stage_ms(self, *stages: str) -> Optional[float]:
        return self.mean_ms(*(f"tick_stage_{s}_s" for s in stages))

    @property
    def tick_period_s(self) -> Optional[float]:
        t = statistics.mean(self.ticks) if self.ticks else 0
        return self.window_s / t if t else None

    @property
    def step_device_s(self) -> Optional[float]:
        """Device time of one execution of the step program.  Executions
        are the trace's module events named ``node_step``; where the trace
        names none, they are counted from ``node.ticks`` and the time is
        all device-op time of the slice."""
        t = self.trace
        if t is None or t.busy_s <= 0:
            return None
        if t.step_executions:
            return t.ops_in_steps_s / t.step_executions
        n = sum(self.ticks) * t.window_s / self.window_s
        return t.busy_s * t.n_devices / n if n else None


def read_metric(name: str, r: Readings) -> Optional[float]:
    mod = importlib.import_module(f"benchmark.layer_metrics.{name}")
    v = mod.read(r)
    return None if v is None else float(v)
