"""The program's own registry over a window, for the per-layer readers that
read spans and counters the harness does not name.

``marks(node)`` reads EVERY histogram, counter and gauge of a node through
its public ``node.metrics.to_dict()`` (no list of names: the next tracing
metric is a reader file and a ``per_layer`` entry); ``delta(before, after)``
is what one window added.  A ``Readings`` that carries the per-node deltas
as ``program`` (and the traced slice's file as ``xplane``) is read by
``layer_metrics/commit_*_ms.py``, ``read_*_ms.py``, ``inbox_*.py`` and
``ticks_late.py``; without the field those readers return None.

``harness.py`` does not fill the two fields yet (a tracing PR may not edit
it; PERF.md section 7 names the lines).  Until it does, ``install()`` wraps
the harness's own ``window``, ``read_metric`` and ``load_benchmark`` at run
time for ``span_probe.py``, so that the probe runs the one path of
``harness.run_cell`` and prints the same result line with these metrics in
it.
"""

from __future__ import annotations

import json
import os
from typing import List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))


def marks(node) -> dict:
    doc = node.metrics.to_dict()
    return {"histograms": {k: (h["count"], h["count"] * h["mean"])
                           for k, h in doc["histograms"].items()},
            "counters": dict(doc["counters"]),
            "gauges": dict(doc["gauges"])}


def delta(before: dict, after: dict) -> dict:
    """Histograms as (count, total) added, counters as added, gauges as
    they stand at the end."""
    h0, c0 = before["histograms"], before["counters"]
    return {"histograms": {k: (n - h0.get(k, (0, 0.0))[0],
                               t - h0.get(k, (0, 0.0))[1])
                           for k, (n, t) in after["histograms"].items()},
            "counters": {k: v - c0.get(k, 0)
                         for k, v in after["counters"].items()},
            "gauges": dict(after["gauges"])}


def program_of(r) -> Optional[List[dict]]:
    return getattr(r, "program", None)


def pooled_mean_ms(r, *names: str) -> Optional[float]:
    """Sum over ``names`` of the mean over ALL nodes' samples: the phase
    pairs of one span plane are observed once per span on the node that
    led it, so pooled means over the same spans add up to the mean of
    their end-to-end histogram."""
    prog = program_of(r)
    if prog is None:
        return None
    out = 0.0
    for name in names:
        n = sum(p["histograms"].get(name, (0, 0.0))[0] for p in prog)
        if n == 0:
            return None
        out += sum(p["histograms"].get(name, (0, 0.0))[1]
                   for p in prog) / n
    return 1e3 * out


def worst_node_mean(r, name: str) -> Optional[float]:
    """The largest per-node mean of one histogram: a backlog stands on
    the node that was disturbed, not on the busiest."""
    prog = program_of(r)
    if prog is None:
        return None
    means = [t / n for n, t in (p["histograms"].get(name, (0, 0.0))
                                for p in prog) if n]
    return max(means) if means else None


def counter_sum(r, name: str) -> Optional[float]:
    prog = program_of(r)
    if prog is None:
        return None
    return float(sum(p["counters"].get(name, 0) for p in prog))


# ---------------------------------------------------------------- the probe

def extra_entries() -> List[dict]:
    """The ``per_layer`` entries of the readers that need ``program``:
    ``BENCHMARK.json`` gains them with the harness edit."""
    with open(os.path.join(HERE, "span_metrics.json")) as f:
        return json.load(f)["per_layer"]


class Installed:
    """What ``install()`` gathered while ``harness.run_cell`` ran."""

    def __init__(self):
        self.nodes: list = []
        self.program: Optional[List[dict]] = None
        self.documents: Optional[dict] = None


def install(harness, rd) -> Installed:
    """Wrap three functions of the harness, for this process only: the
    window is bracketed by ``marks``, every ``Readings`` gets ``program``
    and ``xplane`` before its first reader runs, and the benchmark's
    ``per_layer`` list is followed by ``extra_entries()``."""
    from . import stagespans
    got = Installed()
    window, read_metric, load = (harness.window, rd.read_metric,
                                 harness.load_benchmark)

    def marked_window(cluster, *a, **kw):
        got.nodes = [c.node for c in cluster.containers]
        before = [marks(n) for n in got.nodes]
        out = window(cluster, *a, **kw)
        got.program = [delta(b, marks(n))
                       for b, n in zip(before, got.nodes)]
        return out

    def filled_read_metric(name, r):
        if program_of(r) is None:
            r.program = got.program
            r.xplane = stagespans.find_run_xplane()
            got.documents = documents(got.nodes)
        return read_metric(name, r)

    def load_with_extras():
        bench = load()
        bench["per_layer"] = bench["per_layer"] + extra_entries()
        return bench

    harness.window = marked_window
    rd.read_metric = filled_read_metric
    harness.load_benchmark = load_with_extras
    return got


def documents(nodes) -> dict:
    """Each node's /latency and /hops documents and its registry, through
    the node's public surface: what ``spans_<workload>.json`` holds."""
    return {"nodes": [{"node": n.node_id, "ticks": n.ticks,
                       "latency": n.latency_snapshot(),
                       "hops": n.hops_snapshot(),
                       "metrics": n.metrics.to_dict()} for n in nodes]}
