"""The tick loop's stage spans read from a traced slice.

The program (``rafting_tpu/utils/profiling.py StageSpans``) writes one
``raft.<name>`` span per phase of every tick of every node onto
``/host:CPU`` of whatever profiler session runs, each carrying ``node`` and
``tick``; phases are siblings that tile the loop period (``raft.wait`` is the
sleep between two ticks).  This file reduces them to per-tick stage times
and to the share of the device's idle time in which every tick thread was
asleep.  A program without such spans (the parent of PR 24) yields nothing,
and every reader built on this returns None.

``tracered.py`` keeps the device side; its plane names, ``union`` and
``find_xplane`` are used from there.
"""

from __future__ import annotations

import functools
import glob
import os
import tempfile
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from .tracered import (DEVICE_PLANE, HOST_PLANE, OPS_LINE, Interval,
                       find_xplane, union)

PREFIX = "raft."
WAIT = "wait"
# The phases of one tick() call, first and last.
FIRST, LAST = "dispatch_intake", "tail"


@dataclass
class Stages:
    # node -> tick -> phase -> [seconds, first start, last end]
    ticks: Dict[int, Dict[int, Dict[str, List[float]]]] = \
        field(default_factory=dict)
    waits: Dict[int, List[Interval]] = field(default_factory=dict)
    idle: List[Interval] = field(default_factory=list)   # device idle

    def complete(self, node: int) -> List[Dict[str, List[float]]]:
        """The ticks of ``node`` that lie whole inside the slice."""
        return [t for t in self.ticks.get(node, {}).values()
                if FIRST in t and LAST in t]

    def busiest(self) -> Optional[int]:
        """The node whose ticks cost most: the one a commit waits for."""
        best, best_s = None, -1.0
        for node in self.ticks:
            whole = self.complete(node)
            if whole:
                s = sum(p[0] for t in whole for p in t.values()) / len(whole)
                if s > best_s:
                    best, best_s = node, s
        return best

    def mean_ms(self, *phases: str) -> Optional[float]:
        """Per-tick mean of these phases' summed time, busiest node."""
        node = self.busiest()
        if node is None:
            return None
        whole = self.complete(node)
        return 1e3 * sum(t[p][0] for t in whole for p in phases
                         if p in t) / len(whole)

    def unspanned_ms(self) -> Optional[float]:
        """Per-tick mean of the time between the start of the first phase
        and the end of the last that no phase covers, busiest node."""
        node = self.busiest()
        if node is None:
            return None
        whole = self.complete(node)
        return 1e3 * sum((t[LAST][2] - t[FIRST][1])
                         - sum(p[0] for p in t.values())
                         for t in whole) / len(whole)

    def idle_in_wait_pct(self) -> Optional[float]:
        """Share of the device's idle time in which EVERY node's tick
        thread was inside ``raft.wait``."""
        total = sum(b - a for a, b in self.idle)
        if not self.waits or total <= 0:
            return None
        asleep = None
        for spans in self.waits.values():
            u = union(spans)
            asleep = u if asleep is None else intersect(asleep, u)
        return 100.0 * sum(b - a for a, b in
                           intersect(asleep, self.idle)) / total


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def reduce_planes(planes) -> Stages:
    """``planes``: ``ProfileData.planes``, or objects shaped like them."""
    s = Stages()
    for p in planes:
        if p.name == HOST_PLANE:
            for ln in p.lines:
                for e in ln.events:
                    if not e.name.startswith(PREFIX):
                        continue
                    stats = dict(e.stats)
                    if "node" not in stats or "tick" not in stats:
                        continue
                    node, tick = int(stats["node"]), int(stats["tick"])
                    a = e.start_ns * 1e-9
                    b = a + e.duration_ns * 1e-9
                    name = e.name[len(PREFIX):]
                    if name == WAIT:
                        s.waits.setdefault(node, []).append((a, b))
                        continue
                    rec = s.ticks.setdefault(node, {}).setdefault(
                        tick, {}).setdefault(name, [0.0, a, b])
                    rec[0] += b - a
                    rec[1], rec[2] = min(rec[1], a), max(rec[2], b)
        elif DEVICE_PLANE.match(p.name):
            every, ops = [], []
            for ln in p.lines:
                evs = [(e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9)
                       for e in ln.events]
                every += evs
                if ln.name == OPS_LINE:
                    ops = evs
            if not every:
                continue
            lo, hi = min(a for a, _ in every), max(b for _, b in every)
            edges = [lo] + [t for ab in union(ops) for t in ab] + [hi]
            s.idle += [(edges[i], edges[i + 1])
                       for i in range(0, len(edges), 2)
                       if edges[i + 1] > edges[i]]
    s.idle = union(s.idle) if s.idle else []
    return s


@functools.lru_cache(maxsize=2)
def reduce_file(path: str) -> Stages:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)


def _process_started() -> float:
    try:
        return os.stat(f"/proc/{os.getpid()}").st_ctime
    except OSError:
        return 0.0


def find_run_xplane() -> Optional[str]:
    """The xplane of THIS process's traced slice.  ``harness.data_root()``
    makes the run's directory as ``raftbench-*`` under the temp directory
    or, where that is memory, under the checkout's ignored output path,
    and ``harness.run_cell`` traces into its ``trace`` subdirectory; the
    directory goes when the run ends."""
    from .harness import OUT_DIR
    since = _process_started() - 1.0
    found = [d for base in {tempfile.gettempdir(), OUT_DIR}
             for d in glob.glob(os.path.join(base, "raftbench-*", "trace"))
             if os.path.getmtime(d) >= since]
    for d in sorted(found, key=os.path.getmtime, reverse=True):
        try:
            return find_xplane(d)
        except FileNotFoundError:
            continue
    return None


def of(r) -> Optional[Stages]:
    """The stage spans behind a ``Readings``: from ``r.xplane`` where the
    harness filled it in, else from this process's traced slice."""
    path = getattr(r, "xplane", None) or find_run_xplane()
    return reduce_file(path) if path else None
