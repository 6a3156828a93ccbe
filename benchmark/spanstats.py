"""The statistics the program writes on its stage spans, read from a traced
slice.

Beside ``node`` and ``tick`` a ``raft.<phase>`` span may carry what that
tick's phase did, known only once the phase is done: ``raft.reads`` carries
``queries`` and ``barriers`` (what the tick served, and under how many
ReadIndex barriers), ``raft.maintain`` carries ``ring_used`` (the fullest
log ring of the node, in entries), ``ring_slots``, ``led`` (lanes the node
leads), ``checkpoints`` and ``by_pressure``.  This file gathers them per
phase, node and tick.  A program whose spans carry no such statistic (the
parent of PR 26) yields nothing, and every reader built on this returns
None.

``stagespans.py`` keeps the phases' times and finds the run's xplane.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

from .stagespans import PREFIX, find_run_xplane
from .tracered import HOST_PLANE

# phase -> node -> tick -> {statistic: value}
Stats = Dict[str, Dict[int, Dict[int, Dict[str, float]]]]
KEYS = ("node", "tick")


def reduce_planes(planes) -> Stats:
    """``planes``: ``ProfileData.planes``, or objects shaped like them."""
    out: Stats = {}
    for p in planes:
        if p.name != HOST_PLANE:
            continue
        for ln in p.lines:
            for e in ln.events:
                if not e.name.startswith(PREFIX):
                    continue
                stats = dict(e.stats)
                if not all(k in stats for k in KEYS):
                    continue
                extra = {k: float(v) for k, v in stats.items()
                         if k not in KEYS and isinstance(v, (int, float))}
                if extra:
                    out.setdefault(e.name[len(PREFIX):], {}).setdefault(
                        int(stats["node"]), {}).setdefault(
                        int(stats["tick"]), {}).update(extra)
    return out


@functools.lru_cache(maxsize=2)
def reduce_file(path: str) -> Stats:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)


def of(r) -> Optional[Stats]:
    """The span statistics behind a ``Readings``: from ``r.xplane`` where
    the harness filled it in, else from this process's traced slice."""
    path = getattr(r, "xplane", None) or find_run_xplane()
    return reduce_file(path) if path else None


def rows(stats: Optional[Stats], phase: str, need: str
         ) -> Dict[int, List[Dict[str, float]]]:
    """node -> the ticks' statistics of ``phase`` that carry ``need``."""
    if not stats:
        return {}
    out = {}
    for node, ticks in stats.get(phase, {}).items():
        have = [s for s in ticks.values() if need in s]
        if have:
            out[node] = have
    return out
