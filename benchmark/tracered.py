"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the benchmark
reports: the device's busy time as the union of the intervals in which an
operation ran, the executions and device time of the step program, the
device operations that took most time, and the longest idle gaps labelled
by what the host was doing in them.

A device plane is one named ``/device:TPU:<n>`` (``DEVICE_PLANE``); its
``XLA Ops`` line holds one event per executed operation and its
``XLA Modules`` line one event per executed program.  Host threads are the
lines of ``/host:CPU``.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE, HOST_PLANE = "XLA Ops", "XLA Modules", "/host:CPU"
Interval = Tuple[float, float]          # seconds from the trace's origin
LABELLED_GAPS = 50          # the longest idle gaps are labelled, no more
MIN_HOST_SPAN_S = 1e-4      # host spans shorter than this label nothing


@dataclass
class Reduction:
    window_s: float = 0.0               # length of the traced slice
    busy_s: float = 0.0                 # mean over the device planes
    n_devices: int = 0
    step_executions: int = 0            # module events named like the step
    step_device_s: float = 0.0          # their summed device time
    ops_in_steps_s: float = 0.0         # op time inside those executions
    device_ops: List[list] = field(default_factory=list)   # [name, seconds]
    idle_gaps: List[list] = field(default_factory=list)    # [label, seconds]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def short_name(name: str) -> str:
    """An XLA op event is named by its whole HLO line
    (``%fusion.27 = s32[16,64]{...} fusion(...)``): keep the op's name."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def _events(line) -> List[Tuple[str, float, float]]:
    return [(short_name(e.name), e.start_ns * 1e-9,
             (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]


def _clip(evs, lo: float, hi: float):
    return [(n, max(a, lo), min(b, hi)) for n, a, b in evs
            if b > lo and a < hi]


def reduce_planes(planes, window: Optional[Interval] = None,
                  step_name: str = "node_step") -> Reduction:
    """``planes``: ``ProfileData.planes``.  ``window``: the slice to judge,
    in seconds on the trace's clock; by default from the first to the last
    device event."""
    dev, host = [], []
    for p in planes:
        if DEVICE_PLANE.match(p.name):
            lines = {ln.name: _events(ln) for ln in p.lines}
            dev.append((lines.get(OPS_LINE, []), lines.get(MODULES_LINE, [])))
        elif p.name == HOST_PLANE:
            for ln in p.lines:
                host += [e for e in _events(ln) if e[2] - e[1] > MIN_HOST_SPAN_S]
    r = Reduction(n_devices=len(dev))
    every = [e for ops, mods in dev for e in ops + mods]
    if not every:
        return r
    lo, hi = window or (min(a for _, a, _ in every),
                        max(b for _, _, b in every))
    r.window_s = hi - lo
    by_op: Dict[str, float] = {}
    gaps: List[Interval] = []
    for ops, mods in dev:
        ops = _clip(ops, lo, hi)
        busy = union([(a, b) for _, a, b in ops])
        r.busy_s += sum(b - a for a, b in busy) / len(dev)
        for n, a, b in ops:
            by_op[n] = by_op.get(n, 0.0) + (b - a)
        edges = [lo] + [t for ab in busy for t in ab] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        steps = [(a, b) for n, a, b in _clip(mods, lo, hi) if step_name in n]
        r.step_executions += len(steps)
        r.step_device_s += sum(b - a for a, b in steps)
        su = union(steps)
        starts = [a for a, _ in su]
        for _, a, b in ops:         # an op lies inside one execution
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and a < su[i][1]:
                r.ops_in_steps_s += min(b, su[i][1]) - a
    r.device_ops = [[n, s] for n, s in
                    sorted(by_op.items(), key=lambda kv: -kv[1])[:10]]
    by_label: Dict[str, float] = {}
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:LABELLED_GAPS]:
        best, best_s = "host: no span", 0.0
        for n, ha, hb in host:
            s = min(b, hb) - max(a, ha)
            if s > best_s:
                best, best_s = n, s
        by_label[best] = by_label.get(best, 0.0) + (b - a)
    r.idle_gaps = [[n[:80], s] for n, s in
                   sorted(by_label.items(), key=lambda kv: -kv[1])[:10]]
    return r


def reduce_file(path: str, window: Optional[Interval] = None,
                step_name: str = "node_step") -> Reduction:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, window,
                         step_name)


def describe(path: str, top: int = 12) -> List[str]:
    """Planes, lines, event counts and the commonest names: what one reads
    by hand before trusting a reduction."""
    from jax.profiler import ProfileData
    out = []
    for p in ProfileData.from_file(path).planes:
        out.append(f"PLANE {p.name}")
        for ln in p.lines:
            evs = _events(ln)
            tot: Dict[str, List[float]] = {}
            for n, a, b in evs:
                t = tot.setdefault(n, [0, 0.0])
                t[0] += 1
                t[1] += b - a
            span = (min(a for _, a, _ in evs), max(b for _, _, b in evs)) \
                if evs else (0, 0)
            out.append(f"  LINE {ln.name!r} events={len(evs)} "
                       f"span=({span[0]:.4f},{span[1]:.4f})")
            for n, (c, s) in sorted(tot.items(),
                                    key=lambda kv: -kv[1][1])[:top]:
                out.append(f"    {c:7d} x {s:10.6f}s  {n[:100]}")
    return out
