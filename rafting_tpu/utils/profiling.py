"""Device profiling hooks (SURVEY §5: the reference has no tracing at all —
logback lines only, support/RaftConfig.java:137-141 — so the TPU build adds
JAX profiler integration from the start).

:class:`StageSpans` names the phase a node's tick thread is in.  At
each phase boundary it observes ``tick_stage_<name>_s`` in the node's
registry and, while ANY ``jax.profiler`` session runs (whoever started
it), emits a ``raft.<name>`` span carrying ``node`` and ``tick`` on
``/host:CPU`` of that session, on the device trace's clock.
"""

from __future__ import annotations

import logging
import time
from typing import Dict, Optional

from jax.profiler import TraceAnnotation

log = logging.getLogger(__name__)

# The sleep between two steps: the one phase that may outlast a period.
WAIT = "wait"


class StageSpans:
    """The phase the tick thread is in, one at a time: ``enter`` ends the
    open phase and starts the next at the same instant, so the phases of a
    loop period tile it with no gap and no overlap.  Spans are siblings —
    no parent span: a trace reducer that labels a device gap with the
    host span of greatest overlap would hand every gap to the parent.
    Tick thread only (the registry's single writer).  With no profiler
    session a boundary costs one flag test beside its histogram sample.

    A phase of a step that outlasts the loop's ``period`` is a STALL:
    counter ``stage_stalls`` and one warning, from the two instants the
    boundary has taken anyway.  It is what leaves the peers' slices
    queued past the inbox's collapse (transport/inbox.py)."""

    __slots__ = ("_metrics", "_node", "tick", "period", "spent", "_name",
                 "_t0", "_observe", "_span")

    def __init__(self, metrics, node_id: int):
        self._metrics = metrics
        self._node = int(node_id)
        self.tick = 0
        # What a started loop says its period is (seconds), once its
        # first step (which loads or compiles the program) is done; None
        # under a caller that steps the node itself, who has no period.
        self.period: Optional[float] = None
        # Seconds per phase since begin(): what the node sums into the
        # composite stages (dispatch, scan_wait) and the tick's total.
        self.spent: Dict[str, float] = {}
        self._name: Optional[str] = None
        self._t0 = 0.0
        self._observe = True
        self._span = None

    def begin(self, tick: int) -> None:
        """A new tick: spans from here carry its number."""
        self.tick = tick
        self.spent.clear()

    def enter(self, name: str, observe: bool = True) -> float:
        """Boundary: the open phase ends, ``name`` starts.  Returns the
        instant.  ``observe=False`` emits the span but leaves the
        histogram to the caller (host phases that split one call's time
        by the engine's own measurement)."""
        now = self.leave()
        self._name, self._t0, self._observe = name, now, observe
        if TraceAnnotation.is_enabled():
            self._span = TraceAnnotation("raft." + name, node=self._node,
                                         tick=self.tick)
            self._span.__enter__()
        return now

    def note(self, **stats) -> None:
        """Statistics of the open phase, known only once its work is done
        (what a tick served, how full a ring stands): they ride the
        phase's span beside ``node`` and ``tick``.  No session, no span,
        nothing to do."""
        if self._span is not None:
            self._span.set_metadata(**stats)

    def total(self, *names: str) -> float:
        """Seconds spent in these phases since begin()."""
        return sum(self.spent.get(n, 0.0) for n in names)

    def leave(self) -> float:
        """End the open phase (if any) with none following."""
        span, self._span = self._span, None
        if span is not None:
            span.__exit__(None, None, None)
        now = time.perf_counter()
        name, self._name = self._name, None
        if name is not None:
            dt = now - self._t0
            self.spent[name] = self.spent.get(name, 0.0) + dt
            if self._observe:
                self._metrics.observe(f"tick_stage_{name}_s", dt)
            if self.period is not None and dt > self.period \
                    and name != WAIT:
                self._metrics["stage_stalls"] += 1
                log.warning(
                    "node %d tick %d: stage %s took %.3f s (period %.3f s)",
                    self._node, self.tick, name, dt, self.period)
        return now
