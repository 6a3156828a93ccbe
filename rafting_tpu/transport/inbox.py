"""InboxAccumulator: delivers asynchronously arriving peer slices to the
dense per-tick inbox the engine consumes.

Nodes tick independently; a peer may deliver zero, one or several slices
between two local ticks.  Slices are queued per source and drained **one
per source per tick, in arrival order** — the engine sees exactly the
per-tick message planes the sender emitted, just time-shifted.  Ordered
delivery is what makes the leader's pipelined AppendEntries window sound
(several un-acked batches in flight per (group, peer), core/step.py
phase 9): batch k+1's prev-entry check assumes batch k was offered first,
the same in-order contract the reference gets from one TCP connection per
peer (transport/EventNode.java:39-120).

Catch-up: a consumer that falls behind (tick-rate drift, a JIT-compile
stall) must not lag permanently — one-slice-per-tick service can never
drain a standing backlog under sustained traffic, and stale delivery makes
every reply look timed out.  When a source's queue exceeds
COLLAPSE_BACKLOG, the whole backlog is collapsed into one slice,
newest-wins per (kind, group).  Collapsing reorders nothing the protocol
can't absorb: replies/votes are idempotent, and a collapsed (= partially
lost) AppendEntries stream makes the follower reject at the gap, which
resets the leader's window and resends from the ack base — the engine's
normal loss recovery (the reference's per-request timeouts + stale-reply
term fencing, transport/rpc/AsyncService.java:120-132,
context/member/Leader.java:224-227).

AppendEntries payload bytes ride with their frame and are staged here until
the engine accepts the entries (StepInfo.appended_from/to), at which point
the runtime moves them into the durable LogStore.

The backlog is measured where it stands: every slice is stamped at
``merge()``, and ``drain()`` records (under the queue lock) how long each
slice it popped had waited, how many slices each source still holds after
the pop, and how many were collapsed or dropped.  ``take_stats()`` hands
the record to the draining thread — the node's tick thread, which folds it
into its registry; reader threads never touch a registry.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np

from .codec import KIND_FIELDS


@dataclass
class InboxStats:
    """What the drains since the last ``take_stats()`` saw."""
    waits_s: List[float] = field(default_factory=list)   # merge -> drain,
    #                                             one per slice popped
    depth: Dict[int, int] = field(default_factory=dict)  # src -> slices
    #                                   left queued after the last pop
    collapsed: int = 0        # slices merged into one by a collapse
    dropped: int = 0          # slices refused at MAX_QUEUED_SLICES


class InboxAccumulator:
    MAX_QUEUED_SLICES = 64   # per source; beyond this, new slices drop
    COLLAPSE_BACKLOG = 3     # backlog beyond this collapses to one slice

    def __init__(self, cfg, template: Dict[str, Tuple[np.dtype, tuple]]):
        self.cfg = cfg
        self.template = template
        self._lock = threading.Lock()
        # src -> FIFO of (fields, payloads, arrival) slices, fields in the
        # sparse codec.unpack_slice format: field -> (group cols, values);
        # arrival is the merge() instant on this process's perf_counter.
        self._queues: Dict[int, Deque[tuple]] = {}
        self._stats = InboxStats()      # guarded by _lock

    def merge(self, src: int,
              fields: Dict[str, Tuple[np.ndarray, np.ndarray]],
              payloads: Dict[int, Tuple[int, list]]) -> None:
        """Enqueue one unpacked slice from peer ``src`` (payloads as
        per-group contiguous runs, codec.unpack_slice format)."""
        with self._lock:
            q = self._queues.get(src)
            if q is None:
                q = self._queues[src] = deque()
            if len(q) >= self.MAX_QUEUED_SLICES:
                self._stats.dropped += 1
                return   # = network loss; sender's resend timeout recovers
            q.append((fields, payloads, time.perf_counter()))

    def drain(self, arrays: Optional[Dict[str, np.ndarray]] = None
              ) -> Tuple[Dict[str, np.ndarray],
                         Dict[Tuple[int, int], Tuple[int, list]]]:
        """Pop the oldest queued slice of every source and merge them into
        one dense inbox (different sources occupy disjoint [src, :] rows,
        so one slice per source never collides).  A source whose backlog
        exceeds COLLAPSE_BACKLOG has its entire queue collapsed instead
        (newest wins per lane) so lag stays bounded.

        ``arrays``: the zeroed dense planes to fill, one per template
        field — the runtime hands in views of the tick's packed upload
        buffer, so the planes are written where they cross to the device
        from; by default fresh ones are allocated.

        Returns the dense arrays (ownership transfers to the caller) and
        the popped slices' payload runs keyed (src, group) — newest-wins
        per group under collapse, matching the field planes."""
        P, G = self.cfg.n_peers, self.cfg.n_groups
        if arrays is None:
            arrays = {name: np.zeros((P, G) + trail, dt)
                      for name, (dt, trail) in self.template.items()}
        payloads: Dict[Tuple[int, int], Tuple[int, list]] = {}
        with self._lock:
            st = self._stats
            now = time.perf_counter()
            for src, q in self._queues.items():
                if not q:
                    st.depth[src] = 0
                    continue
                if len(q) > self.COLLAPSE_BACKLOG:
                    batch, q_new = list(q), deque()
                    self._queues[src] = q_new
                    st.collapsed += len(batch)
                else:
                    batch = [q.popleft()]
                st.depth[src] = len(self._queues[src])
                for fields, pl, arrived in batch:
                    st.waits_s.append(now - arrived)
                    for name, (cols, vals) in fields.items():
                        arrays[name][src, cols] = vals
                    for g, run in pl.items():
                        payloads[(src, g)] = run
        return arrays, payloads

    def take_stats(self) -> InboxStats:
        """The record of the drains since the last call; the caller owns
        it.  Call from the draining thread, right after ``drain()``."""
        with self._lock:
            st, self._stats = self._stats, InboxStats()
        return st

    @property
    def has_traffic(self) -> bool:
        with self._lock:
            return any(self._queues.values())
