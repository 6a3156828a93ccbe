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
the pop, how many were collapsed or dropped, and how many replies a
collapse overwrote.  ``take_stats()`` hands
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
    merged: int = 0           # replies a collapse overwrote (_merged)


# The replies a leader's window counts one by one (core/step.py phase 6
# releases ONE slot per AppendEntries reply, heartbeat echoes included, and
# the snapshot offer's slot per InstallSnapshot reply): a collapse keeps a
# lane's newest and the window never hears of the others.
COUNTED_REPLIES = tuple(KIND_FIELDS[kind][0] for kind in ("aer", "isr"))


def _merged(batch: List[tuple], n_groups: int) -> int:
    """Replies that merging ``batch`` (one source's slices) newest-wins per
    lane overwrites: per counted kind, the lanes over the slices less the
    distinct lanes."""
    lost = 0
    for valid in COUNTED_REPLIES:
        lanes = [fields[valid][0] for fields, _, _ in batch
                 if valid in fields]
        if len(lanes) > 1:
            seen = np.zeros(n_groups, bool)
            seen[np.concatenate(lanes)] = True
            lost += sum(map(len, lanes)) - int(seen.sum())
    return lost


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

    def pop(self) -> Tuple[Dict[int, List[Dict]],
                           Dict[Tuple[int, int], Tuple[int, list]]]:
        """Pop the oldest queued slice of every source: ``{src: [fields,
        ...]}`` in arrival order (one slice; a source whose backlog
        exceeds COLLAPSE_BACKLOG hands over its entire queue instead, to
        be merged newest-wins per lane so lag stays bounded) and the
        popped slices' payload runs keyed (src, group), newest-wins per
        group under collapse, matching the fields.  The caller merges
        the fields into the form its step takes: :func:`scatter_dense`
        or :func:`fill_columns`."""
        batches: Dict[int, List[Dict]] = {}
        payloads: Dict[Tuple[int, int], Tuple[int, list]] = {}
        collapsed: List[List[tuple]] = []
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
                    collapsed.append(batch)
                else:
                    batch = [q.popleft()]
                st.depth[src] = len(self._queues[src])
                mine = batches[src] = []
                for fields, pl, arrived in batch:
                    st.waits_s.append(now - arrived)
                    mine.append(fields)
                    for g, run in pl.items():
                        payloads[(src, g)] = run
        if collapsed:
            # Counted with the queues free again (a storm's collapse is
            # seventeen slices of tens of thousands of lanes), and written
            # without the lock: this thread alone swaps the record
            # (take_stats) and no other writes this field.
            st.merged += sum(_merged(b, self.cfg.n_groups) for b in collapsed)
        return batches, payloads

    def drain(self, arrays: Optional[Dict[str, np.ndarray]] = None
              ) -> Tuple[Dict[str, np.ndarray],
                         Dict[Tuple[int, int], Tuple[int, list]]]:
        """:meth:`pop` merged into one dense inbox (different sources
        occupy disjoint [src, :] rows, so one slice per source never
        collides).

        ``arrays``: the zeroed dense planes to fill, one per template
        field — the runtime hands in views of the tick's packed upload
        buffer, so the planes are written where they cross to the device
        from; by default fresh ones are allocated.

        Returns the dense arrays (ownership transfers to the caller) and
        the payload runs."""
        P, G = self.cfg.n_peers, self.cfg.n_groups
        if arrays is None:
            arrays = {name: np.zeros((P, G) + trail, dt)
                      for name, (dt, trail) in self.template.items()}
        batches, payloads = self.pop()
        scatter_dense(batches, arrays)
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


def scatter_dense(batches: Dict[int, List[Dict]],
                  arrays: Dict[str, np.ndarray]) -> None:
    """Write popped slices into zeroed dense ``[P, G, ...]`` planes, in
    arrival order: the newest wins a lane."""
    for src, batch in batches.items():
        for fields in batch:
            for name, (cols, vals) in fields.items():
                arrays[name][src, cols] = vals


def fill_columns(batches: Dict[int, List[Dict]], view) -> bool:
    """Write popped slices into an empty column buffer pair (``view``: a
    core/packing.py ColumnView of an ``alloc()``-ed pair) as
    :func:`scatter_dense` writes them into dense planes: a source's
    columns are the union of its slices' lanes, ascending, and each field
    lands at its lanes' places in arrival order.  False, with nothing
    written, when a source holds more columns than the buffers take: the
    step then crosses densely.  Work follows the columns that arrived,
    never P x G."""
    K = view.cols.shape[1]
    plans = []
    for src, batch in batches.items():
        # A kind's fields share one lanes array: place each once.
        shared: List[Tuple[np.ndarray, List]] = []
        for fields in batch:
            by_lanes: Dict[int, Tuple[np.ndarray, List]] = {}
            for name, (cols, vals) in fields.items():
                by_lanes.setdefault(id(cols), (cols, []))[1].append(
                    (name, vals))
            shared.extend(by_lanes.values())
        if any(len(cols) > K for cols, _ in shared):
            return False
        if not shared:
            continue
        union = np.unique(np.concatenate([cols for cols, _ in shared]))
        if len(union) > K:
            return False
        plans.append((src, union, shared))
    for src, union, shared in plans:
        view.n[src] = len(union)
        view.cols[src, :len(union)] = union
        for cols, parts in shared:
            at = np.searchsorted(union, cols)
            for name, vals in parts:
                view.planes[name][src, at] = vals
    return True
