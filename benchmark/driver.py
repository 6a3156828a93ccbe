"""The open-loop driver: fires each operation at its due instant through the
client's handle and never waits for a reply.

Latency is timed from the instant an operation was DUE to the instant its
future resolved, so a stall lengthens the latencies of the operations due
during it; how late the driver itself fired (fired - due) is kept beside it,
so that a starved generator is not read as a fast server.  One thread fires;
completions land through done-callbacks on whatever thread resolves them.
"""

from __future__ import annotations

import json
import math
import threading
import time
from typing import Callable, List, Sequence, Tuple

from .reference import OK, Record
from .traffic import Op


class Completion:
    """What the driver knows of one operation: the record the reference
    reads, plus the instants the metrics read."""
    __slots__ = ("op", "due", "record")

    def __init__(self, op: Op, due: float):
        self.op, self.due = op, due
        self.record = Record(op.seq, op.kind, op.key, op.value, math.nan)

    @property
    def latency_s(self) -> float:
        return self.record.returned - self.due

    @property
    def late_s(self) -> float:
        """How late the driver fired against its schedule."""
        return self.record.invoked - self.due


def command_of(op: Op) -> str:
    if op.kind == "w":
        return json.dumps({"op": "set", "k": op.key, "v": op.value})
    return json.dumps({"op": "get", "k": op.key})


def drive(schedule: Sequence[Op],
          send: Callable[[Op, str], "object"],
          window_s: float, drain_s: float
          ) -> Tuple[List[Completion], float]:
    """Fire ``schedule`` open loop; ``send(op, command)`` returns a future
    (``add_done_callback``, ``exception``, ``result``).  After the window,
    wait at most ``drain_s`` for stragglers.
    Returns one Completion per operation and the window's start on the
    ``time.perf_counter`` clock; what is unresolved at the end keeps
    ``returned = inf`` and the outcome FAILED, and a reply that comes
    later is not counted."""
    clock = time.perf_counter
    done = threading.Event()
    lock = threading.Lock()
    left = [len(schedule)]
    closed = [False]
    out: List[Completion] = []
    t0 = clock()

    def settle(c: Completion, fut) -> None:
        now = clock()
        with lock:
            if closed[0]:
                return
            rec = c.record
            exc = fut.exception() if fut is not None else None
            if fut is not None and exc is None:
                rec.answer, rec.outcome = fut.result(), OK
            elif exc is not None:
                rec.error = f"{type(exc).__name__}: {exc}"[:200]
            rec.returned = now
            left[0] -= 1
            if left[0] == 0:
                done.set()

    for op in schedule:
        due = t0 + op.due_s
        while (now := clock()) < due:
            time.sleep(min(due - now, 0.002))
        c = Completion(op, due)
        out.append(c)
        c.record.invoked = clock()
        try:
            fut = send(op, command_of(op))
        except Exception as e:          # refused before a future existed
            c.record.error = f"{type(e).__name__}: {e}"[:200]
            settle(c, None)
            continue
        fut.add_done_callback(lambda f, c=c: settle(c, f))
    end = t0 + window_s + drain_s
    while not done.is_set() and (now := clock()) < end:
        done.wait(min(0.05, end - now))
    with lock:
        closed[0] = True
    return out, t0
