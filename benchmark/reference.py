"""The plain reference: a sequential key-value model of what the
configurations guarantee, and the comparison that decides ``correct``.

It imports nothing of the program.  It is given the history the driver
recorded on the client side (each operation's invoke and return instants on
one clock, its outcome and its answer) and each replica's final state, and
holds the system to the guarantees the configuration files state:

(a) the replicas are identical on every key touched;
(b) a key's final value belongs to a write that was acknowledged or whose
    outcome is unknown, and no acknowledged write was invoked after that
    write returned;
(c) a read returns the value of a write invoked before the read returned and
    not superseded before the read was invoked (no write acknowledged before
    the read's invocation was itself invoked after the returned write had
    returned), or the key's value before the window and then no write to the
    key was acknowledged before the read was invoked;
(d) every acknowledged write is on all replicas or superseded there: per
    replica, (b) holds, and a key with an acknowledged write is not at its
    value from before the window;
(e) an acknowledged write's reply is the value written.

Every pass is linear in the operations of a key (one sort by return time).
These are necessary conditions of linearizability, not a proof of it: a full
Wing & Gong search is exponential in the operations that overlap on one key,
and under Zipfian traffic at a commit latency of several seconds some
hundreds overlap on the hottest key.
"""

from __future__ import annotations

import bisect
import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional

OK, FAILED = "ok", "failed"    # FAILED: refused, errored or unresolved


@dataclass
class Record:
    """One operation as the client saw it."""
    seq: int
    kind: str                      # "r" | "w"
    key: str
    value: Optional[str]           # what a write wrote
    invoked: float
    returned: float = math.inf     # inf while unresolved
    outcome: str = FAILED
    answer: object = None          # a read's value, a write's reply
    error: str = ""


@dataclass
class Verdict:
    """Each number compared, beside its limit (all limits are 0: the
    comparison is exact)."""
    numbers: Dict[str, int] = field(default_factory=dict)
    examples: List[str] = field(default_factory=list)
    LIMIT = 0

    def add(self, name: str, what: str) -> None:
        self.numbers[name] += 1
        if len(self.examples) < 8:
            self.examples.append(f"{name}: {what}")

    @property
    def correct(self) -> bool:
        return all(v <= self.LIMIT for v in self.numbers.values())

    def lines(self) -> List[str]:
        return [f"{k}={v} limit={self.LIMIT}" for k, v in self.numbers.items()]


NUMBERS = ("replica_divergent_keys", "final_value_violations",
           "lost_acked_writes", "stale_or_unknown_reads",
           "write_reply_mismatches")


def check(history: List[Record], replicas: List[Dict[str, object]],
          initial: Optional[Dict[str, object]] = None) -> Verdict:
    """``replicas``: each replica's key -> value over the keys touched
    (absent keys left out).  ``initial``: the keys' values before the
    window (absent keys left out; empty for a fresh cluster)."""
    initial = initial or {}
    v = Verdict({k: 0 for k in NUMBERS})
    by_key: Dict[str, List[Record]] = {}
    for r in history:
        by_key.setdefault(r.key, []).append(r)

    for key, ops in by_key.items():
        writes = [o for o in ops if o.kind == "w"]
        acked = sorted((o for o in writes if o.outcome == OK),
                       key=lambda o: o.returned)
        by_value = {o.value: o for o in writes}
        start = initial.get(key)
        for o in acked:
            if o.answer != o.value:
                v.add("write_reply_mismatches", f"{key} seq {o.seq}")
        # (a)
        finals = [rep.get(key) for rep in replicas]
        if any(f != finals[0] for f in finals[1:]):
            v.add("replica_divergent_keys", f"{key}: {_short(finals)}")
        # (b), (d): per replica
        last_invoked = max((o.invoked for o in acked), default=-math.inf)
        for i, fin in enumerate(finals):
            if fin == start and fin not in by_value:
                if acked:
                    v.add("lost_acked_writes",
                          f"{key}: replica {i} still at its value from "
                          f"before the window, {len(acked)} acknowledged")
                continue
            w = by_value.get(fin)
            if w is None:
                v.add("final_value_violations",
                      f"{key}: replica {i} holds a value no write wrote")
            elif last_invoked > _ret(w):
                v.add("lost_acked_writes",
                      f"{key}: replica {i} ends on seq {w.seq}, which "
                      f"returned before an acknowledged write was invoked")
        # (c)
        returns = [o.returned for o in acked]
        latest_invoke = []          # prefix max of invoke over acked-by-return
        m = -math.inf
        for o in acked:
            m = max(m, o.invoked)
            latest_invoke.append(m)
        for r in ops:
            if r.kind != "r" or r.outcome != OK:
                continue
            n_before = bisect.bisect_left(returns, r.invoked)
            if r.answer == start and r.answer not in by_value:
                if n_before:
                    v.add("stale_or_unknown_reads",
                          f"{key} seq {r.seq}: answered the value from "
                          f"before the window after {n_before} acknowledged "
                          f"write(s)")
                continue
            w = by_value.get(r.answer)
            if w is None:
                v.add("stale_or_unknown_reads",
                      f"{key} seq {r.seq}: answered a value no write wrote")
            elif w.invoked > r.returned:
                v.add("stale_or_unknown_reads",
                      f"{key} seq {r.seq}: answered seq {w.seq}, invoked "
                      f"after the read returned")
            elif n_before and latest_invoke[n_before - 1] > _ret(w):
                v.add("stale_or_unknown_reads",
                      f"{key} seq {r.seq}: answered seq {w.seq}, superseded "
                      f"before the read was invoked")
    return v


def _ret(o: Record) -> float:
    """When a write stopped being able to take effect: its return if it was
    acknowledged, never if its outcome is unknown (a write that failed on
    the client's side may still commit)."""
    return o.returned if o.outcome == OK else math.inf


def _short(values) -> str:
    return str([None if x is None else str(x)[:24] for x in values])
