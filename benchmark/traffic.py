"""The one traffic generator: a traffic file of parameters plus ``--seed``
gives the operations of a run.  A new mix is a new file under ``traffic/``,
never new code.

Every seed gets the same amount of work in another order, so that runs
differ by the system and not by the draw: the number of operations is
``round(rate_ops_s * seconds)`` (Poisson arrivals conditioned on their
count are uniform order statistics over the window), the read/write split
is exact, key ranks are a systematic sample of the key distribution
(each hot rank gets its expected count to within one; the seed moves the
tail), and the targets are balanced over the members.  The seed draws the
arrival instants, every permutation and the sample's offset.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from typing import List, Optional

import numpy as np

FIELDS = ("read_share", "key_space", "key_bytes", "key_dist", "value_bytes",
          "arrival", "rate_ops_s", "targets", "clients")
_FNV_OFFSET, _FNV_PRIME = 0xCBF29CE484222325, 0x100000001B3


def _fnv1a64_vec(x: np.ndarray) -> np.ndarray:
    """FNV-1a over the eight bytes of each element (YCSB's key scrambler)."""
    x = x.astype(np.uint64)
    h = np.full(x.shape, _FNV_OFFSET, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (x & np.uint64(0xFF))) * np.uint64(_FNV_PRIME)
            x = x >> np.uint64(8)
    return h


def load_traffic(path: str) -> dict:
    with open(path) as f:
        t = json.load(f)
    missing = [k for k in FIELDS if k not in t]
    if missing:
        raise ValueError(f"{path}: missing {missing}")
    if t["arrival"] != "poisson":
        raise ValueError(f"{path}: arrival {t['arrival']!r} is not known")
    if t["targets"] != "all-members":
        raise ValueError(f"{path}: targets {t['targets']!r} is not known")
    if t["clients"] != "open-loop":
        raise ValueError(f"{path}: clients {t['clients']!r} is not known")
    if t["key_dist"]["kind"] not in ("uniform", "zipfian"):
        raise ValueError(f"{path}: key_dist {t['key_dist']!r} is not known")
    if not 0.0 <= t["read_share"] <= 1.0 or t["rate_ops_s"] <= 0:
        raise ValueError(f"{path}: read_share or rate_ops_s out of range")
    return t


@dataclass(frozen=True)
class Op:
    seq: int
    due_s: float            # offset from the window's start
    kind: str               # "r" | "w"
    key: str
    group: int              # index into the configuration's open groups
    target: int             # member whose stub takes the operation
    value: Optional[str]    # unique to the write; None for a read


def key_cdf(key_space: int, dist: dict) -> np.ndarray:
    if dist["kind"] == "uniform":
        w = np.full(key_space, 1.0 / key_space)
    else:
        w = 1.0 / np.power(np.arange(1, key_space + 1, dtype=np.float64),
                           float(dist["constant"]))
        w /= w.sum()
    return np.cumsum(w)


def key_name(key_id: int, key_bytes: int) -> str:
    """``k0001234`` (8 bytes, etcd's benchmark) or ``user`` + 19 digits
    (23 bytes, YCSB's ``user<hash>``)."""
    prefix = "k" if key_bytes <= 12 else "user"
    return prefix + str(key_id).zfill(key_bytes - len(prefix))


def value_for(seed: int, seq: int, value_bytes: int) -> str:
    """A value no other write of any run carries, padded to size."""
    head = f"{seed}.{seq}."
    return head + "x" * max(0, value_bytes - len(head))


def make_schedule(traffic: dict, seed: int, seconds: float, n_groups: int,
                  n_members: int = 3,
                  rate_ops_s: Optional[float] = None) -> List[Op]:
    """The operations due in a window of ``seconds``, in order of due
    time.  ``rate_ops_s`` overrides the file's rate (the knee sweep)."""
    rate = float(traffic["rate_ops_s"] if rate_ops_s is None else rate_ops_s)
    n = max(1, int(round(rate * seconds)))
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    due = np.sort(rng.random(n)) * seconds
    n_reads = int(round(traffic["read_share"] * n))
    kinds = np.array(["r"] * n_reads + ["w"] * (n - n_reads))
    rng.shuffle(kinds)
    cdf = key_cdf(int(traffic["key_space"]), traffic["key_dist"])
    u = (np.arange(n) + rng.random()) / n
    ranks = np.minimum(np.searchsorted(cdf, u), len(cdf) - 1)
    rng.shuffle(ranks)
    if traffic["key_dist"].get("scrambled"):
        key_ids = _fnv1a64_vec(ranks) % np.uint64(traffic["key_space"])
    else:
        key_ids = ranks.astype(np.uint64)
    groups = _fnv1a64_vec(key_ids ^ np.uint64(0x9E3779B97F4A7C15)) \
        % np.uint64(n_groups)
    targets = np.arange(n) % n_members
    rng.shuffle(targets)
    kb, vb = int(traffic["key_bytes"]), int(traffic["value_bytes"])
    return [Op(i, float(due[i]), str(kinds[i]), key_name(int(key_ids[i]), kb),
               int(groups[i]), int(targets[i]),
               value_for(seed, i, vb) if kinds[i] == "w" else None)
            for i in range(n)]
