"""Pallas TPU kernel for the quorum-commit scan — the flagship hot op.

Computes, for every Raft group at once, the leader's commit advancement
(reference Leader.tryCommit + Leadership.majorIndices,
context/member/Leader.java:247-280, Leadership.java:116-130), generalized
to the §6 membership plane:

  1. quorum index = majority-order statistic of the (group x peer) match
     matrix over the group's VOTER bitmask (self slot pre-filled with the
     leader's own last index).  Non-voter slots (learners, removed peers)
     sort below every real match and the per-group majority position is
     popcount(voters) // 2 + 1 — the fixed-majority order statistic is
     the degenerate full-membership case;
  2. JOINT configs (voters_new nonzero) take the MINIMUM of the two
     sets' order statistics: an entry commits only with a quorum in both
     C_old and C_new (Raft §6);
  3. the commit-only-own-term rule (Raft §5.4.2, Leader.java:256-261),
     reduced to ``quorum_idx >= own_from`` — terms are monotone along the
     log and ``own_from`` (RaftState) is the first index of the leader's
     current term, pinned at election win by the §8 no-op;
  4. the full-replication lane (Leader.java:260 ``fullIndex``) takes the
     min over VOTER slots only — a learner hauling itself up from a
     snapshot must never stall the lane (its match says nothing about
     what electable nodes hold);
  5. masked monotone update of commitIndex.

Layout: group-major arrays are reshaped to [rows, 128] so the group axis
rides the TPU lanes; the peer axis (3-10) is a static unroll of an
odd-even transposition sorting network on [rows, 128] tiles in VMEM (one
network per voter set; the joint pass reuses the same plane loads).

``quorum_commit`` dispatches to the Pallas kernel or the pure-jnp masked
reference (identical semantics, parity-tested in tests/test_ops.py).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.types import I32
from .select import take_slots

BLOCK_ROWS = 8          # sublanes per grid step
LANES = 128

# NOTE: no module-level jnp constants here — this module is first
# imported lazily INSIDE node_step's jit trace, where array creation
# would capture a tracer and leak it across traces.
_I32_MAX = (1 << 31) - 1


def _bits(mask: jax.Array, P: int) -> jax.Array:
    """[G] peer bitmask -> [G, P] bool (local copy of core.step.mask_bits;
    ops must not import the step module)."""
    return ((mask[:, None] >> jnp.arange(P, dtype=I32)[None, :]) & 1) > 0


# ---------------------------------------------------------------- reference --

def masked_order_stat(match: jax.Array, bits: jax.Array) -> jax.Array:
    """Majority-order statistic of ``match`` [G, P] over ``bits`` [G, P]:
    the largest x such that at least popcount//2+1 of the masked slots
    hold match >= x.  Non-members become -1 (below any real match, which
    is >= 0), so after an ascending sort the statistic sits at position
    P - majority.  An empty mask yields -1 (no quorum ever).

    The per-lane position select is a STATIC where-chain over the P
    columns, not a take_along_axis: a [G, 1] dynamic gather lowers to a
    per-row scatter/gather loop on the CPU backend and measured ~2x on
    the whole step at 32k groups; P compares+selects are pure vector
    ops."""
    P = match.shape[1]
    sm = jnp.sort(jnp.where(bits, match, jnp.asarray(-1, I32)), axis=1)
    nv = bits.sum(axis=1).astype(I32)
    pos = jnp.clip(P - (nv // 2 + 1), 0, P - 1)
    q = sm[:, 0]
    for p in range(1, P):
        q = jnp.where(pos == p, sm[:, p], q)
    return q


def quorum_commit_ref(match_full, own_from, last, commit, can_lead,
                      voters, voters_new) -> jax.Array:
    """Pure-jnp reference (exactly core/step.py phase 10).

    Two commit lanes, the reference's tryCommit (Leader.java:256-261)
    membership-generalized:

    * quorum lane — the masked majority order statistic (JOINT: min over
      both voter sets), gated by the commit-only-own-term rule
      (``quorum_idx >= own_from``);
    * full-replication lane — the MINIMUM over VOTER slots (both sets
      while joint; learners excluded — Leader.java:260 ``fullIndex``): an
      entry replicated on every voter is on every electable future
      leader's log, so committing it needs no own-term fence.  This is
      what lets a fully-replicated prior-term suffix commit on a
      ring-full lane where the §8 no-op could not be appended.
    """
    P = match_full.shape[1]
    vb = _bits(voters, P)
    q = masked_order_stat(match_full, vb)
    nb = _bits(voters_new, P)
    qn = masked_order_stat(match_full, nb)
    joint = voters_new != 0
    q = jnp.where(joint, jnp.minimum(q, qn), q)
    full = jnp.where(vb | nb, match_full,
                     jnp.asarray(_I32_MAX, I32)).min(axis=1)
    can = can_lead & (q > commit) & (q >= own_from) & (q <= last)
    can_full = can_lead & (full > commit) & (full <= last)
    return jnp.maximum(jnp.where(can, q, commit),
                       jnp.where(can_full, full, commit))


# ------------------------------------------------------------------- kernel --

def _kernel(P: int, match_ref, own_from_ref, commit_ref, last_ref, lead_ref,
            vot_ref, new_ref, out_ref):
    planes_raw = [match_ref[p] for p in range(P)]
    vot = vot_ref[...]
    new = new_ref[...]

    def popcount(word):
        n = (word >> 0) & 1
        for p in range(1, P):
            n = n + ((word >> p) & 1)
        return n

    def order_stat(word):
        # Mask non-members below every real match, run the odd-even
        # transposition network, then select the per-lane majority plane
        # (pos = P - (popcount//2 + 1), a static unroll of P selects).
        planes = [jnp.where(((word >> p) & 1) > 0, planes_raw[p], -1)
                  for p in range(P)]
        for _ in range(P):
            for i in range(0, P - 1, 2):
                lo = jnp.minimum(planes[i], planes[i + 1])
                hi = jnp.maximum(planes[i], planes[i + 1])
                planes[i], planes[i + 1] = lo, hi
            for i in range(1, P - 1, 2):
                lo = jnp.minimum(planes[i], planes[i + 1])
                hi = jnp.maximum(planes[i], planes[i + 1])
                planes[i], planes[i + 1] = lo, hi
        nv = popcount(word)
        pos = P - (nv // 2 + 1)
        pos = jnp.clip(pos, 0, P - 1)
        q = jnp.where(pos == 0, planes[0], 0)
        for p in range(1, P):
            q = jnp.where(pos == p, planes[p], q)
        return q

    q = order_stat(vot)
    qn = order_stat(new)
    q = jnp.where(new != 0, jnp.minimum(q, qn), q)
    both = vot | new
    big = jnp.asarray((1 << 31) - 1, jnp.int32)
    full = jnp.where(((both >> 0) & 1) > 0, planes_raw[0], big)
    for p in range(1, P):
        full = jnp.minimum(
            full, jnp.where(((both >> p) & 1) > 0, planes_raw[p], big))

    commit = commit_ref[...]
    last = last_ref[...]
    lead = lead_ref[...] != 0
    can = lead & (q > commit) & (q >= own_from_ref[...]) & (q <= last)
    can_full = lead & (full > commit) & (full <= last)
    out_ref[...] = jnp.maximum(jnp.where(can, q, commit),
                               jnp.where(can_full, full, commit))


def _pad_rows(a: np.ndarray | jax.Array, G: int, Gp: int, fill=0):
    if Gp == G:
        return a
    pad = [(0, Gp - G)] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, pad, constant_values=fill)


@functools.partial(jax.jit, static_argnums=(3,))
def quorum_commit_pallas(match_full, own_from, state_vec,
                         interpret: bool = False) -> jax.Array:
    """Pallas path.  ``state_vec`` packs (commit, last, can_lead, voters,
    voters_new) as a [5, G] i32 array (can_lead nonzero = active leader
    lane; voters_new nonzero = joint config)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G, P = match_full.shape
    commit, last, can_lead = state_vec[0], state_vec[1], state_vec[2]
    voters, voters_new = state_vec[3], state_vec[4]

    step = BLOCK_ROWS * LANES
    Gp = (G + step - 1) // step * step
    R = Gp // LANES

    def rows(v, fill=0):
        return _pad_rows(v, G, Gp, fill).reshape(R, LANES)

    match_t = _pad_rows(match_full, G, Gp).T.reshape(P, R, LANES)

    grid = (R // BLOCK_ROWS,)
    vec = lambda: pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, P),
        out_shape=jax.ShapeDtypeStruct((R, LANES), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((P, BLOCK_ROWS, LANES), lambda i: (0, i, 0)),
            vec(), vec(), vec(), vec(), vec(), vec(),
        ],
        out_specs=vec(),
        interpret=interpret,
    )(match_t, rows(own_from, fill=1), rows(commit), rows(last),
      rows(can_lead), rows(voters), rows(voters_new))
    return out.reshape(Gp)[:G]


# ------------------------------------------------------------ read barrier --

def read_barrier_release(voters, voters_new, me, read_evid, rq_stamp,
                         rq_head, rq_len, rq_n, reach=None):
    """ReadIndex barrier for every group at once: how many pending read
    batches (FIFO from ``rq_head``) have a confirmed leadership quorum.

    A batch stamped at tick ``s`` releases once the set {self} ∪ {p :
    read_evid[g, p] >= s} covers a majority of the VOTERS — and, while
    joint, of voters_new too (§6: a leadership confirmation is a joint
    decision like any other quorum).  Self counts only if self is a
    voter; learner evidence never counts.  Release is prefix-monotone by
    construction — stamps increase along the FIFO and evidence is a
    per-peer maximum, so a releasable batch implies every older one is
    releasable — but the cumulative-AND guard below keeps FIFO order
    even if a caller hands in unordered stamps.

    ``reach`` ([G] int32): the carried lease (core/step.py phase 6b).
    Evidence stored at tick ``e`` then confirms batches stamped up to
    ``e + reach``: the set above becomes {p : read_evid[g, p] + reach[g]
    >= s}, over real evidence only (0 stays "none"), and a third result
    says how many batches evidence of its own tick alone (reach 0) would
    have released.

    Returns ``(n_rel [G] int32, n_served [G] int32)``: batches released
    and the total individual reads inside them (with ``reach``: ``(n_rel,
    n_served, n_rel_own)``).  This lives beside the
    commit kernel because it is the same shape of op — a quorum order
    statistic over the peer axis feeding a masked monotone update — and
    the Pallas treatment, if ever needed, would tile identically.
    """
    G, K = rq_stamp.shape
    P = read_evid.shape[1]
    j = jnp.arange(K, dtype=I32)[None, :]                       # FIFO pos
    slot = jnp.remainder(rq_head[:, None] + j, K)               # [G, K]
    st = take_slots(rq_stamp, slot)
    n = take_slots(rq_n, slot)
    pending = j < rq_len[:, None]
    # Evidence 0 means "none this leadership"; stamps are >= 1 (the tick
    # clock starts at 1), so the comparison needs no extra guard.
    self_hot = (jnp.arange(P, dtype=I32) == me)[None, None, :]
    vb = _bits(voters, P)[:, None, :]
    nb = _bits(voters_new, P)[:, None, :]

    def released(covers):
        flags = (covers[:, None, :] >= st[:, :, None]) | self_hot  # [G,K,P]
        ok_v = ((flags & vb).sum(axis=2)
                >= vb.sum(axis=2) // 2 + 1)                     # [G, K]
        ok_n = (flags & nb).sum(axis=2) >= nb.sum(axis=2) // 2 + 1
        ok = pending & ok_v & ((voters_new == 0)[:, None] | ok_n)
        return pending & (jnp.cumsum((~ok).astype(I32), axis=1) == 0)

    count = lambda rel: rel.sum(axis=1).astype(I32)
    if reach is None:
        rel = released(read_evid)
        return count(rel), (rel * n).sum(axis=1).astype(I32)
    rel = released(jnp.where(read_evid > 0, read_evid + reach[:, None], 0))
    return (count(rel), (rel * n).sum(axis=1).astype(I32),
            count(released(read_evid)))


def contact_quorum(voters, voters_new, me, heard, since):
    """CheckQuorum contact test for every group at once: has a majority of
    the VOTERS — and, while joint, of ``voters_new`` too (§6: leadership
    liveness is a joint decision like any other quorum) — been heard from
    at/after the window anchor ``since``?

    ``heard`` is [G, P] (own-clock tick of the last valid inbound RPC per
    peer), ``since`` [G].  Self always counts (a node is always in
    contact with itself — the single-voter group is the degenerate case);
    learner contact never does.  The same masked-popcount shape as
    :func:`read_barrier_release` — only the per-peer flag differs.
    Returns [G] bool.
    """
    P = heard.shape[1]
    self_hot = (jnp.arange(P, dtype=I32) == me)[None, :]
    flags = (heard >= since[:, None]) | self_hot                # [G, P]
    vb = _bits(voters, P)
    nb = _bits(voters_new, P)
    ok_v = (flags & vb).sum(axis=1) >= vb.sum(axis=1) // 2 + 1
    ok_n = (flags & nb).sum(axis=1) >= nb.sum(axis=1) // 2 + 1
    return ok_v & ((voters_new == 0) | ok_n)


def quorum_commit(cfg, match_full, log, commit, own_from, can_lead,
                  voters, voters_new):
    """Dispatch: the Pallas kernel when ``cfg.use_pallas``, else inline
    jnp (the default; the two are semantically identical)."""
    if getattr(cfg, "use_pallas", False):
        import os
        state_vec = jnp.stack([commit, log.last, can_lead.astype(I32),
                               voters, voters_new])
        # Interpret mode is a CPU test affordance only; every other
        # backend gets the compiled lowering (an unsupported one then
        # fails LOUDLY instead of silently running the interpreter at
        # 1000x cost).  RAFT_PALLAS_INTERPRET=0/1 overrides either way.
        env = os.environ.get("RAFT_PALLAS_INTERPRET", "").strip().lower()
        if env:
            interpret = env not in ("0", "false", "no", "off")
        else:
            interpret = jax.default_backend() == "cpu"
        return quorum_commit_pallas(match_full, own_from, state_vec,
                                    interpret)
    return quorum_commit_ref(match_full, own_from, log.last, commit,
                             can_lead, voters, voters_new)
