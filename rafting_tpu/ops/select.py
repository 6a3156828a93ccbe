"""Dense addressing along a small static axis.

Every index axis the step addresses by data is small and static: the L
slots of a log ring, the K slots of the read FIFO, the P peer planes.  A
``take_along_axis`` or an ``.at[rows, slot].set`` with per-group indices
lowers to an XLA gather or scatter, and a TPU walks those one element at
a time (9.7 ns a gathered element, 5.8 ns a scattered one on a v5e: 35
such fusions were 8 of the 11 ms of a 10,000-lane step).  A compare of
the axis' own ``arange`` against the wanted slot, and a select, is a
vector pass over an array the step holds anyway: the same values, for
every backend and size.

ops must not import the step module, so these live here and the ring
primitives of core/step.py and the read barrier of ops/quorum.py are
written with them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.types import I32

Array = jax.Array


def take_slots(arr: Array, slot: Array) -> Array:
    """``arr[g, slot[g, k]]`` for ``arr`` [G, L] and ``slot`` [G, K] with
    values in [0, L): one [G, K, L] compare that the compiler fuses into
    its reduce over L (exactly one position hits, so the sum is the
    element)."""
    j = jnp.arange(arr.shape[1], dtype=I32)
    hit = slot[:, :, None] == j[None, None, :]
    return jnp.where(hit, arr[:, None, :], 0).sum(axis=2, dtype=arr.dtype)


def take_plane(field_pg: Array, peer: Array) -> Array:
    """``field_pg[peer[g], g]`` for ``field_pg`` [P, G] or [P, G, K] and
    ``peer`` [G] in [0, P): a where-chain over the P planes."""
    if field_pg.ndim == 3:
        peer = peer[:, None]
    out = field_pg[0]
    for p in range(1, field_pg.shape[0]):
        out = jnp.where(peer == p, field_pg[p], out)
    return out
