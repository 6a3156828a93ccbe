"""Packed pytrees: every leaf of a pytree in a few flat buffers, so that a
tree of some eighty small planes crosses between host and device in two
transfers instead of one per leaf.

The engine's planes are ``int32`` and ``bool`` only.  A :class:`Layout`
puts the ``int32`` leaves, ravelled and end to end, into ``int32`` word
buffers and the ``bool`` leaves into ``uint8`` flag buffers (a byte per
flag: the host then sees ``bool`` planes as views, with the dtype its
masks and its codec expect, at a quarter of the bytes a widened flag would
cross with).  A buffer holds whole leaves and is closed once another leaf
would take it past ``CHUNK_BYTES``: at the sizes a call's fixed cost
matters (a transfer of a few KB and one of 0.5 MB both cost 0.6 ms on a
TPU v5e) that is one word buffer and one flag buffer, and a tree of tens of
MB crosses in pieces of a few MB, which the runtime moves side by side (one
48 MB fetch took 55 ms there, twelve of 4 MB 4 ms).  The layout is derived
from the tree's own structure (``jax.tree.flatten`` order; a ``None``
subtree has no leaves and so no room), never from a list of field names: a
field added to ``Messages`` or ``StepInfo`` finds its place by itself.

Both directions work on either side of the boundary.  On the host
``unpack`` returns numpy **views** into the buffers (no copy: fill an
``alloc()``-ed set in place, or read a fetched set where it lies); under
``jit`` it is static slices and reshapes, and ``pack`` one concatenate of
the ravelled leaves per buffer.
"""

from __future__ import annotations

from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

WORD = np.dtype(np.int32)
FLAG = np.dtype(np.uint8)
BOOL = np.dtype(np.bool_)

# A buffer is closed when the next leaf would take it past this many bytes
# (a larger leaf has a buffer to itself).
CHUNK_BYTES = 4 << 20


class Layout:
    """Where each leaf of one pytree shape lies, and in which buffer.

    Built from anything ``jax.tree.flatten`` takes whose leaves carry
    ``shape`` and ``dtype`` (arrays, or what ``jax.eval_shape`` returns).
    ``buffers`` is the (dtype, length) of each buffer, in the order every
    method takes and returns them.  Hashable and comparable by structure,
    so a jitted function can take it as a static argument."""

    __slots__ = ("treedef", "slots", "buffers", "_hash")

    def __init__(self, tree: Any):
        leaves, self.treedef = jax.tree.flatten(tree)
        slots, kinds, sizes = [], [], []
        filling = {WORD: None, FLAG: None}      # the open buffer of a kind
        for leaf in leaves:
            dt = np.dtype(leaf.dtype)
            if dt not in (WORD, BOOL):
                raise TypeError(
                    f"a packed leaf is int32 or bool, not {dt} "
                    f"(shape {tuple(leaf.shape)})")
            kind = FLAG if dt == BOOL else WORD
            size = int(np.prod(leaf.shape, dtype=np.int64))
            b = filling[kind]
            if b is None or (sizes[b] and (sizes[b] + size) * kind.itemsize
                             > CHUNK_BYTES):
                b = filling[kind] = len(kinds)
                kinds.append(kind)
                sizes.append(0)
            slots.append((b, sizes[b], size, tuple(leaf.shape)))
            sizes[b] += size
        self.slots: Tuple[Tuple[int, int, int, tuple], ...] = tuple(slots)
        self.buffers: Tuple[Tuple[np.dtype, int], ...] = tuple(
            zip(kinds, sizes))
        # Hashed on every call of a function that takes it statically.
        self._hash = hash((self.treedef, self.slots, self.buffers))

    def __eq__(self, other):
        return self is other or (
            isinstance(other, Layout) and self.treedef == other.treedef
            and self.slots == other.slots and self.buffers == other.buffers)

    def __hash__(self):
        return self._hash

    def alloc(self) -> Tuple[np.ndarray, ...]:
        """Fresh zeroed buffers on the host."""
        return tuple(np.zeros(n, dt) for dt, n in self.buffers)

    def unpack(self, buffers: Sequence) -> Any:
        """The tree whose leaves lie in ``buffers``.  Numpy buffers give
        views that share their memory; traced or device buffers give
        slices."""
        leaves = []
        for b, off, size, shape in self.slots:
            flat = buffers[b][off:off + size]
            if self.buffers[b][0] == FLAG:
                flat = flat.view(BOOL) if isinstance(flat, np.ndarray) \
                    else flat != 0
            leaves.append(flat.reshape(shape))
        return jax.tree.unflatten(self.treedef, leaves)

    def pack(self, tree: Any) -> tuple:
        """Buffers holding ``tree``'s leaves.  Numpy leaves are copied
        into fresh host buffers; anything else is concatenated where it
        lives (under ``jit``: on the device)."""
        leaves = self.treedef.flatten_up_to(tree)
        if all(isinstance(leaf, np.ndarray) for leaf in leaves):
            buffers = self.alloc()
            for view, leaf in zip(jax.tree.leaves(self.unpack(buffers)),
                                  leaves):
                view[...] = leaf
            return buffers
        parts = [[] for _ in self.buffers]
        for (b, _, _, _), leaf in zip(self.slots, leaves):
            parts[b].append(jnp.ravel(leaf).astype(self.buffers[b][0]))
        return tuple(jnp.concatenate(p) for p in parts)
