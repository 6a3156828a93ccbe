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


# ---------------------------------------------------------------------------
# Column form: a tree of [P, G, ...] message planes as the columns that hold
# a message.  A step of a large node moves a handful of (peer, group)
# columns of its ~50 message planes, and the slices arrive from the wire as
# columns already; the dense planes exist for the step program alone, which
# expands the columns to them and compacts its outbox from them on the
# device (core/step.py node_step_columns).
# ---------------------------------------------------------------------------

# K: the most columns one peer row may hold and still cross in column form;
# a row beyond it sends the whole step over the dense path, nothing is cut.
# Chosen once, on a TPU v5e at 100,000 lanes x 3 peers (PERF.md, PR 35): the
# largest power of two at which (a) the column pair stays within a
# transfer's fixed-cost regime (0.5 MB: K <= 1,024; 253 KB here, 0.6-0.7 ms
# up like a few bytes) and (b) BOTH forms of the column step cost the
# device less than the packed step they replace (6.17 ms a call there):
# with the columns going in / with the dense operand going in 4.59 / 5.47
# ms at 256, 5.07 / 5.71 at 512, 6.03 / 6.32 at 1,024, whose second form
# is slower than what it replaces.
COLUMNS = 512

# Lanes per block of the device's search for the occupied columns.
_BLOCK = 128


def _field_name(path) -> str:
    key = path[-1] if path else None
    return str(getattr(key, "name", getattr(key, "key", key)))


class ColumnLayout:
    """Where each leaf of one tree of ``[P, G, ...]`` planes lies in the
    column form, per peer row: a count ``n``, the lanes ``cols [K]`` of the
    occupied columns in ascending order (``G`` from ``n`` on), and every
    leaf's values at them, the ``int32`` leaves side by side in ``words
    [K, W]`` and the ``bool`` leaves in ``flags [K, F]`` (the ``[P, G]``
    leaves first, then the wider ones, each group in the tree's order).
    Two buffers: the word buffer ``[P, 1 + K + K * W]`` (count, lanes,
    words) and the flag buffer ``[P, K * F]`` (a byte a flag).  A column is
    OCCUPIED when a flag leaf named ``*_valid`` is set in it (every
    ``[P, G]`` flag leaf, in a tree that has none so named): what else a
    column of the dense planes holds there crosses with it, what it holds
    elsewhere does not cross.

    On the device the dense planes are addressed STACKED
    (:meth:`stack`): the ``[P, G]`` leaves of a kind as one ``[n, P, G]``
    array, so that they share ONE scatter and ONE gather (an index row a
    leaf, peer and column) where each would take its own: a scatter or a
    gather of a few hundred rows costs the chip some 50 us whatever it
    moves, and there are forty leaves.

    Derived from the tree's own structure, as :class:`Layout` is: a field
    added to ``Messages`` finds its place by itself.  ``K`` is the module's
    ``COLUMNS`` when the layout is built."""

    __slots__ = ("treedef", "names", "slots", "occupancy", "P", "G", "K",
                 "W", "F", "Ws", "Fs", "buffers", "_key", "_hash")

    def __init__(self, tree: Any):
        flat, self.treedef = jax.tree_util.tree_flatten_with_path(tree)
        self.P, self.G = (int(d) for d in flat[0][1].shape[:2])
        self.K = int(COLUMNS)
        names, shapes = [], []
        for path, leaf in flat:
            dt = np.dtype(leaf.dtype)
            if dt not in (WORD, BOOL) or leaf.shape[:2] != (self.P, self.G):
                raise TypeError(
                    f"a column leaf is an int32 or bool [P, G, ...] plane, "
                    f"not {dt} {tuple(leaf.shape)}")
            names.append(_field_name(path))
            shapes.append((FLAG if dt == BOOL else WORD,
                           tuple(int(d) for d in leaf.shape[2:])))
        # Offsets: the [P, G] leaves of a kind first (their offset is
        # their place in the kind's stack), then the wider ones.
        width = {WORD: 0, FLAG: 0}
        offs = [0] * len(shapes)
        for wide in (False, True):
            for i, (kind, trail) in enumerate(shapes):
                if bool(trail) == wide:
                    offs[i] = width[kind]
                    width[kind] += int(np.prod(trail, dtype=np.int64))
            if not wide:
                self.Ws, self.Fs = width[WORD], width[FLAG]
        self.names: Tuple[str, ...] = tuple(names)
        self.slots: Tuple[Tuple[np.dtype, int, tuple], ...] = tuple(
            (kind, off, trail) for (kind, trail), off in zip(shapes, offs))
        self.W, self.F = width[WORD], width[FLAG]
        flags = [(name, off) for name, (kind, off, trail)
                 in zip(names, self.slots) if kind == FLAG and not trail]
        named = [off for name, off in flags if name.endswith("_valid")]
        self.occupancy: Tuple[int, ...] = tuple(
            named or [off for _, off in flags])
        self.buffers = ((WORD, (self.P, 1 + self.K + self.K * self.W)),
                        (FLAG, (self.P, self.K * self.F)))
        # Compared and hashed on every call of a function that takes it
        # statically.
        self._key = (self.treedef, self.names, self.slots, self.K, self.P,
                     self.G)
        self._hash = hash(self._key)

    def __eq__(self, other):
        return self is other or (isinstance(other, ColumnLayout)
                                 and self._key == other._key)

    def __hash__(self):
        return self._hash

    @property
    def nbytes(self) -> int:
        return sum(np.dtype(dt).itemsize * int(np.prod(shape))
                   for dt, shape in self.buffers)

    # ------------------------------------------------------------ the parts

    def _parts(self, buffers):
        """(n [P], cols [P, K], words [P, K, W], flags [P, K, F]) of a
        buffer pair, numpy views or traced slices."""
        wbuf, fbuf = buffers
        P, K = self.P, self.K
        flags = fbuf.reshape(P, K, self.F)
        flags = flags.view(BOOL) if isinstance(flags, np.ndarray) \
            else flags != 0
        return (wbuf[:, 0], wbuf[:, 1:1 + K],
                wbuf[:, 1 + K:].reshape(P, K, self.W), flags)

    def _wide(self):
        """(leaf number, kind, offset, width, trail) of the leaves wider
        than ``[P, G]``, in the tree's order."""
        return [(i, kind, off, int(np.prod(trail)), trail)
                for i, (kind, off, trail) in enumerate(self.slots) if trail]

    # ------------------------------------------------------------- the host

    def alloc(self) -> Tuple[np.ndarray, np.ndarray]:
        """Fresh buffers on the host that hold no column."""
        wbuf, fbuf = (np.zeros(shape, dt) for dt, shape in self.buffers)
        wbuf[:, 1:1 + self.K] = self.G
        return wbuf, fbuf

    def view(self, buffers) -> "ColumnView":
        """The host's view of a numpy buffer pair: fill an ``alloc()``-ed
        pair in place through it, or read a fetched pair where it lies."""
        n, cols, words, flags = self._parts(buffers)
        planes = {}
        for name, (kind, off, trail) in zip(self.names, self.slots):
            block = flags if kind == FLAG else words
            planes[name] = block[:, :, off] if not trail else \
                block[:, :, off:off + int(np.prod(trail))].reshape(
                    (self.P, self.K) + trail)
        return ColumnView(self, n, cols, planes)

    # ------------------------------------------------- stacked dense planes

    def stack(self, tree: Any) -> tuple:
        """The dense tree with its ``[P, G]`` leaves stacked by kind:
        ``(words [Ws, P, G], flags [Fs, P, G], *the wider leaves)``."""
        leaves = self.treedef.flatten_up_to(tree)
        xp = np if all(isinstance(leaf, np.ndarray) for leaf in leaves) \
            else jnp
        narrow = {WORD: [], FLAG: []}
        for (kind, _, trail), leaf in zip(self.slots, leaves):
            if not trail:
                narrow[kind].append(leaf)
        return (xp.stack(narrow[WORD]), xp.stack(narrow[FLAG])) + tuple(
            leaves[i] for i, *_ in self._wide())

    def unstack(self, stacked: Sequence) -> Any:
        """The dense tree of :meth:`stack`'s arrays."""
        wide = iter(stacked[2:])
        return jax.tree.unflatten(self.treedef, [
            next(wide) if trail else stacked[kind == FLAG][off]
            for kind, off, trail in self.slots])

    # ----------------------------------------------------- columns -> dense

    def expand(self, buffers, stacked: bool = False) -> Any:
        """The dense tree a buffer pair stands for (its :meth:`stack`
        with ``stacked``): zero planes with the columns written into them.
        Under ``jit`` one K-row scatter for the stacked ``[P, G]`` leaves of
        a kind and one for each wider leaf: cost follows K, not G."""
        n, cols, words, flags = self._parts(buffers)
        P, G, K = self.P, self.G, self.K
        host = isinstance(cols, np.ndarray)
        xp = np if host else jnp
        held = xp.arange(K)[None, :] < n[:, None]
        idx = xp.where(held, cols, G)
        rows = xp.broadcast_to(xp.arange(P)[:, None], (P, K))

        def scatter(shape, dt, vals, lead):
            """``vals`` at (rows, idx) of a zero array, behind ``lead``
            leading axes that every index row writes through."""
            if host:
                out = np.zeros(shape, dt)
                if lead:
                    out[:, rows[held], idx[held]] = vals[:, held]
                else:
                    out[rows[held], idx[held]] = vals[held]
                return out
            if not lead:
                return jnp.zeros(shape, dt).at[rows, idx].set(
                    vals, mode="drop")
            # One index row a (leaf, peer, column), no window: a window
            # over the leaves would make the leaf axis the minor one of
            # the chip's layout and every plane a strided copy.
            n = shape[0]
            return jnp.zeros((n * P, G), dt).at[
                jnp.arange(n)[:, None, None] * P + rows[None],
                jnp.broadcast_to(idx[None], (n, P, K))].set(
                    vals, mode="drop").reshape(shape)

        out = [scatter((self.Ws, P, G), WORD,
                       xp.moveaxis(words[:, :, :self.Ws], 2, 0), True),
               scatter((self.Fs, P, G), BOOL,
                       xp.moveaxis(flags[:, :, :self.Fs], 2, 0), True)]
        for _, kind, off, w, trail in self._wide():
            block = flags if kind == FLAG else words
            out.append(scatter(
                (P, G) + trail, BOOL if kind == FLAG else WORD,
                block[:, :, off:off + w].reshape((P, K) + trail), False))
        return tuple(out) if stacked else self.unstack(out)

    # ----------------------------------------------------- dense -> columns

    def compact(self, tree: Any, stacked: bool = False) -> tuple:
        """The buffer pair of a dense tree (of its :meth:`stack`, with
        ``stacked``): per row the count of occupied columns (the TRUE
        count, also beyond K), the first K of them and every leaf's values
        there.  Under ``jit`` the lanes are found by block (a count per
        block of lanes, then a search inside the K blocks that hold the
        K-th column) and the values by one K-row gather over the stacked
        ``[P, G]`` leaves of a kind and one for each wider leaf: nothing
        is addressed G rows at a time."""
        planes = tree if stacked else self.stack(tree)
        P, G, K = self.P, self.G, self.K
        host = isinstance(planes[0], np.ndarray)
        xp = np if host else jnp
        occ = planes[1][self.occupancy[0]]
        for off in self.occupancy[1:]:
            occ = occ | planes[1][off]
        if host:
            n = occ.sum(axis=1).astype(WORD)
            cols = np.full((P, K), G, WORD)
            for p in range(P):
                at = np.nonzero(occ[p])[0][:K]
                cols[p, :len(at)] = at
        else:
            n, cols = _first_columns(occ, K)
        held = xp.arange(K)[None, :] < n[:, None]
        rows = xp.broadcast_to(xp.arange(P)[:, None], (P, K))
        at = xp.minimum(cols, G - 1)

        def gather(plane, lead):
            if not lead:
                vals = plane[rows, at]
            elif host:
                vals = np.moveaxis(plane[:, rows, at], 0, 2)
            else:       # one index row a (leaf, peer, column), as expand
                n = plane.shape[0]
                vals = jnp.moveaxis(plane.reshape(n * P, G)[
                    jnp.arange(n)[:, None, None] * P + rows[None],
                    jnp.broadcast_to(at[None], (n, P, K))], 0, 2)
            vals = vals.reshape(P, K, -1)
            return xp.where(held[:, :, None], vals,
                            xp.zeros((), vals.dtype))

        blocks = {WORD: [gather(planes[0], True)],
                  FLAG: [gather(planes[1], True)]}
        for (_, kind, _, _, _), plane in zip(self._wide(), planes[2:]):
            blocks[kind].append(gather(plane, False))
        wbuf = xp.concatenate(
            [n[:, None].astype(WORD), cols.astype(WORD),
             xp.concatenate(blocks[WORD], axis=2).reshape(P, K * self.W)],
            axis=1)
        fbuf = xp.concatenate(blocks[FLAG], axis=2).reshape(
            P, K * self.F).astype(FLAG)
        return wbuf, fbuf


def _first_columns(occ, K: int):
    """Traced: per row of ``occ [P, G]`` the count of set lanes and the
    first K of them ascending, ``G`` beyond the count.  By block: counts
    per block of ``_BLOCK`` lanes, the block of the k-th set lane from the
    blocks' running counts (a [K, blocks] compare), its place inside from
    the block's own running count (a K-row gather of one block each)."""
    P, G = occ.shape
    nb = -(-G // _BLOCK)
    blocks = jnp.pad(occ, ((0, 0), (0, nb * _BLOCK - G))).reshape(
        P, nb, _BLOCK)
    per = blocks.sum(axis=2, dtype=WORD)                    # [P, nb]
    upto = jnp.cumsum(per, axis=1)                          # inclusive
    n = upto[:, -1]
    k = jnp.arange(K, dtype=WORD)
    # the block that holds the k-th set lane: blocks wholly before it
    b = (upto[:, None, :] <= k[None, :, None]).sum(axis=2, dtype=WORD)
    b = jnp.minimum(b, nb - 1)                              # [P, K]
    rows = jnp.broadcast_to(jnp.arange(P)[:, None], (P, K))
    # lanes set before block b: the running count of the blocks before it
    before = jnp.where(jnp.arange(nb)[None, None, :] < b[:, :, None],
                       per[:, None, :], 0).sum(axis=2, dtype=WORD)
    inside = blocks[rows, b]                                # [P, K, BLOCK]
    run = jnp.cumsum(inside.astype(WORD), axis=2)
    r = (k[None, :] - before)[:, :, None]                   # rank in block
    pos = (run <= r).sum(axis=2, dtype=WORD)
    cols = jnp.where(k[None, :] < n[:, None], b * _BLOCK + pos, G)
    return n, cols.astype(WORD)


# ---------------------------------------------------------------------------
# What the host reads messages through: one interface over the two forms, so
# that a reader asks for a row's held lanes and the values there and never
# for a [G] plane.  ``row`` gives a field over the lanes a peer row HOLDS
# (every lane of a dense plane, the occupied columns of a column buffer);
# ``lanes`` turns a mask over those into group ids; ``fields`` gives what
# transport/codec.py packs from: per field something indexed by group ids.
# ---------------------------------------------------------------------------

class DenseView:
    """Dense ``[P, G, ...]`` host planes by field name."""

    __slots__ = ("planes",)
    columns = None          # no count: every lane of every row is held

    def __init__(self, planes):
        self.planes = planes

    def row(self, name: str, p: int) -> np.ndarray:
        return self.planes[name][p]

    def lanes(self, p: int, mask: np.ndarray) -> np.ndarray:
        return np.nonzero(mask)[0]

    def over(self, p: int, plane: np.ndarray) -> np.ndarray:
        """A ``[G]`` plane of the host's over the lanes row ``p`` holds."""
        return plane

    def fields(self, p: int):
        return {name: arr[p] for name, arr in self.planes.items()}

    def at(self, name: str, rows: np.ndarray, groups: np.ndarray
           ) -> np.ndarray:
        return self.planes[name][rows, groups]

    def dense(self, name: str) -> np.ndarray:
        return self.planes[name]


class _RowField:
    """One field of one peer row in column form, indexed by the group ids
    of columns the row holds."""

    __slots__ = ("cols", "vals")

    def __init__(self, cols, vals):
        self.cols, self.vals = cols, vals

    def __getitem__(self, groups):
        return self.vals[np.searchsorted(self.cols, groups)]


class ColumnView:
    """A numpy buffer pair of a :class:`ColumnLayout`, by field name."""

    __slots__ = ("layout", "n", "cols", "planes")

    def __init__(self, layout: ColumnLayout, n, cols, planes):
        self.layout, self.n, self.cols, self.planes = layout, n, cols, planes

    @property
    def columns(self) -> int:
        return int(self.n.sum())

    def row(self, name: str, p: int) -> np.ndarray:
        return self.planes[name][p, :self.n[p]]

    def lanes(self, p: int, mask: np.ndarray) -> np.ndarray:
        return self.cols[p, :self.n[p]][mask]

    def over(self, p: int, plane: np.ndarray) -> np.ndarray:
        return plane[self.cols[p, :self.n[p]]]

    def fields(self, p: int):
        cols = self.cols[p, :self.n[p]]
        return {name: _RowField(cols, arr[p, :self.n[p]])
                for name, arr in self.planes.items()}

    def at(self, name: str, rows: np.ndarray, groups: np.ndarray
           ) -> np.ndarray:
        """Values at (row, group) pairs; zero where the row holds no such
        column, as a dense plane reads there."""
        arr = self.planes[name]
        out = np.zeros((len(groups),) + arr.shape[2:], arr.dtype)
        for p in np.unique(rows).tolist():
            mine = np.nonzero(rows == p)[0]
            cols = self.cols[p, :self.n[p]]
            pos = np.minimum(np.searchsorted(cols, groups[mine]),
                             max(len(cols) - 1, 0))
            hit = cols[pos] == groups[mine] if len(cols) else \
                np.zeros(len(mine), bool)
            out[mine[hit]] = arr[p, pos[hit]]
        return out

    def dense(self, name: str) -> np.ndarray:
        lay = self.layout
        arr = self.planes[name]
        out = np.zeros((lay.P, lay.G) + arr.shape[2:], arr.dtype)
        for p in range(lay.P):
            out[p, self.cols[p, :self.n[p]]] = arr[p, :self.n[p]]
        return out
