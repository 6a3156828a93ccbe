"""Packed pytrees: every leaf of a pytree in a few flat buffers, so that a
tree of some eighty small planes crosses between host and device in ONE
transfer instead of one per leaf.

The engine's planes are ``int32`` and ``bool`` only, and there is one kind
of buffer: ``int32`` words.  A :class:`Layout` puts a buffer's ``int32``
leaves, ravelled and end to end, at its head and its ``bool`` leaves behind
them as a byte each, the flag region padded to a whole word (a byte per
flag: the host then sees ``bool`` planes as views, with the dtype its masks
and its codec expect, at a quarter of the bytes a widened flag would cross
with; in a buffer of their own they cost every step a transfer more each
way).  A buffer holds whole leaves in the tree's order and is closed to
words once another ``int32`` leaf would take it past ``CHUNK_BYTES``; a
``bool`` leaf goes into the first buffer that has room left for it, and
into a new one when none has.  At the sizes a call's fixed cost matters (a
transfer of a few KB and one of 0.5 MB both cost 0.6 ms on a TPU v5e) that
is one buffer, and a tree of tens of MB crosses in pieces of a few MB,
which the runtime moves side by side (one 48 MB fetch took 55 ms there,
twelve of 4 MB 4 ms).  The layout is derived from the tree's own structure
(``jax.tree.flatten`` order; a ``None`` subtree has no leaves and so no
room), never from a list of field names: a field added to ``Messages`` or
``StepInfo`` finds its place by itself.

Both directions work on either side of the boundary.  On the host
``unpack`` returns numpy **views** into the buffers (no copy: fill an
``alloc()``-ed set in place, or read a fetched set where it lies); under
``jit`` it is static slices and reshapes (a flag region's words taken apart
into their bytes first), and ``pack`` one concatenate of the ravelled
leaves per buffer (the flags' bytes put together into words first).  A
flag's byte lies where the host's memory has it: byte ``k`` of a word is
its bits ``8k`` to ``8k + 7`` (little-endian, as every host JAX runs on).

The column and row forms below are regions of such a word buffer too, so
that a step's rows and columns cross in one array each way
(:func:`alloc_regions`, :func:`regions`).
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

_SHIFTS = (0, 8, 16, 24)        # of a word's four flag bytes

# Under ``jit`` a word's four flag bytes are taken apart and put together
# 128 words (a vector register's lanes) at a time: the four byte planes of a
# row of words side by side are 512 values in PLANAR order (byte k of word c
# at 128 k + c), the flags they stand for are the same values INTERLEAVED
# (flag 4 c + k), and a fixed 512 x 512 permutation takes one to the other as
# a matrix product of 0/1 values, exact in bfloat16.  The chip's compiler
# turns every elementwise spelling of that interleave (a stack and a reshape,
# a bit-cast to [n, 4] bytes) into a relayout through arrays whose minor
# dimension is 4: 4.5 ms for the 11 MB of flags of a 100,000-lane packed
# step, against 6.2 ms for the whole step before (PERF.md, PR 42).
_LANES = 128


def _interleave() -> np.ndarray:
    """``[512, 512]`` 0/1: planar place ``128 k + c`` -> flag ``4 c + k``."""
    k, c = np.divmod(np.arange(4 * _LANES), _LANES)
    perm = np.zeros((4 * _LANES, 4 * _LANES), np.float32)
    perm[np.arange(4 * _LANES), 4 * c + k] = 1
    return perm


def _permute(values, perm: np.ndarray):
    """``values [rows, 512]`` (0/1, or a byte) times the permutation."""
    return jnp.dot(values.astype(jnp.bfloat16),
                   jnp.asarray(perm, jnp.bfloat16),
                   preferred_element_type=jnp.float32)


def flag_words(n: int) -> int:
    """The words ``n`` flags take, a byte each."""
    return -(-int(n) // 4)


def flags_in(words, n: int):
    """``[n] bool``: the flags that lie a byte each at the head of the
    word array ``words``.  Numpy gives a view that shares its memory."""
    if isinstance(words, np.ndarray):
        return words.view(FLAG)[:n].view(BOOL)
    words = words[:flag_words(n)]
    rows = -(-words.shape[0] // _LANES)
    words = jnp.pad(words, (0, rows * _LANES - words.shape[0])).reshape(
        rows, _LANES)
    planar = jnp.concatenate([(words >> s) & 0xFF for s in _SHIFTS], axis=1)
    return _permute(planar, _interleave()).reshape(-1)[:n] != 0


def words_of(flags):
    """``[ceil(n / 4)] int32``: the flat ``[n] bool`` array ``flags`` as a
    byte each, padded with zero bytes to a whole word."""
    n = flags.shape[0]
    if isinstance(flags, np.ndarray):
        out = np.zeros(4 * flag_words(n), FLAG)
        out[:n] = flags
        return out.view(WORD)
    rows = -(-n // (4 * _LANES))
    flags = jnp.pad(flags, (0, rows * 4 * _LANES - n)).reshape(
        rows, 4 * _LANES)
    planar = _permute(flags, _interleave().T).astype(WORD)
    words = planar[:, :_LANES]
    for k, s in enumerate(_SHIFTS[1:], 1):
        words = words | (planar[:, k * _LANES:(k + 1) * _LANES] << s)
    return words.reshape(-1)[:flag_words(n)]


class Layout:
    """Where each leaf of one pytree shape lies, and in which buffer.

    Built from anything ``jax.tree.flatten`` takes whose leaves carry
    ``shape`` and ``dtype`` (arrays, or what ``jax.eval_shape`` returns).
    ``buffers`` is the (dtype, length) of each buffer, in the order every
    method takes and returns them: ``int32`` words all, ``words[b]`` of
    them a buffer's ``int32`` leaves and the rest its flag region.
    ``slots`` has a leaf's buffer, offset (in words for an ``int32`` leaf,
    in bytes from the buffer's start for a ``bool`` one), size, whether it
    is a flag, and shape.  Hashable and comparable by structure, so a
    jitted function can take it as a static argument."""

    __slots__ = ("treedef", "slots", "buffers", "words", "_hash")

    def __init__(self, tree: Any):
        leaves, self.treedef = jax.tree.flatten(tree)
        sizes = [int(np.prod(leaf.shape, dtype=np.int64)) for leaf in leaves]
        kinds = [np.dtype(leaf.dtype) for leaf in leaves]
        for dt, leaf in zip(kinds, leaves):
            if dt not in (WORD, BOOL):
                raise TypeError(
                    f"a packed leaf is int32 or bool, not {dt} "
                    f"(shape {tuple(leaf.shape)})")
        # The words first, as if there were no flags (the buffers that
        # hold words are the ones a tree without flags would have); then
        # each flag leaf into the first buffer with room left for it.
        place, counts = {}, []          # counts[b] = [words, flags]
        for i, (dt, size) in enumerate(zip(kinds, sizes)):
            if dt == WORD:
                if not counts or (counts[-1][0] and 4 * (counts[-1][0] + size)
                                  > CHUNK_BYTES):
                    counts.append([0, 0])
                place[i] = (len(counts) - 1, counts[-1][0])
                counts[-1][0] += size
        for i, (dt, size) in enumerate(zip(kinds, sizes)):
            if dt == BOOL:
                b = next((b for b, (w, f) in enumerate(counts)
                          if 4 * w + f + size <= CHUNK_BYTES), len(counts))
                if b == len(counts):
                    counts.append([0, 0])
                place[i] = (b, counts[b][1])
                counts[b][1] += size
        placed = [place[i] + (sizes[i], kinds[i] == BOOL, tuple(leaf.shape))
                  for i, leaf in enumerate(leaves)]
        self.words: Tuple[int, ...] = tuple(w for w, _ in counts)
        self.slots: Tuple[Tuple[int, int, int, bool, tuple], ...] = tuple(
            (b, off + 4 * self.words[b] * flag, size, flag, shape)
            for b, off, size, flag, shape in placed)
        self.buffers: Tuple[Tuple[np.dtype, int], ...] = tuple(
            (WORD, w + flag_words(f)) for w, f in counts)
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
        slices (of a buffer's flag region taken apart once)."""
        leaves, region = [], {}
        for b, off, size, flag, shape in self.slots:
            buf = buffers[b]
            if not flag:
                flat = buf[off:off + size]
            elif isinstance(buf, np.ndarray):
                flat = buf.view(FLAG)[off:off + size].view(BOOL)
            else:
                w = self.words[b]
                if b not in region:
                    region[b] = flags_in(buf[w:], 4 * (buf.shape[0] - w))
                flat = region[b][off - 4 * w:off - 4 * w + size]
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
        parts = [([], []) for _ in self.buffers]
        for (b, _, _, flag, _), leaf in zip(self.slots, leaves):
            parts[b][flag].append(jnp.ravel(leaf))
        return tuple(jnp.concatenate(
            [w.astype(WORD) for w in words]
            + ([words_of(jnp.concatenate(flags))] if flags else []))
            for words, flags in parts)


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
# largest power of two at which (a) the column buffer stays within a
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
    ONE word buffer of ``size`` words (a region of a larger one, where a
    step's rows cross beside it: :func:`regions`): ``[P, 1 + K + K * W]``
    words (count, lanes, words), then the flags ``[P, K * F]`` a byte
    each, padded to a whole word.  A column is
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
                 "W", "F", "Ws", "Fs", "size", "_key", "_hash")

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
        self.size = self.P * (1 + self.K + self.K * self.W) \
            + flag_words(self.P * self.K * self.F)
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
        return WORD.itemsize * self.size

    # ------------------------------------------------------------ the parts

    def _parts(self, buffer):
        """(n [P], cols [P, K], words [P, K, W], flags [P, K, F]) of a
        buffer, numpy views or traced slices."""
        P, K = self.P, self.K
        n_words = P * (1 + K + K * self.W)
        wbuf = buffer[:n_words].reshape(P, 1 + K + K * self.W)
        flags = flags_in(buffer[n_words:], P * K * self.F)
        return (wbuf[:, 0], wbuf[:, 1:1 + K],
                wbuf[:, 1 + K:].reshape(P, K, self.W),
                flags.reshape(P, K, self.F))

    def _wide(self):
        """(leaf number, kind, offset, width, trail) of the leaves wider
        than ``[P, G]``, in the tree's order."""
        return [(i, kind, off, int(np.prod(trail)), trail)
                for i, (kind, off, trail) in enumerate(self.slots) if trail]

    # ------------------------------------------------------------- the host

    def clear(self, buffer: np.ndarray) -> np.ndarray:
        """``buffer`` (zeroed, on the host) made to hold no column."""
        self._parts(buffer)[1][...] = self.G
        return buffer

    def alloc(self) -> np.ndarray:
        """A fresh buffer on the host that holds no column."""
        return self.clear(np.zeros(self.size, WORD))

    def view(self, buffer) -> "ColumnView":
        """The host's view of a numpy buffer: fill an ``alloc()``-ed one
        in place through it, or read a fetched one where it lies."""
        n, cols, words, flags = self._parts(buffer)
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

    def expand(self, buffer, stacked: bool = False) -> Any:
        """The dense tree a buffer stands for (its :meth:`stack`
        with ``stacked``): zero planes with the columns written into them.
        Under ``jit`` one K-row scatter for the stacked ``[P, G]`` leaves of
        a kind and one for each wider leaf: cost follows K, not G."""
        n, cols, words, flags = self._parts(buffer)
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

    def compact(self, tree: Any, stacked: bool = False):
        """The buffer of a dense tree (of its :meth:`stack`, with
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
        return xp.concatenate([wbuf.reshape(-1), words_of(
            xp.concatenate(blocks[FLAG], axis=2).reshape(-1))])


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
# Row form: a tree of [G] planes as the lanes that hold something.  The [G]
# side of a large node's step (HostInbox going up; StepInfo and the mirrored
# state lanes coming down) is some fifty planes of 100,000 lanes of which a
# step touches a handful; they cross as ROWS (a lane id and every plane's
# value there) and are dense on the device alone, where the step program
# expands them into planes and compacts its results from planes
# (core/step.py node_step_columns), exactly as the messages' columns do.
# ---------------------------------------------------------------------------

# The most rows that cross in row form, going up and coming down; a step
# with more crosses as whole planes, decided by the count, nothing is cut.
# Chosen on a TPU v5e at 100,000 lanes (PERF.md, PR 38): a row costs the
# device what its planes' elements cost a gather or a scatter (0.37 us
# coming down over 38 planes, 0.08 us going up over 11, on a fixed 0.1 ms
# of search and comparison), so the step with its compaction reads 5.23 /
# 5.25 / 5.48 / 5.67 ms a call at 128 / 256 / 512 / 1,024 rows each way
# against 5.16 for the program whose pack and unpack they replace (which
# cost the device next to nothing: what rows buy is on the host): the
# largest power of two within 0.1 ms of it.  A steady step of the
# 100,000-Region cell moves a few tens of lanes.
ROWS_IN = 256
ROWS_OUT = 256

HEAD = np.dtype(np.int64)       # a marker, not a dtype that crosses


def _path_name(path) -> str:
    return ".".join(str(getattr(k, "name", getattr(k, "key", k)))
                    for k in path)


def lane_names(tree: Any, G: int) -> list:
    """The path names of ``tree``'s ``[G]`` leaves, in the tree's order."""
    return [_path_name(path) for path, leaf
            in jax.tree_util.tree_flatten_with_path(tree)[0]
            if tuple(leaf.shape) == (G,)]


class RowLayout:
    """Where each leaf of one tree of ``[G]`` planes lies in the row form:
    a count ``n``, the lanes ``ids [K]`` of the rows (``G`` from ``n`` on)
    and every ``[G]`` leaf's values there, field by field: the ``int32``
    leaves in ``words [W, K]``, the ``bool`` leaves in ``flags [F, K]``.
    A leaf of any other shape (a scalar, a few sums) is no plane: it rides
    the HEADER, ``H`` words behind the count.  ONE word buffer of ``size``
    words (a region of a larger one, where a step's columns cross beside
    it: :func:`regions`): ``[1 + H + K + W * K]`` words (count, header,
    lanes, words), then the flags ``[F * K]`` a byte each, padded to a
    whole word.

    Leaves are named by their path (``info.commit``, ``commit``).  The
    ``[G]`` leaves named in ``levels`` are LEVELS (a lane's row crosses
    when one differs from what the other side holds), those in
    ``carried`` cross with a row and never cause one, every other is an
    EVENT (zero unless something happened: a row crosses when one is not
    zero); :meth:`moved` says which lanes those are.  The stacks hold the
    levels first, then the events, then the carried leaves, each group in
    the tree's order.

    On the device the planes are addressed STACKED (:meth:`stack`), the
    leaves of a kind as one ``[n, G]`` array that shares ONE K-row scatter
    going in and ONE K-row gather coming out, as ColumnLayout's are.
    Derived from the tree's own structure: a field added to ``HostInbox``
    or ``StepInfo`` finds its place by itself (as an event)."""

    __slots__ = ("treedef", "names", "slots", "at", "G", "K", "W", "F", "H",
                 "Lw", "Ew", "Lf", "Ef", "size", "_key", "_hash")

    def __init__(self, tree: Any, G: int, K: int, levels=(), carried=()):
        flat, self.treedef = jax.tree_util.tree_flatten_with_path(tree)
        self.G, self.K = int(G), int(K)
        names, kinds, shapes = [], [], []
        for path, leaf in flat:
            dt = np.dtype(leaf.dtype)
            if dt not in (WORD, BOOL):
                raise TypeError(f"a row leaf is int32 or bool, not {dt} "
                                f"(shape {tuple(leaf.shape)})")
            lane = tuple(leaf.shape) == (self.G,)
            names.append(_path_name(path))
            kinds.append((FLAG if dt == BOOL else WORD) if lane else HEAD)
            shapes.append((dt, tuple(int(d) for d in leaf.shape)))
        unknown = (set(levels) | set(carried)) - {
            n for n, k in zip(names, kinds) if k != HEAD}
        if unknown:
            raise ValueError(f"no [G] leaf named {sorted(unknown)}")
        rank = lambda name: (0 if name in levels else
                             2 if name in carried else 1)
        width = {WORD: 0, FLAG: 0, HEAD: 0}
        offs = [0] * len(names)
        counts = {}
        for r in (0, 1, 2):
            for i, (name, kind) in enumerate(zip(names, kinds)):
                if kind != HEAD and rank(name) == r:
                    offs[i] = width[kind]
                    width[kind] += 1
            counts[r] = (width[WORD], width[FLAG])
        for i, kind in enumerate(kinds):
            if kind == HEAD:
                offs[i] = width[HEAD]
                width[HEAD] += int(np.prod(shapes[i][1], dtype=np.int64))
        self.names: Tuple[str, ...] = tuple(names)
        self.slots = tuple(
            (kind, off, dt, shape)
            for kind, off, (dt, shape) in zip(kinds, offs, shapes))
        # name -> (kind, place in the kind's stack or in the header)
        self.at = {name: slot[:2] for name, slot
                   in zip(self.names, self.slots)}
        self.W, self.F, self.H = width[WORD], width[FLAG], width[HEAD]
        self.Lw, self.Lf = counts[0]
        self.Ew, self.Ef = counts[1][0] - self.Lw, counts[1][1] - self.Lf
        self.size = 1 + self.H + self.K + self.W * self.K \
            + flag_words(self.F * self.K)
        self._key = (self.treedef, self.names, self.slots, self.G, self.K)
        self._hash = hash(self._key)

    def __eq__(self, other):
        return self is other or (isinstance(other, RowLayout)
                                 and self._key == other._key)

    def __hash__(self):
        return self._hash

    @property
    def nbytes(self) -> int:
        return WORD.itemsize * self.size

    # ------------------------------------------------------------ the parts

    def _parts(self, buffer):
        """(n, header [H], ids [K], words [W, K], flags [F, K]) of a
        buffer, numpy views or traced slices."""
        H, K = self.H, self.K
        at = 1 + H + K
        n_words = at + self.W * K
        return (buffer[0], buffer[1:1 + H], buffer[1 + H:at],
                buffer[at:n_words].reshape(self.W, K),
                flags_in(buffer[n_words:], self.F * K).reshape(self.F, K))

    def _head(self, header):
        """name -> the header's leaves, in their own shape and dtype."""
        out = {}
        for name, (kind, off, dt, shape) in zip(self.names, self.slots):
            if kind == HEAD:
                flat = header[off:off + int(np.prod(shape, dtype=np.int64))]
                out[name] = (flat != 0 if dt == BOOL else flat).reshape(shape)
        return out

    # ------------------------------------------------------------- the host

    def clear(self, buffer: np.ndarray) -> np.ndarray:
        """``buffer`` (zeroed, on the host) made to hold no row."""
        buffer[1 + self.H:1 + self.H + self.K] = self.G
        return buffer

    def alloc(self) -> np.ndarray:
        """A fresh buffer on the host that holds no row."""
        return self.clear(np.zeros(self.size, WORD))

    def view(self, buffer) -> "RowView":
        """The host's view of a numpy buffer: fill an ``alloc()``-ed one
        in place through it, or read a fetched one where it lies."""
        return RowView(self, buffer, *self._parts(buffer)[1:])

    def planes(self) -> Tuple[np.ndarray, np.ndarray]:
        """Fresh zero planes on the host, stacked: ``(words [W, G], flags
        [F, G])``; :meth:`unstack` gives the tree of views into them."""
        return (np.zeros((self.W, self.G), WORD),
                np.zeros((self.F, self.G), BOOL))

    def copy_levels(self, tree: Any, words, flags) -> None:
        """Host: the level leaves of a dense numpy ``tree`` copied into the
        stacked planes ``words`` and ``flags``; nothing else is touched."""
        for (kind, off, _, _), leaf in zip(
                self.slots, self.treedef.flatten_up_to(tree)):
            if kind == WORD and off < self.Lw:
                np.copyto(words[off], leaf)
            elif kind == FLAG and off < self.Lf:
                np.copyto(flags[off], leaf)

    def whole(self, tree: Any) -> np.ndarray:
        """Host: the buffer that goes up beside planes that crossed
        whole: no row, a count of -1, and ``tree``'s header leaves."""
        buffer = self.alloc()
        view = self.view(buffer)
        view.set_n(-1)
        for name, (kind, *_), leaf in zip(
                self.names, self.slots, self.treedef.flatten_up_to(tree)):
            if kind == HEAD:
                view.set_head(name, leaf)
        return buffer

    # ------------------------------------------------------- stacked planes

    def stack(self, tree: Any) -> tuple:
        """``(words [W, G], flags [F, G], header [H])`` of a dense tree."""
        leaves = self.treedef.flatten_up_to(tree)
        xp = np if all(isinstance(leaf, (np.ndarray, np.generic, int, bool))
                       for leaf in leaves) else jnp
        rows = {WORD: [None] * self.W, FLAG: [None] * self.F}
        head = []
        for (kind, off, dt, shape), leaf in zip(self.slots, leaves):
            if kind == HEAD:
                head.append(xp.ravel(xp.asarray(leaf)).astype(WORD))
            else:
                rows[kind][off] = leaf
        return (xp.stack(rows[WORD]) if self.W else xp.zeros((0, self.G), WORD),
                xp.stack(rows[FLAG]) if self.F else xp.zeros((0, self.G), BOOL),
                xp.concatenate(head) if head else xp.zeros((0,), WORD))

    def unstack(self, words, flags, header) -> Any:
        """The dense tree of :meth:`stack`'s arrays (numpy: views)."""
        head = self._head(header)
        return jax.tree.unflatten(self.treedef, [
            head[name] if kind == HEAD else
            (flags if kind == FLAG else words)[off]
            for name, (kind, off, _, _) in zip(self.names, self.slots)])

    # -------------------------------------------------------- rows -> dense

    def expand(self, buffer, words, flags) -> tuple:
        """``(words, flags, header)``: the stacked planes ``words [W, G]``
        and ``flags [F, G]`` with the rows of a buffer written over them,
        and the buffer's header.  Under ``jit`` one K-row scatter a kind:
        cost follows K, not G."""
        n, header, ids, rw, rf = self._parts(buffer)
        G, K = self.G, self.K
        host = isinstance(ids, np.ndarray)
        xp = np if host else jnp
        held = xp.arange(K) < n
        idx = xp.where(held, ids, G)

        def scatter(base, vals):
            if host:
                out = base.copy()
                out[:, idx[held]] = vals[:, held]
                return out
            rows = base.shape[0]
            # One index row a (leaf, row), no window over the leaves (see
            # ColumnLayout.expand).
            return base.at[
                jnp.arange(rows)[:, None],
                jnp.broadcast_to(idx[None], (rows, K))].set(
                    vals.astype(base.dtype), mode="drop")

        return scatter(words, rw), scatter(flags, rf), header

    # -------------------------------------------------------- dense -> rows

    def moved(self, words, flags, prev_words, prev_flags):
        """``[G] bool``: the lanes whose row crosses.  A level differs
        from ``prev_*`` (stacked planes of the same layout: what the other
        side holds), or an event is not zero."""
        Lw, Ew, Lf, Ef = self.Lw, self.Ew, self.Lf, self.Ef
        return ((words[:Lw] != prev_words[:Lw]).any(axis=0)
                | (flags[:Lf] != prev_flags[:Lf]).any(axis=0)
                | (words[Lw:Lw + Ew] != 0).any(axis=0)
                | flags[Lf:Lf + Ef].any(axis=0))

    def compact(self, words, flags, header, moved):
        """The buffer of stacked planes: the count of ``moved`` lanes
        (the TRUE count, also beyond K), the first K of them ascending and
        every plane's value there.  Under ``jit`` the lanes are found by
        block (``_first_columns``) and the values by one K-row gather a
        kind: nothing is addressed G rows at a time."""
        G, K = self.G, self.K
        host = isinstance(words, np.ndarray)
        xp = np if host else jnp
        if host:
            at = np.nonzero(moved)[0]
            n = np.asarray(len(at), WORD)
            ids = np.full(K, G, WORD)
            ids[:min(K, len(at))] = at[:K]
        else:
            n, ids = _first_columns(moved[None, :], K)
            n, ids = n[0], ids[0]
        held = xp.arange(K) < n
        at = xp.minimum(ids, G - 1)

        def gather(plane):
            if host:
                vals = plane[:, at]
            else:
                rows = plane.shape[0]
                vals = plane[jnp.arange(rows)[:, None],
                             jnp.broadcast_to(at[None], (rows, K))]
            return xp.where(held[None, :], vals, xp.zeros((), vals.dtype))

        return xp.concatenate([
            xp.reshape(n, (1,)).astype(WORD), header.astype(WORD),
            ids.astype(WORD), gather(words).reshape(-1),
            words_of(gather(flags).reshape(-1))])


class RowView:
    """A numpy buffer of a :class:`RowLayout`: the count ``n`` (set
    it with :meth:`set_n`), the lanes ``ids [K]``, ``words [W, K]`` and
    ``flags [F, K]`` field by field, and by name ``field(name)`` (a ``[K]``
    view) and ``head(name)`` / ``set_head(name, value)``."""

    __slots__ = ("layout", "_wbuf", "header", "ids", "words", "flags")

    def __init__(self, layout: RowLayout, wbuf, header, ids, words, flags):
        self.layout, self._wbuf, self.header = layout, wbuf, header
        self.ids, self.words, self.flags = ids, words, flags

    @property
    def n(self) -> int:
        return int(self._wbuf[0])

    def set_n(self, n: int) -> None:
        self._wbuf[0] = n

    def field(self, name: str) -> np.ndarray:
        kind, off = self.layout.at[name]
        return (self.flags if kind == FLAG else self.words)[off]

    def head(self, name: str):
        return self.layout._head(self.header)[name]

    def set_head(self, name: str, value) -> None:
        flat = np.ravel(np.asarray(value))
        off = self.layout.at[name][1]
        self.header[off:off + flat.size] = flat


# ---------------------------------------------------------------------------
# One array a direction: a column step's rows and columns are regions of one
# word buffer (going up: HostInbox's rows, then the inbox's columns; coming
# down: the Readback's rows, then the outbox's columns).
# ---------------------------------------------------------------------------

def regions(buffer, *layouts) -> list:
    """The buffers of ``layouts`` (row or column layouts) that lie end to
    end in ``buffer``: numpy views, or traced slices."""
    out, at = [], 0
    for lay in layouts:
        out.append(buffer[at:at + lay.size])
        at += lay.size
    return out


def alloc_regions(*layouts) -> Tuple[np.ndarray, list]:
    """One fresh word buffer on the host that holds a buffer of each of
    ``layouts`` end to end, each holding nothing yet, and those buffers
    as views of it: filled in place, they cross in one transfer."""
    buffer = np.zeros(sum(lay.size for lay in layouts), WORD)
    parts = regions(buffer, *layouts)
    for lay, part in zip(layouts, parts):
        lay.clear(part)
    return buffer, parts


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
    """A numpy buffer of a :class:`ColumnLayout`, by field name."""

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
