"""Packed pytrees (core/packing.py) and the packed step the served path
runs (core/step.py node_step_packed): the layout follows the dataclasses
through every optional subtree, the host side is views and not copies, the
packed step is node_step bit for bit, and a tick's upload buffers are its
own until its host phase is done with them.  And the column form of the
message planes (ColumnLayout, node_step_columns): the same step, bit for
bit, whichever way its messages cross."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rafting_tpu.core.cluster import route
from rafting_tpu.core import packing
from rafting_tpu.core.packing import ColumnLayout, Layout, RowLayout
from rafting_tpu.core.step import (
    COLUMN_BUFFERS, Readback, column_layouts, first_carry, node_step,
    node_step_columns, node_step_packed, pack_outbox, pack_readback,
    step_layouts,
)
from rafting_tpu.core.types import (
    EngineConfig, HostInbox, Messages, init_state,
)
from rafting_tpu.testkit.harness import LocalCluster

BASE = dict(n_groups=8, log_slots=16, batch=4, max_submit=4,
            election_ticks=6, heartbeat_ticks=2, rpc_timeout_ticks=5)


def assert_trees_equal(got, want, tag=""):
    """Same structure (None subtrees included) and every leaf equal in
    dtype, shape and value."""
    got, want = jax.device_get((got, want))
    assert jax.tree.structure(got) == jax.tree.structure(want), tag
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        where = f"{tag} {jax.tree_util.keystr(path)}"
        assert a.dtype == b.dtype and a.shape == b.shape, where
        np.testing.assert_array_equal(a, b, err_msg=where)


# ------------------------------------------ (a) packed step == node_step ----


@pytest.mark.parametrize("extra,durable", [
    (dict(n_peers=3), True),
    (dict(n_peers=3), False),
    (dict(n_peers=5), True),
    (dict(n_peers=3, heat=True, check_quorum=True), True),
    (dict(n_peers=3, trace_depth=16), False),
    (dict(n_peers=5, heat=True, check_quorum=True, trace_depth=16), False),
], ids=["p3-durable", "p3", "p5-durable", "p3-heat-cq-durable", "p3-trace",
        "p5-heat-cq-trace"])
def test_packed_step_is_node_step_bit_for_bit(extra, durable):
    """A whole cluster stepped twice from a common state over 40 ticks,
    once through node_step and once through node_step_packed, each node
    fed the other nodes' outboxes of the tick before over randomly cut
    links, with random offers of writes and reads: every leaf of the
    state, the outbox, the StepInfo and the mirrored lanes agrees."""
    cfg = EngineConfig(**BASE, **extra)
    N, G = cfg.n_peers, cfg.n_groups
    inputs, readback = step_layouts(cfg, durable)
    assert inputs == step_layouts(cfg, durable)[0]
    assert inputs != step_layouts(cfg, not durable)[0]
    # At the sizes where a call's fixed cost matters: one buffer each way,
    # the flags a byte each behind the words.
    assert len(inputs.buffers) == len(readback.buffers) == 1
    assert {dt for dt, _ in inputs.buffers + readback.buffers} == {packing.WORD}
    rng = np.random.default_rng(7)
    plain = [init_state(cfg, n, seed=3) for n in range(N)]
    packed = [init_state(cfg, n, seed=3) for n in range(N)]
    outboxes = [jax.device_get(Messages.empty(cfg))] * N
    tails = [np.zeros(G, np.int32)] * N
    led = 0
    for t in range(40):
        inflight = jax.tree.map(lambda *a: np.stack(a), *outboxes)
        inboxes = jax.device_get(
            route(inflight, jnp.asarray(rng.random((N, N)) > 0.1)))
        outboxes = []
        for n in range(N):
            inbox = jax.tree.map(lambda a: a[n], inboxes)
            host = jax.device_get(HostInbox.empty(cfg)).replace(
                submit_n=rng.integers(0, cfg.max_submit + 1, G,
                                      dtype=np.int32),
                read_n=rng.integers(0, 3, G, dtype=np.int32),
                read_veto=np.asarray(rng.random() < 0.05),
                # The fsynced tail as a pipelined host feeds it: the
                # tick before's log end.
                durable_tail=tails[n] if durable else None)
            plain[n], p_out, p_info = node_step(
                cfg, plain[n], *jax.tree.map(jnp.asarray, (inbox, host)))
            packed[n], bufs = node_step_packed(
                cfg, inputs, packed[n], inputs.pack((host, inbox)))
            back = readback.unpack(jax.device_get(bufs))
            tag = f"tick {t} node {n}"
            assert_trees_equal(packed[n], plain[n], tag)
            s = plain[n]
            assert_trees_equal(back, Readback(
                info=p_info, outbox=p_out, term=s.term,
                voted_for=s.voted_for, role=s.role, leader_id=s.leader_id,
                commit=s.commit, base=s.log.base,
                base_term=s.log.base_term, heat=s.heat,
                # Derived from the states on either side of the step:
                # tests/test_window_stats.py recomputes it.
                windows=back.windows), tag)
            assert (back.heat is None) == (not cfg.heat)
            assert (back.info.cq_stepdown is None) == (not cfg.check_quorum)
            outboxes.append(back.outbox)
            tails[n] = back.info.log_tail
            led += int(back.info.submit_acc.sum())
    assert led > 0, "no leader ever accepted a write: the run proved little"


# ------------------------------------------------- (b) the host side ----


def _tree(rng):
    i32 = lambda *shape: rng.integers(-9, 9, shape, dtype=np.int32)
    flag = lambda *shape: rng.random(shape) < 0.5
    return {"a": i32(5), "none": None, "b": (flag(3, 4), i32(3, 4, 2)),
            "scalar": np.asarray(True), "c": [i32(1), flag(7)]}


def _odd_tree(rng):
    """Flag bytes that are no multiple of four (13 + 1 + 3), and no word
    leaf behind the last flag."""
    i32 = lambda *shape: rng.integers(-9, 9, shape, dtype=np.int32)
    flag = lambda *shape: rng.random(shape) < 0.5
    return [flag(13), i32(2, 3), np.asarray(False), flag(3)]


def _flag_run_tree(rng):
    """A run of flag leaves that a 64-byte chunk closes inside: the
    buffer of the words has room for the first and the fourth, the others
    open a buffer that holds flags alone."""
    i32 = lambda *shape: rng.integers(-9, 9, shape, dtype=np.int32)
    flag = lambda *shape: rng.random(shape) < 0.5
    return [i32(6), (flag(18), flag(21), flag(30), flag(5)), i32(3),
            flag(2, 3)]


def _long_flags_tree(rng):
    """Flag regions of several rows of 512 (the device takes a word's
    bytes apart 128 words at a time), the last one part full, and leaves
    that start in the middle of a row."""
    i32 = lambda *shape: rng.integers(-9, 9, shape, dtype=np.int32)
    flag = lambda *shape: rng.random(shape) < 0.5
    return {"a": flag(3, 433), "b": i32(7), "c": flag(515), "d": flag(2)}


# name -> (tree maker, CHUNK_BYTES, the layout's buffers in words)
LAYOUT_CASES = {
    # 5 + 24 + 1 words, then 12 + 1 + 7 flags: five words of them
    "one-buffer": (_tree, None, (35,)),
    # 6 words, then 17 flags: five words, three bytes of padding
    "odd-flag-bytes": (_odd_tree, None, (11,)),
    # 36 B of words + 18 flags = 54 B: the next 21 flags would pass 64 and
    # open buffer two, which takes the 30 too; the 5 still fit buffer one
    # (59 B), the last 6 do not: 23 flags in 6 words, 57 in 15
    "chunk-closes-in-a-flag-run": (_flag_run_tree, 64, (9 + 6, 15)),
    # 7 words, then 1,299 + 515 + 2 flags in 454 words: three rows and a half
    "flag-rows": (_long_flags_tree, None, (7 + 454,)),
}


@pytest.fixture(params=list(LAYOUT_CASES))
def layout_case(request, monkeypatch):
    make, chunk, words = LAYOUT_CASES[request.param]
    if chunk is not None:
        monkeypatch.setattr(packing, "CHUNK_BYTES", chunk)
    tree = make(np.random.default_rng(len(request.param)))
    layout = Layout(tree)
    assert layout.buffers == tuple((np.int32, n) for n in words)
    return tree, layout


def test_host_round_trip_is_the_identity_and_unpack_gives_views(layout_case):
    """``bool`` leaves lie a byte each behind their buffer's words and
    come back as ``bool`` VIEWS of the word buffer, the ``int32`` leaves
    as views too: nothing is copied on the host in either direction."""
    tree, layout = layout_case
    buffers = layout.pack(tree)
    assert [(b.dtype, b.size) for b in buffers] == list(layout.buffers)
    back = layout.unpack(buffers)
    assert_trees_equal(back, tree)
    for leaf in jax.tree.leaves(back):
        assert sum(np.shares_memory(leaf, b) for b in buffers) == 1
        assert leaf.flags.c_contiguous
    # A flag is a byte, 0 or 1, in its buffer's flag region, which is
    # padded to a whole word and no further.
    flags = [leaf for leaf in jax.tree.leaves(tree) if leaf.dtype == bool]
    regions = [b.view(np.uint8)[4 * w:] for b, w in zip(buffers, layout.words)]
    assert sum(int(r.sum()) for r in regions) == sum(
        int(leaf.sum()) for leaf in flags)
    assert all(r.max(initial=0) <= 1 for r in regions)
    assert 0 <= sum(r.size for r in regions) - sum(
        leaf.size for leaf in flags) < 4 * len(buffers)
    # Filled in place: what is written through a view is in the buffer.
    fresh = layout.alloc()
    assert not any(b.any() for b in fresh)
    views = layout.unpack(fresh)
    for view, leaf in zip(jax.tree.leaves(views), jax.tree.leaves(tree)):
        view[...] = leaf
    for got, want in zip(fresh, buffers):
        np.testing.assert_array_equal(got, want)


def test_device_round_trip_matches_the_host_layout(layout_case):
    """pack under jit lays the leaves out where the host's unpack finds
    them, and unpack under jit finds what the host's pack laid out, bit
    for bit: a flag's byte is the same byte on both sides."""
    tree, layout = layout_case
    buffers = layout.pack(tree)
    on_device = jax.device_get(
        jax.jit(layout.pack)(jax.tree.map(jnp.asarray, tree)))
    for got, want in zip(on_device, buffers):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)
    assert_trees_equal(layout.unpack(on_device), tree)
    assert_trees_equal(jax.jit(layout.unpack)(buffers), tree)


def test_a_buffer_is_closed_at_the_chunk_bound(monkeypatch):
    """Whole leaves: the ``int32`` ones in flatten order, a new buffer
    once the next would pass CHUNK_BYTES, as if the tree had no flags
    (a leaf larger than the bound has a buffer to itself); each ``bool``
    one in the first buffer with room left for it; both sides of the
    boundary agree on the pieces."""
    monkeypatch.setattr(packing, "CHUNK_BYTES", 64)
    i32 = lambda n, v: np.full(n, v, np.int32)
    tree = [i32(10, 1), np.ones(40, bool), i32(6, 2), i32(1, 3),
            np.zeros(30, bool), i32(40, 4), i32(2, 5)]
    layout = Layout(tree)
    # 64 B of words | 4 B + 40 flags | 160 B | 8 B + 30 flags
    assert layout.buffers == tuple((np.int32, n) for n in (
        16, 1 + 10, 40, 2 + 8))
    assert layout.words == (16, 1, 40, 2)
    assert layout.words == tuple(n for _, n in Layout(
        [leaf for leaf in tree if leaf.dtype == np.int32]).buffers)
    buffers = layout.pack(tree)
    assert_trees_equal(layout.unpack(buffers), tree)
    on_device = jax.jit(layout.pack)(jax.tree.map(jnp.asarray, tree))
    for got, want in zip(jax.device_get(on_device), buffers):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)
    assert layout != Layout(tree[:-1])
    monkeypatch.undo()
    assert len(Layout(tree).buffers) == 1


def test_a_leaf_that_is_neither_int32_nor_bool_is_refused():
    with pytest.raises(TypeError, match="int32 or bool"):
        Layout({"x": np.zeros(3, np.float32)})


# ------------------------------ (c) every tick has buffers of its own ----


def test_next_dispatch_leaves_the_last_ticks_arrays_alone(tmp_path):
    """Every dispatch fills upload buffers of its own: the inbox planes a
    tick's host phase read are neither reused nor overwritten by the next
    dispatch (an upload may alias host memory, and the step that reads it
    runs asynchronously)."""
    cfg = EngineConfig(n_peers=3, **BASE)
    c = LocalCluster(cfg, str(tmp_path), seed=1)
    try:
        c.wait_leader(0)
        node = c.nodes[0]
        held = []       # (a tick's planes as its host phase began, copies)
        host_phase = node._host_phase

        def keep(ctx):
            arrays = ctx.arrays.planes               # a DenseView's
            if held:
                before, copies = held[-1]
                for name, plane in before.items():
                    np.testing.assert_array_equal(plane, copies[name], name)
                    assert not np.shares_memory(plane, arrays[name])
            held.append((arrays, {k: v.copy() for k, v in arrays.items()}))
            return host_phase(ctx)
        node._host_phase = keep
        c.tick(6)
        assert len(held) == 6
        assert sum(bool(a["ae_valid"].any() or a["aer_valid"].any())
                   for a, _ in held) >= 4, "no traffic reached the node"
    finally:
        c.close()


# ------------------------------- (d) the column step == node_step ----


@pytest.fixture
def small_columns(small):
    """``set(K)``: ``small`` (tests/conftest.py) at this file's sizes:
    column buffers of K columns a row, the rows as they ship (nothing of
    8 lanes overflows them), and buffers so small that the 8-lane shape's
    planes do not fit one."""
    return lambda k: small(None, None, columns=k, chunk_bytes=256)


def _occupied(msgs):
    """[P, G]: the columns that hold a valid message of any kind."""
    return np.any([np.asarray(getattr(msgs, f))
                   for f in msgs.__dataclass_fields__
                   if f.endswith("_valid")], axis=0)


def _on_columns(msgs, occ):
    """``msgs`` with everything outside its occupied columns zeroed: what
    the column form carries of a dense tree."""
    return jax.tree.map(
        lambda a: np.where(occ.reshape(occ.shape + (1,) * (a.ndim - 2)),
                           a, np.zeros((), a.dtype)), msgs)


@pytest.mark.parametrize("durable", [True, False], ids=["durable", "serial"])
@pytest.mark.parametrize("k_out", [8, 2], ids=["columns-out", "overflow-out"])
@pytest.mark.parametrize("columns_in", [True, False],
                         ids=["columns-in", "dense-in"])
def test_column_step_is_node_step_bit_for_bit(small_columns, columns_in,
                                              k_out, durable):
    """The cluster of the packed test stepped through node_step and
    through node_step_columns: the messages go in as columns whenever
    every source's fit the K of the case (else densely, as the runtime
    decides), come out as columns or, where a row overflows, through
    pack_outbox; state, StepInfo, mirrors and the dense outbox agree leaf
    for leaf, the columns are the outbox on its occupied columns, and the
    counts are the true ones."""
    small_columns(k_out)
    cfg = EngineConfig(n_peers=3, **BASE)
    N, G = cfg.n_peers, cfg.n_groups
    lay = column_layouts(cfg, durable)
    assert lay is not None and lay.columns.K == k_out
    assert lay.inputs == step_layouts(cfg, durable)[0]
    rng = np.random.default_rng(11)
    plain = [init_state(cfg, n, seed=3) for n in range(N)]
    cols = [init_state(cfg, n, seed=3) for n in range(N)]
    carries = [first_carry(lay) for n in range(N)]
    outboxes = [jax.device_get(Messages.empty(cfg))] * N
    tails = [np.zeros(G, np.int32)] * N
    seen = dict(columns_in=0, dense_in=0, columns_out=0, overflow_out=0)
    for t in range(40):
        inflight = jax.tree.map(lambda *a: np.stack(a), *outboxes)
        inboxes = jax.device_get(
            route(inflight, jnp.asarray(rng.random((N, N)) > 0.4)))
        outboxes = []
        for n in range(N):
            # What a drain delivers: only valid columns hold anything.
            inbox = jax.tree.map(lambda a: a[n], inboxes)
            inbox = _on_columns(inbox, _occupied(inbox))
            host = jax.device_get(HostInbox.empty(cfg)).replace(
                submit_n=rng.integers(0, cfg.max_submit + 1, G,
                                      dtype=np.int32),
                read_n=rng.integers(0, 3, G, dtype=np.int32),
                durable_tail=tails[n] if durable else None)
            plain[n], p_out, p_info = node_step(
                cfg, plain[n], *jax.tree.map(jnp.asarray, (inbox, host)))
            held = lay.columns.compact(inbox)
            fits = bool((lay.columns.view(held).n <= lay.columns.K).all())
            # HostInbox whole (its planes packed, a row buffer that holds
            # none): tests/test_plane_rows.py has the rows.  The columns
            # ride behind the rows, in the one buffer that goes up.
            up = lay.rows_in.whole(host)
            if columns_in and fits:
                bufs = lay.host.pack(host) + (np.concatenate([up, held]),)
                seen["columns_in"] += 1
            else:
                bufs = lay.inputs.pack((host, inbox)) + (up,)
                seen["dense_in"] += 1
            cols[n], carries[n], out, dense = node_step_columns(
                cfg, lay, columns_in and fits, cols[n], carries[n], bufs)
            tag = f"tick {t} node {n}"
            assert_trees_equal(cols[n], plain[n], tag)
            s = plain[n]
            h_back = lay.back.unpack(jax.device_get(
                pack_readback(lay, carries[n])))
            assert_trees_equal(
                h_back, Readback(
                    info=p_info, outbox=None, term=s.term,
                    voted_for=s.voted_for, role=s.role,
                    leader_id=s.leader_id, commit=s.commit, base=s.log.base,
                    base_term=s.log.base_term, heat=s.heat,
                    windows=h_back.windows), tag)
            assert_trees_equal(lay.columns.unstack(dense), p_out, tag)
            p_out = jax.device_get(p_out)
            occ = _occupied(p_out)
            out = jax.device_get(out)
            assert out.dtype == np.int32 and out.shape == (lay.columns.size,)
            view = lay.columns.view(out)
            np.testing.assert_array_equal(view.n, occ.sum(axis=1), tag)
            if (view.n <= lay.columns.K).all():
                seen["columns_out"] += 1
                assert_trees_equal(lay.columns.expand(out),
                                   _on_columns(p_out, occ), tag)
                for p in range(N):
                    np.testing.assert_array_equal(
                        view.lanes(p, view.row("ae_valid", p)),
                        np.nonzero(p_out.ae_valid[p])[0], tag)
            else:
                seen["overflow_out"] += 1
                assert_trees_equal(lay.outbox.unpack(jax.device_get(
                    pack_outbox(lay, dense))), p_out, tag)
            outboxes.append(p_out)
            tails[n] = np.asarray(p_info.log_tail)
    assert seen["columns_in"] > 20 if columns_in else not seen["columns_in"]
    assert seen["columns_out"] > 20
    assert seen["overflow_out"] > 20 if k_out < G else not seen["overflow_out"]
    assert not columns_in or k_out == G or seen["dense_in"] > 5


@pytest.mark.parametrize("counts", [(0, 1, 4), (4, 4, 4), (5, 0, 1),
                                    (3, 5, 4), (0, 0, 0)],
                         ids=lambda c: "-".join(map(str, c)))
def test_column_counts_per_row(small_columns, counts):
    """Rows of 0, 1, K and K + 1 columns, different in every row, on
    planes made by hand with junk outside the occupied columns: the device
    and the host compact alike, the count is the true one also beyond K,
    the first K columns are kept in lane order, and expanding what fits
    gives the planes back on their occupied columns."""
    small_columns(4)
    cfg = EngineConfig(n_peers=3, **{**BASE, "n_groups": 300})
    lay = column_layouts(cfg, True).columns
    assert (lay.P, lay.G, lay.K) == (3, 300, 4)
    rng = np.random.default_rng(sum(counts))
    tree = jax.tree.map(
        lambda a: (rng.integers(1, 99, a.shape, dtype=np.int32)
                   if a.dtype == np.int32 else np.zeros(a.shape, bool)),
        jax.device_get(Messages.empty(cfg)))
    valid = [f for f in tree.__dataclass_fields__ if f.endswith("_valid")]
    lanes = []
    for p, c in enumerate(counts):
        at = np.sort(rng.choice(cfg.n_groups, c, replace=False))
        lanes.append(at)
        for g in at:
            getattr(tree, valid[rng.integers(len(valid))])[p, g] = True
            tree.aer_success[p, g] = rng.random() < 0.5     # a plain flag
    pair = lay.compact(tree)
    assert pair.dtype == np.int32 and pair.shape == (lay.size,)
    np.testing.assert_array_equal(
        pair, jax.jit(lay.compact)(jax.tree.map(jnp.asarray, tree)))
    n, held, _, _ = lay._parts(pair)
    np.testing.assert_array_equal(n, counts)
    for p, at in enumerate(lanes):
        np.testing.assert_array_equal(held[p, :min(len(at), lay.K)],
                                      at[:lay.K])
        assert (held[p, len(at):] == lay.G).all()
    if max(counts) <= lay.K:
        want = _on_columns(tree, _occupied(tree))
        assert_trees_equal(lay.expand(pair), want)
        assert_trees_equal(jax.jit(lay.expand)(jnp.asarray(pair)), want)
        # host round trip: the identity, in both orders
        np.testing.assert_array_equal(pair, lay.compact(lay.expand(pair)))


def test_a_new_field_finds_its_place_in_the_columns(small_columns):
    """The column layout follows the tree: a kind nobody listed anywhere
    takes its words and flags and, being named ``*_valid``, counts as
    occupancy."""
    small_columns(3)
    P, G = 2, 10
    tree = {"a_valid": np.zeros((P, G), bool),
            "a_x": np.zeros((P, G), np.int32),
            "a_wide": np.zeros((P, G, 5), np.int32),
            "a_flag": np.zeros((P, G), bool)}
    before = ColumnLayout(tree)
    assert (before.W, before.F, len(before.occupancy)) == (6, 2, 1)
    tree["zz_valid"] = np.zeros((P, G), bool)
    tree["zz_word"] = np.zeros((P, G, 2), np.int32)
    lay = ColumnLayout(tree)
    assert (lay.W, lay.F, len(lay.occupancy)) == (8, 3, 2) and lay != before
    # count, lanes and words a row, then a byte a flag: 18 in 5 words
    assert lay.size == P * (1 + 3 + 3 * 8) + 5 and lay.nbytes == 4 * lay.size
    assert lay.alloc().shape == (lay.size,)
    tree["zz_valid"][1, 7] = tree["a_valid"][1, 2] = True
    tree["zz_word"][1, 7] = (5, 6)
    tree["a_wide"][1, 2] = np.arange(5)
    tree["a_x"][0, 3] = 9           # not in an occupied column: stays behind
    pair = lay.compact(tree)
    view = lay.view(pair)
    assert view.n.tolist() == [0, 2] and view.columns == 2
    assert view.lanes(1, view.row("zz_valid", 1)).tolist() == [7]
    assert view.fields(1)["zz_word"][np.array([7])].tolist() == [[5, 6]]
    back = lay.expand(pair)
    assert back["a_x"].sum() == 0 and back["zz_word"][1, 7].tolist() == [5, 6]
    np.testing.assert_array_equal(back["a_wide"], tree["a_wide"])
    with pytest.raises(TypeError, match="int32 or bool"):
        ColumnLayout({"x": np.zeros((P, G), np.float32)})


# ---------------------------------------- (e) the [G] planes as rows ----


@pytest.mark.parametrize("count", [0, 1, 4, 5, 300],
                         ids=lambda c: f"{c}-rows")
def test_row_counts_round_trip(count):
    """0, 1, K, K + 1 and G moved lanes of a Readback made by hand (junk
    everywhere, so every lane moves unless told not to): the device and
    the host compact alike, the count is the true one also beyond K, the
    first K lanes are kept ascending with every plane's value, the leaves
    that are no planes ride the header, and writing what fits over the
    planes it was compared with gives the planes back."""
    G, K = 300, 4
    rng = np.random.default_rng(count)
    tree = {"info": {"log_tail": rng.integers(1, 99, G, dtype=np.int32),
                     "ready": rng.random(G) < 0.5,
                     "acc": np.zeros(G, np.int32),
                     "abort": np.zeros(G, bool),
                     "start": rng.integers(1, 99, G, dtype=np.int32)},
            "term": rng.integers(1, 99, G, dtype=np.int32),
            "sums": np.arange(5, dtype=np.int32), "veto": np.asarray(True)}
    lay = RowLayout(tree, G, K, levels=["info.log_tail", "info.ready", "term"],
                    carried=["info.start"])
    assert (lay.W, lay.F, lay.H) == (4, 2, 6)
    assert (lay.Lw, lay.Ew, lay.Lf, lay.Ef) == (2, 1, 1, 1)
    # count, header, lanes and words, then a byte a flag
    assert lay.size == 1 + 6 + K + 4 * K + 2 * K // 4
    # ``count`` lanes move: a level differs from what the other side
    # holds, or an event happened.
    at = np.sort(rng.choice(G, count, replace=False))
    for i, g in enumerate(at):
        if i % 6 == 2:
            tree["info"]["acc"][g] = 7
        elif i % 6 == 5:
            tree["info"]["abort"][g] = True
    words, flags, header = lay.stack(tree)
    assert header.tolist() == [0, 1, 2, 3, 4, 1]
    assert_trees_equal(lay.unstack(words, flags, header), tree)
    prev_w, prev_f = words.copy(), flags.copy()
    for i, g in enumerate(at):
        if i % 3 == 0:
            prev_w[rng.integers(lay.Lw), g] += 1
        elif i % 3 == 1:
            prev_f[0, g] ^= True
    prev_w[lay.Lw:] = prev_f[lay.Lf:] = 0       # events are not kept
    moved = lay.moved(words, flags, prev_w, prev_f)
    np.testing.assert_array_equal(np.nonzero(moved)[0], at)
    pair = lay.compact(words, flags, header, moved)
    assert pair.dtype == np.int32 and pair.shape == (lay.size,)
    on_device = jax.jit(lambda w, f, h, pw, pf: lay.compact(
        w, f, h, lay.moved(w, f, pw, pf)))(
            *map(jnp.asarray, (words, flags, header, prev_w, prev_f)))
    np.testing.assert_array_equal(pair, np.asarray(on_device))
    view = lay.view(pair)
    assert view.n == count and view.head("sums").tolist() == [0, 1, 2, 3, 4]
    assert bool(view.head("veto")) is True
    held = min(count, K)
    np.testing.assert_array_equal(view.ids[:held], at[:K])
    assert (view.ids[held:] == G).all()
    np.testing.assert_array_equal(view.field("info.start")[:held],
                                  tree["info"]["start"][at[:K]])
    np.testing.assert_array_equal(view.field("info.abort")[:held],
                                  tree["info"]["abort"][at[:K]])
    if count <= K:
        # The levels the other side holds, patched by the rows, are the
        # planes; so are zero event planes written at the rows.
        base_w, base_f = prev_w.copy(), prev_f.copy()
        base_w[lay.Lw + lay.Ew:] = words[lay.Lw + lay.Ew:]  # carried: as is
        for got in (lay.expand(pair, base_w, base_f),
                    jax.jit(lay.expand)(jnp.asarray(pair),
                                        jnp.asarray(base_w),
                                        jnp.asarray(base_f))):
            np.testing.assert_array_equal(np.asarray(got[0]), words)
            np.testing.assert_array_equal(np.asarray(got[1]), flags)
            np.testing.assert_array_equal(np.asarray(got[2]), header)
    # A whole upload: no row, a count of -1 and the header.
    whole = lay.view(lay.whole(tree))
    assert whole.n == -1 and whole.head("sums").tolist() == [0, 1, 2, 3, 4]
    with pytest.raises(ValueError, match="no \\[G\\] leaf"):
        RowLayout(tree, G, K, levels=["sums"])
    with pytest.raises(TypeError, match="int32 or bool"):
        RowLayout({"x": np.zeros(G, np.float32)}, G, K)


@pytest.mark.parametrize("config, columns, words, buffers", [
    ("coord-1g-3v", False, 1, 1), ("multiraft-1k-3v", False, 1, 1),
    ("multiraft-10k-3v", False, 2, 2), ("multiraft-10k-3v-hb2", False, 2, 2),
    ("multiraft-100k-3v", True, 10, 11),
    ("multiraft-100k-3v-hib", True, 10, 11),
])
def test_shape_rule_on_the_benchmarks_configurations(config, columns, words,
                                                     buffers):
    """Which program a node runs follows from its shape alone: the cells
    whose dense ``int32`` planes take fewer than COLUMN_BUFFERS buffers
    keep node_step_packed (at 10,000 lanes, two buffers, columns cost the
    chip more than they saved: PERF.md, PR 35), the 100,000-Region cells
    take columns.  The count is of the buffers that hold words, which are
    the ones the words had while the flags had buffers of their own
    (``words``: 1, 2 and 10 then as now): that the flags now ride behind
    them moves no shape across the rule, and takes a buffer a way off
    every packed step (``buffers``: what the dense operand crosses in;
    2, 3 and 12 before)."""
    import json
    import os
    from rafting_tpu.api import RaftConfig
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           config + ".json")) as f:
        raft = json.load(f)["raft_config"]
    uris = [f"raft://127.0.0.1:{7001 + i}" for i in range(3)]
    cfg = RaftConfig(local=uris[0], peers=tuple(uris[1:]),
                     data_dir="unused", **raft).engine_config()
    lay = column_layouts(cfg, True)
    assert (lay is not None) == columns
    inputs, _ = step_layouts(cfg, True)
    assert sum(w > 0 for w in inputs.words) == words
    assert (words >= COLUMN_BUFFERS) == columns
    assert len(inputs.buffers) == buffers
