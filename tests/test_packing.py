"""Packed pytrees (core/packing.py) and the packed step the served path
runs (core/step.py node_step_packed): the layout follows the dataclasses
through every optional subtree, the host side is views and not copies, the
packed step is node_step bit for bit, and a tick's upload buffers are its
own until its host phase is done with them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rafting_tpu.core.cluster import route
from rafting_tpu.core import packing
from rafting_tpu.core.packing import Layout
from rafting_tpu.core.step import (
    Readback, node_step, node_step_packed, step_layouts,
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
    # At the sizes where a call's fixed cost matters: two buffers each way.
    assert len(inputs.buffers) == len(readback.buffers) == 2
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
                base_term=s.log.base_term, heat=s.heat), tag)
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


def test_host_round_trip_is_the_identity_and_unpack_gives_views():
    tree = _tree(np.random.default_rng(1))
    layout = Layout(tree)
    assert layout.buffers == ((np.int32, 5 + 24 + 1), (np.uint8, 12 + 1 + 7))
    buffers = layout.pack(tree)
    assert [(b.dtype, b.size) for b in buffers] == list(layout.buffers)
    back = layout.unpack(buffers)
    assert_trees_equal(back, tree)
    for leaf in jax.tree.leaves(back):
        assert any(np.shares_memory(leaf, b) for b in buffers)
        assert leaf.flags.c_contiguous
    # Filled in place: what is written through a view is in the buffer.
    fresh = layout.alloc()
    assert not any(b.any() for b in fresh)
    views = layout.unpack(fresh)
    for view, leaf in zip(jax.tree.leaves(views), jax.tree.leaves(tree)):
        view[...] = leaf
    for got, want in zip(fresh, buffers):
        np.testing.assert_array_equal(got, want)


def test_device_round_trip_matches_the_host_layout():
    """pack under jit lays the leaves out where the host's unpack finds
    them, and unpack under jit finds what the host's pack laid out."""
    tree = _tree(np.random.default_rng(2))
    layout = Layout(tree)
    buffers = jax.device_get(
        jax.jit(layout.pack)(jax.tree.map(jnp.asarray, tree)))
    assert_trees_equal(layout.unpack(buffers), tree)
    assert_trees_equal(jax.jit(layout.unpack)(layout.pack(tree)), tree)


def test_a_buffer_is_closed_at_the_chunk_bound(monkeypatch):
    """Whole leaves, in flatten order, a new buffer of the kind once the
    next leaf would pass CHUNK_BYTES; a leaf larger than the bound has a
    buffer to itself; both sides of the boundary agree on the pieces."""
    monkeypatch.setattr(packing, "CHUNK_BYTES", 64)
    i32 = lambda n, v: np.full(n, v, np.int32)
    tree = [i32(10, 1), np.ones(40, bool), i32(6, 2), i32(1, 3),
            np.zeros(30, bool), i32(40, 4), i32(2, 5)]
    layout = Layout(tree)
    assert layout.buffers == (
        (np.int32, 16), (np.uint8, 40), (np.int32, 1), (np.uint8, 30),
        (np.int32, 40), (np.int32, 2))
    buffers = layout.pack(tree)
    assert_trees_equal(layout.unpack(buffers), tree)
    on_device = jax.jit(layout.pack)(jax.tree.map(jnp.asarray, tree))
    for got, want in zip(jax.device_get(on_device), buffers):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)
    assert layout != Layout(tree[:-1])
    monkeypatch.undo()
    assert len(Layout(tree).buffers) == 2


def test_a_leaf_that_is_neither_int32_nor_bool_is_refused():
    with pytest.raises(TypeError, match="int32 or bool"):
        Layout({"x": np.zeros(3, np.float32)})


# ----------------------------------- (c) a pending tick keeps its buffers ----


def test_next_dispatch_leaves_a_pending_ticks_arrays_alone(tmp_path):
    """Overlapped order (tick() driven with no deadline): the tick that
    is pending while the next one dispatches still reads its own inbox
    planes, which the next dispatch neither reuses nor overwrites."""
    cfg = EngineConfig(n_peers=3, **BASE)
    c = LocalCluster(cfg, str(tmp_path), seed=1, pipeline=True)
    try:
        c.wait_leader(0)
        node = c.nodes[0]
        held = []       # (the pending tick's planes, their copies)
        for _ in range(6):
            c.tick()
            arrays = node._pending.arrays
            if held:
                before, copies = held[-1]
                for name, plane in before.items():
                    np.testing.assert_array_equal(plane, copies[name], name)
                    assert not np.shares_memory(plane, arrays[name])
            held.append((arrays, {k: v.copy() for k, v in arrays.items()}))
        assert sum(bool(a["ae_valid"].any() or a["aer_valid"].any())
                   for a, _ in held) >= 4, "no traffic reached the node"
    finally:
        c.close()
