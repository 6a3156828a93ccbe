"""Multi-chip Mesh sharding path under pytest (VERDICT r3 #3).

Covers the EXACT program ``__graft_entry__.dryrun_multichip`` runs — a whole
N-node cluster sharded over a ``Mesh('node', 'group')`` via
``core/shard.py shard_cluster``, advanced with the fused multi-tick scan —
so a sharding regression fails ``pytest tests/``, not only the driver
artifact (round-2 lesson: green suite, red artifact).

Parity contract: the sharded and unsharded runs are THE SAME jitted
program on the same inputs, so the results must agree bit-exactly.  The
conftest pins an 8-device virtual CPU platform (the driver validates the
same path on N virtual devices; on real hardware the node-axis transpose
rides the interconnect)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from rafting_tpu.core.shard import (
    shard_cluster, state_pspecs, validate_cluster_shapes,
)
from rafting_tpu.core.sim import run_cluster_ticks
from rafting_tpu.core.types import (
    EngineConfig, LEADER, Messages, RaftState, StepInfo, init_state,
)


def _stacked_cluster(cfg):
    N = cfg.n_peers
    states = jax.tree.map(lambda *xs: jnp.stack(xs),
                          *[init_state(cfg, i, seed=0) for i in range(N)])
    inflight = jax.tree.map(lambda a: jnp.broadcast_to(a, (N,) + a.shape),
                            Messages.empty(cfg))
    info = jax.tree.map(lambda a: jnp.broadcast_to(a, (N,) + a.shape),
                        StepInfo.empty(cfg))
    conn = jnp.ones((N, N), jnp.bool_)
    submit = jnp.full((N, cfg.n_groups), 2, jnp.int32)
    return states, inflight, info, conn, submit


def _mesh(n_nodes: int, n_shard: int) -> Mesh:
    devices = jax.devices()
    assert len(devices) >= n_nodes * n_shard, \
        "conftest must pin 8 virtual CPU devices"
    return Mesh(np.asarray(devices[:n_nodes * n_shard])
                .reshape(n_nodes, n_shard), ("node", "group"))


def test_sharded_matches_unsharded_bitexact():
    """The dryrun program: shard over a (4 node x 2 group) mesh, run the
    fused 64-tick scan, compare against the identical unsharded run."""
    cfg = EngineConfig(n_groups=256, n_peers=4, log_slots=32, batch=4,
                       max_submit=4, election_ticks=10, heartbeat_ticks=3)
    # Unsharded baseline (fresh inputs; run_cluster_ticks donates its args).
    s0, m0, i0, conn0, sub0 = _stacked_cluster(cfg)
    ref_states, _, ref_info = run_cluster_ticks(cfg, 64, s0, m0, i0,
                                                conn0, sub0)

    s1, m1, i1, conn1, sub1 = _stacked_cluster(cfg)
    mesh = _mesh(4, 2)
    s1, m1, i1, conn1, sub1 = shard_cluster(mesh, cfg, s1, m1, i1,
                                            conn1, sub1)
    sh_states, _, sh_info = run_cluster_ticks(cfg, 64, s1, m1, i1,
                                              conn1, sub1)

    assert ref_states.lease is not None      # heartbeat 3: a carried lease
    for f in dataclasses.fields(RaftState):
        if f.name == "log":
            continue
        # a plain lane, or a sub-state of lanes (the lease's guards)
        a = jax.tree.leaves(getattr(ref_states, f.name))
        b = jax.tree.leaves(getattr(sh_states, f.name))
        assert len(a) == len(b) and all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(a, b)), f"state field {f.name} diverged"
    for f in dataclasses.fields(type(ref_states.log)):
        a = np.asarray(getattr(ref_states.log, f.name))
        b = np.asarray(getattr(sh_states.log, f.name))
        assert np.array_equal(a, b), f"log field {f.name} diverged"
    for f in dataclasses.fields(StepInfo):
        a = np.asarray(getattr(ref_info, f.name))
        b = np.asarray(getattr(sh_info, f.name))
        assert np.array_equal(a, b), f"info field {f.name} diverged"

    # And the run must be a healthy cluster, not vacuous agreement.
    roles = np.asarray(sh_states.role)
    assert ((roles == LEADER).sum(axis=0) == 1).all(), "one leader per group"
    assert (np.asarray(sh_states.commit).max(axis=0) > 0).all()


def test_sharded_bench_shape_5peer_L256():
    """A deep-ring shape on a sharded mesh (VERDICT r4 #4): 5 peers
    and L=256 — config-4's peer count with a 256-slot ring — with
    the node axis replicated (5 does not divide the device count; the
    group axis carries the parallelism, exactly the single-chip scaling
    story) and the group axis split 8 ways.  Bit-exact parity with the
    unsharded run plus cluster health."""
    cfg = EngineConfig(n_groups=512, n_peers=5, log_slots=256, batch=32,
                       max_submit=32, election_ticks=10, heartbeat_ticks=3,
                       rpc_timeout_ticks=8)
    s0, m0, i0, conn0, sub0 = _stacked_cluster(cfg)
    ref_states, _, _ = run_cluster_ticks(cfg, 48, s0, m0, i0, conn0, sub0)

    s1, m1, i1, conn1, sub1 = _stacked_cluster(cfg)
    mesh = _mesh(1, 8)
    s1, m1, i1, conn1, sub1 = shard_cluster(mesh, cfg, s1, m1, i1,
                                            conn1, sub1)
    sh_states, _, _ = run_cluster_ticks(cfg, 48, s1, m1, i1, conn1, sub1)

    assert np.array_equal(np.asarray(ref_states.commit),
                          np.asarray(sh_states.commit))
    assert np.array_equal(np.asarray(ref_states.term),
                          np.asarray(sh_states.term))
    roles = np.asarray(sh_states.role)
    assert ((roles == LEADER).sum(axis=0) == 1).all()
    assert (np.asarray(sh_states.commit).max(axis=0) > 0).all()


def test_sharded_scale_32k_groups():
    """The dryrun's new scale point (G=32k over a 4x2 mesh, VERDICT r4
    #4) under pytest, so the node-axis all-to-all is exercised at a
    realistic group extent in the suite, not only in the driver artifact.
    Health-checked (not parity — a second unsharded 32k run would double
    an already long test)."""
    cfg = EngineConfig(n_groups=32_768, n_peers=4, log_slots=32, batch=4,
                       max_submit=4, election_ticks=10, heartbeat_ticks=3)
    s, m, i, conn, sub = _stacked_cluster(cfg)
    mesh = _mesh(4, 2)
    s, m, i, conn, sub = shard_cluster(mesh, cfg, s, m, i, conn, sub)
    states, _, _ = run_cluster_ticks(cfg, 64, s, m, i, conn, sub)
    roles = np.asarray(states.role)
    assert ((roles == LEADER).sum(axis=0) == 1).all(), "one leader per group"
    commit = np.asarray(states.commit)
    assert (commit.max(axis=0) > 0).all(), "every group commits at 32k"


def test_shard_specs_land_on_declared_axes():
    """The group axis of every sharded array is split over the 'group' mesh
    axis and the node axis over 'node' — checked via the addressable shard
    shapes, so a spec typo (e.g. size-based inference collision) fails."""
    cfg = EngineConfig(n_groups=64, n_peers=2, log_slots=16, batch=4,
                       max_submit=4, election_ticks=10, heartbeat_ticks=3)
    s, m, i, conn, sub = _stacked_cluster(cfg)
    mesh = _mesh(2, 4)
    s, m, i, conn, sub = shard_cluster(mesh, cfg, s, m, i, conn, sub)
    # term: [N=2, G=64] split 2 x 4 -> local shard [1, 16]
    shard = s.term.addressable_shards[0]
    assert shard.data.shape == (1, 16), shard.data.shape
    # message plane: [N, P, G] -> node and group axes split, peer replicated
    shard = m.ae_valid.addressable_shards[0]
    assert shard.data.shape == (1, 2, 16), shard.data.shape
    # log ring: [N, G, L] -> L replicated
    shard = s.log.term.addressable_shards[0]
    assert shard.data.shape == (1, 16, 16), shard.data.shape


def test_validate_cluster_shapes_rejects_mismatch():
    """Negative: a shape whose declared group axis does not hold G fails
    validation loudly (the guard that makes per-field specs safe)."""
    cfg = EngineConfig(n_groups=64, n_peers=2, log_slots=16, batch=4,
                       max_submit=4, election_ticks=10, heartbeat_ticks=3)
    s, m, i, conn, sub = _stacked_cluster(cfg)
    bad = s.replace(term=s.term[:, :32])      # G axis halved
    with pytest.raises(AssertionError):
        validate_cluster_shapes(cfg, bad, m, i, conn, sub)
    with pytest.raises(AssertionError):
        validate_cluster_shapes(cfg, s, m, i, conn[:1], sub)
    with pytest.raises(AssertionError):
        validate_cluster_shapes(
            cfg, s, m.replace(ae_valid=m.ae_valid[..., :32]), i, conn, sub)
