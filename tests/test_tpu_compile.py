"""AOT compiles for a described TPU v5e: the kernels and the step of the
served path at real sizes, compiled by the chip's own compiler with no chip
attached (`/opt/skills/guides/on-chip-measurement` section 2).  They refuse
here what the chip would refuse — a kernel that does not lower, a program
that does not fit — at no chip time.  A compile that passes is not a run.

Only one process at a time may load the TPU library, and the driver runs
the tests with several workers: so the topology is described inside a
fixture of THIS file (nothing at import, in a skipif, in parametrize or in
conftest.py), every compile happens in the test's own process, and all
cases stay in this one file.
"""

import dataclasses
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from rafting_tpu.core.packing import Layout
from rafting_tpu.core.step import (
    WINDOW_SUMS, column_layouts, compact_readback, first_carry, node_step,
    node_step_columns, node_step_packed, step_layouts)
from rafting_tpu.core.types import HostInbox, Messages, init_state
from rafting_tpu.ops.quorum import quorum_commit_pallas

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM_BYTES = 16 * 1024 ** 3      # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    """Shapes of ``tree``'s leaves, placed on the described chip."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _cell_engine(config):
    """The engine as a benchmark configuration's file builds it."""
    from rafting_tpu.api import RaftConfig
    with open(os.path.join(REPO, "benchmark", "configs",
                           config + ".json")) as f:
        raft = json.load(f)["raft_config"]
    uris = [f"raft://127.0.0.1:{7001 + i}" for i in range(3)]
    return RaftConfig(local=uris[0], peers=tuple(uris[1:]),
                      data_dir="unused", **raft).engine_config()


def _lower_step(sharding, cfg, packed):
    """``node_step`` as the pipelined runtime feeds it (durable-tail lane
    present), or ``node_step_packed``, the program the runtime calls,
    lowered for the described chip at ``cfg``'s shape."""
    state = _on(sharding, jax.eval_shape(lambda: init_state(cfg, 0, seed=0)))
    inbox = _on(sharding, jax.eval_shape(lambda: Messages.empty(cfg)))
    host = _on(sharding, jax.eval_shape(
        lambda: HostInbox.empty(cfg).replace(
            durable_tail=jnp.zeros((cfg.n_groups,), jnp.int32))))
    if not packed:
        return node_step.lower(cfg, state, inbox, host)
    inputs, _ = step_layouts(cfg, True)
    assert inputs == Layout((host, inbox))
    bufs = tuple(jax.ShapeDtypeStruct((n,), dt, sharding=sharding)
                 for dt, n in inputs.buffers)
    return node_step_packed.lower(cfg, inputs, state, bufs)


@pytest.mark.parametrize("n_peers", [3, 5])
def test_quorum_kernel_compiles_at_100k_groups(one_chip, n_peers):
    G = 100_000
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    compiled = quorum_commit_pallas.lower(
        i32(G, n_peers), i32(G), i32(5, G), False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("packed", [False, True],
                         ids=["node_step", "node_step_packed"])
def test_node_step_fits_one_chip_at_the_smoke_shape(one_chip, packed):
    """node_step at the shape chip_smoke.py serves at, as the pipelined
    runtime feeds it (durable-tail lane present), and node_step_packed,
    the program the runtime calls: the same step between the unpacking of
    the upload buffers and the packing of the result buffers.  Three nodes
    share the chip: three states resident, and in the worst case three
    steps' outputs and temporaries in flight at once."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    import chip_smoke
    uris = [f"raft://127.0.0.1:{7001 + i}" for i in range(3)]
    cfg = chip_smoke.served_config(
        uris, 0, chip_smoke.SERVED_LANES, "unused").engine_config()
    assert cfg.n_groups == chip_smoke.SERVED_LANES and cfg.n_peers == 3

    lowered = _lower_step(one_chip, cfg, packed)
    if packed:
        inputs, readback = step_layouts(cfg, True)
        # 48 MB each way at this shape: in pieces (core/packing.py).
        assert len(inputs.buffers) > 2 and len(readback.buffers) > 2
        _, out = lowered.out_info
        assert tuple((np.dtype(o.dtype), o.shape[0]) for o in out) \
            == readback.buffers
    mem = lowered.compile().memory_analysis()
    per_node = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
    assert 3 * per_node < HBM_BYTES, mem


@pytest.mark.parametrize("config, carry", [("multiraft-10k-3v", 0),
                                           ("multiraft-10k-3v-hb2", 1)])
@pytest.mark.parametrize("packed", [False, True],
                         ids=["node_step", "node_step_packed"])
def test_node_step_holds_no_gather_and_no_scatter(one_chip, packed, config,
                                                  carry):
    """The step addresses its rings, the read FIFO and the peer planes by
    compare-and-select along the axis (ops/select.py): at the shape of the
    10,000-Region cell, as its configuration file builds the engine, the
    chip's compiler is left with no gather and no scatter (35 of them
    were 8 of a step's 11 ms on the chip), and with no [G, K, L] one-hot
    among its temporaries (25,023,488 bytes of them before; the one-hot of
    the AppendEntries build alone would be 61 MB).  The same on TiKV's own
    heartbeat, where the lease is carried (core/step.py phase 6b: a second
    pass of the read barrier and two guard lanes)."""
    cfg = _cell_engine(config)
    assert (cfg.n_groups, cfg.n_peers, cfg.log_slots, cfg.batch,
            cfg.max_submit, cfg.read_slots) == (10_000, 3, 64, 8, 8, 4)
    assert cfg.lease_carry_ticks == carry

    lowered = _lower_step(one_chip, cfg, packed)
    if packed:
        # The served program: the window sums (core/step.py window_sums,
        # five reductions over the [G, P] planes the step has written)
        # are in it, and come down in a buffer that crossed already.
        _, readback = step_layouts(cfg, True)
        assert (len(WINDOW_SUMS),) in [shape for *_, shape in readback.slots]
        _, out = lowered.out_info
        # Two buffers each way (three while the flags had one of their own).
        assert tuple((np.dtype(o.dtype), o.shape[0]) for o in out) \
            == readback.buffers and len(out) == 2
    compiled = lowered.compile()
    hlo = compiled.as_text()
    found = {op: len(re.findall(rf"\b{op}\(", hlo))
             for op in ("gather", "scatter")}
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"{found} temp_size_in_bytes={temp}")
    assert found == {"gather": 0, "scatter": 0}
    assert temp < 32 * 1024 ** 2, temp


@pytest.mark.parametrize("config, strict", [("coord-1g-3v", False),
                                            ("coord-1g-3v-ri", True)])
def test_the_strict_step_compiles_beside_the_lease_one(one_chip, config,
                                                       strict):
    """node_step_packed as the two coordination-service cells build it,
    through the chip's compiler: with ``read_lease`` off (PR 45's cell:
    the stamp counter, a word on an AppendEntries and one on its reply)
    the step stays one program with no gather and no scatter, takes and
    returns one buffer, and its operand is longer by those two [P, G]
    words and nothing else."""
    cfg = _cell_engine(config)
    assert (cfg.n_groups, cfg.n_peers, cfg.read_lease) == (16, 3, not strict)
    inputs, readback = step_layouts(cfg, True)
    assert len(inputs.buffers) == len(readback.buffers) == 1
    lease_cfg = dataclasses.replace(cfg, read_lease=True)
    extra = 2 * cfg.n_peers * cfg.n_groups if strict else 0
    assert sum(inputs.words) \
        == sum(step_layouts(lease_cfg, True)[0].words) + extra
    compiled = _lower_step(one_chip, cfg, packed=True).compile()
    hlo = compiled.as_text()
    assert {op: len(re.findall(rf"\b{op}\(", hlo))
            for op in ("gather", "scatter")} == {"gather": 0, "scatter": 0}


def _index_rows(hlo):
    """(op, index rows) of every gather and scatter of a compiled module's
    text: how many index tuples its indices operand holds."""
    shapes = dict(re.findall(r"%([\w.-]+) = \w+\[([\d,]*)\]", hlo))
    out = []
    for op, args, rest in re.findall(
            r"\b(gather|scatter)\(([^)]*)\)([^\n]*)", hlo):
        indices = re.findall(r"%([\w.-]+)", args)[1]
        dims = [int(d) for d in shapes[indices].split(",") if d]
        vector = int(re.search(r"index_vector_dim=(\d+)", rest).group(1))
        if vector < len(dims):      # else: scalar indices, no such axis
            dims.pop(vector)
        out.append((op, int(np.prod(dims, dtype=np.int64))))
    return out


@pytest.mark.parametrize("columns_in, config", [
    (True, "multiraft-100k-3v"), (False, "multiraft-100k-3v"),
    (True, "multiraft-100k-3v-hib")],
    ids=["columns-in", "dense-in", "columns-in-hibernate"])
def test_column_step_compiles_at_100k_lanes_and_addresses_k_rows(
        one_chip, columns_in, config):
    """node_step_columns at the 100,000-Region cell's shape, as its
    configuration file builds the engine, in both forms of its operand:
    it fits the chip three nodes at a time, and whatever gathers and
    scatters the chip's compiler is left with (the expansion of the
    inbox's columns, the compaction of the outbox) address at most P x K
    index rows a leaf (the [P, G] leaves of a kind share one scatter and
    one gather, an index row a leaf, peer and column): none walks the G
    lanes.  The same with hibernation compiled in (PR 41's cell: three
    more lanes of state, a flag each way on the wire, one more level and
    one more row field), which adds no gather and no scatter."""
    cfg = _cell_engine(config)
    assert (cfg.n_groups, cfg.n_peers) == (100_000, 3)
    assert cfg.hibernate == config.endswith("-hib")
    lay = column_layouts(cfg, True)
    assert lay is not None
    P, K = cfg.n_peers, lay.columns.K
    assert lay.columns.nbytes <= 512 * 1024     # one transfer's fixed cost
    # The window sums ride the [G] part of the readback, whose buffers are
    # as many as before them (20 bytes more in one that crossed already).
    assert (len(WINDOW_SUMS),) in [shape for *_, shape in lay.back.slots]
    assert len(lay.back.buffers) == 4

    words = lambda n: jax.ShapeDtypeStruct((n,), jnp.int32,
                                           sharding=one_chip)
    bufs = lambda layout: tuple(words(n) for _, n in layout.buffers)

    state = _on(one_chip, jax.eval_shape(lambda: init_state(cfg, 0, seed=0)))
    carry = _on(one_chip, jax.eval_shape(lambda: first_carry(lay)))
    # One buffer beside the planes: the rows, and the columns behind them.
    operand = bufs(lay.host) + (words(lay.rows_in.size + lay.columns.size),) \
        if columns_in else bufs(lay.inputs) + (words(lay.rows_in.size),)
    lowered = node_step_columns.lower(
        cfg, lay, columns_in, state, carry, operand)
    assert lowered.out_info[2].shape == (lay.columns.size,)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    per_node = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
    assert 3 * per_node < HBM_BYTES, mem
    found = _index_rows(compiled.as_text())
    print(f"K={K} {len(found)} gathers and scatters, "
          f"most index rows {max(r for _, r in found)}: {found}")
    # The [G] planes' rows going in: one scatter a kind (the K_in rows of
    # HostInbox over the resident planes), beside the columns' own.
    rin, rout = lay.rows_in, lay.rows_out
    most = max(lay.columns.Ws * P * K, lay.columns.Fs * P * K,
               rin.W * rin.K)
    assert found and all(rows <= most < cfg.n_groups
                         for _, rows in found), found
    assert len(found) <= 14, found      # not one a leaf: some 50 us each
    assert "scatter" in {op for op, _ in found}
    # ... and coming out, in the program of their own: one gather a kind
    # (the K_out rows that moved) and the one-block-a-row gather of the
    # search for them.
    # ... and ONE result: the rows and, behind them, the outbox's columns.
    rows = compact_readback.lower(lay, carry, carry, words(lay.columns.size))
    assert rows.out_info.shape == (lay.rows_out.size + lay.columns.size,)
    rows = rows.compile()
    found = _index_rows(rows.as_text())
    print(f"compact_readback: {found}")
    assert found and {op for op, _ in found} == {"gather"}, found
    assert all(n <= rout.W * rout.K < cfg.n_groups for _, n in found), found
    assert len(found) <= 3, found
