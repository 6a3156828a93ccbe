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

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from rafting_tpu.core.packing import Layout
from rafting_tpu.core.step import node_step, node_step_packed, step_layouts
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

    state = _on(one_chip, jax.eval_shape(lambda: init_state(cfg, 0, seed=0)))
    inbox = _on(one_chip, jax.eval_shape(lambda: Messages.empty(cfg)))
    host = _on(one_chip, jax.eval_shape(
        lambda: HostInbox.empty(cfg).replace(
            durable_tail=jnp.zeros((cfg.n_groups,), jnp.int32))))
    if packed:
        inputs, readback = step_layouts(cfg, True)
        assert inputs == Layout((host, inbox))
        # 48 MB each way at this shape: in pieces (core/packing.py).
        assert len(inputs.buffers) > 2 and len(readback.buffers) > 2
        bufs = tuple(jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)
                     for dt, n in inputs.buffers)
        lowered = node_step_packed.lower(cfg, inputs, state, bufs)
        _, out = lowered.out_info
        assert tuple((np.dtype(o.dtype), o.shape[0]) for o in out) \
            == readback.buffers
    else:
        lowered = node_step.lower(cfg, state, inbox, host)
    mem = lowered.compile().memory_analysis()
    per_node = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
    assert 3 * per_node < HBM_BYTES, mem
