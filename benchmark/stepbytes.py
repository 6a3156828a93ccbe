"""The bytes one execution of the step program must move, from shapes alone.

The step (``core/step.py node_step``) is elementwise and small-reduction
work over per-group lanes: no matrix multiplication, so its roofline is the
memory one.  The least it can move: every leaf of the state pytree read once
and written once (the state is donated and returned), the inbox and the host
inbox planes read, the outbox and the info planes written.  ``nbytes`` come
from ``jax.eval_shape``; nothing runs.
"""

from __future__ import annotations

from typing import Dict


def _nbytes(tree) -> int:
    import jax
    return sum(int(l.size) * l.dtype.itemsize for l in jax.tree.leaves(tree))


def step_bytes(engine_cfg) -> Dict[str, int]:
    """``engine_cfg``: the node's ``EngineConfig``.  Shapes as the pipelined
    runtime calls the step (durable-tail lane present)."""
    import jax
    import jax.numpy as jnp

    from rafting_tpu.core.step import node_step
    from rafting_tpu.core.types import HostInbox, Messages, init_state

    state = jax.eval_shape(lambda: init_state(engine_cfg, 0, seed=0))
    inbox = jax.eval_shape(lambda: Messages.empty(engine_cfg))
    host = jax.eval_shape(lambda: HostInbox.empty(engine_cfg).replace(
        durable_tail=jnp.zeros((engine_cfg.n_groups,), jnp.int32)))
    new_state, outbox, info = jax.eval_shape(
        lambda s, i, h: node_step(engine_cfg, s, i, h), state, inbox, host)
    parts = {"state_read": _nbytes(state), "state_written": _nbytes(new_state),
             "inbox_read": _nbytes(inbox), "host_inbox_read": _nbytes(host),
             "outbox_written": _nbytes(outbox), "info_written": _nbytes(info)}
    parts["total"] = sum(parts.values())
    return parts


def roofline_share_pct(bytes_moved: int, seconds: float,
                       peak_bytes_per_s: float) -> float:
    """Least time the device could take over the time it took, in %."""
    return 100.0 * (bytes_moved / peak_bytes_per_s) / seconds
