"""Hot-path lint (tier-1): the columnar rewrites that feed the host
phase must not silently regress into per-group Python loops.

The durable tick's cost model is O(groups-VISITED), not O(n_groups): a
single reintroduced ``for g in range(n_groups)`` on the persist/send/
apply/read path turns a 100k-group tick from microseconds back into
hundreds of milliseconds and no functional test catches it — throughput
regressions only show on the chip.  This lint greps the hot methods'
source for the banned idioms instead; sparse ``np.nonzero(...)``-driven
``.tolist()`` loops over dirty subsets remain the approved pattern."""

import ast
import inspect
import os
import re
import textwrap

import rafting_tpu.runtime.node as node_mod
from rafting_tpu.runtime.node import RaftNode

# Methods on the per-tick hot path (persist / send / apply / read) plus
# boot recovery.  Banned substrings mean "visits every group".
HOT_METHODS = (
    "_persist_prepare", "_persist_stage", "_sweep_rejections",
    "_stash_outbox_sections", "_flush_sends",
    "_harvest_reads", "_serve_reads",
    "_host_phase", "_persist", "_persist_stage_native", "_build_spans",
    "_recover_machines",
)
BANNED = (
    "for g in range(",                # dense group walk
    "range(self.cfg.n_groups)",       # dense group walk, spelled long
    "np.arange(G).tolist()",          # dense walk via arange
    "for g in list(self._reads_released",   # the pre-gate released walk
)


def test_hot_methods_have_no_dense_group_loops():
    for name in HOT_METHODS:
        src = inspect.getsource(getattr(RaftNode, name))
        for pat in BANNED:
            assert pat not in src, (
                f"RaftNode.{name} reintroduced a dense per-group loop "
                f"({pat!r}): visit np.nonzero(...) sparse subsets instead "
                f"— see _persist_stage's wrote/mask idiom and "
                f"_serve_reads' _rel_min columnar gate")


def test_one_host_phase_and_no_switch_to_fork_it():
    """The host phase is written once (``_host_phase``; its one varying
    step is ``_persist``, chosen from what the store can do), and no
    source file of the package names an environment switch that used to
    fork it, or any knob of the retired CPU-era bench scripts."""
    phases = [n for n in vars(RaftNode) if n.startswith("_host_phase")]
    assert phases == ["_host_phase"], phases
    gone = re.compile(
        r"RAFT_PIPELINE|RAFT_NATIVE_HOST|RAFT_HOST_WORKERS|BENCH_[A-Z]")
    pkg = os.path.dirname(os.path.dirname(node_mod.__file__))
    for root, _dirs, files in os.walk(pkg):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    hit = gone.search(fh.read())
                assert hit is None, (
                    f"{os.path.join(root, f)} names {hit.group(0)}: the "
                    f"engine is the store's (LogStore(force_python=...)), "
                    f"and there is one tick order")


def test_one_tick_order_and_nothing_to_choose_another():
    """A tick is ``_dispatch``, ``_fetch``, ``_host_phase(ctx)``, and
    nothing of it outlives ``tick()``: no argument builds a node or a
    test cluster with another order, the node has no second send path and
    keeps no tick for later, and no source file of the package names a
    piece of the overlapped order that went (the names are spelled in
    parts here so that a grep for them finds the program alone)."""
    from rafting_tpu.testkit.harness import LocalCluster

    for cls in (RaftNode, LocalCluster):
        assert "pipe" + "line" not in inspect.signature(cls.__init__).parameters
    assert list(inspect.signature(RaftNode._host_phase).parameters) == [
        "self", "ctx"]
    assert not hasattr(RaftNode, "_eager" + "_send")
    assert not re.search(r"self\._pend" + r"ing\b",
                         inspect.getsource(RaftNode))
    tick = inspect.getsource(RaftNode.tick)
    order = [tick.index(call) for call in (
        "self._dispatch(", "self._fetch(ctx)", "self._host_phase(ctx)")]
    assert order == sorted(order)
    gone = re.compile("|".join((
        "deferred" + "_ae", "defer" + "_send", "EAGER" + "_KINDS",
        "settles" + "_now", "_host" + "_costs", "ticks" + "_settled",
        "eager" + "_sends", "pipeline" + "_enabled")))
    pkg = os.path.dirname(os.path.dirname(node_mod.__file__))
    for root, _dirs, files in os.walk(pkg):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    hit = gone.search(fh.read())
                assert hit is None, (
                    f"{os.path.join(root, f)} names {hit.group(0)}: a "
                    f"piece of the overlapped tick order, which is gone")


def test_a_tick_crosses_the_device_boundary_packed():
    """_dispatch and _fetch move a tick's planes as the two packed
    buffers of core/packing.py.  A transfer per leaf (``jnp.asarray`` of
    each plane, a ``device_get`` over a tuple of many results) costs a
    fixed 0.1-0.3 ms a call on a TPU whatever the plane's size: some
    130 of them were half of a tick's host time, and no functional test
    sees them come back."""
    for name in ("_dispatch", "_fetch"):
        src = textwrap.dedent(inspect.getsource(getattr(RaftNode, name)))
        assert "jnp.asarray(" not in src, (
            f"RaftNode.{name} uploads a plane by itself: write it into "
            f"the tick's packed buffers (step_layouts(...)[0].unpack)")
        for call in ast.walk(ast.parse(src)):
            if not (isinstance(call, ast.Call)
                    and isinstance(call.func, ast.Attribute)
                    and call.func.attr == "device_get"):
                continue
            for arg in call.args:
                if isinstance(arg, (ast.Tuple, ast.List)):
                    assert len(arg.elts) <= 2, (
                        f"RaftNode.{name} fetches {len(arg.elts)} results "
                        f"in one device_get: add them to core/step.py "
                        f"Readback so they ride the packed buffers")
    assert "node_step_packed(" in inspect.getsource(RaftNode._dispatch)


def test_send_plane_uses_section_packing():
    """Frames are built per-kind via pack_kind_section + assemble_slice
    (the quarantine mask needs per-section control of the columns); a
    revived whole-frame pack_slice call would pack a poisoned stripe's
    lanes with the rest."""
    src = inspect.getsource(node_mod)
    assert "pack_slice(" not in src, (
        "runtime/node.py calls pack_slice — pack per-kind sections with "
        "pack_kind_section and frame them with assemble_slice")
    assert "pack_kind_section" in \
        inspect.getsource(RaftNode._stash_outbox_sections)


def test_stub_history_gate_is_single_is_none_test():
    """Client-history recording (testkit/history.py) must cost exactly
    one ``is None`` test per blocking call when disabled — the same
    contract as the node's latency tracer.  A recorder lookup, dict get,
    or try/except on the disabled path would tax every production
    execute/execute_read to subsidize a test-only feature."""
    from rafting_tpu.api.stub import RaftStub
    for name in ("execute", "execute_read"):
        src = inspect.getsource(getattr(RaftStub, name))
        gates = src.count("self._history is not None")
        assert gates == 1, (
            f"RaftStub.{name} must gate history recording behind exactly "
            f"one 'self._history is not None' test (found {gates}); the "
            f"recorder itself lives entirely behind it")
        # The disabled path falls straight through to the private impl —
        # no attribute juggling, no exception handling on this frame.
        assert "getattr" not in src and "try:" not in src, (
            f"RaftStub.{name} grew logic on the history-disabled path")


def test_columnar_gates_present():
    """Positive checks: the columnar structures the loops were replaced
    WITH are still the mechanism (guards against a rewrite that drops
    both the loop and the feature)."""
    assert "groups_with_snapshots" in \
        inspect.getsource(RaftNode._recover_machines)
    assert "_rel_min" in inspect.getsource(RaftNode._serve_reads)
    assert "_rel_min" in inspect.getsource(RaftNode._harvest_reads)
