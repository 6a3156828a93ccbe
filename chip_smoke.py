#!/usr/bin/env python
"""chip_smoke.py — prove on one TPU chip that the program still starts,
elects, commits, fsyncs, applies and answers.

One process, the default backend, no fallback: when JAX's first device is
not a TPU the script says why and exits non-zero without printing a result.
Phases (any failure raises and the exit code is non-zero):

1. device    — platform, kind, count, versions, compile-cache directory.
2. engine    — the fused device engine (core/sim.py) at 100,000 groups x 3
               peers under submit load: one leader per group, commit
               frontier equal across nodes after a drain, ClusterChecker
               clean; the same cfg/seed at 4,096 groups on the chip and on
               the in-process CPU backend must agree bit for bit; the
               Pallas quorum kernel must be in the compiled module and
               give the inline path's states; the scalar oracle must agree
               with the kernel tick by tick.
3. served    — three RaftContainers over localhost TCP, 100,000 group lanes
               each, WAL fsync on, FileMachine applies, whatever
               host tier / WAL engine the program selects here: writes and
               linearizable reads through RaftStub, every acknowledged
               write in all three replicas' machines, one node destroyed,
               re-created on its data directory, recovered and serving.
4. result    — the last stdout line is the JSON object the driver reads.

``--multichip`` (four chips) runs ONLY the group-sharded engine and its
one-chip comparison.
"""

import argparse
import contextlib
import gc
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import urllib.request
from collections import Counter

SEED = 21
ENGINE_GROUPS = 100_000      # BASELINE north-star size
PARITY_GROUPS = 4_096        # chip vs in-process CPU backend, bit for bit
TICKS_PER_CALL = 64          # one device execution (~0.5 s a tick at 100k)
SERVED_LANES = 100_000       # group lanes per node engine
SERVED_TICK_MS = 1000        # see served_config
SERVED_GROUPS = 8            # groups opened and driven
CLIENTS_PER_GROUP = 8        # sequential clients of each group
WRITES_PER_CLIENT = 5
READ_EVERY = 4               # one linearizable read per this many writes


def say(phase: str, **facts) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in facts.items()),
          flush=True)


# --------------------------------------------------------------- accounting

_events = Counter()
_seconds = Counter()


def _watch_compiles() -> None:
    import jax.monitoring as mon
    mon.register_event_listener(lambda name, **kw: _events.update([name]))
    mon.register_event_duration_secs_listener(
        lambda name, secs, **kw: _seconds.update({name: secs}))


def _peak_bytes(device):
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


@contextlib.contextmanager
def phase(name: str, device):
    """Time a phase and report what it compiled: requests that consulted
    the persistent cache, how many hit, backend compile seconds, and the
    device's peak memory so far."""
    ev0, sec0, t0 = Counter(_events), Counter(_seconds), time.perf_counter()
    yield
    req = "/jax/compilation_cache/compile_requests_use_cache"
    hit = "/jax/compilation_cache/cache_hits"
    comp = "/jax/core/compile/backend_compile_duration"
    say(name, seconds=round(time.perf_counter() - t0, 2),
        compile_requests=_events[req] - ev0[req],
        cache_hits=_events[hit] - ev0[hit],
        compile_seconds=round(_seconds[comp] - sec0[comp], 2),
        peak_device_bytes=_peak_bytes(device))


# ------------------------------------------------------------------- device

def phase_device(n_chips: int):
    import importlib.metadata as md

    import jax
    import jaxlib

    from rafting_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    _watch_compiles()
    devices = jax.devices()
    d = devices[0]
    say("device", platform=d.platform, kind=repr(d.device_kind),
        count=len(devices), jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=md.version("libtpu"), compile_cache=cache_dir)
    if d.platform != "tpu":
        print(f"chip_smoke: JAX's default backend is {d.platform!r}, not a "
              "TPU; this script proves the program on the chip and has no "
              "CPU mode (the tests cover the CPU).", flush=True)
        sys.exit(2)
    if len(devices) != n_chips:
        print(f"chip_smoke: need {n_chips} chip(s), JAX reports "
              f"{len(devices)}.", flush=True)
        sys.exit(2)
    return devices


# ------------------------------------------------------------------- engine

def engine_cfg(n_groups: int, use_pallas: bool = False):
    """The shape the fused engine is driven at."""
    from rafting_tpu import EngineConfig
    return EngineConfig(n_groups=n_groups, n_peers=3, log_slots=64, batch=8,
                        max_submit=8, election_ticks=10, heartbeat_ticks=3,
                        rpc_timeout_ticks=8, pre_vote=True,
                        use_pallas=use_pallas)


def cluster_inputs(cfg, seed: int, put):
    """(states, inflight, info, conn, submit) of a fresh cluster, placed by
    ``put`` (a device or a sharding rule)."""
    import jax.numpy as jnp

    from rafting_tpu import DeviceCluster
    c = DeviceCluster(cfg, seed=seed)
    submit = jnp.full((cfg.n_peers, cfg.n_groups), cfg.max_submit, jnp.int32)
    return put(c.states, c.inflight, c.last_info, c.conn, submit)


def drive_engine(cfg, n_ticks: int, inputs, loads, on_call=None):
    """Compile ``run_cluster_ticks`` once for ``inputs`` and execute it
    once per entry of ``loads`` (True = full submit load, False = drain).
    Returns (final states, compiled program, compile seconds)."""
    import jax.numpy as jnp

    from rafting_tpu.core.sim import run_cluster_ticks
    states, inflight, info, conn, submit = inputs
    t0 = time.perf_counter()
    compiled = run_cluster_ticks.lower(
        cfg, n_ticks, states, inflight, info, conn, submit).compile()
    compile_s = time.perf_counter() - t0
    for k, load in enumerate(loads):
        sub = submit if load else jnp.zeros_like(submit)
        t0 = time.perf_counter()
        states, inflight, info = compiled(states, inflight, info, conn, sub)
        if on_call is not None:
            on_call(k, states, t0)
    return states, compiled, compile_s


def check_converged(cfg, snap: dict) -> None:
    """After a drain: exactly one leader per group, every group committed,
    the commit frontier equal across nodes, the live window inside the
    ring, and the committed entries' terms equal on every node."""
    import numpy as np

    from rafting_tpu import LEADER
    role, commit = snap["role"], snap["commit"]
    n_lead = (role == LEADER).sum(axis=0)
    assert (n_lead == 1).all(), f"leaders per group: {np.unique(n_lead)}"
    assert (commit > 0).all(), "a group never committed"
    assert (commit == commit[:1]).all(), "commit frontier differs across nodes"
    last, base, log_term = snap["last"], snap["base"], snap["log_term"]
    L = cfg.log_slots
    assert ((last - base) <= L).all(), "log window exceeds the ring"
    floor = base.max(axis=0)
    for k in range(L):
        idx = commit[0] - k
        live = idx > floor
        slot = np.broadcast_to((idx % L)[None, :, None], role.shape + (1,))
        terms = np.take_along_axis(log_term, slot, axis=2)[..., 0]
        assert (terms == terms[:1])[:, live].all(), \
            f"committed entry terms differ across nodes at commit-{k}"


def phase_engine(chip, cpu, n_groups: int, parity_groups: int, n_ticks: int,
                 audit_groups: int = 2_048) -> None:
    import jax
    import numpy as np

    from rafting_tpu.core.cluster import cluster_snapshot
    from rafting_tpu.testkit.invariants import ClusterChecker

    def on(device):
        return lambda *trees: jax.device_put(trees, device)

    # -- full size on the chip, inline quorum commit -------------------------
    cfg = engine_cfg(n_groups)
    checker = ClusterChecker(engine_cfg(min(audit_groups, n_groups)))
    fence = {}

    def audit(k, states, t0):
        if k == 0:
            np.asarray(states.commit)   # a host read fences for certain
        if k == 1:
            # The second execution is warm: does block_until_ready wait for
            # it, or only a host read?
            fence["dispatch_s"] = time.perf_counter() - t0
            jax.block_until_ready(states.commit)
            fence["block_s"] = time.perf_counter() - t0
            np.asarray(states.commit)
            fence["read_s"] = time.perf_counter() - t0
        if k >= 1:
            snap = cluster_snapshot(states)
            checker.check({f: a[:, :audit_groups] if a.ndim > 1 else a
                           for f, a in snap.items()})

    with phase("engine.full", chip):
        states, _, compile_s = drive_engine(
            cfg, n_ticks, cluster_inputs(cfg, SEED, on(chip)),
            (True, True, False), audit)
        snap = cluster_snapshot(states)
        check_converged(cfg, snap)
        say("engine.full", groups=n_groups, peers=cfg.n_peers,
            ticks=3 * n_ticks, ticks_per_execution=n_ticks,
            compile_s=round(compile_s, 2),
            committed=int(snap["commit"][0].astype(np.int64).sum()),
            one_leader_per_group=True, commit_equal_across_nodes=True,
            cluster_checker_groups=min(audit_groups, n_groups))
        waited = fence["block_s"] - fence["dispatch_s"]
        say("engine.fence", execution_ticks=n_ticks, completed=True,
            dispatch_s=round(fence["dispatch_s"], 4),
            block_until_ready_s=round(fence["block_s"], 4),
            host_read_s=round(fence["read_s"], 4),
            block_until_ready_fences=bool(
                fence["read_s"] - fence["block_s"] < 0.25 * waited))

    # -- the Pallas quorum kernel: in the module, same states ----------------
    with phase("engine.pallas", chip):
        cfg_p = engine_cfg(n_groups, use_pallas=True)
        states_p, compiled_p, compile_s = drive_engine(
            cfg_p, n_ticks, cluster_inputs(cfg_p, SEED, on(chip)),
            (True, True, False))
        has_kernel = "tpu_custom_call" in compiled_p.as_text()
        if chip.platform == "tpu":
            assert has_kernel, "use_pallas=True compiled without the kernel"
        snap_p = cluster_snapshot(states_p)
        for f, a in snap.items():
            assert np.array_equal(a, snap_p[f]), \
                f"Pallas path differs from the inline path in {f}"
        say("engine.pallas", groups=n_groups, compile_s=round(compile_s, 2),
            kernel_in_module=has_kernel, equals_inline=True)
    del states, states_p, compiled_p, snap, snap_p

    # -- chip vs the in-process CPU backend, bit for bit ----------------------
    with phase("engine.parity", chip):
        cfg_s = engine_cfg(parity_groups)
        snaps = []
        for device in (chip, cpu):
            with jax.default_device(device):
                st, _, _ = drive_engine(
                    cfg_s, 4 * n_ticks,
                    cluster_inputs(cfg_s, SEED, on(device)), (True, False))
                snaps.append(cluster_snapshot(st))
        check_converged(cfg_s, snaps[0])
        for f, a in snaps[0].items():
            assert np.array_equal(a, snaps[1][f]), \
                f"chip and CPU backend disagree in {f}"
        say("engine.parity", groups=parity_groups, ticks=8 * n_ticks,
            reference=f"{cpu.platform} backend in process",
            bit_identical=sorted(snaps[0]))


def phase_oracle(chip) -> None:
    """The scalar oracle against the kernel on the chip, at the size and
    schedules of tests/test_oracle_parity.py."""
    from rafting_tpu import EngineConfig
    from rafting_tpu.testkit.parity import run_parity

    with phase("engine.oracle", chip):
        cfg = EngineConfig(n_groups=8, n_peers=3, log_slots=16, batch=4,
                           max_submit=4, election_ticks=6, heartbeat_ticks=2,
                           rpc_timeout_ticks=5, pre_vote=True)
        _, stats = run_parity(0, n_ticks=60, cfg=cfg)
        say("engine.oracle", groups=cfg.n_groups, ticks=60,
            partitions=stats["partitions"], agrees_every_tick=True)


# ------------------------------------------------------------------- served

def _wait(pred, what: str, timeout: float) -> None:
    deadline = time.monotonic() + timeout
    while not pred():
        if time.monotonic() > deadline:
            raise AssertionError(f"{what} not reached in {timeout:.0f}s")
        time.sleep(0.05)


def _machine_lines(container, lane: int) -> list:
    path = os.path.join(container.config.data_dir, "machines",
                        f"group_{lane}.txt")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return f.read().splitlines()


class GroupClient(threading.Thread):
    """One sequential client of one group: distinct writes through
    ``execute`` with linearizable reads interleaved (``read`` and
    ``execute_read`` in turn).  FileMachine has no query method, so a read
    answers with its ReadIndex: it must cover the latest acknowledged
    write and lie below the next one."""

    def __init__(self, container, name: str, tag: str, n_writes: int):
        super().__init__(name=f"client-{name}-{tag}", daemon=True)
        self.stub = container.get_stub(name)
        self.group, self.tag, self.n_writes = name, f"{name}-{tag}", n_writes
        self.acked = []          # (apply index, payload)
        self.reads = []          # (ReadIndex, index of the write before it)
        self.unknown = []        # (payload, exception): outcome not known
        self.futures = []
        self.error = None

    def run(self) -> None:
        try:
            for k in range(self.n_writes):
                payload = f"{self.tag}-{k:04d}"
                fut = self.stub.submit(payload, timeout=120)
                self.futures.append(fut)
                try:
                    self.acked.append((fut.result(timeout=120), payload))
                except Exception as e:   # e.g. accepted, then leadership moved
                    self.unknown.append((payload, repr(e)))
                    continue
                if k % READ_EVERY == READ_EVERY - 1:
                    if (k // READ_EVERY) % 2:
                        fut = self.stub.read("latest", timeout=120)
                        self.futures.append(fut)
                        r = fut.result(timeout=120)
                    else:
                        r = self.stub.execute_read("latest", timeout=120)
                    self.reads.append((r, self.acked[-1][0]))
        except Exception as e:
            self.error = e
        finally:
            self.stub.close()


def run_clients(containers, names, tag: str, n_clients: int,
                n_writes: int) -> list:
    clients = [GroupClient(containers[(i + k) % len(containers)], name,
                           f"{tag}{k}", n_writes)
               for i, name in enumerate(names) for k in range(n_clients)]
    for c in clients:
        c.start()
    for c in clients:
        c.join()
    for c in clients:
        if c.error is not None:
            raise c.error
        assert all(f.done() for f in c.futures), \
            f"{c.name}: a future was left unresolved"
        idx = [i for i, _ in c.acked]
        assert idx == sorted(set(idx)), f"{c.name}: apply order broken"
        for r, wrote in c.reads:
            assert r >= wrote, \
                f"{c.name}: read at {r} misses the write acknowledged at {wrote}"
            later = [i for i in idx if i > wrote]
            assert not later or r < later[0], \
                f"{c.name}: read at {r} saw a write that was not yet sent"
    return clients


def check_replicas(containers, lanes: dict, clients, timeout: float) -> int:
    """Every acknowledged write is in every replica's machine, at its
    apply index, and the replicas' files are identical."""
    n = 0
    for c in clients:
        lane = lanes[c.group]
        want = {i: p for i, p in c.acked}

        def have(container):
            lines = dict(l.split(":", 1) for l in
                         _machine_lines(container, lane))
            return all(lines.get(str(i)) == p for i, p in want.items())

        _wait(lambda: all(have(k) for k in containers),
              f"acknowledged writes of {c.tag} on all replicas", timeout)
        n += len(want)
    for lane in lanes.values():
        _wait(lambda: len({tuple(_machine_lines(k, lane))
                           for k in containers}) == 1,
              f"identical replica files for lane {lane}", timeout)
    return n


def served_config(uris, i: int, n_lanes: int, data_dir: str,
                  tick_ms: int = SERVED_TICK_MS):
    """Node ``i``'s configuration: the product's default engine shape
    (log_slots, batch, max_submit as RaftConfig ships them) at ``n_lanes``
    group lanes.  tests/test_tpu_compile.py compiles node_step at it.

    The tick interval has to be one the node can keep.  Three nodes of
    100,000 dense lanes sharing one chip tick well under once a second,
    and the runtime reads a gap between ticks of more than
    ``read_fresh_ticks`` intervals as a paused host: it then vetoes all
    read-barrier evidence (runtime/node.py _dispatch), so at a nominal
    20 ms tick no linearizable read was ever served on the chip."""
    from rafting_tpu.api import RaftConfig
    return RaftConfig(local=uris[i],
                      peers=tuple(u for u in uris if u != uris[i]),
                      n_groups=n_lanes, tick_ms=tick_ms, election_mul=6.0,
                      data_dir=data_dir, seed=SEED)


def phase_served(chip, n_lanes: int, n_groups: int, n_clients: int,
                 n_writes: int, root: str,
                 tick_ms: int = SERVED_TICK_MS) -> None:
    import numpy as np

    from rafting_tpu.api import RaftContainer
    from rafting_tpu.log import wal
    from rafting_tpu.testkit.harness import free_ports
    from rafting_tpu.utils.metrics import validate_exposition

    assert wal.native_available(), \
        f"the native WAL engine did not build here: {wal._build_err}"
    uris = [f"raft://127.0.0.1:{p}" for p in free_ports(3)]
    configs = [served_config(uris, i, n_lanes,
                             os.path.join(root, f"node{i}"), tick_ms)
               for i in range(3)]
    containers = []
    try:
        with phase("served.start", chip):
            for cfg in configs:
                # One at a time: the first node's first tick compiles
                # node_step; the others then find it compiled.
                c = RaftContainer(cfg).create()
                containers.append(c)
                _wait(lambda: c.node.ticks >= 2, "first ticks", 600)
            node = containers[0].node
            store = node.store.wal
            say("served.selected", lanes=n_lanes, tick_ms=tick_ms,
                host_tier=("native" if node.store.can_stage_native
                           else "python"),
                host_workers=node.host_workers,
                wal_engine=type(store.engines[0]).__name__
                if hasattr(store, "engines") else type(store).__name__,
                wal_shards=getattr(store, "n_shards", 1), wal_fsync=True,
                machine=type(containers[0].factory.machine_provider(
                    configs[0], 0)).__name__,
                transport="tcp")
            _wait(lambda: any(c.node.is_leader(0) for c in containers),
                  "admin group leader", 300)

        with phase("served.open", chip):
            names = [f"g{k}" for k in range(n_groups)]
            lanes = {name: containers[k % 3].open_context(name, timeout=300)
                     for k, name in enumerate(names)}
            for lane in lanes.values():
                _wait(lambda: all(c.node.is_active(lane) for c in containers)
                      and any(c.node.is_leader(lane) and c.node.is_ready(lane)
                              for c in containers),
                      f"ready leader for lane {lane}", 300)

        with phase("served.load", chip):
            ticks0 = [c.node.ticks for c in containers]
            t0 = time.perf_counter()
            clients = run_clients(containers, names, "a", n_clients,
                                  n_writes)
            load_s = time.perf_counter() - t0
            ticks = [c.node.ticks - k for c, k in zip(containers, ticks0)]
            acked = check_replicas(containers, lanes, clients, 120)
            unknown = sum(len(c.unknown) for c in clients)
            offered = n_groups * n_clients * n_writes
            assert acked >= 0.9 * offered, \
                f"only {acked} of {offered} writes acknowledged: " \
                f"{[c.unknown[:2] for c in clients if c.unknown]}"
            say("served.load", groups=n_groups, writes_acked=acked,
                writes_outcome_unknown=unknown,
                reads=sum(len(c.reads) for c in clients),
                reads_linearizable=True, replicas_identical=3,
                futures_unresolved=0, seconds=round(load_s, 2),
                seconds_per_tick=round(load_s / max(min(ticks), 1), 4),
                ticks=min(ticks))

        with phase("served.metrics", chip):
            for c in containers:
                port = c.node.start_observability().port
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics", timeout=30) as r:
                    page = r.read().decode()
                validate_exposition(page)
                for stage in ("scan_wait", "wal", "fsync", "send", "apply",
                              "reads", "maintain"):
                    assert f"raft_tick_stage_{stage}_s_count" in page, stage
                h = c.node.metrics.histogram("tick_stage_scan_wait_s")
                assert h.n > 0 and h.total > 0, "scan_wait never observed"
            stages = {k: round(v["mean"], 5) for k, v in
                      containers[0].node.metrics.breakdown().items()}
            say("served.metrics", tick_stage_series=True,
                scan_wait_nonzero=True, node0_stage_mean_s=stages,
                node0_tick_mean_s=round(containers[0].node.metrics.histogram(
                    "tick_latency_s").summary()["mean"], 5))

        with phase("served.restart", chip):
            # Restart a node that does not lead the admin group.
            k = next(i for i, c in enumerate(containers)
                     if not c.node.is_leader(0))
            lane_ids = np.asarray(sorted(lanes.values()))
            tail = containers[k].node._durable_tail_m[lane_ids].copy()
            containers[k].destroy()
            containers[k] = RaftContainer(configs[k]).create()
            fresh = containers[k].node
            recovered = fresh._durable_tail_m[lane_ids]
            assert (recovered >= tail).all() and (tail > 0).all(), \
                f"WAL recovery lost entries: {tail} -> {recovered}"
            for lane in lanes.values():
                _wait(lambda: fresh.is_active(lane)
                      and any(c.node.is_leader(lane) and c.node.is_ready(lane)
                              for c in containers),
                      f"lane {lane} live after the restart", 300)
            # The restarted node serves: every client goes through it.
            again = run_clients([containers[k]], names, "b", 1, READ_EVERY)
            acked2 = check_replicas(containers, lanes, clients + again, 120)
            say("served.restart", node=k, wal_tail_recovered=True,
                caught_up=True, writes_acked_through_it=acked2 - acked,
                reads=sum(len(c.reads) for c in again),
                replicas_identical=3)
    finally:
        for c in containers:
            c.destroy()


# ---------------------------------------------------------------- multichip

def phase_multichip(devices, n_groups: int, n_ticks: int) -> None:
    """One cluster's groups split over a Mesh('node','group') of 1 x 4 at
    P=3 — the group axis really split, an odd voter count — against the
    same cfg/seed on one chip."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from rafting_tpu.core.cluster import cluster_snapshot
    from rafting_tpu.core.shard import shard_cluster

    n = len(devices)
    cfg = engine_cfg(n_groups)
    mesh = Mesh(np.asarray(devices).reshape(1, n), ("node", "group"))
    with phase("multichip.sharded", devices[0]):
        states, compiled, compile_s = drive_engine(
            cfg, n_ticks,
            cluster_inputs(cfg, SEED, lambda *t: shard_cluster(mesh, cfg, *t)),
            (True, True, False))
        share = n_groups // n
        for name in ("term", "role", "commit"):
            shards = getattr(states, name).addressable_shards
            assert {s.device for s in shards} == set(devices), name
            assert all(s.data.shape == (cfg.n_peers, share)
                       for s in shards), name
        ring = states.log.term.addressable_shards
        assert {s.device for s in ring} == set(devices)
        assert all(s.data.shape == (cfg.n_peers, share, cfg.log_slots)
                   for s in ring)
        snap = cluster_snapshot(states)
        check_converged(cfg, snap)
        say("multichip.sharded", mesh=f"node=1 x group={n}",
            peers=cfg.n_peers, groups=n_groups, groups_per_chip=share,
            ticks=3 * n_ticks, compile_s=round(compile_s, 2),
            every_chip_holds_its_share=True,
            per_chip_bytes_in_use=[
                (d.memory_stats() or {}).get("bytes_in_use")
                for d in devices])
    with phase("multichip.one_chip", devices[0]):
        one, _, _ = drive_engine(
            cfg, n_ticks,
            cluster_inputs(cfg, SEED,
                           lambda *t: jax.device_put(t, devices[0])),
            (True, True, False))
        snap1 = cluster_snapshot(one)
        for f, a in snap.items():
            assert np.array_equal(a, snap1[f]), \
                f"sharded and one-chip runs disagree in {f}"
        say("multichip.one_chip", groups=n_groups, bit_identical=sorted(snap))


# --------------------------------------------------------------------- main

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: only the group-sharded engine and "
                         "its one-chip comparison")
    args = ap.parse_args()
    t0 = time.perf_counter()
    devices = phase_device(4 if args.multichip else 1)
    chip = devices[0]
    if args.multichip:
        phase_multichip(devices, ENGINE_GROUPS, TICKS_PER_CALL)
    else:
        import jax
        phase_engine(chip, jax.devices("cpu")[0], ENGINE_GROUPS,
                     PARITY_GROUPS, TICKS_PER_CALL)
        phase_oracle(chip)
        gc.collect()
        root = tempfile.mkdtemp(prefix="chip-smoke-")
        try:
            phase_served(chip, SERVED_LANES, SERVED_GROUPS, CLIENTS_PER_GROUP,
                         WRITES_PER_CLIENT, root)
        finally:
            shutil.rmtree(root, ignore_errors=True)
    say("total", seconds=round(time.perf_counter() - t0, 1),
        compile_requests=_events[
            "/jax/compilation_cache/compile_requests_use_cache"],
        cache_hits=_events["/jax/compilation_cache/cache_hits"],
        compile_seconds=round(
            _seconds["/jax/core/compile/backend_compile_duration"], 1),
        peak_device_bytes=_peak_bytes(chip))
    print(json.dumps({"ok": True, "device": {
        "platform": chip.platform, "kind": chip.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
