"""Bring-up of the system under test: three ``RaftContainer``s in this process
over localhost TCP, as a configuration file describes them.

This is the only file of the benchmark that drives the program's entry
points (``RaftContainer`` -> ``RaftStub`` -> ``RaftNode``); everything it
measures with is in the benchmark's other files.
"""

from __future__ import annotations

import json
import os
import socket
import time
import zlib
from typing import Dict, List, Optional

from rafting_tpu.api import RaftConfig, RaftContainer, RaftFactory
from rafting_tpu.machine.kv_machine import KVMachine, KVMachineProvider

CONFIG_FIELDS = ("source", "voters", "open_groups", "lifecycle", "machine",
                 "raft_config", "injected_delay_ms", "latency_limit_ms",
                 "trace_slice_s", "guarantees", "assumed", "reduced")


def load_config(path: str) -> dict:
    with open(path) as f:
        cfg = json.load(f)
    missing = [k for k in CONFIG_FIELDS if k not in cfg]
    if missing:
        raise ValueError(f"{path}: missing {missing}")
    if cfg["lifecycle"] != "local-registry" or cfg["machine"] != "kv" \
            or cfg["voters"] != 3 or cfg["injected_delay_ms"] != 0:
        raise ValueError(f"{path}: only groups opened through the nodes' "
                         "own registries, the kv machine, 3 voters and no "
                         "injected delay are wired")
    if not 1 <= cfg["open_groups"] < cfg["raft_config"]["n_groups"]:
        raise ValueError(f"{path}: open_groups must leave lane 0 to @raft")
    return cfg


class FaultyKVMachine(KVMachine):
    """KVMachine with the defects the controls switch on, read from its
    provider at every use so that a machine made later has them too.  Only
    a cluster built with ``faults=True`` (the controls and their tests)
    holds these; a run of the benchmark holds the program's own machines.
    While ``drop_sets`` is true this replica acknowledges a third of the
    ``set``s it applies (chosen by a checksum of the value) without storing
    them; ``stale_reads`` is the program's own knob (a linearizable read
    answers with the key's previous value)."""

    def __init__(self, provider: "FaultyProvider", path: str, group: int):
        super().__init__(path, group=group)
        self._provider = provider

    def _apply_op(self, cmd: dict):
        v = cmd.get("v")
        if self._provider.drop_sets and cmd.get("op") == "set" \
                and isinstance(v, str) and zlib.crc32(v.encode()) % 3 == 0:
            return v
        return super()._apply_op(cmd)

    def read(self, payload: bytes):
        self.stale_reads = self._provider.stale_reads
        return super().read(payload)


class FaultyProvider(KVMachineProvider):
    drop_sets = False

    def bootstrap(self, group: int) -> KVMachine:
        return FaultyKVMachine(
            self, os.path.join(self.root, f"kv_{group}.json"), group)


class BenchFactory(RaftFactory):
    """The default wiring (TCP transport, the WAL the node builds itself)
    with the program's key-value machine in place of the FileMachine."""

    def __init__(self, faults: bool = False):
        self.faults = faults
        self.provider: Optional[KVMachineProvider] = None

    def machine_provider(self, config: RaftConfig, node_id: int):
        root = os.path.join(config.data_dir, "machines")
        self.provider = FaultyProvider(root) if self.faults \
            else KVMachineProvider(root)
        return self.provider


def free_ports(n: int) -> List[int]:
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def group_name(i: int) -> str:
    return f"g{i + 1:05d}"


class Cluster:
    def __init__(self, config: dict, data_root: str, seed: int, say,
                 faults: bool = False):
        self.config, self.data_root, self.say = config, data_root, say
        self.factories = [BenchFactory(faults) for _ in range(3)]
        self.n_open = int(config["open_groups"])
        self.names = [group_name(i) for i in range(self.n_open)]
        self.containers: List[RaftContainer] = []
        self.lanes: Dict[str, int] = {}
        self._stubs: Dict[tuple, object] = {}
        uris = [f"raft://127.0.0.1:{p}" for p in free_ports(3)]
        self.raft_configs = [
            RaftConfig(local=u, peers=tuple(p for p in uris if p != u),
                       data_dir=os.path.join(data_root, f"node{i}"),
                       seed=seed % (2 ** 31 - 1), **config["raft_config"])
            for i, u in enumerate(uris)]

    # ----------------------------------------------------------------- boot

    def boot(self, timeout_s: float) -> None:
        """First node alone (its first tick compiles or loads node_step),
        then the others, each re-opening the groups its registry holds;
        wait until every open group has a ready leader and every member
        routes to it."""
        t0 = time.perf_counter()
        for rc, factory in zip(self.raft_configs, self.factories):
            os.makedirs(rc.data_dir, exist_ok=True)
            # The container's own durable registry, written before it
            # starts: create() re-opens every lane registered there.
            with open(os.path.join(rc.data_dir, "groups.json"), "w") as f:
                json.dump({n: [i + 1, True]
                           for i, n in enumerate(self.names)}, f)
            c = RaftContainer(rc, factory, admin=False).create()
            self.containers.append(c)
            self._wait(lambda: c.node.ticks >= 2, "first ticks", timeout_s)
        self.say("boot", containers=3,
                 seconds=round(time.perf_counter() - t0, 2))
        t1 = time.perf_counter()
        self.lanes = {n: i + 1 for i, n in enumerate(self.names)}
        lanes = list(self.lanes.values())
        nodes = [c.node for c in self.containers]
        progress = {"at": time.monotonic()}

        def ready() -> bool:
            led = sum(any(n.is_leader(g) and n.is_ready(g) for n in nodes)
                      for g in lanes)
            # Every member's leader hint points somewhere: a stub routes.
            routed = [sum(n.is_active(g) and n.leader_hint(g) is not None
                          for g in lanes) for n in nodes]
            if time.monotonic() - progress["at"] > 10:
                progress["at"] = time.monotonic()
                self.say("electing", ready_leaders=led, routed=routed,
                         ticks=[n.ticks for n in nodes])
            return led == len(lanes) and min(routed) == len(lanes)

        self._wait(ready, f"ready leaders for {len(lanes)} group(s)",
                   timeout_s, every=min(0.25, self.raft_configs[0]
                                        .tick_interval))
        self.say("groups", open=len(lanes), seconds=round(
            time.perf_counter() - t1, 2), ticks=[n.ticks for n in nodes])

    def _wait(self, pred, what: str, timeout_s: float,
              every: float = 0.05) -> None:
        deadline = time.monotonic() + timeout_s
        while not pred():
            if time.monotonic() > deadline:
                raise TimeoutError(f"{what} not reached in {timeout_s:.0f}s")
            time.sleep(every)

    # -------------------------------------------------------------- clients

    def stub(self, member: int, group: int):
        key = (member, group)
        s = self._stubs.get(key)
        if s is None:
            s = self._stubs[key] = \
                self.containers[member].get_stub(self.names[group])
        return s

    def send(self, op, command: str):
        s = self.stub(op.target, op.group)
        return s.read(command) if op.kind == "r" else s.submit(command)

    def warm_up(self, timeout_s: float) -> int:
        """A write AND a read through every member, all in flight together,
        until each has completed (a member that follows forwards, one that
        leads serves locally; the first read compiles a step variant).
        An attempt that fails or does not answer within a few ticks is
        given up and made again on another group: a group whose leadership
        the node's health plane is moving away at boot can leave a read
        unanswered.  Keys lie outside every traffic mix's key space."""
        deadline = time.monotonic() + timeout_s
        attempt_s = max(5.0, 25 * self.raft_configs[0].tick_interval)
        todo = [(m, k) for m in range(3) for k in ("w", "r")]
        n = 0
        while todo:
            if time.monotonic() > deadline:
                raise TimeoutError(f"warm-up: {todo} not completed in "
                                   f"{timeout_s:.0f}s after {n} attempts")
            futs = []
            for member, kind in todo:
                # stride over the groups: never the same few low lanes
                s = self.stub(member, (7919 * n + 13) % self.n_open)
                cmd = json.dumps(
                    {"op": "set", "k": f"warm{n}", "v": n} if kind == "w"
                    else {"op": "get", "k": f"warm{n}"})
                n += 1
                futs.append(s.submit(cmd) if kind == "w" else s.read(cmd))
            until = time.monotonic() + attempt_s
            again = []
            for item, fut in zip(todo, futs):
                try:
                    fut.result(timeout=max(0.05, until - time.monotonic()))
                except Exception:
                    again.append(item)
            todo = again
        return n

    # ------------------------------------------------------------- readings

    def machine_of(self, member: int, group: int) -> KVMachine:
        lane = self.lanes[self.names[group]]
        return self.containers[member].node.dispatcher.machine(lane)

    def replica_states(self, keys_by_group: Dict[int, set]
                       ) -> List[Dict[str, object]]:
        """Each replica's value for every key touched (absent left out)."""
        out = []
        for member in range(3):
            state: Dict[str, object] = {}
            for g, keys in keys_by_group.items():
                data = self.machine_of(member, g).data
                for k in keys:
                    if k in data:
                        state[k] = data[k]
            out.append(state)
        return out

    def applied_everywhere(self, groups) -> bool:
        """Every member has applied what any member has applied, on the
        groups touched: the drain's end."""
        for g in groups:
            if len({self.machine_of(i, g).last_applied()
                    for i in range(3)}) != 1:
                return False
        return True

    def set_fault(self, fault: Optional[str]) -> None:
        """``stale_reads``: every machine answers reads with the previous
        value (the program's own defect knob).  ``drop_apply``: member 2's
        machines acknowledge a third of the sets without storing them.
        Only a cluster built with ``faults=True`` can be broken."""
        if fault not in (None, "stale_reads", "drop_apply"):
            raise ValueError(fault)
        for i, f in enumerate(self.factories):
            if not f.faults:
                if fault is not None:
                    raise ValueError("this cluster was built sound")
                continue
            f.provider.stale_reads = fault == "stale_reads"
            f.provider.drop_sets = fault == "drop_apply" and i == 2

    # ------------------------------------------------------------- teardown

    def destroy(self) -> None:
        for c in self.containers:
            c.destroy()
