#!/usr/bin/env python3
"""Chip-side check of the step as the two coordination-service cells build
it, lease on (``coord-1g-3v``) and off (``coord-1g-3v-ri``): three nodes
stepped through ``node_step_packed`` on the default backend (the chip: the
program the runtime calls, its state donated) and, beside them, through
``node_step`` on the CPU backend of the same process, from one seed, through
an election and a few hundred reads over a random mix of timer and arrival
steps.  State, outbox and info must agree leaf for leaf after every step.
The chip's compiler has twice written over donated state where no CPU test
could see it (PERF.md section 6, PRs 38 and 42); this is what would see it
at 16 lanes.

    python3 tools/read_index_probe.py check [--steps 300] [--config NAME ...]

Prints a ``[check]`` line a configuration (reads stamped, stamped in arrival
steps, released, released by the lease) and exits 1 on the first leaf that
differs.  ``JAX_PLATFORMS=cpu`` rehearses the control flow (both sides on
the CPU backend)."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONFIGS = ("coord-1g-3v", "coord-1g-3v-ri")


def engine_config(name):
    from rafting_tpu.api import RaftConfig
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        raft = json.load(f)["raft_config"]
    uris = [f"raft://127.0.0.1:{7001 + i}" for i in range(3)]
    return RaftConfig(local=uris[0], peers=tuple(uris[1:]),
                      data_dir="unused", **raft).engine_config()


def check(name, steps, seed=45):
    import jax
    import numpy as np
    from rafting_tpu.core import step
    from rafting_tpu.core.types import HostInbox, Messages, init_state
    from rafting_tpu.testkit.parity import route_numpy

    cfg = engine_config(name)
    G, N = cfg.n_groups, cfg.n_peers
    cpu = jax.devices("cpu")[0]
    inputs, readback = step.step_layouts(cfg, True)
    rng = np.random.default_rng(seed)
    chip = [init_state(cfg, n, seed=seed) for n in range(N)]
    with jax.default_device(cpu):
        ref = [init_state(cfg, n, seed=seed) for n in range(N)]
    outboxes = [jax.device_get(Messages.empty(cfg))] * N
    tails = [np.zeros(G, np.int32)] * N
    seen = dict(stamped=0, on_arrival=0, released=0, by_lease=0, led=0)
    for t in range(steps):
        inboxes = route_numpy(outboxes, np.ones((N, N), bool))
        outboxes = []
        for n in range(N):
            arrival = t > 0 and rng.random() < 0.6
            host = jax.device_get(HostInbox.empty(cfg)).replace(
                submit_n=(rng.random(G) < 0.2).astype(np.int32),
                read_n=rng.integers(0, 3, G).astype(np.int32),
                durable_tail=tails[n],
                clock=np.asarray(int(not arrival), np.int32))
            bufs = inputs.alloc()
            jax.tree.map(np.copyto, inputs.unpack(bufs), (host, inboxes[n]))
            chip[n], out = step.node_step_packed(
                cfg, inputs, chip[n], jax.device_put(bufs))
            got = readback.unpack(jax.device_get(out))
            with jax.default_device(cpu):
                ref[n], w_out, w_info = step.node_step(
                    cfg, ref[n], jax.device_put(inboxes[n], cpu),
                    jax.device_put(host, cpu))
            for what, a, b in (("state", chip[n], ref[n]),
                               ("outbox", got.outbox, w_out),
                               ("info", got.info, w_info)):
                for (path, x), y in zip(
                        jax.tree_util.tree_flatten_with_path(
                            jax.device_get(a))[0],
                        jax.tree.leaves(jax.device_get(b))):
                    if not np.array_equal(x, y):
                        print(f"[check] config={name} step={t} node={n} "
                              f"{what}{jax.tree_util.keystr(path)} differs",
                              flush=True)
                        return False
            info = got.info
            acc = np.asarray(info.read_acc) > 0
            seen["stamped"] += int(acc.sum())
            seen["on_arrival"] += int(acc.sum()) if arrival else 0
            seen["released"] += int(np.asarray(info.read_rel).sum())
            seen["by_lease"] += int(np.asarray(info.read_lease).sum())
            seen["led"] += int(np.asarray(info.ready).sum())
            tails[n] = np.asarray(info.log_tail).copy()
            outboxes.append(got.outbox)
    ok = seen["released"] > 100 and seen["on_arrival"] > 0 \
        and (cfg.read_lease or seen["by_lease"] == 0)
    print(f"[check] config={name} device={jax.devices()[0].device_kind!r} "
          f"read_lease={cfg.read_lease} steps={steps} equal=True "
          + " ".join(f"{k}={v}" for k, v in seen.items()) + f" ok={ok}",
          flush=True)
    return ok


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=("check",))
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--config", action="append", default=[])
    a = ap.parse_args()
    ok = all([check(name, a.steps) for name in (a.config or CONFIGS)])
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
