#!/usr/bin/env python3
"""Chip probes for the row form of a column step's [G] planes (PERF.md, PR 38).

    python3 tools/rows_probe.py device [--rows 512,512 --rows 1024,512 ...]
    python3 tools/rows_probe.py check [--steps 40] [--config NAME]
    python3 tools/rows_probe.py run --half up|both --workload W --seed N \
        --seconds S [--trace 1]

``device``: the served step program at the 100,000-Region cell's shape, wall
clock around ``block_until_ready``, median of 30 calls in one process: the
packed step, and the column step for each ``--rows K_in,K_out``.  Every call
carries empty messages and a handful of rows, as a step between two heartbeat
rounds does.
Beside each median: the arrays the call takes from the host and returns to
it (what a step uploads and fetches), and their bytes.  ``--config NAME``
times another configuration's packed step (its column step too where its
shape takes one).

``check``: three nodes at that shape stepped through the packed step and
through the row form on this backend; every state, outbox, mirror and the
device's durable plane must agree after every step (``--config
multiraft-100k-3v-hib --steps 90``: with hibernation compiled into the step,
long enough for the lanes to fall asleep; the line every ten steps counts
them).

``run``: ``benchmark/run.py`` with, for ``--half up``, every Readback taken
whole (``pack_readback``) and the mirrors swapped as a packed step's are: the
up half alone.  ``--half both`` is ``benchmark/run.py`` itself.  Either prints, per node over
window + drain, the row and column counters, the lanes the host phase's
selection passes ran over (``host_lanes_scanned``; 0 on a tree without the
counter) and the untraced means of the stages the row form touches, the
fetch's and the host phase's (``wal``, ``apply``, ``reads``, ``maintain``)
(``[rows]`` lines); ``--watch 1`` adds a line a node every
10 s of the boot (lanes whose commit lies past what is applied, and why);
``--cpu-lanes N`` rehearses the control flow on the CPU at N lanes.
"""

import time

T_PROCESS = time.time()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import statistics   # noqa: E402
import sys          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


CONFIG = "multiraft-100k-3v"


def engine_config():
    from rafting_tpu.api import RaftConfig
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        raft = json.load(f)["raft_config"]
    uris = [f"raft://127.0.0.1:{7001 + i}" for i in range(3)]
    return RaftConfig(local=uris[0], peers=tuple(uris[1:]),
                      data_dir="unused", **raft).engine_config()


def median_ms(call, n=30):
    import jax
    times = []
    for _ in range(n + 3):
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        times.append(time.perf_counter() - t0)
    return statistics.median(times[3:]) * 1e3


def crossing(up, down) -> str:
    """The arrays a call takes from the host and returns to it."""
    import jax
    up, down = jax.tree.leaves(up), jax.tree.leaves(down)
    return (f"{len(up)} array(s) up ({sum(a.nbytes for a in up)} B), "
            f"{len(down)} down ({sum(a.nbytes for a in down)} B)")


def device(rows):
    import jax
    from rafting_tpu.core import packing, step
    from rafting_tpu.core.types import NIL, init_state

    cfg = engine_config()
    print("device", jax.devices()[0].device_kind, "config", CONFIG, "lanes",
          cfg.n_groups, flush=True)
    inputs, _ = step.step_layouts(cfg, True)
    state = [init_state(cfg, 0, seed=1)]
    dense = tuple(jax.device_put(inputs.alloc()))

    def packed():
        state[0], out = step.node_step_packed(cfg, inputs, state[0], dense)
        return out

    t0 = time.perf_counter()
    out = jax.block_until_ready(packed())
    print(f"node_step_packed: first call {time.perf_counter() - t0:.1f} s, "
          f"median {median_ms(packed):.3f} ms; {crossing(dense, out)}",
          flush=True)
    if step.column_layouts(cfg, True) is None:
        return
    for k_in, k_out in rows:
        packing.ROWS_IN, packing.ROWS_OUT = k_in, k_out
        step.column_layouts.cache_clear()
        lay = step.column_layouts(cfg, True)
        state = [init_state(cfg, 0, seed=1)]
        host = lay.host.alloc()
        lay.host.unpack(host).xfer_target[...] = NIL
        resident = tuple(jax.device_put(host))
        # One array up: the rows, and the (empty) columns behind them.
        up, (rp, _) = packing.alloc_regions(lay.rows_in, lay.columns)
        carry = [step.first_carry(lay)]
        view = lay.rows_in.view(rp)
        view.set_n(3)
        view.ids[:3] = (5, 77, 4242)
        view.field("xfer_target")[:3] = NIL
        view.field("submit_n")[:3] = 1
        view.set_head("clock", 0)
        rp, up = (jax.device_put(rp),), (jax.device_put(up),)

        def columns():
            last = carry[0]
            out = step.node_step_columns(
                cfg, lay, True, state[0], last, resident + up)
            state[0], carry[0] = out[0], out[1]
            return step.compact_readback(lay, carry[0], last, out[2])

        t0 = time.perf_counter()
        out = jax.block_until_ready(columns())
        first = time.perf_counter() - t0
        print(f"node_step_columns rows in/out {k_in}/{k_out}: first call "
              f"{first:.1f} s, median {median_ms(columns):.3f} ms; "
              f"{crossing(up, out)}", flush=True)

        def back():
            return columns(), step.pack_readback(lay, carry[0])

        out = jax.block_until_ready(back())
        print(f"  ... + pack_readback: median {median_ms(back):.3f} ms; "
              f"{crossing(up, out)}", flush=True)

        def dense_in():     # the other form of the operand
            last = carry[0]
            out = step.node_step_columns(
                cfg, lay, False, state[0], last, dense + rp)
            state[0], carry[0] = out[0], out[1]
            return (step.compact_readback(lay, carry[0], last, out[2]),
                    step.pack_outbox(lay, out[3]))

        t0 = time.perf_counter()
        out = jax.block_until_ready(dense_in())
        first = time.perf_counter() - t0
        print(f"  dense operand + pack_outbox: first call {first:.1f} s, "
              f"median {median_ms(dense_in, 10):.3f} ms; "
              f"{crossing(dense + rp, out)}", flush=True)


def on_valid(msgs):
    """``msgs`` with every field zeroed outside its kind's valid lanes.
    What a step writes there follows what its inbox held on lanes that
    carried no message (a reply's match hint is computed from the
    request's fields, valid or not), and the two forms differ exactly
    there: a dense inbox keeps what the sender's planes held, an inbox
    expanded from columns holds zeros.  No receiver reads such a lane.
    (Unseen while every step after the elections was a heartbeat round
    and crossed densely in both forms; a store whose lanes sleep takes
    the column form on most steps.)"""
    import numpy as np
    out = {}
    for name in msgs.__dataclass_fields__:
        a = getattr(msgs, name)
        if a is None:
            continue
        a = np.asarray(a)
        valid = np.asarray(getattr(msgs, name.split("_", 1)[0] + "_valid"))
        out[name] = np.where(
            valid.reshape(valid.shape + (1,) * (a.ndim - 2)), a,
            np.zeros((), a.dtype))
    return msgs.replace(**out)


def check(steps, whole_in=False):
    """Three nodes at the cell's shape on this backend, stepped through
    ``node_step_packed`` on dense planes and through ``node_step_columns``
    with HostInbox as rows (or whole, by the count) and the Readback as
    rows patched into a mirror (or whole): states, outboxes, mirrors and
    the device's durable plane must agree after every step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from rafting_tpu.core import packing, step
    from rafting_tpu.core.cluster import route
    from rafting_tpu.core.types import HostInbox, Messages, NIL, init_state

    cfg = engine_config()
    G, N = cfg.n_groups, cfg.n_peers
    print("device", jax.devices()[0].device_kind, "lanes", G, flush=True)
    inputs, readback = step.step_layouts(cfg, True)
    lay = step.column_layouts(cfg, True)
    rin, rout = lay.rows_in, lay.rows_out
    rng = np.random.default_rng(3)
    plain = [init_state(cfg, n, seed=3) for n in range(N)]
    rows = [init_state(cfg, n, seed=3) for n in range(N)]
    carry = [step.first_carry(lay) for _ in range(N)]
    sent = [None] * N
    mirror = [rout.planes() for _ in range(N)]
    first = [True] * N
    zero = lay.host.alloc()
    lay.host.unpack(zero).xfer_target[...] = NIL
    resident = tuple(jax.device_put(zero))
    outboxes = [jax.device_get(Messages.empty(cfg))] * N
    tails = [np.zeros(G, np.int32)] * N
    seen = dict(rows_in=0, whole_in=0, rows_out=0, whole_out=0, cols_in=0,
                cols_out=0)
    for t in range(steps):
        inflight = jax.tree.map(lambda *a: np.stack(a), *outboxes)
        inboxes = jax.device_get(route(inflight, jnp.ones((N, N), bool)))
        outboxes = []
        for n in range(N):
            inbox = jax.tree.map(lambda a: a[n], inboxes)
            host = jax.device_get(HostInbox.empty(cfg))
            sub = np.zeros(G, np.int32)
            sub[rng.choice(G, 3, replace=False)] = 1
            rd = np.zeros(G, np.int32)
            rd[rng.choice(G, 2, replace=False)] = 1
            host = host.replace(submit_n=sub, read_n=rd,
                                durable_tail=tails[n],
                                clock=np.asarray(int(t % 4 != 1), np.int32))
            bufs = inputs.alloc()
            h_view, i_view = inputs.unpack(bufs)
            jax.tree.map(np.copyto, (h_view, i_view), (host, inbox))
            plain[n], out = step.node_step_packed(
                cfg, inputs, plain[n], jax.device_put(bufs))
            want = readback.unpack(jax.device_get(out))
            # -- the row form of the same step
            said = (sub != 0) | (rd != 0)
            if sent[n] is not None:
                said |= tails[n] != sent[n]
            ids = np.nonzero(said)[0]
            held = lay.columns.compact(inbox)
            fits = bool((lay.columns.view(held).n <= lay.columns.K).all())
            seen["cols_in"] += fits
            if sent[n] is None or len(ids) > rin.K or whole_in:
                seen["whole_in"] += 1
                sent[n] = tails[n].copy()
                rp = rin.whole(host)
                up = tuple(jax.device_put(lay.host.pack(host)))
            else:
                seen["rows_in"] += 1
                rp = rin.alloc()
                v = rin.view(rp)
                v.set_n(len(ids))
                v.ids[:len(ids)] = ids
                v.set_head("clock", host.clock)
                v.field("xfer_target")[:len(ids)] = NIL
                v.field("submit_n")[:len(ids)] = sub[ids]
                v.field("read_n")[:len(ids)] = rd[ids]
                v.field("durable_tail")[:len(ids)] = tails[n][ids]
                sent[n][ids] = tails[n][ids]
                up = resident
            if fits:        # one array: the rows, the columns behind them
                operand = up + (jax.device_put(
                    np.concatenate([rp, held])),)
            else:
                dense = lay.inputs.alloc()
                d_host, d_inbox = lay.inputs.unpack(dense)
                jax.tree.map(np.copyto, d_inbox, inbox)
                jax.tree.map(np.copyto, d_host, lay.host.unpack(
                    jax.device_get(up)))
                operand = tuple(jax.device_put(dense + (rp,)))
            last = carry[n]
            rows[n], carry[n], c_out, o_dense = step.node_step_columns(
                cfg, lay, fits, rows[n], last, operand)
            down = jax.device_get(step.compact_readback(
                lay, carry[n], last, c_out))
            r_down, c_down = packing.regions(down, rout, lay.columns)
            tag = f"step {t} node {n}"
            bad = []
            for (path, a), b in zip(
                    jax.tree_util.tree_flatten_with_path(
                        jax.device_get(rows[n]))[0],
                    jax.tree.leaves(jax.device_get(plain[n]))):
                if not np.array_equal(a, b):
                    at = np.argwhere(np.asarray(a) != np.asarray(b))
                    bad.append((jax.tree_util.keystr(path), len(at),
                                at[:3].tolist(),
                                np.asarray(a)[tuple(at[0])].tolist(),
                                np.asarray(b)[tuple(at[0])].tolist()))
            if bad:
                print(tag, "fits", fits, "rows in", len(ids),
                      "clock", int(host.clock), flush=True)
                for line in bad:
                    print("  STATE DIFFERS", line, flush=True)
                raise AssertionError(tag)
            np.testing.assert_array_equal(
                np.asarray(carry[n].durable), tails[n], tag + " durable")
            got_out = jax.device_get(lay.columns.unstack(o_dense))
            for a, b in zip(jax.tree.leaves(on_valid(got_out)),
                            jax.tree.leaves(on_valid(want.outbox))):
                np.testing.assert_array_equal(a, b, tag + " outbox")
            # The outbox's columns, as they came down behind the rows: the
            # dense outbox on its valid lanes wherever every row fits.
            np.testing.assert_array_equal(c_down, jax.device_get(c_out), tag)
            cols = lay.columns.view(c_down)
            if (cols.n <= lay.columns.K).all():
                seen["cols_out"] += 1
                held = want.outbox.replace(**{
                    name: cols.dense(name) for name in cols.planes})
                for a, b in zip(jax.tree.leaves(on_valid(held)),
                                jax.tree.leaves(on_valid(want.outbox))):
                    np.testing.assert_array_equal(a, b, tag + " columns")
            view = rout.view(r_down)
            words, flags = mirror[n]
            if view.n > rout.K or first[n]:
                first[n] = False
                seen["whole_out"] += 1
                back = lay.back.unpack(jax.device_get(
                    step.pack_readback(lay, carry[n])))
                rout.copy_levels(back, words, flags)
                moved = None
            else:
                seen["rows_out"] += 1
                moved = view.ids[:view.n]
                flags[:, moved] = view.flags[:, :view.n]
                words[:, moved] = view.words[:, :view.n]
                back = rout.unstack(words, flags, view.header)
            took = np.asarray(want.info.submit_acc) > 0
            fix = lambda b: b._replace(outbox=None, info=b.info.replace(
                submit_start=np.where(took, b.info.submit_start, 0)))
            for a, b in zip(jax.tree.leaves(fix(back)),
                            jax.tree.leaves(fix(want))):
                np.testing.assert_array_equal(a, b, tag + " mirror")
            if moved is not None:
                words[rout.Lw:, moved] = 0
                flags[rout.Lf:, moved] = False
            outboxes.append(want.outbox)
            tails[n] = np.asarray(want.info.log_tail)
        if t % 10 == 0:
            print("step", t, seen, "led", [int((np.asarray(
                s.role) == 3).sum()) for s in plain], "asleep",
                [0 if s.hib is None else int(np.asarray(s.hib.asleep).sum())
                 for s in plain], flush=True)
    print("CHECK OK", seen, flush=True)


def run(a):
    from benchmark import harness
    if a.half == "up":
        import rafting_tpu.runtime.node as node_mod
        real = node_mod.RaftNode._fetch_rows

        def whole(self, ctx, fetched):
            self._rows_whole_out = True
            return real(self, ctx, fetched)

        node_mod.RaftNode._fetch_rows = whole
    names = ("ticks", "steps_rows_in", "row_overflows_in", "steps_rows_out",
             "row_overflows_out", "steps_columns_in", "column_overflows_in",
             "steps_columns_out", "column_overflows_out", "stage_stalls",
             "host_lanes_scanned")
    stages = ("dispatch_intake", "dispatch_upload", "dispatch_enqueue",
              "scan_device", "scan_fetch", "mirrors",
              "wal", "apply", "reads", "maintain")
    real_window = harness.window

    def window(cluster, *args, **kw):
        """The window, with each node's row counters and the means of the
        stages the row form touches printed over window + drain."""
        nodes = [c.node for c in cluster.containers]
        hist = lambda n, k: n.metrics.histogram(f"tick_stage_{k}_s")
        before = [([n.metrics[k] for k in names],
                   [(hist(n, k).total, hist(n, k).n) for k in stages])
                  for n in nodes]
        out = real_window(cluster, *args, **kw)
        for n, (c0, h0) in zip(nodes, before):
            print("[rows]", n.node_id,
                  {k: int(n.metrics[k] - v) for k, v in zip(names, c0)},
                  {k: round((hist(n, k).total - t) * 1e3
                            / max(hist(n, k).n - m, 1), 3)
                   for k, (t, m) in zip(stages, h0)}, flush=True)
        return out

    harness.window = window
    if a.watch:
        import threading
        import numpy as np
        from benchmark import cluster as cluster_mod
        boot = cluster_mod.Cluster.boot

        def watched(self, timeout_s):
            def loop():
                while True:
                    time.sleep(10)
                    for c in list(self.containers):
                        n = c.node
                        G = n.cfg.n_groups
                        applied = n.dispatcher.applied_frontier(G)
                        commit = np.asarray(n.h_commit)
                        gap = np.nonzero(commit > applied)[0]
                        some = [(int(g), int(commit[g]), int(applied[g]),
                                 int(n.h_base[g]), int(n.h_term[g]),
                                 int(n.h_role[g]),
                                 int(n._durable_tail_m[g]),
                                 int(n.store.tail(int(g))))
                                for g in gap[:3]]
                        print("[watch]", n.node_id, "ticks", n.ticks,
                              "gap", len(gap), "(g, commit, applied, base, "
                              "term, role, durable, wal tail)", some,
                              "commit max", int(commit.max()),
                              "base max", int(np.asarray(n.h_base).max()),
                              "counts", n._lane_counts, flush=True)
            threading.Thread(target=loop, daemon=True).start()
            return boot(self, timeout_s)

        cluster_mod.Cluster.boot = watched
    overrides = None
    if a.cpu_lanes:
        # A rehearsal of the control flow on the CPU, never a measurement:
        # the cell cut to a few lanes, and buffers so small that the shape
        # takes the column step and its rows and columns overflow.
        from rafting_tpu.core import packing
        packing.CHUNK_BYTES, packing.COLUMNS = 2048, 6
        packing.ROWS_IN = packing.ROWS_OUT = 4
        overrides = {"raft_config": {"n_groups": a.cpu_lanes, "tick_ms": 100},
                     "open_groups": a.cpu_lanes - 1,
                     "traffic": {"rate_ops_s": 40},
                     "latency_limit_ms": 5000, "trace_slice_s": 1}
    try:
        result = harness.run_cell(a.workload, a.seed, a.seconds,
                                  bool(a.trace), T_PROCESS,
                                  on_chip=not a.cpu_lanes,
                                  overrides=overrides)
    except SystemExit as e:
        sys.stdout.flush()
        os._exit(e.code if isinstance(e.code, int) else 1)
    except BaseException:
        import traceback
        traceback.print_exc()
        sys.stdout.flush()
        os._exit(1)
    harness.finish(result)


def main():
    global CONFIG
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="cmd", required=True)
    d = sub.add_parser("device")
    d.add_argument("--rows", action="append", default=[])
    d.add_argument("--config", default=CONFIG,
                   help="benchmark/configs/<name>.json")
    c = sub.add_parser("check")
    c.add_argument("--steps", type=int, default=40)
    c.add_argument("--whole-in", type=int, default=0)
    c.add_argument("--config", default=CONFIG,
                   help="benchmark/configs/<name>.json: the shape and the "
                        "engine's fields (multiraft-100k-3v-hib: the step "
                        "with hibernation compiled in)")
    r = sub.add_parser("run")
    r.add_argument("--half", choices=("up", "both"), required=True)
    r.add_argument("--workload", required=True)
    r.add_argument("--seed", type=int, required=True)
    r.add_argument("--seconds", type=float, required=True)
    r.add_argument("--trace", type=int, choices=(0, 1), default=0)
    r.add_argument("--cpu-lanes", type=int, default=0)
    r.add_argument("--watch", type=int, default=0)
    a = ap.parse_args()
    if a.cmd == "check":
        CONFIG = a.config
        check(a.steps, bool(a.whole_in))
    elif a.cmd == "device":
        CONFIG = a.config
        device([tuple(int(k) for k in s.split(","))
                for s in a.rows] or [(512, 512)])
    else:
        run(a)


if __name__ == "__main__":
    main()
