"""One run of one cell: set-up, warm-up, the measured window, the drain, the
comparison with the reference, and the result line.

``run.py`` (on the chip) and ``rehearse.py`` (CPU, tiny sizes, no device
metric) both come through here, as do the controls and the tests: there is
one path.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import sys
import threading
import time
from collections import Counter
from typing import Dict, List, Optional

from . import readings as rd
from .driver import Completion, drive
from .reference import OK, check
from .traffic import load_traffic, make_schedule

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
OUT_DIR = os.path.join(ROOT, "benchmark_out")      # git-ignored
COMPILE_REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HITS = "/jax/compilation_cache/cache_hits"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def say(tag: str, **facts) -> None:
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in facts.items()),
          flush=True)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, workload: str):
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    traffic = os.path.join(HERE, "traffic", cell["traffic"] + ".json")
    return cell, os.path.join(ROOT, conf["file"]), traffic


def metrics_of(bench: dict, section: str, workload: str) -> List[dict]:
    return [m for m in bench[section]
            if "workloads" not in m or workload in m["workloads"]]


def fs_type(path: str) -> str:
    """Filesystem type of the mount that holds ``path`` (/proc/mounts)."""
    path = os.path.realpath(path)
    best, kind = "", "unknown"
    try:
        with open("/proc/mounts") as f:
            for line in f:
                _, mnt, typ = line.split()[:3]
                if (path == mnt or path.startswith(mnt.rstrip("/") + "/")) \
                        and len(mnt) > len(best):
                    best, kind = mnt, typ
    except OSError:
        pass
    return kind


def data_root() -> str:
    """A new directory for this run's WALs, machines and registries, on a
    filesystem whose fsync reaches a device: the temp directory unless it is
    memory, else under the checkout's ignored output path."""
    import tempfile
    base = tempfile.gettempdir()
    if fs_type(base) in ("tmpfs", "ramfs"):
        base = OUT_DIR
        os.makedirs(base, exist_ok=True)
    root = tempfile.mkdtemp(prefix="raftbench-", dir=base)
    say("data", root=root, filesystem=fs_type(root))
    return root


class CompileWatch:
    """Compile requests, persistent-cache hits and backend compile seconds,
    as ``jax.monitoring`` reports them."""

    def __init__(self):
        import jax.monitoring as mon
        self.events, self.seconds = Counter(), Counter()
        mon.register_event_listener(
            lambda name, **kw: self.events.update([name]))
        mon.register_event_duration_secs_listener(
            lambda name, secs, **kw: self.seconds.update({name: secs}))

    def mark(self):
        return (self.events[COMPILE_REQUESTS], self.events[CACHE_HITS],
                self.seconds[BACKEND_COMPILE])

    def since(self, mark) -> dict:
        now = self.mark()
        return {"compile_requests": now[0] - mark[0],
                "cache_hits": now[1] - mark[1],
                "compile_seconds": round(now[2] - mark[2], 3)}


class TraceSlice:
    """Profiler trace of a steady slice of the window, started and stopped
    by a thread of its own so that the generator never waits for it."""

    def __init__(self, log_dir: str, start_s: float, length_s: float):
        self.log_dir, self.start_s, self.length_s = log_dir, start_s, length_s
        self.error: Optional[BaseException] = None
        self._t = threading.Thread(target=self._run, name="bench-trace",
                                   daemon=True)

    def start(self) -> None:
        self._t.start()

    def _run(self) -> None:
        import jax
        try:
            time.sleep(self.start_s)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # host TraceMe spans only
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            time.sleep(self.length_s)
            jax.profiler.stop_trace()
        except BaseException as e:            # reported by the harness
            self.error = e

    def join(self) -> None:
        self._t.join()
        if self.error is not None:
            raise self.error


COUNTERS = ("elections", "read_vetoes", "lease_vetoes", "reads_served",
            "read_lease_hits", "read_batches_aborted", "leader_evacuations",
            "leadership_transfers_attempted", "checkquorum_stepdowns",
            "slow_io_ticks", "admission_shed")


def window(cluster, schedule, seconds: float, drain_s: float,
           trace_dir: Optional[str] = None, trace_slice_s: float = 0.0):
    """Drive one window and return what it produced."""
    nodes = [c.node for c in cluster.containers]
    counters0 = {k: sum(n.metrics[k] for n in nodes) for k in COUNTERS}
    marks = [rd.histogram_marks(n) for n in nodes]
    ticks0 = [n.ticks for n in nodes]
    fsync0 = sum(n.store.wal.stats().get("fsync_calls", 0) for n in nodes)
    tracer = None
    if trace_dir:
        length = min(trace_slice_s, seconds / 2)
        tracer = TraceSlice(trace_dir, (seconds - length) / 2, length)
        tracer.start()
    t_wall = time.perf_counter()
    completions, t0 = drive(schedule, cluster.send, seconds, drain_s)
    elapsed = time.perf_counter() - t_wall
    after = [rd.histogram_marks(n) for n in nodes]
    hist = [{k: (a[k][0] - m[k][0], a[k][1] - m[k][1]) for k in m}
            for m, a in zip(marks, after)]
    # Tick and fsync counts cover window + drain; scale to the window.
    scale = seconds / elapsed if elapsed > 0 else 1.0
    ticks = [max(1, round((n.ticks - k) * scale))
             for n, k in zip(nodes, ticks0)]
    fsyncs = sum(n.store.wal.stats().get("fsync_calls", 0)
                 for n in nodes) - fsync0
    if tracer is not None:
        tracer.join()
    say("program", **{k: int(sum(n.metrics[k] for n in nodes) - v)
                      for k, v in counters0.items()})
    return completions, hist, ticks, fsyncs, elapsed


def settle_and_check(cluster, completions: List[Completion],
                     initial: Optional[Dict[str, object]] = None,
                     timeout_s: float = 60.0):
    """Wait until the replicas have applied the same prefix on every group
    touched, read their states, and compare with the reference."""
    keys_by_group: Dict[int, set] = {}
    for c in completions:
        keys_by_group.setdefault(c.op.group, set()).add(c.op.key)
    deadline = time.monotonic() + timeout_s
    while not cluster.applied_everywhere(keys_by_group) \
            and time.monotonic() < deadline:
        time.sleep(0.1)
    replicas = cluster.replica_states(keys_by_group)
    return check([c.record for c in completions], replicas, initial)


def end_to_end(completions: List[Completion], seconds: float,
               limit_s: float) -> Dict[str, float]:
    """The client-side metrics over ALL operations due in the window.  An
    operation that failed or never resolved has no reply: it enters the
    percentiles at the time it was given up (a lower bound of its true
    latency) and counts as missed in goodput."""
    def lat(c: Completion) -> float:
        return c.latency_s if math.isfinite(c.record.returned) \
            else seconds + limit_s - c.op.due_s
    writes = [lat(c) for c in completions if c.op.kind == "w"]
    reads = [lat(c) for c in completions if c.op.kind == "r"]
    good = sum(1 for c in completions
               if c.record.outcome == OK and c.latency_s <= limit_s)
    out = {"goodput": good / seconds}
    for name, lats in (("commit", writes), ("read", reads)):
        for q in (50, 90, 95, 99) if lats else ():
            out[f"{name}_p{q}_ms"] = 1e3 * rd.percentile(lats, q)
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_process: float, on_chip: bool,
             overrides: Optional[dict] = None,
             fault: Optional[str] = None) -> dict:
    """One run.  ``overrides`` (rehearsal and tests only) replaces keys of
    the configuration (``raft_config``, ``open_groups``, the limit, the
    traced slice) and of the ``traffic``; ``fault`` switches on one of the
    controls' defects."""
    bench = load_benchmark()
    cell, config_path, traffic_path = find_cell(bench, workload)
    from .cluster import Cluster, load_config
    config = load_config(config_path)
    traffic = load_traffic(traffic_path)
    ov = overrides or {}
    traffic.update(ov.get("traffic", {}))
    config["raft_config"].update(ov.get("raft_config", {}))
    config.update({k: ov[k] for k in ("open_groups", "latency_limit_ms",
                                      "trace_slice_s") if k in ov})
    limit_s = config["latency_limit_ms"] / 1e3

    import jax
    from rafting_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    watch = CompileWatch()
    devices = jax.devices()
    d = devices[0]
    say("device", platform=d.platform, kind=repr(d.device_kind),
        count=len(devices), jax=jax.__version__, compile_cache=cache_dir)
    if on_chip and (d.platform != "tpu" or len(devices) < cell["chips"]):
        print(f"benchmark: cell {workload} needs {cell['chips']} TPU chip(s); "
              f"JAX reports {len(devices)} x {d.platform!r}.  No result.",
              flush=True)
        raise SystemExit(2)

    schedule = make_schedule(traffic, seed, seconds, config["open_groups"])
    say("traffic", name=cell["traffic"], operations=len(schedule),
        rate_ops_s=traffic["rate_ops_s"], seconds=seconds, seed=seed)
    root = data_root()
    cluster = Cluster(config, root, seed, say, faults=fault is not None)
    result = None
    try:
        tick_s = config["raft_config"]["tick_ms"] / 1e3
        boot_mark = watch.mark()
        cluster.boot(timeout_s=max(300.0, 400 * tick_s))
        cluster.set_fault(fault)
        n_warm = cluster.warm_up(timeout_s=max(120.0, 200 * tick_s))
        node = cluster.containers[0].node
        say("selected", pipeline=bool(node.pipeline),
            wal=type(node.store.wal).__name__, warm_up_operations=n_warm,
            machine=type(cluster.machine_of(0, 0)).__name__,
            **watch.since(boot_mark))
        trace_dir = os.path.join(root, "trace") if trace else None
        setup_s = time.time() - t_process
        win_mark = watch.mark()
        jax.config.update("jax_log_compiles", True)   # names a culprit
        completions, hist, ticks, fsyncs, elapsed = window(
            cluster, schedule, seconds, limit_s, trace_dir,
            config["trace_slice_s"])
        jax.config.update("jax_log_compiles", False)
        compiled = watch.since(win_mark)
        say("window", seconds=seconds, with_drain=round(elapsed, 2),
            ticks=ticks, **compiled)
        verdict = settle_and_check(cluster, completions,
                                   timeout_s=max(30.0, 30 * tick_s))
        for line in verdict.lines():
            say("compare", number=line)
        for ex in verdict.examples:
            say("compare", example=ex)
        late = [c.late_s for c in completions]
        failed = [c for c in completions if c.record.outcome != OK]
        say("generator", late_p50_ms=round(1e3 * rd.percentile(late, 50), 3),
            late_max_ms=round(1e3 * max(late), 3))
        if failed:
            say("failed", n=len(failed), kinds=dict(Counter(
                c.record.error.split(":")[0] or "unresolved"
                for c in failed)))
        e2e = end_to_end(completions, seconds, limit_s)
        e2e["setup_s"] = setup_s
        say("end_to_end", **{k: round(v, 3) for k, v in e2e.items()})
        if compiled["compile_requests"]:
            say("WARNING", compiled_inside_window=compiled)

        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(devices),
                  "memory_peak_bytes": max(
                      (dv.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for dv in devices)}
        line = {"correct": bool(verdict.correct),
                "attempted": len(completions), "failed": len(failed),
                "compiles_in_window": compiled["compile_requests"]}
        if not trace:
            units = {m["name"]: m["unit"] for m in
                     metrics_of(bench, "end_to_end", workload)}
            line["metrics"] = {k: {"value": e2e[k], "unit": u}
                               for k, u in units.items() if k in e2e}
        else:
            line.update(per_layer(bench, workload, config, cluster, d,
                                  completions, hist, ticks, fsyncs, seconds,
                                  trace_dir, device, on_chip))
        line["device"] = device
        result = line
    finally:
        cluster_done(cluster, root)
    return result


def per_layer(bench, workload, config, cluster, d, completions, hist, ticks,
              fsyncs, seconds, trace_dir, device, on_chip) -> dict:
    from . import tracered
    from .stepbytes import step_bytes
    red = None
    if on_chip:
        xplane = tracered.find_xplane(trace_dir)
        red = tracered.reduce_file(xplane)
        os.makedirs(OUT_DIR, exist_ok=True)
        with open(os.path.join(OUT_DIR, f"trace_{workload}.txt"), "w") as f:
            f.write("\n".join(tracered.describe(xplane)) + "\n")
        device["busy_s"], device["window_s"] = red.busy_s, red.window_s
        with open(os.path.join(HERE, "peaks.json")) as f:
            peaks = json.load(f)
        if d.device_kind not in peaks:
            raise SystemExit(f"no peaks for device kind {d.device_kind!r} "
                             "in benchmark/peaks.json")
        peak = peaks[d.device_kind]["hbm_bytes_per_s"]
        say("trace", window_s=round(red.window_s, 4),
            busy_s=round(red.busy_s, 4), devices=red.n_devices,
            step_executions=red.step_executions,
            step_device_s=round(red.step_device_s, 4),
            ops_in_steps_s=round(red.ops_in_steps_s, 4))
    else:
        peak = None
    sb = step_bytes(cluster.containers[0].node.cfg)
    r = rd.Readings(
        window_s=seconds, histograms=hist, ticks=ticks, fsync_calls=fsyncs,
        acked_writes=sum(1 for c in completions if c.op.kind == "w"
                         and c.record.outcome == OK),
        commit_latencies_s=[c.latency_s for c in completions
                            if c.op.kind == "w" and c.record.outcome == OK],
        read_latencies_s=[c.latency_s for c in completions
                          if c.op.kind == "r" and c.record.outcome == OK],
        gen_late_s=[c.late_s for c in completions],
        step_bytes=sb["total"], peak_bytes_per_s=peak, trace=red)
    say("step_bytes", **sb)
    metrics = {}
    for m in metrics_of(bench, "per_layer", workload):
        v = rd.read_metric(m["name"], r)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    say("per_layer", **{k: round(v["value"], 4) for k, v in metrics.items()})
    out = {"metrics": metrics}
    if red is not None:
        out["breakdown"] = {"device_ops": red.device_ops,
                            "idle_gaps": red.idle_gaps}
    return out


def cluster_done(cluster, root: str, patience_s: float = 60.0) -> None:
    """The containers' own graceful destroy (the tick loops end, every
    group's machine file is dumped), after the result is in hand and
    outside every metric; then the run's data goes.  ``close()`` may wait
    minutes for a WAL collection in flight, and a run has to end: after
    ``patience_s`` the destroy is left to its (daemon) thread."""
    t0 = time.perf_counter()
    t = threading.Thread(target=cluster.destroy, name="bench-teardown",
                         daemon=True)
    t.start()
    t.join(patience_s)
    shutil.rmtree(root, ignore_errors=True)
    say("teardown", seconds=round(time.perf_counter() - t0, 2),
        finished=not t.is_alive())


def finish(result: Optional[dict]) -> None:
    """Print the result as the last line and end the process, daemon
    threads and all."""
    sys.stdout.flush()
    if result is None:
        os._exit(1)
    print(json.dumps(result), flush=True)
    sys.stderr.flush()
    os._exit(0)
