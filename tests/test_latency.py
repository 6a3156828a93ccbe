"""Per-entry latency tracing plane (ISSUE 13): seeded sampler
determinism, span completeness through the commit path,
crash-in-the-fsync-window outcome-unknown semantics (a crashed span
never fabricates a latency), the /latency endpoint + exposition
round-trip, native wal_stats() parity with Python-side timings, and the
metrics registry's single-writer/snapshot-reader thread contract.
"""

import errno
import json
import threading
import time
import urllib.request

import pytest

from rafting_tpu.core.types import EngineConfig
from rafting_tpu.log import wal as wal_mod
from rafting_tpu.log.store import LogStore
from rafting_tpu.api import StorageFaultError
from rafting_tpu.testkit.harness import LocalCluster, wal_store_factory
from rafting_tpu.utils.latency import (
    ACKED, COMMITTED, PHASE_PAIRS, SUBMITTED, LatencyTracer,
    tracer_from_env,
)
from rafting_tpu.utils.metrics import Histogram, Metrics, validate_exposition

CFG = EngineConfig(n_groups=4, n_peers=3, log_slots=32, batch=4,
                   max_submit=4, election_ticks=6, heartbeat_ticks=2,
                   rpc_timeout_ticks=5, trace_depth=32)


def _get(port: int, path: str):
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                    timeout=5) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


# ------------------------------------------------ sampler determinism --


def test_sampler_is_deterministic_in_seed_and_rate():
    """The sampled set is a pure function of (seed, rate): same seed →
    same set, exact 1/rate density over any aligned window, and first_in
    agrees with a brute-force membership scan for every (seq0, n)."""
    N = 10_000
    for seed in (0, 1, 7, 12345):
        a = LatencyTracer(64, seed=seed)
        b = LatencyTracer(64, seed=seed)
        picks_a = [s for s in range(N) if a.sampled(s)]
        assert picks_a == [s for s in range(N) if b.sampled(s)]
        assert len(picks_a) in (N // 64, N // 64 + 1)
        # Stride: consecutive picks are exactly `rate` apart.
        assert all(y - x == 64 for x, y in zip(picks_a, picks_a[1:]))
    # Different seeds (mod rate) shift the residue class.
    t0, t5 = LatencyTracer(8, seed=0), LatencyTracer(8, seed=5)
    assert {s % 8 for s in range(64) if t0.sampled(s)} == {0}
    assert {s % 8 for s in range(64) if t5.sampled(s)} == {3}
    # first_in is the O(1) form of the scan, for ranges crossing hits,
    # missing them, and degenerate n.
    tr = LatencyTracer(8, seed=5)
    for seq0 in range(0, 40):
        for n in (0, 1, 3, 8, 17):
            brute = next((k for k in range(n) if tr.sampled(seq0 + k)), -1)
            assert tr.first_in(seq0, n) == brute, (seq0, n)


def test_tracer_from_env_disable_and_parse(monkeypatch):
    monkeypatch.setenv("RAFT_LAT_SAMPLE", "0")
    assert tracer_from_env() is None
    monkeypatch.setenv("RAFT_LAT_SAMPLE", "-3")
    assert tracer_from_env() is None
    monkeypatch.setenv("RAFT_LAT_SAMPLE", "16")
    assert tracer_from_env().rate == 16
    monkeypatch.setenv("RAFT_LAT_SAMPLE", "junk")
    assert tracer_from_env().rate == 64
    monkeypatch.delenv("RAFT_LAT_SAMPLE")
    assert tracer_from_env(default_rate=32).rate == 32


def test_disabled_plane_holds_no_tracer(tmp_path, monkeypatch):
    """RAFT_LAT_SAMPLE=0: the node holds no tracer at all — the hot-path
    hook is one attribute-is-None check, and /latency reports disabled."""
    monkeypatch.setenv("RAFT_LAT_SAMPLE", "0")
    c = LocalCluster(CFG, str(tmp_path))
    try:
        node = c.nodes[0]
        assert node._lat is None
        snap = node.latency_snapshot()
        assert snap["enabled"] is False
    finally:
        c.close()


# -------------------------------------------- span completeness (e2e) --


@pytest.mark.parametrize("engine", [
    "python", pytest.param("native", marks=pytest.mark.skipif(
        not wal_mod.native_available(), reason="native WAL engine unavailable"))])
@pytest.mark.parametrize("shape", ["packed", "columns"])
def test_span_completeness_and_reconciliation(tmp_path, monkeypatch,
                                              take_shape, shape, engine):
    """Rate-1 sampling through a live cluster: every acked submit yields
    an outcome-ok span with every write-phase stamp in protocol order,
    and the phase-pair histograms telescope — the sum of per-phase means
    equals the end-to-end mean (the /latency vs /metrics reconciliation
    the acceptance criteria call for).  Under either persist step: the
    Python one stamps ``staged`` before its barrier and ``fsynced``
    behind it, the native one both at its one call's return; and
    whichever step the shape takes."""
    monkeypatch.setenv("RAFT_LAT_SAMPLE", "1")
    take_shape(CFG, shape)
    c = LocalCluster(
        CFG, str(tmp_path),
        store_factory=wal_store_factory(str(tmp_path), engine))
    try:
        assert all(n.store.can_stage_native == (engine == "native")
                   for n in c.nodes.values())
        c.wait_leader(0)
        for i in range(6):
            c.submit_via_leader(0, b"span-%d" % i)
        c.tick(8)
        node = c.nodes[c.leader_of(0)]
        tr = node._lat
        assert tr.counts["sampled"] >= 6
        assert tr.counts["ok"] >= 6
        assert tr.counts["unknown"] == 0
        oks = [sp for sp in tr.recent if sp.outcome == "ok"
               and sp.kind == "w"]
        assert len(oks) >= 6
        for sp in oks:
            stamps = sp.t[SUBMITTED:ACKED + 1]
            assert all(v > 0.0 for v in stamps), sp.to_dict()
            assert stamps == sorted(stamps), \
                f"phase stamps out of protocol order: {sp.to_dict()}"
            assert sp.group == 0 and sp.idx >= 1 and sp.tick >= 0
        # Telescoping reconciliation: phase means sum to the e2e mean.
        h = node.metrics._histograms
        e2e = h["lat_e2e_s"].summary()
        assert e2e["count"] == len(oks)
        total = sum(h[f"lat_{name}_s"].summary()["mean"]
                    for name, _a, _b in PHASE_PAIRS)
        assert total == pytest.approx(e2e["mean"], rel=0.05)
    finally:
        c.close()


def test_read_span_served(tmp_path, monkeypatch):
    monkeypatch.setenv("RAFT_LAT_SAMPLE", "1")
    c = LocalCluster(CFG, str(tmp_path))
    try:
        lead = c.wait_leader(0)
        c.submit_via_leader(0, b"rw")
        node = c.nodes[lead]
        fut = node.read(0, b"q")
        for _ in range(100):
            if fut.done():
                break
            c.tick()
        assert fut.done() and fut.exception() is None
        c.tick()   # harvest the retired ring
        reads = [sp for sp in node._lat.recent if sp.kind == "r"]
        assert reads and all(sp.outcome == "ok" for sp in reads)
        assert node.metrics._histograms["lat_read_e2e_s"].n >= 1
    finally:
        c.close()


# ----------------------------- crash in the fsync window: no latency --


def test_crashed_span_is_outcome_unknown_never_a_latency(tmp_path,
                                                         monkeypatch):
    """An entry whose fsync fails dies outcome-unknown: the span records
    the outcome, contributes NO latency sample, and the ok/e2e counters
    agree — a crashed span must never fabricate a latency."""
    monkeypatch.setenv("RAFT_LAT_SAMPLE", "1")

    c = LocalCluster(
        CFG, str(tmp_path),
        store_factory=wal_store_factory(str(tmp_path), "python"))
    try:
        lead = c.wait_leader(0)
        c.submit_via_leader(0, b"pre-fault")
        node = c.nodes[lead]
        tr = node._lat
        ok_before = tr.counts["ok"]
        e2e_before = node.metrics._histograms["lat_e2e_s"].n

        node.store.set_fault("fsync", value=errno.EIO, shard=0)
        fut = node.submit(0, b"doomed")
        for _ in range(100):
            if fut.done():
                break
            c.tick()
        assert isinstance(fut.exception(), StorageFaultError)
        c.tick()   # harvest the retired ring
        assert tr.counts["unknown"] >= 1
        dead = [sp for sp in tr.recent if sp.outcome == "unknown"]
        assert dead, "crashed span never retired"
        # No fabricated latency: ok count and the e2e histogram moved in
        # lockstep, and neither counted the crashed span.
        assert tr.counts["ok"] == ok_before
        assert node.metrics._histograms["lat_e2e_s"].n == e2e_before
        for sp in dead:
            assert sp.t[ACKED] == 0.0
    finally:
        c.close()


# ------------------------------------------- endpoint + exposition ----


def test_latency_endpoint_and_exposition_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("RAFT_LAT_SAMPLE", "1")
    c = LocalCluster(CFG, str(tmp_path), wal_shards=2, host_workers=2)
    try:
        lead = c.wait_leader(0)
        for i in range(4):
            c.submit_via_leader(0, b"lat-%d" % i)
        c.tick(5)
        node = c.nodes[lead]
        srv = node.start_observability()

        status, body = _get(srv.port, "/latency")
        assert status == 200
        doc = json.loads(body)
        assert doc["sampling"]["rate"] == 1
        assert doc["sampling"]["counts"]["ok"] >= 4
        assert doc["slo"]["target_s"] > 0
        assert "send_commit" in doc["phases"]
        assert doc["lat_e2e"]["count"] >= 4
        assert all("phases" in sp and "tick" in sp for sp in doc["recent"])

        # /metrics: the same histograms, strict-validated exposition.
        status, body = _get(srv.port, "/metrics")
        text = body.decode()
        validate_exposition(text)
        assert "raft_lat_e2e_s_bucket" in text
        assert "raft_lat_send_commit_s_bucket" in text
        assert "raft_lat_e2e_p999_s" in text
        assert "raft_lat_spans_ok_total" in text
        # /latency and /metrics percentiles come from one histogram.
        assert doc["lat_e2e"]["count"] == node.metrics._histograms[
            "lat_e2e_s"].n

        # /healthz grew the latency block.
        status, body = _get(srv.port, "/healthz")
        h = json.loads(body)
        assert h["latency"]["sampling_rate"] == 1
        assert h["latency"]["slo_target_s"] > 0
        assert "e2e_p999_s" in h["latency"]
        assert "io_slow" in h["latency"]

        # Discoverability: the 404 page lists /latency.
        status, body = _get(srv.port, "/nope")
        assert "/latency" in json.loads(body)["paths"]
    finally:
        c.close()


# -------------------------------------- native wal_stats() parity -----


@pytest.mark.skipif(not wal_mod.native_available(),
                    reason="native WAL unavailable (no toolchain/.so)")
def test_native_wal_stats_fsync_parity(tmp_path):
    """The C-side fsync accounting agrees with Python-side wall timing
    of the same sync() calls within 10% (plus a small absolute slack for
    ctypes call overhead on very fast filesystems)."""
    s = LogStore(str(tmp_path / "wal"), shards=1)
    try:
        base = s.wal.stats()
        assert set(base) == set(wal_mod.WAL_STAT_KEYS)
        py_total = 0.0
        idx = {g: 1 for g in range(4)}
        for r in range(40):
            g = r % 4
            s.append_entries(g, idx[g], [1], [b"x" * 4096])
            idx[g] += 1
            t0 = time.perf_counter()
            s.sync()
            py_total += time.perf_counter() - t0
        cur = s.wal.stats()
        native_s = (cur["fsync_ns"] - base["fsync_ns"]) / 1e9
        assert cur["fsync_calls"] > base["fsync_calls"]
        assert cur["bytes"] > base["bytes"]
        # C measures inside the call; Python wraps it — native <= python,
        # and they agree within 10% (or 2ms of accumulated overhead).
        assert native_s <= py_total
        assert py_total - native_s <= max(0.10 * py_total, 2e-3), \
            (native_s, py_total)
    finally:
        s.close()


def test_python_wal_stats_accounting(tmp_path):
    """The pure-Python tier keeps the same counters, so /latency's
    per-stripe WAL view is tier-independent."""
    s = LogStore(str(tmp_path / "wal"), force_python=True, shards=2)
    try:
        s.append_entries(0, 1, [1], [b"a" * 100])
        s.append_entries(1, 1, [1], [b"b" * 100])
        s.sync()
        cur = s.wal.stats()
        assert set(cur) == set(wal_mod.WAL_STAT_KEYS)
        assert cur["fsync_calls"] >= 2 and cur["bytes"] >= 200
        per = s.wal.stats_per_stripe()
        assert len(per) == 2
        for k in wal_mod.WAL_STAT_KEYS:
            assert sum(p[k] for p in per) == cur[k]
    finally:
        s.close()


# ------------------------- registry thread contract (satellite audit) --


def test_histogram_reader_race_stays_consistent():
    """One writer hammers observe while readers render + validate the
    exposition page: every scrape must parse, keep le-buckets monotone,
    and agree _count == the +Inf bucket (the snapshot-consistency fix —
    reading the live counts list against a stale n broke this)."""
    m = Metrics()
    stop = threading.Event()
    errs = []

    def writer():
        i = 0
        while not stop.is_set():
            m.observe("race_s", (i % 1000) * 1e-6)
            i += 1

    def reader():
        while not stop.is_set():
            try:
                text = m.render_prometheus()
                validate_exposition(text)
                counts = {}
                for line in text.splitlines():
                    if line.startswith("raft_race_s_bucket"):
                        v = int(line.rsplit(" ", 1)[1])
                        prev = counts.get("last", 0)
                        assert v >= prev, "bucket series not monotone"
                        counts["last"] = v
                    elif line.startswith("raft_race_s_count"):
                        assert int(line.rsplit(" ", 1)[1]) \
                            == counts["last"], "_count != +Inf bucket"
                s = m.histogram("race_s").summary()
                assert s["count"] >= 0 and s["p50"] >= 0
            except Exception as e:      # propagate to the main thread
                errs.append(e)
                return

    w = threading.Thread(target=writer)
    rs = [threading.Thread(target=reader) for _ in range(2)]
    w.start()
    [r.start() for r in rs]
    time.sleep(0.5)
    stop.set()
    w.join()
    [r.join() for r in rs]
    if errs:
        raise errs[0]


def test_histogram_merge_shards():
    a, b = Histogram(), Histogram()
    for v in (1e-5, 2e-4, 0.3):
        a.observe(v)
    for v in (3e-5, 0.7):
        b.observe(v)
    a.merge(b)
    assert a.n == 5
    assert a.max == 0.7
    assert a.total == pytest.approx(1e-5 + 2e-4 + 0.3 + 3e-5 + 0.7)
    assert sum(a.counts) == 5
    with pytest.raises(ValueError):
        a.merge(Histogram(bounds=[1.0, 2.0]))


def test_host_phase_observes_only_from_tick_thread(tmp_path,
                                                   monkeypatch):
    """The documented single-writer contract, enforced: with the WAL
    engine at width 4 under submit load, every Histogram.observe lands
    on the tick thread — the engine's threads hand their timings back
    through the one call and client threads park samples in tracer
    rings, so the registry never sees a second writer."""
    monkeypatch.setenv("RAFT_LAT_SAMPLE", "1")
    seen = set()
    orig = Histogram.observe

    def spy(self, v):
        seen.add(threading.get_ident())
        orig(self, v)

    monkeypatch.setattr(Histogram, "observe", spy)
    c = LocalCluster(CFG, str(tmp_path), wal_shards=4, host_workers=4)
    try:
        c.wait_leader(0)
        for i in range(8):
            c.submit_via_leader(0, b"sw-%d" % i)
        c.tick(10)
        assert seen, "no observations — the probe is vacuous"
        assert seen == {threading.get_ident()}, \
            f"observe from non-tick threads: {seen}"
    finally:
        c.close()
