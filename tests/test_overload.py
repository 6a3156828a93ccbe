"""Overload robustness, end to end (ISSUE 15 acceptance scenarios).

Unit tier: the CoDel-style admission controller's window state machine
(proportional jump + sqrt ramp, good-window decay, the warmup-window
clamp regression), per-tenant fair shedding, the client retry budget and
per-peer circuit breaker, and the retry-after hint's round trip through
the forward wire's string encoding.

Cluster tier: forced-shed refusals are typed, marked pre-log, and carry
retry-after hints; admission counters reach /metrics and the /healthz
overload block reports DEGRADED (not unhealthy) while shedding;
quarantined stripes fast-fail with UnavailableError; and an open-loop
burst with a mid-run follower kill/restart shows refusals never become
lost acks — every OK-acked payload is applied, no shed payload ever is.

The 2x-capacity no-collapse A/B sweep (goodput plateau + bounded
admitted p999 with admission on; latency collapse with RAFT_ADMISSION=0)
is ``slow``-marked.
"""

import errno
import json
import os
import random
import urllib.request

import pytest

from rafting_tpu.api import (
    BusyLoopError, CircuitBreaker, OverloadError, RetryBudget,
    StorageFaultError, UnavailableError, retry_after_of,
)
from rafting_tpu.api.anomaly import is_refusal, wire_refusal
from rafting_tpu.api.retry import CLOSED, HALF_OPEN, OPEN, BreakerBoard
from rafting_tpu.core.types import EngineConfig
from rafting_tpu.log import LogStore
from rafting_tpu.runtime.admission import (
    MAX_LEVEL, AdmissionController, admission_from_env,
)
from rafting_tpu.testkit.harness import LocalCluster
from rafting_tpu.testkit.openloop import (
    OpenLoopResult, OpenLoopSpec, gen_schedule, no_collapse_check,
    run_open_loop, zipf_weights,
)

CFG = EngineConfig(n_groups=4, n_peers=3, log_slots=32, batch=4,
                   max_submit=4, election_ticks=10, heartbeat_ticks=3,
                   rpc_timeout_ticks=8)


# ---------------------------------------------------------------------------
# Controller unit tier (injected clock — no wall time, no cluster)
# ---------------------------------------------------------------------------

def test_controller_ramp_and_decay():
    a = AdmissionController(target_s=0.05, target_ticks=0.0,
                            interval_s=0.1, seed=1)
    assert not a.overloaded and a.admit() is None

    # Arm the window, then close it with min-sojourn 0.2s (4x target):
    # the PROPORTIONAL term must jump straight to the overshoot
    # fraction 1 - 0.05/0.2 = 0.75 in ONE window, not crawl up the
    # sqrt ramp (1 - 1/sqrt(2) ~= 0.29).
    a.note_delay(0.2, now=100.0)
    a.note_delay(0.25, now=100.05)
    a.note_delay(0.3, now=100.11)
    assert a.overloaded and a.lifo_now()
    assert abs(a.level - 0.75) < 1e-9
    assert a.retry_after() > 0.0

    # Sustained badness saturates at MAX_LEVEL, never 1.0: a trickle of
    # admits keeps sampling the queue so recovery can be observed.
    t = 100.11
    for _ in range(40):
        a.note_delay(5.0, now=t)
        t += 0.11
        a.note_delay(5.0, now=t)
    assert a.level == MAX_LEVEL

    # Good windows (queue drained -> sojourn 0.0) halve the level each
    # interval and snap to 0 below the floor: full recovery.
    for _ in range(12):
        a.note_delay(0.0, now=t)
        t += 0.11
        a.note_delay(0.0, now=t)
    assert a.level == 0.0 and not a.overloaded and not a.lifo_now()
    assert a.admit() is None

    # Shedding decisions while the level is pinned are probabilistic
    # but seeded: both outcomes occur, refusals carry a positive hint.
    a.force_level(0.5)
    hints = [a.admit(tenant="t") for _ in range(200)]
    sheds = [h for h in hints if h is not None]
    assert sheds and len(sheds) < 200
    assert all(h > 0 for h in sheds)
    assert a.shed == len(sheds) and a.admitted >= 200 - len(sheds)


def test_controller_warmup_window_clamp():
    """Regression: a window armed while the tick EWMA was transiently
    huge (first-tick JIT compile) must not freeze the controller — the
    window end may only SHRINK as the interval estimate recovers."""
    a = AdmissionController(target_s=0.05, target_ticks=3.0,
                            interval_s=0.1, seed=1)
    a.note_tick(30.0)                 # compile tick: interval_now() ~ 90s
    a.note_delay(0.5, now=0.0)        # arms a window ending near t=90
    for _ in range(200):              # steady state: 5ms ticks
        a.note_tick(0.005)
    assert a.interval_now() == 0.1
    # Without the clamp this window stays open until t~90 and the
    # controller never reacts; with it, two samples an interval apart
    # close the window and the level jumps.
    a.note_delay(0.5, now=1.0)
    a.note_delay(0.5, now=1.2)
    assert a.overloaded and a.level >= 0.75


def test_controller_expiry_engages_midwindow():
    a = AdmissionController(target_s=0.05, target_ticks=0.0,
                            interval_s=0.1, expire_factor=2.0, seed=1)
    assert a.expire_age() is None
    # The age cap must engage as soon as the CURRENT window's min
    # crosses the target — before any bad-window verdict — so the
    # backlog from overload onset is burned, not served a second late.
    a.note_delay(0.2, now=50.0)
    a.note_delay(0.2, now=50.01)
    assert not a.overloaded
    assert a.expire_age() == pytest.approx(2.0 * 0.05)
    # And stays engaged while shedding even if the window just rolled.
    a.force_level(0.6)
    a._win_min = None
    assert a.expire_age() == pytest.approx(2.0 * 0.05)
    # expire_factor=0 disables late shedding outright.
    off = AdmissionController(expire_factor=0.0)
    off.force_level(0.9)
    assert off.expire_age() is None


def test_controller_tenant_fairness():
    a = AdmissionController(seed=3)
    a.force_level(0.4)
    # Last closed window: "hog" took 900 of 1000 admits — 2.7x its fair
    # share of a 3-tenant window, well past the 2x over-share bar.
    a._tenant_win = {"hog": 900, "mouse": 50, "m2": 50}
    a._win_total = 1000
    n = 2000
    hog_shed = sum(1 for _ in range(n) if a.admit(tenant="hog") is not None)
    mouse_shed = sum(1 for _ in range(n)
                     if a.admit(tenant="mouse") is not None)
    # Over-share tenant sheds at min(0.98, 2*level + 0.25) = 0.98 >>
    # in-share tenant's protected level/2 = 0.2.
    assert hog_shed / n > 0.9
    assert mouse_shed / n < 0.3
    assert a.shed_tenant == hog_shed  # only over-share sheds counted
    # No tenant tag -> base level applies, no fairness bookkeeping.
    anon_shed = sum(1 for _ in range(n) if a.admit() is not None)
    assert 0.3 < anon_shed / n < 0.5


def test_admission_from_env(monkeypatch):
    monkeypatch.setenv("RAFT_ADMISSION", "0")
    assert not admission_from_env().enabled
    monkeypatch.setenv("RAFT_ADMISSION", "1")
    monkeypatch.setenv("RAFT_ADMISSION_TARGET_MS", "80")
    monkeypatch.setenv("RAFT_ADMISSION_LIFO", "0")
    monkeypatch.setenv("RAFT_ADMISSION_EXPIRE", "0")
    a = admission_from_env(seed=5)
    assert a.enabled and a.target_s == pytest.approx(0.08)
    assert not a.lifo and a.expire_factor == 0.0
    # Disabled controller admits everything and sheds nothing.
    d = AdmissionController(enabled=False)
    d.force_level(0.95)
    assert all(d.admit() is None for _ in range(50))
    assert d.expire_age() is None and not d.lifo_now()


# ---------------------------------------------------------------------------
# Client self-protection units
# ---------------------------------------------------------------------------

def test_retry_budget_token_bucket():
    b = RetryBudget(ratio=0.1, cap=2.0)
    assert b.tokens == pytest.approx(2.0)  # starts full: allow a burst
    assert b.try_spend() and b.try_spend()
    assert not b.try_spend()               # drained: stop retrying
    for _ in range(10):                    # 10 requests -> ~1 token back
        b.deposit()
    assert b.tokens == pytest.approx(1.0)
    assert b.try_spend(0.9) and not b.try_spend(0.9)
    for _ in range(100):                   # cap bounds the burst size
        b.deposit()
    assert b.tokens == pytest.approx(2.0)


def test_circuit_breaker_walk():
    clock = [1000.0]
    rng = random.Random(0)
    br = CircuitBreaker(trip_after=3, cooldown_s=1.0, max_cooldown_s=4.0,
                        probe_p=1.0, clock=lambda: clock[0], rng=rng)
    assert br.state == CLOSED and br.allow()
    br.failure()
    br.failure()
    assert br.state == CLOSED        # under the trip threshold
    br.failure()
    assert br.state == OPEN and not br.allow()
    assert br.retry_after_s() > 0.0
    clock[0] += 1.01                 # cooldown elapsed: probe slot
    assert br.allow()                # probe_p=1.0 -> always probes
    assert br.state == HALF_OPEN
    br.failure()                     # probe failed: reopen, cooldown x2
    assert br.state == OPEN and not br.allow()
    clock[0] += 1.5
    assert not br.allow()            # doubled cooldown not yet elapsed
    clock[0] += 0.6
    assert br.allow() and br.state == HALF_OPEN
    br.success()                     # probe landed: full close
    assert br.state == CLOSED and br.allow()

    board = BreakerBoard(trip_after=3)
    assert board.get(1) is board.get(1)
    assert board.get(1) is not board.get(2)


def test_retry_after_round_trip():
    # The hint is embedded in the MESSAGE so it survives the forward
    # wire's "REFUSED:Type: detail" string encoding.
    e = OverloadError("node 2: shedding load", retry_after_s=0.7312)
    assert retry_after_of(e) == pytest.approx(0.7312, abs=1e-3)
    assert isinstance(e, BusyLoopError)

    rebuilt = wire_refusal("OverloadError", str(e))
    assert type(rebuilt).__name__ == "OverloadError"
    assert is_refusal(rebuilt)
    assert retry_after_of(rebuilt) == pytest.approx(0.7312, abs=1e-3)

    u = wire_refusal("UnavailableError", "group 3: stripe quarantined")
    assert isinstance(u, StorageFaultError) and is_refusal(u)
    assert retry_after_of(wire_refusal("RaftError", "no hint here")) is None

    # Double-wrapping must not stack two hints in one message: the
    # constructor keeps the embedded one, so the WIRE round trip
    # preserves the origin hint (the local attribute still wins for the
    # object in hand).
    b = BusyLoopError(str(OverloadError("x", retry_after_s=0.5)),
                      retry_after_s=9.9)
    assert str(b).count("[retry_after=") == 1
    assert retry_after_of(b) == pytest.approx(9.9, abs=1e-3)
    assert retry_after_of(wire_refusal("BusyLoopError", str(b))) \
        == pytest.approx(0.5, abs=1e-3)


# ---------------------------------------------------------------------------
# Open-loop harness units
# ---------------------------------------------------------------------------

def test_openloop_schedule_properties():
    spec = OpenLoopSpec(rate=500.0, duration_s=1.0, n_tenants=4,
                        n_groups=4, seed=11)
    s1, s2 = gen_schedule(spec), gen_schedule(spec)
    assert s1 == s2, "schedule must be a pure function of the spec"
    assert s1 != gen_schedule(OpenLoopSpec(rate=500.0, duration_s=1.0,
                                           n_tenants=4, n_groups=4,
                                           seed=12))
    assert all(0.0 <= t < spec.duration_s for t, _, _ in s1)
    assert sorted(t for t, _, _ in s1) == [t for t, _, _ in s1]
    # Poisson at 500/s for 1s: count concentrates around 500.
    assert 350 < len(s1) < 650

    # Zipf weights skew monotonically and normalize.
    w = zipf_weights(4, 1.1)
    assert w[0] > w[1] > w[2] > w[3] and sum(w) == pytest.approx(1.0)

    # A pinned hot-tenant share overrides the Zipf tenant draw.
    hot = OpenLoopSpec(rate=2000.0, duration_s=1.0, n_tenants=4,
                       n_groups=4, hot_tenant_share=0.8, seed=7)
    sched = gen_schedule(hot)
    share = sum(1 for _, t, _ in sched if t == "tenant-0") / len(sched)
    assert 0.72 < share < 0.88

    # MMPP burstiness: quiet dwells at spec.rate, bursts at 10x — the
    # max arrivals in any 50ms bucket must beat plain Poisson's.
    mm = OpenLoopSpec(rate=500.0, duration_s=1.0, n_tenants=4, n_groups=4,
                      mmpp=(5000.0, 0.1, 0.05), seed=11)
    def peak_bucket(sched):
        buckets = {}
        for t, _, _ in sched:
            buckets[int(t / 0.05)] = buckets.get(int(t / 0.05), 0) + 1
        return max(buckets.values())
    assert peak_bucket(gen_schedule(mm)) > peak_bucket(s1)


def test_no_collapse_check_predicate():
    def res(ok, offered, p999):
        r = OpenLoopResult(duration_s=1.0)
        r.ok, r.offered, r.p999_s = ok, offered, p999
        return r
    healthy = [res(400, 500, 0.2), res(800, 1000, 0.3), res(820, 2000, 0.4)]
    ok, why = no_collapse_check(healthy, slo_s=1.0)
    assert ok, why
    collapsed = [res(400, 500, 0.2), res(800, 1000, 0.3), res(300, 2000, 0.4)]
    ok, why = no_collapse_check(collapsed, slo_s=1.0)
    assert not ok and "collapsed" in why
    blown_tail = [res(400, 500, 0.2), res(800, 1000, 2.5)]
    ok, why = no_collapse_check(blown_tail, slo_s=1.0)
    assert not ok and "p999" in why
    assert not no_collapse_check([], slo_s=1.0)[0]


# ---------------------------------------------------------------------------
# Cluster tier
# ---------------------------------------------------------------------------

def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5) as r:
        return r.status, r.read()


def test_forced_shed_refusals_metrics_and_healthz(tmp_path):
    c = LocalCluster(CFG, str(tmp_path))
    try:
        lead = c.wait_leader(0)
        c.submit_via_leader(0, b"warm")    # readiness gate open for sure
        node = c.nodes[lead]
        srv = node.start_observability(port=0)
        try:
            # Counters are pre-registered: visible at 0 before any shed.
            _, body = _get(srv.port, "/metrics")
            text = body.decode()
            for name in ("raft_admission_admitted", "raft_admission_shed",
                         "raft_admission_shed_tenant",
                         "raft_admission_expired"):
                assert name in text
            st, body = _get(srv.port, "/healthz")
            h = json.loads(body)
            assert st == 200 and h["ok"]
            ov = h["overload"]
            assert ov["enabled"] and not ov["shedding"]
            assert not ov["degraded"] and ov["retry_after_s"] == 0.0

            # Pin the controller into overload: refusals must be typed,
            # marked pre-log, and carry a positive retry-after hint.
            node.admission.force_level(0.9)
            outcomes = [node.submit(0, b"ov-%03d" % i, tenant="t")
                        for i in range(120)]
            refused = [f for f in outcomes
                       if f.done() and f.exception() is not None]
            assert refused, "level 0.9 must shed most of 120 submits"
            assert len(refused) < 120, "MAX_LEVEL trickle must admit some"
            for f in refused:
                e = f.exception()
                assert isinstance(e, OverloadError) and is_refusal(e)
                assert retry_after_of(e) > 0.0

            # Shedding is DEGRADED, not unhealthy: ok stays True so the
            # node is weighed down, not ejected.
            _, body = _get(srv.port, "/healthz")
            h = json.loads(body)
            assert h["ok"] and h["overload"]["shedding"]
            assert h["overload"]["degraded"]
            assert h["overload"]["retry_after_s"] > 0.0
            assert h["overload"]["shed_total"] == len(refused)

            # The tick thread folds the client-side counters into the
            # registry; admitted entries still commit.
            c.tick(30)
            _, body = _get(srv.port, "/metrics")
            text = body.decode()
            shed_line = [l for l in text.splitlines()
                         if l.startswith("raft_admission_shed_total ")][0]
            assert float(shed_line.split()[1]) == float(len(refused))
            done_ok = [f for f in outcomes
                       if f.done() and f.exception() is None]
            assert done_ok, "admitted submissions must still commit"
        finally:
            srv.close()
    finally:
        c.close()


def test_paced_tick_loop_does_not_read_its_own_period_as_a_queue(tmp_path):
    """A loop paced slower than its work (tick_ms above the tick's own
    cost — the served path at 100k lanes on one chip) makes every
    submission wait about one tick PERIOD before the device sees it.
    The controller's target is counted in ticks, so its tick must be
    that period: measured against the work time alone, each one-period
    wait looked like a standing queue and an idle cluster shed its only
    client."""
    import time
    c = LocalCluster(CFG, str(tmp_path), seed=9)
    try:
        lead = c.wait_leader(0)
        node = c.nodes[lead]
        pace = 0.15                # well above one tick's work
        for _ in range(25):        # the loop is paced from its first tick
            time.sleep(pace)
            c.tick(1)
        futs = []
        for k in range(20):
            if node.is_leader(0) and node.is_ready(0):
                futs.append(node.submit(0, b"paced-%d" % k))
            time.sleep(pace)
            c.tick(1)
        c.tick(10)
        adm = node.admission
        assert adm.shed == 0 and adm.expired == 0 and adm.level == 0.0, \
            adm.snapshot()
        assert futs and all(f.done() and f.exception() is None
                            for f in futs)
    finally:
        c.close()


def test_quarantined_stripe_fast_fails_unavailable(tmp_path):
    def store_factory(i):
        return LogStore(os.path.join(str(tmp_path), f"node{i}", "wal"),
                        force_python=True, shards=4)
    c = LocalCluster(CFG, str(tmp_path), store_factory=store_factory)
    try:
        lead = c.wait_leader(0)
        c.wait_leader(1)
        c.submit_via_leader(0, b"pre-fault")
        node = c.nodes[lead]
        # Groups stripe g % 4 over 4 shards: group 0 lives on stripe 0.
        node.store.set_fault("fsync", value=errno.EIO, shard=0)
        doomed = node.submit(0, b"doomed")
        for _ in range(100):
            if doomed.done() and node._poisoned_stripes:
                break
            c.tick()
        assert 0 in node._poisoned_stripes

        # Fast-fail, not a future that rides to its timeout: the refusal
        # is synchronous, typed, and marked pre-log retry-safe.
        for fut in (node.submit(0, b"after"), node.read(0, b"q")):
            assert fut.done()
            e = fut.exception()
            assert isinstance(e, UnavailableError)
            assert isinstance(e, StorageFaultError) and is_refusal(e)
        # Healthy groups on other stripes keep serving.
        c.submit_via_leader(1, b"healthy-post")
        c.assert_file_parity(1)
    finally:
        c.close()


def test_openloop_shed_during_nemesis_never_loses_acks(tmp_path,
                                                       monkeypatch):
    """Open-loop burst + follower kill/restart: every OK-acked payload
    must be applied somewhere, and no payload refused with a marked shed
    may EVER apply — a refusal that lands in the log would double-apply
    on retry, the exact bug class the pre-log marking rules out."""
    monkeypatch.setenv("RAFT_ADMISSION_TARGET_MS", "2")
    monkeypatch.setenv("RAFT_ADMISSION_TARGET_TICKS", "0.5")
    c = LocalCluster(CFG, str(tmp_path), seed=3)
    try:
        for g in range(CFG.n_groups):
            c.wait_leader(g)
        for n in c.nodes.values():
            n.admission.force_level(0.7)  # shed from the first arrival

        outcome = {}   # seq -> (group, exc name or None)

        def submit(grp, tenant, seq):
            try:
                lead = c.leader_of(grp)
            except AssertionError:
                lead = None
            node = c.nodes.get(lead) if lead is not None else None
            if node is None:
                node = next(iter(c.nodes.values()))
            fut = node.submit(grp, b"ol-%05d" % seq, tenant=tenant)

            def _done(f, seq=seq, grp=grp):
                e = f.exception()
                outcome[seq] = (grp, None if e is None
                                else type(e).__name__)
            fut.add_done_callback(_done)
            return fut

        steps = [0]
        victim = (c.wait_leader(0) + 1) % CFG.n_peers

        def step():
            steps[0] += 1
            if steps[0] == 120:
                c.kill_node(victim)          # nemesis: follower crash...
            elif steps[0] == 200:
                c.restart_node(victim)       # ...and recovery mid-burst
            c.tick()

        spec = OpenLoopSpec(rate=500.0, duration_s=1.0, n_tenants=3,
                            n_groups=CFG.n_groups, deadline_s=30.0,
                            seed=5)
        res = run_open_loop(spec, submit, step=step, drain_s=5.0)
        c.tick(40)   # let every replica finish applying

        assert res.ok > 0, "burst must make progress through the nemesis"
        assert res.shed_overload > 0, "forced level must shed some load"

        applied = {}  # group -> set of applied payload strings
        for g in range(CFG.n_groups):
            applied[g] = set()
            for i in c.nodes:
                applied[g].update(c.command_payloads(i, g))
        for seq, (g, kind) in outcome.items():
            payload = "ol-%05d" % seq
            if kind is None:
                assert payload in applied[g], \
                    f"acked seq {seq} lost from group {g}"
            elif kind in ("OverloadError", "BusyLoopError",
                          "UnavailableError"):
                assert payload not in applied[g], \
                    f"shed seq {seq} applied in group {g}"
        # Every resolved outcome is accounted for in the result taxonomy.
        assert res.ok + res.late + res.shed + res.errors == len(outcome)
        assert res.offered == len(gen_schedule(spec))
    finally:
        c.close()


@pytest.mark.slow
def test_openloop_2x_no_collapse_ab(tmp_path, monkeypatch):
    """The ISSUE 15 acceptance demo, sized for CI: at ~2x capacity the
    admission-controlled cluster keeps goodput >= 85% of peak with the
    admitted p999 inside the SLO, while the SAME offered load with
    RAFT_ADMISSION=0 blows the tail (late/pending work piles up)."""
    import time as _time

    # Bench-sized engine: enough log slack that snapshot compaction
    # keeps up with a sustained closed-loop firehose (the tiny 32-slot
    # CFG is sized for protocol tests, not throughput runs).
    bcfg = EngineConfig(n_groups=4, n_peers=3, log_slots=64, batch=8,
                       max_submit=8, election_ticks=10, heartbeat_ticks=3,
                       rpc_timeout_ticks=8)

    def probe_capacity(c):
        # Closed-loop throughput at this scale: burst-submit to every
        # leader, tick until drained, repeat.
        t0 = _time.monotonic()
        done = 0
        for _ in range(12):
            futs = []
            for g in range(bcfg.n_groups):
                ld = c.leader_of(g)
                if ld is not None:
                    futs.append(c.nodes[ld].submit_batch(g, [b"cap"] * 8))
            for _ in range(200):
                if all(f.done() for f in futs):
                    break
                c.tick()
            done += sum(8 for f in futs
                        if f.done() and f.exception() is None)
        return done / max(_time.monotonic() - t0, 1e-9)

    def run(root, mults, admission_on):
        if admission_on:
            monkeypatch.delenv("RAFT_ADMISSION", raising=False)
        else:
            monkeypatch.setenv("RAFT_ADMISSION", "0")
        c = LocalCluster(bcfg, root, seed=7)
        try:
            for g in range(bcfg.n_groups):
                c.wait_leader(g)
            cap = max(probe_capacity(c), 50.0)

            def submit(grp, tenant, seq):
                lead = c.leader_of(grp)
                if lead is None:
                    return None
                return c.nodes[lead].submit(grp, b"x-%06d" % seq,
                                            tenant=tenant)
            out = []
            for m in mults:
                spec = OpenLoopSpec(rate=cap * m, duration_s=1.5,
                                    n_tenants=4, n_groups=bcfg.n_groups,
                                    deadline_s=1.0, seed=int(m * 100))
                out.append(run_open_loop(spec, submit, step=c.tick,
                                         drain_s=4.0))
            return out
        finally:
            c.close()

    on1, on2 = run(str(tmp_path / "on"), [1.0, 2.0], True)
    (off2,) = run(str(tmp_path / "off"), [2.0], False)

    ok, why = no_collapse_check([on1, on2], slo_s=1.0)
    assert ok, f"admission-on sweep collapsed: {why} " \
               f"(1x={on1.to_dict()}, 2x={on2.to_dict()})"
    assert on2.shed_overload > 0, "2x capacity must shed with admission on"
    assert off2.shed_overload == 0, "RAFT_ADMISSION=0 must never shed"
    # Collapse evidence on the uncontrolled side: deadline-missed and
    # never-resolved work piles up and the tail blows past the
    # controlled side's.
    assert off2.late + off2.pending > on2.late + on2.pending
    assert off2.p999_s > on2.p999_s, \
        f"off={off2.to_dict()} vs on={on2.to_dict()}"
