"""Ops tests: the Pallas quorum-commit kernel vs the jnp reference, the
metrics registry, and a full-engine parity run with use_pallas on."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rafting_tpu.core.types import EngineConfig
from rafting_tpu.ops.quorum import (
    quorum_commit_pallas, quorum_commit_ref,
)
from rafting_tpu.utils.metrics import Histogram, Metrics


def _random_case(rng, G, P, L):
    base = rng.integers(0, 5, G).astype(np.int32)
    length = rng.integers(0, L - 5, G).astype(np.int32)
    last = base + length
    match = rng.integers(0, L, (G, P)).astype(np.int32)
    match[:, 0] = last  # self slot = own last
    commit = np.minimum(rng.integers(0, L, G), last).astype(np.int32)
    # First own-term index: anywhere from below base to beyond last
    # (exercises both grant and refuse sides of the own-term rule).
    own_from = rng.integers(0, L + 4, G).astype(np.int32)
    lead = (rng.random(G) < 0.7)
    full = (1 << P) - 1
    # Random voter sets (always nonempty), ~half the lanes JOINT with an
    # independently random C_new — the membership plane's whole input
    # space (learner slots are simply absent from both masks).
    voters = (rng.integers(1, full + 1, G)).astype(np.int32)
    voters_new = np.where(rng.random(G) < 0.5,
                          rng.integers(1, full + 1, G), 0).astype(np.int32)
    return (jnp.asarray(match), jnp.asarray(own_from), jnp.asarray(last),
            jnp.asarray(commit), jnp.asarray(lead), jnp.asarray(voters),
            jnp.asarray(voters_new))


# L=256 with P=5 is config-4's peer count with a deep ring — an earlier
# kernel's O(L) unrolled ring select made exactly this shape 4x more
# expensive than L=64; the own_from reduction removed the ring from the
# kernel entirely, and this parametrization keeps the shape pinned in
# the suite.
@pytest.mark.parametrize("P,L", [(3, 16), (5, 256), (7, 64)])
def test_pallas_quorum_matches_reference(P, L):
    rng = np.random.default_rng(42 + P)
    G = 1000   # odd G exercises lane padding
    match, own_from, last, commit, lead, voters, vnew = \
        _random_case(rng, G, P, L)
    ref = quorum_commit_ref(match, own_from, last, commit, lead, voters,
                            vnew)
    state_vec = jnp.stack([commit, last, lead.astype(jnp.int32),
                           voters, vnew])
    interpret = jax.default_backend() != "tpu"
    got = quorum_commit_pallas(match, own_from, state_vec, interpret)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_masked_quorum_full_membership_matches_fixed():
    """With every slot a voter (the boot config), the masked kernel must
    reproduce the plain fixed-majority order statistic exactly: the
    majority-th largest match commits inside (commit, last] from the
    own-term fence up, the row minimum (full replication) below it
    too."""
    rng = np.random.default_rng(7)
    P, L, G = 3, 16, 500
    match, own_from, last, commit, lead, _, _ = _random_case(rng, G, P, L)
    full = jnp.full((G,), (1 << P) - 1, jnp.int32)
    zero = jnp.zeros((G,), jnp.int32)
    ref = quorum_commit_ref(match, own_from, last, commit, lead, full, zero)
    m, o, la, c, ld = map(np.asarray, (match, own_from, last, commit, lead))
    srt = np.sort(m, axis=1)
    q, lo = srt[:, P - (P // 2 + 1)], srt[:, 0]
    want = np.maximum(
        np.where(ld & (q > c) & (q >= o) & (q <= la), q, c),
        np.where(ld & (lo > c) & (lo <= la), lo, c))
    np.testing.assert_array_equal(np.asarray(ref), want)


def test_full_replication_commit_lane():
    """Reference Leader.java:260: an index replicated on ALL voters (min
    over VOTER slots) commits even below own_from — the lane that lets a
    fully-replicated prior-term suffix commit on a ring-full lane where
    the §8 no-op could not be appended.  A majority-only match must still
    respect the own-term fence."""
    own_from = jnp.asarray([5, 5], jnp.int32)   # no own-term entry yet
    last = jnp.asarray([4, 4], jnp.int32)
    commit = jnp.asarray([0, 0], jnp.int32)
    lead = jnp.asarray([True, True])
    voters = jnp.asarray([0b111, 0b111], jnp.int32)
    vnew = jnp.zeros(2, jnp.int32)
    # Group 0: full replication at 4 -> commits to 4 despite own_from=5.
    # Group 1: majority at 4 but one peer at 0 -> fence holds, commit 0.
    match = jnp.asarray([[4, 4, 4], [4, 4, 0]], jnp.int32)
    got = quorum_commit_ref(match, own_from, last, commit, lead, voters,
                            vnew)
    np.testing.assert_array_equal(np.asarray(got), [4, 0])
    # The Pallas kernel implements the same two lanes.
    state_vec = jnp.stack([commit, last, lead.astype(jnp.int32), voters,
                           vnew])
    interpret = jax.default_backend() != "tpu"
    got_k = quorum_commit_pallas(match, own_from, state_vec, interpret)
    np.testing.assert_array_equal(np.asarray(got_k), [4, 0])


def test_full_replication_lane_ignores_learners():
    """ISSUE 7 small fix: the full-replication lane takes the min over
    VOTER slots only — a learner hauling itself up from a snapshot
    (match 0) must not stall fullIndex.  P=4: slots 0-2 voters at match
    4, slot 3 a lagging learner at 0."""
    own_from = jnp.asarray([5], jnp.int32)      # own-term fence would block
    last = jnp.asarray([4], jnp.int32)
    commit = jnp.asarray([0], jnp.int32)
    lead = jnp.asarray([True])
    voters = jnp.asarray([0b0111], jnp.int32)   # learner slot 3 excluded
    vnew = jnp.zeros(1, jnp.int32)
    match = jnp.asarray([[4, 4, 4, 0]], jnp.int32)
    got = quorum_commit_ref(match, own_from, last, commit, lead, voters,
                            vnew)
    np.testing.assert_array_equal(np.asarray(got), [4])
    state_vec = jnp.stack([commit, last, lead.astype(jnp.int32), voters,
                           vnew])
    interpret = jax.default_backend() != "tpu"
    got_k = quorum_commit_pallas(match, own_from, state_vec, interpret)
    np.testing.assert_array_equal(np.asarray(got_k), [4])


def test_joint_quorum_needs_both_sets():
    """§6: while joint, an index commits only with a quorum in BOTH
    C_old and C_new."""
    own_from = jnp.asarray([1, 1], jnp.int32)
    last = jnp.asarray([4, 4], jnp.int32)
    commit = jnp.asarray([0, 0], jnp.int32)
    lead = jnp.asarray([True, True])
    # P=5: C_old = {0,1,2}, C_new = {3,4}.
    voters = jnp.asarray([0b00111, 0b00111], jnp.int32)
    vnew = jnp.asarray([0b11000, 0b11000], jnp.int32)
    # Group 0: quorum in C_old (0,1) but NOT in C_new (3 at 0, 4 at 0).
    # Group 1: quorums in both sets -> commit 4.
    match = jnp.asarray([[4, 4, 0, 0, 0], [4, 4, 0, 4, 4]], jnp.int32)
    got = quorum_commit_ref(match, own_from, last, commit, lead, voters,
                            vnew)
    np.testing.assert_array_equal(np.asarray(got), [0, 4])
    state_vec = jnp.stack([commit, last, lead.astype(jnp.int32), voters,
                           vnew])
    interpret = jax.default_backend() != "tpu"
    got_k = quorum_commit_pallas(match, own_from, state_vec, interpret)
    np.testing.assert_array_equal(np.asarray(got_k), [0, 4])


def test_engine_parity_with_pallas_quorum():
    """A full cluster run with use_pallas=True must behave identically to
    the jnp path: elect one leader per group and commit under load."""
    from rafting_tpu.core.cluster import DeviceCluster

    base_cfg = EngineConfig(n_groups=48, n_peers=3, log_slots=32, batch=4,
                            max_submit=4)
    results = {}
    for flag in (False, True):
        cfg = dataclasses.replace(base_cfg, use_pallas=flag)
        c = DeviceCluster(cfg, seed=9)
        for _ in range(50):
            c.tick(submit_n=2)
        for _ in range(10):
            c.tick()
        snap = c.snapshot()
        assert ((snap["role"] == 3).sum(axis=0) == 1).all()
        assert (snap["commit"].max(axis=0) > 0).all()
        results[flag] = snap["commit"].max(axis=0)
    # Same seed, same schedule -> identical commit frontiers.
    np.testing.assert_array_equal(results[False], results[True])


# ----------------------------------------------------------------- metrics --

def test_metrics_counters_and_histograms():
    m = Metrics()
    m.inc("commits", 5)
    m["commits"] += 3
    assert m["commits"] == 8
    m.gauge("groups_active", 17)
    for v in [1e-5, 2e-5, 1e-3, 0.5]:
        m.observe("tick_latency_s", v)
    d = m.to_dict()
    assert d["counters"]["commits"] == 8
    assert d["gauges"]["groups_active"] == 17
    h = d["histograms"]["tick_latency_s"]
    assert h["count"] == 4 and h["max"] == 0.5
    assert d["rates"]["commits_per_sec"] > 0
    assert m.to_json()


def test_histogram_quantiles():
    h = Histogram(bounds=[0.001, 0.01, 0.1, 1.0])
    for _ in range(98):
        h.observe(0.005)
    h.observe(0.5)
    h.observe(5.0)
    assert h.quantile(0.5) == 0.01   # conservative upper bound
    assert h.quantile(0.99) >= 1.0
    assert h.summary()["count"] == 100


def test_node_metrics_report(tmp_path):
    from rafting_tpu.testkit.harness import LocalCluster

    cfg = EngineConfig(n_groups=2, n_peers=3, log_slots=16, batch=4,
                       max_submit=4)
    c = LocalCluster(cfg, str(tmp_path))
    try:
        c.wait_leader(0)
        c.submit_via_leader(0, b"x")
        c.tick(5)
        rep = c.nodes[0].metrics.to_dict()
        assert rep["histograms"]["tick_latency_s"]["count"] > 0
        assert rep["gauges"]["groups_active"] == 2
        total_led = sum(n.metrics.to_dict()["gauges"]["groups_led"]
                        for n in c.nodes.values())
        assert total_led == 2
    finally:
        c.close()


# ---------------------------------------------------------------------------
# The ring primitives against a plain numpy model.  Every read and write
# the step makes over the log ring, the read FIFO and the peer planes is a
# compare-and-select along that axis (ops/select.py); the model below
# addresses one element at a time, as the gathers and scatters they
# replaced did.
# ---------------------------------------------------------------------------

_G, _L, _B, _P, _K = 37, 64, 8, 3, 4      # odd G; the shipped L, B = S, K


def _ring_case(seed):
    """A log whose live window wraps the ring in most rows: bases up to
    3 L, lengths 0 .. L."""
    from rafting_tpu.core.types import LogState
    rng = np.random.default_rng(seed)
    base = rng.integers(0, 3 * _L, _G).astype(np.int32)
    last = base + rng.integers(0, _L + 1, _G).astype(np.int32)
    log = LogState(
        term=jnp.asarray(rng.integers(1, 50, (_G, _L)), jnp.int32),
        conf=jnp.asarray(np.where(rng.random((_G, _L)) < 0.15,
                                  rng.integers(1, 1 << 9, (_G, _L)), 0),
                         jnp.int32),
        base=jnp.asarray(base), base_term=jnp.asarray(
            rng.integers(1, 50, _G), jnp.int32),
        base_conf=jnp.asarray(rng.integers(1, 8, _G), jnp.int32),
        last=jnp.asarray(last))
    return rng, log


def _windows(rng, log, K):
    """[G, K] consecutive indices from starts around the live window's
    two ends: rows with idx < base, == base, inside, == last, > last."""
    base, last = np.asarray(log.base), np.asarray(log.last)
    start = np.where(rng.random(_G) < 0.5, base - 2, last - K + 3) \
        + rng.integers(-2, 3, _G)
    start[:4] = [base[0], last[1] + 1, base[2] - K, last[3]]
    return (np.maximum(start, 0)[:, None]
            + np.arange(K)[None, :]).astype(np.int32)


def _np_read(log, idx, ring, under_base, absent):
    out = np.empty(idx.shape, np.int32)
    ring, base, last = (np.asarray(a) for a in (ring, log.base, log.last))
    for g in range(idx.shape[0]):
        for k in range(idx.shape[1]):
            i = idx[g, k]
            out[g, k] = under_base(g) if i <= base[g] else \
                ring[g, i % _L] if i <= last[g] else absent
    return out


def _case_read(kind, K):
    def run():
        from rafting_tpu.core.step import (
            ring_conf_batch, ring_term_at, ring_terms_batch)
        rng, log = _ring_case(100 + K)
        idx = _windows(rng, log, K)
        if kind == "terms":
            got = ring_terms_batch(log, jnp.asarray(idx))
            want = _np_read(log, idx, log.term,
                            lambda g: int(log.base_term[g]), -1)
        elif kind == "conf":
            got = ring_conf_batch(log, jnp.asarray(idx))
            want = _np_read(log, idx, log.conf, lambda g: 0, 0)
        else:
            got = ring_term_at(log, jnp.asarray(idx[:, 0]))[:, None]
            want = _np_read(log, idx[:, :1], log.term,
                            lambda g: int(log.base_term[g]), -1)
        np.testing.assert_array_equal(np.asarray(got), want)
    return run


def _case_latest_conf(short):
    def run():
        from rafting_tpu.core.step import latest_conf
        rng, log = _ring_case(7 + short)
        base, last = np.asarray(log.base), np.asarray(log.last)
        upto = np.maximum(last - rng.integers(0, 9, _G), base) \
            .astype(np.int32) if short else last
        cidx, word = latest_conf(log, jnp.asarray(upto))
        conf = np.asarray(log.conf)
        for g in range(_G):
            want = (0, int(log.base_conf[g]))
            for i in range(base[g] + 1, min(upto[g], last[g]) + 1):
                if conf[g, i % _L]:
                    want = (i, conf[g, i % _L])
            assert (int(cidx[g]), int(word[g])) == want, g
    return run


def _case_write(K, mask_kind):
    def run():
        from rafting_tpu.core.step import ring_write_batch
        rng, log = _ring_case(300 + K)
        idx = _windows(rng, log, K)
        vals = rng.integers(100, 200, (_G, K)).astype(np.int32)
        col = np.arange(K)[None, :]
        mask = {
            "none": np.zeros((_G, K), bool),
            "all": np.ones((_G, K), bool),
            # What the follower's append and the submit write: the first
            # n of the window, n = 0 .. K by row.
            "prefix": col < rng.integers(0, K + 1, _G)[:, None],
            "suffix": col >= rng.integers(0, K + 1, _G)[:, None],
            # Whole rows dropped (the scatter's out-of-range rows).
            "rows": np.broadcast_to((rng.random(_G) < 0.5)[:, None],
                                    (_G, K)),
        }[mask_kind]
        got = ring_write_batch(log.term, jnp.asarray(idx), jnp.asarray(vals),
                               jnp.asarray(mask))
        want = np.asarray(log.term).copy()
        for g in range(_G):
            for k in range(K):
                if mask[g, k]:
                    want[g, idx[g, k] % _L] = vals[g, k]
        np.testing.assert_array_equal(np.asarray(got), want)
    return run


def _case_span():
    from rafting_tpu.core.step import ring_span
    rng = np.random.default_rng(11)
    start = rng.integers(0, 5 * _L, _G).astype(np.int32)
    n = rng.integers(0, _L + 1, _G).astype(np.int32)
    n[:3] = [0, _L, 1]
    got = np.asarray(ring_span(_L, jnp.asarray(start), jnp.asarray(n)))
    want = np.zeros((_G, _L), bool)
    for g in range(_G):
        for i in range(start[g], start[g] + n[g]):
            want[g, i % _L] = True
    np.testing.assert_array_equal(got, want)


def _case_take_plane(ndim):
    def run():
        from rafting_tpu.ops.select import take_plane
        rng = np.random.default_rng(13 + ndim)
        shape = (_P, _G) if ndim == 2 else (_P, _G, _B)
        field = rng.integers(0, 1000, shape).astype(np.int32)
        peer = rng.integers(0, _P, _G).astype(np.int32)
        got = take_plane(jnp.asarray(field), jnp.asarray(peer))
        np.testing.assert_array_equal(
            np.asarray(got), field[peer, np.arange(_G)])
    return run


def _case_fifo_slots():
    """take_slots over the K-slot read FIFO, the other axis it addresses."""
    from rafting_tpu.ops.select import take_slots
    rng = np.random.default_rng(17)
    fifo = rng.integers(1, 99, (_G, _K)).astype(np.int32)
    slot = ((rng.integers(0, _K, _G)[:, None] + np.arange(_K)[None, :])
            % _K).astype(np.int32)
    np.testing.assert_array_equal(
        np.asarray(take_slots(jnp.asarray(fifo), jnp.asarray(slot))),
        np.take_along_axis(fifo, slot, axis=1))


def _case_barrier(lens):
    def run():
        from rafting_tpu.ops.quorum import read_barrier_release
        rng = np.random.default_rng(19)
        me = 1
        head = rng.integers(0, _K, _G).astype(np.int32)
        rq_len = {"empty": np.zeros(_G), "full": np.full(_G, _K),
                  "mixed": rng.integers(0, _K + 1, _G)}[lens] \
            .astype(np.int32)
        # Stamps rise along the FIFO from the head, as the step writes
        # them; evidence falls somewhere among them.
        t0 = rng.integers(1, 20, _G)
        stamp = np.zeros((_G, _K), np.int32)
        n = rng.integers(1, 9, (_G, _K)).astype(np.int32)
        for g in range(_G):
            for j in range(_K):
                stamp[g, (head[g] + j) % _K] = t0[g] + 2 * j
        evid = (t0[:, None] + rng.integers(-2, 2 * _K + 1, (_G, _P))) \
            .astype(np.int32)
        voters = rng.integers(1, 8, _G).astype(np.int32)
        vnew = np.where(rng.random(_G) < 0.3, rng.integers(1, 8, _G), 0) \
            .astype(np.int32)
        n_rel, n_served = read_barrier_release(
            *(jnp.asarray(a) for a in (voters, vnew)), me,
            *(jnp.asarray(a) for a in (evid, stamp, head, rq_len, n)))

        def quorum(bits, s, g):
            members = [p for p in range(_P) if bits >> p & 1]
            yes = [p for p in members if p == me or evid[g, p] >= s]
            return len(yes) >= len(members) // 2 + 1

        for g in range(_G):
            rel = served = 0
            for j in range(rq_len[g]):
                k = (head[g] + j) % _K
                if not (quorum(voters[g], stamp[g, k], g) and
                        (vnew[g] == 0 or quorum(vnew[g], stamp[g, k], g))):
                    break
                rel, served = rel + 1, served + n[g, k]
            assert (int(n_rel[g]), int(n_served[g])) == (rel, served), g
    return run


def _case_fifo_intake():
    """The step's own write into the read FIFO: a ready leader with
    rq_len 0 .. K is offered a batch; a full FIFO takes nothing and no
    slot but the tail's moves."""
    from rafting_tpu.core.step import node_step
    from rafting_tpu.core.types import (
        LEADER, HostInbox, Messages, init_state)
    cfg = EngineConfig(n_groups=16, n_peers=_P, read_slots=_K)
    G = cfg.n_groups
    rng = np.random.default_rng(23)
    head = rng.integers(0, _K, G).astype(np.int32)
    rq_len = (np.arange(G) % (_K + 1)).astype(np.int32)
    fifo = {name: rng.integers(1, 99, (G, _K)).astype(np.int32)
            for name in ("rq_idx", "rq_stamp", "rq_n")}
    st = init_state(cfg, 0, seed=0)
    st = st.replace(
        role=jnp.full((G,), LEADER, jnp.int32),
        term=jnp.ones((G,), jnp.int32), commit=jnp.full((G,), 5, jnp.int32),
        leader_id=jnp.zeros((G,), jnp.int32),
        hb_due=jnp.full((G,), 1 << 20, jnp.int32),
        rq_head=jnp.asarray(head), rq_len=jnp.asarray(rq_len),
        **{k: jnp.asarray(v) for k, v in fifo.items()})
    active = np.asarray(st.active)
    host = HostInbox.empty(cfg).replace(
        read_n=jnp.full((G,), 3, jnp.int32))
    now = int(st.now) + 1
    new, _, info = node_step(cfg, st, Messages.empty(cfg), host)
    took = active & (rq_len < _K)
    np.testing.assert_array_equal(np.asarray(info.read_acc) > 0, took)
    np.testing.assert_array_equal(np.asarray(info.read_rel), 0)
    np.testing.assert_array_equal(np.asarray(new.rq_len),
                                  np.where(active, rq_len + took, 0))
    for name, put in (("rq_idx", 5), ("rq_stamp", now), ("rq_n", 3)):
        want = fifo[name].copy()
        for g in np.nonzero(took)[0]:
            want[g, (head[g] + rq_len[g]) % _K] = put
        np.testing.assert_array_equal(np.asarray(getattr(new, name)), want)


_RING_CASES = (
    [(f"read_{kind}_K{K}", _case_read(kind, K))
     for kind in ("terms", "conf") for K in (1, _B, _P * _B)]
    + [("read_term_at", _case_read("at", 1)),
       ("latest_conf_upto_last", _case_latest_conf(0)),
       ("latest_conf_upto_short", _case_latest_conf(1))]
    + [(f"write_K{K}_mask_{m}", _case_write(K, m))
       for K in (1, _B) for m in ("none", "all", "prefix", "suffix", "rows")]
    + [("span", _case_span),
       ("take_plane_2d", _case_take_plane(2)),
       ("take_plane_3d", _case_take_plane(3)),
       ("fifo_take_slots", _case_fifo_slots),
       ("barrier_rq_len_0", _case_barrier("empty")),
       ("barrier_rq_len_K", _case_barrier("full")),
       ("barrier_rq_len_mixed", _case_barrier("mixed")),
       ("fifo_intake_rq_len_0_to_K", _case_fifo_intake)])


@pytest.mark.parametrize("case", [c for _, c in _RING_CASES],
                         ids=[n for n, _ in _RING_CASES])
def test_ring_primitive_matches_numpy_model(case):
    case()
