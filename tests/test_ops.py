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
