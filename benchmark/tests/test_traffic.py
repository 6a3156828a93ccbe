"""The schedule is a function of the traffic file and the seed, and every
seed gets the same amount of work."""

import glob
import os
from collections import Counter

import pytest

from benchmark.traffic import load_traffic, make_schedule

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIXES = sorted(glob.glob(os.path.join(HERE, "traffic", "*.json")))
BIG = 2 ** 31 + 12345          # more than 32 signed bits hold


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_same_seed_same_schedule_other_seed_other_order(path):
    t = load_traffic(path)
    a = make_schedule(t, BIG, 10.0, 999)
    assert a == make_schedule(t, BIG, 10.0, 999)
    b = make_schedule(t, BIG + 1, 10.0, 999)
    assert a != b
    assert [o.due_s for o in a] == sorted(o.due_s for o in a)
    assert all(0 <= o.due_s < 10.0 for o in a)


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_every_seed_gets_the_same_work(path):
    t = load_traffic(path)
    n = round(t["rate_ops_s"] * 10.0)
    for seed in (1, 7, BIG):
        s = make_schedule(t, seed, 10.0, 999)
        assert len(s) == n
        kinds = Counter(o.kind for o in s)
        assert kinds["r"] == round(t["read_share"] * n)
        assert max(Counter(o.target for o in s).values()) \
            - min(Counter(o.target for o in s).values()) <= 1
        writes = [o for o in s if o.kind == "w"]
        assert len({o.value for o in writes}) == len(writes)
        assert all(len(o.value) == t["value_bytes"] for o in writes)
        assert all(len(o.key) == t["key_bytes"] for o in s)
        assert all(0 <= o.group < 999 for o in s)


def test_zipfian_hot_key_share_is_the_distributions():
    t = load_traffic(os.path.join(HERE, "traffic", "ycsb-a-steady.json"))
    shares = []
    for seed in (3, 4):
        s = make_schedule(t, seed, 100.0, 99999, rate_ops_s=100)
        shares.append(Counter(o.key for o in s).most_common(1)[0][1] / len(s))
    # zipfian 0.99 over 10^6 keys: the hottest key draws about 6.5%
    assert all(0.055 < x < 0.075 for x in shares), shares
    assert abs(shares[0] - shares[1]) < 0.002
