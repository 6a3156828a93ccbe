"""The comparison passes a correct history and fails a stale read, a lost
acknowledged write, diverged replicas and an altered reply."""

import math

from benchmark.reference import FAILED, OK, Record, check


def w(seq, key, inv, ret, ok=True):
    v = f"v{seq}"
    return Record(seq, "w", key, v, inv, ret if ok else math.inf,
                  OK if ok else FAILED, v if ok else None)


def r(seq, key, inv, ret, answer):
    return Record(seq, "r", key, None, inv, ret, OK, answer)


def history():
    return [w(0, "a", 0.0, 1.0), r(1, "a", 1.5, 2.0, "v0"),
            w(2, "a", 2.5, 3.5), w(3, "a", 2.6, 3.6),      # concurrent
            r(4, "a", 3.0, 3.2, "v0"),                     # before either ack
            r(5, "a", 4.0, 4.5, "v3"),
            r(6, "b", 0.0, 0.5, None),                     # never written
            w(7, "c", 0.0, 1.0, ok=False),                 # outcome unknown
            r(8, "c", 2.0, 2.5, "v7")]                     # ... but it landed


def replicas(a="v3", c="v7"):
    return [{"a": a, "c": c} for _ in range(3)]


def test_correct_history_passes():
    v = check(history(), replicas())
    assert v.correct, v.examples
    assert check(history(), replicas(a="v2", c=None) and
                 [{"a": "v2"}] * 3).numbers["stale_or_unknown_reads"] == 0


def test_stale_read_fails():
    h = history() + [r(9, "a", 5.0, 5.5, "v0")]    # v0 superseded by 3.6
    v = check(h, replicas())
    assert not v.correct and v.numbers["stale_or_unknown_reads"] == 1
    h = history() + [r(9, "a", 5.0, 5.5, None)]    # absent after acks
    assert check(h, replicas()).numbers["stale_or_unknown_reads"] == 1
    h = history() + [r(9, "a", 0.1, 0.2, "v3")]    # from the future
    assert check(h, replicas()).numbers["stale_or_unknown_reads"] == 1


def test_lost_acknowledged_write_fails():
    v = check(history(), replicas(a="v0"))         # ends before v2/v3 began
    assert not v.correct and v.numbers["lost_acked_writes"] == 3
    reps = [{"c": "v7"}] * 3                       # a: acknowledged, absent
    assert check(history(), reps).numbers["lost_acked_writes"] == 3


def test_diverged_replicas_fail():
    reps = replicas()
    reps[2] = {"a": "v2", "c": "v7"}               # both could be last: (b)
    v = check(history(), reps)                     # holds, (a) does not
    assert not v.correct
    assert v.numbers["replica_divergent_keys"] == 1
    assert v.numbers["lost_acked_writes"] == 0


def test_unknown_value_and_altered_reply_fail():
    v = check(history(), replicas(a="forged"))
    assert v.numbers["final_value_violations"] == 3
    h = history()
    h[0].answer = "v0-altered"
    assert check(h, replicas()).numbers["write_reply_mismatches"] == 1


def test_initial_state_is_the_value_before_the_window():
    h = [r(0, "a", 0.0, 0.5, "old"), w(1, "a", 1.0, 2.0),
         r(2, "a", 3.0, 3.5, "old")]
    v = check(h, [{"a": "v1"}] * 3, initial={"a": "old"})
    assert v.numbers["stale_or_unknown_reads"] == 1
