"""Latency is timed from the due instant: a stall of the server lengthens
the latencies of the operations due during it, and the driver's own lateness
is kept apart."""

import threading
import time
from concurrent.futures import Future

from benchmark.driver import drive
from benchmark.reference import OK
from benchmark.traffic import Op


def _ops(n, gap):
    return [Op(i, i * gap, "w", f"k{i}", 0, 0, f"v{i}") for i in range(n)]


def test_stalled_server_lengthens_later_latencies():
    stall = {"at": 3, "for": 0.6}

    def send(op, command):           # a server that blocks its caller once
        if op.seq == stall["at"]:
            time.sleep(stall["for"])
        f = Future()
        f.set_result(op.value)
        return f

    comps, _ = drive(_ops(8, 0.05), send, window_s=0.4, drain_s=0.5)
    assert all(c.record.outcome == OK for c in comps)
    lat = [c.latency_s for c in comps]
    assert max(lat[:3]) < 0.3
    assert lat[3] >= 0.6
    # seq 4 was due 50 ms after seq 3 and waited behind the stall
    assert lat[4] >= 0.6 - 0.05 - 0.005
    assert lat[7] >= 0.6 - 4 * 0.05 - 0.005
    late = [c.late_s for c in comps]
    assert late[4] >= 0.5 and late[0] < 0.3


def test_unresolved_and_failed_operations_are_counted_not_dropped():
    def send(op, command):
        f = Future()
        if op.seq == 0:
            f.set_exception(RuntimeError("refused"))
        elif op.seq == 1:
            pass                      # never resolves
        elif op.seq == 2:
            raise ValueError("no future at all")
        else:
            threading.Timer(0.05, f.set_result, (op.value,)).start()
        return f

    comps, _ = drive(_ops(4, 0.01), send, window_s=0.05, drain_s=0.2)
    out = [c.record.outcome for c in comps]
    assert out == ["failed", "failed", "failed", OK]
    assert comps[1].record.returned == float("inf")
    assert "RuntimeError" in comps[0].record.error
    assert "ValueError" in comps[2].record.error
    assert 0.05 <= comps[3].latency_s < 0.15
