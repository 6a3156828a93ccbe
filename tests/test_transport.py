"""Transport plane tests: codec round-trip, inbox merge semantics, real TCP
delivery and the ephemeral snapshot channel."""

import os
import threading
import time

import numpy as np
import pytest

from rafting_tpu.core.types import EngineConfig
from rafting_tpu.transport import (
    InboxAccumulator, TcpTransport, messages_template)
from rafting_tpu.transport import codec


CFG = EngineConfig(n_groups=8, n_peers=3, log_slots=16, batch=4, max_submit=4)


def _dense_fields(G, B):
    """A dense outbox slice with a couple of valid messages per kind."""
    f = {}
    for name, (dt, trail) in messages_template(CFG).items():
        f[name] = np.zeros((G,) + trail, dt)
    f["ae_valid"][2] = True
    f["ae_term"][2] = 7
    f["ae_prev_idx"][2] = 4
    f["ae_prev_term"][2] = 6
    f["ae_commit"][2] = 3
    f["ae_n"][2] = 2
    f["ae_ents"][2, :2] = 7
    f["rv_valid"][5] = True
    f["rv_term"][5] = 9
    f["rv_prevote"][5] = True
    f["aer_valid"][1] = True
    f["aer_term"][1] = 7
    f["aer_success"][1] = True
    f["aer_match"][1] = 6
    return f


def test_codec_roundtrip():
    tmpl = messages_template(CFG)
    fields = _dense_fields(CFG.n_groups, CFG.batch)
    payloads = {(2, 5): b"cmd-5", (2, 6): b"cmd-6"}
    packed = codec.pack_slice(
        1, fields, lambda g, i: payloads.get((g, i)))
    frames = codec.FrameReader().feed(packed)
    assert len(frames) == 1 and frames[0][0] == codec.MSGS
    src, out, got_payloads = codec.unpack_slice(frames[0][1], tmpl)
    assert src == 1
    cols, vals = out["ae_term"]
    assert cols.tolist() == [2] and vals.tolist() == [7]
    cols, ents = out["ae_ents"]
    assert ents.shape == (1, CFG.batch) and ents[0, :2].tolist() == [7, 7]
    run = got_payloads[2]
    assert list(got_payloads) == [2]
    assert run.start == 5 and run.end == 6
    assert run.materialize() == [b"cmd-5", b"cmd-6"]
    assert run.entry(0) == b"cmd-5" and bytes(run.piece(0, 2)).endswith(b"-6")
    cols, vals = out["rv_prevote"]
    assert cols.tolist() == [5] and bool(vals[0])


def test_codec_drops_ae_with_missing_payload():
    """An AE column whose payload is unavailable must be dropped (loss
    semantics), never shipped with a substitute empty command."""
    tmpl = messages_template(CFG)
    fields = _dense_fields(CFG.n_groups, CFG.batch)
    packed = codec.pack_slice(1, fields, lambda g, i: None)
    src, out, payloads = codec.unpack_slice(
        codec.FrameReader().feed(packed)[0][1], tmpl, CFG.n_groups)
    assert "ae_valid" not in out          # AE column dropped entirely
    assert payloads == {}
    assert "rv_valid" in out and "aer_valid" in out  # other kinds intact
    # Heartbeat (n=0) AE needs no payload and must survive payload_fn=None.
    hb = {name: np.zeros((CFG.n_groups,) + trail, dt)
          for name, (dt, trail) in tmpl.items()}
    hb["ae_valid"][4] = True
    hb["ae_term"][4] = 3
    packed = codec.pack_slice(0, hb, None)
    _, out, _ = codec.unpack_slice(
        codec.FrameReader().feed(packed)[0][1], tmpl, CFG.n_groups)
    assert out["ae_term"][0].tolist() == [4]


def test_codec_empty_slice_is_none():
    f = {name: np.zeros((CFG.n_groups,) + trail, dt)
         for name, (dt, trail) in messages_template(CFG).items()}
    assert codec.pack_slice(0, f, None) is None


def test_frame_reader_partial_and_crc():
    body = codec.pack_hello(1, 8, 3, 4)
    r = codec.FrameReader()
    assert r.feed(body[:5]) == []
    frames = r.feed(body[5:])
    assert frames[0][0] == codec.HELLO
    assert codec.unpack_hello(frames[0][1]) == (1, 8, 3, 4,
                                                codec.SCHEMA_TAG)
    bad = bytearray(body)
    bad[-1] ^= 0xFF
    with pytest.raises(IOError):
        codec.FrameReader().feed(bytes(bad))


def test_inbox_fifo_per_source():
    tmpl = messages_template(CFG)
    acc = InboxAccumulator(CFG, tmpl)
    # Two successive AE slices from src 1 for group 2: delivered one per
    # drain, oldest first (ordered delivery is what keeps the pipelined
    # AppendEntries window sound — see transport/inbox.py module doc).
    for term in (7, 8):
        f = _dense_fields(CFG.n_groups, CFG.batch)
        f["ae_term"][2] = term
        packed = codec.pack_slice(1, f, lambda g, i: b"x")
        _, body = codec.FrameReader().feed(packed)[0]
        src, fields, payloads = codec.unpack_slice(body, tmpl)
        acc.merge(src, fields, payloads)
    arrays, payloads = acc.drain()
    assert arrays["ae_valid"][1, 2] and arrays["ae_term"][1, 2] == 7
    assert acc.has_traffic   # second slice still queued
    arrays2, _ = acc.drain()
    assert arrays2["ae_valid"][1, 2] and arrays2["ae_term"][1, 2] == 8
    assert not acc.has_traffic
    # post-drain: clean slate
    arrays3, _ = acc.drain()
    assert not arrays3["ae_valid"].any()


def _feed_ae_slices(acc, tmpl, terms):
    for term in terms:
        f = _dense_fields(CFG.n_groups, CFG.batch)
        f["ae_term"][2] = term
        packed = codec.pack_slice(1, f, lambda g, i: b"x")
        _, body = codec.FrameReader().feed(packed)[0]
        src, fields, payloads = codec.unpack_slice(body, tmpl)
        acc.merge(src, fields, payloads)


def test_inbox_backlog_collapse():
    """A backlog beyond COLLAPSE_BACKLOG is collapsed to one slice
    (newest wins) so a lagging consumer catches up instead of serving
    stale traffic forever."""
    tmpl = messages_template(CFG)
    acc = InboxAccumulator(CFG, tmpl)
    k = InboxAccumulator.COLLAPSE_BACKLOG
    _feed_ae_slices(acc, tmpl, range(1, k + 2))   # k+1 queued > threshold
    arrays, _ = acc.drain()
    assert int(arrays["ae_term"][1, 2]) == k + 1  # newest won
    assert not acc.has_traffic                    # backlog fully consumed


def test_inbox_overflow_drops_newest():
    tmpl = messages_template(CFG)
    acc = InboxAccumulator(CFG, tmpl)
    cap = InboxAccumulator.MAX_QUEUED_SLICES
    _feed_ae_slices(acc, tmpl, range(1, cap + 3))  # 2 beyond the bound
    arrays, _ = acc.drain()
    # Overflow slices (cap+1, cap+2) were dropped at merge; the collapse
    # delivers the newest retained slice.
    assert int(arrays["ae_term"][1, 2]) == cap
    assert not acc.has_traffic


def _free_ports(n):
    import socket
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def test_tcp_delivery_and_snapshot(tmp_path):
    p0, p1 = _free_ports(2)
    peers = {0: ("127.0.0.1", p0), 1: ("127.0.0.1", p1)}

    blob = b"SNAPDATA" * 100
    src_file = tmp_path / "snap-src"
    src_file.write_bytes(blob)

    def provider(group, index, term):
        return 10, 3, str(src_file)

    ts = {}
    cfg2 = EngineConfig(n_groups=8, n_peers=2, log_slots=16, batch=4,
                        max_submit=4)
    tmpl2 = messages_template(cfg2)
    accs = {i: InboxAccumulator(cfg2, tmpl2) for i in (0, 1)}
    for i in (0, 1):
        ts[i] = TcpTransport(i, dict(peers), cfg2, tmpl2,
                             on_slice=accs[i].merge,
                             snapshot_provider=provider)
        ts[i].start()
    try:
        f = {name: np.zeros((cfg2.n_groups,) + trail, dt)
             for name, (dt, trail) in tmpl2.items()}
        f["rv_valid"][3] = True
        f["rv_term"][3] = 5
        packed = codec.pack_slice(0, f, None)
        deadline = time.time() + 10
        while not accs[1].has_traffic and time.time() < deadline:
            ts[0].send_slice(1, packed)
            time.sleep(0.05)
        arrays, _ = accs[1].drain()
        assert arrays["rv_valid"][0, 3] and arrays["rv_term"][0, 3] == 5
        # snapshot side channel (streamed to a file)
        dest = str(tmp_path / "snap-dest")
        res = ts[0].fetch_snapshot(1, group=3, index=10, term=3,
                                   dest_path=dest, timeout=10)
        assert res == (10, 3)
        assert open(dest, "rb").read() == blob
    finally:
        ts[0].close()
        ts[1].close()


def test_tcp_snapshot_larger_than_max_body(tmp_path):
    """A snapshot bigger than the frame codec's 64MB MAX_BODY must stream
    through chunking (the reference's raw sendfile side channel frees it
    from the codec cap the same way, EventBus.java:98-111)."""
    p0, p1 = _free_ports(2)
    peers = {0: ("127.0.0.1", p0), 1: ("127.0.0.1", p1)}

    total = codec.MAX_BODY + (1 << 20)       # 65 MB
    src_file = tmp_path / "big-snap"
    with open(src_file, "wb") as f:
        f.seek(total - 1)
        f.write(b"\x7f")                     # sparse on disk, full on wire

    def provider(group, index, term):
        return 99, 4, str(src_file)

    cfg2 = EngineConfig(n_groups=8, n_peers=2, log_slots=16, batch=4,
                        max_submit=4)
    tmpl2 = messages_template(cfg2)
    ts = {}
    for i in (0, 1):
        ts[i] = TcpTransport(i, dict(peers), cfg2, tmpl2,
                             on_slice=lambda *a: None,
                             snapshot_provider=provider)
        ts[i].start()
    try:
        dest = str(tmp_path / "big-dest")
        res = ts[0].fetch_snapshot(1, group=0, index=99, term=4,
                                   dest_path=dest, timeout=60)
        assert res == (99, 4)
        assert os.path.getsize(dest) == total
        with open(dest, "rb") as f:
            f.seek(total - 1)
            assert f.read(1) == b"\x7f"
    finally:
        ts[0].close()
        ts[1].close()


def test_reconnect_backoff_math():
    """Jittered exponential ladder: doubles from RECONNECT_DELAY, caps at
    RECONNECT_MAX, and every draw lands in [0.5, 1.0] x the deterministic
    base so a restarted peer never sees a sender stampede."""
    from rafting_tpu.transport.tcp import (
        PeerSender, RECONNECT_DELAY, RECONNECT_MAX)
    s = PeerSender(0, 1, ("127.0.0.1", 1), b"hello")
    for attempts in range(1, 24):
        base = min(RECONNECT_MAX, RECONNECT_DELAY * 2 ** min(attempts - 1, 6))
        for _ in range(16):
            d = s._backoff(attempts)
            assert 0.5 * base <= d <= base
    assert s._backoff(20) <= RECONNECT_MAX


def test_refused_connects_of_a_peer_never_reached_are_not_reconnects():
    """A sender pointed at an address nobody listens on yet (a node that
    boots before its peers) counts ``connects_refused_total`` on every
    attempt and no ``reconnects_total``: the health plane reads reconnects
    as this node's own flapping link and evacuates leadership on them
    (PERF.md, PR 31: a cold boot's 20 s alone degraded node 0).  stop()
    interrupts the backoff wait promptly."""
    import socket as _socket

    from rafting_tpu.transport.tcp import PeerSender
    from rafting_tpu.utils.metrics import Metrics

    # Reserve a port nobody is listening on.
    probe = _socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()

    m = Metrics()
    s = PeerSender(0, 1, ("127.0.0.1", port), b"hello", metrics=m)
    s.start()
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and m["connects_refused_total"] < 1:
        time.sleep(0.02)
    t0 = time.monotonic()
    s.stop()
    assert time.monotonic() - t0 < 5   # stop never waits out the backoff
    assert m["connects_refused_total"] >= 1
    assert m["reconnects_total"] == 0
    assert not s.connected


def test_a_drop_after_the_peer_was_reached_is_a_reconnect():
    """Once a channel has reached its peer, every later failure is a link
    that flaps: ``reconnects_total``, whether or not the peer is back."""
    import socket as _socket

    from rafting_tpu.transport.tcp import PeerSender
    from rafting_tpu.utils.metrics import Metrics

    srv = _socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    m = Metrics()
    s = PeerSender(0, 1, srv.getsockname(), b"hello", metrics=m)
    s.start()
    try:
        conn, _ = srv.accept()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and not s.connected:
            time.sleep(0.01)
        assert s.connected
        conn.close()
        srv.close()                     # the peer is gone for good
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and m["reconnects_total"] < 1:
            s.send(b"x" * 64)           # a send finds the reset
            time.sleep(0.05)
        assert m["reconnects_total"] >= 1
        assert m["connects_refused_total"] == 0
    finally:
        s.stop()


# ------------------------------------------------------- fault injection --
# The chaos plane's network nemesis (transport/faults.py): per-directed-
# link cut/drop/delay/dup/reorder, runtime-togglable, consulted by BOTH
# backends — these tests pin the per-backend delivery semantics.

from rafting_tpu.transport import (  # noqa: E402
    LinkFaults, LoopbackNetwork, LoopbackTransport)
from rafting_tpu.utils.metrics import Metrics  # noqa: E402


def test_linkfaults_asymmetric_and_partition():
    f = LinkFaults(3, seed=1)
    f.set_link(0, 1, False)          # A->B dead...
    assert f.plan(0, 1).cut
    assert f.plan(1, 0) == (True, False, 0.0, False, False)  # ...B->A alive
    f.restore(0, 1)
    assert not f.plan(0, 1).cut
    f.partition([[0], [1, 2]])
    assert f.plan(1, 2).deliver and f.plan(2, 1).deliver
    assert f.plan(0, 1).cut and f.plan(1, 0).cut and f.plan(2, 0).cut
    assert not f.link_up(0, 2) and f.link_up(1, 2)
    f.heal()
    assert f.plan(0, 1).deliver and f.plan(0, 2).deliver
    assert f.snapshot()["counters"]["cut"] == 4


def test_linkfaults_plan_deterministic_per_link():
    """Fault verdicts are a pure function of (seed, link, frame count):
    same seed replays the identical stream, another link's traffic never
    perturbs it — the property that makes a seeded soak replayable."""
    spec = dict(drop_p=0.3, dup_p=0.2, reorder_p=0.2, delay_p=0.1,
                delay_s=0.01)
    a, b, c = (LinkFaults(2, seed=42), LinkFaults(2, seed=42),
               LinkFaults(2, seed=43))
    for t in (a, b, c):
        t.set_flaky(0, 1, **spec)
    sa = [a.plan(0, 1) for _ in range(300)]
    assert sa == [b.plan(0, 1) for _ in range(300)]
    assert sa != [c.plan(0, 1) for _ in range(300)]
    d = LinkFaults(2, seed=42)
    d.set_flaky(0, 1, **spec)
    d.set_flaky(1, 0, drop_p=0.5)
    interleaved = []
    for _ in range(300):
        interleaved.append(d.plan(0, 1))
        d.plan(1, 0)                 # concurrent reverse-link traffic
    assert interleaved == sa


def _rv_frame(term, src=0):
    f = {name: np.zeros((CFG.n_groups,) + trail, dt)
         for name, (dt, trail) in messages_template(CFG).items()}
    f["rv_valid"][3] = True
    f["rv_term"][3] = term
    return codec.pack_slice(src, f, None)


def _loop_pair(seed=0):
    net = LoopbackNetwork(2)
    got = {0: [], 1: []}
    ts = {}
    tmpl = messages_template(CFG)
    for i in (0, 1):
        ts[i] = LoopbackTransport(
            net, i, CFG, tmpl,
            on_slice=lambda src, fields, payloads, _i=i:
                got[_i].append(int(fields["rv_term"][1][0])))
        ts[i].start()
    net.faults = LinkFaults(2, seed=seed)
    return net, ts, got


def test_loopback_fault_drop_dup_asymmetric():
    net, ts, got = _loop_pair()
    ts[0].metrics = Metrics()
    net.faults.set_flaky(0, 1, drop_p=1.0)
    ts[0].send_slice(1, _rv_frame(5))
    assert got[1] == []                      # dropped
    net.faults.set_flaky(0, 1, dup_p=1.0)
    ts[0].send_slice(1, _rv_frame(6))
    assert got[1] == [6, 6]                  # duplicated
    ts[1].send_slice(0, _rv_frame(9, src=1))
    assert got[0] == [9]                     # reverse link untouched
    assert ts[0].metrics["net_faults_dropped_total"] == 1
    assert ts[0].metrics["net_faults_duplicated_total"] == 1
    snap = net.faults.snapshot()["counters"]
    assert snap["dropped"] == 1 and snap["duplicated"] == 1


def test_loopback_delay_keeps_order_reorder_swaps():
    """Holdback semantics: a DELAYED frame rides out before the link's
    next frame (time shifted, order kept); a REORDERED frame rides out
    after it (the adjacent swap); heal drains held frames."""
    net, ts, got = _loop_pair()
    f = net.faults
    f.set_flaky(0, 1, delay_p=1.0, delay_s=0.01)
    ts[0].send_slice(1, _rv_frame(1))
    assert got[1] == []                      # held
    f.set_flaky(0, 1)                        # clear
    ts[0].send_slice(1, _rv_frame(2))
    assert got[1] == [1, 2]                  # delay: order preserved
    f.set_flaky(0, 1, reorder_p=1.0)
    ts[0].send_slice(1, _rv_frame(3))
    assert got[1] == [1, 2]                  # held
    f.set_flaky(0, 1)
    ts[0].send_slice(1, _rv_frame(4))
    assert got[1] == [1, 2, 4, 3]            # reorder: adjacent swap
    f.set_flaky(0, 1, reorder_p=1.0)
    ts[0].send_slice(1, _rv_frame(7))
    f.set_link(0, 1, False)
    ts[0].send_slice(1, _rv_frame(8))        # cut: lost, held stays held
    assert got[1] == [1, 2, 4, 3]
    f.restore(0, 1)
    net.flush_held()                         # heal-time drain
    assert got[1] == [1, 2, 4, 3, 7]


def test_loopback_partition_heal_midrun():
    net, ts, got = _loop_pair()
    net.faults.partition([[0], [1]])
    ts[0].send_slice(1, _rv_frame(1))
    ts[1].send_slice(0, _rv_frame(2, src=1))
    assert got == {0: [], 1: []}
    net.faults.heal()
    ts[0].send_slice(1, _rv_frame(3))
    ts[1].send_slice(0, _rv_frame(4, src=1))
    assert got == {0: [4], 1: [3]}


def _tcp_pair_with_faults():
    p0, p1 = _free_ports(2)
    peers = {0: ("127.0.0.1", p0), 1: ("127.0.0.1", p1)}
    cfg2 = EngineConfig(n_groups=8, n_peers=2, log_slots=16, batch=4,
                        max_submit=4)
    tmpl2 = messages_template(cfg2)
    faults = LinkFaults(2, seed=0)
    accs = {i: InboxAccumulator(cfg2, tmpl2) for i in (0, 1)}
    ts = {}
    for i in (0, 1):
        t = TcpTransport(i, dict(peers), cfg2, tmpl2,
                         on_slice=accs[i].merge, faults=faults)
        t.metrics = Metrics()   # before start(): senders capture it
        ts[i] = t
    for t in ts.values():
        t.start()
    return ts, accs, faults, cfg2, tmpl2


def _tcp_rv(cfg2, tmpl2, term, src=0):
    f = {name: np.zeros((cfg2.n_groups,) + trail, dt)
         for name, (dt, trail) in tmpl2.items()}
    f["rv_valid"][3] = True
    f["rv_term"][3] = term
    return codec.pack_slice(src, f, None)


def _tcp_wait_term(acc, want, send, deadline_s=15):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        send()
        time.sleep(0.05)
        if acc.has_traffic:
            arrays, _ = acc.drain()
            terms = arrays["rv_term"][arrays["rv_valid"]]
            if want in terms.tolist():
                return True
    return False


def test_tcp_fault_drop_then_heal():
    ts, accs, faults, cfg2, tmpl2 = _tcp_pair_with_faults()
    try:
        # Sanity: traffic flows, then a 100% drop regime silences the
        # link WITHOUT killing the connection, and clearing it heals.
        assert _tcp_wait_term(accs[1], 1,
                              lambda: ts[0].send_slice(
                                  1, _tcp_rv(cfg2, tmpl2, 1)))
        faults.set_flaky(0, 1, drop_p=1.0)
        for _ in range(10):
            ts[0].send_slice(1, _tcp_rv(cfg2, tmpl2, 2))
        time.sleep(0.5)
        drained = accs[1].drain()[0] if accs[1].has_traffic else None
        assert drained is None or 2 not in \
            drained["rv_term"][drained["rv_valid"]].tolist()
        dropped = ts[0].metrics["net_faults_dropped_total"]
        assert dropped >= 1
        faults.set_flaky(0, 1)               # heal mid-run
        assert _tcp_wait_term(accs[1], 3,
                              lambda: ts[0].send_slice(
                                  1, _tcp_rv(cfg2, tmpl2, 3)))
    finally:
        for t in ts.values():
            t.close()


def test_tcp_asymmetric_partition_and_backoff_under_flapping():
    """An injected one-way cut severs 0->1 only (1->0 keeps flowing),
    senders ride the SAME jittered-exponential reconnect ladder a real
    switch flap would (PR 12's backoff plane), and each heal of a
    flapping partition resumes delivery."""
    ts, accs, faults, cfg2, tmpl2 = _tcp_pair_with_faults()
    try:
        assert _tcp_wait_term(accs[1], 1,
                              lambda: ts[0].send_slice(
                                  1, _tcp_rv(cfg2, tmpl2, 1)))
        base_rec = ts[0].metrics["reconnects_total"]
        for flap, term in ((1, 10), (2, 11)):
            faults.set_link(0, 1, False)     # 0->1 dead...
            ts[0].send_slice(1, _tcp_rv(cfg2, tmpl2, 5))  # severs sender
            assert _tcp_wait_term(accs[0], 20 + flap,
                                  lambda: ts[1].send_slice(
                                      0, _tcp_rv(cfg2, tmpl2, 20 + flap,
                                                 src=1)))  # ...1->0 alive
            deadline = time.time() + 10
            while time.time() < deadline \
                    and ts[0].metrics["reconnects_total"] <= base_rec:
                time.sleep(0.05)
            assert ts[0].metrics["reconnects_total"] > base_rec, \
                "cut sender never entered the reconnect ladder"
            faults.set_link(0, 1, True)      # heal: ladder reconnects
            assert _tcp_wait_term(accs[1], term,
                                  lambda: ts[0].send_slice(
                                      1, _tcp_rv(cfg2, tmpl2, term)),
                                  deadline_s=20)
            base_rec = ts[0].metrics["reconnects_total"]
        assert ts[0].metrics["net_faults_cut_total"] >= 1
    finally:
        for t in ts.values():
            t.close()
