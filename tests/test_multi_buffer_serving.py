"""Serving over packed layouts that hold several word buffers each way.

At 10,000 lanes the dense message planes are 4.83 MB a node each way, past
``core/packing.py CHUNK_BYTES`` (4 MiB): the step's upload and readback each
cross in two word buffers, the flags behind the words of the second
(``multiraft-10k-3v``, PERF.md PR 31; a flag buffer more until PR 42).
These tests force such a shape at a size a CPU holds, by a small
``CHUNK_BYTES`` (three buffers each way: one more and the shape would take
the column step, ``core/step.py COLUMN_BUFFERS``, as it did unnoticed at
2 KB while that step moved four arrays a way): (a) the step over many buffers is leaf for leaf the step
over one; (b) three served containers take writes and linearizable reads
through ``RaftStub`` over such layouts, agree with a sequential model and
pass ``testkit/linz.py``.
"""

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rafting_tpu.api import RaftConfig, RaftContainer
from rafting_tpu.core import packing
from rafting_tpu.core.cluster import route
from rafting_tpu.core.step import (
    column_layouts, node_step_packed, step_layouts)
from rafting_tpu.core.types import (
    EngineConfig, HostInbox, Messages, init_state)
from rafting_tpu.testkit import linz
from rafting_tpu.testkit.harness import (
    free_ports, kv_factory, scaled_election_mul)
from rafting_tpu.testkit.history import History

SMALL_CHUNK = 3072          # bytes: a few [P, G] planes a buffer at 16 lanes


@pytest.fixture
def small_chunks(monkeypatch):
    """Layouts built while this holds close a buffer every 3 KB.  The
    layout cache is emptied on both sides so that no other test sees
    them.  Not ``small`` (tests/conftest.py): that one makes a shape take
    the column step, this one keeps it PACKED over several buffers (3 KB
    is two word buffers at 16 lanes, under the four the shape rule asks
    for)."""
    step_layouts.cache_clear()
    column_layouts.cache_clear()
    monkeypatch.setattr(packing, "CHUNK_BYTES", SMALL_CHUNK)
    yield
    step_layouts.cache_clear()
    column_layouts.cache_clear()


def _words(layout):
    """The buffers that hold words (none holds flags alone here)."""
    return sum(w > 0 for w in layout.words)


# Reads by the lease, and by strict ReadIndex (read_lease off: the step
# and the layouts gain read_seq / ae_seq / aer_seq, PR 45).
READS = [pytest.param(True, id="lease"), pytest.param(False, id="strict")]


@pytest.mark.parametrize("lease", READS)
def test_step_over_many_buffers_is_the_one_buffer_step(small_chunks,
                                                       monkeypatch, lease):
    cfg = EngineConfig(n_groups=16, n_peers=3, log_slots=16, batch=4,
                       max_submit=4, election_ticks=5, heartbeat_ticks=1,
                       rpc_timeout_ticks=4, read_lease=lease)
    N, G = cfg.n_peers, cfg.n_groups
    many_in, many_back = step_layouts(cfg, True)
    assert _words(many_in) >= 2 and _words(many_back) >= 2
    with monkeypatch.context() as m:
        m.setattr(packing, "CHUNK_BYTES", 4 << 20)
        step_layouts.cache_clear()
        one_in, one_back = step_layouts(cfg, True)
        assert len(one_in.buffers) == len(one_back.buffers) == 1
        rng = np.random.default_rng(11)
        one = [init_state(cfg, n, seed=5) for n in range(N)]
        outboxes = [jax.device_get(Messages.empty(cfg))] * N
        tails = [np.zeros(G, np.int32)] * N
        runs = []           # (node, host, inbox, readback) per step
        for t in range(30):
            inflight = jax.tree.map(lambda *a: np.stack(a), *outboxes)
            inboxes = jax.device_get(
                route(inflight, jnp.asarray(rng.random((N, N)) > 0.1)))
            outboxes = []
            for n in range(N):
                inbox = jax.tree.map(lambda a: a[n], inboxes)
                host = jax.device_get(HostInbox.empty(cfg)).replace(
                    submit_n=rng.integers(0, cfg.max_submit + 1, G,
                                          dtype=np.int32),
                    read_n=rng.integers(0, 3, G, dtype=np.int32),
                    durable_tail=tails[n])
                one[n], bufs = node_step_packed(
                    cfg, one_in, one[n], one_in.pack((host, inbox)))
                back = one_back.unpack(jax.device_get(bufs))
                runs.append((n, host, inbox, back))
                outboxes.append(back.outbox)
                tails[n] = back.info.log_tail
    # The same inputs through the many-buffer layouts (traced under the
    # small chunk, as the served path would trace them).
    many = [init_state(cfg, n, seed=5) for n in range(N)]
    accepted = 0
    for k, (n, host, inbox, want) in enumerate(runs):
        many[n], bufs = node_step_packed(
            cfg, many_in, many[n], many_in.pack((host, inbox)))
        assert len(bufs) == len(many_back.buffers)
        got = many_back.unpack(jax.device_get(bufs))
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree.leaves(want)):
            np.testing.assert_array_equal(
                a, b, err_msg=f"step {k} {jax.tree_util.keystr(path)}")
        accepted += int(want.info.submit_acc.sum())
    for a, b in zip(jax.tree.leaves(jax.device_get(many)),
                    jax.tree.leaves(jax.device_get(one))):
        np.testing.assert_array_equal(a, b)
    assert accepted > 0, "no leader accepted a write: the run proved little"


@pytest.fixture(params=READS)
def served(request, tmp_path, small_chunks):
    ports = free_ports(3)
    uris = [f"raft://127.0.0.1:{p}" for p in ports]
    cs = [RaftContainer(RaftConfig(
        local=u, peers=tuple(p for p in uris if p != u), n_groups=16,
        log_slots=32, batch=4, max_submit=4, tick_ms=20, seed=3,
        read_lease=request.param, data_dir=str(tmp_path / f"node{i}"),
        election_mul=scaled_election_mul(10)), kv_factory()).create()
        for i, u in enumerate(uris)]
    yield cs
    for c in cs:
        c.destroy()


def _cmd(op, k, v=None):
    d = {"op": op, "k": k}
    if v is not None:
        d["v"] = v
    return json.dumps(d)


def test_served_cluster_over_many_buffers_is_linearizable(served):
    cs = served
    for c in cs:
        assert c.open_context("kv") == 1
    inputs, readback = step_layouts(cs[0].node.cfg, True)
    assert _words(inputs) >= 2 and _words(readback) >= 2
    assert len(inputs.buffers) >= 3 and len(readback.buffers) >= 3
    assert column_layouts(cs[0].node.cfg, True) is None     # the packed step
    stubs = [c.get_stub("kv") for c in cs]
    # Sequential phase: one client through every member in turn against a
    # dict (writes through one member, read back through the next).
    model = {}
    for i in range(12):
        k, v = f"s{i % 4}", f"seq-{i}"
        assert stubs[i % 3].execute(_cmd("set", k, v), timeout=30) == v
        model[k] = v
        for j in (1, 2):
            got = stubs[(i + j) % 3].execute_read(_cmd("get", k), timeout=30)
            assert got == model[k], (i, j, got)
    assert stubs[0].execute_read(_cmd("get", "never"), timeout=30) is None
    # Concurrent phase: three recording clients, one per member.
    history = History()
    rec = [c.get_stub("kv").attach_history(history, f"c{i}")
           for i, c in enumerate(cs)]

    def client(i):
        rng = np.random.default_rng(100 + i)
        for seq in range(25):
            k = f"r{rng.integers(3)}"
            try:
                if rng.random() < 0.5:
                    rec[i].execute_read(_cmd("get", k), timeout=10)
                else:
                    rec[i].execute(_cmd("set", k, f"c{i}-{seq}"), timeout=10)
            except Exception:
                pass        # recorded as fail or info by the stub

    threads = [threading.Thread(target=client, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    counts = history.counts()
    assert counts["ok"] >= 40, counts
    res = linz.check(history)
    assert res.ok, res.render()
    # Every step moved three buffers each way (the loops run on: a step
    # that has started may not have uploaded yet, nor the one before it
    # been fetched).
    for c in cs:
        m = c.node.metrics
        assert m["h2d_transfers"] >= 3 * (m["ticks"] - 2) > 0
        assert m["d2h_transfers"] >= 3 * (m["ticks"] - 2)
    # The three replicas end identical.
    deadline = time.monotonic() + 20
    machines = [c.node.dispatcher.machine(1) for c in cs]
    while time.monotonic() < deadline and len(
            {m.last_applied() for m in machines}) != 1:
        time.sleep(0.05)
    assert machines[0].data == machines[1].data == machines[2].data
    assert all(machines[0].data[k] == v for k, v in model.items())
    for s in stubs + rec:
        s.close()
