"""RaftNode: one live Multi-Raft node — device engine + host runtime.

The top-level runtime object, playing the role of the reference's whole
wiring layer (RaftContainer + ContextManager + RaftRoutine + NettyCluster,
RaftContainer.java:41-58, context/ContextManager.java:43-55): it owns the
device-resident consensus state for ALL groups, the durable log tier, the
state-machine dispatcher, the snapshot archive and the transport endpoint,
and advances everything with one `tick()`.

Tick protocol (the host half of the engine's contract):

1. build the HostInbox: queued client submissions, finished snapshot
   installs, compaction grants from the maintain policy;
2. drain the transport inbox accumulator into the dense inbox planes,
   views of the tick's packed upload buffers (core/packing.py: one word
   buffer, the flags a byte each behind the words; more only when the
   planes take many MB), which cross to the device in one transfer each;
3. run the fused device step (`node_step_packed`) — all groups at once —
   and fetch its packed results, again one transfer a buffer;
4. PERSIST: stage WAL writes implied by the step (appended entries with
   payloads, truncations, (term, ballot) stable records), then ONE
   fsync-barrier `LogStore.sync()`;
5. only then RELEASE the outbox to peers — the reference's
   persist-before-reply durability rule (context/member/RaftMember.java:25,
   RocksLog flushWal after append, command/storage/RocksLog.java:87,195)
   amortized over every group in one barrier;
6. drive state-machine applies from the new commit frontier;
7. run the snapshot/compaction maintain policy and snapshot downloads.

Payload flow: a leader's payloads enter via `submit()`; a follower's arrive
staged with AppendEntries frames and are durably adopted only for the range
the device engine actually accepted (StepInfo.appended_from/to).
"""

from __future__ import annotations

import json
import logging
import math
import os
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


from ..core.packing import DenseView, alloc_regions, regions
from ..core.step import (
    WINDOW_SUMS, column_layouts, compact_readback, first_carry,
    node_step_columns, node_step_packed, pack_outbox, pack_readback,
    step_layouts)
from ..core.types import (
    I32, I32_SAFE_MAX, LEADER, NIL, EngineConfig, HostInbox,
    StepInfo, boot_conf_word as _boot_conf_word, init_state,
)
from ..log.store import LogStore, restore_raft_state
from ..machine.dispatch import ApplyDispatcher
from ..machine.spi import Checkpoint, MachineProvider
from ..snapshot.archive import SnapshotArchive
from ..snapshot.policy import MaintainAgreement
from ..transport import InboxAccumulator, messages_template
from ..transport.inbox import fill_columns, scatter_dense
from ..transport.codec import (
    BEAT, KIND_FIELDS, assemble_slice, frame, pack_beat,
    pack_hops, pack_kind_section,
)
from ..api.anomaly import (
    BatchAbortedError, BusyLoopError, LeadershipEvacuatedError,
    NotLeaderError, NotReadyError, ObsoleteContextError, OverloadError,
    StorageFaultError, UnavailableError, as_refusal,
)
from .admission import admission_from_env
from .txn import txn_plane_from_env
from ..log.wal import WalNoSpace, WalSyncError
from ..utils.health import health_from_env
from ..utils.heat import heat_registry_from_env
from ..utils.latency import (
    ACKED, FSYNCED, HOP_ECHO, HOP_REQUEST, OFFERED, SENT, SERVED, STAGED,
    hops_from_env, tracer_from_env,
)
from ..utils.metrics import Metrics
from ..utils.profiling import StageSpans
from ..utils.tracelog import TraceLog

log = logging.getLogger(__name__)

# Shared length vector for election no-op spans (one empty payload);
# consumers only read it.
_NOOP_LENS = np.zeros(1, np.uint32)

# A loop starts a step for arriving work only when a whole step fits this
# many times into what is left of the period: once for the step itself,
# once more for its tail and for a step slower than any lately seen.
SETTLE_MARGIN = 2.0

# Heartbeat rounds a node follows at once (``hb_round_s``).  A started loop
# has one open, the period's; a caller that steps its nodes in lock step
# advances the clock every step and hears of a round two steps later.
HB_ROUNDS_OPEN = 4


def arrival_step_at(now: float, ended: float, took: float,
                    due: Optional[float], cost: float,
                    waited: float = 0.0) -> Optional[float]:
    """When a loop with work waiting at ``now`` may start a step for it
    between two timer ticks, or None when the work is to ride the
    timer's own tick at ``due``.  ``ended`` and ``took``: when the last
    step ended and how long it ran; ``waited``: how much of ``took`` its
    thread spent blocked on another agent, holding no interpreter;
    ``cost``: what a whole step has lately taken (all in seconds, on the
    clock of ``now``).  Two rules, both about what the loop observes of
    itself:

    * the gap: no step starts before the time the last step HELD THE
      INTERPRETER (``took - waited``) has passed since it ended.
      Arrivals inside the gap share the next step, so the batching a
      dense load needs comes back by itself as load rises (interpreter
      time a step grows with the operations it carries), and one node's
      arrival steps never take more than about half of an interpreter.
      Blocked is what the loop observes at boundaries it has anyway: the
      wait for the device (stage ``scan_device``: ``block_until_ready``),
      the copy down (``scan_fetch``: ``device_get``) and the WAL's own
      fsync seconds; all three run with the interpreter's lock released,
      so charging them a second time behind the step bought no other
      thread anything.  ``dispatch_enqueue`` is the host's own work on
      the call of the step (CPU, not a wait: PERF.md, PR 36) and
      ``dispatch_upload`` holds the lock for most of its length (PERF.md,
      PR 43): both count as held.  A Python stage that waited for the
      interpreter counts as held too, so three loops that crowd one
      interpreter lengthen their own gaps.  ``waited`` 0 (a step that
      failed) is the step's whole duration;
    * room: ``SETTLE_MARGIN`` times ``cost`` (the WHOLE step, waits and
      all) fits between the start and ``due``, so a step of the usual
      length ends before the timer's tick is due.  A step that started
      runs to its end whatever it then takes: one that outlasts the
      period makes the timer's tick late (``tick_late_s``,
      ``ticks_late``).  A node whose step fills its period gets None
      every time and ticks by the timer alone, as does a caller with no
      loop and so no deadline (``due`` None).
    """
    at = max(now, ended + max(0.0, took - waited))
    if due is None or at + SETTLE_MARGIN * cost > due:
        return None
    return at


class BatchSubmit:
    """One future for a whole batch of commands (resolves to the list of
    apply results in submission order; ``single=True`` — the plain
    ``submit()`` path — resolves to the lone result itself and fails with
    the bare error).  Amortizes the per-command ``Future`` cost — a
    ``threading.Condition`` allocation per command was the top client-side
    cost under dense load.  Speaks the dispatcher's promise-sink protocol
    (``_complete``/``_fail``) directly, so a whole accepted batch registers
    as ONE promise range.  Completion/failure happen on the tick thread
    only (the dispatcher's single-writer rule), so no extra locking is
    needed.  On failure the future raises ``BatchAbortedError`` carrying
    per-slot outcomes, so an already committed-and-applied prefix is never
    silently discarded."""

    __slots__ = ("_future", "results", "completed", "_remaining", "single",
                 "_err", "span")

    # One shared lock for the lazy-future handoff (creation vs completion
    # can race across client and tick threads).  Class-level on purpose: a
    # lock PER batch would reintroduce the per-batch allocation cost the
    # laziness exists to kill, and the critical sections are a few
    # dictionary-free statements.
    _lock = threading.Lock()

    def __init__(self, n: int, single: bool = False, eager: bool = True):
        """``eager=False`` defers the Future (and its Condition allocation)
        until someone actually reads ``.future`` — the bulk fan-out path
        (submit_batch_many) creates ~100k batches per round whose futures
        are usually never awaited."""
        self._future: Optional[Future] = Future() if eager else None
        self.results: list = [None] * n
        self.completed: list = [False] * n
        self._remaining = n
        self.single = single
        self._err: Optional[Exception] = None
        # Sampled lifecycle span riding this batch (utils/latency.py) —
        # at most one entry per batch is traced, so the per-entry
        # _complete loop stays span-free; the ack stamp fires once, when
        # the batch resolves.
        self.span = None

    @property
    def future(self) -> Future:
        f = self._future
        if f is None:
            with self._lock:
                f = self._future
                if f is None:
                    f = Future()
                    # Completion state that landed before this publish is
                    # replayed here; later completions see _future set.
                    if self._err is not None:
                        f.set_exception(self._err)
                    elif self._remaining == 0:
                        f.set_result(
                            self.results[0] if self.single else self.results)
                    self._future = f
        return f

    def _complete(self, k: int, result) -> None:
        self.results[k] = result
        self.completed[k] = True
        self._remaining -= 1
        if self._remaining == 0:
            sp = self.span
            if sp is not None:
                sp.mark(ACKED if sp.kind == "w" else SERVED)
                sp.tr.retire(sp, "ok")
            with self._lock:
                f = self._future
            if f is not None and not f.done():
                f.set_result(
                    self.results[0] if self.single else self.results)

    def _fail(self, err: Exception) -> None:
        sp = self.span
        if sp is not None:
            # The batch died after (possibly) entering the log: the
            # entry MAY still commit on a new leader — outcome-unknown,
            # never a fabricated latency (utils/latency.py).
            sp.tr.retire(sp, "unknown")
        wrapped = err if self.single else BatchAbortedError(
            err, list(self.results), list(self.completed))
        with self._lock:
            if self._err is None:
                self._err = wrapped
            f = self._future
        if f is not None and not f.done():
            f.set_exception(wrapped)

    def _refuse(self, err: Exception) -> None:
        """Pre-log refusal of the WHOLE batch: nothing was enqueued, so the
        future carries the bare (marked) refusal — not a BatchAbortedError
        — matching submit_batch's refusal contract."""
        sp = self.span
        if sp is not None:
            sp.tr.retire(sp, "refused")   # provably never entered the log
        with self._lock:
            if self._err is None:
                self._err = err
            f = self._future
        if f is not None and not f.done():
            f.set_exception(err)


class _SubBatch:
    """One queued client batch: an arena of payload bytes plus its promise
    sink.  ``taken`` tracks how many entries the device already accepted
    (a batch can be consumed across ticks); the queue drops it once fully
    taken.  Building the arena happens on the CLIENT thread (submit /
    submit_batch), so the tick thread's accept path is pure pointer
    arithmetic — no per-entry Python ever again."""

    __slots__ = ("run", "sink", "taken", "t_enq")

    def __init__(self, run, sink: BatchSubmit):
        self.run = run          # codec.PayloadRun (start unused: 0)
        self.sink = sink
        self.taken = 0
        # Enqueue instant — the sojourn clock the admission controller's
        # queue-delay signal reads at device-accept time (runtime/
        # admission.py).
        self.t_enq = time.monotonic()


class _ReadBatch:
    """One client call's linearizable reads (``read`` / ``read_batch``):
    query payloads + promise sink.  WAITING from enqueue until its
    group's offer slot is won; from there it is one part of a
    :class:`_ReadOffer`."""

    __slots__ = ("payloads", "sink", "t_enq")

    def __init__(self, payloads, sink: BatchSubmit, t_enq: float):
        self.payloads = payloads
        self.sink = sink
        self.t_enq = t_enq


class _ReadOffer:
    """What one ReadIndex barrier carries: every batch of a group that was
    WAITING when the group's offer slot was won, in arrival order.

    An offer moves through the host stages that mirror the device FIFO
    (core/types.py rq_* lanes): OFFERED (this tick's HostInbox.read_n, one
    count for all parts) -> PENDING (the device stamped it with a
    ReadIndex; awaiting the quorum barrier) -> RELEASED (barrier
    confirmed; served once ``applied >= read_index``).  The device stamps
    an offer whole or not at all, so there is no ``taken`` cursor; each
    part keeps its own sink, span and result list.  ``lease``: the
    step that stamped it also released it (StepInfo.read_lease), with no
    ReadIndex round trip; ``carried``: on evidence of an earlier tick
    (StepInfo.read_carried: the lease outlived the period it was
    acknowledged in, core/step.py phase 6b); ``woke``: that step woke the
    lane from hibernation, so the barrier is the heartbeat that wakes its
    followers; ``t_stamp``: when the host phase of the stamping step saw
    the stamp (``time.perf_counter``), from which a batch that a later
    step's acknowledgements release measures its round."""

    __slots__ = ("parts", "n", "lease", "carried", "woke", "t_stamp")

    def __init__(self, parts: List[_ReadBatch]):
        self.parts = parts
        self.n = sum(len(b.payloads) for b in parts)
        self.lease = self.carried = self.woke = False
        self.t_stamp = 0.0


class _TickCtx:
    """One tick in flight, from ``_dispatch`` to the end of its host phase.

    Created by ``_dispatch`` holding the step's packed result buffers on
    the device (the scan may still be executing); ``_fetch`` pulls them and
    sets the host planes, numpy views into the fetched buffers; the host
    phase (``_host_phase``) consumes those, with the per-tick inputs
    (inbox arrays, staged payload runs, offered counts) carried here."""

    __slots__ = (
        # dispatch-time host inputs
        "submit_n", "read_n", "staged_payloads", "arrays",
        # whether the step advanced the engine's clock (a timer tick) or
        # was started for arriving work inside a period
        "timer", "started",
        # the packed results on the device and their layout (dispatch);
        # for a column step (core/step.py node_step_columns) its layouts
        # (``packed`` is then the one buffer of its rows and the outbox's
        # columns) and the dense outbox left on the device
        "packed", "readback", "columns", "out_dense",
        # a column step's [G] side (_RowStep; None on a packed step)
        "rows",
        # -> host views of the fetched buffers (fetch)
        "info", "outbox", "term", "voted", "role", "leader", "commit",
        "base", "base_term",
        # Hibernation (cfg.hibernate): the lanes the step's peer-lost
        # signal named and those it asked something beside a write or a
        # read (dispatch); the lanes it woke (fetch).
        "wake_ids", "asked_ids", "woke_ids",
    )


class _RowStep:
    """The ``[G]`` side of one column step in flight (core/step.py
    node_step_columns): what the step left on the device for a fetch
    whose rows do not hold its results, and the lanes the step wrote into
    the node's persistent planes, which its host phase clears again."""

    __slots__ = (
        # the step's RowCarry on the device (pack_readback's operand)
        "carry",
        # the (submit_n, read_n) planes ctx.submit_n / ctx.read_n are, the
        # lanes written into them at dispatch and the values there
        "up", "sub_ids", "sub_n", "read_ids", "read_n",
        # the lanes whose event planes the fetch wrote (None: the step's
        # results came down whole and wrote none), and what those rows
        # moved the sum of the commit plane by
        "down_ids", "commit_delta",
    )


class _PersistPrep:
    """One tick's persist plan, precomputed once for either persist
    step: columnar change-detection arrays, the popped submission spans,
    and the staged-frame metadata.  Building this is cheap (a handful of
    fancy indexes + one lock'd queue pop); the per-written-group span
    staging it feeds is the expensive part."""

    __slots__ = (
        # the lanes the plan was selected among (None: every lane) and
        # those of them whose (term, ballot) moved; dirty and log_tail are
        # the step's planes, read at lanes
        "ids", "dirty", "stable_g", "log_tail", "h_term", "h_voted",
        "h_base", "h_base_term",
        "wrote", "wrote_l", "lo_l", "hi_l", "nsub_l", "sublo_l",
        "src_l", "term_l", "fr_valid", "fr_n", "fr_start",
        "fr_ents", "fr_cents", "own_by_g", "staged_payloads",
        "noop_g", "noop_idx", "noop_term", "lanes",
        "conf_g", "conf_app", "conf_term", "conf_word",
        "sub_acc", "submit_n",
    )


def _at(plane: np.ndarray, lanes: Optional[np.ndarray]) -> np.ndarray:
    """``plane`` over ``lanes`` (None: over every lane, the plane itself)."""
    return plane if lanes is None else plane[lanes]


class RaftNode:
    # Read by benchmark/harness.py's ``[selected]`` line and by nothing
    # else: there is one tick order (tick()), and it feeds durable_tail.
    pipeline = True

    def __init__(self, cfg: EngineConfig, node_id: int, data_dir: str,
                 provider: MachineProvider,
                 transport_factory: Callable,
                 seed: int = 0,
                 maintain: Optional[MaintainAgreement] = None,
                 initial_active: Optional[np.ndarray] = None,
                 group_queue_cap: int = 512,
                 total_queue_cap: int = 500_000,
                 busy_threshold: int = 1_000,
                 store=None,
                 serializer=None,
                 wal_shards: Optional[int] = None,
                 host_workers: Optional[int] = None,
                 latency_slo_s: Optional[float] = None):
        """``transport_factory(node, on_slice, snapshot_provider)`` builds
        the transport endpoint (TcpTransport / LoopbackTransport).
        ``initial_active`` masks which group lanes start open (default all;
        the container passes the admin-group view so closed groups stay
        inert, reference Administrator restart re-creation,
        command/admin/Administrator.java:50-57).
        ``store``: any LogStoreSPI product (log/spi.py; reference StateLoader
        SPI via RaftFactory.loadState, support/RaftFactory.java:18) —
        default is the durable segmented WAL under ``data_dir``.
        ``serializer``: CmdSerializer for command/result encoding across
        the leader-forward relay (api/serial.py; reference CmdSerializer,
        support/serial/CmdSerializer.java:11-24) — default JSON.
        ``wal_shards``: stripe count for the default WAL store (ignored
        when ``store`` is passed) — default from env RAFT_WAL_SHARDS,
        else 4.
        ``host_workers``: thread width of the native WAL engine's
        stage-and-fsync call and payload pack (default 1), clamped to
        the store's stripe count; a store whose engine is the Python
        one stages on the tick thread, width 1, whatever is asked.
        ``latency_slo_s``: end-to-end commit-latency SLO target the
        latency plane's burn gauges measure against (utils/latency.py)
        — default env RAFT_SLO_MS (milliseconds), else 500ms."""
        from ..api.serial import JsonSerializer

        self.cfg = cfg
        self.node_id = node_id
        self.data_dir = data_dir
        self.serializer = serializer or JsonSerializer()
        os.makedirs(data_dir, exist_ok=True)
        if wal_shards is None:
            wal_shards = int(os.environ.get("RAFT_WAL_SHARDS", "4"))

        self.store = store if store is not None \
            else LogStore(os.path.join(data_dir, "wal"),
                          shards=max(1, wal_shards))
        n_stripes = int(getattr(self.store, "n_stripes", 1))
        # group -> WAL stripe (the store's g % S map): what a storage
        # fault quarantines.
        self._stripe_of = np.arange(cfg.n_groups, dtype=np.int64) % n_stripes
        self._n_stripes = n_stripes
        # The native WAL engine stages and fsyncs a tick in ONE call, on
        # this many OS threads (worker k owns shards s % W == k, no GIL);
        # the Python engine stages on the tick thread (see _persist).
        # Both write byte-identical segments, so recovery is
        # interchangeable between them.
        self._native_wal = bool(getattr(self.store, "can_stage_native",
                                        False))
        self.host_workers = min(max(1, int(host_workers or 1)), n_stripes) \
            if self._native_wal else 1
        self.archive = SnapshotArchive(os.path.join(data_dir, "snapshots"))
        self.dispatcher = ApplyDispatcher(
            provider, self._payload,
            payload_window_fn=self.store.payloads_window,
            payload_runs_fn=getattr(self.store, "payload_runs", None))
        self.maintain = maintain or MaintainAgreement(cfg.n_groups)
        self.maintain.watch_ring(cfg.log_slots, cfg.max_submit)
        self.template = messages_template(cfg)
        self.acc = InboxAccumulator(cfg, self.template)
        # Set when work for the next step waits: a peer's slice merged, a
        # submission or a read queued, a drain that left slices behind.
        # A started loop sleeps on it between steps (_run); nobody else
        # reads it.
        self._wake = threading.Event()
        self.transport = transport_factory(self, self._on_slice,
                                           self._serve_snapshot)

        # Crash recovery: device state from the WAL (reference
        # RaftContext.initialize restore order, context/RaftContext.java:
        # 91-113), machines from their newest archived snapshot.
        self.state = restore_raft_state(cfg, node_id, self.store, seed=seed)
        if initial_active is not None:
            self.state = self.state.replace(
                active=jnp.asarray(initial_active, bool))
        self._recover_machines()
        self.h_active = np.asarray(self.state.active).copy()

        # Group lifecycle changes (open/close), applied at the next tick on
        # the tick thread (reference ContextManager create/exit/destroy,
        # context/ContextManager.java:112-167).
        self._lifecycle_lock = threading.Lock()
        self._lifecycle: List[Tuple[int, bool, bool]] = []  # (group, active, purge)
        # Lane incarnations this node has activated: when the admin layer
        # re-allocates a lane to a NEW group (gen bump) and this node missed
        # the destroy (meta-snapshot catch-up), the gen mismatch forces a
        # purge before activation.
        self._lane_gens_path = os.path.join(data_dir, "lane_gens.json")
        self._lane_gens: Dict[str, int] = {}
        if os.path.exists(self._lane_gens_path):
            try:
                with open(self._lane_gens_path) as f:
                    self._lane_gens = json.load(f)
            except (OSError, ValueError):
                self._lane_gens = {}

        # Host mirrors of per-group device lanes (refreshed each tick).
        G = cfg.n_groups
        self.h_role = np.zeros(G, np.int32)
        self.h_leader = np.full(G, NIL, np.int32)
        self.h_term = np.asarray(self.state.term).copy()
        self.h_commit = np.asarray(self.state.commit).copy()
        self.h_base = np.asarray(self.state.log.base).copy()
        # Floors already pushed to the WAL (mirror, avoids per-group floor
        # queries every tick).
        self._wal_floor = self.h_base.astype(np.int64).copy()
        # Durable-state mirrors for change detection: _persist visits only
        # groups whose (term, ballot) or durable tail actually moved, so
        # the steady-state staging cost is O(groups-with-writes), not O(G)
        # (VERDICT r3 #2 — the per-dirty-group Python loops were the
        # durable tier's scaling wall).  After restore the device log tail
        # IS the durable tail, and stable sentinels of -2 force the first
        # write per lane.
        self._stable_term_m = np.full(G, -2, np.int64)
        self._stable_voted_m = np.full(G, -2, np.int64)
        self._durable_tail_m = np.asarray(self.state.log.last) \
            .astype(np.int64).copy()
        # Readiness gate (reference Leader.isReady, Leader.java:52-64): a
        # fresh leader reports not-ready until a majority of peers reply.
        self.h_ready = np.zeros(G, bool)
        # Hibernation (cfg.hibernate; core/step.py "hibernation"): the
        # mirror of StepInfo.asleep, the open lanes asleep (kept running
        # while rows come down), and the lanes the host's peer-lost signal
        # wakes at the next step (HostInbox.wake).
        self.h_asleep = np.zeros(G, bool)
        self._asleep_n = 0
        self._wake_ids = np.zeros(0, np.int64)
        # The node-level beat.  A node whose every lane sleeps sends no
        # lane traffic, so a dead node would be silent the way a healthy
        # one is.  With hibernation on, a peer that got no frame of ours
        # between two timer steps gets an empty one (_node_beat), every
        # frame a peer's node sends bumps the transport's ``heard`` count
        # for it, and a peer whose count has stood for election_ticks
        # timer steps in a row is taken as lost: every asleep lane that
        # follows it is woken (as rows) and starts a whole new election
        # timeout.  Counts of our own timer steps, no clock: a cold group
        # whose leader's node dies has a new leader within election_ticks
        # + 2 x election_ticks periods of the death, or on the first
        # request to any of its members.  What TiKV gets from its store
        # heartbeat through PD.
        self._beat_frame = frame(BEAT, pack_beat(node_id))
        self._beat_sent: set = set()
        self._heard_n: Dict[int, int] = {}
        self._silent: Dict[int, int] = {}
        # Unready episodes: the timer tick at which a lane this node led
        # READY stopped being ready (0: no episode open), and how many led
        # lanes the last step found unready.  _fetch touches the array
        # only in a step that has such a lane or follows one
        # (_track_unready); the refusal gate reads it.
        self._unready_since = np.zeros(G, np.int32)
        self._unready_n = 0
        # NotReady refusals by the gate: bumped on the caller's thread
        # (plain adds: a count for an operator, not an account), folded
        # into the registry by the tick thread, its one writer (tick()).
        self._not_ready_refusals = 0
        self._not_ready_folded = 0

        # Client submissions: group -> FIFO of _SubBatch arenas, bounded
        # (reference EventLoop queue capacity + busy threshold,
        # support/EventLoop.java:16-17, 136-138).  _queued_n mirrors each
        # queue's ENTRY count so the per-tick submit_n inbox lane is one
        # numpy minimum over all groups instead of a dict walk.
        self._submit_lock = threading.Lock()
        self._submissions: Dict[int, deque] = {}
        self._queued_n = np.zeros(G, np.int32)
        self._queued_total = 0
        self.group_queue_cap = group_queue_cap
        self.total_queue_cap = total_queue_cap
        self.busy_threshold = busy_threshold   # free slots -> BusyLoopError
        # Admission control (runtime/admission.py): CoDel-style queue-
        # delay policy over the offer queues.  The hard caps above are
        # correctness backstops; the controller sheds BEFORE they fill,
        # keeping admitted-request latency bounded under open-loop
        # overload.  RAFT_ADMISSION=0 disables (admit() then always
        # passes and only the caps remain).
        self.admission = admission_from_env(seed=seed ^ node_id)
        self._tick_started: Optional[float] = None  # previous tick's start
        self._adm_delay: Optional[float] = None  # this tick's sojourn sample
        self._adm_fold = [0, 0, 0, 0]  # counters folded into metrics
        # Cross-group transaction plane (runtime/txn.py): the driver
        # gate client threads check before txn_begin (txn-level shed),
        # and the deadline-expiry recovery sweep the tick loop drives
        # for groups this node leads.
        self.txn = txn_plane_from_env()

        # Linearizable read plane (ReadIndex + lease, core/step.py phase
        # 8b): the host-side FIFO mirror of the device's rq_* lanes.  A
        # batch is WAITING until the group's offer slot frees; then every
        # waiting batch of the group becomes one offer (_ReadOffer),
        # OFFERED for exactly the ticks its HostInbox.read_n is up,
        # PENDING once the device stamps it (StepInfo.read_acc/
        # read_index), RELEASED once the quorum barrier confirms
        # (read_rel, FIFO order), and served —
        # the machine queried at ``applied >= read_index`` — on the tick
        # thread.  Reads never enter the log, so EVERY read failure is a
        # marked retry-safe refusal (api/anomaly.py as_refusal).
        self._read_lock = threading.Lock()
        self._reads_waiting: Dict[int, deque] = {}
        self._reads_offered: Dict[int, _ReadOffer] = {}
        self._reads_pending: Dict[int, deque] = {}   # (read_index, offer)
        self._reads_released: Dict[int, deque] = {}  # (read_index, offer)
        self._read_queued_n = np.zeros(G, np.int32)
        # Columnar serve gate: per group, the smallest read_index any
        # released batch still waits on (int64 sentinel = no batch).
        # _serve_reads visits nonzero(applied >= _rel_min) instead of
        # walking every group with a released deque each tick.
        self._rel_min = np.full(G, np.iinfo(np.int64).max, np.int64)
        # Wall-clock pause detection feeding HostInbox.read_veto: a tick
        # gap longer than read_fresh_ticks intervals means stored lease
        # evidence (and anything queued in the inbox across the pause) is
        # stale — the host analog of the device fault model's
        # stall-loses-inbound rule.  Armed only when the tick loop runs on
        # a real cadence (start(); manual tick() drivers have no
        # wall-clock meaning).
        self._tick_interval: Optional[float] = None
        self._last_tick_wall: Optional[float] = None
        self._read_veto_hold = 0   # ticks of veto left after a pause
        # A carried lease (cfg.lease_carry_ticks) is as long as
        # cfg.lease_ticks of this node's clock: the wall-clock starts of
        # that many timer steps, the newest last (_hold_read_veto).
        self._tick_walls: deque = deque(maxlen=cfg.lease_ticks)

        # Membership plane (§6): pending change/transfer requests, offered
        # to the device every tick until accepted or failed (the device
        # refuses silently while another change is in flight; acceptance
        # latches into the log).  Mirrors of the device's active config
        # feed membership() and the request-settled checks.
        self._member_lock = threading.Lock()
        # g -> [target_voters, target_learners, Future, accepted: bool]
        self._conf_pending: Dict[int, list] = {}
        # g -> [target_peer, Future, fired: bool]
        self._xfer_pending: Dict[int, list] = {}
        self.h_conf_word = np.asarray(self.state.conf_word).copy()
        self.h_conf_idx = np.asarray(self.state.conf_idx).copy()
        self.h_conf_pending = np.asarray(
            self.h_conf_idx > np.asarray(self.state.commit)).copy()
        # Snapshot-install config round trip: the offer's config word,
        # pended at request time, fed back as HostInbox.snap_conf on
        # completion (g -> (offered_idx, word)).
        self._snap_conf: Dict[int, Tuple[int, int]] = {}

        # Snapshot downloads: a BOUNDED global worker pool fetches bytes to
        # temp files (reference: ONE dedicated snapshot NIO thread,
        # transport/NettyCluster.java:42-43 — thread-per-lagging-group
        # would spawn thousands under 100k-group catch-up, BASELINE config
        # 5); every store/dispatcher/archive mutation happens on the tick
        # thread (single-writer discipline — the analog of the reference's
        # per-group event-loop rule, context/member/RaftMember.java:31-35).
        self._snap_lock = threading.Lock()
        self._snap_cv = threading.Condition(self._snap_lock)
        self._snap_fetched: List[Tuple[int, int, int, str]] = []
        self._snap_inflight: set = set()
        self._snap_serial = 0       # downloads started (names their files)
        # Queue entries carry the lane's fetch epoch: a purge bumps it, so
        # a stale queued fetch can never run against a recreated lane even
        # if the lane has re-entered _snap_inflight by the time a worker
        # pops it (single-flight per group is epoch+membership together).
        # A deque: mass catch-up (100k lagging groups, BASELINE config 5)
        # enqueues that many entries, and a list.pop(0) drain would be
        # O(n^2) under the lock the tick thread shares.
        self._snap_queue: "deque[Tuple[int, int, int, int, int]]" = deque()
        self._snap_epoch: Dict[int, int] = {}
        self._snap_threads: List[threading.Thread] = []
        self.snap_fetch_workers = 4

        # Compaction grants computed at the end of tick t, applied in t+1.
        self._compact_grant = np.zeros(G, np.int64)

        # WAL GC cadence/thresholds (VERDICT r1 #5: milestones advance the
        # logical floor, but disk is only reclaimed by the checkpoint
        # rewrite — trigger it when the dead fraction justifies the cost).
        # The rewrite runs three-phase so the tick thread never stalls on
        # it: begin (seal+rotate) and finish (swap+repoint) are bounded;
        # the live-set rewrite happens on _gc_thread (VERDICT r2 #6).
        self.wal_gc_check_ticks = 128
        self.wal_gc_ratio = 4.0
        self.wal_gc_min_bytes = 8 << 20
        # Hard bound on checkpoint work per tick: whatever the policy says
        # is due, at most this many machines checkpoint in one tick (the
        # rest stay due and drain over the following ticks) — maintenance
        # must never own the tick latency (reference: checkpoints run on a
        # bounded 5-thread pool off the loop, RaftRoutine.java:46-49).
        # Scaled with the group count: compaction can only advance past a
        # snapshot, so sustained acceptance per group is bounded by
        # cap * (log_slots - slack) / n_groups entries per tick — a FIXED
        # cap silently throttled the whole durable tier to ~0.65
        # entries/tick/group at 100k groups (the r4 "falling with scale"
        # curve).  The clamp keeps per-tick checkpoint work bounded
        # (~100-150us each) so maintenance still cannot own tick latency.
        self.max_checkpoints_per_tick = min(1536, max(256,
                                                      cfg.n_groups // 32))
        self._ckpt_cursor = 0   # round-robin position for the cap above
        # Off-thread checkpoint saves: the tick thread serializes the
        # machine (single-writer rule — applies mutate it) and enqueues the
        # archive copy/rotate to a small worker pool; completions are
        # harvested next maintain pass, and only THEN does the milestone
        # feed the compaction policy (a grant must never outrun its saved
        # snapshot).  The queue is bounded: when full, remaining due groups
        # simply stay due — backpressure, not loss.  _ckpt_inflight keeps
        # at most ONE save in flight per group, so same-group archive
        # ordering needs no worker sharding.
        self._ckpt_cv = threading.Condition()
        self._ckpt_queue: "deque[Tuple[int, str, int, int]]" = deque()
        self._ckpt_done: List[Tuple[int, int, bool]] = []
        self._ckpt_inflight: set = set()
        self._ckpt_threads: List[threading.Thread] = []
        self.ckpt_workers = 2
        self.ckpt_queue_cap = 4 * self.max_checkpoints_per_tick
        # _gc_phase handoff protocol: the tick thread writes 0->1 (start),
        # the worker writes 1->2 or 1->-1 (done/failed), the tick thread
        # consumes 2/-1 back to 0.  Exactly one side may write in each
        # phase, and the value is a single int — atomic under CPython's
        # GIL.  A free-threaded runtime would need a threading.Event here.
        self._gc_phase = 0       # 0 idle / 1 rewriting / 2 finish / -1 abort
        self._gc_thread: Optional[threading.Thread] = None

        # Two counts.  ``ticks``: every step, whoever started it: the
        # (node, tick) identifier on spans and stamps.  ``timer_ticks``:
        # the steps that advanced the engine's clock (HostInbox.clock 1),
        # one per period under a loop, every step for a caller that drives
        # tick() itself: what every host-side cadence and hold counts in
        # (a read veto, checkpoint and compaction intervals, evacuation
        # cool-downs, heat decay), since those mean time.
        self.ticks = 0
        self.timer_ticks = 0
        # Counter/gauge/histogram registry (SURVEY §5: the build must add
        # commits/sec, election counts, per-step latency histograms).
        self.metrics = Metrics()
        # Membership counters render at 0 on /metrics from boot
        # (tests/test_metrics_prom.py asserts the exposition carries them).
        for _c in ("membership_changes_entered",
                   "membership_changes_committed",
                   "membership_changes_aborted",
                   "leadership_transfers_attempted",
                   "leadership_transfers_succeeded",
                   "leadership_transfers_aborted",
                   "timeout_now_sent"):
            self.metrics[_c] += 0
        # Storage-fault plane (see _storage_fault): failure-response
        # policy state + its counters, rendered at 0 from boot.
        #   - fsync failure     -> fail-stop stripe quarantine (never
        #     retry fsync on the failed fd — fsyncgate), lanes go silent;
        #   - ENOSPC            -> admission backpressure, barrier retried
        #     (engines kept their staged buffers);
        #   - slow fsync        -> gray-failure watchdog gauge;
        #   - conf-flush error  -> transient, retried next barrier.
        self._poisoned_stripes: set = set()
        self._healthy_groups: Optional[np.ndarray] = None  # None = all
        # Device-feed clamp: the per-group tail actually CONFIRMED by a
        # barrier.  None = every staged record is synced and the staged
        # mirror (_durable_tail_m) is the truth (the zero-copy fast
        # path); materialized only while a barrier failure leaves staged-
        # but-unsynced records, so the scan can never self-ack them.
        self._acked_tail: Optional[np.ndarray] = None
        self._sync_pending = False     # kept buffers / dirty conf to flush
        self._io_backpressure = False  # ENOSPC: refuse new submissions
        self._io_slow = False
        self._slow_io_s = float(os.environ.get("RAFT_SLOW_IO_S", "0.5"))
        # Background snapshot scrubber (archive.scrub): a budgeted pass
        # every interval, a few groups per pass, round-robin cursor.
        self.scrub_interval_ticks = int(
            os.environ.get("RAFT_SCRUB_TICKS", "512"))
        self.scrub_groups_per_pass = 4
        self._scrub_cursor = 0
        for _c in ("fsync_failures", "enospc_backpressure",
                   "storage_transient_errors", "slow_io_ticks",
                   "ckpt_failures", "scrub_ok", "scrub_corrupt",
                   "reconnects_total", "connects_refused_total"):
            self.metrics[_c] += 0
        # Network-nemesis counters (transport/faults.py): rendered at 0
        # so a clean cluster exposes the whole injection family and a
        # chaos run's effects are visible on the ordinary /metrics page.
        from ..transport.faults import COUNTERS as _FAULT_COUNTERS
        for _c in _FAULT_COUNTERS:
            self.metrics[_c] += 0
        self.metrics.gauge("stripes_poisoned", 0)
        self.metrics.gauge("io_backpressure", 0)
        self.metrics.gauge("io_slow", 0)
        # Admission-control plane: counters render at 0 from boot; the
        # level gauge tracks the controller's shed probability.
        for _c in ("admission_admitted", "admission_shed",
                   "admission_shed_tenant", "admission_expired"):
            self.metrics[_c] += 0
        self.metrics.gauge("admission_level", 0.0)
        self.metrics.gauge("admission_shedding", 0)
        # Txn-plane counters rendered from boot (same contract as the
        # admission counters: a scraper sees the series at 0, not a gap).
        for _name in ("txn_committed", "txn_aborted", "txn_refused",
                      "txn_unknown", "txn_resolved_commit",
                      "txn_resolved_abort", "txn_resolve_retry"):
            self.metrics[_name] += 0
        self.metrics.gauge("txn_inflight", 0.0)
        # The transport reports its own health (reconnects_total) into
        # the node registry; set before start() spawns sender threads.
        self.transport.metrics = self.metrics
        # Per-entry commit-path latency plane (utils/latency.py): a
        # seeded deterministic sampler stamps span records through
        # submitted -> offered -> staged -> fsynced -> sent -> committed
        # -> applied -> acked (served for reads).  RAFT_LAT_SAMPLE=0
        # disables it entirely — the node holds None and every hot-path
        # hook is one is-None check.
        if latency_slo_s is None:
            latency_slo_s = float(
                os.environ.get("RAFT_SLO_MS", "500")) / 1e3
        self._lat = tracer_from_env(seed=seed, slo_s=latency_slo_s)
        # Spans offered to the device THIS tick, awaiting the tick's
        # staged/fsynced/sent stamps (tick/host-phase thread only).
        self._lat_tick: list = []
        # Last native/Python WAL-engine stats snapshot (cumulative
        # counters — _fold_wal_stats folds deltas into the registry).
        self._wal_stat_last: Optional[dict] = None
        self.metrics.gauge(
            "lat_sample_rate", self._lat.rate if self._lat else 0)
        # Per-group heat accounting (cfg.heat): the fetched device heat
        # lanes drain into a decaying host registry each tick — top-K hot
        # groups, idleness ages, and the active-set gauge (the proof
        # metric for the sparse-tick work, ROADMAP item 2).  None when
        # the config carries no heat lanes.
        self.heat = heat_registry_from_env(G) if cfg.heat else None
        if self.heat is not None:
            for _c in ("heat_appended", "heat_sent", "heat_commits",
                       "heat_reads"):
                self.metrics[_c] += 0
            self.metrics.gauge("heat_active_set", 0)
            self.metrics.gauge("heat_half_life_ticks", self.heat.half_life)
        # Cross-node hop tracing (utils/latency.py HopTracer): decomposes
        # a sampled span's send_commit into per-peer wire/fsync/quorum
        # segments via a HOPS sideband on the AE traffic.  Enabled by
        # default whenever the transport exists — a node must echo hop
        # contexts for its LEADERS' samples even if its own sampling is
        # off — and disabled with RAFT_HOP_TRACE=0.
        self._hops = hops_from_env(node_id, cfg.n_peers)
        if self._hops is not None:
            for _c in ("hop_tracked", "hop_requests_sent", "hop_echoes",
                       "hop_finalized", "hop_dropped_unknown",
                       "hop_expired", "hop_foreign_seen",
                       "hop_foreign_expired"):
                self.metrics[_c] += 0
            self.transport.on_hops = self._on_hops
        # Gray-failure self-healing plane (utils/health.py): decayed
        # per-peer + self scorecards fed each tick from the hop
        # histograms, the storage-fault plane, the transport and the
        # admission controller; the CheckQuorum contact lanes feed
        # last-contact at an admin cadence.  A self-degraded node
        # EVACUATES leadership (rate-limited, never to a degraded
        # peer) instead of waiting for the device-side 6c step-down.
        # RAFT_HEALTH=0 disables the whole plane.
        self.health = health_from_env(cfg.n_peers, node_id)
        for _c in ("checkquorum_stepdowns", "leader_evacuations",
                   "lease_vetoes"):
            self.metrics[_c] += 0
        if self.health is not None:
            self.metrics.gauge("health_self_score", 0.0)
            self.metrics.gauge("health_self_degraded", 0)
            self.metrics.gauge("health_degraded_peers", 0)
        # Groups this node evacuated: group -> (target, expiry tick).
        # Read by _refusal to return the typed LeadershipEvacuated
        # refusal (api/anomaly.py) while the fleet re-points.
        self._evacuated: Dict[int, Tuple[int, int]] = {}
        self._evac_cooldown = int(os.environ.get(
            "RAFT_EVAC_COOLDOWN_TICKS", str(8 * cfg.election_ticks)))
        self._evac_groups_per_round = int(
            os.environ.get("RAFT_EVAC_GROUPS", "8"))
        self._evac_next_ok = 0
        # Flight-recorder drain (cfg.trace_depth > 0): per-group decoded
        # timelines + labeled metrics (elections by cause, leader churn)
        # harvested from the device event rings each tick.  Inert when
        # tracing is off.  Served over HTTP by start_observability().
        self.tracelog = TraceLog(cfg)
        self._obsrv = None
        # The phase the tick thread is in (utils/profiling.py): a
        # tick_stage_<name>_s sample per phase per tick, and a raft.<name>
        # span in whatever jax.profiler session is running.
        self._stages = StageSpans(self.metrics, node_id)
        self._tick_due: Optional[float] = None   # _run: next start due
        self._tick_stagger = False               # start(): share a host
        # Seconds per whole step of the loop, the last eight: one slow
        # step is forgotten after as many.  arrival_step_at() weighs their
        # median against the time left until _tick_due.
        self._step_costs: deque = deque(maxlen=8)
        # Seconds the tick() call under way spent in the WAL's fsyncs,
        # as _host_phase adds them up: with the stages scan_device and
        # scan_fetch what its thread spent blocked on another agent, which
        # _run takes off the gap behind the step (arrival_step_at()).
        self._fsync_waited = 0.0
        # _await_step to the step it lets start: (held, gap, waited), for
        # the step's raft.dispatch_intake span and the two metrics; None
        # under a caller that steps the node itself.
        self._gap_given: Optional[Tuple[bool, float, float]] = None
        # Heartbeat rounds in flight, oldest first: [the engine's clock of
        # the timer's step that sent the period's heartbeats, that step's
        # start, the peers whose acknowledgement of it is still out].
        self._hb_rounds: deque = deque(maxlen=HB_ROUNDS_OPEN)
        self._hb_closed: Optional[float] = None   # start of a round this
        #                           step's drain closed (tick() ends it)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # The [G] side of a shape that takes the column step (core/step.py
        # column_layouts; all of it unused on any other): what the device
        # keeps from step to step (RowCarry), the host's copy of the
        # durable_tail plane the device holds (None: not told yet), the
        # device's resident zero HostInbox planes by layout, the
        # (submit_n, read_n) planes of the steps in flight, the node's
        # persistent stacked Readback planes (words, flags) and the tree
        # of views into them that the mirrors and ctx.info are once rows
        # came down, the last
        # Readback that came down whole while they are stale, whether the
        # next fetch must take the results whole (the first step, a step
        # after a purge), and the running counts behind the gauges of
        # _fetch (None: recount).
        self._carry = None
        self._dur_sent: Optional[np.ndarray] = None
        self._resident: Dict[object, tuple] = {}
        self._up_planes: List[Tuple[np.ndarray, np.ndarray]] = []
        self._back_planes: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self._back_tree = None
        self._whole_back = None
        self._rows_whole_out = True
        self._lane_counts: Optional[List[int]] = None
        # Open lanes this node leads (raft.maintain's ``led``), counted by
        # every fetch beside _lane_counts.
        self._led_open = 0
        # What the host phase holds between steps so that a step whose
        # Readback came down as rows can work from those rows alone
        # (_host_lanes has the rule; a whole step of such a node rebuilds
        # them, no other node keeps them): whether they are good, the sum
        # of the commit plane, the lanes whose ring stands under pressure
        # ([G] bool and their count), and each open lane's ring fill (-1:
        # closed) with the lanes counted per fill (_ring_state).  The
        # apply backlog is the dispatcher's; released reads wait in
        # _reads_released.
        self._host_sets_ok = False
        self._commit_sum = 0
        self._pressed_m: Optional[np.ndarray] = None
        self._pressed_n = 0
        self._fill_m: Optional[np.ndarray] = None
        self._fill_hist: Optional[np.ndarray] = None
        # Lanes the open host stage's selection passes have run over
        # (_where and the sums beside it), folded into the stage's span
        # and the counter host_lanes_scanned by _note_scanned.
        self._scanned = 0
        # Per-peer outbox sections a tick's host phase packed, flushed as
        # ONE frame per peer (_flush_sends) — the accumulator drains one
        # slice per source per tick, so two frames would back up.  Tick
        # thread only.
        self._held_sections: Dict[int, List[bytes]] = {}
        self.metrics.gauge("wal_shards",
                           getattr(getattr(self.store, "wal", None),
                                   "n_shards", 1))
        self.metrics.gauge("host_workers", self.host_workers)
        self.metrics.gauge("native_host", int(self._native_wal))
        # Ticks started.
        self.metrics["ticks"] += 0
        # Those of them a loop started for arriving work, between two
        # timer ticks (HostInbox.clock 0).
        self.metrics["ticks_on_arrival"] += 0
        # Host-to-device and device-to-host transfers made by the ticks:
        # the packed buffers of _dispatch and _fetch, one word buffer each
        # way unless the planes take more than a few MB (core/packing.py
        # CHUNK_BYTES).
        self.metrics["h2d_transfers"] += 0
        self.metrics["d2h_transfers"] += 0
        # Steps whose messages crossed as columns, and steps of a shape
        # that takes columns whose messages did not fit them, each way
        # (core/step.py node_step_columns).
        for name in ("steps_columns_in", "steps_columns_out",
                     "column_overflows_in", "column_overflows_out",
                     "steps_rows_in", "steps_rows_out",
                     "row_overflows_in", "row_overflows_out"):
            self.metrics[name] += 0
        self.metrics["hb_rounds_closed"] += 0
        # Lanes the host phase's selection passes ran over (``scanned`` on
        # raft.wal / apply / reads / maintain): n_groups a pass on a step
        # that looks at every lane, the rows that moved on one that came
        # down as rows.
        self.metrics["host_lanes_scanned"] += 0
        # Read plane: offers the device stamped (one ReadIndex barrier
        # each) and the queries that rode a barrier another call opened.
        self.metrics["read_barriers"] += 0
        self.metrics["reads_coalesced"] += 0
        # Barriers released in the step that stamped them by evidence of
        # an earlier tick (never more than read_lease_hits), and barrier
        # heartbeats that a batch left pending asked for.
        self.metrics["read_lease_carried"] += 0
        self.metrics["read_kicks"] += 0
        # Batches that a LATER step's acknowledgements released (a
        # ReadIndex round, not the lease in the stamping step), and
        # batches stamped in a step that work started between two timer
        # ticks.
        self.metrics["read_rounds"] += 0
        self.metrics["read_stamps_on_arrival"] += 0
        # Hibernation: lanes that fell asleep and woke, the wakes by what
        # caused them (a request of this host's, a message, the peer-lost
        # signal), and the empty frames of the node-level beat.
        for name in ("lane_sleeps", "lane_wakes", "wake_request",
                     "wake_message", "wake_peer_lost", "node_beats_sent"):
            self.metrics[name] += 0
        self.metrics.gauge("lanes_asleep", 0)
        # Maintenance a full log ring asked for ahead of the cadence
        # (snapshot/policy.py): checkpoints serialized, grants issued.
        self.metrics["ckpt_by_pressure"] += 0
        self.metrics["compactions_by_pressure"] += 0

    # ------------------------------------------------------------------ API

    def start(self, tick_interval: float = 0.02,
              stagger: bool = False) -> None:
        """Run the tick loop in a background thread (the node's
        'event loop'; interval plays the reference's tick,
        support/RaftConfig.java:171-185).  ``stagger``
        (``RaftConfig.tick_stagger``): this node shares its host with the
        cluster's other nodes, and ticks on its own share of a grid
        (``_next_start``)."""
        self._tick_interval = tick_interval
        self._tick_stagger = stagger
        self.transport.start()
        self._thread = threading.Thread(
            target=self._run, args=(tick_interval,),
            name=f"raft-node-{self.node_id}", daemon=True)
        self._thread.start()

    def start_observability(self, host: str = "127.0.0.1",
                            port: int = 0):
        """Attach and start the HTTP observability plane (/metrics,
        /healthz, /timeline — runtime/obsrv.py).  Returns the server;
        read ``.port`` for the bound port.  Closed with the node."""
        from .obsrv import ObservabilityServer

        if self._obsrv is None:
            self._obsrv = ObservabilityServer(self, host, port).start()
        return self._obsrv

    def latency_snapshot(self) -> dict:
        """The /latency document (runtime/obsrv.py): sampler state, SLO
        burn, per-phase and end-to-end percentiles, recent sampled spans
        — plus the WAL engines' per-stripe stage/fsync/pack counters.
        Snapshot reads only; safe off the tick thread (same contract as
        /metrics)."""
        tr = self._lat
        doc = {"enabled": tr is not None}
        if tr is not None:
            doc.update(tr.snapshot(self.metrics))
        wal = getattr(self.store, "wal", None)
        per = getattr(wal, "stats_per_stripe", None)
        if per is not None:
            doc["wal_stripes"] = [
                dict(s, stripe=i) for i, s in enumerate(per())]
        doc["txn_plane"] = self.txn.snapshot()
        if self._hops is not None:
            # Hop-phase decomposition of send_commit (the fleet
            # attribution plane) rides the latency document too, so one
            # scrape answers both "when" and "where".
            doc["hops"] = self._hops.snapshot(self.metrics)
        return doc

    def heatmap_snapshot(self, k: int = 16) -> dict:
        """The /heatmap document (runtime/obsrv.py): decayed top-K hot
        groups, idleness-age distribution, active-set size.  Snapshot
        reads only — safe off the tick thread (utils/heat.py)."""
        if self.heat is None:
            return {"enabled": False}
        doc = {"enabled": True}
        doc.update(self.heat.snapshot(k))
        return doc

    def hops_snapshot(self) -> dict:
        """The /hops document (runtime/obsrv.py): per-peer and aggregate
        hop-segment summaries + recent finalized decompositions."""
        if self._hops is None:
            return {"enabled": False}
        doc = {"enabled": True}
        doc.update(self._hops.snapshot(self.metrics))
        return doc

    def _on_hops(self, origin: int, direction: int, records,
                 t_recv_ns: int) -> None:
        """Transport reader-thread intake for HOPS frames (assigned as
        ``transport.on_hops``): requests park on the follower half,
        echoes on the leader half; both drain on the tick thread."""
        h = self._hops
        if h is None:
            return
        if direction == HOP_REQUEST:
            h.recv_requests(origin, records, t_recv_ns)
        else:
            h.recv_echoes(origin, records, t_recv_ns)

    def close(self) -> None:
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
        if self._lat is not None:
            # Final harvest: retired-but-unmerged spans land in the
            # histograms before the registry goes quiet (spans still in
            # flight stay un-counted — never a fabricated latency).
            self._lat.harvest(self.metrics)
        if self._hops is not None:
            # Same rule for hop contexts: fold what settled, never
            # fabricate segments for spans still in flight.
            self._hops.fold(self.metrics)
        if self._obsrv is not None:
            self._obsrv.close()
            self._obsrv = None
        self.transport.close()
        # Checkpoint workers drain their queue after _stop (no serialized
        # temp file is stranded), then exit.
        with self._ckpt_cv:
            self._ckpt_cv.notify_all()
        for t in self._ckpt_threads:
            t.join(timeout=30)
        # In-flight snapshot workers touch the store; they must finish (or
        # observe _stop) before the native WAL handle is released.
        with self._snap_cv:
            self._snap_cv.notify_all()
        for t in self._snap_threads:
            t.join(timeout=10)
        # Settle a pending three-phase GC: with the tick thread stopped,
        # ownership transfers here (still single-writer).
        if self._gc_thread is not None:
            self._gc_thread.join(timeout=300)
            if self._gc_thread.is_alive():
                # The worker still holds the native handle: releasing it
                # would be a use-after-free.  Leak the store (the WAL is
                # crash-safe; recovery re-derives everything) and bail.
                log.error("node %d: WAL GC worker failed to stop; leaking "
                          "store handle", self.node_id)
                self.dispatcher.close()
                return
        if self._gc_phase == 2:
            try:
                if self.store.gc_finish() != 0:
                    self.store.gc_abort()
            except Exception:
                self.store.gc_abort()
        elif self._gc_phase != 0:
            self.store.gc_abort()
        self._gc_phase = 0
        self.dispatcher.close()
        self._fold_wal_stats()   # final engine-counter fold (short runs
        self.store.close()       # never reach a 32-tick maintain pass)

    def submit(self, group: int, payload: bytes,
               tenant: Optional[str] = None) -> Future:
        """Offer a command to the group's replicated log.  The returned
        future completes with the machine's apply result (reference
        RaftStub.submit -> Promise, command/RaftStub.java:65-74).

        Refusals mirror the reference's taxonomy: NotLeader (redirect hint),
        NotReady (leading but a majority of followers unhealthy —
        Leader.isReady, Leader.java:52-64 -> NotReadyException,
        RaftStub.java:84-87) and BusyLoop (bounded queues,
        support/EventLoop.java:136-138).

        Concurrency contract: ``h_role``/``h_ready``/``h_leader`` are
        device mirrors refreshed once per tick and read here WITHOUT
        synchronization (the reference instead pins the isReady check to
        the group's event loop, Leader.java:52-64).  The race is bounded
        and safe: a stale mirror can only mis-route a submission by one
        tick — a wrongly-ACCEPTED command still commits only if the device
        engine (the authority) sees this node as a ready leader when it
        drains the queue, otherwise the queue is rejected with NotLeader on
        the next tick (`_persist` rejection sweep); a wrongly-REFUSED
        command just returns a retryable error to the client."""
        from ..transport.codec import PayloadRun

        sink = BatchSubmit(1, single=True)
        fut = sink.future
        err = self._refusal(group)
        if err is not None:
            fut.set_exception(err)
            return fut
        adm = self.admission
        ra = adm.admit(1, tenant)
        if ra is not None:
            fut.set_exception(as_refusal(OverloadError(
                f"group {group}: admission shed (overload)",
                retry_after_s=ra)))
            return fut
        run = PayloadRun.single(0, payload)
        with self._submit_lock:
            if (int(self._queued_n[group]) >= self.group_queue_cap
                    or self._queued_total
                    >= self.total_queue_cap - self.busy_threshold):
                fut.set_exception(as_refusal(BusyLoopError(
                    f"group {group}: submission queue full",
                    retry_after_s=adm.busy_retry_after())))
                return fut
            q = self._submissions.setdefault(group, deque())
            b = _SubBatch(run, sink)
            # LIFO under overload (deadline-aware: the freshest request is
            # the likeliest to still be inside its deadline).  Never ahead
            # of a partially-consumed head — its remaining entries keep
            # their place, all other cross-batch order is free (promise
            # ranges are registered per pop span, not by queue position).
            if adm.lifo_now() and q and q[0].taken == 0:
                q.appendleft(b)
            else:
                q.append(b)
            self._queued_n[group] += 1
            self._queued_total += 1
            tr = self._lat
            if tr is not None:
                seq = tr.next_seq_w(1)
                if tr.sampled(seq):
                    sink.span = tr.make_span(seq, "w", 0)
        self._wake.set()
        return fut

    def submit_batch(self, group: int, payloads,
                     tenant: Optional[str] = None) -> Future:
        """Offer many commands with ONE future resolving to the list of
        apply results (in order).  Same refusal taxonomy as :meth:`submit`,
        reported on the single future; one queue-capacity check and one
        lock acquisition cover the whole batch.  If any command in the
        batch fails (NotLeader on step-down, ObsoleteContext, snapshot
        jump), the future raises :class:`BatchAbortedError`, whose
        ``completed``/``results`` report exactly which prefix already
        committed and applied — do NOT blindly resubmit the whole batch
        (see the error's docstring for the client contract)."""
        from ..transport.codec import PayloadRun

        batch = BatchSubmit(len(payloads))
        fut = batch.future
        err = self._refusal(group)
        if err is not None:
            fut.set_exception(err)
            return fut
        if not payloads:
            fut.set_result([])
            return fut
        adm = self.admission
        ra = adm.admit(len(payloads), tenant)
        if ra is not None:
            fut.set_exception(as_refusal(OverloadError(
                f"group {group}: admission shed (overload)",
                retry_after_s=ra)))
            return fut
        run = PayloadRun.from_payloads(0, payloads)
        with self._submit_lock:
            n = len(payloads)
            if (int(self._queued_n[group]) + n > self.group_queue_cap
                    or self._queued_total + n
                    > self.total_queue_cap - self.busy_threshold):
                fut.set_exception(as_refusal(BusyLoopError(
                    f"group {group}: submission queue full",
                    retry_after_s=adm.busy_retry_after())))
                return fut
            q = self._submissions.setdefault(group, deque())
            b = _SubBatch(run, batch)
            if adm.lifo_now() and q and q[0].taken == 0:  # see submit()
                q.appendleft(b)
            else:
                q.append(b)
            self._queued_n[group] += n
            self._queued_total += n
            tr = self._lat
            if tr is not None:
                seq0 = tr.next_seq_w(n)
                k = tr.first_in(seq0, n)
                if k >= 0:
                    batch.span = tr.make_span(seq0 + k, "w", k)
        self._wake.set()
        return fut

    def submit_batch_many(self, groups, payloads) -> List[BatchSubmit]:
        """Offer the SAME batch of commands to many groups at once (the
        vectorized client entry — one arena build and one lock acquisition
        for the whole fan-out; each group still gets its own BatchSubmit
        with the full refusal taxonomy).  Returns the per-group handles;
        read ``handle.future`` to await a group's results — the Future
        (and its Condition) is allocated lazily on first access, so a
        fire-and-forget driver feeding 100k groups per round never pays
        for 100k Futures.  Refusals are recorded on the handle the same
        lazy way (``handle.future`` raises them on ``result()``)."""
        from ..transport.codec import PayloadRun

        sinks: List[BatchSubmit] = []
        n = len(payloads)
        if n == 0:
            for _ in groups:
                sinks.append(BatchSubmit(0, eager=False))
            return sinks
        run = PayloadRun.from_payloads(0, payloads)
        # Refusal prechecks read the tick-refreshed mirrors (same bounded
        # one-tick race as submit/_refusal — see submit's docstring).
        role, ready, active = self.h_role, self.h_ready, self.h_active
        leader, qn = self.h_leader, self._queued_n
        hg, bp = self._healthy_groups, self._io_backpressure
        cap = self.group_queue_cap - n
        tr = self._lat
        adm = self.admission
        with self._submit_lock:
            headroom = (self.total_queue_cap - self.busy_threshold
                        - self._queued_total)
            for g in groups:
                g = int(g)
                sink = BatchSubmit(n, eager=False)
                sinks.append(sink)
                if hg is not None and not hg[g]:
                    sink._refuse(as_refusal(UnavailableError(
                        f"group {g}: WAL stripe quarantined after a "
                        f"durability failure")))
                    continue
                if bp:
                    sink._refuse(as_refusal(BusyLoopError(
                        f"group {g}: storage backpressure (WAL out of "
                        f"disk space)", retry_after_s=1.0)))
                    continue
                if not active[g]:
                    sink._refuse(as_refusal(
                        ObsoleteContextError(f"group {g} closed")))
                    continue
                if role[g] != LEADER:
                    hint = int(leader[g])
                    sink._refuse(as_refusal(NotLeaderError(
                        g, None if hint == NIL else hint)))
                    continue
                if not ready[g]:
                    sink._refuse(self._not_ready(g))
                    continue
                ra = adm.admit(n)
                if ra is not None:
                    sink._refuse(as_refusal(OverloadError(
                        f"group {g}: admission shed (overload)",
                        retry_after_s=ra)))
                    continue
                if qn[g] > cap or headroom < n:
                    sink._refuse(as_refusal(BusyLoopError(
                        f"group {g}: submission queue full",
                        retry_after_s=adm.busy_retry_after())))
                    continue
                self._submissions.setdefault(g, deque()).append(
                    _SubBatch(run, sink))
                qn[g] += n
                self._queued_total += n
                headroom -= n
                if tr is not None:
                    # Seqs are allocated per ACCEPTED group only (the
                    # sampled set is deterministic over accepted
                    # submissions); first_in is O(1), so the 100k-group
                    # fan-out never loops to decide.
                    seq0 = tr.next_seq_w(n)
                    k = tr.first_in(seq0, n)
                    if k >= 0:
                        sink.span = tr.make_span(seq0 + k, "w", k)
        self._wake.set()
        return sinks

    def read(self, group: int, payload: bytes,
             tenant: Optional[str] = None) -> Future:
        """Linearizable read: resolve with the machine's ``read(payload)``
        result (or, for machines without the read SPI, the quorum-confirmed
        ReadIndex itself) WITHOUT appending to the log.

        Protocol (ReadIndex, Raft dissertation §6.4, vectorized in
        core/step.py phase 8b): the device stamps the batch with the
        leader's commit index, confirms leadership via a majority of
        same-term heartbeat acks (receipt-anchored when cfg.read_lease —
        often zero extra round trips — else echo-anchored, one round
        trip), and the host serves it once the apply frontier covers the
        stamp.  Every failure of a read future is a MARKED refusal
        (api/anomaly.py): a read never enters any log, so retrying it
        elsewhere is always safe — unlike submit's accept-abort ambiguity.
        """
        return self.read_batch(group, [payload], _single=True,
                               tenant=tenant)

    def read_batch(self, group: int, payloads,
                   _single: bool = False,
                   tenant: Optional[str] = None) -> Future:
        """Offer many linearizable queries as ONE read batch with one
        future resolving to the list of results in order.  The whole batch
        shares one ReadIndex barrier — and shares it with every other
        batch of the group that waits when the group's offer slot is won
        (``_dispatch``), single ``read`` calls included: the amortization
        the read plane exists for needs no caller to batch by hand; this
        method is for the caller that wants one result list.  Same refusal
        taxonomy as :meth:`submit_batch`, but every refusal/abort is
        retry-safe (see :meth:`read`)."""
        sink = BatchSubmit(len(payloads), single=_single)
        fut = sink.future
        err = self._refusal(group)
        if err is not None:
            fut.set_exception(err)
            return fut
        if not payloads:
            fut.set_result([])
            return fut
        n = len(payloads)
        adm = self.admission
        ra = adm.admit(n, tenant)
        if ra is not None:
            fut.set_exception(as_refusal(OverloadError(
                f"group {group}: admission shed (overload)",
                retry_after_s=ra)))
            return fut
        with self._read_lock:
            if int(self._read_queued_n[group]) + n > self.group_queue_cap:
                fut.set_exception(as_refusal(BusyLoopError(
                    f"group {group}: read queue full",
                    retry_after_s=adm.busy_retry_after())))
                return fut
            self._reads_waiting.setdefault(group, deque()).append(
                _ReadBatch(list(payloads), sink, time.monotonic()))
            self._read_queued_n[group] += n
            tr = self._lat
            if tr is not None:
                seq0 = tr.next_seq_r(n)
                k = tr.first_in(seq0, n)
                if k >= 0:
                    sp = tr.make_span(seq0 + k, "r", k)
                    if sp is not None:
                        sp.group = group
                    sink.span = sp
        self._wake.set()
        return fut

    def _refusal(self, group: int) -> Optional[Exception]:
        """The submission refusal taxonomy, shared by submit/submit_batch
        (reference: RaftStub.process checks, command/RaftStub.java:79-91).
        All are marked pre-log refusals: nothing was enqueued, so a retry
        elsewhere can never double-apply (api/anomaly.py as_refusal)."""
        if self._healthy_groups is not None \
                and not self._healthy_groups[group]:
            # Typed fast-fail (UnavailableError subclasses
            # StorageFaultError): the lane is fail-stop silent, so the
            # client should route around this node NOW instead of riding
            # a future to its timeout.
            return as_refusal(UnavailableError(
                f"group {group}: WAL stripe quarantined after a "
                f"durability failure — retry against the new leader"))
        if self._io_backpressure:
            return as_refusal(BusyLoopError(
                f"group {group}: storage backpressure (WAL out of "
                f"disk space)", retry_after_s=1.0))
        if not self.h_active[group]:
            return as_refusal(ObsoleteContextError(f"group {group} closed"))
        if self.h_role[group] != LEADER:
            hint = int(self.h_leader[group])
            ev = self._evacuated.get(group)
            if ev is not None and self.timer_ticks < ev[1]:
                # Health-driven hand-off: the typed refusal carries the
                # evacuation target so clients re-point in one hop even
                # before the leader mirror catches up (api/anomaly.py).
                return as_refusal(LeadershipEvacuatedError(
                    group, None if hint == NIL else hint, target=ev[0]))
            return as_refusal(
                NotLeaderError(group, None if hint == NIL else hint))
        if not self.h_ready[group]:
            return self._not_ready(group)
        return None

    def _not_ready(self, group: int) -> Exception:
        """The gate's refusal on a led lane that is not ready, with the
        episode it falls in (``_unready_since``: none is open on a leader
        that has not been ready yet since its election)."""
        self._not_ready_refusals += 1
        why = f"group {group}: leader lacks a healthy majority"
        since = int(self._unready_since[group])
        if since:
            why += (f" (unready for {self.timer_ticks - since} periods, "
                    f"since tick {since})")
        return as_refusal(NotReadyError(why))

    def is_leader(self, group: int) -> bool:
        return bool(self.h_role[group] == LEADER)

    def is_ready(self, group: int) -> bool:
        """Leading AND a majority of peers healthy (reference
        Leader.isReady, Leader.java:52-64)."""
        return bool(self.h_ready[group])

    def leader_hint(self, group: int) -> Optional[int]:
        h = int(self.h_leader[group])
        return None if h == NIL else h

    # ------------------------------------------------------------- tick loop

    # A tick counts as late when it starts more than this share of a
    # period after it was due.  Measured (PERF.md, PR 23 call 25): a tick
    # of 1.2 periods (0.2 late) leaves the peers' slice-per-tick streams
    # in step, one of 1.8 (0.8 late) leaves a standing backlog of one
    # slice per source in this node's InboxAccumulator; half a period
    # separates the two.
    LATE_TICK_SHARE = 0.5

    def _next_start(self, now: float, interval: float) -> float:
        """When the tick after the one starting at ``now`` is due.  One
        period on, unless the nodes of this cluster were said to share a
        host (``start(stagger=True)``: three containers in one process,
        as the benchmark's coordination service runs them).  Then it is
        the first instant of this node's grid more than half a period
        away: the grid is ``k * interval`` on the host's monotonic
        clock, node i of P a share i/P of a period after node 0, so no
        two of them ever tick together.  Left to where their boots
        happened to put them, ticks that overlap stay overlapped (each
        start is one period after the last) and every stage of every
        tick takes two to three times as long, the tick threads queueing
        for one interpreter: 75-95 ms a tick against 26-31 at 16 lanes
        (PERF.md, PR 26), and a whole process was in one state or the
        other.  On the grid a tick that overran its period starts the
        next at once and is back on the grid one tick later."""
        if not self._tick_stagger:
            return now + interval
        P = self.cfg.n_peers
        phase = interval * (self.node_id % P) / P
        return (math.floor((now + 0.5 * interval - phase) / interval)
                + 1) * interval + phase

    def _note_tick_start(self, now: float, interval: float,
                         due_next: float) -> None:
        """Tick thread, once per loop period, at the timer's tick: how
        late it starts against when it was due, and when the loop is to
        start the next one (``due_next``).  Steps started for arriving
        work in between are not the timer's and are not counted here."""
        due, self._tick_due = self._tick_due, due_next
        if due is None:
            return
        late = max(0.0, now - due)
        self.metrics.observe("tick_late_s", late)
        if late > self.LATE_TICK_SHARE * interval:
            self.metrics["ticks_late"] += 1

    def _on_slice(self, src: int, fields, payloads) -> None:
        """Reader threads: a peer's slice is queued for the next step, and
        a loop asleep between two timer ticks hears of it."""
        self.acc.merge(src, fields, payloads)
        self._wake.set()

    def _run(self, interval: float) -> None:
        """The loop: a step when the period's timer fires, and a step when
        work waits in between (``_wake``) and ``arrival_step_at`` finds
        the gap passed and room before the timer.  The gap behind a step
        is the time it held the interpreter: its duration less what
        ``tick()`` observed of its own waits (the stages ``scan_device``
        and ``scan_fetch`` and the WAL's fsync seconds of that call; not
        ``dispatch_upload`` or ``dispatch_enqueue``, which are the host's
        own work), and the whole duration of a step that failed (a
        ``tick()`` that returns has run its host phase).  The
        period stays the ENGINE's clock whatever the arrival rate: only
        the timer's step advances it (``tick(arrival=False)``), and
        ``_next_start``, ``tick_late_s`` and ``ticks_late`` are about
        timer ticks alone.  The first step is the timer's."""
        st = self._stages
        while not self._stop.is_set():
            t0 = time.perf_counter()
            arrival = self._tick_due is not None and t0 < self._tick_due
            if not arrival:
                self._note_tick_start(t0, interval,
                                      self._next_start(t0, interval))
            waited = 0.0
            try:
                self.tick(arrival=arrival)
                waited = self._fsync_waited + st.total(
                    "scan_device", "scan_fetch")
            except Exception:
                log.exception("node %d tick failed", self.node_id)
                st.leave()
            ended = time.perf_counter()
            self._step_costs.append(ended - t0)
            st.enter("wait")
            # From the loop's second step on, a stage that outlasts the
            # period is a stall (the first loads or compiles the program).
            st.period = interval
            self._await_step(ended, ended - t0, waited)
            st.leave()
        st.period = None    # whoever steps the node next has no period

    def _await_step(self, ended: float, took: float, waited: float) -> None:
        """Tick thread, between two steps: sleep until the timer's tick is
        due, or until work waits and ``arrival_step_at`` lets a step
        start for it, whichever is first.  Leaves the step it lets start
        ``_gap_given``: whether work stood waiting for the gap's end
        (``held``), the gap, and what the last step's waits took off it."""
        due, wake, stop = self._tick_due, self._wake, self._stop
        # What a step has lately cost: the median of the last few, not
        # their maximum.  The one step that a pause stretched is paid for
        # by the gap it leaves behind it; remembered as the cost, it
        # would hold the next eight periods' arrivals back to the timer
        # (and a backlog with them), and a step that starts a little too
        # close to the timer costs that tick a few milliseconds, no more.
        costs = sorted(self._step_costs)
        cost = costs[len(costs) // 2] if costs else 0.0
        held = False
        while not stop.is_set():
            now = time.perf_counter()
            if now >= due:
                break
            if not wake.is_set():
                wake.wait(due - now)
                continue
            at = arrival_step_at(now, ended, took, due, cost, waited)
            if at is not None and at <= now:
                break
            # Inside the gap, or no room before the timer: the work waits
            # (and what arrives meanwhile shares its step).
            held = at is not None
            stop.wait((due if at is None else at) - now)
        self._gap_given = (held, max(0.0, took - waited), waited)

    def set_active(self, group: int, active: bool,
                   purge: bool = False) -> None:
        """Open or close a group lane (thread-safe; takes effect next tick).
        Closing makes the lane inert — no timers, no RPCs, no submissions
        (reference exitContext, context/ContextManager.java:126-133).
        ``purge=True`` (destroy) additionally wipes the lane's durable log,
        machine state, snapshots and device lanes so a future group can
        reuse it from scratch (reference destroyContext,
        context/ContextManager.java:139-167)."""
        with self._lifecycle_lock:
            self._lifecycle.append((group, active, purge))

    def is_active(self, group: int) -> bool:
        return bool(self.h_active[group])

    def activate_lane(self, lane: int, gen: int) -> None:
        """Activate a lane for incarnation ``gen``: if the lane last served
        an older incarnation, purge it first so the new group starts from
        scratch (covers a destroy this node never saw)."""
        known = self._lane_gens.get(str(lane), 0)
        if gen > known:
            if known > 0 or self.store.tail(lane) > 0 \
                    or self.store.stable(lane) is not None:
                self.set_active(lane, False, purge=True)
            self._lane_gens[str(lane)] = gen
            tmp = self._lane_gens_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(self._lane_gens, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self._lane_gens_path)
        self.set_active(lane, True)

    def tick(self, arrival: bool = False) -> StepInfo:
        """Advance the node one step and return its StepInfo.

        ``arrival``: the node's own loop (``_run``) says so of a step it
        starts because work arrived between two timer ticks.  Such a step
        is a whole tick (dispatch, fetch, host phase behind its own fsync
        barrier) that leaves the engine's clock where it is
        (``HostInbox.clock`` 0): no timer of the engine can expire in it,
        and the host-side cadences that mean time (``timer_ticks``) stand
        still too.  Every other caller (``LocalCluster``,
        ``chip_smoke.py``, the lock-step tests) leaves it False, and each
        of their steps is a period of the engine's clock, as it always
        was.

        One order, the only one: the step is DISPATCHED (``_dispatch``:
        intake, one upload, JAX async dispatch), its results are FETCHED
        (``_fetch``: wait, one copy down, mirrors) and its HOST PHASE runs
        at once (``_host_phase``: WAL staging, THE fsync barrier, stamps,
        one flush of one frame a peer, applies, read serving, maintain),
        then the tail; nothing of a tick outlives ``tick()``.  Every
        acknowledgement, AE-response, vote, AppendEntries, served read and
        future of the tick leaves behind that one barrier, by order alone.
        Beside the order, the scan's commit quorum counts this node's own
        match only up to the FSYNCED tail it is fed every step
        (``HostInbox.durable_tail``): after a barrier that FAILED the host's
        staged mirror is ahead of the disk, and what the clamp is then fed
        is the confirmed tail (``_acked_tail``), so no un-fsynced range is
        ever self-acknowledged into a commit, and a failed barrier changes
        what the one program is fed, never which program runs.
        """
        st = self._stages
        # Every instant of tick() belongs to one named phase (the stage
        # histograms and raft.<name> profiler spans): dispatch_intake,
        # dispatch_upload, dispatch_enqueue (_dispatch); wal, fsync, send,
        # apply, reads, maintain (_host_phase); scan_device, scan_fetch,
        # mirrors (_fetch); tail.  _run adds wait.
        st.begin(self.ticks)
        self._fsync_waited = 0.0
        if self._lat is not None:
            self._lat.tick = self.ticks
        _tick_t0 = st.enter("dispatch_intake")
        st.note(arrival=int(arrival))
        m = self.metrics
        m["ticks"] += 1
        if arrival:
            m["ticks_on_arrival"] += 1
        given, self._gap_given = self._gap_given, None
        if given is not None:
            # The loop started this step: what _await_step gave it.  A
            # timer step is never held by the gap (its work rode the
            # timer for want of room, or there was none).
            held, gap, waited = given
            held = int(held and arrival)
            st.note(held=held, gap_ms=1e3 * gap, waited_ms=1e3 * waited)
            if arrival:
                m["steps_held"] += held
                m.observe("arrival_gap_s", gap)
        # Whatever was queued before this instant the intake below sees;
        # whatever is queued after it sets the event again.
        self._wake.clear()
        ctx = self._dispatch(arrival, _tick_t0)
        self._fetch(ctx)
        self._host_phase(ctx)
        # tick_latency_s ends here, where it always has; the tail below
        # (admission, txn, span harvest, health) is the stage after it.
        m.observe("tick_latency_s", st.enter("tail") - _tick_t0)
        m.observe("tick_stage_dispatch_s", st.total(
            "dispatch_intake", "dispatch_upload", "dispatch_enqueue"))
        m.observe("tick_stage_scan_wait_s",
                  st.total("scan_device", "scan_fetch"))
        # The admission controller's tick is the time from one timer
        # tick's start to the next: a submission can queue for a whole
        # PERIOD (a step for it starts at once only where the loop has
        # the room), however short the tick's own work is.  Fed the work
        # time alone, a loop paced slower than its work (tick_ms=1000 on
        # the chip) saw every one-period wait as a standing queue and
        # shed an idle cluster's traffic.  Every step feeds its sojourn
        # sample; only the timer's feeds the period.
        if arrival:
            self._admission_tick(None)
        else:
            prev, self._tick_started = self._tick_started, _tick_t0
            self._admission_tick(time.perf_counter() - _tick_t0
                                 if prev is None else _tick_t0 - prev)
            # Txn plane: fold driver/resolver counters and (every
            # sweep_every timer ticks) resolve expired write-intents on
            # groups this node leads (runtime/txn.py — coordinator
            # timeouts are driven off this tick loop, not off any client
            # thread).
            self.txn.tick(self)
        if self._lat is not None:
            # Merge retired spans from every thread's ring into the
            # shared histograms — tick thread only, so the registry
            # keeps its single-writer contract (utils/metrics.py).
            self._lat.harvest(self.metrics)
        if self._hops is not None:
            # Pair echoes with pending contexts and finalize settled
            # spans — after harvest so a span retired this tick already
            # carries its outcome.
            self._hops.fold(self.metrics)
        # Health scorecards last: the fold above just refreshed the hop
        # histograms this tick's peer scoring reads.  Once a period: a
        # score is a sum of per-tick penalties and its decay a count of
        # ticks.
        if not arrival:
            self._health_tick()
            if self.cfg.hibernate:
                self._node_beat()
        refused = self._not_ready_refusals
        if refused != self._not_ready_folded:
            m["refused_not_ready"] += refused - self._not_ready_folded
            self._not_ready_folded = refused
        opened, self._hb_closed = self._hb_closed, None
        if opened is not None:
            # This step's drain held the last acknowledgement of a
            # heartbeat round: the round ends where the step does.
            took = time.perf_counter() - opened
            m["hb_rounds_closed"] += 1
            m.observe("hb_round_s", took)
            st.note(hb_round_s=took)
        st.leave()
        return ctx.info

    def _admission_tick(self, tick_s: Optional[float]) -> None:
        """Per-step admission-controller feed + metrics fold (tick thread
        only — the registry's single-writer contract).  ``tick_s``: the
        period just measured, on the timer's tick; None on a step
        started for arriving work.  The sojourn
        sample was stashed by this tick's ``_persist_prepare`` pop; when
        nothing popped AND the queues are empty, 0.0 is fed (the queue
        drained — the strongest good signal); a non-empty queue with no
        pop carries no information (None)."""
        adm = self.admission
        if not adm.enabled:
            return
        if tick_s is not None:
            adm.note_tick(tick_s)
        d, self._adm_delay = self._adm_delay, None
        if d is None and self._queued_total == 0:
            d = 0.0
        adm.note_delay(d)
        if d is not None:
            self.metrics.observe("admission_queue_delay_s", d)
        m, folded = self.metrics, self._adm_fold
        cur = (adm.admitted, adm.shed, adm.shed_tenant, adm.expired)
        for i, name in enumerate(("admission_admitted", "admission_shed",
                                  "admission_shed_tenant",
                                  "admission_expired")):
            delta = cur[i] - folded[i]
            if delta:
                m[name] += delta
                folded[i] = cur[i]
        m.gauge("admission_level", round(adm.level, 4))
        m.gauge("admission_shedding", 1 if adm.overloaded else 0)

    # ------------------------------------------------- tick: health plane

    def _health_tick(self) -> None:
        """Per-period gray-failure scorecard feed + leadership evacuation
        (tick thread only, on the timer's tick; its cadences and
        cool-downs count ``timer_ticks``).  The registry folds this
        tick's self signals (slow-I/O watchdog, stripe quarantine, ENOSPC
        backpressure, reconnects, admission shed level) and the hop
        histograms' per-peer windowed deltas; when the SELF score crosses the degraded
        threshold, up to ``RAFT_EVAC_GROUPS`` led groups are handed to
        their most caught-up non-degraded voter via the §3.10 transfer
        plane — proactive step-down while this node can still replicate,
        instead of waiting to become the fleet's slowest quorum member.
        Rate-limited by ``RAFT_EVAC_COOLDOWN_TICKS`` so a flapping score
        cannot thrash leadership."""
        h = self.health
        if h is None:
            return
        adm = self.admission
        h.ingest(self.timer_ticks, self.metrics,
                 io_slow=self._io_slow,
                 poisoned_stripes=len(self._poisoned_stripes),
                 backpressure=self._io_backpressure,
                 admission_level=adm.level if adm.enabled else 0.0)
        # Contact feed from the device qc lanes (max over groups -> [P]
        # last-heard ticks), at an admin cadence like catch_up_gaps.
        if self.state.qc is not None and self.timer_ticks % 16 == 0:
            heard = np.asarray(jax.device_get(self.state.qc.heard))
            h.note_contact(heard.max(axis=0))
        # Expired evacuation markers age out (the fleet has re-pointed).
        for g in [g for g, (_, exp) in self._evacuated.items()
                  if self.timer_ticks >= exp]:
            del self._evacuated[g]
        bad = h.degraded_peers()
        m = self.metrics
        m.gauge("health_self_score", round(h._decayed(h.self_score), 4))
        m.gauge("health_self_degraded", int(h.self_degraded()))
        m.gauge("health_degraded_peers", len(bad))
        if not h.self_degraded() or self.timer_ticks < self._evac_next_ok:
            return
        led = np.nonzero(self.h_role == LEADER)[0]
        if led.size == 0:
            return
        from ..core.types import conf_new_of, conf_voters_of

        moved = 0
        gaps = None     # one fetch a round, at the first group that moves
        for g in led:
            g = int(g)
            if moved >= self._evac_groups_per_round:
                break
            with self._member_lock:
                busy = g in self._xfer_pending
            if busy or g in self._evacuated:
                continue
            w = int(self.h_conf_word[g])
            vmask = conf_voters_of(w) | conf_new_of(w)
            cand = [p for p in range(self.cfg.n_peers)
                    if ((vmask >> p) & 1) and p != self.node_id
                    and p not in bad]
            if not cand:
                continue   # nowhere healthy to go — stay and serve
            if gaps is None:
                gaps = self.catch_up_gaps()
            target = min(cand, key=lambda p: gaps[g, p])
            fut = self.transfer_leadership(g, target)
            if fut.done() and fut.exception() is not None:
                continue   # refused (raced a role change) — not an evac
            self._evacuated[g] = (
                target, self.timer_ticks + 8 * self.cfg.election_ticks)
            m["leader_evacuations"] += 1
            h.note_evacuation(g, target)
            moved += 1
        if moved:
            self._evac_next_ok = self.timer_ticks + self._evac_cooldown
            log.warning(
                "node %d degraded (score %.2f): evacuated %d group(s)",
                self.node_id, h._decayed(h.self_score), moved)

    def health_snapshot(self) -> dict:
        """The /healthz ``peers`` block (runtime/obsrv.py): per-peer and
        self scorecards, degraded flags, contact ages, evacuation audit.
        Snapshot reads only — safe off the tick thread (same contract as
        /metrics)."""
        if self.health is None:
            return {"enabled": False}
        doc = {"enabled": True}
        doc.update(self.health.snapshot())
        doc["evacuated_groups"] = {
            str(g): {"target": t, "expiry_tick": e}
            for g, (t, e) in sorted(self._evacuated.items())}
        return doc

    # ------------------------------------------------------- tick: dispatch

    def _dispatch(self, arrival: bool, started: float) -> _TickCtx:
        cfg = self.cfg
        G = cfg.n_groups

        # -- 0. group lifecycle ----------------------------------------------
        with self._lifecycle_lock:
            changes, self._lifecycle = self._lifecycle, []
        with self._snap_lock:
            fetched, self._snap_fetched = self._snap_fetched, []
        if changes or fetched:
            # Lanes open, close or are wiped, and an installed snapshot
            # moves a durable tail, a floor and the apply frontier: the
            # next host phase looks at every lane (_host_lanes).
            self._host_sets_ok = False
        if changes:
            act = np.asarray(self.state.active).copy()
            purged = []
            hg = self._healthy_groups
            for g, a, purge in changes:
                act[g] = a
                if not a:
                    # Strand nothing: queued-but-unaccepted submissions AND
                    # registered promises both fail out when a lane closes.
                    # A QUARANTINE-driven close rejects with the typed
                    # Unavailable refusal (queued work never reached any
                    # log — retry-safe elsewhere); promise aborts for that
                    # case already fired in _quarantine_stripes with the
                    # unmarked outcome-unknown StorageFaultError.
                    if hg is not None and not hg[g]:
                        exc_f = lambda: UnavailableError(
                            f"group {g}: WAL stripe quarantined after a "
                            f"durability failure — retry against the new "
                            f"leader")
                    else:
                        exc_f = lambda: ObsoleteContextError(
                            f"group {g} closed")
                    self.dispatcher.abort_promises(
                        g, ObsoleteContextError(f"group {g} closed"))
                    self._reject_submissions(g, exc_f())
                    # Reads too — including barrier-confirmed ones: the
                    # machine they would query is going away.
                    self._reject_reads(g, exc_f(), drop_released=True)
                    self._reject_membership(g, exc_f())
                if purge:
                    purged.append(g)
            self.state = self.state.replace(active=jax.device_put(act))
            self.h_active = act
            self._lane_counts = None
            if purged:
                self._purge_lanes(purged)

        # -- 1. host inbox ---------------------------------------------------
        # A shape that takes the column step (core/step.py column_layouts)
        # keeps no fresh [G] planes: its intake is _dispatch_rows'.
        lay = column_layouts(cfg, True)
        if lay is not None:
            return self._dispatch_rows(lay, arrival, started, fetched)
        with self._submit_lock:
            # One vector op over the entry-count mirror — the dict walk
            # was O(groups-with-queues) per tick.
            submit_n = np.minimum(
                self._queued_n, cfg.max_submit).astype(np.int32)
        read_n = np.zeros(G, np.int32)
        for g, n in self._offer_reads():
            read_n[g] = n
        read_veto = self._hold_read_veto(arrival)
        snap_done = np.zeros(G, bool)
        snap_idx = np.zeros(G, np.int32)
        snap_term = np.zeros(G, np.int32)
        snap_conf = np.zeros(G, np.int32)
        for g, idx, term, cw in self._install_snapshots(fetched):
            snap_done[g] = True
            snap_idx[g] = idx
            snap_term[g] = term
            snap_conf[g] = cw
        # Membership plane: re-offer every pending change/transfer until
        # the device latches it (intake is idempotent — an accepted change
        # equals the active config or is fenced as in-flight, so a
        # duplicate offer can never append a second entry).
        conf_voters = np.zeros(G, np.int32)
        conf_learners = np.zeros(G, np.int32)
        xfer_target = np.full(G, NIL, np.int32)
        with self._member_lock:
            for g, ent in self._conf_pending.items():
                conf_voters[g] = ent[0]
                conf_learners[g] = ent[1]
            for g, ent in self._xfer_pending.items():
                xfer_target[g] = ent[0]
        # Durability feedback: the fsynced tail per group.  The scan
        # clamps its own commit-quorum match to it (core/step.py phase
        # 10).  Every completed host phase ends with its fsync barrier, so
        # the mirror is durable by construction at dispatch time; after a
        # FAILED barrier the staged mirror is ahead of the disk, and the
        # confirmed tail (_acked_tail) is fed instead.
        src = self._durable_tail_m if self._acked_tail is None \
            else self._acked_tail
        durable = np.minimum(src, I32_SAFE_MAX).astype(np.int32)
        compact_to = self._compact_grant.astype(np.int32)
        self._compact_grant = np.zeros(G, np.int64)
        wake, wake_ids, asked = None, self._wake_ids, None
        if cfg.hibernate:
            # The peer-lost signal, and the lanes asked something beside
            # a write or a read (what a wake is put down to).
            self._wake_ids = wake_ids[:0]
            wake = np.zeros(G, bool)
            wake[wake_ids] = True
            asked = np.flatnonzero(
                (conf_voters != 0) | (xfer_target != NIL) | (compact_to > 0)
                | snap_done)

        # -- 2. network inbox ------------------------------------------------
        # This tick's upload buffers (core/packing.py), fresh: the [G]
        # host planes and the drained slices' messages as zeroed dense
        # planes that are views of the packed buffers, filled where they
        # cross to the device from.  ctx.arrays reads them (DenseView)
        # until this tick's host phase is done with it.
        inputs, readback = step_layouts(cfg, True)
        batches, staged_payloads = self.acc.pop()
        buffers = inputs.alloc()
        host, inbox = inputs.unpack(buffers)
        arrays = DenseView({name: getattr(inbox, name)
                            for name in self.template})
        scatter_dense(batches, arrays.planes)
        self._fold_inbox_stats()
        if self._hb_rounds:
            self._hb_acknowledged(arrays)
        # The [G] host planes built above are copied into theirs (a few
        # KB; 4 MB at 100,000 lanes).
        jax.tree.map(np.copyto, host, HostInbox(
            submit_n=submit_n, snap_done=snap_done, snap_idx=snap_idx,
            snap_term=snap_term, snap_conf=snap_conf, compact_to=compact_to,
            conf_voters=conf_voters, conf_learners=conf_learners,
            xfer_target=xfer_target, read_n=read_n, read_veto=read_veto,
            clock=int(not arrival), durable_tail=durable, wake=wake))

        # -- 2b. upload: every host plane crosses to the device here, after
        # the whole intake, in one transfer per buffer ----------------------
        st = self._stages
        st.enter("dispatch_upload")
        packed = jax.device_put(buffers)
        st.note(transfers=len(packed), bytes=sum(b.nbytes for b in buffers),
                columns=0, dense=1, rows=0, planes_dense=1)
        self.metrics["h2d_transfers"] += len(packed)

        # -- 3. device step (async dispatch: no transfer, no block) ----------
        # The step hands back, packed the same way, everything _fetch reads
        # (core/step.py Readback): the tick keeps no reference to a leaf of
        # the state, which the next step donates.
        st.enter("dispatch_enqueue")
        self.state, packed = node_step_packed(
            cfg, inputs, self.state, packed)

        ctx = _TickCtx()
        ctx.submit_n, ctx.read_n = submit_n, read_n
        ctx.timer = not arrival
        ctx.started = started
        ctx.staged_payloads, ctx.arrays = staged_payloads, arrays
        ctx.packed, ctx.readback = packed, readback
        ctx.columns = ctx.out_dense = ctx.rows = None
        ctx.wake_ids, ctx.asked_ids, ctx.woke_ids = wake_ids, asked, None
        return ctx

    def _dispatch_rows(self, lay, arrival: bool, started: float,
                       fetched) -> _TickCtx:
        """``_dispatch`` from the host inbox on, for a shape that takes the
        column step: HostInbox goes up as the ROWS of the lanes that have
        something to say (a queued write, an offered read, a snapshot
        done, a pending membership change or transfer, a compaction
        grant, a durable tail that moved since the device was last told:
        core/packing.py RowLayout) beside the device's resident zero
        planes, and no fresh [G] plane is built, copied or uploaded.  A
        step with more such lanes than the row buffer holds (an election
        storm's durable tails), and the first step, upload the planes
        whole as a packed step does: decided by the count, nothing cut."""
        cfg = self.cfg
        rl = lay.rows_in
        # -- 1. host inbox, as (lanes, values) --------------------------------
        with self._submit_lock:
            sub_ids = np.flatnonzero(self._queued_n)
            sub_n = np.minimum(
                self._queued_n[sub_ids], cfg.max_submit).astype(np.int32)
        offers = self._offer_reads()
        read_ids = np.fromiter((g for g, _ in offers), np.int64, len(offers))
        read_n = np.fromiter((n for _, n in offers), np.int32, len(offers))
        read_veto = self._hold_read_veto(arrival)
        snaps = list(self._install_snapshots(fetched))
        with self._member_lock:
            confs = [(g, ent[0], ent[1])
                     for g, ent in self._conf_pending.items()]
            xfers = [(g, ent[0]) for g, ent in self._xfer_pending.items()]
        grant_ids = np.flatnonzero(self._compact_grant)
        grants = self._compact_grant[grant_ids].astype(np.int32)
        self._compact_grant[grant_ids] = 0
        # Durability feedback (see _dispatch): a LEVEL, which the device
        # keeps beside its state and the rows patch where the host's
        # plane moved since it was last told (_dur_sent is what the
        # device holds, lane for lane: the commit clamp of phase 10
        # reads it).
        durable = self._durable_tail_m if self._acked_tail is None \
            else self._acked_tail
        dur_ids = sub_ids[:0]
        if self._dur_sent is not None:
            dur_ids = np.flatnonzero(durable != self._dur_sent)
        fields = [
            ("submit_n", sub_ids, sub_n), ("read_n", read_ids, read_n),
            ("compact_to", grant_ids, grants)]
        if snaps:
            at = np.asarray([g for g, *_ in snaps], np.int64)
            fields += [
                ("snap_done", at, True),
                ("snap_idx", at, [idx for _, idx, _, _ in snaps]),
                ("snap_term", at, [term for _, _, term, _ in snaps]),
                ("snap_conf", at, [cw for _, _, _, cw in snaps])]
        if confs:
            at = np.asarray([g for g, _, _ in confs], np.int64)
            fields += [("conf_voters", at, [v for _, v, _ in confs]),
                       ("conf_learners", at, [ln for _, _, ln in confs])]
        if xfers:
            fields.append(("xfer_target",
                           np.asarray([g for g, _ in xfers], np.int64),
                           [t for _, t in xfers]))
        wake_ids, asked = self._wake_ids, None
        if cfg.hibernate:
            # The lanes asked something beside a write or a read (what a
            # wake is put down to), then the peer-lost signal.
            asked = np.concatenate([at for _, at, _ in fields[2:]])
            self._wake_ids = wake_ids[:0]
            if wake_ids.size:
                fields.append(("wake", wake_ids, True))
        # The count decides (a storm moves every lane's durable tail: no
        # sort of 100,000 lanes to find that out).
        said = [dur_ids] + [at for _, at, _ in fields]
        whole = self._dur_sent is None \
            or max(len(at) for at in said) > rl.K
        if not whole:
            ids = np.unique(np.concatenate(said))
            whole = ids.size > rl.K
        # What a step that fits uploads, in ONE array: the rows, and
        # behind them the columns (core/packing.py alloc_regions).
        up, (rows, columns) = alloc_regions(rl, lay.columns)
        view = rl.view(rows)
        view.set_head("read_veto", read_veto)
        view.set_head("clock", int(not arrival))

        # -- 2. network inbox ------------------------------------------------
        # The drained slices' messages as the columns they arrived as
        # when every source fits the column buffers (nothing is then
        # allocated, zeroed or walked P x G), else as zeroed dense planes
        # that are views of the packed buffers (_dispatch).
        batches, staged_payloads = self.acc.pop()
        arrays = lay.columns.view(columns)
        resident = ()
        if fill_columns(batches, arrays):
            if whole:
                buffers = lay.host.alloc()
                host = lay.host.unpack(buffers)
            else:
                buffers, host = (), None
                resident = self._resident_zero(lay)
        else:
            buffers = lay.inputs.alloc()
            host, inbox = lay.inputs.unpack(buffers)
            arrays = DenseView({name: getattr(inbox, name)
                                for name in self.template})
            scatter_dense(batches, arrays.planes)
            up = rows           # the columns stay behind
        self._fold_inbox_stats()
        if self._hb_rounds:
            self._hb_acknowledged(arrays)
        if host is not None:
            host.xfer_target[...] = NIL
        if whole:
            # The planes whole, written where they cross from: what
            # _dispatch builds, from the same (lanes, values).
            view.set_n(-1)
            for name, at, vals in fields:
                getattr(host, name)[at] = vals
            np.copyto(host.durable_tail, np.minimum(durable, I32_SAFE_MAX),
                      casting="unsafe")
            self._dur_sent = durable.copy()
        else:
            n = ids.size
            view.set_n(n)
            view.ids[:n] = ids
            view.field("xfer_target")[:n] = NIL
            for name, at, vals in fields:
                view.field(name)[np.searchsorted(ids, at)] = vals
            # Every row says its lane's level, moved or not.
            view.field("durable_tail")[:n] = np.minimum(
                durable[ids], I32_SAFE_MAX)
            self._dur_sent[dur_ids] = durable[dur_ids]
        buffers += (up,)

        # -- 2b. upload ------------------------------------------------------
        st = self._stages
        st.enter("dispatch_upload")
        packed = resident + jax.device_put(buffers)
        columns_in = arrays.columns
        st.note(transfers=len(buffers), bytes=sum(b.nbytes for b in buffers),
                columns=columns_in or 0, dense=int(columns_in is None),
                rows=0 if whole else int(ids.size), planes_dense=int(whole))
        m = self.metrics
        m["h2d_transfers"] += len(buffers)
        m["column_overflows_in" if columns_in is None
          else "steps_columns_in"] += 1
        m["row_overflows_in" if whole else "steps_rows_in"] += 1

        # -- 3. device step (async dispatch: no transfer, no block) ----------
        # The step hands back its results twice: as the rows that moved
        # and the outbox's columns (put into one buffer by a program of
        # its own, enqueued behind the step), fetched, and whole (the
        # carry's planes, the dense outbox), left on the device for the
        # fetch of a step that does not fit them.
        st.enter("dispatch_enqueue")
        if self._carry is None:
            self._carry = first_carry(lay)
        last = self._carry
        self.state, self._carry, out, out_dense = node_step_columns(
            cfg, lay, columns_in is not None, self.state, last, packed)
        back = compact_readback(lay, self._carry, last, out)

        ctx = _TickCtx()
        step = ctx.rows = _RowStep()
        step.carry, step.down_ids = self._carry, None
        step.up = self._up_planes.pop() if self._up_planes else (
            np.zeros(cfg.n_groups, np.int32), np.zeros(cfg.n_groups, np.int32))
        ctx.submit_n, ctx.read_n = step.up
        step.sub_ids, step.sub_n = sub_ids, sub_n
        step.read_ids, step.read_n = read_ids, read_n
        ctx.wake_ids, ctx.asked_ids, ctx.woke_ids = wake_ids, asked, None
        ctx.submit_n[sub_ids] = sub_n
        ctx.read_n[read_ids] = read_n
        ctx.timer = not arrival
        ctx.started = started
        ctx.staged_payloads, ctx.arrays = staged_payloads, arrays
        ctx.packed, ctx.readback = (back,), lay.back
        ctx.columns, ctx.out_dense = lay, out_dense
        return ctx

    def _resident_zero(self, lay) -> tuple:
        """The device's own copy of ``lay.host``'s planes holding no event
        (zero; ``xfer_target`` NIL), uploaded once: what a step's rows are
        written over."""
        zero = self._resident.get(lay)
        if zero is None:
            buffers = lay.host.alloc()
            lay.host.unpack(buffers).xfer_target[...] = NIL
            zero = self._resident[lay] = tuple(jax.device_put(buffers))
        return zero

    def _offer_reads(self) -> List[Tuple[int, int]]:
        """Tick thread, at a step's intake: the (lane, reads) this step
        offers the device (``HostInbox.read_n``)."""
        # Read plane: when a group's offer slot is free, promote ALL of
        # its waiting batches into it as one offer — one read_n, one
        # stamp, one barrier for every read the group holds.  What keeps
        # that linearizable: only batches WAITING at this instant are
        # merged, so every merged read was invoked before the step that
        # stamps its ReadIndex is even dispatched; a read that arrives
        # while an offer exists waits for the next slot and never joins a
        # stamped offer.  An unstamped offer (no free device slot / not
        # leader yet) simply stays offered, as it is, and is re-offered
        # next tick.
        with self._read_lock:
            for g, q in self._reads_waiting.items():
                if q and g not in self._reads_offered:
                    offer = _ReadOffer(list(q))
                    q.clear()
                    self._read_queued_n[g] -= offer.n
                    self._reads_offered[g] = offer
                    for b in offer.parts:
                        if b.sink.span is not None:
                            # The group's offer slot is won: submitted ->
                            # offered was the wait for it
                            # (lat_read_queue_s).
                            b.sink.span.mark(OFFERED)
            return [(g, offer.n) for g, offer in self._reads_offered.items()]

    def _hold_read_veto(self, arrival: bool) -> bool:
        """Tick thread, at a step's intake: ``HostInbox.read_veto``."""
        cfg = self.cfg
        if not cfg.read_lease:
            # Strict ReadIndex anchors nothing at a receipt and reads no
            # clock (core/step.py phase 6b): a pause stretches nothing,
            # and evidence dropped would only cost a pending read a
            # second round.  Never raised.
            return False
        # Wall-clock pause detection (HostInbox.read_veto contract): a gap
        # beyond read_fresh_ticks tick intervals invalidates stored lease
        # evidence AND whatever acks queued in the inbox across the pause.
        # The veto is HELD for read_fresh_ticks consecutive ticks, not one:
        # pause-era acks still sitting in socket buffers drain through the
        # reader threads into the accumulator over the FOLLOWING ticks too,
        # and a single-tick veto would let receipt-anchored lease evidence
        # resurrect from them one tick later (the tick clock did not
        # advance during the pause, so the freshness bound alone cannot
        # reject them).
        wall = time.monotonic()
        if self._tick_interval and self._last_tick_wall is not None:
            gap = wall - self._last_tick_wall
            # A carried lease (core/step.py phase 6b, case a) is sound
            # while the last cfg.lease_ticks ticks of this clock took no
            # more than one period over their number: a loop that stood
            # still, or ran late by that much in sum, has a `now` that
            # lags the clocks the followers' promise runs on.  The
            # starts are the timer steps', this one's included.
            walls = self._tick_walls
            if not arrival:
                walls.append(wall)
            late = (cfg.lease_carry_ticks and len(walls) == walls.maxlen
                    and wall - walls[0]
                    > self._tick_interval * (cfg.lease_ticks + 1))
            if late or gap > self._tick_interval * max(cfg.read_fresh_ticks,
                                                       2):
                self.note_pause()
        read_veto = self._read_veto_hold > 0
        if read_veto and not arrival:
            # The hold is a length of TIME: read_fresh_ticks periods,
            # not that many steps a few milliseconds apart.
            self._read_veto_hold -= 1
        self._last_tick_wall = wall
        return read_veto

    def note_pause(self) -> None:
        """For whoever steps the node by hand (no loop, so no wall clock
        to see a pause on): the node has sat out one period or more.
        What the loop's own detection does: stored lease evidence, and
        what queued up meanwhile, is dropped for the veto's hold."""
        self._read_veto_hold = max(self.cfg.read_fresh_ticks, 2)
        self.metrics["read_vetoes"] += 1

    def _hb_open(self, outbox, started: float, kicked=None) -> None:
        """Tick thread, at the fetch of the timer's step: the period's
        heartbeat round opens against every peer this step addresses an
        AppendEntries to.  The outbox stamps every lane with the engine's
        clock (``ae_tick``), which the acknowledgements echo; a node that
        leads nothing opens nothing.  Nor does a timer step in which no
        lane's heartbeat was due (a heartbeat every second period leaves
        such steps): entries for a write, or the barrier heartbeat of a
        read left pending (``kicked``: StepInfo.read_kick), are a lane's
        own traffic and no round, and ``hb_round_s`` stays a reading per
        round."""
        clock, peers = None, set()
        cadence = self.cfg.heartbeat_ticks > 1
        if cadence and kicked is not None:
            kicked = np.asarray(kicked)
        for p in range(self.cfg.n_peers):
            if p == self.node_id:
                continue
            valid = outbox.row("ae_valid", p)
            if cadence and valid.any():
                valid = valid & (outbox.row("ae_n", p) == 0)
                if kicked is not None:
                    valid = valid & ~outbox.over(p, kicked)
            if valid.any():
                peers.add(p)
                if clock is None:
                    clock = int(outbox.row("ae_tick", p)[valid.argmax()])
        if peers:
            self._hb_rounds.append([clock, started, peers])

    def _hb_acknowledged(self, arrays) -> None:
        """Tick thread, right after the drain (``acc.pop()`` merged into
        ``arrays``, a DenseView or a ColumnView): strike from each open
        round the peers whose drained slices acknowledge a heartbeat of
        that round's period (``aer_tick`` echoes the clock it was sent
        at).  The round whose last peer this step's drain strikes closes
        with this step (``tick`` notes ``hb_round_s`` at its end); rounds
        opened before it can no longer close and go with it."""
        heard = {}
        for p in range(self.cfg.n_peers):
            valid = arrays.row("aer_valid", p)
            if valid.any():
                heard[p] = arrays.row("aer_tick", p)[valid]
        if not heard:
            return
        rounds = self._hb_rounds
        for clock, started, peers in rounds:
            for p, echo in heard.items():
                if p in peers and (echo == clock).any():
                    peers.discard(p)
        while rounds and any(not r[2] for r in rounds):
            clock, started, peers = rounds.popleft()
            if not peers:
                self._hb_closed = started

    def _fold_inbox_stats(self) -> None:
        """Tick thread, right after ``acc.drain()``: fold what the drain
        saw (transport/inbox.py InboxStats) into the registry — the wait
        of every slice popped, the deepest queue left behind (one sample
        a tick, so the histogram's mean over a window is a depth in
        slices), the same per source as gauges, the slices collapsed or
        dropped and the replies a collapse overwrote (each of which the
        leader's window waits for in vain: core/step.py window_sums);
        the last two ride the step's raft.dispatch_intake span too.
        Reader threads only ever touch the accumulator."""
        stats = self.acc.take_stats()
        m = self.metrics
        for w in stats.waits_s:
            m.observe("inbox_wait_s", w)
        backlog = max(stats.depth.values(), default=0)
        m.observe("inbox_backlog", backlog)
        if backlog:
            # A slice per source per step (drain): what this step left
            # queued is work for the next one, so a started loop works a
            # backlog off instead of carrying it from period to period.
            self._wake.set()
        for src, depth in stats.depth.items():
            m.gauge(f"inbox_backlog_src{src}", depth)
        if stats.collapsed:
            m["inbox_collapsed"] += stats.collapsed
            m["inbox_replies_merged"] += stats.merged
        if stats.dropped:
            m["inbox_dropped"] += stats.dropped
        self._stages.note(collapsed=stats.collapsed, merged=stats.merged)

    # --------------------------------------------------------- tick: fetch

    def _fetch(self, ctx: _TickCtx) -> None:
        """Pull the dispatched scan's results to the host (the tick's one
        wait for the device: the whole step) and refresh the per-tick
        mirrors."""
        st = self._stages
        # The wait is split where the work happens: scan_device is the
        # device's remaining work on this tick's step, scan_fetch the
        # device-to-host copy (tick_stage_scan_wait_s, observed by tick(),
        # stays their sum).  One transfer per packed buffer for everything
        # the host needs this tick; the planes below are views of the
        # fetched buffers (the heat lanes are a None subtree when cfg.heat
        # is off).
        st.enter("scan_device")
        packed = jax.block_until_ready(ctx.packed)
        st.enter("scan_fetch")
        fetched = jax.device_get(packed)
        ctx.packed = None
        if ctx.columns is not None:
            counts = self._fetch_rows(ctx, fetched)
        else:
            st.note(transfers=len(fetched),
                    bytes=sum(b.nbytes for b in fetched),
                    columns=0, dense=1, rows=0, planes_dense=1)
            self.metrics["d2h_transfers"] += len(fetched)
            st.enter("mirrors")
            back = ctx.readback.unpack(fetched)
            ctx.outbox = DenseView({name: getattr(back.outbox, name)
                                    for name in self.template})
            counts = self._mirrors_whole(ctx, back)
        self._mirrors_tail(ctx, *counts)

    def _fetch_rows(self, ctx: _TickCtx, fetched) -> tuple:
        """``_fetch`` for a column step, from its one fetched buffer on:
        the Readback's [G] planes came down as the rows of the lanes that
        moved and, behind them, the outbox as its columns, and each
        region's counts say whether it holds its part.  A part that does
        not fit (a row of the outbox beyond the column buffers; more
        lanes moved than the row buffer holds: a storm, a step after a
        purge, the first) is packed on the device from what the step left
        there and fetched as node_step_packed's is: that part crosses
        whole, nothing is cut."""
        lay, st, m = ctx.columns, self._stages, self.metrics
        rows, outbox = regions(fetched[0], lay.rows_out, lay.columns)
        rows, outbox = lay.rows_out.view(rows), lay.columns.view(outbox)
        n_rows = rows.n
        whole = n_rows > lay.rows_out.K or self._rows_whole_out
        extra = ()
        if whole:
            extra += pack_readback(lay, ctx.rows.carry)
            m["row_overflows_out"] += 1
        else:
            m["steps_rows_out"] += 1
        if (outbox.n > lay.columns.K).any():
            outbox = None
            extra += pack_outbox(lay, ctx.out_dense)
            m["column_overflows_out"] += 1
        else:
            m["steps_columns_out"] += 1
        ctx.out_dense = ctx.rows.carry = None
        extra = jax.device_get(extra)
        st.note(transfers=len(fetched) + len(extra),
                bytes=sum(b.nbytes for b in fetched + extra),
                columns=0 if outbox is None else outbox.columns,
                dense=int(outbox is None),
                rows=0 if whole else n_rows, planes_dense=int(whole))
        m["d2h_transfers"] += len(fetched) + len(extra)
        st.enter("mirrors")
        n_back = len(lay.back.buffers) if whole else 0
        if outbox is None:
            dense = lay.outbox.unpack(extra[n_back:])
            outbox = DenseView({name: getattr(dense, name)
                                for name in self.template})
        ctx.outbox = outbox
        if not whole:
            return self._mirrors_rows(ctx, rows)
        self._rows_whole_out = False
        self._whole_back = back = lay.back.unpack(extra[:n_back])
        return self._mirrors_whole(ctx, back)

    def _mirrors_whole(self, ctx: _TickCtx, back) -> tuple:
        """The mirrors of a step whose Readback came down whole: they are
        the fetched planes, swapped in.  Returns ``_mirrors_tail``'s
        arguments."""
        cfg = self.cfg
        h_info = back.info
        h_term, h_role, h_leader = back.term, back.role, back.leader_id
        h_commit, h_base = back.commit, back.base
        ctx.info = h_info
        ctx.term, ctx.voted, ctx.role = h_term, back.voted_for, h_role
        ctx.leader, ctx.commit = h_leader, h_commit
        ctx.base, ctx.base_term = h_base, back.base_term

        if cfg.debug_checks:
            from ..core.step import raise_debug_violations
            raise_debug_violations(h_info, f"node {self.node_id}")

        # i32 lane-overflow guard (core/types.py I32_SAFE_MAX): indices,
        # terms and the tick clock are int32 on device by design — fail
        # loudly with ~2^20 of headroom rather than wrap silently.  The
        # long-horizon story is snapshots + lane purge (index resets), not
        # wider lanes.
        self._guard_i32(int(np.asarray(h_info.log_tail).max(initial=0)),
                        int(h_term.max(initial=0)))

        old_role, was_ready = self.h_role, self.h_ready
        self.h_role, self.h_leader = h_role, h_leader
        self.h_commit, self.h_base = h_commit, h_base
        self.h_term = h_term
        self.h_ready = np.asarray(h_info.ready)
        if cfg.hibernate:
            asleep, was = np.asarray(h_info.asleep), self.h_asleep
            self.h_asleep = asleep
            self._asleep_n = int(asleep.sum())
            self._note_sleep(ctx, np.flatnonzero(was & ~asleep),
                             int((asleep & ~was).sum()))
        self.metrics["elections"] += int(
            ((h_role == LEADER) & (old_role != LEADER)).sum())
        self._leadership_lost(
            np.nonzero((old_role == LEADER) & (h_role != LEADER))[0])

        # Membership plane: refresh config mirrors, settle pending
        # change/transfer futures, fold the tick's counters.
        self._harvest_membership(h_info, h_role)

        # -- CheckQuorum fold ------------------------------------------------
        # Device 6c step-downs (a leader lost voter-quorum contact) and
        # the lease reads they vetoed, folded into counters so a gray
        # failure is visible on the ordinary /metrics page.  None
        # subtrees when cfg.check_quorum is off.
        if h_info.cq_stepdown is not None:
            self._fold_checkquorum(int(np.asarray(h_info.cq_stepdown).sum()),
                                   int(np.asarray(h_info.cq_veto).sum()))

        # Open lanes for which this node neither leads ready nor knows a
        # leader: what tells a store that is electing from one that is
        # sick or overloaded.  Sampled every step, on /metrics and on the
        # step's raft.mirrors span.
        # The two halves apart: open lanes this node leads and is not
        # ready on (``unready``: a fresh leader still waiting for its
        # first majority, or a leader whose followers' windows timed out
        # and cool down), and open lanes it follows without knowing whom.
        led = h_role == LEADER
        unready = self.h_active & led & ~self.h_ready
        self._lane_counts = [
            int(self.h_active.sum()), int(led.sum()), int(unready.sum()),
            int((self.h_active & ~led & (h_leader == NIL)).sum())]
        self._led_open = int((self.h_active & led).sum())
        return back.heat, back.windows, lambda: (unready, was_ready)

    def _mirrors_rows(self, ctx: _TickCtx, rows) -> tuple:
        """The mirrors of a step whose Readback came down as rows: the
        node's persistent planes, patched at the lanes that moved, and
        every pass of ``_mirrors_whole`` made over those lanes alone.
        ``ctx.info``'s level fields and the mirrors are views of the
        persistent planes (the next fetch patches them: a step's host
        phase always ends before the next fetch); its event fields are
        persistent zero planes written here at the rows and cleared at
        the same rows when the step's host phase is done (``_rows_done``).
        Returns ``_mirrors_tail``'s arguments."""
        cfg = self.cfg
        rl = ctx.columns.rows_out
        n = rows.n
        ids = rows.ids[:n]
        back = self._persistent_back(rl)
        h_info = ctx.info = back.info
        ctx.term, ctx.voted, ctx.role = back.term, back.voted_for, back.role
        ctx.leader, ctx.commit = back.leader_id, back.commit
        ctx.base, ctx.base_term = back.base, back.base_term
        new = lambda name: rows.field(name)[:n]
        self._guard_i32(int(new("info.log_tail").max(initial=0)),
                        int(new("term").max(initial=0)))

        # What the mirrors hold at the rows, before the patch.
        act = self.h_active[ids]
        old_role, old_ready = self.h_role[ids], self.h_ready[ids]
        old_leader = self.h_leader[ids]
        old_pending = self.h_conf_pending[ids]
        old_conf_idx = self.h_conf_idx[ids]
        old_commit = int(self.h_commit[ids].sum(dtype=np.int64))
        old_asleep = self.h_asleep[ids] if cfg.hibernate else None
        # Client threads read h_role, h_leader and h_ready lane by lane
        # while this patches them: the flags first, so that no lane reads
        # as led before its readiness is the new step's.
        words, flags = self._back_planes
        flags[:, ids] = rows.flags[:, :n]
        words[:, ids] = rows.words[:, :n]
        ctx.rows.down_ids = ids
        ctx.rows.commit_delta = \
            int(new("commit").sum(dtype=np.int64)) - old_commit
        if cfg.debug_checks:
            from ..core.step import raise_debug_violations
            raise_debug_violations(h_info, f"node {self.node_id}")

        role, ready, leader = new("role"), new("info.ready"), new("leader_id")
        m = self.metrics
        if cfg.hibernate:
            # A lane that woke or fell asleep is a row that moved.
            asleep = new("info.asleep")
            woke = ids[old_asleep & ~asleep]
            slept = int((asleep & ~old_asleep).sum())
            self._asleep_n += slept - woke.size
            self._note_sleep(ctx, woke, slept)
        m["elections"] += int(((role == LEADER) & (old_role != LEADER)).sum())
        self._leadership_lost(ids[(old_role == LEADER) & (role != LEADER)])

        conf_idx = new("info.conf_idx")
        m["membership_changes_entered"] += int(
            (new("info.conf_app_idx") > 0).sum())
        m["membership_changes_committed"] += int(
            (old_pending & ~new("info.conf_pending")
             & (old_conf_idx == conf_idx) & (conf_idx > 0)).sum())
        m["timeout_now_sent"] += int(new("info.xfer_fired").sum())
        self._settle_membership(h_info, back.role)
        if h_info.cq_stepdown is not None:
            self._fold_checkquorum(int(new("info.cq_stepdown").sum()),
                                   int(new("info.cq_veto").sum()))

        # The gauges' counts, kept running: each row takes its lane's old
        # share out and puts its new one in.
        if self._lane_counts is None:
            self._lane_counts = self.count_lanes()
            self._led_open = int(
                ((self.h_role == LEADER) & self.h_active).sum())
        else:
            counts = self._lane_counts
            for sign, r, rd, ld in ((-1, old_role, old_ready, old_leader),
                                    (1, role, ready, leader)):
                led = r == LEADER
                counts[1] += sign * int(led.sum())
                counts[2] += sign * int((act & led & ~rd).sum())
                counts[3] += sign * int((act & ~led & (ld == NIL)).sum())
                self._led_open += sign * int((act & led).sum())

        def masks():
            """``_track_unready``'s [G] masks, in the rare step that asks:
            the lanes whose readiness changed are among the rows."""
            was_ready = self.h_ready.copy()
            was_ready[ids] = old_ready
            return (self.h_active & (self.h_role == LEADER) & ~self.h_ready,
                    was_ready)

        return back.heat, rows.head("windows"), masks

    def _persistent_back(self, rl):
        """The Readback whose leaves are the node's persistent planes,
        which the mirrors are views of while rows come down.  After a
        step whose results came down whole (the mirrors are then that
        fetch's planes) the levels are copied over from it, once."""
        if self._back_planes is None:
            self._back_planes = rl.planes()
            self._back_tree = rl.unstack(*self._back_planes,
                                         np.zeros(rl.H, np.int32))
        back, stale = self._back_tree, self._whole_back
        if stale is not None:
            self._whole_back = None
            rl.copy_levels(stale, *self._back_planes)
            self.h_role, self.h_leader = back.role, back.leader_id
            self.h_commit, self.h_base = back.commit, back.base
            self.h_term = back.term
            self.h_ready = back.info.ready
            self.h_conf_word = back.info.conf_word
            self.h_conf_idx = back.info.conf_idx
            self.h_conf_pending = back.info.conf_pending
            if self.cfg.hibernate:
                self.h_asleep = back.info.asleep
        return back

    def count_lanes(self) -> List[int]:
        """[open lanes, lanes led, open led lanes not ready, open lanes
        followed without a known leader], recounted over the mirrors:
        what ``_fetch`` keeps running while rows come down."""
        led = self.h_role == LEADER
        return [int(self.h_active.sum()), int(led.sum()),
                int((self.h_active & led & ~self.h_ready).sum()),
                int((self.h_active & ~led & (self.h_leader == NIL)).sum())]

    def _note_sleep(self, ctx: _TickCtx, woke: np.ndarray,
                    slept: int) -> None:
        """Tick thread, where a step's ``asleep`` level reaches the
        mirror: count the lanes that fell asleep and those that woke, the
        wakes by cause.  A lane the peer-lost signal named woke of that; a
        lane this step offered something on (a write, a read, a config
        change, a transfer, a grant, an installed snapshot) of a request;
        every other of a message."""
        ctx.woke_ids = woke
        m = self.metrics
        if slept:
            m["lane_sleeps"] += slept
        if not woke.size:
            return
        lost = np.isin(woke, ctx.wake_ids)
        asked = ~lost & ((ctx.submit_n[woke] > 0) | (ctx.read_n[woke] > 0)
                         | np.isin(woke, ctx.asked_ids))
        n_lost, n_asked = int(lost.sum()), int(asked.sum())
        m["lane_wakes"] += int(woke.size)
        m["wake_peer_lost"] += n_lost
        m["wake_request"] += n_asked
        m["wake_message"] += int(woke.size) - n_lost - n_asked

    def _node_beat(self) -> None:
        """Tick thread, at the end of a timer step with hibernation on:
        the node-level beat (``__init__`` has the rule).  A peer this node
        sent no frame since the last timer step gets an empty one; a peer
        whose frames have not moved the transport's count for
        ``election_ticks`` timer steps in a row is taken as lost, and the
        asleep lanes that follow it are woken at the next step."""
        t = self.transport
        heard = getattr(t, "heard", None)
        if heard is None:       # a transport of someone else's: no beat
            return
        T = self.cfg.election_ticks
        for p in range(self.cfg.n_peers):
            if p == self.node_id:
                continue
            if p not in self._beat_sent:
                t.send_slice(p, self._beat_frame)
                self.metrics["node_beats_sent"] += 1
            n = heard.get(p, 0)
            if n != self._heard_n.get(p):
                self._heard_n[p], self._silent[p] = n, 0
                continue
            silent = self._silent[p] = self._silent.get(p, 0) + 1
            if silent % T == 0:
                lost = np.flatnonzero(self.h_asleep & (self.h_leader == p))
                if lost.size:
                    self._wake_ids = np.union1d(self._wake_ids, lost)
                    self._wake.set()
        self._beat_sent.clear()

    def _guard_i32(self, log_tail: int, term: int) -> None:
        hi_lane = max(log_tail, term, self.timer_ticks)
        if hi_lane >= I32_SAFE_MAX:
            raise OverflowError(
                f"node {self.node_id}: an int32 engine lane reached "
                f"{hi_lane} (>= I32_SAFE_MAX {I32_SAFE_MAX}); a group "
                "needs a snapshot + lane purge before its log index/term "
                "wraps (see core/types.py)")

    def _leadership_lost(self, lanes: np.ndarray) -> None:
        # Leadership lost: abort outstanding client promises BEFORE any
        # apply could complete them with a different command's result at
        # the same index (reference abortPromise on role change,
        # context/RaftContext.java:165-187).  The command may still commit
        # cluster-wide — NotLeader tells the client to re-check, the
        # standard Raft client contract.
        for g in lanes.tolist():
            self.dispatcher.abort_promises(
                g, NotLeaderError(g, self.leader_hint(g)))
            self._reject_submissions(g)
            # Un-served reads fail as RETRY-SAFE refusals (they never
            # entered the log); batches that already passed their barrier
            # (RELEASED) stay — a confirmed ReadIndex remains a valid
            # linearization point under any later leadership.
            self._reject_reads(g)

    def _fold_checkquorum(self, n_down: int, n_veto: int) -> None:
        if n_down:
            self.metrics["checkquorum_stepdowns"] += n_down
        if n_veto:
            self.metrics["lease_vetoes"] += n_veto

    def _mirrors_tail(self, ctx: _TickCtx, h_heat, windows, masks) -> None:
        """What ``_fetch`` does once the mirrors are the step's, whichever
        form they came down in."""
        cfg, st = self.cfg, self._stages
        # -- flight-recorder drain -------------------------------------------
        # Opt-in with the recorder itself: decoded events feed per-group
        # timelines (HTTP /timeline) and the labeled metrics aggregate
        # counters cannot express (elections by cause, leader churn).
        # The cheap [G] event-count lane is pulled first; the full rings
        # (and the per-moved-group decode) transfer only on ticks where
        # something actually recorded — a quiet node pays one [G] pull.
        # The drain scales with groups-moved per tick, like every other
        # host-side per-group path here.
        if cfg.trace_depth:
            h_trn = jax.device_get(self.state.trace.n)
            if self.tracelog.moved(h_trn):
                for k, v in self.tracelog.ingest(
                        jax.device_get(self.state.trace)).items():
                    if v:
                        self.metrics[k] += v

        # -- heat drain ------------------------------------------------------
        # The device heat lanes (cumulative per-group activity) fold into
        # the decaying registry: one numpy delta against the mirror, a
        # counter fold, and the active-set gauge.  Cumulative lanes mean
        # a skipped drain (storage-fault tick) loses nothing.
        if self.heat is not None and h_heat is not None:
            d_app, d_sent, d_com, d_rd = self.heat.ingest(
                self.timer_ticks, h_heat.appended, h_heat.sent,
                h_heat.commits, h_heat.reads)
            m = self.metrics
            if d_app:
                m["heat_appended"] += d_app
            if d_sent:
                m["heat_sent"] += d_sent
            if d_com:
                m["heat_commits"] += d_com
            if d_rd:
                m["heat_reads"] += d_rd
            m.gauge("heat_active_set", self.heat.active_set_size())

        self.ticks += 1
        self.timer_ticks += int(ctx.timer)
        if ctx.timer:
            kicked = ctx.info.read_kick
            if cfg.hibernate and ctx.woke_ids is not None \
                    and ctx.woke_ids.size:
                # A woken leader's first heartbeat is its lane's own
                # traffic, as a read's barrier heartbeat is: the round is
                # the awake lanes' cadence.
                kicked = np.array(kicked)
                kicked[ctx.woke_ids] = True
            self._hb_open(ctx.outbox, ctx.started, kicked)
        n_open, n_led, n_unready, n_lost = self._lane_counts
        leaderless = n_unready + n_lost
        # The leader's windows as the step left them (core/step.py
        # window_sums, reduced on the device).
        win = windows.tolist()
        pairs, occupied, full, cooling, timed_out = win
        m = self.metrics
        m.gauge("groups_active", n_open)
        m.gauge("groups_leaderless", leaderless)
        m.gauge("groups_led", n_led)
        m.gauge("groups_led_unready", n_unready)
        m.gauge("window_slots_occupied", occupied)
        m.gauge("window_pairs_cooling", cooling)
        if timed_out:
            m["window_timeouts"] += timed_out
        st.note(leaderless=leaderless, open=n_open, led=n_led,
                unready=n_unready, win_pairs=pairs,
                win_slots=pairs * cfg.inflight_limit, win_occupied=occupied,
                win_full=full, win_cooling=cooling, win_timeouts=timed_out)
        # Hibernation: open lanes asleep after the step (a timer step's:
        # the gauge and the span say how the period begins), and lanes it
        # woke; 0 and 0 where the field is off.
        woken = 0 if ctx.woke_ids is None else int(ctx.woke_ids.size)
        if ctx.timer:
            m.gauge("lanes_asleep", self._asleep_n)
            st.note(asleep=self._asleep_n, woken=woken)
        elif woken:
            st.note(woken=woken)
        was_unready, self._unready_n = self._unready_n, n_unready
        if n_unready or was_unready:
            self._track_unready(*masks(), ctx.timer, win)

    def _track_unready(self, unready: np.ndarray, was_ready: np.ndarray,
                       timer: bool, win: List[int]) -> None:
        """Tick thread, in a step that finds a led lane unready or follows
        one that did (no other step does ``[G]`` work for this): close the
        episodes of lanes that are ready again or no longer led (their
        length in periods, as seconds of the loop's period, into
        ``lane_unready_s``; a caller that steps the node itself has no
        period and a period counts as a second), open one where a lane
        led READY by the last step is unready now (a fresh leader that
        has not been ready yet opens none: that is an election, and
        ``groups_led_unready`` counts it), and say so once a period while
        an episode has lasted longer than an RPC timeout."""
        since, now = self._unready_since, self.timer_ticks
        over = np.nonzero((since != 0) & ~unready)[0]
        if over.size:
            period_s = self._tick_interval or 1.0
            lengths = self.metrics.histogram("lane_unready_s")
            for periods, n in zip(*np.unique(now - since[over],
                                             return_counts=True)):
                lengths.observe(int(periods) * period_s, int(n))
            since[over] = 0
        since[unready & was_ready] = now
        if not timer:       # the timer's step alone: a line a period
            return
        lanes = np.nonzero(since)[0]
        if lanes.size and now - int(since[lanes].min()) \
                > self.cfg.rpc_timeout_ticks:
            oldest = lanes[np.argsort(since[lanes], kind="stable")[:3]]
            log.warning(
                "node %d tick %d: %d led lane(s) unready, the oldest "
                "(lane, since) %s; windows: %s", self.node_id, now,
                self._unready_n, [(int(g), int(since[g])) for g in oldest],
                dict(zip(WINDOW_SUMS, win)))

    # ---------------------------------------------------- tick: host phase

    def _host_phase(self, ctx: _TickCtx) -> None:
        """One fetched tick's host work, right behind its fetch: WAL
        staging, THE fsync barrier, outbox release (one flush: each peer
        receives ONE slice per tick, as its inbox accumulator drains
        them), applies + future completion, read serving, maintenance.
        Everything that acknowledges the tick, and everything it sends,
        runs here, strictly after its barrier.

        Only the persist step varies (``_persist``: the native WAL
        engine's one call, or the Python engine's stage and barrier);
        every stage boundary is entered here, once.

        Storage faults surface here: a failed durability barrier
        (WalSyncError / WalNoSpace from the store) aborts the rest of
        the phase — nothing past the barrier (sends, future
        completions, reads) runs for this tick — and feeds the
        failure-response policy in ``_storage_fault``.  ``pre_tail``
        is the durable-tail mirror BEFORE any staging, at the lanes the
        staging can move (every lane, or the step's ``ids``: spans,
        truncations and floors are selected among them), so the policy
        knows exactly which per-group tails a failed barrier left
        unconfirmed.

        Every stage selects its work among ``ids`` (``_host_lanes``: the
        rows that moved and the lanes the step offered on; None: among
        all lanes, the same expressions over whole planes)."""
        ids = self._host_lanes(ctx)
        pre_tail = (ids, self._durable_tail_m.copy() if ids is None
                    else self._durable_tail_m[ids])
        G = self.cfg.n_groups
        st = self._stages
        m = self.metrics
        try:
            try:
                # -- 4. persistence barrier ----------------------------------
                # One span, raft.wal, under the native engine (its one
                # call stages AND fsyncs); raft.wal then raft.fsync under
                # the Python one.  Either way the two histograms split
                # the time up to the send boundary by the fsync's own
                # seconds (observe=False).
                _t0 = st.enter("wal", observe=False)
                prep, fsync_s, blob_fn = self._persist(ctx, ids)
                self._fsync_waited += fsync_s
                self._watch_io(fsync_s)
                if self._lat_tick:
                    # The Python step stamped STAGED before its barrier
                    # (a stamp is first-wins); the native call's stage
                    # and fsync resolve together, here.
                    self._lat_stamp(STAGED)
                    self._lat_stamp(FSYNCED)
                if self._hops is not None:
                    # The fsynced stamp sits strictly after _barrier_ok():
                    # a storage-fault abort above means an unsynced tail
                    # never produces a durability echo.
                    self._hops.fold_foreign(self._durable_tail_m,
                                            fsynced=True)
                self._sweep_rejections(prep)
                self._hops_scan(ctx)
                # The arena views the spans pinned are staged: drop the
                # frame pins early.
                ctx.staged_payloads = ctx.arrays = None
                _t1 = st.enter("send")
                m.observe("tick_stage_wal_s",
                          max(0.0, (_t1 - _t0) - fsync_s))
                m.observe("tick_stage_fsync_s", fsync_s)

                # -- 5. release outbox (only ever after the barrier) ---------
                held, sent = self._stash_outbox_sections(
                    ctx.outbox, blob_fn=blob_fn)
                for p, secs in held.items():
                    self._held_sections.setdefault(p, []).extend(secs)
                self._flush_sends()
                st.note(lanes=sent)
                st.enter("apply")
                if self._lat_tick:
                    self._lat_stamp(SENT)

                # -- 6. applies ----------------------------------------------
                if self._lat is not None:
                    # Commit stamps strictly precede apply/ack stamps:
                    # advance() completes promises (and the traced
                    # batch's ack) below.
                    self._lat.mark_committed(ctx.commit)
                # The lanes whose commit moved are among the ids; a lane
                # that an earlier advance() left behind is in its backlog.
                disp = self.dispatcher
                lanes = ids
                if ids is not None and len(disp.backlog):
                    lanes = np.union1d(ids, disp.backlog)
                self._scanned += G if lanes is None else len(lanes)
                done = disp.applied_total
                st.note(lanes=disp.advance(ctx.commit, lanes=lanes))
                m["applies"] += disp.applied_total - done
                applied = disp.applied_view(G)
                if ids is None:
                    self._scanned += G
                    self._commit_sum = int(ctx.commit.sum(dtype=np.int64))
                else:
                    self._commit_sum += ctx.rows.commit_delta
                m["commits"] = self._commit_sum
                # (The rejection sweep's pass, made behind the barrier,
                # is counted here too.)
                self._note_scanned()
                st.enter("reads")

                # -- 6b. read plane: stamped/released bookkeeping + serving --
                st.note(lanes=self._harvest_reads(ctx.info, ids,
                                                  ctx.woke_ids,
                                                  arrival=not ctx.timer)
                        + self._serve_reads(applied, ids))
                self._note_scanned()
                st.enter("maintain")

                # -- 7. maintain: checkpoints, compaction, snapshot downloads
                self._maintain(applied, ctx.base, ctx.term, ctx.timer,
                               ids, keep=ctx.rows is not None)
                self._snapshot_requests(ctx.info, ctx.base, ids)
                self._note_scanned()
                # The stages are observed at their boundaries; whichever
                # phase follows (scan_device, tail, dispatch_intake) ends
                # maintain.
                # Empty-payload short-circuits (machine/spi.py
                # applies_empty opt-in), published behind the apply
                # phase that counts them: nonzero here explains a
                # last_applied that lags the commit frontier without
                # digging through warn-once logs.
                skips = self.dispatcher.empty_skips
                if skips:
                    m.gauge("empty_apply_skips", skips)
                # The phase ran to its end: what it keeps for the next
                # one is good (_host_lanes), on a node that keeps it.
                self._host_sets_ok = ctx.rows is not None
            except (WalNoSpace, WalSyncError) as e:
                self._storage_fault(e, *pre_tail)
        finally:
            # A column step's planes are cleared even on failure: the next
            # step must find them zero.
            if ctx.rows is not None:
                self._rows_done(ctx)

    def _host_lanes(self, ctx: _TickCtx) -> Optional[np.ndarray]:
        """The lanes this step's host phase looks at, ascending, or None
        for every lane.  THE rule of the phase, stated once: **a step
        whose Readback came down as rows is worked from the rows that
        moved, united with the lanes the step itself offered on, if the
        last host phase ran to its end and nothing has moved the host's
        planes behind the phases' backs since; every other step is
        worked whole and rebuilds what the phases keep.**

        Why the rows are enough: every event field of ``ctx.info`` is
        zero outside them and every level differs from what the last
        phase saw only there (_mirrors_rows), a refused offer sits on a
        lane the step offered on, and what needs work on a lane whose
        row did not move the phases keep as sets, each patched at the
        ids: the apply backlog (machine/dispatch.py ``backlog``), the
        released reads (``_reads_released``), the rings under pressure
        and every open ring's fill (``_ring_state``), the commit sum.
        A WAL floor still to push cannot hide outside the rows either:
        a phase that ran to its end left ``h_base <= _wal_floor`` on
        every lane.

        What makes the next phase run whole (``_host_sets_ok`` False;
        the planes themselves are good, only the sets are not): a phase
        that did not reach its end (a failed barrier and everything
        ``_storage_fault`` does about it, any other exception), a
        lifecycle change (``h_active``, a purge, the dispatcher's
        mirror), an installed snapshot (durable tail, floor and apply
        frontier move at dispatch), a node's first phase.  While the
        confirmed-tail clamp stands (``_acked_tail``: a barrier failed
        and has not been made good, or a stripe is quarantined) every
        phase runs whole."""
        step = ctx.rows
        ok, self._host_sets_ok = self._host_sets_ok, False
        if step is None or step.down_ids is None or not ok \
                or self._acked_tail is not None:
            return None
        return np.unique(np.concatenate(
            (step.down_ids, step.sub_ids, step.read_ids, ctx.wake_ids)))

    def _where(self, lanes: Optional[np.ndarray],
               mask: np.ndarray) -> np.ndarray:
        """One selection pass of the host phase: the lanes whose ``mask``
        holds, ascending, where ``mask`` was taken over ``lanes`` (None:
        over every lane)."""
        self._scanned += len(mask)
        hit = np.flatnonzero(mask)
        return hit if lanes is None else lanes[hit]

    def _note_scanned(self) -> None:
        """A host stage is done: the lanes its selection passes ran over,
        on its span and on /metrics."""
        n, self._scanned = self._scanned, 0
        self.metrics["host_lanes_scanned"] += n
        self._stages.note(scanned=n)

    def _rows_done(self, ctx: _TickCtx) -> None:
        """A column step's host phase is over: clear what the step wrote
        into the node's persistent planes at the lanes it wrote (its
        offers; the events of the rows that came down), so that the next
        step finds zero planes."""
        step = ctx.rows
        ctx.submit_n[step.sub_ids] = 0
        ctx.read_n[step.read_ids] = 0
        self._up_planes.append(step.up)
        if step.down_ids is not None:
            rl = ctx.columns.rows_out
            words, flags = self._back_planes
            words[rl.Lw:, step.down_ids] = 0
            flags[rl.Lf:, step.down_ids] = False

    def _lat_stamp(self, phase: int) -> None:
        """Stamp one lifecycle phase on every span the device accepted
        this tick (populated by _persist_prepare's submission pop; tick /
        host-phase thread only).  One is-None-cheap loop over at most a
        handful of sampled spans."""
        for sp in self._lat_tick:
            sp.mark(phase)

    def _hops_scan(self, ctx: _TickCtx) -> None:
        """Detect which peers' AE frames this tick cover a tracked
        sampled span and queue hop requests for them; the records ride
        the next per-peer flush.  Must run AFTER _persist_prepare (which
        registers this tick's spans) — the AE frame carrying a freshly
        appended entry is in THIS tick's outbox, and once followers ack
        it no later frame ever covers that index again.  O(tracked
        spans); a node with no live spans pays one attribute check."""
        if self._hops is not None:
            out = ctx.outbox
            self._hops.scan_outbox(out.dense("ae_valid"),
                                   out.dense("ae_prev_idx"),
                                   out.dense("ae_n"))

    def _persist(self, ctx: _TickCtx, ids: Optional[np.ndarray] = None
                 ) -> Tuple[_PersistPrep, float, Optional[Callable]]:
        """The host phase's one varying step: make the tick's writes
        durable.  Returns the tick's persist plan, the seconds the fsync
        took, and the outbox pack's ``payload_blob_fn`` (None: the
        codec's own).  When it returns, the barrier has completed
        (``_barrier_ok``); a failed one raises out of the host phase.

        The native WAL engine takes the tick in ONE call — arena
        staging, per-shard fsync on real OS threads with the GIL
        released — when the store has it, no stripe is quarantined (a
        poisoned stripe's fsync must never be retried: ``_barrier``
        carves it out) and the tick carries no membership-config entry
        (the conf sidecar is one global document, written from Python;
        ``_persist_prepare`` says so before it mutates anything).
        Otherwise the Python engine stages on the tick thread and
        ``_barrier`` fsyncs.  Segment bytes, record order and the
        ack-after-fsync barrier are the same either way."""
        prep = None
        if self._native_wal and not self._poisoned_stripes:
            prep = self._persist_prepare(ctx, ids, for_stripes=True)
        if prep is not None:
            self._stages.note(lanes=prep.lanes)
            _stage_s, fsync_s = self._persist_stage_native(prep)
            self._note_scanned()
            # The conf sidecar (dirty only when an adoption span
            # truncated recorded conf entries) flushes before any ack
            # leaves.
            self.store.conf_flush()
            self._barrier_ok()
            return prep, fsync_s, self._native_blob_fn
        prep = self._persist_prepare(ctx, ids)
        self._stages.note(lanes=prep.lanes)
        # NOTE: staging is NOT masked while stripes are quarantined — a
        # poisoned engine only buffers (its flush/fsync never run again),
        # and skipping span-build would drop device-accepted sinks before
        # they register as promises (hung futures).  The carve-out happens
        # at the barrier (_barrier) and at outbox packing (silence).
        need_sync = self._persist_stage(prep)
        self._note_scanned()
        _t1 = self._stages.enter("fsync", observe=False)
        if self._lat_tick:
            self._lat_stamp(STAGED)
        if self._hops is not None:
            self._hops.fold_foreign(self._durable_tail_m, fsynced=False)
        if need_sync or self._sync_pending:
            self._barrier()     # THE durability barrier
            self._barrier_ok()
        return prep, time.perf_counter() - _t1, None

    def _native_blob_fn(self, cols, starts, ns):
        """codec ``payload_blob_fn``: native AE blob pack (None → the
        codec's Python per-column loop)."""
        return self.store.pack_ae_blob(cols, starts, ns,
                                       workers=self.host_workers)

    # ------------------------------------------------- storage-fault policy

    def _barrier(self) -> None:
        """THE durability barrier with quarantined stripes carved out:
        a poisoned stripe is fail-stop — its fsync is NEVER retried on
        the same fd (the page cache may have dropped the dirty pages
        that failed to reach the device, so a later "clean" return
        would be a lie — the PostgreSQL fsyncgate lesson).  The conf
        sidecar and every healthy stripe still barrier normally."""
        if not self._poisoned_stripes:
            self.store.sync()
            return
        cf = getattr(self.store, "conf_flush", None)
        if cf is not None:
            cf()
        healthy = [s for s in range(self._n_stripes)
                   if s not in self._poisoned_stripes]
        if healthy and hasattr(self.store, "sync_stripes"):
            self.store.sync_stripes(healthy)

    def _barrier_ok(self) -> None:
        """A durability barrier completed: everything staged on healthy
        stripes is now on disk.  Clear the retry/backpressure state and
        advance the device-feed clamp for healthy groups (quarantined
        groups stay frozen at their last confirmed tail forever)."""
        self._sync_pending = False
        if self._io_backpressure:
            self._io_backpressure = False
            self.metrics.gauge("io_backpressure", 0)
            log.warning("node %d: WAL barrier recovered — admission "
                        "backpressure released", self.node_id)
        if self._acked_tail is None:
            return
        if self._healthy_groups is None:
            self._acked_tail = None   # fully clean: back to the fast path
        else:
            np.copyto(self._acked_tail, self._durable_tail_m,
                      where=self._healthy_groups)

    def _watch_io(self, fsync_s: float) -> None:
        """Slow-I/O watchdog: a barrier that completes but takes longer
        than RAFT_SLOW_IO_S is a gray failure — surfaced on /metrics
        (slow_io_ticks, io_slow) and /healthz, never acted on
        automatically (a slow disk is not a broken disk)."""
        if fsync_s > self._slow_io_s:
            self.metrics["slow_io_ticks"] += 1
            if not self._io_slow:
                self._io_slow = True
                self.metrics.gauge("io_slow", 1)
                log.warning("node %d: slow storage — fsync barrier took "
                            "%.3fs (threshold %.3fs)", self.node_id,
                            fsync_s, self._slow_io_s)
        elif self._io_slow:
            self._io_slow = False
            self.metrics.gauge("io_slow", 0)

    def _storage_fault(self, exc: Exception, lanes: Optional[np.ndarray],
                       pre_tail: np.ndarray) -> None:
        """Failure-response policy for a failed durability barrier —
        the principled taxonomy the storage nemesis exercises:

        * ``WalNoSpace`` (ENOSPC): RETRIABLE.  Engines rewound their
          segments and kept their staged buffers; engage admission
          backpressure (new submissions refuse with BusyLoop) and force
          the next tick's barrier to retry the flush.  The tick loop
          never wedges.
        * ``WalSyncError`` with poisoned shards (fsync failure, torn
          write): FAIL-STOP for those stripes.  Quarantine their groups
          — fail in-flight futures, go silent so peers re-elect.
        * ``WalSyncError`` with no shards (conf-sidecar flush):
          transient; skip the tick and retry at the next barrier.

        In every case the rest of this tick's host phase was aborted —
        nothing past the failed barrier (sends, future completions,
        read serving) ran, preserving ack-after-fsync — and the
        device-feed clamp ``_acked_tail`` pins the affected groups at
        ``pre_tail`` (the durable tails before the phase staged
        anything, at ``lanes``: every lane for None, else the lanes the
        phase could move a tail on) so the scan can never self-ack a
        staged-but-unsynced range into a commit."""
        G = self.cfg.n_groups
        poisoned = set(getattr(exc, "shards", ()) or ())
        nospace = set(getattr(exc, "nospace", ()) or ())
        if isinstance(exc, WalNoSpace):
            nospace |= poisoned
            poisoned = set()
        if poisoned or nospace:
            unconfirmed = np.isin(self._stripe_of,
                                  sorted(poisoned | nospace))
        else:
            # Global transient (conf flush precedes the shard fsyncs in
            # store.sync): conservatively treat every group's staged
            # records as unconfirmed until the retried barrier lands.
            unconfirmed = np.ones(G, bool)
        if self._acked_tail is None:
            self._acked_tail = self._durable_tail_m.copy()
        at = slice(None) if lanes is None else lanes
        held = self._acked_tail[at]
        self._acked_tail[at] = np.where(
            unconfirmed[at], np.minimum(held, pre_tail), held)
        self._sync_pending = True
        if nospace:
            if not self._io_backpressure:
                self._io_backpressure = True
                self.metrics.gauge("io_backpressure", 1)
            self.metrics["enospc_backpressure"] += 1
            log.error("node %d: WAL out of disk space — admission "
                      "backpressure engaged, barrier will retry: %s",
                      self.node_id, exc)
        new = poisoned - self._poisoned_stripes
        if new:
            self._quarantine_stripes(new, exc)
        elif not poisoned and not nospace:
            self.metrics["storage_transient_errors"] += 1
            log.error("node %d: durability barrier failed (transient, "
                      "retried next tick): %s", self.node_id, exc)

    def _quarantine_stripes(self, shards, cause: Exception) -> None:
        """Fail-stop quarantine: the groups on ``shards`` go SILENT —
        in-flight futures fail with StorageFaultError, their lanes
        deactivate (next dispatch), and no frame for them ever leaves
        again (outbox packing masks them) — so a healthy replica takes
        over at the peers' next election timeout.  Deliberately NO
        TimeoutNow/transfer: any further send for these groups could
        carry a staged-but-unsynced range that followers would ack into
        a commit this node cannot durably back (see PARITY.md).

        Queued-but-unoffered submissions and reads are failed by the
        lifecycle sweep when the deactivation applies (a direct reject
        here could race an already-dispatched tick's accept accounting);
        new arrivals are refused immediately via ``_refusal``."""
        self._poisoned_stripes |= set(shards)
        self.metrics["fsync_failures"] += len(shards)
        self.metrics.gauge("stripes_poisoned", len(self._poisoned_stripes))
        self._healthy_groups = ~np.isin(self._stripe_of,
                                        sorted(self._poisoned_stripes))
        bad = np.nonzero(~self._healthy_groups & self.h_active)[0]
        log.error("node %d: WAL stripe(s) %s fail-stop after durability "
                  "failure (%s) — quarantining %d group(s); lanes go "
                  "silent, peers re-elect", self.node_id,
                  sorted(shards), cause, len(bad))
        for g in bad.tolist():
            g = int(g)
            self.dispatcher.abort_promises(g, StorageFaultError(
                f"group {g}: WAL stripe quarantined after a durability "
                f"failure ({cause}); outcome unknown — the entry may "
                f"already be replicated"))
            self.set_active(g, False)

    # ---------------------------------------------------------- persistence

    def _persist_prepare(self, ctx: _TickCtx,
                         ids: Optional[np.ndarray] = None,
                         for_stripes: bool = False
                         ) -> Optional[_PersistPrep]:
        """Precompute one tick's persist inputs — the change-detected
        lane lists (selected among ``ids``; None: among all lanes), the
        staged-frame metadata fancy-indexes, and the ONE lock'd
        submission-queue pop — for ``_persist_stage`` or
        ``_persist_stage_native`` to consume.  Every list is ascending,
        whichever lanes it was selected among: the WAL's per-shard record
        order follows them.

        ``for_stripes=True`` (the plan is for the native engine, whose
        threads each own whole stripes) bails out (returns None) when
        the tick carries membership-config entries — leader conf appends
        or adopted conf words: the conf sidecar is one global doc and
        conf traffic is rare, so those ticks take the Python step
        instead.  The bail happens BEFORE any mutation (in particular
        before the submission pop): the caller re-runs prepare, and a
        double pop would desynchronize the durable log from the promise
        map."""
        info, submit_n = ctx.info, ctx.submit_n
        h_term, h_voted, h_leader = ctx.term, ctx.voted, ctx.leader
        h_base, h_base_term = ctx.base, ctx.base_term
        staged_payloads, inbox_arrays = ctx.staged_payloads, ctx.arrays
        app_from = np.asarray(info.appended_from)
        app_to = np.asarray(info.appended_to)
        sub_start = np.asarray(info.submit_start)
        sub_acc = np.asarray(info.submit_acc)
        wrote = self._where(ids, _at(app_to, ids) > 0)
        conf_app = np.asarray(info.conf_app_idx)
        conf_g = self._where(ids, _at(conf_app, ids) > 0)
        if for_stripes and len(conf_g):
            return None
        wrote_l = wrote.tolist()
        # Staged-frame metadata for the whole wrote set in three fancy
        # indexes (the per-group [src, g] scalar reads were ~3 numpy
        # scalar indexings per adopting group).
        if inbox_arrays is not None and len(wrote):
            # Rows of the wrote set alone, whichever form the inbox
            # crossed in: [len(wrote), ...] copies, indexed like wrote_l.
            src_clip = np.maximum(h_leader[wrote], 0)
            at = inbox_arrays.at
            fr_valid = (at("ae_valid", src_clip, wrote)
                        & (h_leader[wrote] >= 0)).tolist()
            fr_n = at("ae_n", src_clip, wrote).tolist()
            fr_start = (at("ae_prev_idx", src_clip, wrote) + 1).tolist()
            fr_ents = at("ae_ents", src_clip, wrote)
            fr_cents = at("ae_cents", src_clip, wrote)
            if for_stripes and bool(fr_cents.any()):
                # Adopted config words: put_conf is the Python step's.
                return None
        else:
            fr_valid = [False] * len(wrote_l)
            fr_n = [0] * len(wrote_l)
            fr_start = [0] * len(wrote_l)
            fr_ents = None
            fr_cents = None

        p = _PersistPrep()
        p.ids = ids
        p.dirty = dirty = np.asarray(info.dirty)
        p.log_tail = np.asarray(info.log_tail)
        p.h_term, p.h_voted = h_term, h_voted
        p.h_base, p.h_base_term = h_base, h_base_term
        p.submit_n, p.sub_acc = submit_n, sub_acc
        p.staged_payloads = staged_payloads
        p.wrote, p.wrote_l = wrote, wrote_l
        # Row extraction as plain lists: the staging loop runs once per
        # written group (~100k/tick at scale) and a numpy scalar index +
        # int() costs ~3x a list index.
        p.lo_l = app_from[wrote].tolist()
        p.hi_l = app_to[wrote].tolist()
        p.nsub_l = sub_acc[wrote].tolist()
        p.sublo_l = sub_start[wrote].tolist()
        p.src_l = h_leader[wrote].tolist()
        p.term_l = h_term[wrote].tolist()
        p.fr_valid, p.fr_n, p.fr_start = fr_valid, fr_n, fr_start
        p.fr_ents, p.fr_cents = fr_ents, fr_cents
        # (term, ballot) change detection (reference RaftMember ctor
        # persists first, context/member/RaftMember.java:25) — the store
        # writes + mirror updates happen in the stage.
        p.stable_g = self._where(ids, _at(dirty, ids) & (
            (_at(h_term, ids) != _at(self._stable_term_m, ids))
            | (_at(h_voted, ids) != _at(self._stable_voted_m, ids))))
        noop_arr = np.asarray(info.noop_idx)
        p.noop_idx = noop_arr
        p.noop_term = np.asarray(info.noop_term)
        p.noop_g = self._where(ids, _at(noop_arr, ids) > 0).tolist()
        # Lanes the persist step visits one by one in Python: a span per
        # written lane and per election no-op, a stable record per lane
        # whose (term, ballot) moved.
        p.lanes = len(wrote_l) + len(p.noop_g) + len(p.stable_g)
        p.conf_g = conf_g.tolist()
        p.conf_app = conf_app
        p.conf_term = np.asarray(info.conf_app_term)
        p.conf_word = np.asarray(info.conf_app_word)
        # Pop every accepting group's accepted prefix under ONE lock;
        # promise-range registration happens in the stage, outside it.
        own_by_g: Dict[int, List[tuple]] = {}
        sub_groups = wrote[sub_acc[wrote] > 0]
        tr = self._lat
        lat_tick = self._lat_tick
        lat_tick.clear()
        if len(sub_groups) or self._queued_total > 0:
            # Sojourn clock for the admission controller: device-accept
            # time minus the OLDEST queued batch's enqueue time — the
            # tick's max queue delay, one sample per tick (consumed by
            # tick()'s note_delay feed).
            adm_now = time.monotonic()
            adm_oldest = None
            adm = self.admission
            adm_expire = adm.expire_age() if adm.enabled else None
            expired = []
            with self._submit_lock:
                for g in sub_groups.tolist():
                    acc_n = int(sub_acc[g])
                    q = self._submissions.get(g)
                    cursor = int(sub_start[g])
                    need = acc_n
                    taken_spans = own_by_g[g] = []
                    while need > 0:
                        # The device never accepts more than submit_n
                        # (== queue depth at inbox build); an empty queue
                        # here means the durable log and the promise map
                        # would silently desynchronize.
                        assert q, (f"g={g}: device accepted {acc_n} "
                                   "submissions beyond the queued depth")
                        b = q[0]
                        avail = len(b.run) - b.taken
                        take = min(avail, need)
                        taken_spans.append((cursor, b, b.taken, take))
                        if tr is not None:
                            sp = b.sink.span
                            if sp is not None and sp.outcome is None \
                                    and b.taken <= sp.k < b.taken + take:
                                # Device accepted the traced entry: pin
                                # its (group, log index) and queue it
                                # for this tick's durability stamps and
                                # the cross-tick commit watch.
                                sp.group = g
                                sp.idx = cursor + (sp.k - b.taken)
                                sp.tick = self.ticks
                                sp.mark(OFFERED)
                                lat_tick.append(sp)
                                tr.pending_commit.append(sp)
                                if self._hops is not None:
                                    # Hop attribution follows the span:
                                    # its (group, idx) is pinned now, so
                                    # the fetch-side coverage scan can
                                    # match AE frames to it.
                                    self._hops.track(sp)
                        b.taken += take
                        cursor += take
                        need -= take
                        if b.taken == len(b.run):
                            q.popleft()
                    self._queued_n[g] -= acc_n
                    self._queued_total -= acc_n
                # Sojourn sample + late shed over EVERY non-empty queue
                # — not just groups the device accepted from this tick.
                # A group whose device log is momentarily full accepts
                # nothing for a few ticks; its queue must neither rot
                # invisibly (delay sample) nor past the age cap (late
                # shed, CoDel's queue drop: the backlog admitted before
                # the controller engaged would otherwise be served long
                # past any client deadline).  The oldest entries sit at
                # the HEAD while the queue is still FIFO (pre-engage
                # transient) and at the TAIL once LIFO kicks in, so
                # check both ends.  Only untouched batches (taken == 0)
                # are expirable; never entries the device accepted.
                for g, q in self._submissions.items():
                    if not q:
                        continue
                    t0 = min(q[0].t_enq, q[-1].t_enq)
                    if adm_oldest is None or t0 < adm_oldest:
                        adm_oldest = t0
                    if adm_expire is not None:
                        while q and q[0].taken == 0 \
                                and adm_now - q[0].t_enq > adm_expire:
                            self._expire_batch(g, q.popleft(), expired)
                        while q and q[-1].taken == 0 \
                                and adm_now - q[-1].t_enq > adm_expire:
                            self._expire_batch(g, q.pop(), expired)
            if adm_oldest is not None:
                self._adm_delay = adm_now - adm_oldest
            # Fail expired sinks OUTSIDE the submit lock: future done-
            # callbacks run inline and must not execute under our lock.
            for g, sink in expired:
                sink._fail(as_refusal(OverloadError(
                    f"group {g}: shed from queue after exceeding the "
                    "overload age cap",
                    retry_after_s=adm.retry_after())))
        p.own_by_g = own_by_g
        return p

    def _expire_batch(self, g: int, b: "_SubBatch", out: list) -> None:
        """Unlink one never-accepted batch from the queue accounting
        (submit lock held; the sink fails after the lock drops)."""
        nb = len(b.run)
        self._queued_n[g] -= nb
        self._queued_total -= nb
        self.admission.expired += nb
        out.append((g, b.sink))

    def _stage_stable(self, prep: _PersistPrep) -> bool:
        """Stage the tick's (term, ballot) stable records (durable
        before any reply leaves) as ONE batch of moved lanes (steady
        state: an empty call) and refresh the stable mirrors.  Returns
        whether anything was staged.  Shared by ``_persist_stage`` and
        ``_persist_stage_native`` (stable records are Python-staged into
        the engine buffers ahead of the native call — the per-shard
        record order stays stable → entries → truncates → milestones,
        the same bytes either way)."""
        moved = prep.stable_g
        h_term, h_voted = prep.h_term, prep.h_voted
        if not len(moved):
            return False
        put_batch = getattr(self.store, "put_stable_batch", None)
        if put_batch is not None:
            put_batch(moved.tolist(), h_term[moved].tolist(),
                      h_voted[moved].tolist())
        else:
            for g in moved.tolist():
                self.store.put_stable(g, int(h_term[g]), int(h_voted[g]))
        self._stable_term_m[moved] = h_term[moved]
        self._stable_voted_m[moved] = h_voted[moved]
        return True

    def _build_spans(self, prep: _PersistPrep) -> List[tuple]:
        """Build the tick's arena spans — ``(g, start, piece, lens,
        terms)`` — plus the promise-range registrations and membership
        sidecar records that travel with them.  Pure assembly: no WAL
        write happens here, so the Python staging path and the native
        columnar handoff consume identical spans."""
        # Entries appended/overwritten this tick land as contiguous
        # arena SPANS — crossing into the WAL engine once per stage with
        # numpy vectors (VERDICT r4 #2: the per-entry Python staging
        # loops here were the durable tier's scaling wall).  Adoption
        # spans slice the wire frame's arena directly; own-submission
        # spans slice the client-built batch arenas.
        spans: List[tuple] = []   # (g, start_idx, piece, lens_u32, terms_i64)
        # Election-win no-ops (Raft §8, engine phase 3): staged FIRST —
        # a no-op's index precedes any same-tick submission range, and
        # WAL replay order must match index order (an append drops the
        # suffix at >= its index).
        for g in prep.noop_g:
            spans.append((g, int(prep.noop_idx[g]), b"",
                          _NOOP_LENS, int(prep.noop_term[g])))
        reg_range = self.dispatcher.register_promise_range
        staged_payloads = prep.staged_payloads
        own_by_g = prep.own_by_g
        wrote_l, lo_l, hi_l = prep.wrote_l, prep.lo_l, prep.hi_l
        nsub_l, sublo_l = prep.nsub_l, prep.sublo_l
        src_l, term_l = prep.src_l, prep.term_l
        fr_valid, fr_n, fr_start = prep.fr_valid, prep.fr_n, prep.fr_start
        fr_ents, fr_cents = prep.fr_ents, prep.fr_cents
        put_conf = getattr(self.store, "put_conf", None)
        conf_overwrite = getattr(self.store, "conf_overwrite", None)
        for j, g in enumerate(wrote_l):
            lo, hi = lo_l[j], hi_l[j]
            n_sub = nsub_l[j]
            sub_lo = sublo_l[j]
            leader_src = src_l[j]
            # The written range splits into a follower-adoption prefix and
            # an own-submission suffix (in practice a tick has one or the
            # other: adoption needs a non-leader at phase 4, submission a
            # leader at phase 8).
            adopt_hi = min(hi, sub_lo - 1) if n_sub else hi
            gap = False
            if adopt_hi >= lo:
                # Follower adoption: ONE arena slice per group from the
                # leader's frame (payload run + term vector travel in the
                # same frame, so their coverage agrees; both are still
                # bounds-checked).  A partially covered range stages the
                # covered prefix — the durable prefix stays contiguous and
                # the leader's resend re-delivers the rest (same loss
                # semantics as the reference's rejected AE).
                run = staged_payloads.get((leader_src, g)) \
                    if leader_src >= 0 else None
                end_cov = lo - 1
                if run is not None and fr_valid[j] and fr_n[j] > 0 \
                        and lo >= run.start and lo >= fr_start[j]:
                    end_cov = min(adopt_hi, run.end,
                                  fr_start[j] + fr_n[j] - 1)
                if end_cov >= lo:
                    k = lo - run.start
                    cnt = end_cov - lo + 1
                    koff = lo - fr_start[j]
                    terms = fr_ents[j, koff:koff + cnt]
                    spans.append((g, lo, run.piece(k, cnt),
                                  run.lens[k:k + cnt], terms))
                    # The membership sidecar mirrors the WAL's overwrite
                    # semantics: an adoption span at `lo` kills every
                    # durable entry at >= lo (a conflicting AE can
                    # overwrite a recorded config entry with ORDINARY
                    # entries — the sidecar record must die with it, or
                    # recovery resurrects a dead voter set), then the
                    # span's own config entries (nonzero conf words in
                    # the frame) are re-recorded for the durable range.
                    if conf_overwrite is not None:
                        conf_overwrite(g, lo)
                    if put_conf is not None and fr_cents is not None:
                        cw = fr_cents[j, koff:koff + cnt]
                        if cw.any():
                            for kk in np.nonzero(cw)[0].tolist():
                                put_conf(g, lo + kk, int(cw[kk]))
                gap = end_cov < adopt_hi
            if n_sub and not gap and hi >= sub_lo:
                # Own accepted submissions, all at our term: slice the
                # client-built arenas; register each span as ONE promise
                # range (the per-entry Future registration was ~10% of
                # the durable tick).
                term_g = term_l[j]
                for start_idx, b, k0, take in own_by_g.get(g, ()):
                    reg_range(g, start_idx, take, b.sink, k0)
                    spans.append((g, start_idx, b.run.piece(k0, take),
                                  b.run.lens[k0:k0 + take], term_g))
            elif n_sub:
                # Adoption gap ahead of a same-tick submission range:
                # unreachable by kernel phase order, asserted like the
                # queue-depth invariant above.  Reaching here
                # needs one tick to BOTH adopt follower entries (phase 4,
                # gated role != LEADER after the phase-3 election update)
                # AND accept own submissions (phase 8, requires LEADER) —
                # and the only promotions between those phases (phase 7
                # timers) stop at CANDIDATE.  Were it ever reached,
                # registering promises without staging payloads would
                # leave the accepted entries durable nowhere: pack_slice
                # drops their AE columns forever and the group wedges
                # with hung futures — fail loudly instead.
                raise AssertionError(
                    f"g={g}: adoption gap [{lo}, {adopt_hi}] ahead of "
                    f"device-accepted own submissions at {sub_lo} — "
                    "kernel phase order makes adopt+accept in one tick "
                    "impossible")
        # Config entries this node appended as leader (§6 intake accept or
        # the automatic joint leave): staged durably with an EMPTY payload
        # like the §8 no-op — appended AFTER the per-group spans above, so
        # WAL replay order matches index order (a conf entry's index is
        # the tick's highest) — plus the sidecar record recovery rebuilds
        # the conf ring from.  The Python step only: prepare keeps
        # conf-bearing ticks from the native engine.
        if prep.conf_g:
            conf_app, conf_term = prep.conf_app, prep.conf_term
            conf_word = prep.conf_word
            for g in prep.conf_g:
                spans.append((g, int(conf_app[g]), b"",
                              _NOOP_LENS, int(conf_term[g])))
                if put_conf is not None:
                    put_conf(g, int(conf_app[g]), int(conf_word[g]))
        return spans

    def _persist_stage(self, prep: _PersistPrep) -> bool:
        """The Python step's staging: the tick's durable writes
        (entries, stable records, truncations, floors) into the WAL.
        Returns whether they need an fsync — the caller issues the
        barrier (``_barrier``) and must not release the outbox or
        complete futures before it.  Truncations alone do NOT request a
        sync (a shrink is re-derived at recovery)."""
        any_write = self._stage_stable(prep)
        spans = self._build_spans(prep)
        if spans:
            append_spans = getattr(self.store, "append_spans", None)
            if append_spans is not None:
                append_spans(spans)
            else:
                # LogStoreSPI compat: a store without the arena fast path
                # gets per-entry materialized lists (the old contract).
                bat_g: List[int] = []
                bat_i: List[int] = []
                bat_t: List[int] = []
                bat_p: List[bytes] = []
                for g, start_idx, piece, lens, terms in spans:
                    mv = memoryview(piece)
                    off = 0
                    scalar_term = isinstance(terms, int)
                    for k, ln in enumerate(lens.tolist()):
                        bat_g.append(g)
                        bat_i.append(start_idx + k)
                        bat_t.append(terms if scalar_term else int(terms[k]))
                        bat_p.append(bytes(mv[off:off + ln]))
                        off += ln
                self.store.append_batch(bat_g, bat_i, bat_t, bat_p)
            for g, start_idx, piece, lens, _terms in spans:
                tail_new = start_idx + len(lens) - 1
                if tail_new > self._durable_tail_m[g]:
                    self._durable_tail_m[g] = tail_new
            any_write = True

        # Truncations: durable tail must not exceed the device tail.
        # Change-detected via the durable-tail mirror (shrinks happen only
        # on conflict/snapshot discard — rare).
        ids = prep.ids
        shrunk = self._where(ids, _at(prep.dirty, ids) & (
            _at(self._durable_tail_m, ids) > _at(prep.log_tail, ids)))
        for g in shrunk.tolist():
            self.store.truncate_to(g, int(prep.log_tail[g]))
            self._durable_tail_m[g] = prep.log_tail[g]

        # WAL floor follows the device compaction floor; the pushed-floor
        # mirror keeps this loop over only the groups that moved.
        h_base, h_base_term = prep.h_base, prep.h_base_term
        floors = self._where(ids, _at(h_base, ids)
                             > _at(self._wal_floor, ids))
        wal_floors_moved = False
        for g in floors.tolist():
            self.store.set_floor(g, int(h_base[g]), int(h_base_term[g]))
            self._wal_floor[g] = h_base[g]
            if h_base[g] > self._durable_tail_m[g]:
                self._durable_tail_m[g] = h_base[g]
            wal_floors_moved = True
        return bool(any_write or wal_floors_moved)

    def _persist_stage_native(self, prep: _PersistPrep
                              ) -> Tuple[float, float]:
        """Stage the WHOLE tick's durable writes through the store's
        native ``stage_and_sync`` entry point — entries by raw arena
        pointer, truncations and milestones as columns — and fsync them
        in the same call with real OS threads (worker k owns WAL shards
        ``s % W == k``).  Returns the C-measured ``(stage_s, fsync_s)``
        max-across-workers wall times.

        Per-shard record order matches ``_persist_stage`` byte-for-byte:
        stable records (Python-staged into the engine buffers first) →
        entry frames → truncate records → milestone records.  The
        truncation/floor sets below are its exact change-detected sets;
        only the store-side staging crosses into C."""
        any_write = self._stage_stable(prep)
        spans = self._build_spans(prep)
        for g, start_idx, _piece, lens, _terms in spans:
            tail_new = start_idx + len(lens) - 1
            if tail_new > self._durable_tail_m[g]:
                self._durable_tail_m[g] = tail_new
        any_write = bool(any_write or spans)
        # Truncations: durable tail must not exceed the device tail.  A
        # span this tick never lifts the mirror past log_tail, so this
        # post-span mask equals _persist_stage's; the store applies the
        # rows verbatim (the caller owns the guard on this path).
        ids = prep.ids
        t_gs = self._where(ids, _at(prep.dirty, ids) & (
            _at(self._durable_tail_m, ids) > _at(prep.log_tail, ids)))
        t_tails = prep.log_tail[t_gs].astype(np.int64)
        self._durable_tail_m[t_gs] = t_tails
        # WAL floor follows the device compaction floor (the store
        # re-checks its own wal-floor guard per row).
        f_gs = self._where(ids, _at(prep.h_base, ids)
                           > _at(self._wal_floor, ids))
        f_idx = prep.h_base[f_gs].astype(np.int64)
        f_term = prep.h_base_term[f_gs].astype(np.int64)
        self._wal_floor[f_gs] = f_idx
        self._durable_tail_m[f_gs] = np.maximum(
            self._durable_tail_m[f_gs], f_idx)
        # Truncations alone do NOT request a sync (_persist_stage's
        # contract), but they still stage their records.  A pending barrier (ENOSPC
        # retry: engines kept their staged buffers) forces the fsync
        # even on a write-free tick, else the buffers never flush.
        need_sync = bool(any_write or len(f_gs) or self._sync_pending)
        if not (spans or len(t_gs) or len(f_gs) or need_sync):
            return 0.0, 0.0
        return self.store.stage_and_sync(
            spans, t_gs, t_tails, f_gs, f_idx, f_term,
            workers=self.host_workers, sync=need_sync)

    def _sweep_rejections(self, prep: _PersistPrep) -> None:
        """Submissions offered but refused because we are no longer
        leader: fail fast with a redirect hint.  A still-leading group
        whose ring is briefly full keeps its queue (backpressure, not
        rejection — the reference distinguishes BusyLoop from NotLeader,
        support/anomaly/).  Refusals carry no durability dependency, so
        they may precede the tick's fsync barrier.  Orchestrator-only
        (touches the submit lock and client futures)."""
        ids = prep.ids
        offered, took = _at(prep.submit_n, ids), _at(prep.sub_acc, ids)
        rejected = self._where(ids, (offered > 0) & (took < offered)
                               & (_at(self.h_role, ids) != LEADER))
        for g in rejected.tolist():
            self._reject_submissions(int(g))

    def _reject_submissions(self, g: int,
                            exc: Optional[Exception] = None) -> None:
        """Fail every QUEUED-but-never-device-accepted submission.  These
        provably never entered the log, so the error is a marked refusal
        (retry-safe) — unlike dispatcher.abort_promises, which covers
        commands already accepted into the log.  A batch whose prefix was
        already accepted fails with the refusal as cause; its
        BatchAbortedError reports exactly which slots completed (the
        accepted prefix's promise range stays registered — identical to
        the old per-slot behavior)."""
        with self._submit_lock:
            q = self._submissions.pop(g, None)
            if not q:
                return
            self._queued_total -= int(self._queued_n[g])
            self._queued_n[g] = 0
        err = as_refusal(exc or NotLeaderError(g, self.leader_hint(g)))
        for b in q:
            b.sink._fail(err)

    # ------------------------------------------------------------ read plane

    def _harvest_reads(self, info: StepInfo,
                       ids: Optional[np.ndarray] = None,
                       woke_ids: Optional[np.ndarray] = None,
                       arrival: bool = False) -> int:
        """Tick thread: mirror the device read FIFO's transitions reported
        in StepInfo — offers the device STAMPED move to pending with
        their ReadIndex; pending offers whose barrier RELEASED move to
        released (FIFO, exactly read_rel of them); device-side ABORTS
        (leadership/term change dropped the whole FIFO) fail every
        un-served batch as a retry-safe refusal.  All of them are events:
        selected among ``ids`` (None: among all lanes).  Returns the lanes
        visited."""
        read_acc = np.asarray(info.read_acc)
        read_idx = np.asarray(info.read_index)
        read_rel = np.asarray(info.read_rel)
        read_lease = np.asarray(info.read_lease)
        read_carried = np.asarray(info.read_carried)
        lease, carried = _at(read_lease, ids), _at(read_carried, ids)
        self._scanned += 2 * len(lease)
        self.metrics["read_lease_hits"] += int(lease.sum())
        self.metrics["read_lease_carried"] += int(carried.sum())
        stamped = self._where(ids, _at(read_acc, ids) > 0).tolist()
        if stamped:
            # Batches this step stamped and left pending: each asked for
            # a barrier heartbeat of its own (phase 9).
            kick = _at(np.asarray(info.read_kick), ids)
            self._scanned += len(kick)
            kicks = int(kick.sum())
            self.metrics["read_kicks"] += kicks
            self._stages.note(kicks=kicks)
        released = self._where(ids, _at(read_rel, ids) > 0).tolist()
        aborted = self._where(
            ids, _at(np.asarray(info.read_abort), ids)).tolist()
        # Lanes this step woke (cfg.hibernate): a batch stamped on one
        # pays the barrier that wakes its followers.
        woke = set(woke_ids.tolist()) if stamped and woke_ids is not None \
            else ()
        # One reading of the wall's monotonic clock for a step that stamps
        # or releases, none for one that does neither.
        t_now = time.perf_counter() if stamped or released else 0.0
        rounds, round_s = 0, 0.0
        with self._read_lock:
            for g in stamped:
                b = self._reads_offered.pop(g, None)
                # The device stamps exactly the offer, whole (its intake
                # reads HostInbox.read_n built from this mirror) — a
                # mismatch means the FIFOs desynchronized, the read
                # analog of the submit queue-depth invariant.
                assert b is not None and int(read_acc[g]) == b.n, \
                    (f"g={g}: device stamped {int(read_acc[g])} reads "
                     "beyond the offer")
                b.lease = bool(read_lease[g])
                b.carried = bool(read_carried[g])
                b.woke = g in woke
                b.t_stamp = t_now
                self._reads_pending.setdefault(g, deque()).append(
                    (int(read_idx[g]), b))
                m = self.metrics
                m["read_barriers"] += 1
                m["reads_coalesced"] += b.n - len(b.parts[0].payloads)
                m.observe("read_batch_queries", b.n)
            for g in released:
                q = self._reads_pending.get(g)
                rel = self._reads_released.setdefault(g, deque())
                for _ in range(int(read_rel[g])):
                    assert q, (f"g={g}: device released a read batch the "
                               "host FIFO does not hold")
                    idx, b = q.popleft()
                    rel.append((idx, b))
                    if not b.lease:
                        # Stamped in an earlier step: a round of
                        # acknowledgements came between.
                        rounds += 1
                        round_s += t_now - b.t_stamp
                # Columnar serve gate: remember the smallest ReadIndex
                # still waiting so _serve_reads visits only groups whose
                # apply frontier actually reached one.
                if rel[0][0] < self._rel_min[g]:
                    self._rel_min[g] = rel[0][0]
        if stamped or released:
            on_arrival = len(stamped) if arrival else 0
            self.metrics["read_stamps_on_arrival"] += on_arrival
            self.metrics["read_rounds"] += rounds
            self._stages.note(stamps=len(stamped), arrival_stamps=on_arrival,
                              rounds=rounds, round_ms=1e3 * round_s)
        for g in aborted:
            self._reject_reads(g)
        return len(stamped) + len(released) + len(aborted)

    def _serve_reads(self, applied: np.ndarray,
                     ids: Optional[np.ndarray] = None) -> int:
        """Tick thread: serve released batches whose ReadIndex the apply
        frontier covers.  Machine ``read`` runs here — the same
        single-writer thread as applies, so queries see a consistent
        machine with no extra locking (machine/spi.py read SPI).
        Returns the lanes visited."""
        # Columnar gate: one vector compare picks the groups whose apply
        # frontier reached a released batch's ReadIndex — the every-tick
        # walk over all groups holding a released deque was a per-group
        # Python loop on the hot path.  A step worked from its rows
        # (``ids``) makes the compare over the lanes that hold a released
        # batch, which is where _rel_min is not its sentinel.
        lanes = None
        if ids is not None:
            with self._read_lock:
                lanes = np.fromiter(self._reads_released, np.int64,
                                    len(self._reads_released))
            lanes.sort()
        due = self._where(lanes, _at(applied, lanes)
                          >= _at(self._rel_min[:len(applied)], lanes))
        if not len(due):
            return 0
        sentinel = np.iinfo(np.int64).max
        ready: List[Tuple[int, int, _ReadOffer]] = []
        with self._read_lock:
            for g in due.tolist():
                q = self._reads_released.get(g)
                if not q:
                    # Stale gate (batches rejected out from under it).
                    self._rel_min[g] = sentinel
                    self._reads_released.pop(g, None)
                    continue
                a = int(applied[g])
                while q and q[0][0] <= a:
                    idx, b = q.popleft()
                    ready.append((g, idx, b))
                if q:
                    self._rel_min[g] = q[0][0]
                else:
                    self._rel_min[g] = sentinel
                    del self._reads_released[g]
        if not ready:
            return len(due)
        now = time.monotonic()
        queries = lease_hits = lease_carried = woke = 0
        for g, idx, offer in ready:
            machine = self.dispatcher.machine(g)
            rd = getattr(machine, "read", None)
            served = queries
            for b in offer.parts:
                try:
                    for k, payload in enumerate(b.payloads):
                        b.sink._complete(
                            k, idx if rd is None else rd(payload))
                except Exception as e:
                    # Query errors are still retry-safe: the read mutated
                    # nothing (SPI contract) and never entered the log.
                    # The call that sent the query fails; the parts that
                    # share its barrier do not.
                    b.sink._fail(as_refusal(e))
                    continue
                queries += len(b.payloads)
                self.metrics.observe("read_barrier_latency_s",
                                     now - b.t_enq)
            # A barrier the lease released in the step that stamped it
            # (counter read_lease_hits, counted at the stamp), where it
            # served a query: never more than ``queries``.
            lease_hits += int(offer.lease and queries > served)
            lease_carried += int(offer.carried and queries > served)
            woke += int(offer.woke and queries > served)
        self.metrics["reads_served"] += queries
        # What this tick served, on its raft.reads span.
        # ``woke``: barriers stamped in a step that woke their lane from
        # hibernation, where they served a query (woke + lease_hits <=
        # queries; 0 where the field is off).
        self._stages.note(queries=queries, barriers=len(ready),
                          lease_hits=lease_hits,
                          lease_carried=lease_carried, woke=woke)
        return len(due)

    def _reject_reads(self, g: int, exc: Optional[Exception] = None,
                      drop_released: bool = False) -> None:
        """Fail every un-served read batch for ``g`` (waiting + every part
        of the offered and pending offers; ``drop_released`` adds
        barrier-confirmed ones too — only lane close/purge does that,
        since a confirmed ReadIndex stays servable across leadership
        changes).  Always a MARKED refusal: reads never enter the log, so
        any retry is safe."""
        with self._read_lock:
            q = self._reads_waiting.pop(g, None)
            batches = list(q) if q else []
            offer = self._reads_offered.pop(g, None)
            if offer is not None:
                batches.extend(offer.parts)
            pend = self._reads_pending.pop(g, None)
            if pend:
                batches.extend(b for _, o in pend for b in o.parts)
            if drop_released:
                rel = self._reads_released.pop(g, None)
                if rel:
                    batches.extend(b for _, o in rel for b in o.parts)
                self._rel_min[g] = np.iinfo(np.int64).max
            self._read_queued_n[g] = 0
        if not batches:
            return
        err = as_refusal(exc or NotLeaderError(g, self.leader_hint(g)))
        for b in batches:
            b.sink._fail(err)
        self.metrics["read_batches_aborted"] += len(batches)

    # ------------------------------------------------------------ membership

    def change_membership(self, group: int, voters: int,
                          learners: int = 0) -> Future:
        """Reconfigure one group to the TARGET config (§6 joint
        consensus): ``voters``/``learners`` are peer-slot bitmasks.  A
        voter-set change walks C_old -> C_old,new -> C_new through the
        log (the leave entry auto-appends when the joint entry commits);
        a learner-only change is a single entry.  The future resolves —
        with the decoded config — once the FINAL config is active and
        committed, or fails with NotLeader on leadership loss (marked
        retry-safe only if the change provably never entered the log).
        One change in flight per group, here AND on the device."""
        from ..core.types import conf_pack

        fut: Future = Future()
        P = self.cfg.n_peers
        full = (1 << P) - 1
        voters = int(voters)
        learners = int(learners) & ~voters
        if not (0 < voters <= full) or not (0 <= learners <= full) \
                or (voters | learners) > full:
            fut.set_exception(ValueError(
                f"bad membership masks for P={P}: voters={voters:#x} "
                f"learners={learners:#x}"))
            return fut
        err = self._refusal(group)
        if err is not None:
            fut.set_exception(err)
            return fut
        final = int(conf_pack(voters, 0, learners))
        with self._member_lock:
            if group in self._conf_pending:
                fut.set_exception(as_refusal(BusyLoopError(
                    f"group {group}: a membership change is already "
                    "pending")))
                return fut
            if int(self.h_conf_word[group]) == final \
                    and not self.h_conf_pending[group]:
                # Already the active committed config: resolve like the
                # settled path would.
                fut.set_result({"voters": voters, "learners": learners})
                return fut
            self._conf_pending[group] = [voters, learners, fut, False]
        return fut

    def transfer_leadership(self, group: int, target: int) -> Future:
        """Hand leadership of ``group`` to voter ``target`` (§3.10
        TimeoutNow): fence submissions, wait for the target's match to
        cover the log end, tell it to campaign.  Resolves with the
        target id once this node observes its own step-down after the
        TimeoutNow went out; fails (retry-safe) if the transfer aborts —
        deadline, target not a voter, leadership lost first."""
        from ..core.types import conf_new_of, conf_voters_of

        fut: Future = Future()
        target = int(target)
        err = self._refusal(group)
        if err is not None:
            fut.set_exception(err)
            return fut
        w = int(self.h_conf_word[group])
        if not (0 <= target < self.cfg.n_peers) \
                or target == self.node_id \
                or not ((conf_voters_of(w) | conf_new_of(w))
                        >> target) & 1:
            # The device intake only latches VOTER targets; refusing here
            # keeps a learner/removed-slot request from pending forever.
            fut.set_exception(as_refusal(ValueError(
                f"transfer target {target} is not a voter of group "
                f"{group}")))
            return fut
        with self._member_lock:
            if group in self._xfer_pending:
                fut.set_exception(as_refusal(BusyLoopError(
                    f"group {group}: a leadership transfer is already "
                    "pending")))
                return fut
            # TTL covers the never-latched case (the config changed under
            # us, the device keeps refusing intake): the device's own
            # deadline only starts once a transfer latches.
            ttl = 6 * self.cfg.election_ticks + 20
            self._xfer_pending[group] = [target, fut, False, ttl]
        self.metrics["leadership_transfers_attempted"] += 1
        return fut

    def membership(self, group: int) -> dict:
        """Decoded active config of one group (device mirror)."""
        from ..core.types import (
            conf_learners_of, conf_new_of, conf_voters_of,
        )

        w = int(self.h_conf_word[group])
        return {
            "voters": int(conf_voters_of(w)),
            "voters_new": int(conf_new_of(w)),
            "learners": int(conf_learners_of(w)),
            "joint": bool(conf_new_of(w)),
            "pending": bool(self.h_conf_pending[group]),
            "conf_idx": int(self.h_conf_idx[group]),
        }

    def catch_up_gaps(self) -> np.ndarray:
        """[G, P] leader-side replication lag of every peer: ``last -
        match`` (0 = fully caught up; the rebalancer polls it to decide
        when a learner is promotable).  An admin-cadence device read of
        the two whole planes: an eager index into a device array would
        compile a slice program per shape at its first use, in whatever
        tick an evacuation or a transfer happens to fall."""
        import jax

        last, match = jax.device_get(
            (self.state.log.last, self.state.match_idx))
        return np.maximum(0, np.asarray(last)[:, None] - np.asarray(match))

    def _harvest_membership(self, info: StepInfo, h_role) -> None:
        """Tick thread: refresh config mirrors from StepInfo, resolve
        pending change/transfer futures, fold membership counters."""
        conf_word = np.asarray(info.conf_word)
        conf_idx = np.asarray(info.conf_idx)
        conf_pending = np.asarray(info.conf_pending)
        app_idx = np.asarray(info.conf_app_idx)
        fired = np.asarray(info.xfer_fired)
        m = self.metrics
        m["membership_changes_entered"] += int((app_idx > 0).sum())
        # A config entry COMMITTED when its pending flag clears at the
        # same entry index (a truncation rollback changes the index too
        # and must not count).
        m["membership_changes_committed"] += int(
            (self.h_conf_pending & ~conf_pending
             & (self.h_conf_idx == conf_idx) & (conf_idx > 0)).sum())
        m["timeout_now_sent"] += int(fired.sum())
        self.h_conf_word = conf_word
        self.h_conf_idx = conf_idx
        self.h_conf_pending = conf_pending
        self._settle_membership(info, h_role)

    def _settle_membership(self, info: StepInfo, h_role) -> None:
        """Tick thread, once the config mirrors are the step's: resolve
        the pending change/transfer futures the step settles (a walk over
        the pending operations, each reading its own lane)."""
        from ..core.types import conf_pack

        conf_word, conf_pending = info.conf_word, info.conf_pending
        app_idx, fired = info.conf_app_idx, info.xfer_fired
        x_abort = info.xfer_abort
        m = self.metrics
        settled: List[Tuple[Future, Optional[Exception], object]] = []
        with self._member_lock:
            for g, ent in list(self._conf_pending.items()):
                tv, tl, fut, accepted = ent
                if app_idx[g] > 0:
                    ent[3] = accepted = True
                final = int(conf_pack(tv, 0, tl))
                if int(conf_word[g]) == final and not conf_pending[g]:
                    del self._conf_pending[g]
                    settled.append((fut, None, {
                        "voters": tv, "learners": tl}))
                elif h_role[g] != LEADER:
                    del self._conf_pending[g]
                    err = NotLeaderError(g, self.leader_hint(g))
                    # Never accepted into the log -> marked retry-safe
                    # refusal; accepted -> unmarked (the change may still
                    # commit under the new leader).
                    settled.append((fut,
                                    err if accepted else as_refusal(err),
                                    None))
                    m["membership_changes_aborted"] += 1
            for g, ent in list(self._xfer_pending.items()):
                tgt, fut, was_fired, ttl = ent
                if fired[g]:
                    ent[2] = was_fired = True
                ent[3] = ttl = ttl - 1
                if h_role[g] != LEADER and was_fired:
                    # Relinquished after TimeoutNow: the transfer
                    # succeeded (the target campaigns with a complete
                    # log; the leader hint converges to it).
                    del self._xfer_pending[g]
                    settled.append((fut, None, tgt))
                    m["leadership_transfers_succeeded"] += 1
                elif h_role[g] != LEADER or x_abort[g] or ttl <= 0:
                    del self._xfer_pending[g]
                    settled.append((fut, as_refusal(NotLeaderError(
                        g, self.leader_hint(g))), None))
                    m["leadership_transfers_aborted"] += 1
        for fut, err, res in settled:
            if fut.done():
                continue
            if err is None:
                fut.set_result(res)
            else:
                fut.set_exception(err)

    def _reject_membership(self, g: int, exc: Exception) -> None:
        """Fail pending membership ops for a closing/destroyed lane."""
        with self._member_lock:
            ent = self._conf_pending.pop(g, None)
            xent = self._xfer_pending.pop(g, None)
        if ent is not None and not ent[2].done():
            ent[2].set_exception(as_refusal(exc))
            self.metrics["membership_changes_aborted"] += 1
        if xent is not None and not xent[1].done():
            xent[1].set_exception(as_refusal(exc))
            self.metrics["leadership_transfers_aborted"] += 1

    def _purge_lanes(self, lanes: List[int]) -> None:
        """Wipe destroyed lanes end to end: durable WAL state, machine,
        archived snapshots, and every device-side lane (term, log, vote,
        replication bookkeeping) back to boot values."""
        lane_set = set(lanes)
        # Settle the checkpoint pool for these lanes: drop queued saves,
        # then wait out any in-flight one (bounded) — a worker's archive
        # insert must not race destroy() and resurrect a dead snapshot.
        with self._ckpt_cv:
            if self._ckpt_queue:
                self._ckpt_queue = deque(
                    e for e in self._ckpt_queue if e[0] not in lane_set)
            deadline = time.monotonic() + 10
            while True:
                pending = (self._ckpt_inflight & lane_set) \
                    - {d[0] for d in self._ckpt_done}
                if not pending:
                    break
                if time.monotonic() > deadline:
                    log.error("purge: checkpoint save still in flight for "
                              "%s after 10s", sorted(pending))
                    break
                self._ckpt_cv.wait(timeout=0.1)
            self._ckpt_done = [d for d in self._ckpt_done
                               if d[0] not in lane_set]
        self._ckpt_inflight -= lane_set
        for g in lanes:
            self.store.reset_group(g)
            self.dispatcher.drop_machine(g, destroy=True)
            self.archive.destroy(g)     # also clears any pending download
            with self._snap_cv:
                # Epoch bump invalidates any queued-but-unstarted fetch for
                # the old incarnation even if the recreated lane re-enters
                # _snap_inflight before the worker pops it.
                self._snap_epoch[g] = self._snap_epoch.get(g, 0) + 1
                self._snap_inflight.discard(g)
            self.maintain.note_checkpoint(g, 0, 0)
            self.maintain.snap_index[g] = 0
            self.maintain.applied_at_snap[g] = 0
        self.store.sync()
        idx = jnp.asarray(lanes, I32)
        s, L, P = self.state, self.cfg.log_slots, self.cfg.n_peers
        z = jnp.zeros((len(lanes),), I32)
        self.state = s.replace(
            term=s.term.at[idx].set(0),
            role=s.role.at[idx].set(0),
            voted_for=s.voted_for.at[idx].set(NIL),
            leader_id=s.leader_id.at[idx].set(NIL),
            commit=s.commit.at[idx].set(0),
            applied=s.applied.at[idx].set(0),
            log=s.log.replace(
                term=s.log.term.at[idx].set(0),
                conf=s.log.conf.at[idx].set(0),
                base=s.log.base.at[idx].set(0),
                base_term=s.log.base_term.at[idx].set(0),
                base_conf=s.log.base_conf.at[idx].set(
                    _boot_conf_word(self.cfg)),
                last=s.log.last.at[idx].set(0)),
            next_idx=s.next_idx.at[idx].set(1),
            match_idx=s.match_idx.at[idx].set(0),
            send_next=s.send_next.at[idx].set(1),
            inflight=s.inflight.at[idx].set(0),
            hb_inflight=s.hb_inflight.at[idx].set(0),
            own_from=s.own_from.at[idx].set(0),
            sent_at=s.sent_at.at[idx].set(0),
            need_snap=s.need_snap.at[idx].set(False),
            ok_at=s.ok_at.at[idx].set(0),
            fail_at=s.fail_at.at[idx].set(0),
            fail_streak=s.fail_streak.at[idx].set(0),
            votes=s.votes.at[idx].set(False),
            prevotes=s.prevotes.at[idx].set(False),
            read_evid=s.read_evid.at[idx].set(0),
            rq_idx=s.rq_idx.at[idx].set(0),
            rq_stamp=s.rq_stamp.at[idx].set(0),
            rq_n=s.rq_n.at[idx].set(0),
            rq_head=s.rq_head.at[idx].set(0),
            rq_len=s.rq_len.at[idx].set(0),
            conf_idx=s.conf_idx.at[idx].set(0),
            conf_word=s.conf_word.at[idx].set(_boot_conf_word(self.cfg)),
            xfer_to=s.xfer_to.at[idx].set(NIL),
            xfer_dl=s.xfer_dl.at[idx].set(0),
            trace=(s.trace.replace(
                tick=s.trace.tick.at[idx].set(0),
                kind=s.trace.kind.at[idx].set(0),
                term=s.trace.term.at[idx].set(0),
                aux=s.trace.aux.at[idx].set(0),
                n=s.trace.n.at[idx].set(0))
                if s.trace is not None else None),
            heat=(s.heat.replace(
                appended=s.heat.appended.at[idx].set(0),
                sent=s.heat.sent.at[idx].set(0),
                commits=s.heat.commits.at[idx].set(0),
                reads=s.heat.reads.at[idx].set(0))
                if s.heat is not None else None),
        )
        if s.trace is not None:
            for g in lanes:
                self.tracelog.reset_group(int(g))
        if self.heat is not None:
            # Device heat lanes just reset to 0 — the registry's
            # cumulative mirror must follow or the next ingest would see
            # a negative delta for the recreated lane.
            for g in lanes:
                self.heat.reset_group(int(g))
        # The lanes' device state and the mirrors below change under the
        # column step's rows: its next results come down whole.
        self._rows_whole_out = True
        # device_get arrays may be read-only views; replace, don't mutate
        hc = np.array(self.h_commit)
        hb = np.array(self.h_base)
        hc[np.asarray(lanes)] = 0
        hb[np.asarray(lanes)] = 0
        self.h_commit, self.h_base = hc, hb
        self._wal_floor[np.asarray(lanes)] = 0
        self._durable_tail_m[np.asarray(lanes)] = 0
        self._stable_term_m[np.asarray(lanes)] = -2
        self._stable_voted_m[np.asarray(lanes)] = -2
        hcw = np.array(self.h_conf_word)
        hci = np.array(self.h_conf_idx)
        hcp = np.array(self.h_conf_pending)
        hcw[np.asarray(lanes)] = _boot_conf_word(self.cfg)
        hci[np.asarray(lanes)] = 0
        hcp[np.asarray(lanes)] = False
        self.h_conf_word, self.h_conf_idx = hcw, hci
        self.h_conf_pending = hcp
        for g in lanes:
            self._snap_conf.pop(g, None)
            self._reject_membership(
                g, ObsoleteContextError(f"group {g} destroyed"))

    def _payload(self, g: int, idx: int) -> Optional[bytes]:
        return self.store.payload(g, idx)

    # ------------------------------------------------------------------ send

    def _stash_outbox_sections(self, h_out,
                               blob_fn: Optional[Callable] = None
                               ) -> Tuple[Dict[int, List[bytes]], int]:
        """Pack one tick's outbox into per-peer kind
        sections and return {peer: [sections]} and the message columns
        (lane by kind by peer) packed — the caller folds into
        ``_held_sections``; ``_flush_sends`` assembles each peer's
        sections into ONE MSGS frame."""
        P = self.cfg.n_peers
        # Quarantine silence: no frame for a poisoned stripe's groups
        # ever leaves (their staged ranges may not be durable here — a
        # resent AE could let followers quorum-commit a range this node
        # cannot back).  Central choke point for every packing site.
        mask = self._healthy_groups
        win = self.store.payloads_window
        runs = getattr(self.store, "payload_runs", None)
        held: Dict[int, List[bytes]] = {}
        packed = 0
        for p in range(P):
            if p == self.node_id:
                continue
            fields = h_out.fields(p)
            healthy = None if mask is None else h_out.over(p, mask)
            secs: List[bytes] = []
            for kind in KIND_FIELDS:
                valid = h_out.row(KIND_FIELDS[kind][0], p)
                if healthy is not None:
                    valid = valid & healthy
                cols = h_out.lanes(p, valid)
                if not len(cols):
                    continue
                sec, n_cols = pack_kind_section(
                    kind, fields, win, runs, cols=cols,
                    payload_blob_fn=blob_fn)
                if n_cols:
                    secs.append(sec)
                    packed += n_cols
            if secs:
                held[p] = secs
        return held, packed

    def _flush_sends(self) -> None:
        """Assemble every peer's held sections into ONE MSGS frame and
        release it.  The single per-tick flush point, behind the tick's
        fsync barrier.

        Hop-tracing sideband: pending HOPS requests/echoes piggyback on
        the same send_slice blob (FrameReader parses concatenated
        frames), so hop records share fate with the tick's real traffic
        — a cut link delays both identically and ``wire`` measures the
        path the entries actually took."""
        held, self._held_sections = self._held_sections, {}
        hops = self._hops
        if hops is not None:
            for p in hops.out_peers():
                held.setdefault(p, [])
        for p, secs in held.items():
            blob = assemble_slice(self.node_id, secs) if secs else b""
            if hops is not None:
                out = hops.take_out(p)
                if out is not None:
                    reqs, echoes = out
                    if reqs:
                        blob += pack_hops(HOP_REQUEST, self.node_id, reqs)
                    if echoes:
                        blob += pack_hops(HOP_ECHO, self.node_id, echoes)
            if blob:
                self.transport.send_slice(p, blob)
                if self.cfg.hibernate:
                    self._beat_sent.add(p)

    # -------------------------------------------------------------- maintain

    def _maintain(self, applied: np.ndarray, h_base, h_term,
                  timer: bool, ids: Optional[np.ndarray] = None,
                  keep: bool = False) -> None:
        """Checkpoints, compaction grants, WAL GC and the scrubber, once a
        period: on the host phase of the timer's tick (``timer``), with
        every cadence counted in ``timer_ticks``.  A step started for
        arriving work skips the policy pass unless a log ring under
        pressure asks for it (snapshot/policy.py ``pressed``: such a ring
        is due at once, and its release chain of save, harvest, grant
        and compaction moves a step at a time); every step notes how
        full the fullest ring stands.  ``ids`` / ``keep``: _ring_state."""
        now = self.timer_ticks
        n_ckpt = n_pressed = lanes = 0
        pressed, ring_used = self._ring_state(h_base, ids, keep)
        if timer or pressed:
            n_ckpt, n_pressed, lanes = self._maintain_pass(
                now, applied, h_base)
        # The fullest ring this node holds (the fsynced tail is the
        # tick's log tail once its host phase is here), on /metrics and
        # on this tick's raft.maintain span.
        self.metrics.gauge("log_ring_used_max", ring_used)
        self._stages.note(
            ring_used=ring_used, ring_slots=self.cfg.log_slots,
            led=self._led_open,
            checkpoints=n_ckpt, by_pressure=n_pressed, lanes=lanes)
        if not timer:
            return
        self._maintain_gc(now)
        if now % 32 == 0:
            self._fold_wal_stats()
        if self.scrub_interval_ticks \
                and now % self.scrub_interval_ticks == 0:
            self._scrub_archive()

    def _ring_state(self, h_base, ids: Optional[np.ndarray],
                    keep: bool) -> Tuple[bool, int]:
        """(does any log ring stand under pressure, the fullest open
        ring's fill: ``durable tail - base``) once the step's writes are
        staged.  Over whole planes for ``ids`` None, where a node whose
        steps can come down as rows (``keep``) also files what it found:
        the pressed lanes as a mask and their count, each open lane's
        fill and the lanes counted per fill.  A step worked from its rows
        patches those at ``ids`` (both operands of ``pressed`` are levels
        that come down with a row; a durable tail moves only on a lane
        whose row moved) and reads the answers off them: the fullest ring
        is the highest occupied count.  A fill lies in 0..log_slots; one
        beyond (a snapshot installed ahead of its base) is counted in one
        more bucket, and while that one is occupied the maximum is taken
        over the planes."""
        slots = self.cfg.log_slots
        tail, active = self._durable_tail_m, self.h_active
        if ids is None:
            pressed = self.maintain.pressed(self.h_commit, h_base)
            fill = tail - h_base
            self._scanned += 2 * len(fill)
            if keep:
                self._pressed_m = pressed
                self._pressed_n = int(pressed.sum())
                self._fill_m = np.where(
                    active, np.clip(fill, 0, slots + 1), -1)
                self._fill_hist = np.bincount(
                    self._fill_m[active], minlength=slots + 2)
            return bool(pressed.any()), int(fill[active].max(initial=0))
        pressed = self.maintain.pressed(self.h_commit[ids], h_base[ids])
        self._pressed_n += int(pressed.sum()) \
            - int(self._pressed_m[ids].sum())
        self._pressed_m[ids] = pressed
        fill = np.where(active[ids],
                        np.clip(tail[ids] - h_base[ids], 0, slots + 1), -1)
        was, hist = self._fill_m[ids], self._fill_hist
        np.subtract.at(hist, was[was >= 0], 1)
        np.add.at(hist, fill[fill >= 0], 1)
        self._fill_m[ids] = fill
        self._scanned += 2 * len(ids)
        top = np.flatnonzero(hist)
        ring_used = int(top[-1]) if len(top) else 0
        if ring_used > slots:
            self._scanned += len(tail)
            ring_used = int((tail - h_base)[active].max(initial=0))
        return self._pressed_n > 0, ring_used

    def _maintain_pass(self, now: int, applied: np.ndarray, h_base
                       ) -> Tuple[int, int, int]:
        """One pass of the checkpoint and compaction policy at timer tick
        ``now``; returns (checkpoints serialized, of them by pressure,
        lanes visited: saves harvested and groups found due)."""
        # Harvest completed off-thread saves FIRST: a milestone feeds the
        # compaction policy only once its archive copy is durable on disk
        # (a compaction grant must never outrun its snapshot).
        with self._ckpt_cv:
            done, self._ckpt_done = self._ckpt_done, []
        for g, idx, ok in done:
            self._ckpt_inflight.discard(g)
            if ok:
                self.maintain.note_checkpoint(g, now, idx)
                self.metrics["snapshots_taken"] += 1
            else:
                # Archive copy failed (disk error / injected fault): the
                # previous milestone stands — note_checkpoint was NOT
                # called, so compaction never advances past a snapshot
                # that does not exist on disk, the group stays due, and
                # the save retries on a later maintain pass.  Surfaced,
                # never wedged.
                self.metrics["ckpt_failures"] += 1
        need = self.maintain.need_checkpoint(now, applied, h_base)
        pressed = self.maintain.ckpt_pressed
        n_ckpt = n_pressed = 0
        # The policy pass looks at every lane, whatever moved: it asks
        # what is due, which is a matter of time (the two calls of the
        # policy, the two selections made of their answers).
        self._scanned += 4 * len(need)
        due = np.nonzero(need)[0]
        if len(due) > self.max_checkpoints_per_tick:
            # Rotate the selection across ticks: a fixed [:cap] slice would
            # starve high-index groups forever under sustained load.
            pos = int(np.searchsorted(due, self._ckpt_cursor, side="right"))
            due = np.concatenate([due[pos:], due[:pos]])
            due = due[:self.max_checkpoints_per_tick]
        if len(due):
            self._ckpt_cursor = int(due[-1])
        # The tick thread only SERIALIZES the machine (single-writer rule:
        # applies mutate it on this thread) and reads the snapshot term;
        # the archive copy + rotation happen on the worker pool.  Bounded
        # queue: when full, the remaining due groups simply stay due —
        # backpressure, never loss — so maintenance can no longer own the
        # tick latency (reference: checkpoints run on a bounded pool off
        # the loop, RaftRoutine.java:46-49).
        queued = False
        for g in due.tolist():
            if g in self._ckpt_inflight:
                continue   # one save in flight per group (archive order)
            with self._ckpt_cv:
                if len(self._ckpt_queue) >= self.ckpt_queue_cap:
                    self.metrics["ckpt_backpressure"] += 1
                    break
            try:
                ckpt = self.dispatcher.machine(g).checkpoint(0)
            except Exception:
                log.exception("checkpoint failed g=%d", g)
                continue
            # Snapshot term = term of the log entry at the checkpoint index
            # (a store read — tick thread only, like every store access).
            t = self.store.entry_term(g, ckpt.index)
            if t < 0:
                t = self.store.floor_term(g)
            self._ckpt_inflight.add(g)
            with self._ckpt_cv:
                # Capacity RE-checked in the same acquisition as the
                # append: the pre-check above ran in an earlier cv block,
                # and check-then-append across separate acquisitions is
                # not atomic — the bound must hold at append time, never
                # transiently overshoot.  A refused group stays due and
                # retries next tick (backpressure, not loss).
                full = len(self._ckpt_queue) >= self.ckpt_queue_cap
                if not full:
                    self._ckpt_queue.append((g, ckpt.path, ckpt.index, t))
                    self._ckpt_cv.notify()
                    queued = True
                    n_ckpt += 1
                    n_pressed += int(pressed[g])
            if full:
                self._ckpt_inflight.discard(g)
                self.metrics["ckpt_backpressure"] += 1
                try:
                    os.unlink(ckpt.path)
                except OSError:
                    pass
                break
        if queued:
            self._ensure_ckpt_workers()
        self._compact_grant = self.maintain.compact_targets(
            now, self.h_commit.astype(np.int64), h_base.astype(np.int64))
        m = self.metrics
        m["ckpt_by_pressure"] += n_pressed
        m["compactions_by_pressure"] += int(
            self.maintain.compact_pressed.sum())
        return n_ckpt, n_pressed, len(done) + len(due)

    def _fold_wal_stats(self) -> None:
        """Fold the WAL engines' cumulative stage/fsync/pack counters
        (native wal_stats() or the PyWal mirror — log/wal.py) into the
        metrics registry as wal_* counters.  The engine counters never
        reset; this keeps the last snapshot and folds deltas, so the
        registry survives engine reopen (a fresh engine restarts at 0
        and the max(0, ...) clamp drops the negative delta)."""
        wal = getattr(self.store, "wal", None)
        stats = getattr(wal, "stats", None)
        if stats is None:
            return
        cur = stats()
        last = self._wal_stat_last or {}
        m = self.metrics
        for k, v in cur.items():
            m[f"wal_{k}"] += max(0, v - last.get(k, 0))
        self._wal_stat_last = cur

    def _scrub_archive(self) -> None:
        """Background snapshot scrubber: one budgeted verify pass —
        a few groups per interval, round-robin, newest snapshots first
        (archive.scrub) — so a latent bit flip in an archived snapshot
        is caught and quarantined BEFORE recovery or a lagging follower
        would read it.  Runs on the tick thread against tiny per-group
        budgets; the CRC walk is the cost of one extra file read."""
        gs = self.archive.groups_with_snapshots(self.cfg.n_groups)
        if not gs:
            return
        for _ in range(min(self.scrub_groups_per_pass, len(gs))):
            g = gs[self._scrub_cursor % len(gs)]
            self._scrub_cursor += 1
            try:
                ok, corrupt = self.archive.scrub(g, limit=2)
            except OSError:
                log.exception("snapshot scrub failed g=%d", g)
                continue
            self.metrics["scrub_ok"] += ok
            self.metrics["scrub_corrupt"] += corrupt

    def _ensure_ckpt_workers(self) -> None:
        self._ckpt_threads = [t for t in self._ckpt_threads if t.is_alive()]
        while len(self._ckpt_threads) < self.ckpt_workers:
            t = threading.Thread(
                target=self._ckpt_worker,
                name=f"raft-ckpt-{self.node_id}-{len(self._ckpt_threads)}",
                daemon=True)
            t.start()
            self._ckpt_threads.append(t)

    def _ckpt_worker(self) -> None:
        """Pool worker: archive machine checkpoints until shutdown (the
        queue is drained even after _stop so no serialized temp file is
        stranded un-archived)."""
        while True:
            with self._ckpt_cv:
                while not self._ckpt_queue and not self._stop.is_set():
                    self._ckpt_cv.wait(timeout=0.5)
                if not self._ckpt_queue:
                    return   # _stop set and nothing left
                g, path, idx, term = self._ckpt_queue.popleft()
            ok = True
            try:
                self.archive.save_checkpoint(g, path, idx, term)
            except Exception:
                log.exception("checkpoint archive failed g=%d", g)
                ok = False
            finally:
                try:
                    os.unlink(path)
                except OSError:
                    pass
            with self._ckpt_cv:
                self._ckpt_done.append((g, idx, ok))
                self._ckpt_cv.notify_all()

    def _maintain_gc(self, now: int) -> None:
        """Physical WAL GC, three-phase so no tick stalls on the rewrite
        (reference: RocksDB reclaims off the consensus path via deleteRange
        + background compaction, command/storage/RocksLog.java:228-242)."""
        if self._gc_phase == 2:       # worker done: bounded swap-in
            try:
                if self.store.gc_finish() == 0:
                    self.metrics["wal_gc_runs"] += 1
                else:
                    self.store.gc_abort()
            except Exception:
                log.exception("WAL GC finish failed")
                self.store.gc_abort()
            self._gc_phase = 0
            self._gc_thread = None
            self.metrics.gauge("wal_segments", self.store.segment_count())
        elif self._gc_phase == -1:    # worker failed: drop the attempt
            self.store.gc_abort()
            self._gc_phase = 0
            self._gc_thread = None
        elif (self._gc_phase == 0 and self.wal_gc_check_ticks
              and now % self.wal_gc_check_ticks == 0):
            try:
                if not self.store.should_gc(self.wal_gc_ratio,
                                            self.wal_gc_min_bytes):
                    return
                if self.store.gc_begin() < 0:
                    return
            except Exception:
                log.exception("WAL GC begin failed")
                return
            self._gc_phase = 1
            self._gc_thread = threading.Thread(
                target=self._gc_worker,
                name=f"raft-walgc-{self.node_id}", daemon=True)
            self._gc_thread.start()

    def _gc_worker(self) -> None:
        try:
            ok = self.store.gc_rewrite() >= 0
        except Exception:
            log.exception("WAL GC rewrite failed")
            ok = False
        # Handoff: the tick thread performs finish/abort (single-writer
        # rule — the worker never touches live engine state).
        self._gc_phase = 2 if ok else -1

    # -------------------------------------------------------------- snapshot

    def _serve_snapshot(self, group: int, index: int, term: int
                        ) -> Optional[Tuple[int, int, str]]:
        """Transport callback: serve our newest snapshot for the group
        (reference EventBus WaitSnap -> TransSnap + sendfile,
        transport/EventBus.java:98-111).  Returns (index, term, path); the
        transport streams the file in chunks, so snapshot size is
        unbounded by the frame codec's MAX_BODY."""
        snap = self.archive.last_snapshot(group)
        if snap is None or not os.path.exists(snap.path):
            return None
        if self.archive.verify_snapshot(snap.path) == "corrupt":
            # Never propagate a corrupt milestone to a follower; the
            # scrubber (tick thread) will quarantine it — this callback
            # runs on a transport thread and only reads.
            log.error("node %d: refusing to serve corrupt snapshot %s",
                      self.node_id, snap.path)
            return None
        return snap.index, snap.term, snap.path

    def _snapshot_requests(self, info: StepInfo, h_base,
                           ids: Optional[np.ndarray] = None) -> None:
        req = self._where(ids, _at(np.asarray(info.snap_req), ids))
        queued = False
        for g in req.tolist():
            g = int(g)
            if g in self._snap_inflight:
                continue
            idx = int(np.asarray(info.snap_req_idx)[g])
            term = int(np.asarray(info.snap_req_term)[g])
            peer = int(np.asarray(info.snap_req_from)[g])
            if self.archive.pend_snapshot(g, idx, term, peer) is None:
                continue
            # The offer's config word (is_conf) rides to install time: it
            # becomes the installer's base_conf via HostInbox.snap_conf.
            self._snap_conf[g] = (idx,
                                  int(np.asarray(info.snap_req_conf)[g]))
            with self._snap_cv:
                self._snap_inflight.add(g)
                self._snap_queue.append(
                    (self._snap_epoch.get(g, 0), g, peer, idx, term))
                queued = True
        if queued:
            with self._snap_cv:
                self._snap_cv.notify_all()
            # Lazily grow the pool up to the bound (reference: one snapshot
            # IO thread; here a small pool, NettyCluster.java:42-43).
            self._snap_threads = [t for t in self._snap_threads
                                  if t.is_alive()]
            while len(self._snap_threads) < self.snap_fetch_workers:
                t = threading.Thread(
                    target=self._snap_worker,
                    name=f"raft-snapfetch-{self.node_id}-"
                         f"{len(self._snap_threads)}", daemon=True)
                t.start()
                self._snap_threads.append(t)

    def _snap_worker(self) -> None:
        """Pool worker: drain queued snapshot fetches until node shutdown.
        A fetch queued before a lane purge (stale epoch) or whose lane is
        no longer marked in flight is skipped."""
        while True:
            with self._snap_cv:
                while not self._snap_queue and not self._stop.is_set():
                    self._snap_cv.wait(timeout=0.5)
                if self._stop.is_set():
                    return
                ep, g, peer, idx, term = self._snap_queue.popleft()
                if (ep != self._snap_epoch.get(g, 0)
                        or g not in self._snap_inflight):
                    continue
            self._download_snapshot(g, peer, idx, term, ep)

    def _download_snapshot(self, g: int, peer: int, idx: int,
                           term: int, ep: int) -> None:
        """Worker: fetch ONE snapshot's bytes to a temp file (reference
        SnapChannel download, transport/EventNode.java:122-267).  Install —
        every store/dispatcher/archive mutation — happens on the tick
        thread in ``_install_snapshots``.

        ``ep`` is the lane's fetch epoch at dispatch: if a purge bumped it
        while the fetch was in flight, this download belongs to a dead
        incarnation — it must neither surface its bytes, nor fail the NEW
        incarnation's pending, nor cancel its in-flight marker."""
        # A file of its own for every download: a lane leaves
        # _snap_inflight when its download is done, the device goes on
        # asking until the snapshot is INSTALLED (the next dispatch), and
        # the host phase of a tick whose dispatch the download's end just
        # missed then starts a second download, which runs beside the tick
        # thread that is archiving the first.  Under one name the second
        # truncated what the first install was reading (an empty snapshot
        # installed at the right index: tests/test_host_rows.py's
        # snapshot_install twins, one run in ten under load).
        self._snap_serial += 1
        tmp = os.path.join(self.data_dir,
                           f"snap-recv-g{g}-e{ep}-{self._snap_serial}.tmp")
        ok = False

        def current() -> bool:
            return ep == self._snap_epoch.get(g, 0)

        try:
            res = self.transport.fetch_snapshot(peer, g, idx, term, tmp)
            with self._snap_cv:
                if res is None or self._stop.is_set() or not current():
                    if current():
                        self.archive.fail_pending(g)
                    return
                got_idx, got_term = res
                self._snap_fetched.append((g, got_idx, got_term, tmp))
                ok = True
        except Exception:
            log.exception("snapshot fetch failed g=%d", g)
            with self._snap_cv:
                if current():
                    self.archive.fail_pending(g)
        finally:
            if not ok:
                # Every failure path drops the partial download.
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
            with self._snap_cv:
                if current():
                    self._snap_inflight.discard(g)

    def _install_snapshots(self, fetched) -> List[Tuple[int, int, int]]:
        """Tick thread: install downloaded snapshots (reference
        restoreCheckpoint, context/RaftRoutine.java:482-541).  Applies and
        installs run on the same thread, so the reference's halt-the-apply-
        pool dance is unnecessary by construction."""
        done = []
        for g, got_idx, got_term, tmp in fetched:
            try:
                # The lane may have been closed/destroyed while the fetch
                # was in flight (purge clears archive pending): discard.
                if not self.h_active[g] or self.archive.pending(g) is None:
                    try:
                        os.unlink(tmp)
                    except OSError:
                        pass
                    continue
                snap = self.archive.install_pending(g, tmp, got_idx, got_term)
                self.dispatcher.resume_from(
                    g, Checkpoint(path=snap.path, index=snap.index))
                # The offered config applies only if the downloaded
                # snapshot IS the offered milestone (the server may have
                # rotated to a newer one, whose config we do not know —
                # then base_conf stays and AE adoption corrects it).
                pend = self._snap_conf.pop(g, None)
                cw = pend[1] if pend is not None \
                    and pend[0] == snap.index else 0
                # Durable milestone before the device adopts it (the stable-
                # record rule for snapshots, support/StableLock.java:82-91).
                if getattr(self.store, "put_conf", None) is not None:
                    self.store.set_floor(g, snap.index, snap.term,
                                         conf_word=cw)
                else:
                    self.store.set_floor(g, snap.index, snap.term)
                self._wal_floor[g] = max(self._wal_floor[g], snap.index)
                self._durable_tail_m[g] = max(self._durable_tail_m[g],
                                              snap.index)
                try:
                    self._barrier()   # poisoned stripes carved out
                    self._barrier_ok()
                except (WalNoSpace, WalSyncError):
                    # Keep the flush pending; the installed archive file
                    # itself is already durable, so the retried fetch
                    # (device re-requests) converges once space frees.
                    self._sync_pending = True
                    raise
                self.maintain.note_checkpoint(g, self.timer_ticks,
                                              snap.index)
                self.metrics["snapshots_installed"] += 1
                done.append((g, snap.index, snap.term, cw))
            except Exception:
                log.exception("snapshot install failed g=%d", g)
                self.archive.clear_pending(g)
            finally:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
        return done

    # -------------------------------------------------------------- recovery

    def _recover_machines(self) -> None:
        """Boot-time machine catch-up: if a machine lags the newest archived
        snapshot (or the WAL floor — entries below it are gone), recover it
        from the snapshot before applies start (reference bootstrap replay,
        command/admin/Administrator.java:44-57 analog).

        Visits only the groups the archive actually holds snapshots for
        (ONE root listdir) — the ``range(n_groups)`` walk cost 100k
        ``last_snapshot`` probes on a cold start, and each probe CREATED
        the group's directory as a side effect (100k mkdirs for a node
        that never checkpointed)."""
        for g in self.archive.groups_with_snapshots(self.cfg.n_groups):
            # Verify-on-recovery: a corrupt newest milestone is
            # quarantined and the walk falls back to the previous one —
            # WAL replay above the older snapshot restores the rest
            # (the store keeps entries above ITS floor, which only ever
            # advanced to milestones whose archive copy was durable).
            snap = self.archive.verified_last_snapshot(g)
            if snap is None:
                continue
            m = self.dispatcher.machine(g)
            if m.last_applied() < snap.index:
                m.recover(Checkpoint(path=snap.path, index=snap.index))
