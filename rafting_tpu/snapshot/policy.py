"""MaintainAgreement: when to checkpoint machines and compact logs.

Port of the reference's policy semantics (command/MaintainAgreement.java):
a checkpoint is triggered when enough state changes accumulated
(``state_change_threshold``), the dirty log is long enough
(``dirty_log_tolerance``) and minimum intervals elapsed
(MaintainAgreement.java:85-103); log compaction runs on its own cadence
gated on an existing snapshot (118-130).  Times here are node ticks, not
wall-clock — the policy is driven once per runtime tick.

One instance tracks ALL groups in numpy lanes (the policy itself is
vectorized; only the actual checkpoint work is per-group host code).

Beside the cadence, pressure: a group whose un-compacted log stands past
``pressure_at`` entries of its ring is due at once (``watch_ring``).  The
cadence was sized for thousands of mostly idle groups; a group that takes
``max_submit`` entries a tick fills a 64-slot ring in 8 ticks, well inside
one ``snap_min_interval``, and from then on its intake is whatever the
calendar releases.
"""

from __future__ import annotations

import numpy as np

# Ticks from "due" to room in the ring: the tick that serializes the
# machine, the one that harvests the finished archive copy and grants the
# compaction, the step that applies the grant; and a save releases nothing
# past its own index, so the entries accepted but not yet applied when it
# was cut (two ticks of intake at a settled round trip) stay.  A group is
# under pressure once its ring no longer has room for that many ticks of
# full intake.
RELEASE_TICKS = 5
# ... but never before a quarter of the ring is used: where ``max_submit``
# is large against ``log_slots`` the room above would be the whole ring,
# and every entry would ask for a checkpoint.
PRESSURE_FLOOR_SHARE = 0.25


class MaintainAgreement:
    def __init__(self, n_groups: int, *,
                 state_change_threshold: int = 64,
                 dirty_log_tolerance: int = 16,
                 snap_min_interval: int = 20,
                 compact_min_interval: int = 10,
                 compact_slack: int = 8):
        G = n_groups
        self.state_change_threshold = state_change_threshold
        self.dirty_log_tolerance = dirty_log_tolerance
        self.snap_min_interval = snap_min_interval
        self.compact_min_interval = compact_min_interval
        self.compact_slack = compact_slack
        # Phase-stagger the cadences across groups: groups booted together
        # would otherwise cross their thresholds TOGETHER, turning
        # maintenance into a synchronized storm (thousands of checkpoint
        # file copies in one tick — a multi-second stall at 8k+ groups)
        # instead of a steady trickle.
        self.last_snap_tick = -(np.arange(G, dtype=np.int64)
                                % max(snap_min_interval, 1))
        self.last_compact_tick = -(np.arange(G, dtype=np.int64)
                                   % max(compact_min_interval, 1))
        self.snap_index = np.zeros(G, np.int64)     # newest archived snapshot
        self.applied_at_snap = np.zeros(G, np.int64)
        # Entries of un-compacted log past which a group is under
        # pressure; None until the node says what ring it runs
        # (watch_ring), and then no group ever is.
        self.pressure_at = None
        # What the last need_checkpoint / compact_targets call owed to
        # pressure alone: [G] bool, for the node's counters.
        self.ckpt_pressed = np.zeros(G, bool)
        self.compact_pressed = np.zeros(G, bool)

    def watch_ring(self, log_slots: int, max_submit: int) -> None:
        """The engine shape this policy maintains (the node calls it once,
        whoever built the policy): fixes ``pressure_at``.  With the shape
        ``RaftConfig`` ships (64 slots, 8 a tick) that is 24 entries,
        three eighths of the ring."""
        self.pressure_at = max(log_slots - RELEASE_TICKS * max_submit,
                               int(log_slots * PRESSURE_FLOOR_SHARE))

    def pressed(self, frontier: np.ndarray,
                log_base: np.ndarray) -> np.ndarray:
        """[G] bool: groups whose un-compacted log up to ``frontier`` (the
        applied or the commit index) stands past ``pressure_at``."""
        if self.pressure_at is None:
            return np.zeros(len(log_base), bool)
        return frontier - log_base > self.pressure_at

    def need_checkpoint(self, now: int, applied: np.ndarray,
                        log_base: np.ndarray) -> np.ndarray:
        """[G] bool: machines whose state moved enough to checkpoint
        (MaintainAgreement.needMaintain, 85-103), or whose ring is under
        pressure and would release something by one."""
        changed = applied - self.applied_at_snap
        dirty = applied - log_base
        due = now - self.last_snap_tick >= self.snap_min_interval
        by_cadence = ((changed >= self.state_change_threshold)
                      & (dirty >= self.dirty_log_tolerance) & due)
        self.ckpt_pressed = (self.pressed(applied, log_base)
                             & (changed > 0) & ~by_cadence)
        return by_cadence | self.ckpt_pressed

    def note_checkpoint(self, g: int, now: int, index: int) -> None:
        self.last_snap_tick[g] = now
        self.snap_index[g] = index
        self.applied_at_snap[g] = index

    def compact_targets(self, now: int, commit: np.ndarray,
                        log_base: np.ndarray) -> np.ndarray:
        """[G] int: compact-to index per group (0 = keep).  Compaction never
        passes the newest snapshot (the reference gates flush on the
        snapshot milestone, RaftRoutine.compactLog:365-400) and keeps
        ``compact_slack`` committed entries for briefly-lagging followers;
        a ring under pressure is compacted without waiting out the
        interval, to the same target."""
        due = now - self.last_compact_tick >= self.compact_min_interval
        pressed = self.pressed(commit, log_base)
        target = np.minimum(self.snap_index,
                            np.maximum(commit - self.compact_slack, 0))
        target = np.where((due | pressed) & (target > log_base), target, 0)
        self.compact_pressed = pressed & ~due & (target > 0)
        if target.any():
            self.last_compact_tick = np.where(
                target > 0, now, self.last_compact_tick)
        return target.astype(np.int64)
