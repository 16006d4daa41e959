"""The real LSM storage plane: a multi-tree ``StorageGroup`` of
``LSMTree``s sharing one I/O plane, with ``LSMEngine`` as the 1-tree
instantiation.

Ownership split
===============

``LSMTree`` (per tree — one primary tree, plus one sibling tree per
secondary index) owns everything whose state is a single LSM tree:

* the memtable plane (``active``/``sealed``) and its flush queue;
* the run levels (``tables``/``_order``) and the scheduling-plane
  metadata (``meta``, a ``component.LSMTree``) the merge POLICY reads;
* the cached read view + Bloom filter stack (the fused-probe operand);
* the merge policy, per-tree merge SCHEDULER, write constraint, and the
  streaming-merge cursor state of its running merges;
* per-tree stats and flush-quantum debt.

``StorageGroup`` owns everything cross-cutting EXACTLY ONCE:

* the ``ExecBackend`` (every kernel-vs-host decision, all trees);
* the group-committed ``WriteAheadLog`` — ONE log whose frames carry a
  tree id, with GLOBAL LSNs numbering entries in group admission order
  (primary writes and the index maintenance they induce interleave in
  one total order, which is what makes multi-tree recovery a prefix
  property);
* the I/O budget: each ``pump(budget)`` epoch first syncs/repays WAL
  traffic, then splits the remainder ACROSS TREES by background debt
  via ``apportion_largest_remainder`` (the same largest-remainder
  apportionment the per-tree scheduler and the fleet arbiter use), so
  primary compaction, index compaction and durability all draw from the
  paper's single-disk write budget;
* the reentrant lock, the virtual clock ``now``, snapshots
  (``checkpoint.EngineSnapshotStore`` saves every tree's runs + a
  per-tree ``flushed_lsn``), and recovery (``wal.RecoverySession``
  replays the WAL suffix over N trees, routing frames by tree id).

``LSMEngine`` subclasses ``StorageGroup`` with no secondary indexes:
the single-tree engine every existing caller (fleet, twophase, faults,
benchmarks) keeps using.  The group mirrors the legacy engine surface —
``active``/``sealed``/``tables``/``stats``/``seal_active``/
``_read_view``/… delegate to the primary tree — so 1-tree behavior is
bit-identical to the pre-split engine.

Secondary indexes
=================

An index (``IndexSpec``) is a sibling LSM tree mapping a uint32
ATTRIBUTE (``extract(value)``; default = the value's low 32 bits) to
the primary key (stored as the index tree's int32 value, so primary
keys must stay below 2**31 in indexed groups).  Newest-wins dedup makes
it a unique index: one primary key per attribute.  Both maintenance
strategies from the paper (fig25-27) are real:

* **eager** — on every put/delete the group resolves the OLD value
  first (real point lookups through the fused probe, batched per
  admitted chunk with intra-chunk occurrences resolved in-memory),
  deletes the stale index entry (tombstone) and inserts the new one.
  The index tree is exact at all times, so ``index_scan`` is a COVERING
  scan and ``index_lookup`` is one probe of the index tree.
* **lazy** — puts append ``attr -> pk`` blindly (no lookup, no stale
  deletion; deletes touch the index not at all), and every index READ
  validates candidates against the primary: an entry counts only if the
  primary's current value still maps to that attribute.  Ingestion is
  cheaper; reads pay the validation probe.

Index maintenance entries are WAL-framed under the index tree's id
BEFORE admission (crash point ``post-primary-pre-index`` sits between
the primary admit and the index admit), admitted stall-free
(``force_admit`` — the primary's gate already paced the batch), and
flushed/merged by the index tree under the shared budget.

Execution model (per tree, unchanged from the pre-split engine)
===============================================================

Deterministic cooperative quanta: flushes take strict priority, then
merges per the tree scheduler's allocation.  All background work is
STREAMED so one quantum costs O(quantum): a merge keeps per-run
cursors, cuts each window at a global key boundary (the merge-path
pivot — no equal-key group straddles windows, so concatenated windows
are bit-identical to the one-shot merge), and accumulates output into
preallocated host buffers that ``_finish_merge`` binds as O(1)
views.  Flushes larger than the remaining quantum carry their overshoot
as per-tree debt repaid before new work.

Read view contract (per tree): point reads and scans go through a
cached ``_ReadView`` over the disk tables, NEWEST-FIRST by
``(-data_stamp, level)``, maintained INCREMENTALLY — a flush prepends
one table, a merge completion bisect-inserts its outputs; no re-sort.
The Bloom stack (``_FilterStack``) is event-driven: background events
journal adds/removes in O(1) and the first point lookup after an event
applies the journal (donated device row writes, host mirror in
lockstep).  ``get_batch`` walks the view newest-first with early exit
behind ONE fused multi-table probe; ``scan_range`` resolves every run's
window in one k-way newest-wins merge (tombstones filtered in-merge).

Backend / dispatch: every launch routes through the group's ONE
``ExecBackend`` (host-vs-kernel per op per size class from the measured
calibration artifact; the three legacy booleans map to forced modes via
``ExecBackend.from_legacy`` and are exposed read-only).

Spans and counters: every get/put/scan/pump call opens an ``lsm.*``
span and one child span per step (``core/tracing.py``), recorded only
while a profiler trace runs; the wait for the group lock is ``lsm.lock``.
The counters are the trees' ``stats``; beside the flush, merge and WAL
counts, each probe launch adds ``probe_cells`` (the (row, key) cells
it probed: the probe prunes each row to the keys inside its table's key
range) and ``probe_live_cells`` (the (row, key) pairs whose table's key
range holds the key, counted from the filter stack's ranges); a get's
``bloom_skips`` are the probed cells its filters ruled out.

Thread safety: every foreground entry point and the background plane
take the GROUP's reentrant lock internally; ``lock()`` exposes it for
compound atomicity.  ``scan_range`` releases it for the merge itself
(run windows are immutable snapshots).

Durability contract (group-owned; ``core/wal.py``)
==================================================

* With no WAL the group is volatile (the seed's behavior).
* With a WAL, every admitted chunk — primary puts/deletes AND the index
  maintenance entries they induce — is appended as one tree-tagged
  frame BEFORE its memtable admits it, so the admitted history and the
  log agree entry-for-entry (global LSN == group admission index).
  fsyncs happen at ``group_commit_entries`` and at every pump epoch;
  synced traffic is charged to the group's WAL debt and repaid from the
  budget ahead of all trees.
* ``flushed_lsn`` is per tree (everything below the oldest unflushed
  memtable's ``start_lsn`` is in that tree's SSTables); the group's is
  their MINIMUM — the snapshot's WAL-truncation point (segment-granular:
  the log drops whole sealed segments below it, so ``wal.start_lsn``
  may land before it and replay skips the overlap per tree).
* Recovery: restore each tree's snapshot section, then replay
  ``wal.frames_since`` in global LSN order, routing frames by tree id
  and skipping, inside a frame, the prefix below that tree's snapshot
  origin.  The recovered group answers every read bit-identically to an
  uncrashed group fed the same durable prefix — per tree
  (``tests/test_durability.py`` pins this, crash points x policies, and
  the multi-tree crash between primary admit and eager index
  maintenance).
* Tombstones: deletes admit the reserved ``TOMBSTONE`` value through
  the ordinary write path (WAL, memtable, flush, merge carry it as
  data); the read plane hides it; merges nothing-older overlaps drop
  them (``compact_all`` reclaims space-amp to ~1).  Eager index
  maintenance writes the same tombstones into index trees to kill stale
  entries.

Online recovery and the fault-tolerance plane
=============================================

A ``RecoverySession(online=True)`` reopens the group FOR TRAFFIC before
replay finishes.  The consistency contract:

* **Watermark**: ``_replay_watermark`` is the durable replay frontier —
  every LSN below it has been re-admitted.  Reads observe exactly
  ``durable prefix up to the watermark + live writes``; the watermark
  only advances.
* **Fresh-segment rule**: the session rotates the WAL tail at open, so
  frames written by live traffic never interleave with the frames being
  replayed; the group LSN jumps to the live frontier (max of the log's
  end and the replay end) before the first live write.
* **Live writes win**: per-tree ``_live_keys`` records keys written
  since the reopen; the replay step drops those keys' history (the
  memtable is newest-wins by insertion order, so un-filtered replay
  would resurrect stale values).
* Replay itself is a pump-driven debt stream: ``_pump_locked``
  arbitrates it against flush/merge/WAL debt via the same
  largest-remainder split, so a starved budget slows FULL recovery but
  never time-to-first-read.  ``seal_active`` and the group
  ``flushed_lsn`` cap their LSN claims at the watermark — snapshot
  truncation can never drop un-replayed WAL.

Transient I/O faults (``core/iostack.py``) retry with capped
exponential backoff; ENOSPC surfaces as ``StorageFull`` and is absorbed
as a constraint stall (writes refuse work, drain when space returns) —
never data loss.  A background ``Scrubber`` (``enable_scrub``) streams
CRC verification over live tables from the pump budget; a corrupt table
is quarantined (out of the read view immediately), repaired from the
snapshot store or by whole-tree WAL rebuild, and only when no durable
copy survives does the tree turn ``corrupt`` — after which reads raise
``UnrepairableCorruptionError``, a typed error instead of a wrong
answer.  ``health()`` exposes the fault-plane counters.
"""
from __future__ import annotations

import bisect
import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.bloom.ops import device_stack, set_stack_row, stack_width
from .backend import HOST, ExecBackend, merge_kway_host  # noqa: F401
                                        # (merge_kway_host re-export: the
                                        # fleet's scan gather shares it)
from .component import Component, MergeOp
from .component import LSMTree as ComponentTree
from .constraints import ComponentConstraint, NoConstraint
from .iostack import StorageFull, UnrepairableCorruptionError
from .memtable import (MemTable, SENTINEL_KEY, TOMBSTONE,
                       drop_tombstones)
from .policies import MergePolicy
from .scheduler import (FairScheduler, MergeScheduler,
                        apportion_largest_remainder)
from .sstable import SSTable
from .tracing import span

ENTRY_BYTES = 1024  # paper's 1 KB records: 1 entry == 1 KB of I/O budget


@dataclass
class _ReadView:
    """Cached snapshot of ONE tree's disk tables for the read plane.

    ``tables`` is newest-first by ``(-data_stamp, level)`` — an O(tables)
    tuple snapshot of the tree's insertion-maintained ``_order`` list.
    ``filts``/``meta`` stay ``None`` until the first point lookup applies
    the persistent ``_FilterStack``'s pending journal
    (``LSMTree._view_filters``): ``filts`` is the stack's DEVICE array
    (capacity rows, only live slots meaningful), ``meta`` the host-side
    per-row (n_bits, k, lo, hi); each table's probe row is its own
    ``stack_slot``, and ``rank`` maps a row to its table's index in
    ``tables``.  Scan-only workloads never populate them.
    """
    tables: tuple
    filts: Optional["jnp.ndarray"] = None
    meta: Optional[np.ndarray] = None
    rank: Optional[np.ndarray] = None


class _FilterStack:
    """Persistent device-side Bloom filter stack with slot reuse — the
    fused multi-table probe's operand, maintained incrementally and
    EVENT-DRIVEN (one stack per tree).

    The tree notes every table add/remove as it happens
    (``note_add``/``note_remove``, O(1) bookkeeping, NO device work — so
    background quanta and scan-only workloads never touch the stack).
    ``sync(tables)``, called on the first point lookup after a view
    rebuild, applies the pending journal: removed tables free their
    rows; each added table takes a free row via ONE donated device row
    write (``set_stack_row``, O(filter width)) and records the row in
    ``SSTable.stack_slot`` so the probe path needs no per-view gather.
    An add whose table is merged away before any read CANCELS against
    its remove — its filter row (and, with lazy Bloom construction, the
    filter itself) is never built at all.

    The stack is rebuilt from scratch only when capacity or row width
    must grow or occupancy falls below 1/4 of capacity — geometric
    sizing, amortized O(rows changed) per background event instead of
    the O(tables * filter-bytes) restack-and-reupload of the per-view
    ``stack_filters`` path this replaces.

    ``meta`` rows are (n_bits, k, lo, hi): each row's filter geometry
    and its table's first and last key, which the probe prunes by.  Free
    rows keep (128, 1, 1, 0): their ``[1, 0]`` holds no key, so no probe
    reads their stale words, and their k never inflates the probe's
    static ``k_max``.
    """

    def __init__(self):
        self.filts: Optional["jnp.ndarray"] = None   # (cap, width // 128, 128)
        self.filts_np: Optional[np.ndarray] = None   # host mirror of the
                                                     # stack — the backend's
                                                     # HOST probe operand
        self.meta = np.zeros((0, 4), np.uint32)      # host (cap, 4)
        self.slots: dict[int, int] = {}              # component cid -> row
        self.free: list[int] = []
        self._add: dict[int, SSTable] = {}           # pending, cid-keyed
        self._remove: list[int] = []                 # pending, cids

    @property
    def cap(self) -> int:
        return 0 if self.filts is None else int(self.filts.shape[0])

    @property
    def width(self) -> int:
        return 0 if self.filts_np is None else int(self.filts_np.shape[1])

    def note_add(self, table: SSTable) -> None:
        self._add[table.component.cid] = table

    def note_remove(self, cid: int) -> None:
        if self._add.pop(cid, None) is not None:
            return                       # never materialized: cancelled
        if cid in self.slots:
            self._remove.append(cid)

    def _rebuild(self, tables) -> None:
        cap = max(4, 2 * len(tables))
        width = stack_width(max((t.bloom_host().shape[0] for t in tables),
                                default=1))
        stk = np.zeros((cap, width), np.uint32)
        self.meta = np.tile(np.array(_FREE_ROW, np.uint32), (cap, 1))
        self.slots = {}
        for i, t in enumerate(tables):
            w = t.bloom_host()
            stk[i, :w.shape[0]] = w
            self.meta[i] = _row_meta(t)
            self.slots[t.component.cid] = i
            t.stack_slot = i
        self.free = list(range(len(tables), cap))
        self.filts_np = stk
        self.filts = device_stack(stk)   # independent device copy: row
                                         # writes donate the device buffer
                                         # and must never alias the mirror
        self._add.clear()
        self._remove.clear()

    def sync(self, tables) -> tuple["jnp.ndarray", np.ndarray]:
        """Apply the pending add/remove journal; returns
        ``(filts, meta)`` (probe rows come from each table's
        ``stack_slot``).  The previous device array is donated by row
        writes — every external reference must be replaced by the
        returned one."""
        if self.filts is None:
            self._rebuild(tables)
            return self.filts, self.meta
        for cid in self._remove:
            row = self.slots.pop(cid, None)
            if row is not None:
                self.free.append(row)
                self.meta[row] = _FREE_ROW
        self._remove.clear()
        if self._add:
            adds = list(self._add.values())
            need_w = max(t.bloom_host().shape[0] for t in adds)
            n_live = len(self.slots) + len(adds)
            if need_w > self.width or len(adds) > len(self.free) \
                    or (self.cap > 8 and 4 * n_live < self.cap):
                self._rebuild(tables)
                return self.filts, self.meta
            for t in adds:
                row = self.free.pop()
                words = t.bloom_host()
                if words.shape[0] != self.width:
                    padded = np.zeros(self.width, np.uint32)
                    padded[:words.shape[0]] = words
                    words = padded
                self.filts = set_stack_row(self.filts, words, row)
                self.filts_np[row] = words        # keep the host mirror
                                                  # (HOST probe operand)
                                                  # in lockstep
                self.meta[row] = _row_meta(t)
                self.slots[t.component.cid] = row
                t.stack_slot = row
            self._add.clear()
        elif self.cap > 8 and 4 * len(self.slots) < self.cap:
            self._rebuild(tables)
        return self.filts, self.meta

    def live_cells(self, keys) -> int:
        """Rows x keys a probe of ``keys`` needs: the (row, key) pairs
        whose table's key range holds the key, the only rows that can
        answer it.  One sort and two searches, whatever the rows."""
        sk = np.sort(np.asarray(keys, np.uint32))
        held = (np.searchsorted(sk, self.meta[:, 3], "right")
                - np.searchsorted(sk, self.meta[:, 2], "left"))
        return int(np.maximum(held, 0).sum())


#: a free stack row's (n_bits, k, lo, hi): its key range holds no key
_FREE_ROW = (128, 1, 1, 0)


def _row_meta(t: SSTable) -> tuple[int, int, int, int]:
    """A table's stack row: filter geometry, first and last key (an
    empty table's ``[1, 0]`` holds none)."""
    lo, hi = (int(t.keys_np[0]), int(t.keys_np[-1])) if len(t) else (1, 0)
    return t.n_bits, t.k_hashes, lo, hi


@dataclass
class _RunningMerge:
    op: MergeOp
    inputs: list[SSTable]
    drop: bool = False         # reclaim tombstones (bottom-level merge)
    # -- streaming cursor state (opened lazily by ``_open_merge``) -----
    tables: Optional[list] = None          # inputs sorted newest-first
    run_keys: Optional[list] = None        # per-run host key mirrors
    run_vals: Optional[list] = None
    cursors: Optional[np.ndarray] = None   # per-run consumed prefix
    lens: Optional[np.ndarray] = None
    # merged-but-unreleased output: windows are written incrementally
    # into PREALLOCATED host buffers (capacity = sum of input lens,
    # allocated once at ``_open_merge``) so ``_finish_merge`` binds the
    # finished table as O(1) views — no O(merge-size) concatenate
    buf_keys: Optional[np.ndarray] = None
    buf_vals: Optional[np.ndarray] = None
    # True while every window ran on the device: the finished table then
    # keeps a device copy of its output (placed once, at finish)
    on_device: bool = True
    emitted: int = 0           # post-dedup entries emitted so far
    tombs_in: int = 0          # input tombstones seen in consumed windows
                               # (counted per quantum: O(consumed), so the
                               # finish step never scans the inputs)
    # -- legacy one-shot state (``streaming_merge=False`` baseline) ----
    cursor: int = 0            # entries of the merged stream already emitted
    merged_keys: Optional[np.ndarray] = None
    merged_vals: Optional[np.ndarray] = None
    # owning tree (None on hand-built cursors: the group defaults to the
    # primary) — lets the GROUP dispatch advance/finish per merge, so
    # instance-level instrumentation on the engine sees every tree's
    # merges
    tree: Optional["LSMTree"] = field(default=None, repr=False)


def _identity_attr(vals: np.ndarray) -> np.ndarray:
    """Default index attribute: the value's low 32 bits as uint32."""
    return (np.asarray(vals).astype(np.int64)
            & 0xFFFFFFFF).astype(np.uint32)


@dataclass(frozen=True)
class IndexSpec:
    """Declaration of one secondary index (a sibling LSM tree).

    ``mode`` picks the maintenance strategy (``"eager"`` exact-at-all-
    times vs ``"lazy"`` blind-append + read validation — see the module
    docstring).  ``extract`` maps a value array (int32) to uint32
    attributes; ``None`` = the value's low 32 bits.  Tree knobs default
    to the primary's (``policy`` is shared — policies are stateless
    config — but each index tree gets its OWN ``FairScheduler`` unless
    one is given: schedulers may carry state)."""
    name: str
    mode: str = "eager"
    extract: Optional[Callable[[np.ndarray], np.ndarray]] = None
    policy: Optional[MergePolicy] = None
    scheduler: Optional[MergeScheduler] = None
    constraint: Optional[ComponentConstraint] = None
    memtable_entries: Optional[int] = None
    num_memtables: Optional[int] = None


@dataclass
class _IndexState:
    """Resolved runtime state of one index."""
    name: str
    mode: str
    extract: Callable[[np.ndarray], np.ndarray]
    tree_id: int


class LSMTree:
    """One LSM tree of a ``StorageGroup``: memtable plane, run levels,
    read view + filter stack, merge policy/scheduler/constraint, and the
    streaming-merge state of its running merges.  Cross-cutting concerns
    (backend, WAL, budget, lock, clock, faults) live on ``self.group``
    and are owned exactly once — see the module docstring."""

    def __init__(self, group: "StorageGroup", tree_id: int, name: str,
                 policy: MergePolicy, scheduler: MergeScheduler,
                 constraint: ComponentConstraint, memtable_entries: int,
                 num_memtables: int, unique_keys: float,
                 streaming_merge: bool):
        self.group = group
        self.tree_id = int(tree_id)
        self.name = name
        self.policy = policy
        self.scheduler = scheduler
        self.constraint = constraint
        self.memtable_entries = int(memtable_entries)
        self.num_memtables = int(num_memtables)
        self.unique_keys = unique_keys
        self.streaming_merge = bool(streaming_merge)
        self.meta = ComponentTree(unique_keys=unique_keys)  # scheduling-
                                                # plane model (policy input)
        self.active = MemTable(self.memtable_entries)
        self.active.start_lsn = group._lsn
        self.sealed: list[MemTable] = []
        self.tables: dict[int, SSTable] = {}     # component id -> SSTable
        self._order: list[SSTable] = []          # newest-first (see module
                                                 # docstring: insertion-
                                                 # maintained, no re-sort)
        self._fstack = _FilterStack()            # lazy device filter stack
        self._view: Optional[_ReadView] = None   # cached read view
        self._view_epoch = 0                     # bumped on invalidation
        self.running: dict[int, _RunningMerge] = {}
        self.pending_flush: list[tuple[np.ndarray, np.ndarray]] = []
        self._stamp = 0
        self.stalled = False
        self._flush_debt = 0             # flush-quantum overshoot owed
        self._live_keys: Optional[set] = None   # keys written since an
                                         # online-recovery reopen (the
                                         # replay step drops history for
                                         # them — live writes win)
        self.corrupt = False             # unrepairable corruption: reads
                                         # raise, never answer wrong
        self.stats = {"puts": 0, "stall_events": 0, "flushes": 0,
                      "merges": 0, "merge_bytes": 0, "merge_touched": 0,
                      "lookups": 0, "bloom_skips": 0,
                      "deletes": 0, "replayed": 0, "tombstones_dropped": 0,
                      "flush_bytes": 0, "logical_bytes": 0,
                      "probe_cells": 0, "probe_live_cells": 0}

    # ------------------------------------------------------------ memtables
    def seal_active(self, next_start_lsn: Optional[int] = None) -> None:
        """Seal the active memtable (it becomes a flush candidate) and
        open a fresh one whose ``start_lsn`` is the group's current WAL
        position — the bookkeeping behind ``flushed_lsn``.  Group-
        internal admission paths that seal MID-chunk (``force_admit``)
        pass the LSN of the chunk's next entry instead, since the chunk
        was WAL-framed before any of it was admitted.

        During ONLINE recovery the new memtable's origin is capped by
        the replay watermark: the active memtable mixes live writes
        (LSN >= the live frontier) with replayed history (LSN < the
        watermark), so the only safe ``flushed_lsn`` claim is the
        watermark — snapshot truncation must never drop un-replayed
        WAL."""
        self.sealed.append(self.active)
        self.active = MemTable(self.memtable_entries)
        lsn = self.group._lsn if next_start_lsn is None \
            else int(next_start_lsn)
        wm = self.group._replay_watermark
        if wm is not None:
            lsn = min(lsn, wm)
        self.active.start_lsn = lsn

    def _refresh_stall(self):
        self.stalled = self.constraint.violated(self.meta)

    def force_admit(self, keys, vals, base_lsn: int) -> None:
        """Stall-free admission for group-internal writes (index
        maintenance): seals past ``num_memtables`` freely — the
        primary's admission gate already paced the batch, and the extra
        sealed memtables are background debt the next pump epochs repay.
        ``base_lsn`` is the chunk's WAL frame base (already logged), so
        a mid-chunk seal stamps the new memtable at the exact LSN of the
        first entry it will hold."""
        keys = np.asarray(keys, np.uint32)
        vals = np.asarray(vals, np.int32)
        n = len(keys)
        pos = 0
        while pos < n:
            if self.active.full:
                self.seal_active(next_start_lsn=base_lsn + pos)
            take = min(n - pos, self.active.capacity - len(self.active))
            took = self.active.put_batch(keys[pos:pos + take],
                                         vals[pos:pos + take])
            assert took == take, "memtable admitted less than its room"
            pos += take

    def replay_admit(self, keys, vals) -> int:
        """Recovery-only admission: entries already durable in the WAL
        re-enter the memtable plane WITHOUT re-logging and WITHOUT
        constraint stalls.  Callers size chunks to the active memtable's
        room and maintain the group's LSN clock (``RecoverySession``
        does both)."""
        keys = np.asarray(keys, np.uint32)
        vals = np.asarray(vals, np.int32)
        if self.active.full:
            self.seal_active()
        took = self.active.put_batch(keys, vals)
        assert took == len(keys), "replay chunk exceeded memtable room"
        self.stats["replayed"] += took
        return took

    # ------------------------------------------------------------------ read
    def _read_view(self) -> _ReadView:
        """The cached read view (see module docstring for the contract):
        an O(tables) snapshot of the insertion-maintained ``_order`` list
        — no sorting, no filter work (filters sync lazily in
        ``_view_filters``).  Epoch-guarded against the wall-clock driver:
        if a flush/merge invalidates mid-build, the snapshot serves this
        call but is NOT cached, so a stale view can never become
        sticky."""
        view = self._view
        if view is None:
            epoch = self._view_epoch
            view = _ReadView(tuple(self._order))
            if epoch == self._view_epoch:
                self._view = view
        return view

    def _view_filters(self, view: _ReadView):
        """Lazily apply the filter stack's pending add/remove journal
        (first point lookup after a background event pays O(rows
        changed); scans never call this).  Returns ``(filts, meta)`` —
        ``None``s when the bloom kernels are unavailable."""
        if view.filts is None and view.tables and set_stack_row is not None:
            view.filts, view.meta = self._fstack.sync(self._order)
            view.rank = np.zeros(len(view.meta), np.int64)
            view.rank[[t.stack_slot for t in view.tables]] = \
                np.arange(len(view.tables))
        return view.filts, view.meta

    def _invalidate_view(self):
        self._view_epoch += 1
        self._view = None

    @staticmethod
    def _order_key(t: SSTable):
        """Newest-first rank of a table in the read view / merge order."""
        return (-t.data_stamp, t.component.level if t.component else 0)

    def get_batch_locked(self, keys) -> tuple[np.ndarray, np.ndarray]:
        """Vectorized newest-wins lookup over THIS tree (group lock
        held): memtables newest-first, then ONE fused Bloom probe of
        the unresolved keys across all disk tables, each table only for
        the keys inside its key range, then sorted searches only for the
        maybe-present (table, key) pairs, newest table first.  Returns
        (found, values); tombstone hits resolve the key but report "not
        found"."""
        if self.corrupt:
            raise UnrepairableCorruptionError(
                f"tree {self.name!r} has unrepairable corruption — "
                "refusing to serve reads")
        q = len(keys)
        self.stats["lookups"] += q
        resolved = np.zeros(q, bool)
        vals = np.zeros(q, np.int32)
        with span("lsm.get.memtables"):
            for mt in (self.active, *reversed(self.sealed)):
                if resolved.all():
                    break
                f, v = mt.get_batch(keys)
                new = f & ~resolved
                vals[new] = v[new]
                resolved |= new
        if not resolved.all():
            with span("lsm.get.filters"):
                view = self._read_view()
                filts, meta = self._view_filters(view)
            if view.tables:
                # ONE probe of the pending keys over the full stack
                # (capacity rows, <= 2x live tables), each row only for
                # the keys inside its table's key range; each table's
                # row is its own stack_slot — no gather.  The backend
                # picks host vs kernel; the host path probes the stack's
                # host mirror.
                pend = np.flatnonzero(~resolved)
                with span("lsm.get.probe"):
                    hits, probed = self.group.backend.probe_multi(
                        filts, meta, keys[pend],
                        filts_host=self._fstack.filts_np)
                self.stats["probe_cells"] += probed
                self.stats["probe_live_cells"] += \
                    self._fstack.live_cells(keys[pend])
                self.stats["bloom_skips"] += probed - len(hits.rows)
                with span("lsm.get.search"):
                    # only tables with a maybe-present key, newest first
                    rank = view.rank[hits.rows]
                    order = np.argsort(rank, kind="stable")
                    tabs, first = np.unique(rank[order], return_index=True)
                    for r, idx in zip(tabs, np.split(pend[hits.keys[order]],
                                                     first[1:])):
                        idx = idx[~resolved[idx]]
                        if not len(idx):
                            continue
                        f, v = view.tables[r].search(keys[idx])
                        hit = idx[f]
                        vals[hit] = v[f]
                        resolved[hit] = True
        found = resolved & (vals != TOMBSTONE)
        vals = np.where(found, vals, 0).astype(np.int32)
        return found, vals

    def _scan_runs(self, lo: int, hi: int) -> list[tuple[np.ndarray,
                                                         np.ndarray]]:
        """Per-run ``[lo, hi)`` windows, NEWEST first (active memtable,
        sealed memtables newest-first, then the read view's tables) —
        the age order the k-way merge dedups by.  Empty windows are
        dropped."""
        if self.corrupt:
            raise UnrepairableCorruptionError(
                f"tree {self.name!r} has unrepairable corruption — "
                "refusing to serve scans")
        runs: list[tuple[np.ndarray, np.ndarray]] = []
        for mt in (self.active, *reversed(self.sealed)):
            ks, vs = mt.scan_range(lo, hi)
            if len(ks):
                runs.append((ks, vs))
        for table in self._read_view().tables:
            ks, vs = table.scan_range(lo, hi)
            if len(ks):
                runs.append((ks, vs))
        return runs

    def scan_range(self, lo: int, hi: int) -> tuple[np.ndarray, np.ndarray]:
        """Newest-wins range scan over THIS tree: sorted (keys, values)
        for ``lo <= key < hi``, resolved across all live runs in one
        k-way merge.  The run-window snapshot runs under the group lock;
        the merge itself runs OUTSIDE it (the captured windows are
        immutable snapshots), so a large scan never extends the pump's
        lock-hold tail."""
        with span("lsm.scan"):
            with self.group._locked(), span("lsm.scan.runs"):
                runs = self._scan_runs(lo, hi)
            if not runs:
                return np.empty(0, np.uint32), np.empty(0, np.int32)
            with span("lsm.scan.merge"):
                if len(runs) == 1:
                    # copy: the windows are views into live run storage
                    # (sealed caches / host mirrors), which callers must
                    # not alias.  Tombstones are filtered like any other
                    # scan result.
                    ks, vs = drop_tombstones(runs[0][0], runs[0][1])
                    return ks.copy(), vs.copy()
                # the backend fuses tombstone filtering into its merge
                # (kernel: the compaction mask; host: drop_tombstones on
                # the merged run)
                return self.group.backend.scan_merge(
                    runs, drop_value=int(TOMBSTONE))

    # ------------------------------------------------------- background I/O
    def pump_tree(self, budget_entries: int) -> int:
        """Advance THIS tree's background work by its quantum of the
        group epoch (group lock held): repay flush-overshoot debt, then
        flushes at strict priority, then merges per the tree scheduler's
        allocation (largest-remainder apportionment, never exceeding the
        quantum).  Returns entries actually charged."""
        g = self.group
        spent = 0
        repay = min(self._flush_debt, budget_entries)
        self._flush_debt -= repay
        spent += repay
        while self.sealed and spent < budget_entries:
            g._fault("pre-flush")
            with span("lsm.pump.flush"):
                mt = self.sealed.pop(0)
                keys, vals = mt.seal()
                table = SSTable.build(
                    keys, vals, level=self.policy.flush_target_level(),
                    created_at=g.now)
                self._bind_table(table)
            self.stats["flushes"] += 1
            self.stats["flush_bytes"] += len(keys) * ENTRY_BYTES
            cost = len(keys)
            avail = budget_entries - spent
            if cost > avail:
                # atomic flush overshoot carried as debt (see pump)
                self._flush_debt += cost - avail
                spent = budget_entries
            else:
                spent += cost
            self._collect_merges()
        if spent >= budget_entries:
            return spent
        self._collect_merges()
        ops = [rm.op for rm in self.running.values()]
        alloc = self.scheduler.allocate(ops) if ops else {}
        remaining = budget_entries - spent
        shares = sorted((op_id, frac) for op_id, frac in alloc.items()
                        if frac > 0)
        if shares and remaining > 0:
            quanta = apportion_largest_remainder(shares, remaining)
            for (op_id, _), quantum in zip(shares, quanta):
                if quantum > 0:
                    # dispatch through the GROUP so instance-level
                    # instrumentation (tests wrap eng._advance_merge)
                    # sees every tree's merges
                    with span("lsm.pump.merge"):
                        spent += g._advance_merge(self.running[op_id],
                                                  quantum)
            assert spent <= budget_entries, \
                "merge quanta exceeded the pump budget"
        return spent

    def _bind_table(self, table: SSTable) -> None:
        """Register a freshly built run as this tree's NEWEST table:
        stamp it, enter it into the scheduling plane and the read plane
        (prepend to ``_order`` — O(1) rank — and journal the filter-stack
        add).  The flush path binds through here; benchmarks use it to
        inject preloaded runs with flush-identical semantics."""
        self._stamp += 1
        table.data_stamp = self._stamp
        table.component.stamp = float(self._stamp)
        table.seal_checksum()
        self.meta.add(table.component)
        self.tables[table.component.cid] = table
        self._order.insert(0, table)
        self._fstack.note_add(table)
        self._invalidate_view()

    def _collect_merges(self):
        for op in self.policy.collect_merges(self.meta, self.group.now):
            inputs = [self.tables[c.cid] for c in op.inputs]
            self.running[op.op_id] = _RunningMerge(op=op, inputs=inputs,
                                                   tree=self)

    def pending_entries(self) -> int:
        """This tree's background I/O debt in entries (group lock held):
        flush-quantum debt, sealed memtables awaiting flush, and the
        unconsumed inputs of every running merge.  The group's pump
        epoch apportions its budget across trees by this number."""
        self._collect_merges()
        pending = self._flush_debt + sum(len(m) for m in self.sealed)
        for rm in self.running.values():
            if rm.lens is not None:       # streaming cursor open
                pending += int((rm.lens - rm.cursors).sum())
            elif rm.merged_keys is not None:   # one-shot materialized
                pending += len(rm.merged_keys) - rm.cursor
            else:
                # unopened cursor: the inputs are the upper bound; a
                # zero-input op (hand-built test cursors) still counts
                # as live work so the group routes it budget
                pending += max(sum(len(t) for t in rm.inputs), 1)
        return pending

    # -- merge execution (the paper's unit of schedulable I/O) -------------
    def _open_merge(self, rm: _RunningMerge):
        """Set up the streaming cursor: sort inputs newest-first (the
        k-way age order — data_stamp is the data-age order; on equal
        stamps the LOWER level holds the newer version) and zero the
        per-run cursors.  No merged output is computed here: each quantum
        merges only its own window."""
        rm.tables = sorted(rm.inputs, key=self._order_key)
        rm.drop = self._tombstone_drop_safe(rm)
        hosts = [t._host() for t in rm.tables]
        rm.run_keys = [h[0] for h in hosts]
        rm.run_vals = [h[1] for h in hosts]
        rm.lens = np.array([len(k) for k in rm.run_keys], np.int64)
        rm.cursors = np.zeros(len(rm.tables), np.int64)
        # preallocate the output ONCE (dedup can only shrink it): each
        # quantum writes its window into the next buffer slice, and
        # ``_finish_merge`` binds ``buf[:emitted]`` views — the finish
        # step never concatenates or copies the merged output
        cap = int(rm.lens.sum())
        rm.buf_keys = np.empty(cap, np.uint32)
        rm.buf_vals = np.empty(cap, np.int32)

    def _tombstone_drop_safe(self, rm: _RunningMerge) -> bool:
        """May this merge reclaim tombstones?  Safe iff NO live table
        OLDER than the merge's output overlaps its key range — then a
        tombstone winner shadows nothing, so dropping it (and the data
        versions it already shadowed via dedup) changes no read.  Checked
        once at merge open against the authoritative ``_order``; tables
        born later are NEWER than the output, so the decision cannot be
        invalidated mid-merge."""
        in_cids = {t.component.cid for t in rm.inputs}
        out_key = (-max(t.data_stamp for t in rm.inputs),
                   rm.op.output_level)
        lo = min(t.component.key_lo for t in rm.inputs)
        hi = max(t.component.key_hi for t in rm.inputs)
        for t in self._order:
            if t.component.cid in in_cids:
                continue
            if self._order_key(t) > out_key and \
                    t.component.key_lo < hi and lo < t.component.key_hi:
                return False
        return True

    def _merge_cut(self, rm: _RunningMerge,
                   target: int) -> tuple[np.ndarray, int]:
        """The merge-path pivot: the largest key-boundary cut whose
        remaining input entries number at most ``target`` (binary search
        for the pivot key over the uint32 key space; per-run window ends
        via ``searchsorted`` on the host mirrors, so only O(k log n)
        entries are touched).  Cutting at a key boundary means no
        equal-key group straddles windows — per-window newest-wins dedup
        composes to the one-shot result.  When even the first key group
        exceeds ``target`` (up to k duplicates of one key), that group is
        taken whole as forced minimal progress: it emits exactly one
        entry.  Returns ``(stops, consumed)``."""
        cur, lens, ks = rm.cursors, rm.lens, rm.run_keys
        rem = int((lens - cur).sum())
        if rem <= target:
            return lens.copy(), rem

        def below(p: int) -> int:
            c = 0
            for i, k in enumerate(ks):
                if cur[i] < lens[i]:
                    c += max(0, int(np.searchsorted(k, np.uint32(p)))
                             - int(cur[i]))
            return c

        lo, hi = 0, 0xFFFFFFFF      # sentinel key never stored: p covers all
        while lo < hi:
            mid = (lo + hi + 1) // 2
            if below(mid) <= target:
                lo = mid
            else:
                hi = mid - 1
        stops = np.array(
            [min(int(lens[i]),
                 max(int(cur[i]), int(np.searchsorted(ks[i],
                                                      np.uint32(lo)))))
             for i in range(len(ks))], np.int64)
        consumed = int((stops - cur).sum())
        if consumed == 0:
            # forced progress: the whole first key group (<= k entries)
            nxt = min(int(ks[i][cur[i]]) for i in range(len(ks))
                      if cur[i] < lens[i])
            stops = np.array(
                [min(int(lens[i]),
                     max(int(cur[i]),
                         int(np.searchsorted(ks[i], np.uint32(nxt),
                                             side="right"))))
                 for i in range(len(ks))], np.int64)
            consumed = int((stops - cur).sum())
        return stops, consumed

    def _advance_merge(self, rm: _RunningMerge, quantum: int) -> int:
        """Advance one merge by ~``quantum`` output entries: cut the next
        window at a global key boundary and merge ONLY that window, so
        the work (and lock-hold time) under a live ``BackgroundDriver``
        is O(quantum + k), never O(total merge size).  Emitted entries
        (post-dedup) are what the budget is charged for, matching the
        paper's written-bytes accounting; heavy dedup therefore spends
        less than the allocated quantum rather than overshooting it."""
        self.group._fault("mid-merge-quantum")
        if not self.streaming_merge:
            return self._advance_merge_oneshot(rm, quantum)
        if rm.tables is None:
            self._open_merge(rm)
        if int((rm.lens - rm.cursors).sum()) == 0:
            self.group._finish_merge(rm)
            return 0
        starts = rm.cursors
        stops, consumed = self._merge_cut(rm, quantum)
        drop = int(TOMBSTONE) if rm.drop else None
        if rm.drop:
            # count reclaimed markers window-by-window (O(consumed)) so
            # ``_finish_merge`` never re-scans the full inputs
            rm.tombs_in += sum(
                int((rm.run_vals[i][starts[i]:stops[i]]
                     == TOMBSTONE).sum())
                for i in range(len(rm.tables)))
        wk, wv, mode = self.group.backend.merge_kway_window(
            list(zip(rm.run_keys, rm.run_vals)),
            starts.tolist(), stops.tolist(), drop_value=drop)
        take = len(wk)
        assert take <= max(quantum, 1), "window emitted beyond its quantum"
        rm.cursors = stops
        rm.buf_keys[rm.emitted:rm.emitted + take] = wk
        rm.buf_vals[rm.emitted:rm.emitted + take] = wv
        rm.on_device &= mode != HOST
        rm.emitted += take
        rm.op.written += take
        self.stats["merge_bytes"] += take * ENTRY_BYTES
        self.stats["merge_touched"] += consumed
        if int((rm.lens - rm.cursors).sum()) == 0:
            self.group._finish_merge(rm)
        return take

    def _materialize_merge(self, rm: _RunningMerge):
        """LEGACY one-shot path (``streaming_merge=False``; kept as the
        measured baseline in ``benchmarks/latency_tail.py`` and the
        streaming differential tests): compute the full merged run at the
        first quantum — an unbounded compute spike under the engine lock,
        which is exactly the cliff the streaming cursor removes."""
        self.stats["merge_touched"] += sum(len(t) for t in rm.inputs)
        tables = sorted(rm.inputs, key=self._order_key)
        rm.drop = self._tombstone_drop_safe(rm)
        drop = int(TOMBSTONE) if rm.drop else None
        if rm.drop:
            rm.tombs_in = sum(int((t._host()[1] == TOMBSTONE).sum())
                              for t in rm.inputs)
        mk, mv, _ = self.group.backend.merge_kway(
            [t._host() for t in tables], drop_value=drop)
        rm.merged_keys, rm.merged_vals = mk, mv

    def _advance_merge_oneshot(self, rm: _RunningMerge, quantum: int) -> int:
        if rm.merged_keys is None:
            self._materialize_merge(rm)
        total = len(rm.merged_keys)
        take = min(quantum, total - rm.cursor)
        if take > 0:
            # the merged run is already materialized whole; the cursor
            # only paces budget charging — finish binds it directly
            rm.cursor += take
            rm.op.written += take
            self.stats["merge_bytes"] += take * ENTRY_BYTES
        if rm.cursor >= total:
            self.group._finish_merge(rm)
        return max(take, 0)

    def _finish_merge(self, rm: _RunningMerge):
        # O(1) output binding: the streaming path binds VIEWS into the
        # preallocated buffers (no concatenate, no copy — pinned in
        # tests/test_backend.py); the one-shot baseline binds its
        # materialized arrays directly.
        if rm.buf_keys is not None:
            keys = rm.buf_keys[:rm.emitted]
            vals = rm.buf_vals[:rm.emitted]
        elif rm.merged_keys is not None:
            keys, vals = rm.merged_keys, rm.merged_vals
        else:  # finished before any quantum ran (all-empty inputs)
            keys = np.empty(0, np.uint32)
            vals = np.empty(0, np.int32)
        # a merge that ran every window on the device leaves its output
        # there: each finished table adopts a device copy, placed once
        on_device = rm.on_device and rm.buf_keys is not None
        stamp = max(t.data_stamp for t in rm.inputs)
        if rm.drop:
            # every input tombstone died here: winners to the drop mask,
            # shadowed ones to dedup — the count was accumulated window-
            # by-window (O(consumed) per quantum, never an input re-scan)
            self.stats["tombstones_dropped"] += rm.tombs_in
        # keep the policy's metadata model in sync with the real output size
        rm.op.output_size = float(len(keys))
        rm.op.written = float(len(keys))
        in_cids = {c.cid for c in rm.op.inputs}
        for cid in in_cids:
            self.tables.pop(cid, None)
            self._fstack.note_remove(cid)
        self._order = [t for t in self._order
                       if t.component.cid not in in_cids]
        outs = self.policy.complete_merge(self.meta, rm.op, self.group.now)
        # partitioned policies may split the output into several files
        def _bind(comp, ks, vs):
            dev = (jax.device_put(ks), jax.device_put(vs)) \
                if on_device and len(ks) else None
            table = SSTable.build(ks, vs, level=comp.level,
                                  created_at=self.group.now, dev=dev)
            table.component = comp
            table.data_stamp = stamp
            comp.stamp = float(stamp)
            # keep the scheduling-plane range metadata honest: the policy's
            # overlap selection must see the REAL key span, else adjacent-
            # level overlaps are missed and newest-wins breaks.  An empty
            # output file spans nothing — an empty range keeps its stale
            # stamp from shadowing future merges in the policy's
            # age-safety audit.
            if len(ks):
                comp.key_lo = float(ks[0]) / 2**32
                comp.key_hi = (float(ks[-1]) + 1) / 2**32
            else:
                comp.key_lo = comp.key_hi = 0.0
            table.seal_checksum()
            self.tables[comp.cid] = table

        if len(outs) == 1:
            _bind(outs[0], keys, vals)
        else:
            # contiguous slice VIEWS at np.array_split's boundaries (the
            # historical split), not index-gather copies
            n = max(len(outs), 1)
            sizes = np.full(n, len(keys) // n, np.int64)
            sizes[:len(keys) % n] += 1
            bounds = np.concatenate([[0], np.cumsum(sizes)])
            for j, comp in enumerate(outs):
                lo, hi = int(bounds[j]), int(bounds[j + 1])
                _bind(comp, keys[lo:hi], vals[lo:hi])
        # bisect-insert the outputs at their (-stamp, level) rank: all
        # outputs of one merge share the rank (same stamp, same level)
        # and hold disjoint key ranges, so inserting them adjacently
        # keeps the newest-first order without a full re-sort
        out_tables = [self.tables[c.cid] for c in outs]
        if out_tables:          # a policy may complete a merge to nothing
            pos = bisect.bisect_left(self._order,
                                     self._order_key(out_tables[0]),
                                     key=self._order_key)
            self._order[pos:pos] = out_tables
        for t in out_tables:
            self._fstack.note_add(t)
        self.running.pop(rm.op.op_id, None)
        self._invalidate_view()
        self.stats["merges"] += 1
        self._collect_merges()

    # ---------------------------------------------------- recovery / info
    @property
    def flushed_lsn(self) -> int:
        """First LSN NOT yet captured in THIS tree's on-disk SSTables.
        Memtables are flushed FIFO and filled in LSN order, so everything
        of this tree below the oldest unflushed memtable's ``start_lsn``
        lives in its SSTables (other trees' entries in that range are
        THEIR problem — the group's replay origin is the min over
        trees)."""
        return self.sealed[0].start_lsn if self.sealed \
            else self.active.start_lsn

    def restore_tables(self, tables, snap: dict) -> int:
        """Rebuild this tree's read view from its snapshot section (the
        recovery path): re-bind each saved run at its recorded
        (stamp, level) rank — ``_order`` re-sorts once, the filter stack
        rebuilds lazily on the first probe.  Returns the section's
        ``flushed_lsn`` (this tree's WAL replay origin)."""
        for keys, vals, tmeta in tables:
            t = SSTable.build(keys, vals, level=int(tmeta["level"]),
                              created_at=float(tmeta["created_at"]))
            t.data_stamp = int(tmeta["stamp"])
            t.component.stamp = float(tmeta["stamp"])
            t.seal_checksum()
            self.meta.add(t.component)
            self.tables[t.component.cid] = t
            self._order.append(t)
        self._order.sort(key=self._order_key)
        self._stamp = max(self._stamp, int(snap.get("stamp", 0)),
                          max((t.data_stamp for t in self._order),
                              default=0))
        self._invalidate_view()
        return int(snap.get("flushed_lsn", 0))

    def start_full_merge(self) -> bool:
        """Queue ONE merge of every live table to the deepest level (the
        ``compact_all`` step; group lock held).  Returns False when there
        is nothing to compact (<= 1 run, no tombstones)."""
        live = list(self._order)
        if not live:
            return False
        if len(live) == 1 and \
                int((live[0]._host()[1] == TOMBSTONE).sum()) == 0:
            return False            # already one run with nothing to drop
        comps = [t.component for t in live]
        op = MergeOp(inputs=comps,
                     output_level=max(self.meta.max_level(),
                                      max(c.level for c in comps)),
                     output_size=float(sum(len(t) for t in live)))
        self.running[op.op_id] = _RunningMerge(op=op, inputs=live,
                                               tree=self)
        return True

    def total_entries(self) -> int:
        return sum(len(t) for t in self.tables.values()) + \
            sum(len(m) for m in self.sealed) + len(self.active)

    def num_components(self) -> int:
        return self.meta.num_components()

    def live_entries(self) -> int:
        """Distinct keys whose newest version is NOT a tombstone — this
        tree's logical data size behind ``space_amp`` (an O(n) full-range
        scan)."""
        return int(len(self.scan_range(0, 0xFFFFFFFF)[0]))

    _merge_kway_host = staticmethod(merge_kway_host)


# canonical stats key order (the legacy engine's dict order)
_STATS_ORDER = ("puts", "stall_events", "flushes", "merges", "merge_bytes",
                "merge_touched", "lookups", "bloom_skips", "deletes",
                "replayed", "tombstones_dropped", "wal_entries", "wal_bytes",
                "wal_syncs", "flush_bytes", "logical_bytes",
                "probe_cells", "probe_live_cells")


class StorageGroup:
    """N LSM trees (one primary + one per secondary index) sharing ONE
    I/O plane: backend, WAL, budget, lock, clock, snapshots, recovery
    (see the module docstring for the ownership split).  With no
    indexes this IS the legacy single-tree engine — every legacy
    attribute/method delegates to the primary tree bit-identically —
    and ``LSMEngine`` is exactly that instantiation."""

    def __init__(self, policy: MergePolicy, scheduler: MergeScheduler,
                 constraint: ComponentConstraint | None = None,
                 memtable_entries: int = 4096, num_memtables: int = 2,
                 unique_keys: float = 1e6,
                 use_kernels: Optional[bool] = None,
                 merge_block: int = 256, interpret: Optional[bool] = None,
                 scan_use_kernels: Optional[bool] = None,
                 streaming_merge: bool = True,
                 wal=None, group_commit_entries: int = 512,
                 wal_sync_cost: int = 32, faults=None,
                 backend: "ExecBackend | str | None" = None,
                 indexes=()):
        # -- durability plane (group-owned) ----------------------------
        self.wal = wal                           # WriteAheadLog | None
        self.group_commit_entries = int(group_commit_entries)
        self.wal_sync_cost = int(wal_sync_cost)  # fixed fsync charge
                                                 # (entries of budget)
        self.faults = faults                     # FaultInjector | None
        self._lsn = wal.end_lsn if wal is not None else 0
        self._wal_debt = 0                       # synced-WAL budget owed
        self._wal_stats = {"wal_entries": 0, "wal_bytes": 0, "wal_syncs": 0}
        # -- fault-tolerance plane -------------------------------------
        self._recovery = None            # active ONLINE RecoverySession
        self._replay_watermark = None    # durable replay frontier while
                                         # recovering (None = steady state)
        self.scrubber = None             # background integrity scrub
                                         # (``enable_scrub``)
        self._health = {"enospc_stalls": 0}
        # -- execution backend (group-owned): every kernel-vs-host
        # decision lives here.  With no legacy boolean given the group
        # dispatches in "auto" mode (compiled on a TPU); the booleans map
        # to a forced-dispatch backend, interpreting kernels only when
        # ``interpret=True`` is passed explicitly.
        legacy = (use_kernels, interpret, scan_use_kernels)
        if backend is None and legacy == (None, None, None):
            backend = "auto"
        if backend is None:
            backend = ExecBackend.from_legacy(
                use_kernels=use_kernels is not False, interpret=interpret,
                scan_use_kernels=scan_use_kernels,
                merge_block=merge_block)
        elif isinstance(backend, str):
            backend = ExecBackend(mode=backend, merge_block=merge_block)
        self.backend = backend
        self.merge_block = int(backend.merge_block)
        self.streaming_merge = bool(streaming_merge)
        self._rlock = threading.RLock()
        self.now = 0.0
        self._recorder = None            # optional WriteTraceRecorder
        self.trees: list[LSMTree] = [
            LSMTree(self, 0, "primary", policy, scheduler,
                    constraint or NoConstraint(), int(memtable_entries),
                    int(num_memtables), unique_keys,
                    self.streaming_merge)]
        self._indexes: dict[str, _IndexState] = {}
        self._eager = False
        for spec in indexes:
            self.add_index(spec)

    # ----------------------------------------------------------- indexes
    def add_index(self, spec: "IndexSpec | str") -> None:
        """Declare a secondary index as a sibling tree.  Must run before
        any write is admitted — indexes are not backfilled."""
        if isinstance(spec, str):
            spec = IndexSpec(spec)
        if spec.mode not in ("eager", "lazy"):
            raise ValueError(f"unknown index mode {spec.mode!r}")
        if spec.name in self._indexes:
            raise ValueError(f"duplicate index {spec.name!r}")
        primary = self.trees[0]
        if len(primary.active) or primary.sealed or primary.tables:
            raise ValueError("indexes must be declared before any write "
                             "(no backfill)")
        tree = LSMTree(
            self, len(self.trees), spec.name,
            spec.policy or primary.policy,
            spec.scheduler or FairScheduler(),
            spec.constraint or NoConstraint(),
            spec.memtable_entries or primary.memtable_entries,
            spec.num_memtables or primary.num_memtables,
            primary.unique_keys, self.streaming_merge)
        self.trees.append(tree)
        self._indexes[spec.name] = _IndexState(
            name=spec.name, mode=spec.mode,
            extract=spec.extract or _identity_attr,
            tree_id=tree.tree_id)
        self._eager = self._eager or spec.mode == "eager"

    @property
    def index_names(self) -> tuple:
        return tuple(self._indexes)

    # ----------------------------------------------------------- backend
    def set_backend(self, backend: "ExecBackend | str") -> None:
        """Swap the execution backend (the fleet plumbs ONE shared
        backend to every shard through here).  Takes an ``ExecBackend``
        or a mode string (``"auto"``/``"host"``/``"interpret"``/
        ``"compiled"``)."""
        if isinstance(backend, str):
            backend = ExecBackend(mode=backend,
                                  merge_block=self.merge_block)
        with self._rlock:
            self.backend = backend
            self.merge_block = int(backend.merge_block)

    # Legacy dispatch flags, now READ-ONLY views of the backend's
    # configuration (no engine code branches on them anymore; they are
    # kept for callers/tests that introspect the dispatch discipline).
    @property
    def use_kernels(self) -> bool:
        lk = self.backend.legacy_use_kernels
        if lk is not None:
            return lk
        return self.backend.decide("merge_kway", 1 << 20) != "host"

    @property
    def scan_use_kernels(self) -> bool:
        lk = self.backend.legacy_scan_use_kernels
        if lk is not None:
            return lk
        return self.backend.decide("scan_merge", 1 << 20) != "host"

    # -------------------------------------------------------- fault hooks
    def _fault(self, point: str) -> None:
        """Hit a named crash point (no-op without an injector)."""
        if self.faults is not None:
            self.faults.hit(point)

    def attach_write_recorder(self, recorder) -> None:
        """Attach a ``metrics.WriteTraceRecorder`` (or None to detach).
        The write path then reports (admitted, offered) ONCE per
        ``put``/``put_batch`` call — per-batch timestamping, so tracing
        costs one branch and the hot path stays vectorized."""
        self._recorder = recorder

    # ------------------------------------------------------------------ write
    def put(self, key: int, value: int) -> bool:
        """Returns False when the write must stall (component constraint
        or no free primary memtable slot) — the caller decides to
        retry/queue."""
        return self.put_batch(np.array([key], np.uint32),
                              np.array([value], np.int32)) == 1

    def put_batch(self, keys, values) -> int:
        """Bulk admission: admit entries in numpy-slice chunks sized to
        the primary memtable's room, computing the seal/stall boundary
        once per chunk.  Returns the count accepted before the first
        stall.  Each admitted chunk triggers index maintenance (eager:
        old-value probe + stale tombstone + insert; lazy: blind append)
        before the next chunk is considered."""
        with span("lsm.put"):
            keys = np.asarray(keys, np.uint32)
            values = np.asarray(values, np.int32)
            if (values == TOMBSTONE).any():
                raise ValueError(
                    "value -2**31 is reserved (delete tombstone)")
            with self._locked():
                return self._put_batch_locked(keys, values)

    def delete(self, key: int) -> bool:
        """Blind delete: admit a TOMBSTONE for ``key`` through the
        ordinary write path (WAL-logged, stall-checked).  Returns False
        when the write must stall — True says the delete was ADMITTED,
        not that the key existed.  Eager indexes get the stale entry
        tombstoned (which makes the delete non-blind for them: the old
        value IS looked up); lazy indexes rely on read validation."""
        return self.delete_batch(np.array([key], np.uint32)) == 1

    def delete_batch(self, keys) -> int:
        """Bulk blind deletes: ``put_batch`` semantics (admit until the
        first stall, returns the admitted count), writing TOMBSTONE
        values."""
        with span("lsm.put"):
            keys = np.asarray(keys, np.uint32)
            vals = np.full(len(keys), TOMBSTONE, np.int32)
            with self._locked():
                return self._put_batch_locked(keys, vals, deletes=True)

    def _put_batch_locked(self, keys, values, deletes: bool = False) -> int:
        primary = self.trees[0]
        n = len(keys)
        if (keys == SENTINEL_KEY).any():
            raise ValueError("key 2**32-1 is reserved")
        if self._indexes and n and int(keys.max()) >= 2 ** 31:
            raise ValueError("indexed groups require primary keys < 2**31 "
                             "(the key is stored as the index value, int32)")
        n_ok = 0
        while n_ok < n:
            if self._recovery is not None and self._indexes:
                # online recovery cannot maintain secondary indexes
                # consistently mid-replay (no live-key tracking for
                # lazily-validated index trees): stall until caught up
                primary.stats["stall_events"] += 1
                break
            primary._refresh_stall()
            if primary.stalled:
                # a constraint-induced rejection IS a stall event: the
                # paper's stall accounting charges the writer whenever
                # the write path refuses work, whichever side refused it
                primary.stats["stall_events"] += 1
                break
            if primary.active.full:
                if len(primary.sealed) >= primary.num_memtables - 1:
                    primary.stats["stall_events"] += 1
                    break
                primary.seal_active()
            # chunk size is known up front (memtable room), so the WAL
            # frame and the memtable admission carry identical entries —
            # the LSN == admission-index invariant recovery relies on
            take = min(n - n_ok,
                       primary.active.capacity - len(primary.active))
            chunk_k = keys[n_ok:n_ok + take]
            chunk_v = values[n_ok:n_ok + take]
            old_found = old_vals = None
            if self._eager:
                # resolve OLD values BEFORE the chunk lands: real point
                # lookups through the fused probe (charged to the
                # primary's lookup stats — eager maintenance pays reads)
                old_found, old_vals = self._chunk_old_values(
                    chunk_k, chunk_v, deletes)
            try:
                self._wal_log(0, chunk_k, chunk_v)
            except StorageFull:
                # out of space: the write path refuses work (a stall,
                # not data loss) until space returns and drains it
                primary.stats["stall_events"] += 1
                self._health["enospc_stalls"] += 1
                break
            with span("lsm.put.memtable"):
                took = primary.active.put_batch(chunk_k, chunk_v)
            assert took == take, "memtable admitted less than its room"
            n_ok += took
            if self._recovery is not None and \
                    primary._live_keys is not None:
                # live writes win: replay must drop these keys' history
                primary._live_keys.update(chunk_k.tolist())
            primary.stats["deletes" if deletes else "puts"] += took
            if self._indexes:
                self._fault("post-primary-pre-index")
                self._maintain_indexes(chunk_k, chunk_v, deletes,
                                       old_found, old_vals)
        primary.stats["logical_bytes"] += n_ok * ENTRY_BYTES
        if self._recorder is not None and n > 0:
            self._recorder.on_puts(n_ok, n)
        return n_ok

    def _chunk_old_values(self, ck, cv, deletes: bool):
        """Pre-admission old values for one chunk (eager maintenance):
        first occurrences of each key probe the primary (one batched
        fused-probe lookup); later intra-chunk occurrences take the
        previous occurrence's NEW value in chunk order — exactly what a
        per-entry sequential maintainer would have seen."""
        primary = self.trees[0]
        n = len(ck)
        order = np.argsort(ck, kind="stable")
        sk = ck[order]
        same = np.zeros(n, bool)
        if n > 1:
            same[1:] = sk[1:] == sk[:-1]
        dup_pos = np.flatnonzero(same)
        dup = np.zeros(n, bool)
        dup[order[dup_pos]] = True
        firsts = np.flatnonzero(~dup)
        old_found = np.zeros(n, bool)
        old_vals = np.zeros(n, np.int32)
        if len(firsts):
            pf, pv = primary.get_batch_locked(ck[firsts])
            old_found[firsts] = pf
            old_vals[firsts] = pv
        if len(dup_pos):
            src = order[dup_pos - 1]     # previous occurrence, chunk order
            dst = order[dup_pos]
            if deletes:
                old_found[dst] = False   # already deleted by the earlier
                                         # entry of this chunk
            else:
                old_found[dst] = True
                old_vals[dst] = cv[src]
        return old_found, old_vals

    @staticmethod
    def _check_attrs(attrs: np.ndarray, name: str) -> None:
        if (attrs == SENTINEL_KEY).any():
            raise ValueError(f"index {name!r}: attribute 2**32-1 is "
                             "reserved (pick an extract that avoids it)")

    def _maintain_indexes(self, ck, cv, deletes: bool,
                          old_found, old_vals) -> None:
        """Apply one admitted primary chunk to every index tree.  Eager:
        the NET index mutation of the chunk is computed sequentially
        (insertion-ordered stale-deletes and inserts, later entries
        overriding earlier ones exactly like a per-entry maintainer),
        then admitted as one tombstone frame + one insert frame — frame
        order makes newest-wins resolve del-then-add correctly.  Lazy:
        one blind ``attr -> pk`` frame per put chunk, nothing on
        deletes."""
        pks = ck.astype(np.int32)
        for st in self._indexes.values():
            tree = self.trees[st.tree_id]
            if st.mode == "lazy":
                if deletes:
                    continue
                attrs = np.asarray(st.extract(cv), np.uint32)
                self._check_attrs(attrs, st.name)
                base = self._wal_log(st.tree_id, attrs, pks)
                tree.force_admit(attrs, pks, base)
                tree.stats["puts"] += len(attrs)
                continue
            new_attrs = None
            if not deletes:
                new_attrs = np.asarray(st.extract(cv), np.uint32)
                self._check_attrs(new_attrs, st.name)
            old_attrs = np.asarray(st.extract(old_vals), np.uint32)
            dels: dict[int, None] = {}
            adds: dict[int, int] = {}
            for i in range(len(ck)):
                a_old = int(old_attrs[i]) if old_found[i] else None
                if deletes:
                    if a_old is not None:
                        adds.pop(a_old, None)
                        dels[a_old] = None
                else:
                    a_new = int(new_attrs[i])
                    if a_old is not None and a_old != a_new:
                        adds.pop(a_old, None)
                        dels[a_old] = None
                    adds[a_new] = int(pks[i])
            if dels:
                dk = np.fromiter(dels.keys(), np.uint32, len(dels))
                dv = np.full(len(dels), TOMBSTONE, np.int32)
                base = self._wal_log(st.tree_id, dk, dv)
                tree.force_admit(dk, dv, base)
                tree.stats["deletes"] += len(dels)
            if adds:
                ak = np.fromiter(adds.keys(), np.uint32, len(adds))
                av = np.fromiter(adds.values(), np.int64,
                                 len(adds)).astype(np.int32)
                base = self._wal_log(st.tree_id, ak, av)
                tree.force_admit(ak, av, base)
                tree.stats["puts"] += len(adds)

    # ------------------------------------------------------------- WAL
    def _wal_log(self, tree: int, keys, vals) -> int:
        """Append one admitted chunk as one tree-tagged WAL frame (the
        group-commit unit) BEFORE memtable admission, hit the
        ack-unknown crash point, and group-commit when enough entries
        accumulated.  Returns the frame's base LSN (the global clock
        advances even without a WAL)."""
        base = self._lsn
        if self.wal is None:
            self._lsn += len(keys)
            return base
        with span("lsm.put.wal"):
            base = self.wal.append(keys, vals, tree=tree)
        self._lsn = self.wal.end_lsn
        self._wal_stats["wal_entries"] += len(keys)
        self._fault("post-wal-pre-memtable")
        if self.wal.unsynced_entries >= self.group_commit_entries:
            self._wal_sync()
        return base

    def _wal_sync(self) -> None:
        """fsync the WAL and charge the synced traffic (entries plus the
        fixed ``wal_sync_cost`` seek charge) to the group's WAL debt —
        repaid from pump budget before ANY tree's flushes/merges, so
        durability I/O competes with compaction for the configured
        bandwidth."""
        if self.wal is None:
            return
        n = self.wal.unsynced_entries
        if n <= 0:
            return
        with span("lsm.wal.sync"):
            self.wal.sync()
        self._wal_debt += n + self.wal_sync_cost
        self._wal_stats["wal_bytes"] += n * ENTRY_BYTES
        self._wal_stats["wal_syncs"] += 1

    # ------------------------------------------------------------------ read
    def get(self, key: int):
        found, vals = self.get_batch(np.array([key], np.uint32))
        return int(vals[0]) if found[0] else None

    def get_batch(self, keys) -> tuple[np.ndarray, np.ndarray]:
        """Primary-tree point reads (see ``LSMTree.get_batch_locked``):
        one fused multi-table Bloom probe behind a newest-first walk with
        early exit.  Thread-safe under the group lock."""
        with span("lsm.get"):
            keys = np.asarray(keys, np.uint32)
            with self._locked():
                return self.trees[0].get_batch_locked(keys)

    def _get_batch_locked(self, keys):
        return self.trees[0].get_batch_locked(keys)

    def scan_range(self, lo: int, hi: int) -> tuple[np.ndarray, np.ndarray]:
        """Primary-tree newest-wins range scan (one k-way merge)."""
        return self.trees[0].scan_range(lo, hi)

    def scan_runs(self, lo: int, hi: int) -> list[tuple[np.ndarray,
                                                        np.ndarray]]:
        """Locked snapshot of the primary tree's per-run ``[lo, hi)``
        windows, newest first, merge NOT applied — the fleet router
        gathers these across shards into ONE flat k-way merge.  The
        returned windows may alias live storage: callers must not write
        through them."""
        with self._locked():
            return self.trees[0]._scan_runs(lo, hi)

    def scan_range_dict(self, lo: int, hi: int) -> dict[int, int]:
        """Dict-compat wrapper over ``scan_range`` (the seed's contract)."""
        ks, vs = self.scan_range(lo, hi)
        return dict(zip(ks.tolist(), vs.tolist()))

    # --------------------------------------------------------- index reads
    def index_lookup(self, name: str,
                     attrs) -> tuple[np.ndarray, np.ndarray]:
        """Attribute -> primary-key lookup through the index tree.
        Returns ``(found, pks)`` (pks as uint32 keys).  Eager indexes
        answer from the index tree alone (it is exact); lazy indexes
        VALIDATE every candidate against the primary — the entry counts
        only if the primary's current value still maps to the queried
        attribute."""
        st = self._indexes[name]
        attrs = np.asarray(attrs, np.uint32)
        with self._rlock:
            tree = self.trees[st.tree_id]
            found, pk_vals = tree.get_batch_locked(attrs)
            found = found.copy()
            if st.mode == "lazy" and found.any():
                idx = np.flatnonzero(found)
                pf, pv = self.trees[0].get_batch_locked(
                    pk_vals[idx].astype(np.uint32))
                valid = pf & (np.asarray(st.extract(pv), np.uint32)
                              == attrs[idx])
                found[idx] = valid
            pks = np.where(found, pk_vals, 0).astype(np.int32)
        return found, pks.astype(np.uint32)

    def get_by_index(self, name: str,
                     attrs) -> tuple[np.ndarray, np.ndarray]:
        """Index-to-primary point read: resolve attributes to primary
        keys, then fetch the primary VALUES.  Returns ``(found,
        values)``."""
        with self._rlock:
            found, pks = self.index_lookup(name, attrs)
            vals = np.zeros(len(found), np.int32)
            idx = np.flatnonzero(found)
            if idx.size:
                pf, pv = self.trees[0].get_batch_locked(pks[idx])
                found = found.copy()
                found[idx] = pf
                vals[idx] = pv
        return found, vals

    def index_scan(self, name: str, lo: int,
                   hi: int) -> tuple[np.ndarray, np.ndarray]:
        """Attribute-range scan ``lo <= attr < hi`` over the index tree.
        Returns sorted ``(attrs, pks)``.  For an EAGER index this is a
        COVERING scan — one k-way merge over the index tree, no primary
        access.  A LAZY index validates every scanned entry against the
        primary (batched)."""
        st = self._indexes[name]
        tree = self.trees[st.tree_id]
        with self._rlock:
            attrs, pk_vals = tree.scan_range(lo, hi)
            if st.mode == "lazy" and len(attrs):
                pf, pv = self.trees[0].get_batch_locked(
                    pk_vals.astype(np.uint32))
                keep = pf & (np.asarray(st.extract(pv), np.uint32) == attrs)
                attrs, pk_vals = attrs[keep], pk_vals[keep]
        return attrs, pk_vals.astype(np.uint32)

    # ------------------------------------------------------- background I/O
    def pump(self, budget_entries: int) -> int:
        """Advance background work by ``budget_entries`` of write I/O —
        one group epoch: sync the WAL and repay its debt first, then
        split the remainder ACROSS TREES by background debt
        (largest-remainder apportionment); each tree spends its quantum
        on flushes (strict priority) then merges per its scheduler.
        Returns entries actually charged."""
        with span("lsm.pump"), self._locked():
            return self._pump_locked(budget_entries)

    def _pump_locked(self, budget_entries: int) -> int:
        spent = 0
        self.now += 1.0
        # every pump is an fsync-epoch boundary: sync the WAL first so
        # its traffic lands in the group debt and is repaid below, ahead
        # of every tree — durability shares the bandwidth budget
        try:
            self._wal_sync()
        except StorageFull:
            self._health["enospc_stalls"] += 1
        repay = min(self._wal_debt, budget_entries)
        self._wal_debt -= repay
        spent += repay
        remaining = budget_entries - spent
        if remaining > 0 and self.scrubber is not None:
            spent += self.scrubber.step(
                min(remaining, self.scrubber.entries_per_epoch))
            remaining = budget_entries - spent
        if remaining > 0:
            rec = self._recovery
            debts = []
            if rec is not None and not rec.done:
                # replay debt competes with flush/merge debt for the
                # same budget — the arbiter sees it as one more stream
                debts.append((-1, rec.remaining))
            for t in self.trees:
                d = t.pending_entries()
                if d > 0:
                    debts.append((t.tree_id, d))
            if len(debts) == 1:
                tid = debts[0][0]
                spent += rec._replay_step(remaining) if tid == -1 \
                    else self.trees[tid].pump_tree(remaining)
            elif debts:
                total = float(sum(d for _, d in debts))
                quanta = apportion_largest_remainder(
                    [(tid, d / total) for tid, d in debts], remaining)
                for (tid, _), q in zip(debts, quanta):
                    if q <= 0:
                        continue
                    spent += rec._replay_step(q) if tid == -1 \
                        else self.trees[tid].pump_tree(q)
        for t in self.trees:
            t._refresh_stall()
        return spent

    def drain(self, budget_entries: int = 1 << 30, max_pumps: int = 10_000):
        """Pump until no background work remains on ANY tree
        (tests/shutdown)."""
        with self._rlock:
            for _ in range(max_pumps):
                busy = False
                for t in self.trees:
                    t._collect_merges()
                    busy = busy or t.sealed or t.running
                if not busy:
                    break
                self.pump(budget_entries)

    # --------------------------------------------- legacy engine surface
    # (the 1-tree API every existing caller uses: delegates to the
    # primary tree / sums over trees — bit-identical for one tree)
    @property
    def policy(self) -> MergePolicy:
        return self.trees[0].policy

    @policy.setter
    def policy(self, p: MergePolicy) -> None:
        self.trees[0].policy = p

    @property
    def scheduler(self) -> MergeScheduler:
        return self.trees[0].scheduler

    @scheduler.setter
    def scheduler(self, s: MergeScheduler) -> None:
        self.trees[0].scheduler = s

    @property
    def constraint(self) -> ComponentConstraint:
        return self.trees[0].constraint

    @constraint.setter
    def constraint(self, c: ComponentConstraint) -> None:
        self.trees[0].constraint = c

    @property
    def tree(self) -> ComponentTree:
        """The PRIMARY tree's scheduling-plane model (legacy name)."""
        return self.trees[0].meta

    @property
    def memtable_entries(self) -> int:
        return self.trees[0].memtable_entries

    @property
    def num_memtables(self) -> int:
        return self.trees[0].num_memtables

    @property
    def active(self) -> MemTable:
        return self.trees[0].active

    @property
    def sealed(self) -> list:
        return self.trees[0].sealed

    @property
    def tables(self) -> dict:
        return self.trees[0].tables

    @property
    def running(self) -> dict:
        return self.trees[0].running

    @property
    def pending_flush(self) -> list:
        return self.trees[0].pending_flush

    @property
    def stalled(self) -> bool:
        return self.trees[0].stalled

    @property
    def _order(self) -> list:
        return self.trees[0]._order

    @property
    def _fstack(self) -> _FilterStack:
        return self.trees[0]._fstack

    @property
    def _stamp(self) -> int:
        return self.trees[0]._stamp

    @property
    def _flush_debt(self) -> int:
        """Total budget debt: group WAL debt + every tree's flush debt
        (the legacy engine kept one combined pot)."""
        return self._wal_debt + sum(t._flush_debt for t in self.trees)

    @property
    def stats(self) -> dict:
        """Merged counters: sum over trees plus the group's WAL
        counters, in the legacy key order.  (A fresh dict per access —
        hold no live reference.)"""
        out = dict.fromkeys(_STATS_ORDER, 0)
        for t in self.trees:
            for k, v in t.stats.items():
                out[k] += v
        for k, v in self._wal_stats.items():
            out[k] += v
        return out

    def seal_active(self) -> None:
        self.trees[0].seal_active()

    _seal_active = seal_active        # compat alias (pre-PR7 name)

    def _refresh_stall(self) -> None:
        for t in self.trees:
            t._refresh_stall()

    def _read_view(self) -> _ReadView:
        return self.trees[0]._read_view()

    def _view_filters(self, view: _ReadView):
        return self.trees[0]._view_filters(view)

    def _invalidate_view(self) -> None:
        self.trees[0]._invalidate_view()

    def _bind_table(self, table: SSTable) -> None:
        self.trees[0]._bind_table(table)

    def _collect_merges(self) -> None:
        for t in self.trees:
            t._collect_merges()

    def _scan_runs(self, lo: int, hi: int):
        return self.trees[0]._scan_runs(lo, hi)

    # merge advance/finish dispatch per-merge via ``rm.tree`` (primary
    # for hand-built cursors): every tree's pump routes its merges
    # THROUGH these two entry points, so wrapping them on the engine
    # instance instruments the whole group
    def _open_merge(self, rm: _RunningMerge) -> None:
        (rm.tree or self.trees[0])._open_merge(rm)

    def _merge_cut(self, rm: _RunningMerge, target: int):
        return (rm.tree or self.trees[0])._merge_cut(rm, target)

    def _tombstone_drop_safe(self, rm: _RunningMerge) -> bool:
        return (rm.tree or self.trees[0])._tombstone_drop_safe(rm)

    def _advance_merge(self, rm: _RunningMerge, quantum: int) -> int:
        return (rm.tree or self.trees[0])._advance_merge(rm, quantum)

    def _finish_merge(self, rm: _RunningMerge) -> None:
        (rm.tree or self.trees[0])._finish_merge(rm)

    _order_key = staticmethod(LSMTree._order_key)
    _merge_kway_host = staticmethod(merge_kway_host)

    # ------------------------------------------------------------------ info
    @contextlib.contextmanager
    def _locked(self):
        """The group lock for one get/put/scan/pump call, the wait for
        it recorded as the ``lsm.lock`` span."""
        with span("lsm.lock"):
            self._rlock.acquire()
        try:
            yield
        finally:
            self._rlock.release()

    def lock(self) -> threading.RLock:
        """The group's reentrant lock (see module docstring): the
        ``BackgroundDriver`` holds it around ``pump``; foreground callers
        sharing a group with a driver must hold it around every engine
        call (``with engine.lock(): ...``)."""
        return self._rlock

    def num_components(self) -> int:
        with self._rlock:
            return sum(t.num_components() for t in self.trees)

    def total_entries(self) -> int:
        with self._rlock:
            return sum(t.total_entries() for t in self.trees)

    def pending_background_entries(self) -> int:
        """Background I/O debt in entries across the WHOLE group: WAL
        debt plus every tree's flush debt, sealed memtables and
        unconsumed merge inputs.  This is the per-shard 'pending debt'
        the fleet's ``GlobalBudgetArbiter`` apportions the global budget
        by — and, within a group, what each pump epoch is split by."""
        with self._rlock:
            out = self._wal_debt + sum(t.pending_entries()
                                       for t in self.trees)
            if self._recovery is not None and not self._recovery.done:
                out += self._recovery.remaining
            return out

    # ----------------------------------------------- durability lifecycle
    @property
    def flushed_lsn(self) -> int:
        """First LSN NOT yet captured in on-disk SSTables, over ALL
        trees (the minimum of the per-tree origins) — the WAL
        truncation point a snapshot records.  During online recovery
        the claim is additionally capped by the replay watermark:
        un-replayed WAL history must never be truncated away."""
        lo = min(t.flushed_lsn for t in self.trees)
        if self._replay_watermark is not None:
            lo = min(lo, self._replay_watermark)
        return lo

    def snapshot(self, store) -> dict:
        """Persist the durable view: fsync the WAL, save every tree's
        live SSTables plus per-tree metadata atomically through
        ``store`` (``checkpoint.EngineSnapshotStore``), then drop whole
        WAL segments whose entries are all captured by the saved tables.
        Returns the manifest dict."""
        with self._rlock:
            self._wal_sync()
            manifest = store.save(self)
            if self.wal is not None:
                archived = self.wal.truncate_upto(self.flushed_lsn)
                if archived:
                    # archival is real I/O: charge the moved entries to
                    # the background budget like any other traffic
                    self._wal_debt += archived
            return manifest

    def restore_tables(self, tables, snap: dict) -> int:
        """Legacy single-tree restore (the multi-tree path goes through
        ``RecoverySession`` -> ``LSMTree.restore_tables`` per tree):
        rebuild the PRIMARY tree's read view and the group clock.
        Returns the snapshot's ``flushed_lsn``."""
        with self._rlock:
            out = self.trees[0].restore_tables(tables, snap)
            self.now = max(self.now, float(snap.get("now", 0.0)))
            return out

    def begin_replay(self, lsn: int) -> None:
        """Position the group at WAL offset ``lsn`` before replay: the
        next admitted entry is entry ``lsn`` of the admitted-write
        history.  (``RecoverySession`` then raises individual trees'
        memtable origins to their own snapshot frontiers.)"""
        with self._rlock:
            self._lsn = int(lsn)
            for t in self.trees:
                t.active.start_lsn = self._lsn

    def replay_admit(self, keys, vals) -> int:
        """Legacy recovery admission into the PRIMARY tree (no
        re-logging, no constraint stalls), advancing the group LSN.
        Multi-tree replay uses ``LSMTree.replay_admit`` per frame with
        session-managed LSNs instead."""
        with self._rlock:
            took = self.trees[0].replay_admit(keys, vals)
            self._lsn += took
            return took

    def compact_all(self, budget_per_pump: int = 1 << 30) -> None:
        """Force-merge every tree into one bottom run: flush every
        memtable, drain policy merges, then merge ALL live tables per
        tree to the deepest level in one op — no older run can overlap
        it, so every tombstone is reclaimed.  This is the space-amp
        floor the durability tests pin."""
        with self._rlock:
            for t in self.trees:
                if len(t.active):
                    t.seal_active()
            self.drain(budget_per_pump)
            started = False
            for t in self.trees:
                started = t.start_full_merge() or started
            if started:
                self.drain(budget_per_pump)

    def live_entries(self) -> int:
        """Distinct keys whose newest version is NOT a tombstone, summed
        over trees (an O(n) full-range scan per tree)."""
        return sum(t.live_entries() for t in self.trees)

    def amplification(self) -> dict:
        """Write/space amplification snapshot (see
        ``metrics.amplification_stats``): bytes written by flush + merge
        + WAL over logical bytes ingested (index maintenance counts in
        the numerator, not the denominator — it IS amplification), and
        physical entries stored over live entries, across all trees."""
        from .metrics import amplification_stats
        with self._rlock:
            return amplification_stats(self.stats,
                                       physical_entries=self.total_entries(),
                                       live_entries=self.live_entries())

    def enable_scrub(self, store=None, entries_per_epoch: int = 256):
        """Attach a background integrity ``Scrubber`` (see
        ``core.scrub``): every pump epoch reserves up to
        ``entries_per_epoch`` of the budget to stream CRC verification
        over live tables, quarantining and repairing on mismatch.
        ``store`` (an ``EngineSnapshotStore``) is the preferred repair
        source.  Returns the scrubber (its ``stats`` feed
        ``health()``)."""
        from .scrub import Scrubber
        with self._rlock:
            self.scrubber = Scrubber(self, store=store,
                                     entries_per_epoch=entries_per_epoch)
            return self.scrubber

    def health(self) -> dict:
        """Fault-plane counters, ``amplification()``-style: a flat
        numeric dict (summable fleet-wide) covering I/O retries and
        backoff, ENOSPC stall epochs, scrub progress and
        quarantine/repair outcomes, WAL archival, and online-recovery
        state."""
        with self._rlock:
            out = {"enospc_stalls": self._health["enospc_stalls"],
                   "recovering": int(self._recovery is not None),
                   "replay_remaining": (self._recovery.remaining
                                        if self._recovery is not None
                                        else 0),
                   "wal_archived_segments": 0, "wal_archived_entries": 0,
                   "io_retries": 0, "io_backoff_s": 0.0, "io_faults": 0,
                   "io_enospc": 0, "io_latency_injected_s": 0.0}
            if self.wal is not None:
                out["wal_archived_segments"] = self.wal.archived_segments
                out["wal_archived_entries"] = self.wal.archived_entries
                for k, v in self.wal.io.stats.items():
                    out[k] += v
            if self.scrubber is not None:
                out.update(self.scrubber.stats)
            else:
                out.update({"scrub_passes": 0, "scrub_tables_checked": 0,
                            "scrub_entries": 0, "tables_quarantined": 0,
                            "tables_repaired": 0,
                            "tables_unrepairable": 0})
            return out

    def close(self) -> None:
        """Graceful shutdown: fsync and release the WAL (no-op without
        one).  The group stays readable afterwards; only the durability
        plane is closed."""
        with self._rlock:
            if self.wal is not None:
                self.wal.close()

    def __enter__(self) -> "StorageGroup":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class LSMEngine(StorageGroup):
    """A single-partition LSM store (uint32 keys -> int32 values): the
    1-tree ``StorageGroup`` — the engine every pre-split caller
    constructs.  Secondary indexes can still be declared (``indexes=``
    or ``add_index``); a bare construction is bit-identical to the
    pre-split single-tree engine."""


class BackgroundDriver:
    """Wall-clock driver: pumps an engine at ``bandwidth_bytes_per_s`` on a
    daemon thread (the serving/ingestion examples use this; tests use
    pump() directly)."""

    def __init__(self, engine: LSMEngine, bandwidth_bytes_per_s: float,
                 quantum_s: float = 0.01):
        self.engine = engine
        self.rate = bandwidth_bytes_per_s
        self.quantum_s = quantum_s
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # the ENGINE's lock, not a private one: a driver-private lock
        # guards nothing, because foreground put/get/scan calls never
        # took it and raced the pump thread.  Sharing engine.lock()
        # makes `with engine.lock():` on the foreground path exclude
        # the pump.
        self._lock = engine.lock()

    def start(self):
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        # Pace by monotonic elapsed time, carrying the undelivered-entry
        # deficit across iterations.  The seed computed one fixed
        # per-quantum budget and slept quantum_s per loop, so every source
        # of iteration overrun — pump compute, lock contention with the
        # foreground, sleep overshoot — silently shrank the delivered
        # bandwidth below the configured budget (the knob every experiment
        # in the paper turns).  Here the budget owed is always
        # elapsed * rate, so slow iterations are repaid by larger quanta.
        t0 = time.monotonic()
        delivered = 0.0                # entry quanta offered to pump()
        per_s = self.rate / ENTRY_BYTES
        # cap each catch-up quantum: an unbounded one would grow with
        # every slow pump (bigger quantum -> longer lock hold -> bigger
        # deficit), starving the foreground in ever-larger bursts.  The
        # residual deficit still carries, so a temporarily slow pump is
        # repaid at up to 4x pace; a persistently slow one is genuine
        # saturation the budget cannot force through.
        q_max = max(1, int(4 * per_s * self.quantum_s))
        while not self._stop.is_set():
            deficit = (time.monotonic() - t0) * per_s - delivered
            quantum = min(int(deficit), q_max)
            if quantum >= 1:
                with self._lock:
                    self.engine.pump(quantum)
                delivered += quantum
            self._stop.wait(self.quantum_s)

    def stop(self):
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=5)
            self._thread = None

    def close(self) -> None:
        """Graceful shutdown: stop the pump thread (any in-flight quantum
        completes under the engine lock before ``stop`` returns), then
        close the engine's durability plane (WAL fsync).  Idempotent."""
        self.stop()
        self.engine.close()

    def __enter__(self) -> "BackgroundDriver":
        if self._thread is None:
            self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.close()
