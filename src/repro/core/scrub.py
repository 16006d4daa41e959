"""Background integrity scrub: detect bit-rot in live SSTables,
quarantine, and repair — budget-charged from ``pump``.

Every table seals a content CRC when it binds into a tree's read view
(flush, merge completion, snapshot restore — ``SSTable.seal_checksum``,
the same ``data_crc32`` formula the snapshot manifest records).  The
``Scrubber`` re-verifies those seals continuously: each pump epoch
reserves a budget slice (``entries_per_epoch``, charged like any other
background I/O) and streams the running CRC over the current table's
key bytes then value bytes, so one quantum costs O(quantum) no matter
how large the table — the verify state (table, phase, offset, running
CRC) carries across epochs, and a full rotation over every live table
of every tree is one *scrub pass*.

On a mismatch the table is QUARANTINED immediately — removed from the
read view, the filter stack, the scheduling plane, and any running
merge that counts it as an input (surviving inputs are released back
to the policy) — so a corrupt run can never serve another read.  Then
repair, in order of cost:

1. **Snapshot copy**: if the snapshot store holds a table with the
   same (tree, stamp, checksum), reload it, verify, and rebind at the
   quarantined table's exact (stamp, level) rank — reads resume
   bit-identically.
2. **WAL rebuild**: otherwise, if the WAL (plus archive) still covers
   the tree's history, the tree's ENTIRE disk state is rebuilt —
   restore the snapshot section, replay the tree's frames up to its
   ``flushed_lsn`` into one fresh newest-stamped run (memtables are
   untouched; they own everything at and above ``flushed_lsn``).
3. **Unrepairable**: no durable copy survives.  The tree is marked
   ``corrupt`` and every subsequent read raises
   ``UnrepairableCorruptionError`` — a typed error, never a wrong
   answer.

All counters are flat numbers (``stats``) rolled up by
``engine.health()`` and summed fleet-wide.
"""
from __future__ import annotations

import bisect
import zlib
from typing import Optional

import numpy as np

from .iostack import CorruptionError
from .sstable import SSTable


class Scrubber:
    """Incremental CRC verifier over a ``StorageGroup``'s live tables.

    Driven from ``StorageGroup._pump_locked`` (group lock ALWAYS held
    in ``step``): each epoch spends at most ``entries_per_epoch`` of
    the pump budget advancing the stream.  ``store`` (an
    ``EngineSnapshotStore`` or None) is the preferred repair source."""

    def __init__(self, group, store=None, entries_per_epoch: int = 256):
        self.group = group
        self.store = store
        self.entries_per_epoch = max(1, int(entries_per_epoch))
        self.stats = {"scrub_passes": 0, "scrub_tables_checked": 0,
                      "scrub_entries": 0, "tables_quarantined": 0,
                      "tables_repaired": 0, "tables_unrepairable": 0}
        self._queue: list[tuple[int, int]] = []   # (tree_id, cid) this pass
        self._cur: Optional[tuple[int, int]] = None
        self._phase = 0        # 0 = keys, 1 = vals
        self._pos = 0          # entries verified in the current phase
        self._crc = 0          # running CRC across both phases
        self._pass_open = False

    # ------------------------------------------------------------ stepping
    def _refill(self) -> None:
        if self._pass_open:
            self.stats["scrub_passes"] += 1
        self._queue = [(t.tree_id, x.component.cid)
                       for t in self.group.trees if not t.corrupt
                       for x in t._order]
        self._pass_open = bool(self._queue)

    def step(self, budget_entries: int) -> int:
        """Advance the scrub stream by up to ``budget_entries`` units
        (one unit = one entry's keys OR values hashed — a full table
        verify costs 2n units, the read I/O of touching its bytes
        twice).  Returns units spent.  Group lock held by the caller."""
        spent = 0
        g = self.group
        last_refill_spent = -1     # guard: never refill twice for free
        while spent < int(budget_entries):
            if self._cur is None:
                if not self._queue:
                    if last_refill_spent == spent:
                        break      # a whole pass cost nothing: all skips
                    self._refill()
                    last_refill_spent = spent
                    if not self._queue:
                        break
                tid, cid = self._queue.pop(0)
                tree = g.trees[tid]
                table = tree.tables.get(cid)
                if table is None or table.crc32 is None or tree.corrupt:
                    continue          # merged away / unsealed: skip free
                self._cur = (tid, cid)
                self._phase = 0
                self._pos = 0
                self._crc = 0
            tid, cid = self._cur
            tree = g.trees[tid]
            table = tree.tables.get(cid)
            if table is None or tree.corrupt:
                self._cur = None      # vanished mid-verify: abandon
                continue
            data = table.keys_np if self._phase == 0 else table.vals_np
            dt = np.uint32 if self._phase == 0 else np.int32
            n = len(data)
            take = min(int(budget_entries) - spent, n - self._pos)
            if take > 0:
                chunk = np.ascontiguousarray(
                    data[self._pos:self._pos + take], dt)
                self._crc = zlib.crc32(chunk.tobytes(), self._crc)
                self._pos += take
                spent += take
                self.stats["scrub_entries"] += take
            if self._pos >= n:
                if self._phase == 0:
                    self._phase = 1
                    self._pos = 0
                    continue
                # both phases done: verdict
                self.stats["scrub_tables_checked"] += 1
                if self._crc != table.crc32:
                    self._handle_corrupt(tree, table)
                self._cur = None
            if take <= 0 and self._cur is not None:
                break                 # budget exhausted mid-table
        return spent

    # ----------------------------------------------------------- repair
    def _handle_corrupt(self, tree, table: SSTable) -> None:
        """Quarantine ``table`` and repair (group lock held)."""
        stamp = int(table.data_stamp)
        level = int(table.component.level)
        created_at = float(table.component.created_at)
        want_crc = int(table.crc32)
        self.stats["tables_quarantined"] += 1
        self._quarantine(tree, table)
        if self._repair_from_store(tree, stamp, level, created_at,
                                   want_crc):
            self.stats["tables_repaired"] += 1
            return
        if self._rebuild_tree_from_wal(tree):
            self.stats["tables_repaired"] += 1
            return
        tree.corrupt = True
        self.stats["tables_unrepairable"] += 1

    def _quarantine(self, tree, table: SSTable) -> None:
        """Remove a corrupt table from every plane it is visible in —
        read view, filter stack, scheduling metadata, running merges
        (surviving merge inputs are released back to the policy)."""
        cid = table.component.cid
        tree.tables.pop(cid, None)
        try:
            tree.meta.remove(table.component)
        except ValueError:
            pass
        tree._order = [t for t in tree._order if t.component.cid != cid]
        tree._fstack.note_remove(cid)
        for op_id, rm in list(tree.running.items()):
            if any(t.component.cid == cid for t in rm.inputs):
                for c in rm.op.inputs:
                    c.merging = False
                del tree.running[op_id]
        tree._invalidate_view()

    def _rebind(self, tree, keys, vals, level: int, stamp: int,
                created_at: float) -> None:
        """Bind repaired content at the quarantined table's exact
        (stamp, level) rank, so newest-wins ordering is unchanged."""
        t = SSTable.build(keys, vals, level=level, created_at=created_at)
        t.data_stamp = int(stamp)
        t.component.stamp = float(stamp)
        t.seal_checksum()
        tree.meta.add(t.component)
        tree.tables[t.component.cid] = t
        pos = bisect.bisect_left(tree._order, tree._order_key(t),
                                 key=tree._order_key)
        tree._order.insert(pos, t)
        tree._fstack.note_add(t)
        tree._invalidate_view()

    def _repair_from_store(self, tree, stamp: int, level: int,
                           created_at: float, want_crc: int) -> bool:
        if self.store is None:
            return False
        try:
            got = self.store.find_table(tree.tree_id, stamp, want_crc)
        except CorruptionError:
            return False
        if got is None:
            return False
        self._rebind(tree, got[0], got[1], level, stamp, created_at)
        return True

    def _rebuild_tree_from_wal(self, tree) -> bool:
        """Rebuild the tree's ENTIRE disk state from snapshot + WAL:
        restore the (verified) snapshot section, then replay this
        tree's frames below its ``flushed_lsn`` into one fresh run.
        Memtables are untouched — they own [flushed_lsn, now)."""
        g = self.group
        if g.wal is None:
            return False
        base = 0
        restored = []
        sec: dict = {}
        if self.store is not None:
            snap = self.store.load()
            if snap is not None:
                sections = snap.get("trees")
                if sections is None:
                    sections = [dict(snap, tree=0)]
                for s in sections:
                    if int(s.get("tree", 0)) == tree.tree_id:
                        sec = s
                        break
                if sec:
                    try:
                        restored = list(self.store.load_tree_tables(sec))
                    except CorruptionError:
                        return False    # snapshot itself is rotten
                    base = int(sec.get("flushed_lsn", 0))
        if g.wal.oldest_lsn > base:
            return False                # history gap: cannot rebuild
        upto = tree.flushed_lsn
        # wipe the disk plane (memtables stay)
        for t in list(tree._order):
            try:
                tree.meta.remove(t.component)
            except ValueError:
                pass
            tree._fstack.note_remove(t.component.cid)
        tree.tables.clear()
        tree._order = []
        for rm in tree.running.values():
            for c in rm.op.inputs:
                c.merging = False
        tree.running.clear()
        tree._invalidate_view()
        if restored:
            tree.restore_tables(restored, sec)
        # one fresh newest-stamped run holds the replayed suffix
        kv: dict[int, int] = {}
        for ftree, fbase, ks, vs in g.wal.frames_since(base):
            if ftree != tree.tree_id or fbase >= upto:
                continue
            end = min(len(ks), upto - fbase)
            skip = max(0, base - fbase)
            for k, v in zip(ks[skip:end].tolist(), vs[skip:end].tolist()):
                kv[k] = v
        if kv:
            sk = np.array(sorted(kv), np.uint32)
            sv = np.array([kv[int(k)] for k in sk], np.int32)
            run = SSTable.build(sk, sv,
                                level=tree.policy.flush_target_level(),
                                created_at=g.now)
            tree._bind_table(run)
        self._queue = [(t, c) for t, c in self._queue
                       if t != tree.tree_id]    # stale cids of this pass
        return True
