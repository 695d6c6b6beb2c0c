"""P-CLHT — persistent Cache-Line Hash Table (RECIPE Condition #1).

Faithful to the paper's §6.2 conversion of CLHT-LB:

* each bucket is exactly one cache line: 3 key/value pairs + a chain
  pointer (``[k0,k1,k2, v0,v1,v2, next, pad]`` = 8 words = 64 B);
* readers are non-blocking and use the CLHT *atomic snapshot* (read
  key, read value, re-read key);
* writers lock the bucket, then commit via a single 8-byte atomic
  store — value first (persisted), then key (the commit point);
* deletes commit by atomically storing 0 to the key word;
* re-hashing is copy-on-write into a fresh table followed by a single
  atomic swap of the table pointer in the superblock.

Conversion action (#1): cache-line flush + fence after each store, with
the paper's optimization that stores preceding the final atomic commit
store may be persisted with one flush of their region before the
commit.  Common-case insert: 2 clwb + 2 fences (paper measures 1.5/2.5).
"""

from __future__ import annotations

import bisect
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..obs import RECORDER as _OBS
from .conditions import (Condition, ConversionSpec, IndexSnapshot,
                         RecipeIndex, register)
from .pmem import NULL, WORDS_PER_LINE, PMem, Region

SLOTS = 3
BUCKET_WORDS = 8
HDR_WORDS = 8  # header line: [n_buckets, overflow_cursor, ...]
MAX_CHAIN = 4  # chain length that triggers a resize
# A delta export (``_delta_export``) patches only the rows written since
# the last export.  Per row it sorts, gathers, hashes and packs on its
# own, about three times the host time of a row of the full export's
# column copies (numpy, 2^18 buckets); at 1/DELTA_ROW_SHARE of the
# rows it still costs under half the full walk, with a smaller upload.
# Past that the full export is taken, and the line record is dropped at
# that size, so it never holds more than an eighth of the table's lines.
DELTA_ROW_SHARE = 8
_LINES = "clht_lines"  # snapshot cache: (table, line record, cursor)

SPEC = register(ConversionSpec(
    name="P-CLHT", structure="hash table", reader="non-blocking",
    writer="blocking", non_smo=Condition.ATOMIC_STORE,
    smo=Condition.ATOMIC_STORE,
    notes="CoW rehash + atomic table-pointer swap; 30 LOC in the paper",
))


_M64 = (1 << 64) - 1


def _locate(t: Region, lines: np.ndarray, keys: np.ndarray):
    """Walk every key's bucket chain from its head (``lines``: line
    indices of the table region) one chain level at a time, gathering
    whole bucket rows: (the word of each key's value, -1 where the key
    is absent; then, for each bucket row read for a key found, its op
    and its line).  An absent key's walk is left out: the per-op path
    walks it again."""
    rows_of = t.cache.reshape(-1, BUCKET_WORDS)
    word = np.full(keys.size, -1, np.int64)
    live = np.arange(keys.size)
    walked_ops, walked = [], []
    while live.size:
        rows = rows_of[lines]
        walked_ops.append(live)
        walked.append(lines)
        hit = rows[:, :SLOTS] == keys[live, None]
        found = hit.any(axis=1)
        word[live[found]] = (lines[found] * BUCKET_WORDS + SLOTS
                             + hit[found].argmax(axis=1))
        more = ~found & (rows[:, 6] != NULL)
        live = live[more]
        lines = rows[more, 6] // BUCKET_WORDS
    walked_ops = np.concatenate(walked_ops)
    walked = np.concatenate(walked)
    found = word[walked_ops] >= 0
    if not found.all():
        walked_ops, walked = walked_ops[found], walked[found]
    return word, walked_ops, walked


def _mix(key: int) -> int:
    """splitmix64 finalizer — the multiplicative hash used everywhere."""
    z = (int(key) + 0x9E3779B97F4A7C15) & _M64
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _M64
    return z ^ (z >> 31)


class PCLHT(RecipeIndex):
    ORDERED = False
    spec = SPEC

    def __init__(self, pmem: PMem, n_buckets: int = 64, grow: bool = True,
                 name: str = "clht"):
        super().__init__(pmem)
        self.grow = grow
        self.name = name
        self._region_prefixes = (f"{name}.",)
        existing = pmem.find(f"{name}.super")
        if existing is not None:
            self.super = existing  # attach (restart): no reinit needed
            return
        self.super = pmem.alloc(f"{name}.super", 8)
        table = self._new_table(n_buckets)
        pmem.store(self.super, 0, table.rid)
        pmem.persist_region(self.super)

    # ------------------------------------------------------------------
    # table layout helpers
    # ------------------------------------------------------------------
    def _new_table(self, n_buckets: int) -> Region:
        # half the region again as overflow-bucket arena
        n_overflow = max(8, n_buckets // 2)
        words = HDR_WORDS + (n_buckets + n_overflow) * BUCKET_WORDS
        t = self.pmem.alloc(f"{self.name}.table[{n_buckets}]", words)
        self.pmem.store(t, 0, n_buckets)
        self.pmem.store(t, 1, HDR_WORDS + n_buckets * BUCKET_WORDS)  # overflow cursor
        self.pmem.persist_region(t)
        return t

    def _table(self) -> Region:
        rid = self.pmem.load(self.super, 0)
        return self.pmem.regions[rid]

    def _bucket_off(self, t: Region, key: int) -> int:
        n = self.pmem.load(t, 0)
        return HDR_WORDS + (_mix(key) % n) * BUCKET_WORDS

    def _alloc_overflow(self, t: Region) -> Optional[int]:
        cur = self.pmem.load(t, 1)
        if cur + BUCKET_WORDS > t.n_words:
            return None
        # The cursor bump is not itself a commit point: an allocated but
        # never-linked bucket is unreachable garbage (RECIPE assumes GC).
        self.pmem.store(t, 1, cur + BUCKET_WORDS)
        self.pmem.persist(t, 1)
        return cur

    # ------------------------------------------------------------------
    # reads — non-blocking, atomic snapshot
    # ------------------------------------------------------------------
    def lookup(self, key: int) -> Optional[int]:
        assert key != NULL
        t = self._table()
        off = self._bucket_off(t, key)
        while off != NULL:
            for s in range(SLOTS):
                k1 = self.pmem.load(t, off + s)
                if k1 == key:
                    v = self.pmem.load(t, off + SLOTS + s)
                    k2 = self.pmem.load(t, off + s)  # atomic snapshot re-check
                    if k2 == key:
                        return v
            off = self.pmem.load(t, off + 6)
        return None

    # ------------------------------------------------------------------
    # writes — bucket-locked, single-atomic-store commit (Condition #1)
    # ------------------------------------------------------------------
    def insert(self, key: int, value: int) -> bool:
        assert key != NULL
        self._bump_epoch()  # batched readers must re-snapshot
        while True:
            status = self._insert_once(key, value)
            if status == "rehash":
                self._rehash()
                continue
            if status == "rehash_done_true":
                self._rehash()
                return True
            return status == "true"

    def _insert_once(self, key: int, value: int) -> str:
        # writers take the resize lock shared; rehash takes it exclusive
        self.pmem.lock_shared(self.super, 0)
        try:
            t = self._table()
            head = self._bucket_off(t, key)
            self.pmem.lock(t, head)
            try:
                off, chain_len = head, 1
                while True:
                    for s in range(SLOTS):
                        if self.pmem.load(t, off + s) == key:
                            return "false"  # CLHT insert fails on existing key
                    nxt = self.pmem.load(t, off + 6)
                    if nxt == NULL:
                        break
                    off, chain_len = nxt, chain_len + 1
                # find an empty slot in the chain
                slot = self._find_empty(t, head)
                if slot is not None:
                    boff, s = slot
                    # value first (persist), then the atomic key store
                    self.pmem.store(t, boff + SLOTS + s, value)
                    self.pmem.clwb(t, boff + SLOTS + s)
                    self.pmem.fence()
                    self.pmem.store(t, boff + s, key)
                    self.pmem.clwb(t, boff + s)
                    self.pmem.fence()
                    if chain_len > MAX_CHAIN and self.grow:
                        return "rehash_done_true"
                    return "true"
                # chain exhausted: link a fresh overflow bucket
                new_off = self._alloc_overflow(t)
                if new_off is None:
                    return "rehash"
                self.pmem.store(t, new_off + SLOTS + 0, value)
                self.pmem.store(t, new_off + 0, key)
                self.pmem.flush_range(t, new_off, new_off + BUCKET_WORDS)
                self.pmem.fence()
                # commit point: single atomic store of the chain pointer
                self.pmem.store(t, off + 6, new_off)
                self.pmem.clwb(t, off + 6)
                self.pmem.fence()
                if chain_len + 1 > MAX_CHAIN and self.grow:
                    return "rehash_done_true"
                return "true"
            finally:
                self.pmem.unlock(t, head)
        finally:
            self.pmem.unlock_shared(self.super, 0)

    def _find_empty(self, t: Region, head: int) -> Optional[Tuple[int, int]]:
        off = head
        while off != NULL:
            for s in range(SLOTS):
                if self.pmem.load(t, off + s) == NULL:
                    return off, s
            off = self.pmem.load(t, off + 6)
        return None

    def update(self, key: int, value: int) -> bool:
        """Native update: probe the chain for the key and commit the new
        value with a single 8-byte atomic store to the value word — the
        CLHT atomic snapshot (key, value, key re-read) makes a torn
        view impossible, so readers see the old or the new value.
        Overwriting with the current value is a no-op that performs no
        stores and leaves every snapshot epoch valid; absent keys fall
        through to insert semantics."""
        assert key != NULL
        self.pmem.lock_shared(self.super, 0)
        try:
            t = self._table()
            head = self._bucket_off(t, key)
            self.pmem.lock(t, head)
            try:
                off = head
                while off != NULL:
                    for s in range(SLOTS):
                        if self.pmem.load(t, off + s) == key:
                            if self.pmem.load(t, off + SLOTS + s) == value:
                                return True  # no-op overwrite
                            self._bump_epoch()
                            self.pmem.store(t, off + SLOTS + s, value)
                            self.pmem.clwb(t, off + SLOTS + s)
                            self.pmem.fence()
                            return True
                    off = self.pmem.load(t, off + 6)
            finally:
                self.pmem.unlock(t, head)
        finally:
            self.pmem.unlock_shared(self.super, 0)
        return self.insert(key, value)

    def delete(self, key: int) -> bool:
        self._bump_epoch()
        self.pmem.lock_shared(self.super, 0)
        try:
            t = self._table()
            head = self._bucket_off(t, key)
            self.pmem.lock(t, head)
            try:
                off = head
                while off != NULL:
                    for s in range(SLOTS):
                        if self.pmem.load(t, off + s) == key:
                            # commit: atomically store 0 to the key word
                            self.pmem.store(t, off + s, NULL)
                            self.pmem.clwb(t, off + s)
                            self.pmem.fence()
                            return True
                    off = self.pmem.load(t, off + 6)
                return False
            finally:
                self.pmem.unlock(t, head)
        finally:
            self.pmem.unlock_shared(self.super, 0)

    # ------------------------------------------------------------------
    # sharded batched writes (_write_batch wave shard runs)
    # ------------------------------------------------------------------
    def _apply_shard_run(self, ops: Sequence[Tuple[str, int, int]],
                         positions: Sequence[int], results: List) -> None:
        """Vectorized shard-run fast path: one shared resize-lock
        acquisition and one vectorized bucket hash for the whole run.
        Inside a group-commit epoch each stretch of consecutive updates
        runs as array operations (``_update_stretch``); every other op
        walks its chain with bulk line loads (counted like the scalar
        walk) and commits with the *exact* scalar store protocol —
        value word first, then the single atomic key / tombstone store,
        flushes riding the enclosing group-commit epoch.  Ops needing
        an overflow link or a rehash defer to the scalar path; epochs
        bump only on actual mutation."""
        from ..kernels.partition import mix64_ref
        pmem = self.pmem
        rehash_after = False
        i, n_ops = 0, len(positions)
        kinds, keys, values = zip(*[ops[p] for p in positions])
        keys = np.array(keys, np.int64)
        # where each update stretch ends: the next op that is no update
        stops = [j for j, kind in enumerate(kinds) if kind != "update"]
        stops.append(n_ops)
        # hash once per run: the bucket is hash % n, so only the cheap
        # vectorized mod repeats when a deferral swapped the table
        hashes = mix64_ref(keys)
        while i < n_ops:
            # fast section: hold the resize lock shared across the run;
            # an op needing the scalar path (rehash) breaks out so the
            # scalar op runs lock-free *in order* — same-key op history
            # must be preserved
            deferred = None
            pmem.lock_shared(self.super, 0)
            try:
                t = self._table()
                n = pmem.load(t, 0)
                heads = HDR_WORDS + (hashes % np.uint64(n)).astype(
                    np.int64) * BUCKET_WORDS
                while i < n_ops:
                    if kinds[i] == "update" and pmem.in_group_commit:
                        end = stops[bisect.bisect_left(stops, i)]
                        i, deferred, grew = self._update_stretch(
                            t, heads, keys, values, i, end, positions,
                            results)
                        rehash_after |= grew
                        if deferred is not None:
                            break
                        continue
                    pos = positions[i]
                    head = int(heads[i])
                    pmem.lock(t, head)
                    try:
                        r = self._run_one(t, head, kinds[i], int(keys[i]),
                                          int(values[i]))
                    finally:
                        pmem.unlock(t, head)
                    if r is None:
                        deferred = pos
                        break
                    if r == "rehash_done_true":
                        results[pos] = True
                        rehash_after = True
                    else:
                        results[pos] = r
                    i += 1
            finally:
                pmem.unlock_shared(self.super, 0)
            if deferred is not None:
                kind, key, value = ops[deferred]
                results[deferred] = self._apply_write(kind, int(key),
                                                      int(value))
                i += 1
        # the growth trigger fired during the run: rehash once at the
        # end (rehash preserves the key→value mapping, so deferring it
        # past the remaining ops cannot change any result)
        if rehash_after and self.grow:
            self._rehash()

    def _update_stretch(self, t: Region, heads: np.ndarray,
                        keys: np.ndarray, values: Sequence[int], lo: int,
                        hi: int, positions: Sequence[int], results: List):
        """The updates ``lo:hi`` of a shard run, in array form, under
        the bucket locks of their distinct heads (taken in one call, in
        ascending slot order).  An update whose key is absent (insert
        semantics) runs ``_run_one`` in its turn, between the array-form
        segments before and after it; the keys found stay where they
        are, since an insert only fills an empty slot or links a bucket
        past the chain's end.  Returns (next op, the position deferred
        to the scalar path or None, whether an insert asked for a
        rehash)."""
        pmem = self.pmem
        words = np.array(values[lo:hi], np.int64)  # a plan holds int64
        slots = np.unique(heads[lo:hi]).tolist()
        pmem.lock_many(t, slots)
        try:
            word, walked_ops, walked = _locate(
                t, heads[lo:hi] // BUCKET_WORDS, keys[lo:hi])
            grew = False
            seg = 0
            for gap in np.flatnonzero(word < 0).tolist() + [hi - lo]:
                if gap > seg:
                    self._apply_updates(t, word[seg:gap], words[seg:gap])
                    for p in positions[lo + seg:lo + gap]:
                        results[p] = True
                if gap == hi - lo:
                    break
                pos = positions[lo + gap]
                r = self._run_one(t, int(heads[lo + gap]), "update",
                                  int(keys[lo + gap]), values[lo + gap])
                if r is None:  # the walks of the ops before it count
                    pmem.account_lines(t, walked[walked_ops < gap])
                    return lo + gap, pos, grew
                grew |= r == "rehash_done_true"
                results[pos] = True
                seg = gap + 1
            pmem.account_lines(t, walked)
        finally:
            pmem.unlock_many(t, slots)
        return hi, None, grew

    def _apply_updates(self, t: Region, word: np.ndarray,
                       value: np.ndarray) -> None:
        """Store a segment of updates whose keys are all present
        (``word``: each one's value word): one store per distinct key,
        of its last value, unless that equals the word already there
        (the no-op rule).  Each store is one 8-byte atomic store, the
        update's commit point; one clwb per dirtied line and the run's
        fence ride the epoch, and the ops are acknowledged when it
        closes."""
        n = word.size
        order = np.argsort(word, kind="stable")
        ordered = word[order]
        last = np.ones(n, bool)  # the last op of each key ...
        last[:-1] = ordered[1:] != ordered[:-1]
        if not last.all():
            keep = np.sort(order[last])  # ... in arrival order
            word, value = word[keep], value[keep]
        store = t.cache[word] != value
        if store.any():
            if not store.all():
                word, value = word[store], value[store]
            self._bump_epoch()
            self.pmem.store_scatter(t, word, value)
            self.pmem.clwb_lines(t, (word // WORDS_PER_LINE).tolist())
            self.pmem.fence()
        self.probe_stats["array_writes"] += n

    def _run_one(self, t: Region, head: int, kind: str, key: int,
                 value: int):
        """One op against its (locked) bucket chain via bulk line loads.
        Returns the op result, 'rehash_done_true' (inserted, chain long
        enough to grow), or None to defer to the scalar path (rehash)."""
        pmem = self.pmem
        off, last, chain_len = head, head, 0
        empty = None
        while off != NULL:
            w = pmem.load_bulk(t, off, BUCKET_WORDS).tolist()
            last, chain_len = off, chain_len + 1
            for s in range(SLOTS):
                if w[s] == key:
                    if kind == "insert":
                        return False  # CLHT insert fails on existing key
                    if kind == "delete":
                        self._bump_epoch()
                        pmem.store(t, off + s, NULL)  # atomic commit
                        pmem.clwb(t, off + s)
                        pmem.fence()
                        return True
                    # update: atomic value-word store (no-op elided)
                    if w[SLOTS + s] == value:
                        return True
                    self._bump_epoch()
                    pmem.store(t, off + SLOTS + s, value)
                    pmem.clwb(t, off + SLOTS + s)
                    pmem.fence()
                    return True
                if empty is None and w[s] == NULL:
                    empty = (off, s)
            off = w[6]
        if kind == "delete":
            return False  # absent: no store, no epoch bump
        if empty is not None:
            boff, s = empty
            # the scalar commit protocol: value first, then the atomic key
            self._bump_epoch()
            pmem.store(t, boff + SLOTS + s, value)
            pmem.clwb(t, boff + SLOTS + s)
            pmem.fence()
            pmem.store(t, boff + s, key)
            pmem.clwb(t, boff + s)
            pmem.fence()
            if chain_len > MAX_CHAIN and self.grow:
                return "rehash_done_true"
            return True
        # chain exhausted: link a fresh overflow bucket (the scalar
        # protocol — bucket persisted, then one atomic chain-pointer
        # store commits it)
        new_off = self._alloc_overflow(t)
        if new_off is None:
            return None  # arena full: the scalar rehash path
        self._bump_epoch()
        pmem.store(t, new_off + SLOTS + 0, value)
        pmem.store(t, new_off + 0, key)
        pmem.flush_range(t, new_off, new_off + BUCKET_WORDS)
        pmem.fence()
        pmem.store(t, last + 6, new_off)  # commit: atomic chain pointer
        pmem.clwb(t, last + 6)
        pmem.fence()
        if chain_len + 1 > MAX_CHAIN and self.grow:
            return "rehash_done_true"
        return True

    # ------------------------------------------------------------------
    # SMO: copy-on-write rehash, atomic table swap (Condition #1)
    # ------------------------------------------------------------------
    def _rehash(self, expect_rid: Optional[int] = None) -> None:
        self._bump_epoch()  # the table pointer is about to move
        self.pmem.lock_excl(self.super, 0)
        try:
            old = self._table()
            if expect_rid is not None and old.rid != expect_rid:
                return  # another writer already resized
            n_old = self.pmem.load(old, 0)
            new = self._new_table(n_old * 2)
            for key, value in self._items(old):
                self._raw_insert(new, key, value)
            # persist the entire new table *before* the commit point
            self.pmem.persist_region(new)
            # commit point: single atomic store of the table pointer
            self.pmem.store(self.super, 0, new.rid)
            self.pmem.clwb(self.super, 0)
            self.pmem.fence()
            self.pmem.free(old)  # unreachable; GC reclaims
        finally:
            self.pmem.unlock(self.super, 0)

    def _raw_insert(self, t: Region, key: int, value: int) -> None:
        """Insert into a private (not yet published) table: no fences."""
        off = HDR_WORDS + (_mix(key) % self.pmem.load(t, 0)) * BUCKET_WORDS
        while True:
            for s in range(SLOTS):
                if self.pmem.load(t, off + s) == NULL:
                    self.pmem.store(t, off + SLOTS + s, value)
                    self.pmem.store(t, off + s, key)
                    return
            nxt = self.pmem.load(t, off + 6)
            if nxt == NULL:
                new_off = self._alloc_overflow(t)
                if new_off is None:  # overflow arena full: grow recursively
                    raise MemoryError("overflow arena exhausted during rehash")
                self.pmem.store(t, off + 6, new_off)
                nxt = new_off
            off = nxt

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------
    def _items(self, t: Region) -> Iterator[Tuple[int, int]]:
        n = self.pmem.load(t, 0)
        for b in range(n):
            off = HDR_WORDS + b * BUCKET_WORDS
            while off != NULL:
                for s in range(SLOTS):
                    k = self.pmem.load(t, off + s)
                    if k != NULL:
                        yield k, self.pmem.load(t, off + SLOTS + s)
                off = self.pmem.load(t, off + 6)

    def keys(self) -> Iterator[int]:
        for k, _ in self._items(self._table()):
            yield k

    def items(self) -> Iterator[Tuple[int, int]]:
        return self._items(self._table())

    def check_invariants(self) -> None:
        seen = {}
        for k, v in self._items(self._table()):
            assert k not in seen, f"duplicate key {k} in table"
            seen[k] = v

    # ------------------------------------------------------------------
    # data-plane export: dense arrays for the Pallas probe kernel
    # ------------------------------------------------------------------
    def export_arrays(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                     int, np.ndarray]:
        """(keys, vals, next) bucket-major views + n_buckets + the
        per-slot fingerprint lane (``fp64`` of each slot's key,
        FP_EMPTY=0 on empty slots), for batched jit/Pallas lookups.
        Layout matches kernels/clht_probe."""
        from ..kernels.probe.fingerprint import fp64
        t = self._table()
        n = self.pmem.load(t, 0)
        total = (t.n_words - HDR_WORDS) // BUCKET_WORDS
        base = t.cache[HDR_WORDS:HDR_WORDS + total * BUCKET_WORDS].reshape(total, BUCKET_WORDS)
        keys = base[:, 0:SLOTS].copy()
        vals = base[:, SLOTS:2 * SLOTS].copy()
        nxt = base[:, 6].copy()
        # chain pointers are word offsets; convert to bucket indices (-1 = none)
        nxt = np.where(nxt == NULL, -1, (nxt - HDR_WORDS) // BUCKET_WORDS)
        return keys, vals, nxt, n, fp64(keys)

    def build_export(self) -> IndexSnapshot:
        """A full export that also arms the table's line record: every
        line stored to from here on is noted, so the next stale
        snapshot can be patched (``_delta_export``).  The record is
        armed before the walk, so no store can fall between the two."""
        t = self._table()
        lines = self.pmem.track_lines(t, self._delta_limit(t))
        snap = super().build_export()
        snap.cache[_LINES] = (t, lines, int(t.cache[1]))
        return snap

    @staticmethod
    def _delta_limit(t: Region) -> int:
        return (t.n_words - HDR_WORDS) // BUCKET_WORDS // DELTA_ROW_SHARE

    def _delta_export(self, stale: IndexSnapshot) -> Optional[IndexSnapshot]:
        """Patch ``stale`` with the bucket rows stored to since it was
        taken: bucket ``b`` is line ``b + 1`` of the table region (the
        header and every bucket are one line each).  Only when the
        record is whole: ``stale`` has its device form; no crash rolled
        the cache back; no foreign store reached the index's regions;
        the table is the same region (no rehash) and its record was
        neither re-armed nor dropped at ``DELTA_ROW_SHARE``.  The rows
        are read from the same volatile cache ``export_arrays`` reads,
        so probes of the patch equal probes of a full export."""
        from ..kernels.clht_probe import DeviceExport, patch_prepared
        from ..kernels.probe.fingerprint import fp64
        prepared = stale.cache.get("clht_probe")
        record = stale.cache.get(_LINES)
        if prepared is None or record is None:
            return None
        t, lines, cursor = record
        if (self.pmem.crashes != stale.epoch[2]
                or self._write_account() != self._accounted_stores
                or self._table() is not t or t.written is not lines):
            return None
        key = self._epoch_key()
        fresh = self.pmem.track_lines(t, self._delta_limit(t))
        with _OBS.span("snapshot.export", index=self.spec.name,
                       delta=True) as sp:
            rows = np.fromiter(lines, np.int64, len(lines))
            rows = np.sort(rows[rows > 0]) - 1  # line 0 is the header
            total = (t.n_words - HDR_WORDS) // BUCKET_WORDS
            w = t.cache[HDR_WORDS:].reshape(total, BUCKET_WORDS)[rows]
            keys = w[:, 0:SLOTS]
            vals = w[:, SLOTS:2 * SLOTS]
            nxt = np.where(w[:, 6] == NULL, -1,
                           (w[:, 6] - HDR_WORDS) // BUCKET_WORDS)
            fps = fp64(keys)
            if sp:
                sp.set(rows=int(rows.size))
        stats = self.probe_stats
        stats["exports"] += 1
        stats["delta_exports"] += 1
        stats["delta_rows"] += int(rows.size)
        # a chain pointer changes only to link a freshly allocated
        # overflow bucket, which moves the allocation cursor first
        now = int(t.cache[1])
        prepared = patch_prepared(prepared, rows, keys, vals, nxt, fps,
                                  relinked=now != cursor, stats=stats)
        return IndexSnapshot(epoch=key, arrays=DeviceExport(prepared),
                             cache={"clht_probe": prepared,
                                    _LINES: (t, fresh, now)},
                             shard_epochs=self._effective_shard_epochs())

    def _kernel_lookup(self, snapshot, queries):
        """The Pallas probe path: bit-identical to scalar ``lookup`` —
        the probe window covers whole overflow chains, the export's
        fingerprint lane filters candidates, and full 64-bit keys are
        compared on fingerprint hits (see kernels/clht_probe)."""
        from ..kernels.clht_probe import snapshot_lookup
        return snapshot_lookup(snapshot, queries,
                               fingerprints=self.fingerprints,
                               stats=self.probe_stats)
