"""P-CLHT delta exports: a stale snapshot made current by patching only
the bucket rows stored to since the last export, on the host and on the
device.  The patched device form must equal a fresh full export's, bit
for bit, and every case the record cannot vouch for must fall back to
the full export."""

import numpy as np
import pytest

from repro.core import PCLHT, PMem, Plan
from repro.core.clht import BUCKET_WORDS, DELTA_ROW_SHARE, HDR_WORDS, \
    MAX_CHAIN, SLOTS
from repro.core.conditions import IndexSnapshot
from repro.kernels.clht_probe import DeviceExport, mix64
from repro.kernels.clht_probe.ops import PATCH_ROWS, _PATCH_COLS, \
    _prepare, _scatter_rows

pytest.importorskip("jax")

N_BUCKETS = 1024


def colliding(n_buckets, bucket, count, rng, taken=()):
    """``count`` fresh keys that hash to ``bucket``."""
    out = []
    while len(out) < count:
        cand = rng.integers(1, 1 << 60, size=1 << 16).astype(np.int64)
        hit = cand[(mix64(cand) % np.uint64(n_buckets)).astype(np.int64)
                   == bucket]
        out += [int(k) for k in hit if int(k) not in taken
                and int(k) not in out]
    return out[:count]


def loaded(seed, n_keys=1400, n_buckets=N_BUCKETS):
    """A P-CLHT loaded through a write plan (nothing tracked: no export
    exists yet), then read once so the snapshot has its device form."""
    rng = np.random.default_rng(seed)
    idx = PCLHT(PMem(), n_buckets=n_buckets)
    keys = [int(k) for k in np.unique(rng.integers(1, 1 << 60,
                                                   size=n_keys))]
    idx.execute(Plan.from_ops([("insert", k, k % 9973 + 1) for k in keys]))
    assert idx._table().written is None
    read_all(idx, keys)
    assert idx.probe_stats["exports"] == 1
    return idx, keys, rng


def read_all(idx, keys):
    """A forced-kernel read of ``keys`` (a snapshot() on a stale
    export), checked against scalar ``lookup``."""
    res = idx.execute(Plan.from_ops([("lookup", k, 0) for k in keys]),
                      force_kernel=True)
    assert res.results == [idx.lookup(k) for k in keys]
    return res


def assert_form_is_full_export(idx):
    """The memoized device form equals ``_prepare`` of a fresh full
    export, bit for bit: halves, fingerprints, chain rows, depth, n."""
    snap = idx._snapshot
    full = _prepare(IndexSnapshot(epoch=None, arrays=idx.export_arrays()),
                    None)
    got = snap.cache["clht_probe"]
    for want, have in zip(full[0] + [full[1], full[2]],
                          got[0] + [got[1], got[2]]):
        assert np.asarray(want).dtype == np.asarray(have).dtype
        assert np.array_equal(np.asarray(want), np.asarray(have))
    assert got[3:] == full[3:]


def counts(idx):
    return {k: idx.probe_stats[k]
            for k in ("exports", "delta_exports", "delta_rows")}


# ----------------------------------------------------------------------
# the differential test: random plans, patched form == full export
# ----------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_delta_export_equals_full_export(seed):
    idx, keys, rng = loaded(seed)
    live = set(keys)
    # an empty bucket whose chain the PUTs grow, one row at a time
    heads = idx.export_arrays()[0][:N_BUCKETS]
    empty = int(np.nonzero((heads == 0).all(axis=1))[0][0])
    chain = colliding(N_BUCKETS, empty, 3 + 2 * MAX_CHAIN, rng, live)
    depth0 = idx._snapshot.cache["clht_probe"][3]
    deltas = 0
    for step in range(MAX_CHAIN + 2):
        upd = rng.choice(sorted(live), 30, replace=False).tolist()
        gone = rng.choice(sorted(live - set(upd)), 8,
                          replace=False).tolist()
        fresh = [int(k) for k in rng.integers(1 << 60, 1 << 61, size=12)]
        fresh += chain[:3] if step == 0 else chain[1 + 2 * step:3 + 2 * step]
        ops = ([("update", k, int(rng.integers(1, 1 << 40))) for k in upd]
               + [("delete", k, 0) for k in gone]
               + [("insert", k, k % 7 + 1) for k in fresh]
               + [("lookup", k, 0) for k in upd[:10] + gone[:4]])
        ops = [ops[i] for i in rng.permutation(len(ops))]
        model = {k: idx.lookup(k) for _, k, _ in ops}
        res = idx.execute(Plan.from_ops(ops))
        for (kind, k, v), got in zip(ops, res.results):  # program order
            if kind == "lookup":
                assert got == model[k]
            elif kind == "delete":
                model[k] = None
            else:
                model[k] = v
        live = (live - set(gone)) | set(fresh)
        before = counts(idx)
        read_all(idx, sorted(live) + gone)
        after = counts(idx)
        assert after["exports"] == before["exports"] + 1
        assert after["delta_exports"] == before["delta_exports"] + 1
        deltas += 1
        assert_form_is_full_export(idx)
        # the host view of a delta is the full export's, read back
        view = idx._snapshot.arrays
        assert isinstance(view, DeviceExport)
        for want, have in zip(idx.export_arrays(), view):
            assert np.array_equal(np.asarray(want), np.asarray(have))
        for k in gone:
            assert idx.lookup(k) is None  # tombstoned
    assert idx.probe_stats["delta_exports"] == deltas
    assert idx._snapshot.cache["clht_probe"][3] > depth0
    assert idx._snapshot.cache["clht_probe"][3] == MAX_CHAIN


@pytest.mark.parametrize("seed", [5, 6])
def test_mixed_plans_match_scalar_reads(seed):
    """Plans that interleave GETs with UPDATE/PUT/DELETE take the
    optimistic and refined reads against delta-made snapshots."""
    idx, keys, rng = loaded(seed)
    model = {k: idx.lookup(k) for k in keys}
    for _ in range(6):
        ops = []
        for _ in range(200):
            k = int(rng.choice(keys))
            r = rng.random()
            if r < 0.5:
                ops.append(("lookup", k, 0))
            elif r < 0.8:
                ops.append(("update", k, int(rng.integers(1, 1 << 40))))
            elif r < 0.9:
                ops.append(("delete", k, 0))
            else:
                ops.append(("insert", k, int(rng.integers(1, 1 << 40))))
        res = idx.execute(Plan.from_ops(ops))
        for (kind, k, v), got in zip(ops, res.results):
            if kind == "lookup":
                assert got == model.get(k)
            elif kind == "update":
                model[k] = v
            elif kind == "delete":
                model.pop(k, None)
            elif k not in model:
                model[k] = v
        read_all(idx, keys)
        assert_form_is_full_export(idx)
    assert idx.probe_stats["delta_exports"] > 0


def test_pre_write_snapshot_keeps_its_answers_after_a_delta():
    """The delta scatters into copies: the stale snapshot's device form
    still answers with the pre-write values, as the optimistic and
    refined reads need."""
    idx, keys, rng = loaded(7)
    old = idx._snapshot
    some = np.asarray(keys[:64], np.int64)
    before = idx._kernel_lookup(old, some)
    idx._write_batch([("update", int(k), 123456789) for k in some])
    new = idx.snapshot()
    assert new is not old and idx.probe_stats["delta_exports"] == 1
    found, vals = idx._kernel_lookup(new, some)
    assert found.all() and (vals == 123456789).all()
    again = idx._kernel_lookup(old, some)
    assert np.array_equal(again[0], before[0])
    assert np.array_equal(again[1], before[1])
    assert (before[1] != 123456789).all()


def test_a_delta_of_no_rows_keeps_the_device_form():
    """Stores to another structure on the same PMem move the epoch key
    but no row of this table: the delta reuses the device form whole."""
    idx, keys, _ = loaded(8)
    other = PCLHT(idx.pmem, n_buckets=64, name="other")
    other.insert(5, 6)
    old = idx._snapshot
    snap = idx.snapshot()
    assert snap is not old and idx.probe_stats["delta_rows"] == 0
    assert snap.cache["clht_probe"] is old.cache["clht_probe"]


def test_a_delta_is_booked_in_spans_counters_and_stats():
    from repro import obs
    from repro.api import open_index
    s = open_index("clht")
    keys = list(range(1, 2001))
    s.execute(Plan.from_ops([("insert", k, k * 7) for k in keys]))
    s.execute(Plan.from_ops([("lookup", k, 0) for k in keys]),
              force_kernel=True)
    s.execute(Plan.from_ops([("update", k, k * 9) for k in keys[:20]]))
    obs.reset()
    obs.enable()
    try:
        res = s.execute(Plan.from_ops([("lookup", k, 0) for k in keys]),
                        force_kernel=True)
    finally:
        obs.disable()
    assert res.results == [k * 9 if k <= 20 else k * 7 for k in keys]
    rows = res.probe["delta_rows"]
    assert res.probe["delta_exports"] == res.probe["exports"] == 1
    assert 0 < rows <= 20
    assert s.stats["delta_exports"] == 1 and s.stats["delta_rows"] == rows
    (ex,) = obs.spans("snapshot.export")
    assert ex.attrs["delta"] is True and ex.attrs["rows"] == rows
    (up,) = obs.spans("snapshot.upload")
    patch = 4 * _PATCH_COLS * PATCH_ROWS
    assert up.attrs["delta"] is True and up.attrs["rows"] == rows
    assert up.attrs["bytes"] == patch
    (wave,) = obs.spans("plan.wave")
    assert wave.attrs["delta_exports"] == 1
    assert wave.attrs["delta_rows"] == rows
    assert wave.attrs["upload_bytes"] == res.probe["upload_bytes"] > patch
    obs.reset()


# ----------------------------------------------------------------------
# the full-export fallbacks
# ----------------------------------------------------------------------
def _powerfail(idx, keys, rng):
    idx._write_batch([("update", k, 77) for k in keys[:20]])
    idx.pmem.crash("powerfail")
    idx.recover()


def _rehash(idx, keys, rng):
    t = idx._table()
    heads = idx.export_arrays()[0][:N_BUCKETS]
    empty = int(np.nonzero((heads == 0).all(axis=1))[0][0])
    chain = colliding(N_BUCKETS, empty, SLOTS * MAX_CHAIN + 1, rng,
                      set(keys))
    idx._write_batch([("insert", k, 1) for k in chain])
    assert idx._table() is not t


def _foreign_store(idx, keys, rng):
    t = idx._table()
    k = keys[0]
    off = HDR_WORDS + int(mix64(np.asarray([k]))[0] % np.uint64(N_BUCKETS)) \
        * BUCKET_WORDS
    slot = [idx.pmem.load(t, off + s) for s in range(SLOTS)].index(k)
    idx.pmem.store(t, off + SLOTS + slot, 4242)  # not through the index
    assert idx.lookup(k) == 4242


def _scalar_writer(idx, keys, rng):
    idx.update(keys[0], 31337)


@pytest.mark.parametrize("fault", [_powerfail, _rehash, _foreign_store,
                                   _scalar_writer],
                         ids=["powerfail", "rehash", "foreign_store",
                              "scalar_writer"])
def test_fallback_to_the_full_export(fault):
    idx, keys, rng = loaded(9)
    idx._write_batch([("update", k, 99) for k in keys[20:40]])
    fault(idx, keys, rng)
    before = counts(idx)
    read_all(idx, keys)
    after = counts(idx)
    assert after["exports"] == before["exports"] + 1
    assert after["delta_exports"] == before["delta_exports"] == 0
    assert_form_is_full_export(idx)
    # the full export re-armed the record: the next stale read patches
    idx._write_batch([("update", k, 5) for k in keys[40:60]])
    read_all(idx, keys)
    assert idx.probe_stats["delta_exports"] == 1
    assert_form_is_full_export(idx)


def test_a_restored_image_takes_the_full_export():
    """A crash-test restore rewrites the cache wholesale, past the line
    record: the next stale read must re-export whole."""
    from repro.core.crash_testing import PMSnapshot
    idx, keys, _ = loaded(12)
    image = PMSnapshot(idx.pmem, idx)
    idx._write_batch([("update", k, 11) for k in keys[:50]])
    read_all(idx, keys)  # a delta: the device form holds the 11s
    assert idx.probe_stats["delta_exports"] == 1
    image.restore(idx.pmem)
    idx._write_batch([("update", k, 12) for k in keys[50:60]])
    read_all(idx, keys)
    assert idx.probe_stats["delta_exports"] == 1
    assert_form_is_full_export(idx)


def test_too_many_rows_take_the_full_export():
    idx, keys, _ = loaded(10)
    rows = (idx._table().n_words - HDR_WORDS) // BUCKET_WORDS
    assert len(keys) > rows // DELTA_ROW_SHARE
    idx._write_batch([("update", k, 3) for k in keys])
    assert idx._table().written is None  # dropped past the limit
    read_all(idx, keys)
    assert idx.probe_stats["delta_exports"] == 0
    assert idx.probe_stats["exports"] == 2


def test_line_record_catches_store_and_store_bulk():
    pmem = PMem()
    r = pmem.alloc("r", 64)
    other = pmem.alloc("o", 64)
    lines = pmem.track_lines(r, limit=5)
    pmem.store(r, 9, 1)
    pmem.store_bulk(r, 30, np.arange(12, dtype=np.int64))
    pmem.store(other, 0, 1)
    assert lines == {1, 3, 4, 5} and other.written is None
    pmem.store(r, 63, 1)
    assert lines == {1, 3, 4, 5, 7} and r.written is lines
    pmem.store(r, 0, 1)  # a sixth line: the record is dropped
    assert r.written is None
    assert pmem.track_lines(r, limit=5) is not lines


# ----------------------------------------------------------------------
# the scatter's shape: one compiled program per table size
# ----------------------------------------------------------------------
def test_delta_scatter_compiles_one_shape_per_table():
    """Deltas of any row count, a patch of several blocks among them,
    run the one scatter program the first delta compiled."""
    idx, keys, rng = loaded(11, n_keys=40000, n_buckets=1 << 15)
    rows_per_delta = []
    compiled0 = _scatter_rows._cache_size()
    for w in [2, 40, 300, PATCH_ROWS + 900, 3, 1200]:
        upd = rng.choice(keys, w, replace=False).tolist()
        res = idx.execute(Plan.from_ops(
            [("update", int(k), len(rows_per_delta) + 17) for k in upd]))
        assert res.acked == w
        before = idx.probe_stats["delta_rows"]
        read_all(idx, keys[:1024])
        rows_per_delta.append(idx.probe_stats["delta_rows"] - before)
        assert 0 < rows_per_delta[-1] <= w
        assert _scatter_rows._cache_size() - compiled0 == 1
    assert max(rows_per_delta) > PATCH_ROWS  # a patch of two blocks
    assert idx.probe_stats["delta_exports"] == len(rows_per_delta)
    assert_form_is_full_export(idx)
