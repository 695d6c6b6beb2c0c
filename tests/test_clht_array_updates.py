"""P-CLHT's update stretches in array form: within a shard run, a stretch
of consecutive updates of present keys is applied as array operations
(``PCLHT._update_stretch``) and must match the per-op path exactly — the
results, the items, the PM image once the epoch closes, the clwb / fence
/ load counters, and the line record the delta export patches from.
The per-op reference below runs each update of a stretch through
``_run_one`` under its own bucket lock, as the shard run did before the
array form existed."""

import sys
import threading

import numpy as np
import pytest

from repro.core import PCLHT, PMem, PMSnapshot, Plan
from repro.core.clht import BUCKET_WORDS, HDR_WORDS
from repro.core.crash_testing import plan_crash_sweep, plan_prefix_states
from repro.core.pmem import CrashPoint
from repro.kernels.partition import mix64_ref

N_BUCKETS = 64


class PerOpCLHT(PCLHT):
    """The per-op shard run: every update of a stretch walks and stores
    on its own, as ``_run_one`` does for every other op."""

    def _update_stretch(self, t, heads, keys, values, lo, hi, positions,
                        results):
        grew = False
        for i in range(lo, hi):
            pos, head = positions[i], int(heads[i])
            self.pmem.lock(t, head)
            try:
                r = self._run_one(t, head, "update", int(keys[i]),
                                  values[i])
            finally:
                self.pmem.unlock(t, head)
            if r is None:
                return i, pos, grew
            grew |= r == "rehash_done_true"
            results[pos] = True
        return hi, None, grew


def colliding(bucket, count, rng, n_buckets=N_BUCKETS):
    """``count`` distinct keys whose head bucket is ``bucket``."""
    out = []
    while len(out) < count:
        cand = rng.integers(1, 1 << 60, size=1 << 14)
        hit = cand[(mix64_ref(cand) % np.uint64(n_buckets)).astype(np.int64)
                   == bucket]
        out += [int(k) for k in hit if int(k) not in out]
    return out[:count]


def loaded(cls, keys, n_buckets=N_BUCKETS):
    """A P-CLHT holding ``keys`` (scalar inserts; no growth on chain
    length), with its table's line record armed by a full export."""
    idx = cls(PMem(), n_buckets=n_buckets, grow=False)
    for k in keys:
        assert idx.insert(k, k % 9973 + 1)
    idx.build_export()
    return idx


def run_both(keys, ops):
    """Apply ``ops`` as one write batch to the array form and to the
    per-op reference, each on its own copy of the same table."""
    out = []
    for cls in (PCLHT, PerOpCLHT):
        idx = loaded(cls, keys)
        c0 = idx.pmem.counters.snapshot()
        res = idx._write_batch(ops)
        out.append((idx, res, idx.pmem.counters.delta(c0)))
    return out


def _keys(seed, n=150):
    rng = np.random.default_rng(seed)
    keys = [int(k) for k in np.unique(rng.integers(1, 1 << 60, size=n))]
    return keys, rng


# each case: (keys to load, a write batch) for a table of n_buckets
def repeated_and_noop(seed, n_buckets=N_BUCKETS):
    """Updates of present keys: each key a few times, ending on a fresh
    value, and keys rewritten with the value they hold."""
    keys, rng = _keys(seed)
    ops = []
    for _ in range(300):
        k = keys[int(rng.integers(0, 60))]
        ops.append(("update", k, int(rng.integers(1, 1 << 40))))
    ops += [("update", k, k % 9973 + 1) for k in keys[60:90]]
    return keys, ops


def absent_keys(seed, n_buckets=N_BUCKETS):
    """Updates of absent keys among present ones: insert semantics, in
    order, and an absent key updated again after its insert."""
    keys, rng = _keys(seed)
    fresh = [int(k) for k in rng.integers(1 << 60, 1 << 61, size=20)]
    ops = []
    for j in range(120):
        k = fresh[j % 20] if j % 5 == 0 else keys[j]
        ops.append(("update", k, int(rng.integers(1, 1 << 40))))
    return keys, ops


def overflow_chains(seed, n_buckets=N_BUCKETS):
    """Keys four and five buckets down their head's chain."""
    rng = np.random.default_rng(seed)
    chained = (colliding(3, 14, rng, n_buckets)
               + colliding(40, 11, rng, n_buckets))
    keys = chained + [int(k) for k in rng.integers(1, 1 << 60, size=60)]
    ops = [("update", k, int(rng.integers(1, 1 << 40)))
           for k in rng.permutation(chained + chained).tolist()]
    return keys, ops


def broken_by_writes(seed, n_buckets=N_BUCKETS):
    """Stretches of updates broken by inserts and deletes of present and
    absent keys, and an update of a key deleted earlier in the run."""
    keys, rng = _keys(seed)
    ops = []
    for j in range(300):
        r = rng.random()
        if r < 0.15:
            ops.append(("insert", int(rng.integers(1 << 60, 1 << 61)), 7))
        elif r < 0.25:
            ops.append(("delete", keys[int(rng.integers(0, 150))], 0))
        else:
            ops.append(("update", keys[int(rng.integers(0, 150))],
                        int(rng.integers(1, 1 << 40))))
    return keys, ops


CASES = [repeated_and_noop, absent_keys, overflow_chains, broken_by_writes]


def assert_same(keys, ops):
    """Both forms give the same results, items, PM image, line record
    and counters (stores aside: a repeated key stores once)."""
    (ia, ra, ca), (ib, rb, cb) = run_both(keys, ops)
    assert ra == rb
    assert sorted(ia.items()) == sorted(ib.items())
    ia.check_invariants()
    for a, b in zip(ia.pmem.regions.values(), ib.pmem.regions.values()):
        assert a.name == b.name
        assert (a.pm == b.pm).all() and (a.cache == b.cache).all()
        assert a.written == b.written
    assert (ca.clwb, ca.fence, ca.loads, ca.lines_touched) \
        == (cb.clwb, cb.fence, cb.loads, cb.lines_touched)
    assert ca.stores <= cb.stores
    ia.pmem.assert_clean()
    assert ia.probe_stats["array_writes"] > 0
    assert ib.probe_stats["array_writes"] == 0
    return ia


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__)
@pytest.mark.parametrize("seed", [1, 2])
def test_array_form_matches_the_per_op_path(case, seed):
    assert_same(*case(seed))


def test_an_insert_that_finds_the_arena_full_defers_in_its_turn():
    """Absent keys fill the overflow arena mid-stretch: the insert that
    finds it full defers to the scalar path (a rehash), and the rest
    of the stretch runs after it on the new table."""
    keys, rng = _keys(12)
    fresh = [int(k) for k in rng.integers(1 << 60, 1 << 61, size=150)]
    ops = [("update", fresh[j // 2] if j % 2 else keys[j // 2],
            int(rng.integers(1, 1 << 40))) for j in range(300)]
    idx = assert_same(keys, ops)
    assert idx._table().name == f"clht.table[{2 * N_BUCKETS}]"


def test_overflow_case_walks_the_chains():
    keys, ops = overflow_chains(1)
    idx = loaded(PCLHT, keys)
    heads = HDR_WORDS + (mix64_ref(np.asarray([op[1] for op in ops]))
                         % np.uint64(N_BUCKETS)).astype(np.int64) \
        * BUCKET_WORDS
    t = idx._table()
    depth = []
    for (_, k, _), off in zip(ops, heads.tolist()):
        d = 1
        while k not in t.cache[off:off + 3]:
            off, d = int(t.cache[off + 6]), d + 1
        depth.append(d)
    assert max(depth) >= 4


def test_a_key_back_at_its_stored_value_stores_nothing():
    """A key updated away and back within one stretch ends where it
    was: the array form stores its last value only, so it stores
    nothing, and the image matches the per-op path's."""
    keys, rng = _keys(5)
    ops = [("update", keys[0], 11), ("update", keys[1], 12),
           ("update", keys[0], keys[0] % 9973 + 1)]
    (ia, ra, ca), (ib, rb, cb) = run_both(keys, ops)
    assert ra == rb == [True] * 3
    assert sorted(ia.items()) == sorted(ib.items())
    assert ca.stores == 1 and cb.stores == 3
    for a, b in zip(ia.pmem.regions.values(), ib.pmem.regions.values()):
        assert (a.pm == b.pm).all()


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__)
def test_the_delta_export_patches_the_same_rows(case):
    """A table large enough that the batch's rows stay under the
    delta's cut (``DELTA_ROW_SHARE``): both forms patch the same rows,
    and the patched snapshot answers as scalar lookups do."""
    pytest.importorskip("jax")
    keys, ops = case(3, n_buckets=2048)
    probes = keys + [op[1] for op in ops]
    rows = []
    for cls in (PCLHT, PerOpCLHT):
        idx = cls(PMem(), n_buckets=2048, grow=False)
        idx.execute(Plan.from_ops([("insert", k, k % 9973 + 1)
                                   for k in keys]))
        idx.execute(Plan.from_ops([("lookup", k, 0) for k in keys]),
                    force_kernel=True)
        idx._write_batch(ops)
        res = idx.execute(Plan.from_ops([("lookup", k, 0) for k in probes]),
                          force_kernel=True)
        assert res.results == [idx.lookup(k) for k in probes]
        rows.append((idx.probe_stats["delta_exports"],
                     idx.probe_stats["delta_rows"]))
    assert rows[0] == rows[1] and rows[0][0] == 1


def expected_array_writes(idx, ops, present):
    """The updates that find their key present when their stretch (a
    maximal run of consecutive updates in one shard's run) begins."""
    shards = idx.shard_route(np.asarray([op[1] for op in ops], np.int64))
    model = set(present)
    count = 0
    for s in range(idx.N_WRITE_SHARDS):
        at_start = None
        for (kind, k, _), sh in zip(ops, shards.tolist()):
            if sh != s:
                continue
            if kind == "update":
                if at_start is None:
                    at_start = set(model)
                count += k in at_start
                model.add(k)
            else:
                at_start = None
                if kind == "insert":
                    model.add(k)
                else:
                    model.discard(k)
    return count


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__)
def test_array_writes_counts_the_ops_of_the_array_form(case):
    keys, ops = case(4)
    idx = loaded(PCLHT, keys)
    res = idx.execute(Plan.from_ops(ops))
    want = expected_array_writes(idx, ops, keys)
    assert res.probe["array_writes"] == want > 0
    assert idx.probe_stats["array_writes"] == want


def test_an_insert_only_plan_counts_no_array_writes():
    keys, rng = _keys(6)
    idx = PCLHT(PMem(), n_buckets=N_BUCKETS)
    res = idx.execute(Plan.from_ops([("insert", k, 3) for k in keys]))
    assert res.probe["array_writes"] == 0
    assert idx.probe_stats["array_writes"] == 0


def test_the_write_wave_span_carries_array_writes():
    from repro import obs
    keys, rng = _keys(7)
    idx = loaded(PCLHT, keys)
    plan = Plan.from_ops([("update", k, 5) for k in keys[:40]]
                         + [("lookup", k, 0) for k in keys[40:80]])
    obs.reset()
    obs.enable()
    try:
        idx.execute(plan)
        waves = [s for s in obs.spans("plan.wave")
                 if s.attrs["kind"] == "write"]
    finally:
        obs.disable()
        obs.reset()
    assert [w.attrs["array_writes"] for w in waves] == [40]
    assert waves[0].attrs["width"] == 40


def mixed_plan(seed, keys, n=48):
    """50 % GETs and 50 % UPDATEs of loaded keys, keys repeating."""
    rng = np.random.default_rng(seed)
    hot = keys[:24]
    return [("lookup" if rng.random() < 0.5 else "update",
             hot[int(rng.integers(0, len(hot)))],
             int(rng.integers(1, 1 << 40))) for _ in range(n)]


@pytest.mark.parametrize("mode,evict", [("powerfail", 0.0),
                                        ("powerfail", 0.5),
                                        ("interrupt", 0.0)])
def test_a_crash_at_every_store_offset_recovers_a_plan_prefix(mode, evict):
    """Crash the plan at every crash point it passes, each store inside
    an update stretch's scatter among them: every key reads its old
    value or a value of its own plan prefix."""
    keys, rng = _keys(8, n=60)
    setup = [("insert", k, k % 9973 + 1) for k in keys]
    ops = mixed_plan(8, keys)
    pmem = PMem(seed=8)
    idx = PCLHT(pmem, n_buckets=N_BUCKETS)
    idx.execute(Plan.from_ops(setup))
    states, model = plan_prefix_states(ops, base=dict(
        (k, k % 9973 + 1) for k in keys))
    snap = PMSnapshot(pmem, idx)
    plan = Plan.from_ops(ops)

    def prime():
        # the same export before every run, so each run passes the same
        # crash points (as plan_crash_sweep primes its re-runs)
        idx._snapshot = None
        idx._accounted_stores = idx._write_account()
        idx.snapshot()

    prime()
    c0, s0 = pmem.crash_calls, pmem.counters.stores
    idx.execute(plan)
    calls, stores = pmem.crash_calls - c0, pmem.counters.stores - s0
    assert idx.probe_stats["array_writes"] > 0 and stores > 10
    assert calls >= stores
    for off in range(calls):
        snap.restore(pmem)
        prime()
        pmem.arm_crash(after_stores=off)
        with pytest.raises(CrashPoint):
            idx.execute(plan)
        pmem.crash(mode=mode, evict_probability=evict)
        idx.recover()
        for k in keys:
            assert idx.lookup(k) in states.get(k, {k % 9973 + 1}), (off, k)
        idx.check_invariants()
    snap.restore(pmem)
    idx.execute(plan)
    assert dict(idx.items()) == model


def test_plan_crash_sweep_passes_on_a_get_update_plan():
    keys, rng = _keys(9, n=60)
    setup = [("insert", k, k % 9973 + 1) for k in keys]
    rep = plan_crash_sweep(lambda p: PCLHT(p, n_buckets=N_BUCKETS),
                           mixed_plan(9, keys), setup_ops=setup,
                           max_points=None)
    assert rep.ok, rep.consistency_failures + rep.stall_failures
    assert rep.n_crash_states > 0


def test_a_scalar_writer_waits_for_the_stretch_locks():
    """A scalar update of a key in a bucket the stretch holds blocks
    until the stretch has stored and unlocked, then lands after it."""
    keys, rng = _keys(10)
    idx = loaded(PCLHT, keys)
    t = idx._table()
    batch = keys[:40]
    k0 = batch[0]
    head = HDR_WORDS + int(mix64_ref(np.asarray([k0]))[0]
                           % np.uint64(N_BUCKETS)) * BUCKET_WORDS
    scatter = idx.pmem.store_scatter
    seen = {}

    def writer():
        seen["r"] = idx.update(k0, 424242)

    def held(region, words, values):
        # the scatter of k0's shard run: its stretch holds k0's bucket
        if "thread" not in seen and idx.pmem.holds_lock(t, head):
            th = threading.Thread(target=writer)
            th.start()
            th.join(timeout=0.005)
            seen["blocked"] = th.is_alive()
            seen["thread"] = th
        scatter(region, words, values)

    idx.pmem.store_scatter = held
    assert idx._write_batch([("update", k, 17) for k in batch]) \
        == [True] * 40
    th = seen["thread"]
    th.join(timeout=10)
    assert not th.is_alive() and seen["r"] is True
    assert seen["blocked"]
    assert idx.lookup(k0) == 424242
    assert all(idx.lookup(k) == 17 for k in batch[1:])
    assert not idx.pmem.locks


def test_scalar_writers_beside_update_stretches_lose_no_write():
    """Stress: scalar writers update keys that share buckets with a
    batch's keys while the batches run as update stretches.  Nothing
    deadlocks, and every key ends on its own writer's last value: a
    torn or clobbered word would show as a lost write."""
    keys, rng = _keys(11, n=400)
    idx = loaded(PCLHT, keys)
    batch_keys, scalar_keys = keys[:200], keys[200:]
    n_threads, rounds = 4, 30
    errors = []

    def scalar(j):
        try:
            for r in range(rounds):
                for k in scalar_keys[j::n_threads]:
                    idx.update(k, k + r + 1)
        except Exception as e:  # surfaces in the main thread
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=scalar, args=(j,))
                   for j in range(n_threads)]
        for th in threads:
            th.start()
        for r in range(rounds):
            idx._write_batch([("update", k, k * 3 + r) for k in batch_keys])
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    assert not errors, errors
    assert idx.probe_stats["array_writes"] == rounds * len(batch_keys)
    for k in batch_keys:
        assert idx.lookup(k) == k * 3 + rounds - 1
    for k in scalar_keys:
        assert idx.lookup(k) == k + rounds
    assert not idx.pmem.locks
