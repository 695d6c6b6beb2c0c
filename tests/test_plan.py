"""The operation-plan API: conflict-wave scheduling must preserve
per-key program order (results positionally identical to scalar
execution), scans must never conflict with scans, single-op plans must
degenerate to the scalar path, a crash mid-plan must recover to a
plan-prefix-consistent state on all five indexes, and the public
``repro.api`` facade must drain pipelines on read."""

import numpy as np
import pytest

from repro.core import (CrashPoint, PART, PBwTree, PCLHT, PHOT, PMasstree,
                        PMem, PMSnapshot, Plan, schedule_waves)
from repro.core.plan import DELETE, GET, PUT, SCAN, UPDATE, _levels_no_scan
from repro.kernels.conflict import (conflict_any, conflict_matrix_ref,
                                    wave_levels_ref)

FACTORIES = [
    ("P-CLHT", lambda p: PCLHT(p, n_buckets=64)),
    ("P-ART", PART),
    ("P-HOT", PHOT),
    ("P-Masstree", PMasstree),
    ("P-BwTree", PBwTree),
]
ORDERED_FACTORIES = [(n, f) for n, f in FACTORIES if n != "P-CLHT"]


def _random_plan(rng, n, n_keys, *, scans):
    kinds = rng.integers(0, 5 if scans else 4, size=n).astype(np.int32)
    keys = rng.integers(1, n_keys, size=n).astype(np.int64)
    aux = rng.integers(1, 50, size=n).astype(np.int64)
    return kinds, keys, aux


def _apply_scalar(idx, kinds, keys, aux):
    out = []
    for k, key, a in zip(kinds.tolist(), keys.tolist(), aux.tolist()):
        if k == GET:
            out.append(idx.lookup(key))
        elif k == PUT:
            out.append(idx.insert(key, a))
        elif k == UPDATE:
            out.append(idx.update(key, a))
        elif k == DELETE:
            out.append(idx.delete(key))
        else:
            out.append(idx.scan(key, a))
    return out


# -- scheduler ------------------------------------------------------------

def test_levels_match_peeling_oracle():
    """The vectorized no-scan level assignment (before the push-late
    pass) is exactly the kernels/conflict peeling oracle."""
    rng = np.random.default_rng(2)
    for _ in range(40):
        n = int(rng.integers(1, 150))
        kinds, keys, _ = _random_plan(rng, n, 20, scans=False)
        got = _levels_no_scan(kinds, keys, push_reads_late=False)
        assert (got == wave_levels_ref(kinds, keys)).all()


def test_waves_respect_conflict_order():
    """Every conflicting op pair lands in waves ordered like program
    order; waves are type-homogeneous and cover the plan exactly."""
    rng = np.random.default_rng(3)
    for trial in range(60):
        n = int(rng.integers(1, 140))
        kinds, keys, _ = _random_plan(rng, n, 18, scans=bool(trial % 2))
        waves = schedule_waves(kinds, keys)
        wpos = np.empty(n, np.int64)
        seen = np.zeros(n, bool)
        for wi, w in enumerate(waves):
            assert not seen[w.indices].any()
            seen[w.indices] = True
            wpos[w.indices] = wi
        assert seen.all()
        conf = conflict_matrix_ref(kinds, keys, kinds, keys)
        conf &= np.tri(n, k=-1, dtype=bool).T  # keep i<j pairs
        ii, jj = np.nonzero(conf)
        assert (wpos[ii] < wpos[jj]).all()


def test_scans_never_conflict_with_scans():
    """Back-to-back scans over identical start keys schedule as ONE
    wave — the PhaseExecutor double-flush fix: scans are reads and
    never fence each other."""
    kinds = np.full(32, SCAN, np.int32)
    keys = np.full(32, 12345, np.int64)
    waves = schedule_waves(kinds, keys)
    assert len(waves) == 1 and waves[0].kind == "scan"
    assert waves[0].indices.size == 32
    # and mixing in non-conflicting reads still yields exactly two
    # read-class waves (no interleaved flushing)
    kinds2 = np.array([SCAN, GET, SCAN, GET, SCAN], np.int32)
    keys2 = np.array([100, 7, 100, 7, 100], np.int64)
    waves2 = schedule_waves(kinds2, keys2)
    assert sorted(w.kind for w in waves2) == ["read", "scan"]


def test_conflict_kernel_matches_ref():
    """Pallas conflict_any against the numpy oracle, across kinds,
    same-key pairs, and scan-window boundaries."""
    rng = np.random.default_rng(5)
    ka, keya, _ = _random_plan(rng, 200, 40, scans=True)
    kb, keyb, _ = _random_plan(rng, 300, 40, scans=True)
    # force boundary cases: equal keys and key == start
    keyb[:40] = keya[:40]
    for wc in (False, True):
        ref = conflict_any(ka, keya, kb, keyb, writes_conflict=wc)
        got = conflict_any(ka, keya, kb, keyb, writes_conflict=wc,
                           use_kernel=True)
        assert (ref == got).all()


# -- execute() semantics --------------------------------------------------

@pytest.mark.parametrize("name,factory", FACTORIES)
def test_execute_equals_scalar_mixed(name, factory):
    """Mixed random plans (incl. same-key RMW chains) produce slot
    results positionally identical to scalar in-order execution."""
    rng = np.random.default_rng(11)
    idx, ref = factory(PMem()), factory(PMem())
    scans = idx.ORDERED
    for round_ in range(3):
        n = 250
        kinds, keys, aux = _random_plan(rng, n, 40, scans=scans)
        plan = Plan.from_arrays(kinds, keys, aux)
        expected = _apply_scalar(ref, kinds, keys, aux)
        got = idx.execute(plan)
        assert got.results == expected, [
            (i, a, b) for i, (a, b) in enumerate(zip(got.results, expected))
            if a != b][:5]
        assert sorted(idx.items()) == sorted(ref.items())
    idx.check_invariants()
    idx.pmem.assert_clean()


@pytest.mark.parametrize("name,factory", FACTORIES)
def test_same_key_rmw_ordering(name, factory):
    """A full insert→read→update→read→delete→read history on one key
    inside one plan observes every intermediate state."""
    idx = factory(PMem())
    k = 0xBEEF
    plan = Plan()
    plan.put(k, 1)
    plan.get(k)
    plan.update(k, 2)
    plan.get(k)
    plan.delete(k)
    plan.get(k)
    res = idx.execute(plan)
    assert res.results == [True, 1, True, 2, True, None]
    assert res.n_waves == 6  # strict alternation cannot batch


@pytest.mark.parametrize("name,factory", ORDERED_FACTORIES)
def test_scan_overlapping_write_fencing(name, factory):
    """A scan must not observe writes that follow it in the plan, and
    must observe writes that precede it — including inserts landing
    inside the scan window (key >= start)."""
    idx = factory(PMem())
    for k in range(10, 100, 10):
        idx.insert(k, k)
    plan = Plan()
    s0 = plan.scan(10, 20)      # pre-state: 10..90
    plan.put(15, 15)            # lands inside the window
    s1 = plan.scan(10, 20)      # must see 15
    plan.delete(20)
    s2 = plan.scan(10, 20)      # must not see 20
    res = idx.execute(plan)
    assert [k for k, _ in res.results[s0]] == list(range(10, 100, 10))
    assert 15 in [k for k, _ in res.results[s1]]
    got2 = [k for k, _ in res.results[s2]]
    assert 20 not in got2 and 15 in got2
    # a scan strictly above every write is conflict-free with them
    plan2 = Plan()
    plan2.put(5, 5)
    hi = plan2.scan(50, 10)
    res2 = idx.execute(plan2)
    assert [k for k, _ in res2.results[hi]][0] == 50


@pytest.mark.parametrize("name,factory", FACTORIES)
def test_single_op_plan_degenerates_to_scalar(name, factory):
    """A single-op plan must not export arrays, probe kernels, or
    partition shards — it is exactly one scalar call."""
    idx = factory(PMem())
    for k in range(1, 40):
        idx.insert(k * 7, k)
    calls = {"export": 0}
    orig = idx.export_arrays

    def counting_export():
        calls["export"] += 1
        return orig()

    idx.export_arrays = counting_export
    plan = Plan()
    plan.get(21)
    assert idx.execute(plan).results == [3]
    plan = Plan()
    plan.put(999983, 5)
    assert idx.execute(plan).results == [True]
    if idx.ORDERED:
        plan = Plan()
        plan.scan(7, 2)
        assert idx.execute(plan).results == [[(7, 1), (14, 2)]]
    assert calls["export"] == 0, "single-op plan touched the export path"


@pytest.mark.parametrize("name,factory", FACTORIES)
def test_mid_wave_crash_prefix_consistent(name, factory):
    """Crash injection at sampled store counts inside execute(): after
    powerfail + recovery, every key's durable state is a prefix of
    that key's op history in the plan (earlier waves durable, the
    in-flight wave all-or-nothing per shard group, later waves
    absent), and the index accepts new writes."""
    pmem = PMem()
    idx = factory(pmem)
    rng = np.random.default_rng(23)
    pre = {int(k): (int(k) % 9973) + 1
           for k in rng.integers(1, 1 << 60, size=60)}
    for k, v in pre.items():
        idx.insert(k, v)
    hot = list(pre)[:4]
    fresh = [int(k) for k in rng.integers(1 << 60, 1 << 61, size=4)]
    plan = Plan()
    # per-key histories spanning several waves
    for k in hot:
        plan.get(k)
        plan.update(k, 111111)
        plan.get(k)
        plan.update(k, 222222)
    for k in fresh:
        plan.put(k, 7)
        plan.get(k)
        plan.delete(k)
    # legal per-key prefix states
    prefix_states = {k: ((pre[k],), (pre[k], 111111, 222222)) for k in hot}
    snap = PMSnapshot(pmem, idx)
    before = pmem.counters.stores
    idx.execute(plan)
    n_stores = pmem.counters.stores - before
    snap.restore(pmem)
    assert n_stores > 0
    for k_at in range(0, n_stores, max(1, n_stores // 7)):
        pmem.arm_crash(after_stores=k_at)
        try:
            idx.execute(plan)
            pmem.disarm_crash()
        except CrashPoint:
            pass
        pmem.crash(mode="powerfail")
        idx.recover()
        for k, v in pre.items():
            got = idx.lookup(k)
            if k in hot:
                assert got in (v, 111111, 222222), (k_at, k, got)
            else:
                assert got == v, (k_at, k, got)
        for k in fresh:
            assert idx.lookup(k) in (None, 7), (k_at, k)
        idx.check_invariants()
        assert idx.insert(31337 + k_at, 1)
        assert idx.lookup(31337 + k_at) == 1
        snap.restore(pmem)


def test_plan_result_telemetry():
    """Wave counts and widths surface through PlanResult (what
    ``run_workload`` tallies as the scheduler's waves and wave ops)."""
    idx = PCLHT(PMem(), n_buckets=64)
    plan = Plan()
    for k in range(100):
        plan.put(k + 1, k)
    for k in range(100):
        plan.get(k + 1)
    res = idx.execute(plan)
    assert res.n_waves == 2
    assert res.wave_widths == [100, 100]
    assert res.mean_wave_width == 100.0
    assert res.found == 100 and res.acked == 100


# -- the public facade ----------------------------------------------------

def test_facade_pipeline_drains_on_read():
    from repro.api import open_index
    s = open_index("clht", n_buckets=64)
    with s.pipeline(depth=64) as p:
        h_put = p.put(1, 10)
        h_get = p.get(1)
        assert not h_get.done
        assert h_get.value == 10       # reading the slot drains
        assert h_put.done and h_put.value is True
        h2 = p.get(2)                  # next generation
    assert h2.done and h2.value is None  # context exit drained
    assert s.stats["plans"] == 2


def test_facade_pipeline_depth_overflow():
    from repro.api import open_index
    s = open_index("art")
    with s.pipeline(depth=8) as p:
        hs = [p.put(k, k) for k in range(1, 12)]
    assert all(h.value for h in hs)
    assert s.stats["plans"] == 2  # one overflow drain + exit drain
    assert s.get(11) == 11


def test_facade_crash_recover_and_scan():
    from repro.api import open_index
    s = open_index("P-Masstree")
    with s.pipeline() as p:
        for k in (5, 3, 9, 7):
            p.put(k, k + 1)
    s.crash()
    assert s.scan(4, 2) == [(5, 6), (7, 8)]
    assert s.get(3) == 4


def test_facade_rejects_unknown_kind():
    from repro.api import open_index
    with pytest.raises(ValueError):
        open_index("btree9000")


def test_from_arrays_plan_accepts_appends():
    """Appending builder ops to a from_arrays plan keeps the
    array-built ops (they materialize into the backing lists)."""
    kinds = np.array([PUT, PUT], np.int32)
    keys = np.array([1, 2], np.int64)
    aux = np.array([10, 20], np.int64)
    plan = Plan.from_arrays(kinds, keys, aux)
    plan.get(1)
    assert len(plan) == 3
    idx = PCLHT(PMem(), n_buckets=64)
    assert idx.execute(plan).results == [True, True, 10]


def test_pipeline_generations_are_garbage_collected():
    """A long-lived pipeline must not retain drained generations'
    results: once the handles die, the generation cell is free."""
    import gc
    import weakref
    from repro.api import open_index
    s = open_index("clht", n_buckets=64)
    p = s.pipeline(depth=16)
    h = p.put(1, 10)
    p.drain()
    assert h.value is True
    wr = weakref.ref(h._gen)
    del h
    gc.collect()
    assert wr() is None, "drained generation results were retained"


@pytest.mark.parametrize("build", ["from_ops", "from_arrays", "builder",
                                   "session"])
def test_plans_reject_key_zero(build):
    """Key 0 is every index's empty-slot word: a plan naming it is
    refused where it is built, whatever the index; a scan may still
    start there."""
    from repro.api import open_index
    with pytest.raises(ValueError, match="key 0"):
        if build == "from_ops":
            Plan.from_ops([("lookup", 1, 0), ("insert", 0, 5)])
        elif build == "from_arrays":
            Plan.from_arrays(np.array([GET, PUT], np.int32),
                             np.array([1, 0], np.int64),
                             np.array([0, 5], np.int64))
        elif build == "builder":
            Plan().delete(0)
        else:
            open_index("clht").get(0)
    scan = Plan.from_arrays(np.array([SCAN], np.int32),
                            np.array([0], np.int64), np.array([4], np.int64))
    assert len(scan) == 1 and len(Plan.from_ops([("scan", 0, 4)])) == 1
