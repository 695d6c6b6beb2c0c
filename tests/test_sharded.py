"""Multi-shard scale-out (``repro.distributed``): sharded plan
execution must be bit-identical to the unsharded index on every kind,
cross-stream admission must serialize conflicting plans and co-admit
disjoint ones, a crash inside one shard's group commit must stay in
that shard (siblings keep serving stale-free with no replay; recovery
replays exactly the crashed shard's sub-plan), the mesh read fan-out
must match the per-shard path, and the per-shard span attribution must
sum exactly to the aggregate ``ShardedPMem`` counters."""

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import (CrashPoint, PART, PBwTree, PCLHT, PHOT, PMasstree,
                        PMem, Plan)
from repro.core.baselines import CCEH
from repro.distributed import ShardedIndex, StreamDriver

# all five RECIPE conversions plus the hand-crafted CCEH baseline —
# the sharded layer treats them uniformly through the plan surface
FACTORIES = [
    ("P-CLHT", lambda p: PCLHT(p, n_buckets=64)),
    ("P-ART", PART),
    ("P-HOT", PHOT),
    ("P-Masstree", PMasstree),
    ("P-BwTree", PBwTree),
    ("CCEH", lambda p: CCEH(p, depth=2, fixed=True)),
]


def _random_plan(rng, n, n_keys, *, scans):
    kinds = rng.integers(0, 5 if scans else 4, size=n).astype(np.int32)
    keys = rng.integers(1, n_keys, size=n).astype(np.int64)
    aux = rng.integers(1, 50, size=n).astype(np.int64)
    return Plan.from_arrays(kinds, keys, aux)


def _load(idx, keys, base=1000):
    plan = Plan()
    for k in keys:
        plan.put(int(k), int(k) + base)
    idx.execute(plan, collect_results=False)


# -- equivalence ----------------------------------------------------------

@pytest.mark.parametrize("name,factory", FACTORIES)
def test_sharded_plan_equivalence(name, factory):
    """Mixed plans on a 4-shard index return exactly what the
    unsharded index returns — results, tallies, and final contents."""
    rng = np.random.default_rng(11)
    solo = factory(PMem())
    sharded = ShardedIndex(factory, 4)
    scans = solo.ORDERED
    for _ in range(3):
        plan = _random_plan(rng, 200, 500, scans=scans)
        r1 = solo.execute(plan)
        r2 = sharded.execute(plan)
        assert r1.results == r2.results
        assert (r1.found, r1.acked, r1.scanned) == \
            (r2.found, r2.acked, r2.scanned)
    assert sorted(solo.items()) == sorted(sharded.items())
    sharded.check_invariants()
    assert sharded.stats["plans"] == 3
    assert sharded.n_shards == 4


def test_sharded_scan_merge_hash_scheme():
    """Hash routing interleaves an ordered index's key ranges across
    shards: the merge-sort scan merge must still be exact."""
    rng = np.random.default_rng(12)
    solo = PART(PMem())
    sharded = ShardedIndex(PART, 4, scheme="hash")
    assert sharded.scheme == "hash"
    for _ in range(2):
        plan = _random_plan(rng, 150, 300, scans=True)
        r1 = solo.execute(plan)
        r2 = sharded.execute(plan)
        assert r1.results == r2.results
        assert r1.scanned == r2.scanned
    assert sharded.stats["scan_merges"] > 0


def test_prefix_routing_keeps_items_globally_sorted():
    sharded = ShardedIndex(PBwTree, 4)  # ordered -> prefix scheme
    assert sharded.scheme == "prefix"
    keys = np.random.default_rng(0).integers(1, 1 << 60, 500)
    _load(sharded, np.unique(keys))
    merged = list(sharded.items())
    assert merged == sorted(merged)


# -- multi-stream admission -----------------------------------------------

def test_streams_conflicting_plans_serialize():
    """Write/write and read/write on one key must never co-admit: the
    driver defers the conflicting head and retries next tick, so each
    stream sees a serial order."""
    idx = ShardedIndex(lambda p: PCLHT(p, n_buckets=64), 2)
    drv = StreamDriver(idx, 2)
    s0, s1 = drv.streams
    k = 42
    t_put0 = s0.submit(Plan.from_ops([("insert", k, 1)]))
    t_get0 = s0.submit(Plan.from_ops([("lookup", k, 0)]))
    t_put1 = s1.submit(Plan.from_ops([("insert", k, 2)]))
    t_get1 = s1.submit(Plan.from_ops([("lookup", k, 0)]))
    drv.run()
    assert drv.stats["deferred_plans"] > 0
    # per-stream program order: each get ran after its stream's put
    assert t_get0.tick > t_put0.tick and t_get1.tick > t_put1.tick
    # the puts serialized (conflicting writes never share a tick)
    assert t_put0.tick != t_put1.tick
    # insert is insert-if-absent: the FIRST admitted put wins, the
    # second is a no-op ack=False — both gets observe the winner
    first, want = ((t_put0, 1) if t_put0.tick < t_put1.tick
                   else (t_put1, 2))
    assert first.result == [True]
    assert t_get0.result == [want] and t_get1.result == [want]


def test_streams_disjoint_plans_coadmit():
    idx = ShardedIndex(lambda p: PCLHT(p, n_buckets=64), 2)
    drv = StreamDriver(idx, 3)
    tickets = [drv.streams[i].submit(
        Plan.from_ops([("insert", 100 + i, i)])) for i in range(3)]
    drv.run()
    assert drv.stats["ticks"] == 1
    assert drv.stats["multi_stream_ticks"] == 1
    assert drv.stats["deferred_plans"] == 0
    assert all(t.result == [True] for t in tickets)


def test_streams_match_sequential_oracle():
    """Disjoint-keyed random plans across 4 streams produce exactly
    the results of running each stream's plans alone, in order — the
    conflict-freedom guarantee of per-tick admission."""
    rng = np.random.default_rng(5)
    idx = ShardedIndex(lambda p: PCLHT(p, n_buckets=64), 4)
    solo = PCLHT(PMem(), n_buckets=64)
    drv = StreamDriver(idx, 4)
    plans, tickets = [], []
    for i in range(4):
        # each stream owns a disjoint key range; ops within it are
        # random, so streams are order-independent by construction
        for _ in range(3):
            plan = _random_plan(rng, 40, 100, scans=False)
            kinds, keys, aux = plan.arrays()
            plan = Plan.from_arrays(kinds, keys + 1000 * i, aux)
            plans.append(plan)
            tickets.append(drv.streams[i].submit(plan))
    drv.run()
    for plan, ticket in zip(plans, tickets):
        assert ticket.result == solo.execute(plan).results
    assert sorted(idx.items()) == sorted(solo.items())


# -- per-shard crash isolation --------------------------------------------

@pytest.mark.parametrize("name,factory", FACTORIES)
def test_per_shard_crash_is_isolated(name, factory):
    """Crash one shard mid-group-commit during a cross-shard update
    plan: siblings finish their sub-plans and serve the new values
    stale-free with NO replay; recovery replays exactly the crashed
    shard's sub-plan and nothing of the siblings'."""
    rng = np.random.default_rng(7)
    idx = ShardedIndex(factory, 4)
    keys = np.unique(rng.integers(1, 1 << 60, 300))
    _load(idx, keys)
    routes = idx.route(keys)
    upd = Plan()
    for k in keys:
        upd.update(int(k), int(k) + 5555)
    victim = int(routes[0])
    idx.pmems[victim].arm_crash(after_stores=3)
    with pytest.raises(CrashPoint):
        idx.execute(upd, collect_results=False)
    assert idx.last_crashed_shard == victim
    assert all(pm.crashes == 0 for s, pm in enumerate(idx.pmems)
               if s != victim)
    # sibling shards completed their sub-plans: stale-free reads of the
    # NEW values, without any recovery or replay anywhere
    sib = [int(k) for k, r in zip(keys, routes) if r != victim]
    gets = Plan.from_ops([("lookup", k, 0) for k in sib])
    res = idx.execute(gets)
    assert res.results == [k + 5555 for k in sib]
    # power-fail ONLY the crashed shard, then replay exactly its
    # pending sub-plan on top of its plan-prefix-consistent image
    idx.crash_shard(victim)
    replayed = idx.recover_shard(victim)
    assert replayed == int((routes == victim).sum())
    oracle = {int(k): int(k) + 5555 for k in keys}
    assert dict(idx.items()) == oracle
    idx.check_invariants()
    assert idx.stats["replayed_ops"] == replayed


def test_whole_domain_crash_abandons_pending_replay():
    """A full powerfail (every shard) is the unsharded contract: the
    in-flight plan is lost, pending per-shard replays are dropped, and
    acked pre-crash state recovers."""
    idx = ShardedIndex(lambda p: PCLHT(p, n_buckets=64), 4)
    keys = list(range(1, 201))
    _load(idx, keys)
    routes = idx.route(np.array(keys, np.int64))
    victim = int(routes[0])
    upd = Plan()
    for k in keys:
        upd.update(k, k + 7777)
    idx.pmems[victim].arm_crash(after_stores=3)
    with pytest.raises(CrashPoint):
        idx.execute(upd, collect_results=False)
    idx.pmem.crash()  # whole-domain powerfail
    idx.recover()
    assert idx.recover_shard(victim) == 0  # nothing pending anymore
    for k in keys:
        got = idx.execute(Plan.from_ops([("lookup", k, 0)])).results[0]
        assert got in (k + 1000, k + 7777)  # prefix-consistent per key


# -- mesh read fan-out ----------------------------------------------------

@pytest.mark.parametrize("name,factory,scheme", [
    ("P-CLHT", lambda p: PCLHT(p, n_buckets=64), "hash"),
    ("P-ART", PART, "prefix"),
    ("P-Masstree", PMasstree, "prefix"),
])
def test_mesh_read_path_matches_per_shard(name, factory, scheme):
    rng = np.random.default_rng(9)
    idx = ShardedIndex(factory, 4)
    assert idx.scheme == scheme
    keys = np.unique(rng.integers(1, 1 << 60, 400))
    _load(idx, keys)
    probe = np.concatenate([keys[:300],
                            rng.integers(1, 1 << 60, 100)])  # mostly hits
    gets = Plan.from_ops([("lookup", int(k), 0) for k in probe])
    r_ps = idx.execute(gets, mesh=False)
    obs.reset()
    obs.enable()
    try:
        r_mesh = idx.execute(gets, mesh=True)
    finally:
        obs.disable()
    assert r_mesh.mesh and not r_ps.mesh
    # fewer devices than shards: the fused probe runs on one device,
    # and the span says so
    [sp] = obs.spans("shard.mesh_lookup")
    assert sp.attrs["placement"] == ("devices" if len(jax.devices()) >= 4
                                     else "one_device")
    assert r_mesh.results == r_ps.results
    assert r_mesh.found == r_ps.found
    # and both equal a dict of what was loaded
    ref = {int(k): int(k) + 1000 for k in keys}
    assert r_mesh.results == [ref.get(int(k)) for k in probe]
    assert idx.stats["mesh_plans"] == 1
    # epoch-keyed cache: a write invalidates the stacked runs
    idx.execute(Plan.from_ops([("insert", 123456789, 1)]),
                collect_results=False)
    r2 = idx.execute(Plan.from_ops([("lookup", 123456789, 0)] * 4),
                     mesh=True)
    assert r2.results == [1] * 4


# -- observability: per-shard attribution ---------------------------------

def test_per_shard_span_attribution_sums_to_pmem_counters():
    """The ``shard.plan`` + ``shard.export`` span counter attributes
    must sum EXACTLY to the aggregate ``ShardedPMem`` counter delta —
    on the per-shard path and the mesh path alike."""
    rng = np.random.default_rng(13)
    idx = ShardedIndex(lambda p: PCLHT(p, n_buckets=64), 4)
    keys = np.unique(rng.integers(1, 1 << 60, 400))
    _load(idx, keys)
    gets = Plan.from_ops([("lookup", int(k), 0) for k in keys[:200]])
    obs.reset()
    obs.enable()
    try:
        c0 = idx.pmem.counters.snapshot()
        idx.execute(_random_plan(rng, 300, 1 << 60, scans=False),
                    collect_results=False)          # per-shard path
        idx.execute(gets, mesh=True)                # mesh path (re-export)
        d = idx.pmem.counters.delta(c0)
    finally:
        obs.disable()
    spans = obs.spans("shard.plan") + obs.spans("shard.export")
    assert spans, "sharded execution emitted no per-shard spans"
    for field in ("stores", "loads", "clwb", "fence", "lines_touched"):
        got = sum(sp.attrs.get(field, 0) for sp in spans)
        assert got == getattr(d, field), \
            f"per-shard {field} attribution drifted: {got}"


# -- the public facade ----------------------------------------------------

def test_api_sharded_session_and_streams():
    from repro.api import open_index
    s = open_index("clht", shards=4, n_buckets=64)
    assert s.shards == 4
    assert s.put(5, 7) and s.get(5) == 7
    drv = s.streams(2)
    t = drv.streams[0].submit(Plan.from_ops([("lookup", 5, 0)]))
    drv.run()
    assert t.result == [7]
    s.crash()  # whole-domain powerfail + re-attach: acked data survives
    assert s.get(5) == 7
    with pytest.raises(ValueError):
        open_index("clht", shards=4, pmem=PMem())
    with pytest.raises(AssertionError):
        open_index("clht", shards=3)


def test_api_unsharded_kwargs_pass_through():
    from repro.api import open_index
    s = open_index("clht", n_buckets=32, grow=False)
    assert s.index.grow is False
    assert s.shards == 1


def test_cceh_plan_surface():
    """The CCEH baseline rides the same plan/execute surface as the
    conversions: mixed plans match a dict oracle and batched reads can
    be forced onto the kernel path."""
    from repro.api import open_index
    s = open_index("cceh", depth=2, fixed=True)
    oracle = {}
    with s.pipeline() as p:
        for k in range(1, 120):
            p.put(k, k * 3)
            oracle[k] = k * 3
    assert dict(s.items()) == oracle
    gets = Plan.from_ops([("lookup", k, 0) for k in range(1, 240)])
    res = s.execute(gets, force_kernel=True)
    assert res.results == [oracle.get(k) for k in range(1, 240)]
    assert res.found == len(oracle)
