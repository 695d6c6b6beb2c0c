"""Optimistic version-validated reads: overlap semantics, the crash
window between probe and re-validation (swept across every plan-surface
index), and exact counter attribution through Session/Server merges."""

import numpy as np
import pytest

from repro.api import open_index
from repro.core import (PART, PBwTree, PCLHT, PHOT, PMasstree, PMem, Plan,
                        plan_crash_sweep, validation_points)
from repro.core.baselines import CCEH, FastFair, LevelHashing
from repro.core.conditions import PROBE_STAT_KEYS
from repro.core.crash_testing import group_commit_boundaries

pytest.importorskip("jax")

FACTORIES = {
    "P-CLHT": PCLHT,
    "P-ART": PART,
    "P-HOT": PHOT,
    "P-BwTree": PBwTree,
    "P-Masstree": PMasstree,
    "CCEH": CCEH,
    "FAST&FAIR": FastFair,
    "LevelHashing": LevelHashing,
}

SETUP = [("insert", k, k * 7) for k in range(1, 49)]
OVERLAP = ([("update", k, k * 9) for k in range(1, 25)]
           + [("lookup", k, 0) for k in range(1, 49)])


def warm(kind="clht", n=64):
    """A populated session whose batched-read snapshot is current."""
    s = open_index(kind)
    with s.pipeline() as p:
        for k in range(1, n + 1):
            p.put(k, k * 7)
    s.index.snapshot()  # warm the export at the post-insert state
    return s


# ----------------------------------------------------------------------
# overlap semantics
# ----------------------------------------------------------------------
def test_optimistic_read_overlaps_write_wave_exactly():
    s = warm(n=64)
    plan = Plan.from_ops([("update", k, k * 11) for k in range(1, 17)]
                         + [("lookup", k, 0) for k in range(1, 65)])
    res = s.execute(plan)
    # per-key program order: updated keys read their new value
    looked = res.results[16:]
    assert looked == [k * 11 if k <= 16 else k * 7 for k in range(1, 65)]
    # the read wave probed the stale snapshot optimistically and
    # re-ran exactly the written-and-moved keys through the fence
    assert res.probe["optimistic_probes"] == 64
    assert res.probe["optimistic_retries"] == 16
    assert s.stats["optimistic_probes"] == 64
    assert s.stats["optimistic_retries"] == 16


def test_noop_writes_cost_no_retries():
    """Updates that store nothing (same value) move no shard version
    and leave the snapshot current — the read wave doesn't even need
    the optimistic protocol, and nothing is retried."""
    s = warm(n=64)
    plan = Plan.from_ops([("update", k, k * 7) for k in range(1, 17)]
                         + [("lookup", k, 0) for k in range(1, 65)])
    res = s.execute(plan)
    assert res.results[16:] == [k * 7 for k in range(1, 65)]
    assert res.probe["optimistic_retries"] == 0


def test_optimistic_disengages_after_crash():
    s = warm(n=64)
    s.crash()
    plan = Plan.from_ops([("update", k, k * 11) for k in range(1, 17)]
                         + [("lookup", k, 0) for k in range(1, 65)])
    res = s.execute(plan)
    assert res.results[16:] == [k * 11 if k <= 16 else k * 7
                                for k in range(1, 65)]
    assert res.probe["optimistic_probes"] == 0  # fenced fallback


def test_optimistic_disengages_on_foreign_stores():
    """Stores to the index's regions that bypass its writers cannot be
    attributed to shards — the optimistic path must fall back."""
    s = warm(n=64)
    region = next(r for r in s.pmem.regions.values()
                  if r.name.startswith(s.index._region_prefixes))
    s.pmem.store(region, 0, s.pmem.load(region, 0))
    plan = Plan.from_ops([("update", k, k * 11) for k in range(1, 17)]
                         + [("lookup", k, 0) for k in range(1, 65)])
    res = s.execute(plan)
    assert res.results[16:] == [k * 11 if k <= 16 else k * 7
                                for k in range(1, 65)]
    assert res.probe["optimistic_probes"] == 0


def test_optimistic_requires_snapshot_current_at_wave_start():
    """Regression (caught by the matrix D-mix oracle): a snapshot that
    predates the overlapping write wave must never be probed
    optimistically.  Two plans write *different* keys routing to the
    SAME shard; after plan 1 the snapshot is stale but no read wave
    re-exported it.  Plan 2's moved shards are all attributable to its
    own writes — yet plan 1's values are not in plan 2's written set,
    so serving the old export would return stale values for them."""
    s = warm(n=400)
    routes = s.index.shard_route(np.arange(1, 401, dtype=np.int64))
    shard = int(np.bincount(routes, minlength=1).argmax())
    same = (np.nonzero(routes == shard)[0] + 1).tolist()
    assert len(same) >= 24, "need 24 keys sharing one shard"
    w1, w2 = same[:12], same[12:24]
    probe = list(dict.fromkeys(same[:24] + list(range(1, 41))))
    s.execute(Plan.from_ops([("update", int(k), int(k) * 11) for k in w1]
                            + [("lookup", int(k), 0) for k in probe]))
    p1 = s.stats["optimistic_probes"]
    assert p1 == len(probe)  # plan 1's snapshot was current: engaged
    res = s.execute(Plan.from_ops([("update", int(k), int(k) * 13)
                                   for k in w2]
                                  + [("lookup", int(k), 0) for k in probe]))
    assert s.stats["optimistic_probes"] == p1  # plan 2: disengaged
    want = {k: k * 7 for k in range(1, 401)}
    want.update({k: k * 11 for k in w1})
    want.update({k: k * 13 for k in w2})
    assert res.results[len(w2):] == [want[k] for k in probe]


def test_direct_lookups_never_go_optimistic():
    """Only the plan scheduler's overlapped read waves opt in; a plain
    read plan (no preceding write wave) takes the fenced path."""
    s = warm(n=64)
    res = s.execute(Plan.from_ops([("lookup", k, 0) for k in range(1, 65)]))
    assert res.probe["optimistic_probes"] == 0
    assert res.results == [k * 7 for k in range(1, 65)]


def test_write_version_gauges_track_shards():
    s = warm(n=64)
    v0 = np.array([s.stats[f"write_version_{i}"]
                   for i in range(s.index.N_WRITE_SHARDS)])
    s.execute(Plan.from_ops([("update", k, k * 13) for k in range(1, 17)]))
    v1 = np.array([s.stats[f"write_version_{i}"]
                   for i in range(s.index.N_WRITE_SHARDS)])
    assert (v1 >= v0).all() and (v1 > v0).any()
    moved = s.index.shard_route(np.arange(1, 17, dtype=np.int64))
    assert set(np.nonzero(v1 > v0)[0]) == set(moved.tolist())


# ----------------------------------------------------------------------
# the crash window between probe and re-validation (satellite: sweep)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("name", list(FACTORIES))
def test_plan_crash_sweep_covers_validation_window(name):
    factory = FACTORIES[name]
    # the dry pass must actually traverse the optimistic window ...
    pmem = PMem(seed=0)
    ix = factory(pmem)
    ix.execute(Plan.from_ops(SETUP), collect_results=False)
    ix._snapshot = None
    ix._accounted_stores = ix._write_account()
    ix.snapshot()
    plan = Plan.from_ops(OVERLAP)
    vpoints = []
    group_commit_boundaries(
        pmem, lambda: vpoints.extend(validation_points(
            pmem, lambda: ix.execute(plan, collect_results=False))))
    assert vpoints, f"{name}: overlapped plan never reached a crash_point"
    assert ix.probe_stats["optimistic_probes"] > 0
    # ... and the armed sweep through it must recover to a plan-prefix
    # consistent image with no torn or stale value surviving
    rep = plan_crash_sweep(factory, OVERLAP, setup_ops=SETUP, max_points=8)
    assert rep.ok, rep.summary()
    assert rep.n_crash_states >= len(set(vpoints))


# ----------------------------------------------------------------------
# exact attribution through metric merges (satellite: attribution)
# ----------------------------------------------------------------------
def test_session_counters_mirror_plan_probe_deltas_exactly():
    s = warm(n=96)
    # warm()'s direct export ran outside any plan: count from here
    base = dict(s.index.probe_stats)
    deltas = {k: 0 for k in PROBE_STAT_KEYS}
    for step in range(3):
        plan = Plan.from_ops(
            [("update", k, k * (13 + step)) for k in range(1, 25)]
            + [("lookup", k, 0) for k in range(1, 97)])
        res = s.execute(plan)
        for k in PROBE_STAT_KEYS:
            deltas[k] += res.probe[k]
    for k in PROBE_STAT_KEYS:
        assert (s.stats[k] == deltas[k]
                == s.index.probe_stats[k] - base[k]), k
    assert (s.stats["candidates"]
            == s.stats["fp_hits"] + s.stats["fp_false_positives"])


def test_probe_counters_sum_exactly_across_session_merges():
    sessions = [warm(n=64) for _ in range(3)]
    for i, s in enumerate(sessions):
        s.execute(Plan.from_ops(
            [("update", k, k * (3 + i)) for k in range(1, 17)]
            + [("lookup", k, 0) for k in range(1, 65)]))
    from repro.obs import MetricsRegistry, MetricsView
    merged = MetricsRegistry()
    for s in sessions:
        merged.merge(s.metrics)
    view = MetricsView(merged)
    for k in PROBE_STAT_KEYS:
        assert view[k] == sum(s.stats[k] for s in sessions), k
    assert view["candidates"] == view["fp_hits"] + view["fp_false_positives"]
    assert view["optimistic_retries"] == sum(
        s.stats["optimistic_retries"] for s in sessions)


def test_sharded_session_folds_probe_stats():
    s = open_index("clht", shards=4)
    with s.pipeline() as p:
        for k in range(1, 600):
            p.put(k, k * 7)
    res = s.execute(Plan.from_ops([("lookup", k, 0) for k in range(1, 600)]),
                    force_kernel=True)
    per_shard = [sh.probe_stats for sh in s.index.shards]
    for k in PROBE_STAT_KEYS:
        assert s.stats[k] == sum(ps[k] for ps in per_shard), k
    assert res.probe["pm_load_words"] > 0
    assert (s.stats["candidates"]
            == s.stats["fp_hits"] + s.stats["fp_false_positives"])


@pytest.fixture(scope="module")
def served():
    import jax
    from repro.configs import get_arch
    from repro.models.model import build_model
    cfg = get_arch("qwen2-0.5b").reduced()
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params


def test_server_probe_sync_is_delta_exact(served):
    from repro.serving.engine import Server
    model, params = served
    server = Server(model, params, page_size=8, n_pages=128)
    for p in ([1, 2, 3, 4, 5, 6, 7, 8], [1, 2, 3, 9, 10, 11],
              [4, 4, 4, 4]):
        server.submit(p, max_new=4)
    server.run_until_drained()
    server.sync_probe_stats()
    server.sync_probe_stats()  # idempotent: deltas, not cumulative re-adds
    for k in PROBE_STAT_KEYS:
        want = (server.kv.table.probe_stats[k]
                + server.kv.prefix.probe_stats[k])
        assert server.stats[k] == want, k
    assert (server.stats["candidates"]
            == server.stats["fp_hits"] + server.stats["fp_false_positives"])
    # merging the server registry elsewhere keeps the exact sums
    from repro.obs import MetricsRegistry, MetricsView
    rollup = MetricsRegistry().merge(server.metrics)
    assert MetricsView(rollup)["candidates"] == server.stats["candidates"]
