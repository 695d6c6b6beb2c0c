"""Chaos recovery harness — RECIPE's instant-recovery SLO, measured.

The paper's second headline claim (§6, §7.5): a converted index's
recovery is *instant* — the PM image IS the index, so after a crash
the engine serves its first request as soon as the failure-atomicity
fixups run, while a DRAM index must first rebuild itself from a log
or a persistent copy.  This harness turns that claim into serving
SLOs.  For each plan-surface index it:

1. loads a committed keyspace and runs live plan traffic,
2. kills the engine mid-plan with a simulated powerfail — the crash
   points are sampled from the plan's *outermost group-commit
   boundaries* (``crash_testing.group_commit_boundaries``, the same
   offsets the correctness sweeps arm), restored from a
   ``PMSnapshot`` image exactly as ``plan_crash_sweep`` does,
3. recovers and measures:

   * ``time_to_first_served_us`` — ``recover()`` plus the first
     scalar GET answered from the PM image.  No export, no warmup:
     this is the instant-recovery number.
   * ``warm_read_us`` — one batched read wave over committed keys,
     which pays the snapshot re-export (the lazy warmup a serving
     tick would run through ``serving.AsyncExporter``).
   * ``warm_prefix_hit_rate`` — fraction of *acked* (committed
     before the crashed plan) keys that read back their committed
     value post-recovery.  Must be exactly 1.0: an acked write that
     vanishes is data loss, not a cold cache.
   * ``requests_lost`` / ``requests_replayed`` — the crashed plan
     never acked, so the client replays it whole
     (``requests_replayed`` = its op count); ``requests_lost`` counts
     acked keys that failed to read back and must be 0.  The replay
     must land the index on the plan's final dict model.
   * ``dram_rebuild_us`` — the DRAM-baseline model: a rebuild-from-
     scratch of the committed pairs into a fresh index (batched
     insert plans + one export warm), timed.  This is *charitable* to
     DRAM — a real restart also re-reads the data from storage.
   * ``instant_recovery_speedup`` = dram_rebuild_us /
     time_to_first_served_us.

``--smoke`` is the CI gate: a quick YCSB-A pass on P-CLHT asserting
time-to-first-served is finite, zero acked-write loss, and that the
pipelined executor (``serving.PlanPipeline``) returns bit-identical
results to the blocking path on the same traffic.
"""

from __future__ import annotations

import statistics
import time
from typing import Callable, Dict, List, Tuple

from repro.core import PMem, Plan
from repro.core.crash_testing import (PMSnapshot, group_commit_boundaries,
                                      plan_prefix_states)
from repro.core.pmem import CrashPoint

from benchmarks.ycsb import ORDERED, UNORDERED, _chunk_plans

ALL_TARGETS: Dict[str, Callable] = {**ORDERED, **UNORDERED}


def _prime(index) -> None:
    """Re-export the batched-read snapshot at the current (restored)
    image — the ``plan_crash_sweep`` discipline, so every armed re-run
    walks the same crash-call trajectory as the dry run."""
    if not hasattr(index, "snapshot"):
        return
    index._snapshot = None
    index._accounted_stores = index._write_account()
    try:
        index.snapshot()
    except NotImplementedError:
        pass


def _sample(offsets: List[int], k: int) -> List[int]:
    if len(offsets) <= k:
        return offsets
    step = len(offsets) / k
    return [offsets[int(i * step)] for i in range(k)]


def recovery_bench(name: str, factory: Callable, *, n: int = 4000,
                   crash_samples: int = 3, chunk: int = 1000,
                   probe_n: int = 1000, seed: int = 7
                   ) -> Dict[str, float]:
    """One index's recovery SLO row; see the module docstring."""
    wl_name = "A"  # 50/50 read/update: live write traffic to crash into
    from repro.core.ycsb import generate
    wl = generate(wl_name, n, n, seed=seed)
    pmem = PMem(seed=0)
    idx = factory(pmem)
    for p in _chunk_plans(wl.load_ops, chunk):
        idx.execute(p, collect_results=False)
    committed = plan_prefix_states(wl.load_ops)[1]
    # live traffic: commit the first chunks, then crash inside the next
    run_chunks = [wl.run_ops[i:i + chunk]
                  for i in range(0, len(wl.run_ops), chunk)]
    pre_ops = [op for c in run_chunks[:-1] for op in c]
    crash_ops = run_chunks[-1]
    for p in _chunk_plans(pre_ops, chunk):
        idx.execute(p, collect_results=False)
    committed = plan_prefix_states(pre_ops, base=committed)[1]
    crash_plan = Plan.from_ops(crash_ops)
    states, final_model = plan_prefix_states(crash_ops, base=committed)
    crash_keys = {k for _, k, _ in crash_ops}
    acked_keys = [k for k in committed if k not in crash_keys]
    probe_keys = acked_keys[:probe_n]
    assert probe_keys, "no acked keys outside the crashed plan to probe"

    snap = PMSnapshot(pmem, idx)
    _prime(idx)
    boundaries = group_commit_boundaries(
        pmem, lambda: idx.execute(crash_plan, collect_results=False))
    offsets = _sample([b for b in boundaries if b > 0] or boundaries[:1],
                      crash_samples)
    assert offsets, f"{name}: crashed plan opened no persist epochs"

    t_first: List[float] = []
    t_warm: List[float] = []
    lost = 0
    durable_frac: List[float] = []
    warm_plan = Plan.from_ops([("lookup", k, 0) for k in probe_keys])
    for off in offsets:
        snap.restore(pmem)
        _prime(idx)
        pmem.arm_crash(after_stores=off)
        try:
            idx.execute(crash_plan, collect_results=False)
            pmem.disarm_crash()
        except CrashPoint:
            pass
        pmem.crash(mode="powerfail")
        t0 = time.perf_counter_ns()
        idx.recover()
        first = idx.lookup(probe_keys[0])
        t_first.append((time.perf_counter_ns() - t0) / 1e3)
        assert first == committed[probe_keys[0]], (
            f"{name}@store{off}: first served read returned {first!r}, "
            f"acked value was {committed[probe_keys[0]]!r}")
        # warm batched read wave: pays the lazy snapshot re-export
        t0 = time.perf_counter_ns()
        res = idx.execute(warm_plan, force_kernel=True)
        t_warm.append((time.perf_counter_ns() - t0) / 1e3)
        hits = sum(r == committed[k]
                   for k, r in zip(probe_keys, res.results))
        lost += len(probe_keys) - hits
        # how far had group commit carried the crashed plan?
        done = sum(idx.lookup(k) == final_model.get(k) for k in crash_keys)
        durable_frac.append(done / max(len(crash_keys), 1))
        # the un-acked plan replays whole and must land on its model
        idx.execute(crash_plan, collect_results=False)
        for k in crash_keys:
            got = idx.lookup(k)
            want = final_model.get(k)
            assert got == want, (
                f"{name}@store{off}: replayed key {k} reads {got!r}, "
                f"model says {want!r}")
    hit_rate = 1.0 - lost / (len(probe_keys) * len(offsets))
    assert lost == 0, (
        f"{name}: {lost} acked reads lost across {len(offsets)} crashes")

    # DRAM-rebuild baseline: fresh index, re-insert every committed
    # pair, warm one export — the work a volatile index must redo
    # before serving anything
    pairs = sorted(committed.items())
    rebuild_ops = [("insert", k, v) for k, v in pairs]
    dram = factory(PMem(seed=0))
    t0 = time.perf_counter_ns()
    for p in _chunk_plans(rebuild_ops, chunk):
        dram.execute(p, collect_results=False)
    if hasattr(dram, "snapshot"):
        dram.snapshot()
    dram_us = (time.perf_counter_ns() - t0) / 1e3

    ttfs = statistics.median(t_first)
    return {
        "time_to_first_served_us": ttfs,
        "warm_read_us": statistics.median(t_warm),
        "warm_prefix_hit_rate": hit_rate,
        "requests_lost": float(lost),
        "requests_replayed": float(len(crash_ops) * len(offsets)),
        "crash_plan_durable_frac": statistics.median(durable_frac),
        "crash_points": float(len(offsets)),
        "dram_rebuild_us": dram_us,
        "instant_recovery_speedup": dram_us / max(ttfs, 1e-3),
        "n_committed": float(len(committed)),
    }


def run(n: int = 4000, *, crash_samples: int = 3
        ) -> List[Tuple[str, Dict[str, float]]]:
    """Recovery SLO rows for every plan-surface index."""
    rows = []
    print(f"# chaos recovery SLO — powerfail at sampled group-commit "
          f"boundaries, {crash_samples} crash points per index "
          f"({n} committed keys)")
    for name, factory in ALL_TARGETS.items():
        r = recovery_bench(name, factory, n=n, crash_samples=crash_samples)
        rows.append((f"recovery/{name}", r))
        print(f"  {name:12s} first-served {r['time_to_first_served_us']:8.1f}us"
              f"  warm {r['warm_read_us']:9.1f}us"
              f"  hit-rate {r['warm_prefix_hit_rate']:.3f}"
              f"  dram-rebuild {r['dram_rebuild_us'] / 1e3:8.1f}ms"
              f"  ({r['instant_recovery_speedup']:9.0f}x)")
    return rows


def smoke(n: int = 2000) -> Dict[str, float]:
    """CI chaos smoke: finite time-to-first-served, zero acked-write
    loss, and pipelined-vs-blocking result equality on quick YCSB-A."""
    from repro.core.ycsb import generate
    from repro.serving import AsyncExporter, PlanPipeline

    r = recovery_bench("P-CLHT", ALL_TARGETS["P-CLHT"], n=n,
                       crash_samples=2)
    assert 0.0 < r["time_to_first_served_us"] < float("inf"), (
        "time-to-first-served is not finite")
    assert r["requests_lost"] == 0.0, "acked writes lost"
    assert r["warm_prefix_hit_rate"] == 1.0, "warm prefix hit rate < 1"

    wl = generate("A", n, n, seed=7)
    plans = _chunk_plans(wl.run_ops, 500)
    idx_b = ALL_TARGETS["P-CLHT"](PMem())
    for p in _chunk_plans(wl.load_ops, 500):
        idx_b.execute(p, collect_results=False)
    base = [idx_b.execute(p) for p in plans]
    idx_p = ALL_TARGETS["P-CLHT"](PMem())
    for p in _chunk_plans(wl.load_ops, 500):
        idx_p.execute(p, collect_results=False)
    with PlanPipeline(idx_p, depth=8, exporter=AsyncExporter()) as pipe:
        got = [t.wait() for t in [pipe.submit(p) for p in plans]]
    assert [g.results for g in got] == [b.results for b in base], (
        "pipelined results diverged from the blocking path")
    assert [(g.found, g.acked) for g in got] == \
        [(b.found, b.acked) for b in base]
    assert dict(idx_b.items()) == dict(idx_p.items())
    print(f"# chaos smoke: first-served "
          f"{r['time_to_first_served_us']:.1f}us, hit-rate "
          f"{r['warm_prefix_hit_rate']:.3f}, 0 acked writes lost; "
          f"pipelined == blocking over {len(plans)} plans "
          f"({sum(len(p) for p in plans)} ops)")
    return r


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller workloads (CI-speed)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: P-CLHT recovery SLO + pipelined-vs-"
                         "blocking equality")
    ap.add_argument("--samples", type=int, default=3,
                    help="crash points sampled per index")
    args = ap.parse_args()
    if args.smoke:
        smoke()
    else:
        run(4000 if args.quick else 20000, crash_samples=args.samples)
