"""Powerfail in the middle of a live plan, on every plan-surface index.

RECIPE's recovery claim (§6): the PM image is the index, so after a
crash a converted index serves its first read as soon as ``recover()``
returns, with every acknowledged write in place.  Per index, a YCSB-A
stream is loaded and partly run, then a further plan is crashed at
sampled outermost group-commit boundaries
(``crash_testing.group_commit_boundaries``), restored from one
``PMSnapshot`` image and re-exported (``prime_export``) each time.
After powerfail + ``recover()``:

* the first scalar read returns the acknowledged value;
* a ``force_kernel`` read plan over acknowledged keys outside the
  crashed plan loses none of them (the batched read re-exports the
  recovered image);
* the crashed plan, never acknowledged, replays whole and lands every
  key it touches on the plan's dict model.
"""

import pytest

from repro.core import (CrashPoint, PART, PBwTree, PCLHT, PHOT, PMasstree,
                        PMem, Plan)
from repro.core.baselines import CCEH, FastFair, LevelHashing
from repro.core.crash_testing import (PMSnapshot, group_commit_boundaries,
                                      plan_prefix_states, prime_export)
from repro.core.ycsb import generate

# the eight indexes of the paper's comparison, as the serving layer
# builds them
FACTORIES = [
    ("FAST&FAIR", lambda p: FastFair(p, fixed=True)),
    ("P-BwTree", PBwTree),
    ("P-Masstree", PMasstree),
    ("P-ART", PART),
    ("P-HOT", PHOT),
    ("CCEH", lambda p: CCEH(p, depth=4, fixed=True)),
    ("LevelHashing", lambda p: LevelHashing(p, n_top=256)),
    ("P-CLHT", lambda p: PCLHT(p, n_buckets=512)),
]

N_KEYS = 600      # loaded keys, and run ops
CHUNK = 200       # ops per plan; the last run chunk is the crashed plan
PROBE = 200       # acknowledged keys read back after each crash
CRASH_POINTS = 2  # sampled group-commit boundaries per index


def _plans(ops):
    return [Plan.from_ops(ops[i:i + CHUNK])
            for i in range(0, len(ops), CHUNK)]


def _sample(offsets, k):
    if len(offsets) <= k:
        return offsets
    step = len(offsets) / k
    return [offsets[int(i * step)] for i in range(k)]


@pytest.mark.parametrize("name,factory", FACTORIES,
                         ids=[n for n, _ in FACTORIES])
def test_powerfail_mid_plan_loses_no_acked_write(name, factory):
    wl = generate("A", N_KEYS, N_KEYS, seed=7)
    pmem = PMem(seed=0)
    idx = factory(pmem)
    for p in _plans(wl.load_ops):
        idx.execute(p, collect_results=False)
    pre_ops, crash_ops = wl.run_ops[:-CHUNK], wl.run_ops[-CHUNK:]
    for p in _plans(pre_ops):
        idx.execute(p, collect_results=False)
    committed = plan_prefix_states(
        pre_ops, base=plan_prefix_states(wl.load_ops)[1])[1]
    final_model = plan_prefix_states(crash_ops, base=committed)[1]
    crash_plan = Plan.from_ops(crash_ops)
    crash_set = {k for _, k, _ in crash_ops}
    crash_keys = sorted(crash_set)
    probe_keys = [k for k in committed if k not in crash_set][:PROBE]
    assert len(probe_keys) == PROBE
    reads = Plan.from_ops([("lookup", k, 0) for k in probe_keys])
    acked = [committed[k] for k in probe_keys]
    replayed = [final_model.get(k) for k in crash_keys]

    snap = PMSnapshot(pmem, idx)
    prime_export(idx)
    boundaries = group_commit_boundaries(
        pmem, lambda: idx.execute(crash_plan, collect_results=False))
    offsets = _sample([b for b in boundaries if b > 0] or boundaries[:1],
                      CRASH_POINTS)
    assert offsets, f"{name}: the crashed plan opened no persist epoch"
    crashes = 0
    for off in offsets:
        snap.restore(pmem)
        prime_export(idx)
        pmem.arm_crash(after_stores=off)
        try:
            idx.execute(crash_plan, collect_results=False)
            pmem.disarm_crash()
        except CrashPoint:
            crashes += 1
        pmem.crash(mode="powerfail")
        idx.recover()
        first = idx.lookup(probe_keys[0])
        assert first == acked[0], (
            f"{name}@{off}: first read after recover() returned {first!r}, "
            f"the acknowledged value is {acked[0]!r}")
        got = idx.execute(reads, force_kernel=True).results
        lost = [k for k, g, a in zip(probe_keys, got, acked) if g != a]
        assert not lost, f"{name}@{off}: {len(lost)} acknowledged keys lost"
        idx.execute(crash_plan, collect_results=False)
        assert [idx.lookup(k) for k in crash_keys] == replayed, (
            f"{name}@{off}: the replayed plan missed its model")
    assert crashes, f"{name}: no armed offset crashed the plan"
