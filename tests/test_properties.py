"""Property-based tests (hypothesis) on the system's invariants.

Skip triage: this module is one of tier-1's three perennial skips.
It skips wholesale wherever hypothesis isn't installed (the CI image
installs it; the minimal local toolchain may not), and every
randomized battery here deliberately has a deterministic fixed-seed
twin that runs everywhere: test_workloads.py (crash sweep),
test_fingerprints.py (fp differential), test_batched_lookup.py
(batch/scalar equivalence).  A skip here therefore loses example
breadth, never coverage of an invariant."""

import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="hypothesis not installed; property tests skipped")
from hypothesis import given, settings, strategies as st

from repro.core import PART, PCLHT, PHOT, PMasstree, PMem, CrashPoint
from repro.core.masstree import perm_pack, perm_slots
from repro.core.art import pack_hdr, unpack_hdr

KEYS = st.integers(min_value=1, max_value=(1 << 62) - 1)


@st.composite
def op_sequences(draw):
    n = draw(st.integers(2, 40))
    keys = draw(st.lists(KEYS, min_size=n, max_size=n, unique=True))
    ops = []
    live = []
    for k in keys:
        ops.append(("insert", k, (k % 1000003) + 1))
        live.append(k)
        if live and draw(st.booleans()):
            victim = live[draw(st.integers(0, len(live) - 1))]
            ops.append(("delete", victim, 0))
    return ops


def _model_of(ops):
    model = {}
    for kind, k, v in ops:
        if kind == "insert":
            model.setdefault(k, v)
        else:
            model.pop(k, None)
    return model


@settings(max_examples=25, deadline=None)
@given(op_sequences())
def test_clht_matches_dict_model(ops):
    """Sequential consistency: the index agrees with a dict after any
    op sequence (inserts never overwrite; deletes remove)."""
    idx = PCLHT(PMem(), n_buckets=4)
    for kind, k, v in ops:
        (idx.insert(k, v) if kind == "insert" else idx.delete(k))
    model = _model_of(ops)
    for k, v in model.items():
        assert idx.lookup(k) == v
    idx.check_invariants()


@settings(max_examples=15, deadline=None)
@given(op_sequences())
def test_art_sorted_iteration_invariant(ops):
    idx = PART(PMem())
    for kind, k, v in ops:
        (idx.insert(k, v) if kind == "insert" else idx.delete(k))
    model = _model_of(ops)
    assert list(idx.keys()) == sorted(model)


@settings(max_examples=10, deadline=None)
@given(op_sequences(), st.integers(0, 10 ** 6), st.data())
def test_single_crash_point_never_loses_acked_keys(ops, seed, data):
    """THE paper invariant: crash after ANY atomic store of ANY op —
    every previously-acknowledged key must read back."""
    pmem = PMem(seed=seed)
    idx = PMasstree(pmem)
    cut = data.draw(st.integers(0, max(len(ops) - 1, 0)))
    acked = {}
    for kind, k, v in ops[:cut]:
        if kind == "insert":
            if idx.insert(k, v):
                acked.setdefault(k, v)
        else:
            idx.delete(k)
            acked.pop(k, None)
    if cut < len(ops):
        kind, k, v = ops[cut]
        n = data.draw(st.integers(0, 30))
        pmem.arm_crash(after_stores=n)
        try:
            if kind == "insert":
                if idx.insert(k, v):
                    acked.setdefault(k, v)
            else:
                idx.delete(k)
                acked.pop(k, None)
            # op completed before the armed point fired: its effect is
            # acknowledged and must persist like any other
            pmem.disarm_crash()
            crashed_key = None
        except CrashPoint:
            crashed_key = k
        pmem.crash(mode="powerfail")
        idx.recover()
        for kk, vv in acked.items():
            if kk != crashed_key:
                assert idx.lookup(kk) == vv


@settings(max_examples=10, deadline=None)
@given(op_sequences(), st.booleans())
def test_batched_lookup_bit_identical_property(ops, crash):
    """The batched execution layer: after ANY op sequence (and an
    optional powerfail), _lookup_batch over every touched key returns
    exactly what scalar lookup does — for both kernel-backed indexes."""
    probe = sorted({k for _, k, _ in ops})
    for factory in (lambda p: PCLHT(p, n_buckets=4), lambda p: PART(p)):
        pmem = PMem()
        idx = factory(pmem)
        for kind, k, v in ops:
            (idx.insert(k, v) if kind == "insert" else idx.delete(k))
        if crash:
            pmem.crash(mode="powerfail")
            idx.recover()
        scalar = [idx.lookup(k) for k in probe]
        assert idx._lookup_batch(probe, force_kernel=True) == scalar
        assert idx._lookup_batch(probe) == scalar  # adaptive path too


@settings(max_examples=100, deadline=None)
@given(st.lists(st.integers(0, 14), max_size=15, unique=True))
def test_masstree_permutation_word_roundtrip(slots):
    assert perm_slots(perm_pack(slots)) == slots


@settings(max_examples=100, deadline=None)
@given(st.integers(0, 7),
       st.lists(st.integers(0, 255), min_size=7, max_size=7))
def test_art_header_word_roundtrip(plen, prefix):
    n, p = unpack_hdr(pack_hdr(plen, tuple(prefix)))
    assert n == plen and p == tuple(prefix)[:plen]


# ---------------------------------------------------------------------------
# randomized group-commit crash-point sweep (the adversarial matrix's
# durability leg): crash at every persist-epoch boundary of a random
# mixed plan, on every plan-surface index
# ---------------------------------------------------------------------------

from repro.core import PBwTree, plan_crash_sweep
from repro.core.baselines import CCEH, FastFair

CRASH_FACTORIES = [
    ("P-CLHT", lambda p: PCLHT(p, n_buckets=8)),
    ("P-ART", PART),
    ("P-HOT", PHOT),
    ("P-Masstree", PMasstree),
    ("P-BwTree", PBwTree),
    ("CCEH", lambda p: CCEH(p, depth=2, fixed=True)),
    ("FAST&FAIR", lambda p: FastFair(p, fixed=True)),
]


@st.composite
def mixed_op_sequences(draw):
    """Insert/update/delete/lookup streams over a small unique keyspace
    (every key's per-op state history is tracked by the oracle)."""
    n = draw(st.integers(2, 12))
    keys = draw(st.lists(KEYS, min_size=n, max_size=n, unique=True))
    ops = []
    for i, k in enumerate(keys):
        ops.append(("insert", k, (k % 1000003) + 1))
        if draw(st.booleans()):
            ops.append(("update", k, (k % 999983) + 7))
        if draw(st.booleans()):
            victim = keys[draw(st.integers(0, i))]
            ops.append(("delete", victim, 0))
        if draw(st.booleans()):
            ops.append(("lookup", keys[draw(st.integers(0, i))], 0))
    return ops


@pytest.mark.parametrize("name,factory", CRASH_FACTORIES,
                         ids=[n for n, _ in CRASH_FACTORIES])
@settings(max_examples=5, deadline=None)
@given(mixed_op_sequences())
def test_crash_at_every_group_commit_point(name, factory, ops):
    """Randomized group-commit crash-point sweep on every plan-surface
    index: crash at (and one store past) each outermost persist-epoch
    boundary of a random mixed plan; after powerfail + recover every
    key must hold a legal plan-prefix state, invariants must hold, new
    writes must succeed, and a clean run must match the dict model.
    (The deterministic twin lives in test_workloads.py so the sweep
    still executes where hypothesis is unavailable.)"""
    report = plan_crash_sweep(factory, ops, max_points=6)
    assert report.n_crash_states > 0
    assert report.ok, f"{name}: {report.summary()}\n" + "\n".join(
        report.consistency_failures + report.durability_failures
        + report.stall_failures)


# ---------------------------------------------------------------------------
# fingerprint probe-lane differential (the deterministic twin — fixed
# RNG streams, adversarial collision sets — lives in
# test_fingerprints.py so the battery still executes where hypothesis
# is unavailable)
# ---------------------------------------------------------------------------

FP_KINDS = ["clht", "art", "hot", "bwtree", "masstree",
            "cceh", "fastfair", "level"]


@pytest.mark.parametrize("kind", FP_KINDS)
@settings(max_examples=3, deadline=None)
@given(st.lists(KEYS, min_size=12, max_size=60, unique=True),
       st.data())
def test_fingerprint_filter_differential_property(kind, keys, data):
    """Random op streams through fp-on and fp-off twins of every
    plan-surface index: batched results must match the scalar oracle
    bit-for-bit on both sides.  On the fingerprinted twin the filter's
    outcome attribution (candidates == fp_hits + fp_false_positives)
    holds exactly; on the other no fingerprint is compared and every
    lane is a full-key candidate (2 PM words each, fingerprint.account).
    Probes stay in the key domain: key 0 is the empty-slot word, which
    plans reject."""
    from repro.api import open_index
    from repro.core import Plan

    probes = sorted((set(keys)
                     | {k ^ 1 for k in keys} | {k + 1 for k in keys}) - {0})
    plan = Plan.from_ops([("lookup", int(q), 0) for q in probes])
    # one drawn stream, replayed identically into both twins
    drop = [data.draw(st.booleans()) for _ in keys]
    results = {}
    for fingerprints in (True, False):
        s = open_index(kind)
        s.index.fingerprints = fingerprints
        model = {}
        for k, d in zip(keys, drop):
            v = (k % 1000003) + 1
            s.index.insert(k, v)
            model.setdefault(k, v)
            if d:
                s.index.delete(k)
                model.pop(k, None)
        res = s.execute(plan, force_kernel=True)
        assert res.results == [model.get(q) for q in probes], kind
        results[fingerprints] = res.results
        st_ = s.index.probe_stats
        if fingerprints:
            assert (st_["candidates"]
                    == st_["fp_hits"] + st_["fp_false_positives"])
        else:
            assert st_["fp_hits"] == 0 == st_["fp_false_positives"]
            assert st_["fp_compares"] == 0
            assert st_["pm_load_words"] == 2 * st_["candidates"]
    assert results[True] == results[False]  # the filter is invisible


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(1, 500), min_size=1, max_size=60))
def test_arena_allocations_never_overlap(sizes):
    from repro.core.arena import Arena, HDR_WORDS
    arena = Arena(PMem(), "prop")
    spans = []
    for n in sizes:
        ptr = arena.alloc(n)
        for (lo, hi) in spans:
            assert ptr + n <= lo or ptr >= hi, "overlap!"
        spans.append((ptr, ptr + n))
        assert ptr % (1 << 16) >= HDR_WORDS  # never in a segment header
