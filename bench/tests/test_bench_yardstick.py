"""The parts of the benchmark that must not move: bytes from shapes, the
traffic generator and the plain reference."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import replay, shapes, traffic  # noqa: E402


def test_clht_lookup_bytes_by_hand():
    # 2 queries, chains of 3 buckets: each bucket 3 x (8 + 8 + 1) slot
    # bytes + an 8-byte chain pointer = 59; per query 8 in + 3 x 59 +
    # 1 found + 8 value out = 194
    assert shapes.clht_lookup_bytes(2, depth=3) == 388
    assert shapes.clht_lookup_bytes(1, depth=1) == 8 + 59 + 9


def test_sorted_lookup_bytes_by_hand():
    # 7 entries: a lower bound takes ceil(log2(8)) = 3 probes of 8 bytes;
    # per query 8 in + 24 + one 16-byte entry + 9 out = 57
    assert shapes.search_steps(7) == 3
    assert shapes.sorted_lookup_bytes(4, run_length=7) == 4 * 57
    assert shapes.search_steps(8) == 4
    assert shapes.sorted_lookup_bytes(1, run_length=1 << 18) == \
        8 + 19 * 8 + 16 + 9


def test_the_key_set_is_drawn_from_the_seed():
    a, b = traffic.key_set(3000, 1), traffic.key_set(3000, 2**33 + 5)
    assert np.intersect1d(a, b).size == 0
    np.testing.assert_array_equal(a, traffic.key_set(3000, 1))


def test_keys_are_distinct_in_range_and_fixed_by_the_seed():
    for seed in (0, 2**31 + 12345, 2**40, -7):
        a = traffic.make_keys(traffic.rng_for(seed, "keys"), 5000)
        b = traffic.make_keys(traffic.rng_for(seed, "keys"), 5000)
        np.testing.assert_array_equal(a, b)
        assert np.unique(a).size == 5000
        assert a.min() >= traffic.KEY_LO and a.max() < traffic.KEY_HI
    assert not np.array_equal(
        traffic.make_keys(traffic.rng_for(1, "keys"), 100),
        traffic.make_keys(traffic.rng_for(2, "keys"), 100))


@pytest.mark.parametrize("mix", [{"get": 1.0}, {"get": 0.5, "update": 0.5},
                                 {"get": 0.95, "update": 0.05}])
def test_every_plan_holds_the_exact_mix(mix):
    keys = traffic.make_keys(traffic.rng_for(3, "keys"), 1000)
    gen = traffic.Traffic({"plan_ops": 4096, "mix": mix}, keys,
                          traffic.rng_for(3, "window"))
    want = traffic.op_counts(mix, 4096)
    for _ in range(3):
        codes, k, aux = gen.next_plan()
        np.testing.assert_array_equal(np.bincount(codes, minlength=3), want)
        assert np.isin(k, keys).all()
        assert ((aux != 0) == (codes == traffic.UPDATE)).all()


def test_zipfian_targets_favour_low_ranks():
    keys = traffic.make_keys(traffic.rng_for(4, "keys"), 10000)
    gen = traffic.Traffic({"plan_ops": 4096, "mix": {"get": 1.0},
                           "keys": {"distribution": "zipfian",
                                    "theta": 0.99}},
                          keys, traffic.rng_for(4, "window"))
    ranks = gen.ranks(200000)
    counts = np.bincount(ranks, minlength=keys.size)
    assert counts[0] > counts[10] > counts[1000]
    assert ranks.max() < keys.size


def test_reference_replays_in_program_order():
    ref = replay.Reference()
    put = np.full(3, traffic.PUT, np.int8)
    keys = np.array([5, 6, 5], np.int64)
    np.testing.assert_array_equal(
        ref.apply(put, keys, np.array([50, 60, 51])), [1, 1, 0])
    codes = np.array([traffic.GET, traffic.UPDATE, traffic.GET,
                      traffic.UPDATE, traffic.GET], np.int8)
    keys = np.array([5, 5, 5, 9, 9], np.int64)
    np.testing.assert_array_equal(
        ref.apply(codes, keys, np.array([0, 55, 0, 90, 0])),
        [50, 1, 55, 1, 90])
    np.testing.assert_array_equal(ref.lookup(np.array([5, 6, 7])),
                                  [55, 60, replay.MISSING])


def test_program_results_encode_like_the_reference():
    np.testing.assert_array_equal(
        replay.encode_results([7, None, True, False]),
        [7, replay.MISSING, 1, 0])
