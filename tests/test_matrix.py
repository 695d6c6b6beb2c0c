"""The adversarial workload matrix at smoke size, through the plan
surface: skewed read-modify-write traffic must not cost more persist
traffic per op than uniform traffic at admission-sized plans, string
keys with scans racing inserts must replay exactly, and a pinned hot
set driven through two client streams must defer plans and book the
deferrals exactly into the session's registry.  Every run is checked
against the sequential ``repro.data.workloads.replay`` oracle."""

from repro.api import Session
from repro.core import PART, PCLHT, PMem, Plan
from repro.core.ycsb import run_workload
from repro.data.workloads import matrix_workload, replay

N = 600     # loaded keys, and run ops
PLAN = 32   # admission-sized plans


def _clht():
    return PCLHT(PMem(), n_buckets=512)


def _run_against_oracle(idx, wl, **kw):
    """Load, run the batched plan path, check the oracle's counts and
    final state, and return the run phase's PM counter delta."""
    run_workload(idx, wl, phase="load", batch_lookups=True)
    c0 = idx.pmem.counters.snapshot()
    done = run_workload(idx, wl, phase="run", batch_lookups=True, **kw)
    d = idx.pmem.counters.delta(c0)
    want = replay(wl.load_ops, wl.run_ops)
    assert (done["found"], done["acked"], done["scanned"]) == \
        want.counts(), f"{wl.name} diverged from the replay oracle"
    assert dict(idx.items()) == want.model
    return d


def test_skew_does_not_raise_persist_traffic_per_op():
    """F mix on P-CLHT in 32-op plans: under Zipf theta 0.9 group
    commit must amortize the repeated-key updates, so clwb and fence
    per op stay at or below the uniform baseline's."""
    per_op = {}
    for theta in (0.0, 0.9):
        wl = matrix_workload("F", N, N, dist="zipfian", theta=theta,
                             seed=11)
        d = _run_against_oracle(_clht(), wl, max_batch=PLAN)
        per_op[theta] = (d.clwb / N, d.fence / N)
    (u_clwb, u_fence), (s_clwb, s_fence) = per_op[0.0], per_op[0.9]
    assert s_clwb > 0 and s_fence > 0
    assert s_clwb <= u_clwb, f"skewed clwb/op {s_clwb} > uniform {u_clwb}"
    assert s_fence <= u_fence, \
        f"skewed fence/op {s_fence} > uniform {u_fence}"


def test_string_keys_scans_racing_inserts_replay_exactly():
    """E mix on P-ART over clustered-prefix string keys (Zipf theta
    0.9), one plan for the whole run: scans and inserts share a plan,
    and found, acked and scanned equal the oracle's."""
    wl = matrix_workload("E", N, N, dist="zipfian", theta=0.9,
                         keyspace="string", seed=11)
    assert any(k == "scan" for k, _, _ in wl.run_ops)
    assert any(k == "insert" for k, _, _ in wl.run_ops)
    _run_against_oracle(PART(PMem()), wl)


def test_hot_set_streams_defer_and_book_exactly():
    """F mix over a 1 % hot set carrying 90 % of the ops, in 32-op
    plans round-robin over two client streams of one ``Session``:
    cross-stream writes to the hot keys collide, so plans defer; the
    deferral count reads back exactly through ``Session.stats``, and
    the run still replays to the oracle's counts."""
    wl = matrix_workload("F", N, N, dist="hotset", hot_frac=0.01,
                         hot_op_frac=0.9, seed=11)
    streams = 2
    sess = Session(_clht(), kind="clht")
    run_workload(sess.index, wl, phase="load", batch_lookups=True)
    drv = sess.streams(streams, collect_results=False)
    for i in range(0, len(wl.run_ops), PLAN):
        drv.streams[(i // PLAN) % streams].submit(
            Plan.from_ops(wl.run_ops[i:i + PLAN]))
    drv.run()
    want = replay(wl.load_ops, wl.run_ops)
    assert (drv.stats["found"], drv.stats["acked"],
            drv.stats["scanned"]) == want.counts()
    deferred = sess.stats["stream_deferred_plans"]
    assert deferred == drv.stats["deferred_plans"] > 0
