"""Pipelined serving runtime: deferred snapshot re-exports with
epoch-guarded publication (``AsyncExporter``), the double-buffered /
coalescing plan executor (``PlanPipeline``) pinned bit-identical to
the blocking path, pipelined ``StreamDriver`` runs, and recovery of
live multi-stream traffic across a powerfail (per-stream program
order survives, no acked write lost)."""

import time

import numpy as np
import pytest

from repro.core import PCLHT, PMem, Plan
from repro.distributed import StreamDriver
from repro.serving import AsyncExporter, PlanPipeline


def _clht():
    return PCLHT(PMem(), n_buckets=16)


def _load(idx, keys):
    idx.execute(Plan.from_ops([("insert", k, k * 10 + 1) for k in keys]),
                collect_results=False)


def _stale_snapshot(idx):
    """Install an export, then invalidate it with a batched write wave
    (the sharded write path keeps the snapshot object but moves the
    epoch key — the 'in use but stale' state submit_if_stale targets)."""
    idx.snapshot()
    idx.execute(Plan.from_ops([("update", k, k + 500) for k in (1, 2, 3, 4)]),
                force_kernel=True, collect_results=False)
    assert idx._snapshot is not None
    assert idx._snapshot.epoch != idx._epoch_key()


class _SlowIndex:
    """Delegate that stretches ``execute`` so the pipeline queue
    deterministically builds up (coalescing / stall tests) while every
    operation still runs on the real index."""

    def __init__(self, inner, delay=0.005):
        self._inner = inner
        self._delay = delay

    def execute(self, *args, **kwargs):
        time.sleep(self._delay)
        return self._inner.execute(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _mixed_plans(n_plans=12, n_ops=40, seed=3):
    """Conflicting mixed-op plans: repeated keys across (and within)
    plans, so per-key program order across plan boundaries is load-
    bearing for the identity assertions."""
    rng = np.random.default_rng(seed)
    plans = []
    for _ in range(n_plans):
        ops = []
        for _ in range(n_ops):
            k = int(rng.integers(1, 30))
            r = rng.random()
            if r < 0.40:
                ops.append(("lookup", k, 0))
            elif r < 0.70:
                ops.append(("update", k, int(rng.integers(1, 1000))))
            elif r < 0.85:
                ops.append(("insert", k, int(rng.integers(1, 1000))))
            else:
                ops.append(("delete", k, 0))
        plans.append(Plan.from_ops(ops))
    return plans


# ---------------------------------------------------------------------------
# AsyncExporter: epoch guard, dedup, staleness policy, crash discard
# ---------------------------------------------------------------------------
def test_publish_export_rejects_outrun_build_whole():
    idx = _clht()
    _load(idx, range(1, 9))
    built = idx.build_export()
    idx.insert(99, 990)  # a write lands mid-build: the epoch moves
    assert not idx.publish_export(built)
    assert idx._snapshot is None, "a stale build must never install"
    fresh = idx.build_export()
    assert idx.publish_export(fresh)
    assert idx._snapshot is fresh


def test_exporter_dedup_and_noop_accounting():
    ex = AsyncExporter()
    idx = _clht()
    _load(idx, range(1, 9))
    _stale_snapshot(idx)
    assert ex.submit(idx)
    assert not ex.submit(idx), "pending jobs must deduplicate"
    assert ex.backlog == 1
    assert ex.run_pending() == 1
    assert ex.backlog == 0
    assert idx._snapshot.epoch == idx._epoch_key()
    # resubmitting a current index runs as a no-op, not a rebuild
    assert ex.submit(idx)
    assert ex.run_pending() == 0
    assert ex.stats["published"] == 1
    assert ex.stats["noop"] == 1


def test_submit_if_stale_policy():
    """Refresh exports in use; never create ones nobody asked for."""
    ex = AsyncExporter()
    idx = _clht()
    _load(idx, range(1, 9))
    assert not ex.submit_if_stale(idx), "no export in use -> no job"
    idx.snapshot()
    assert not ex.submit_if_stale(idx), "current export -> no job"
    _stale_snapshot(idx)
    assert ex.submit_if_stale(idx), "in-use export went stale -> refresh"
    ex.run_pending()
    assert not ex.submit_if_stale(idx), "refreshed -> current again"


def test_discard_pending_is_the_crash_path():
    ex = AsyncExporter()
    idxs = []
    for _ in range(2):
        idx = _clht()
        _load(idx, range(1, 9))
        _stale_snapshot(idx)
        assert ex.submit_if_stale(idx)
        idxs.append(idx)
    assert ex.backlog == 2
    assert ex.discard_pending() == 2
    assert ex.backlog == 0
    assert ex.stats["discarded"] == 2
    assert ex.run_pending() == 0, "discarded jobs must not run later"
    for idx in idxs:  # the stale export was left alone, never half-built
        assert idx._snapshot.epoch != idx._epoch_key()


# ---------------------------------------------------------------------------
# PlanPipeline: bit-identity (through coalescing), boundaries, errors
# ---------------------------------------------------------------------------
def test_pipeline_bit_identical_to_blocking_while_coalescing():
    plans = _mixed_plans()
    idx_b = _clht()
    _load(idx_b, range(1, 30))
    base = [idx_b.execute(p) for p in plans]

    idx_p = _clht()
    _load(idx_p, range(1, 30))
    with PlanPipeline(_SlowIndex(idx_p), depth=8,
                      exporter=AsyncExporter()) as pipe:
        tickets = [pipe.submit(p) for p in plans]
        got = [t.wait() for t in tickets]
        stats = dict(pipe.stats)
    # the slow index guarantees the queue built up and groups formed —
    # identity below holds *through* the coalesced merged executions
    assert stats["coalesced_plans"] > 0
    assert stats["groups"] > 0
    assert [g.results for g in got] == [b.results for b in base]
    assert [(g.found, g.acked, g.scanned) for g in got] == \
        [(b.found, b.acked, b.scanned) for b in base]
    assert dict(idx_p.items()) == dict(idx_b.items())
    # telemetry stays exact under slicing: wave/probe deltas go whole
    # to each group's first ticket, so the sums match blocking's sums
    for field in ("pm_gather_words",):
        assert sum(g.probe.get(field, 0) for g in got) == \
            sum(b.probe.get(field, 0) for b in base), field


def test_collect_results_false_never_coalesces():
    idx = _clht()
    _load(idx, range(1, 9))
    oracle = _clht()
    _load(oracle, range(1, 9))
    plans = [Plan.from_ops([("update", k, 100 + i) for k in (1, 2, 3)])
             for i in range(6)]
    with PlanPipeline(_SlowIndex(idx), depth=8,
                      collect_results=False) as pipe:
        for p in plans:
            pipe.submit(p)
        pipe.drain()
        stats = dict(pipe.stats)
    # tally-only plans have no result slots to slice, so they must
    # execute one by one even though the queue was saturated
    assert stats["coalesced_plans"] == 0
    assert stats["groups"] == 0
    assert stats["plans"] == len(plans)
    for p in plans:
        oracle.execute(p, collect_results=False)
    assert dict(idx.items()) == dict(oracle.items())


def test_error_propagates_and_pipeline_survives():
    idx = _clht()
    _load(idx, range(1, 9))
    with PlanPipeline(idx) as pipe:
        # P-CLHT is unordered: executing a scan raises in the worker
        bad = pipe.submit(Plan.from_ops([("scan", 1, 4)]))
        with pytest.raises(NotImplementedError):
            bad.wait()
        with pytest.raises(NotImplementedError):
            pipe.drain()  # drain surfaces the same error
        # the worker is still alive and the pipeline still usable
        ok = pipe.submit(Plan.from_ops([("lookup", 1, 0)]))
        assert ok.wait().results == [11]


def test_backpressure_stalls_are_counted():
    idx = _clht()
    _load(idx, range(1, 9))
    with PlanPipeline(_SlowIndex(idx, delay=0.01), depth=1) as pipe:
        for i in range(3):
            pipe.submit(Plan.from_ops([("lookup", 1 + i % 8, 0)]))
        pipe.drain()
        stats = dict(pipe.stats)
    assert stats["stalls"] > 0, "depth-1 queue under a slow worker must stall"
    assert stats["max_depth"] >= 1


# ---------------------------------------------------------------------------
# StreamDriver pipelined mode: identical to blocking ticks
# ---------------------------------------------------------------------------
def _stream_workload(drv, plans_per_stream=4, seed=5):
    rng = np.random.default_rng(seed)
    for s, stream in enumerate(drv.streams):
        for j in range(plans_per_stream):
            ops = []
            for _ in range(10):
                k = int(rng.integers(1, 20))
                if rng.random() < 0.5:
                    ops.append(("lookup", k, 0))
                else:
                    ops.append(("update", k, 1 + s * 100 + j))
            stream.submit(Plan.from_ops(ops))


def test_stream_driver_pipelined_identity():
    idx_b = _clht()
    _load(idx_b, range(1, 20))
    drv_b = StreamDriver(idx_b, 3)
    _stream_workload(drv_b)
    tickets_b = [t for s in drv_b.streams for t in s.queue]
    drv_b.run()

    idx_p = _clht()
    _load(idx_p, range(1, 20))
    drv_p = StreamDriver(idx_p, 3)
    _stream_workload(drv_p)
    tickets_p = [t for s in drv_p.streams for t in s.queue]
    with PlanPipeline(idx_p, depth=4) as pipe:
        drv_p.run_pipelined(pipe)

    # per-ticket results AND the tick each plan landed in are identical
    assert [t.result for t in tickets_p] == [t.result for t in tickets_b]
    assert [t.tick for t in tickets_p] == [t.tick for t in tickets_b]
    for name in ("ticks", "admitted_plans", "deferred_plans", "merged_ops",
                 "multi_stream_ticks", "found", "acked", "scanned"):
        assert drv_p.stats[name] == drv_b.stats[name], name
    assert dict(idx_p.items()) == dict(idx_b.items())


def test_stream_driver_pipelined_defers_conflicts_identically():
    """Conflicting cross-stream plans defer the same way in both
    modes: admission is shared (``_admit_tick``), so the contention
    counter and the serialization order are mode-independent."""
    def conflicting(drv):
        for i in range(6):
            drv.streams[i % 2].submit(Plan.from_ops(
                [("update", k, 100 + i) for k in (5, 6, 7)]))

    idx_b = _clht()
    _load(idx_b, (5, 6, 7))
    drv_b = StreamDriver(idx_b, 2, collect_results=False)
    conflicting(drv_b)
    drv_b.run()

    idx_p = _clht()
    _load(idx_p, (5, 6, 7))
    drv_p = StreamDriver(idx_p, 2, collect_results=False)
    conflicting(drv_p)
    with PlanPipeline(idx_p, depth=4, collect_results=False) as pipe:
        drv_p.run_pipelined(pipe)

    assert drv_b.stats["deferred_plans"] > 0
    assert drv_p.stats["deferred_plans"] == drv_b.stats["deferred_plans"]
    assert drv_p.stats["ticks"] == drv_b.stats["ticks"]
    assert dict(idx_p.items()) == dict(idx_b.items())


# ---------------------------------------------------------------------------
# crash mid-traffic: program order survives, no acked write lost
# ---------------------------------------------------------------------------
class _StubModel:
    cfg = None  # Server.__init__ reads only model.cfg


def test_server_streams_survive_crash_and_recover():
    """Concurrent client streams drive writes through the server's PM
    prefix index; a powerfail lands mid-traffic.  Every *acked*
    (ticked) write must read back after recovery, staged exporter work
    must be discarded, and resuming the driver must land each stream's
    key on its final program-order value."""
    from repro.serving.engine import Server
    server = Server(_StubModel(), params=None, page_size=8, n_pages=32)
    drv = server.streams(3)
    n_plans = 5
    val = lambda s, j: 1 + s * 1000 + j  # noqa: E731 — nonzero (P-ART)
    for s, stream in enumerate(drv.streams):
        for j in range(n_plans):
            stream.submit(Plan.from_ops([("update", 100 + s, val(s, j))]))
    for _ in range(2):
        drv.tick()
    acked = {}
    for s, stream in enumerate(drv.streams):
        done = n_plans - len(stream.queue)
        assert done >= 1, "no plan acked before the crash"
        acked[s] = val(s, done - 1)

    # stage exporter work, then pull the plug mid-traffic
    server.kv.prefix.snapshot()
    server.exporter.submit(server.kv.prefix)
    assert server.exporter.backlog == 1
    server.crash_and_recover()
    assert server.exporter.backlog == 0, "staged exports must die with power"
    assert server.stats["async_exports_discarded"] >= 1
    assert server._prebuilt is None

    # no acked write lost: each stream's last ticked value reads back
    for s in range(3):
        assert server.kv.prefix.lookup(100 + s) == acked[s], \
            f"stream {s} lost an acked write across the powerfail"

    # the streams resume on the recovered image and program order holds
    drv.run()
    for s in range(3):
        assert server.kv.prefix.lookup(100 + s) == val(s, n_plans - 1)
    assert drv.pending() == 0
    assert server.stats["stream_ticks"] == drv.stats["ticks"]
