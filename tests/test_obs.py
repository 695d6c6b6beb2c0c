"""Tests for the repro.obs telemetry subsystem: span nesting, the
histogram-vs-numpy percentile oracle, registry merge semantics,
disabled-mode no-op behavior, trace-JSON schema round-trip, and the
end-to-end guarantees the chip benchmark reads (exact per-wave counter
attribution, serving stats view, recovery span)."""

import json

import numpy as np
import pytest

from repro import obs
from repro.core import PCLHT, PMem, Plan
from repro.core.ycsb import generate, run_workload
from repro.obs import (Histogram, MetricsRegistry, MetricsView, Recorder,
                       bucket_index, bucket_upper, chrome_trace,
                       validate_chrome_trace)


@pytest.fixture(autouse=True)
def _clean_recorder():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
def test_span_nesting_and_ordering():
    obs.enable()
    with obs.span("outer", a=1):
        with obs.span("mid") as m:
            m.set(b=2)
            with obs.span("inner"):
                pass
        with obs.span("mid2"):
            pass
    spans = obs.spans()
    assert [s.name for s in sorted(spans, key=lambda s: s.ts)] == \
        ["outer", "mid", "inner", "mid2"]
    by_name = {s.name: s for s in spans}
    assert by_name["outer"].parent_id is None
    assert by_name["mid"].parent_id == by_name["outer"].span_id
    assert by_name["inner"].parent_id == by_name["mid"].span_id
    assert by_name["mid2"].parent_id == by_name["outer"].span_id
    # containment: children start no earlier and end no later
    for child, parent in (("mid", "outer"), ("inner", "mid")):
        c, p = by_name[child], by_name[parent]
        assert c.ts >= p.ts
        assert c.ts + c.dur <= p.ts + p.dur
    assert by_name["mid"].attrs["b"] == 2


def test_add_span_external_timing():
    obs.enable()
    import time
    t0 = time.perf_counter_ns()
    t1 = t0 + 5_000_000
    sp = obs.add_span("recovery.time_to_first_served", t0, t1, n=3)
    assert sp.dur == 5_000_000
    assert obs.spans("recovery.time_to_first_served") == [sp]


def test_disabled_mode_is_noop():
    assert not obs.enabled()
    sp = obs.span("anything", big_attr=list(range(100)))
    assert not sp  # falsy -> `if sp:` guards skip snapshot work
    with sp as inner:
        inner.set(x=1)  # accepted, discarded
    assert obs.spans() == []
    assert not obs.add_span("x", 0, 10)


def test_recorder_isolation():
    r = Recorder()
    r.enable()
    with r.span("private"):
        pass
    assert len(r.spans) == 1
    assert obs.spans() == []  # the global recorder saw nothing


# ---------------------------------------------------------------------------
# histogram vs numpy percentile oracle
# ---------------------------------------------------------------------------
def test_bucket_roundtrip_exact_below_subs():
    for v in range(64):
        idx = bucket_index(v)
        assert bucket_upper(idx) >= v
        assert bucket_index(bucket_upper(idx)) == idx


def test_bucket_monotone():
    vals = [0, 1, 31, 32, 33, 63, 64, 100, 1000, 10**6, 10**12, (1 << 62)]
    idxs = [bucket_index(v) for v in vals]
    assert idxs == sorted(idxs)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_percentile_matches_numpy_oracle(seed):
    rng = np.random.default_rng(seed)
    # mixed scales: sub-bucket-exact small values and wide log range
    x = np.concatenate([
        rng.integers(0, 32, 500),
        rng.integers(32, 5000, 500),
        (10 ** rng.uniform(3, 9, 1000)).astype(np.int64),
    ])
    h = Histogram()
    h.record_many(x)
    assert h.n == x.size
    for q in (1, 10, 25, 50, 75, 90, 95, 99, 99.9, 100):
        oracle = int(np.percentile(x, q, method="inverted_cdf"))
        # bucketing is monotone, so the histogram percentile is exactly
        # the oracle value's bucket upper bound
        assert h.percentile(q) == bucket_upper(bucket_index(oracle)), q
        # relative bucket error is bounded by one sub-bucket (~3.1%)
        assert h.percentile(q) >= oracle
        if oracle >= 32:
            assert h.percentile(q) <= oracle * (1 + 2 / 32)


def test_histogram_merge_equals_union():
    rng = np.random.default_rng(3)
    a, b = rng.integers(1, 10**8, 1000), rng.integers(1, 10**8, 1500)
    ha, hb, hu = Histogram(), Histogram(), Histogram()
    ha.record_many(a)
    hb.record_many(b)
    hu.record_many(np.concatenate([a, b]))
    ha.merge(hb)
    assert ha.n == hu.n and ha.total == hu.total
    assert (ha.counts == hu.counts).all()
    for q in (50, 95, 99):
        assert ha.percentile(q) == hu.percentile(q)


def test_histogram_empty():
    h = Histogram()
    assert h.percentile(50) == 0 and h.mean == 0.0
    assert h.summary() == {"count": 0, "mean": 0.0, "p50": 0,
                           "p95": 0, "p99": 0}


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_registry_merge_across_shards():
    shards = []
    for i in range(3):
        r = MetricsRegistry()
        r.counter("ops").inc(10 * (i + 1))
        r.gauge("depth").set(i + 1)
        r.histogram("lat").record_many([100 * (i + 1)] * 5)
        shards.append(r)
    total = MetricsRegistry()
    for r in shards:
        total.merge(r)
    assert total.counter("ops").value == 60       # counters sum
    assert total.gauge("depth").value == 3        # gauges take the max
    assert total.histogram("lat").n == 15         # histograms bucket-sum
    assert total.as_dict() == {"ops": 60, "depth": 3}


def test_registry_type_conflict_raises():
    r = MetricsRegistry()
    r.counter("x")
    with pytest.raises(ValueError):
        r.gauge("x")


def test_metrics_view_read_only():
    r = MetricsRegistry()
    r.counter("plans").inc(2)
    r.gauge("width").set(7)
    v = MetricsView(r)
    assert v["plans"] == 2 and v["width"] == 7
    assert dict(v) == {"plans": 2, "width": 7}
    assert len(v) == 2 and "plans" in v
    with pytest.raises(TypeError):
        v["plans"] = 5
    with pytest.raises(TypeError):
        del v["plans"]
    with pytest.raises(KeyError):
        v["missing"]
    r.counter("plans").inc()  # live view, not a copy
    assert v["plans"] == 3


# ---------------------------------------------------------------------------
# trace JSON schema round-trip
# ---------------------------------------------------------------------------
def test_trace_schema_roundtrip(tmp_path):
    obs.enable()
    with obs.span("plan.execute", n_ops=4):
        with obs.span("plan.wave", kind="read", wave=0, width=4):
            pass
    obs.disable()
    path = tmp_path / "trace.json"
    obj = obs.write_trace(str(path))
    assert validate_chrome_trace(obj) == []
    loaded = json.loads(path.read_text())
    assert loaded == obj
    assert validate_chrome_trace(loaded) == []
    evs = loaded["traceEvents"]
    assert [e["name"] for e in evs] == ["plan.execute", "plan.wave"]
    assert evs[0]["ph"] == "X" and evs[0]["cat"] == "plan"
    assert evs[1]["args"]["parent_id"] == evs[0]["args"]["span_id"]
    assert evs[1]["args"]["kind"] == "read"


def test_trace_validator_catches_bad_events():
    assert validate_chrome_trace({}) != []
    assert validate_chrome_trace({"traceEvents": [{}]}) != []
    bad = {"traceEvents": [
        {"name": "a", "cat": "a", "ph": "X", "ts": 0, "dur": 1,
         "pid": 1, "tid": 1, "args": {"span_id": 1, "parent_id": 99}}]}
    assert any("parent_id" in e for e in validate_chrome_trace(bad))


# ---------------------------------------------------------------------------
# end-to-end: exact per-wave counter attribution
# ---------------------------------------------------------------------------
def test_plan_wave_attribution_exact():
    pm = PMem()
    idx = PCLHT(pm, n_buckets=128)
    wl = generate("A", 600, 600, seed=11)
    run_workload(idx, wl, phase="load", batch_lookups=True)
    obs.reset()
    obs.enable()
    c0 = pm.counters.snapshot()
    run_workload(idx, wl, phase="run", batch_lookups=True)
    d = pm.counters.delta(c0)
    obs.disable()
    waves = obs.spans("plan.wave")
    assert waves, "no plan.wave spans recorded"
    for field in ("clwb", "fence", "stores", "loads"):
        total = sum(w.attrs[field] for w in waves)
        assert total == getattr(d, field), field


def _validate_with_cli(obj, path):
    """Write the trace and validate it the way the command line does
    (``python -m repro.obs.trace <path>``)."""
    from repro.obs import trace
    path.write_text(json.dumps(obj))
    assert trace.main([str(path)]) == 0


def test_traced_ycsb_run_writes_a_valid_trace(tmp_path):
    """A traced YCSB-A run on P-CLHT: the per-wave clwb/fence add up to
    the run's PMem counter delta, and its trace file validates."""
    pm = PMem()
    idx = PCLHT(pm, n_buckets=512)
    wl = generate("A", 600, 600, seed=7)
    run_workload(idx, wl, phase="load", batch_lookups=True)
    obs.enable()
    c0 = pm.counters.snapshot()
    run_workload(idx, wl, phase="run", batch_lookups=True)
    d = pm.counters.delta(c0)
    obs.disable()
    waves = obs.spans("plan.wave")
    assert (sum(w.attrs["clwb"] for w in waves),
            sum(w.attrs["fence"] for w in waves)) == (d.clwb, d.fence)
    _validate_with_cli(chrome_trace(obs.RECORDER), tmp_path / "trace.json")


def test_traced_sharded_streams_write_a_valid_trace(tmp_path):
    """YCSB-A over four P-CLHT shards driven by two client streams,
    then a mesh read that re-exports under the tracer: the mesh agrees
    with the per-shard path, the ``shard.plan`` + ``shard.export``
    counter attributes add up to the ``ShardedPMem`` delta, and the
    trace file validates."""
    from repro.distributed import ShardedIndex, StreamDriver
    wl = generate("A", 600, 600, seed=7)
    idx = ShardedIndex(lambda p: PCLHT(p, n_buckets=512), 4)
    idx.execute(Plan.from_ops(wl.load_ops), collect_results=False)
    gets = Plan.from_ops([("lookup", k, 0) for _, k, _ in wl.load_ops[:300]])
    r_ps = idx.execute(gets, mesh=False)
    r_mesh = idx.execute(gets, mesh=True)
    assert r_mesh.mesh and not r_ps.mesh
    assert (r_mesh.found, r_mesh.results) == (r_ps.found, r_ps.results)
    obs.enable()
    c0 = idx.pmem.counters.snapshot()
    drv = StreamDriver(idx, 2)
    for i in range(0, len(wl.run_ops), 100):
        drv.streams[(i // 100) % 2].submit(
            Plan.from_ops(wl.run_ops[i:i + 100]))
    drv.run()
    # the run's inserts moved shard epochs: this mesh read re-exports
    # under the tracer, so shard.export spans join the books
    assert idx.execute(gets, mesh=True).found == r_mesh.found
    d = idx.pmem.counters.delta(c0)
    obs.disable()
    assert drv.stats["ticks"] > 0 and drv.stats["admitted_plans"] > 0
    spans = obs.spans("shard.plan") + obs.spans("shard.export")
    assert obs.spans("shard.export")
    for field in ("stores", "loads", "clwb", "fence", "lines_touched"):
        assert sum(sp.attrs.get(field, 0) for sp in spans) == \
            getattr(d, field), field
    _validate_with_cli(chrome_trace(obs.RECORDER), tmp_path / "trace.json")


def test_single_op_plan_emits_wave_span():
    pm = PMem()
    idx = PCLHT(pm, n_buckets=64)
    obs.enable()
    plan = Plan()
    plan.put(42, 43)
    idx.execute(plan)
    obs.disable()
    waves = obs.spans("plan.wave")
    assert len(waves) == 1
    assert waves[0].attrs["kind"] == "write"
    assert waves[0].attrs["clwb"] >= 1 and waves[0].attrs["fence"] >= 1


def test_group_commit_span_counts_close_traffic():
    pm = PMem()
    r = pm.alloc("t", 64)
    obs.enable()
    with pm.group_commit():
        for i in range(16):
            pm.store(r, i, i + 1)
            pm.clwb(r, i)
        pm.fence()
    obs.disable()
    spans = obs.spans("pmem.group_commit")
    assert len(spans) == 1
    sp = spans[0]
    # 16 words = 2 cache lines -> 2 clwb at close + 1 commit fence
    assert sp.attrs["clwb"] == 2 and sp.attrs["fence"] == 1
    assert sp.attrs["stores"] == 16 and not sp.attrs["aborted"]


def test_cas_counts_compare_load():
    pm = PMem()
    r = pm.alloc("t", 8)
    pm.store(r, 0, 5)
    loads0 = pm.counters.loads
    assert pm.cas(r, 0, 5, 6)
    assert pm.counters.loads == loads0 + 1
    assert not pm.cas(r, 0, 5, 7)  # mismatch also pays the load
    assert pm.counters.loads == loads0 + 2


# ---------------------------------------------------------------------------
# serving engine: stats view + recovery span
# ---------------------------------------------------------------------------
class _StubModel:
    cfg = None  # Server.__init__ reads only model.cfg


def _make_server():
    from repro.serving.engine import Server
    return Server(_StubModel(), params=None, page_size=8, n_pages=32)


def test_server_stats_is_metrics_view():
    server = _make_server()
    assert isinstance(server.stats, MetricsView)
    assert server.stats["decode_steps"] == 0
    assert set(server.stats) >= {
        "prefill_tokens", "prefix_hits", "decode_steps",
        "page_translations", "translation_batches",
        "warm_prefixes_restored", "ingest_write_batches",
        "prefix_shard_refined"}
    with pytest.raises(TypeError):
        server.stats["decode_steps"] = 1
    server.metrics.counter("decode_steps").inc(4)
    assert server.stats["decode_steps"] == 4


def test_server_recovery_time_to_first_served():
    server = _make_server()
    server.kv.prefix.insert(123, 7 + 1)
    obs.enable()
    server.crash_and_recover()
    assert server._recover_t0 is not None
    assert len(obs.spans("serve.recover")) == 1
    server._first_service()  # the first served token closes the window
    obs.disable()
    assert server._recover_t0 is None
    spans = obs.spans("recovery.time_to_first_served")
    assert len(spans) == 1 and spans[0].dur >= 0
    assert server.stats["recovery_time_to_first_served_us"] >= 0
    assert server.stats["warm_prefixes_restored"] == 1
    server._first_service()  # idempotent once closed
    assert len(obs.spans("recovery.time_to_first_served")) == 1


# ---------------------------------------------------------------------------
# stream-driver admission telemetry mirrored into Session/Server stats
# ---------------------------------------------------------------------------


def _conflicting_plans(n_plans=6):
    """Write plans that all hit the same keys — at most one can be
    admitted per tick, so every multi-stream tick defers the rest."""
    return [Plan.from_ops([("update", k, 100 + i) for k in (5, 6, 7)])
            for i in range(n_plans)]


def test_session_stats_mirror_stream_deferrals_exactly():
    from repro.api import Session
    sess = Session(PCLHT(PMem(), n_buckets=16), kind="clht")
    for k in (5, 6, 7):
        sess.put(k, k)
    drv = sess.streams(2, collect_results=False)
    for i, plan in enumerate(_conflicting_plans()):
        drv.streams[i % 2].submit(plan)
    drv.run()
    assert drv.stats["deferred_plans"] > 0
    # exact attribution: the registry view must equal the driver's own
    # counters, name for name, with no double counting
    for name in drv.MIRRORED:
        assert sess.stats[f"stream_{name}"] == drv.stats[name], name
    # a second driver on the same session accumulates into the same
    # counters (registry holds the session-lifetime totals)
    before = sess.stats["stream_deferred_plans"]
    drv2 = sess.streams(2, collect_results=False)
    for i, plan in enumerate(_conflicting_plans()):
        drv2.streams[i % 2].submit(plan)
    drv2.run()
    assert drv2.stats["deferred_plans"] > 0
    assert (sess.stats["stream_deferred_plans"]
            == before + drv2.stats["deferred_plans"])


def test_server_stats_mirror_stream_deferrals_exactly():
    server = _make_server()
    for k in (5, 6, 7):
        server.kv.prefix.insert(k, k)  # P-ART: keys/values must be != 0
    drv = server.streams(2, collect_results=False)
    for i, plan in enumerate(_conflicting_plans()):
        drv.streams[i % 2].submit(plan)
    drv.run()
    assert drv.stats["deferred_plans"] > 0
    for name in drv.MIRRORED:
        assert server.stats[f"stream_{name}"] == drv.stats[name], name


# ---------------------------------------------------------------------------
# pipelined-runtime gauges: exact attribution into the registry
# ---------------------------------------------------------------------------


def _stale_export(idx, salt=50):
    """Export, then invalidate via a batched write wave — the snapshot
    object survives (only its epoch key moves), which is the state the
    async exporter refreshes.  ``salt`` varies the written values: an
    update to a key's current value is a no-op and would leave the
    epoch (correctly) untouched."""
    idx.snapshot()
    idx.execute(Plan.from_ops([("update", k, k + salt) for k in (1, 2, 3)]),
                force_kernel=True, collect_results=False)


def test_async_export_backlog_gauge_exact():
    from repro.serving import AsyncExporter
    reg = MetricsRegistry()
    ex = AsyncExporter(metrics=reg)
    view = MetricsView(reg)
    assert view["async_export_backlog"] == 0
    idxs = []
    for _ in range(2):
        idx = PCLHT(PMem(), n_buckets=16)
        for k in (1, 2, 3):
            idx.insert(k, k)
        _stale_export(idx)
        assert ex.submit_if_stale(idx)
        idxs.append(idx)
    assert view["async_export_backlog"] == ex.backlog == 2
    assert view["async_exports_submitted"] == 2
    assert ex.run_pending() == 2
    assert view["async_export_backlog"] == 0
    assert view["async_exports_published"] == 2
    # the crash path drains the gauge too, without publishing anything
    _stale_export(idxs[0], salt=70)
    assert ex.submit_if_stale(idxs[0])
    assert view["async_export_backlog"] == 1
    assert ex.discard_pending() == 1
    assert view["async_export_backlog"] == 0
    assert view["async_exports_discarded"] == 1
    assert view["async_exports_published"] == 2


def test_pipeline_depth_gauge_and_counters_exact():
    import time as _time

    from repro.serving import PlanPipeline

    class _Slow:
        def __init__(self, inner):
            self._inner = inner

        def execute(self, *a, **kw):
            _time.sleep(0.005)
            return self._inner.execute(*a, **kw)

        def __getattr__(self, name):
            return getattr(self._inner, name)

    idx = PCLHT(PMem(), n_buckets=16)
    for k in range(1, 9):
        idx.insert(k, k)
    reg = MetricsRegistry()
    view = MetricsView(reg)
    with PlanPipeline(_Slow(idx), depth=4, metrics=reg) as pipe:
        for i in range(8):
            pipe.submit(Plan.from_ops([("lookup", 1 + i % 8, 0)]))
        pipe.drain()
        stats = dict(pipe.stats)
    # registry view equals the pipeline's own counters, name for name
    assert view["pipeline_plans"] == stats["plans"] == 8
    assert view["pipeline_stalls"] == stats["stalls"]
    assert view["pipeline_coalesced_plans"] == stats["coalesced_plans"]
    # the gauge records the high-water queue depth exactly
    assert view["pipeline_depth"] == stats["max_depth"] >= 1


def test_server_admit_queue_depth_gauge_exact():
    """The admission gauge is set from the queue length at the top of
    every tick — verified on a model-free server (max_batch=0 admits
    nothing, so step() never touches the stub model)."""
    from repro.serving.engine import Server
    server = Server(_StubModel(), params=None, max_batch=0,
                    page_size=8, n_pages=32)
    assert server.stats["admit_queue_depth"] == 0
    for i in range(3):
        server.submit([1, 2, 3, 4], max_new=2)
    server.step(16)
    assert server.stats["admit_queue_depth"] == 3
    assert len(server.queue) == 3  # nothing admitted at max_batch=0
    server.submit([1, 2, 3, 4], max_new=2)
    server.step(16)
    assert server.stats["admit_queue_depth"] == 4


# ---------------------------------------------------------------------------
# the read path: snapshot export and upload, kernel launch and fetch,
# and the exports / upload_bytes / scalar_reads counters
# ---------------------------------------------------------------------------
def _loaded(kind, n=96):
    from repro.api import open_index
    s = open_index(kind)
    s.execute(Plan.from_ops([("insert", k, k * 7) for k in range(1, n + 1)]))
    return s


def _children(parent):
    return [s for s in obs.spans() if s.parent_id == parent.span_id]


def _probe64_read():
    """The host-gathered window path: probe64 over P-CLHT's export."""
    from repro.kernels.clht_probe import batched_lookup
    s = _loaded("clht")
    keys, vals, nxt, n, fps = s.index.snapshot().arrays
    obs.enable()
    found, _ = batched_lookup(np.arange(1, 41, dtype=np.int64), keys, vals,
                              nxt, n_buckets=n, fps=fps)
    assert found.all()


def _plan_read(kind):
    def read():
        s = _loaded(kind)
        obs.enable()
        res = s.execute(Plan.from_ops([("lookup", k, 0)
                                       for k in range(1, 41)]),
                        force_kernel=True)
        assert res.results == [k * 7 for k in range(1, 41)]
    return read


@pytest.mark.parametrize("span,read", [
    ("kernel.clht_probe", _plan_read("clht")),
    ("kernel.scan", _plan_read("masstree")),
    ("kernel.art_probe", _plan_read("art")),
    ("kernel.probe64", _probe64_read),
], ids=["clht_probe", "scan", "art_probe", "probe64"])
def test_launch_and_fetch_nest_in_each_read_kernel_span(span, read):
    read()
    obs.disable()
    outer = obs.spans(span)
    assert len(outer) == 1
    kids = {s.name: s for s in _children(outer[0])}
    assert set(kids) == {"kernel.launch", "kernel.fetch"}
    launch, fetch = kids["kernel.launch"], kids["kernel.fetch"]
    assert launch.ts + launch.dur <= fetch.ts  # launch, then fetch
    assert launch.attrs["bytes"] > 0
    # one packed upload and one packed download per call
    assert launch.attrs["arrays"] == 1 and fetch.attrs["arrays"] == 1
    assert set(outer[0].attrs) <= {"batch", "padded", "depth", "window",
                                   "unit_bits", "fingerprints",
                                   "fp_candidates", "fp_false_positives"}


def test_exports_count_the_export_spans():
    s = _loaded("clht", n=700)
    e0 = s.stats["exports"]
    obs.enable()
    s.execute(Plan.from_ops([("update", k, k * 11) for k in range(1, 601)]))
    res = s.execute(Plan.from_ops([("lookup", k, 0) for k in range(1, 601)]))
    obs.disable()
    spans = obs.spans("snapshot.export")
    assert spans and s.stats["exports"] - e0 == len(spans)
    assert res.probe["exports"] == len(spans)
    assert all(sp.attrs["index"] == "P-CLHT" and sp.attrs["entries"] > 0
               for sp in spans)
    # each export was uploaded once, inside the read wave
    assert len(obs.spans("snapshot.upload")) == len(spans)
    assert sum(w.attrs["exports"] for w in obs.spans("plan.wave")) \
        == len(spans)


def test_upload_bytes_follow_the_export_shapes():
    """64 B per P-CLHT bucket row (four int32 halves of 3 slots, a
    3-lane int32 fingerprint row, an int32 chain pointer) and 16 B per
    padded query (bucket, two halves, fingerprint)."""
    from repro.kernels.probe import pad_queries
    s = _loaded("clht")
    q = 40
    obs.enable()
    res = s.execute(Plan.from_ops([("lookup", k, 0)
                                   for k in range(1, q + 1)]),
                    force_kernel=True)
    obs.disable()
    rows = s.index.snapshot().arrays[0].shape[0]
    table, queries = 64 * rows, 16 * (q + pad_queries(q))
    assert [sp.attrs["bytes"] for sp in obs.spans("snapshot.upload")] \
        == [table]
    assert [sp.attrs["bytes"] for sp in obs.spans("kernel.launch")] \
        == [queries]
    assert res.probe["upload_bytes"] == table + queries
    assert s.stats["upload_bytes"] == table + queries
    (wave,) = obs.spans("plan.wave")
    assert wave.attrs["upload_bytes"] == table + queries


def test_a_read_under_the_rebuild_floor_counts_scalar_reads():
    s = _loaded("clht")
    s.update(5, 55)  # a scalar write: every shard of the export is stale
    width = 40  # under P-CLHT's rebuild floor of 512
    before = s.stats["scalar_reads"]
    obs.enable()
    res = s.execute(Plan.from_ops([("lookup", k, 0)
                                   for k in range(1, width + 1)]))
    obs.disable()
    assert res.results[4] == 55
    assert s.stats["scalar_reads"] - before == width
    assert res.probe["scalar_reads"] == width
    (wave,) = obs.spans("plan.wave")
    assert wave.attrs["kind"] == "read"
    assert wave.attrs["scalar_reads"] == width
    assert not obs.spans("snapshot.export")  # no re-export under the floor


def _read_path_run():
    s = _loaded("clht", n=700)
    out = [s.execute(Plan.from_ops([("lookup", k, 0)
                                    for k in range(1, 601)])).results]
    s.execute(Plan.from_ops([("update", k, k * 3) for k in range(1, 601)]))
    out.append(s.execute(Plan.from_ops([("lookup", k, 0)
                                        for k in range(1, 41)])).results)
    out.append(s.execute(Plan.from_ops([("lookup", k, 0)
                                        for k in range(1, 601)])).results)
    return out, dict(s.stats)


def test_read_path_tracing_off_records_nothing_and_changes_nothing():
    untraced, stats_off = _read_path_run()
    assert obs.spans() == []
    obs.enable()
    traced, stats_on = _read_path_run()
    obs.disable()
    assert traced == untraced
    assert stats_on == stats_off
    names = {s.name for s in obs.spans()}
    assert {"snapshot.export", "snapshot.upload", "kernel.launch",
            "kernel.fetch"} <= names


def test_mesh_read_path_books_its_uploads_to_the_shards():
    from repro.api import open_index
    from repro.core.conditions import PROBE_STAT_KEYS
    s = open_index("masstree", shards=4, mesh_reads=True)
    s.execute(Plan.from_ops([("insert", k, k * 7) for k in range(1, 201)]))
    obs.enable()
    res = s.execute(Plan.from_ops([("lookup", k, 0) for k in range(1, 201)]))
    obs.disable()
    assert res.mesh and res.results == [k * 7 for k in range(1, 201)]
    per_shard = [sh.probe_stats for sh in s.index.shards]
    for k in PROBE_STAT_KEYS:
        assert s.stats[k] == sum(ps[k] for ps in per_shard), k
    (up,) = [sp for sp in obs.spans("snapshot.upload")
             if sp.attrs["kernel"] == "mesh_lookup"]
    assert res.probe["exports"] == 4
    # the stacked export and a query row per shard, two int32 halves
    assert res.probe["upload_bytes"] > up.attrs["bytes"] > 0
