"""The trace reduction, on a small trace recorded on the CPU backend and
on hand-made intervals."""

import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import xplane  # noqa: E402
from bench.peaks import PEAKS, peaks_for  # noqa: E402


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "cpu":
        pytest.skip("records a CPU-backend trace")
    f = jax.jit(lambda x: jnp.sort(x * 3 + 1).sum())
    x = jnp.arange(1 << 18, dtype=jnp.float32)
    f(x).block_until_ready()
    log_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    starts, ends = [], []
    for i in range(5):
        t0 = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(xplane.MARK):
            f(x + i).block_until_ready()
        ends.append(time.perf_counter_ns())
        starts.append(t0)
        time.sleep(0.01)  # a host gap the device sits idle through
    jax.profiler.stop_trace()
    return xplane.read_trace(xplane.newest_xplane(log_dir)), starts, ends


def test_reduction_of_a_cpu_trace(cpu_trace):
    trace, starts, ends = cpu_trace
    assert len(trace.marks) == 5
    offset = xplane.clock_offset(trace.marks, starts)
    lo, hi = starts[0] + offset, ends[-1] + offset
    # the marks land where the host clock says, give or take scheduling
    for m, s, e in zip(trace.marks, starts, ends):
        assert abs(m.start - (s + offset)) < 2e6
        assert abs((m.end - m.start) - (e - s)) < 2e6
    busy = xplane.busy_ns(trace.ops[0], lo, hi)
    idle = sum(e - s for s, e in xplane.gaps(trace.ops[0], lo, hi))
    assert 0 < busy < hi - lo
    assert busy + idle == pytest.approx(hi - lo)
    # the sleeps between executes are idle time outside every mark
    timeline = xplane.innermost_timeline(
        [(m.start, m.end, m.name) for m in trace.marks])
    by_label = xplane.attribute(xplane.gaps(trace.ops[0], lo, hi), timeline)
    assert sum(by_label.values()) == pytest.approx(idle)
    assert by_label[xplane.CLIENT] >= 4 * 0.01e9 * 0.9
    assert xplane.op_times(trace, 0, lo, hi)


def test_busy_and_gaps_of_overlapping_intervals():
    ev = [xplane.Event(s, e, "op") for s, e in
          [(10, 20), (15, 30), (40, 50), (45, 48), (90, 120)]]
    assert xplane.merge((e.start, e.end) for e in ev) == [
        (10, 30), (40, 50), (90, 120)]
    assert xplane.busy_ns(ev, 0, 100) == 20 + 10 + 10
    assert xplane.gaps(ev, 0, 100) == [(0, 10), (30, 40), (50, 90)]


def test_idle_gaps_go_to_the_innermost_open_span():
    spans = [(0, 100, "plan.execute"), (10, 60, "plan.lookup_batch"),
             (20, 40, "kernel.clht_probe"), (70, 80, "plan.write_batch")]
    timeline = xplane.innermost_timeline(spans)
    got = xplane.attribute([(0, 30), (50, 75), (95, 110)], timeline)
    assert got == {"plan.execute": 10 + 5 + 5 + 5,
                   "plan.lookup_batch": 10 + 10,
                   "kernel.clht_probe": 10,
                   "plan.write_batch": 5,
                   xplane.CLIENT: 10}


def test_clock_offset_is_the_median_and_counts_must_match():
    marks = [xplane.Event(s, s + 5, xplane.MARK) for s in (105, 207, 300)]
    assert xplane.clock_offset(marks, [100, 200, 298]) == 5
    with pytest.raises(ValueError):
        xplane.clock_offset(marks, [100, 200])


def test_short_names_of_programs_and_ops():
    assert xplane.short_name("jit__gather_probe(17807753327407019999)") == \
        "jit__gather_probe"
    assert xplane.short_name("%fusion.14 = s32[2048,3]{0,1} fusion(%a)") == \
        "%fusion.14"


def test_peaks_are_keyed_by_device_kind():
    v5e = peaks_for("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert all("source" in p for p in PEAKS.values())
    with pytest.raises(KeyError):
        peaks_for("cpu")
