"""The four-shard P-Masstree cell, rehearsed end to end on four virtual
CPU devices, so the ``shard_map`` form of the fan-out runs; and its
three readers on hand-made windows."""

import dataclasses
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import spec, xplane  # noqa: E402
from bench.peaks import peaks_for  # noqa: E402
from bench.shapes import sorted_lookup_bytes  # noqa: E402
from bench.window import Window  # noqa: E402
from bench_rehearsal import rehearse  # noqa: E402

CELL = "masstree-4shard-ycsb-c"
V5E = "TPU v5 lite"


@pytest.fixture
def four_devices(monkeypatch):
    """``rehearse`` passes this process's environment on."""
    flags = os.environ.get("XLA_FLAGS", "")
    monkeypatch.setenv("XLA_FLAGS", (
        flags + " --xla_force_host_platform_device_count=4").strip())


def test_mesh_cell_rehearsal_is_correct_on_four_devices(four_devices):
    rc, result, err = rehearse(CELL, seed=3000000019)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True and result["rehearsal"] is True
    assert result["device"]["count"] == 4
    assert {"ops_per_s", "setup_s"} <= set(result["metrics"])
    assert "0 programs lowered inside the window" in err


def test_mesh_cell_traced_rehearsal_reads_its_metrics(four_devices):
    rc, result, err = rehearse(CELL, "--trace", "1", seed=3000000023)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True
    assert {"mesh_fetch_share", "mesh_host_share"} <= set(result["metrics"])
    idle = dict(result["breakdown"]["idle_gaps"])
    assert {"shard.route", "shard.results", "kernel.fetch"} <= set(idle)


def test_mesh_cell_half_batch_reads_incorrect(four_devices):
    rc, result, err = rehearse(CELL, "--fault", "half_batch")
    assert rc == 0, err[-3000:]
    assert result["correct"] is False


# -- the readers on hand-made windows --------------------------------------

@dataclasses.dataclass
class S:
    """A span as the window holds it."""
    name: str
    dur: float
    span_id: int
    parent_id: int = None
    ts: float = 0.0
    attrs: dict = dataclasses.field(default_factory=dict)


def window(spans, host_ns=1000.0, program_ns=None):
    w = Window(host_ns=host_ns, spans=spans, compiles=0, config={},
               keys=1 << 20, device_kind=V5E, devices=[0, 1, 2, 3])
    if program_ns is not None:  # one fused-probe program on each chip
        w.trace = xplane.DeviceTrace(
            ops={}, marks=[],
            programs={d: [xplane.Event(0.0, program_ns, "jit_mesh_probe(7)"),
                          xplane.Event(0.0, 5e6, "jit_scan_window(3)")]
                      for d in w.devices})
        w.lo, w.hi = 0.0, 1e9
    return w


def reader(name):
    return spec._load_reader(name)


def lookup(span_id, ops, q_pad, run_max, n_pad, dur=500.0):
    return S("shard.mesh_lookup", dur, span_id,
             attrs={"ops": ops, "q_pad": q_pad, "run_max": run_max,
                    "n_pad": n_pad, "placement": "devices"})


def test_mesh_probe_roofline_counts_real_queries_and_the_live_run():
    read = reader("mesh_probe_roofline")
    spans = [lookup(1, 4096, 2048, 262_000, 1 << 19),
             lookup(2, 4096, 2048, 262_000, 1 << 19)]
    got = read(window(spans, program_ns=2e6))
    least_s = (2 * sorted_lookup_bytes(4096, 262_000)
               / peaks_for(V5E)["hbm_bytes_per_s"])
    # the device time is summed over the four chips
    assert got == pytest.approx(100 * least_s / (4 * 2e6 / 1e9))
    assert 0 < got < 100


def test_mesh_probe_roofline_leaves_padding_out():
    """Doubling the query slots or the padded run changes nothing: only
    the real queries and the longest live run enter the bytes."""
    read = reader("mesh_probe_roofline")
    tight = [lookup(1, 4096, 1024, 262_000, 1 << 18)]
    padded = [lookup(1, 4096, 4096, 262_000, 1 << 20)]
    assert read(window(tight, program_ns=2e6)) == \
        read(window(padded, program_ns=2e6))


def test_mesh_probe_roofline_reads_nothing_without_its_sources():
    read = reader("mesh_probe_roofline")
    # a program whose span carries no run length (the parent's)
    old = [S("shard.mesh_lookup", 500, 1, attrs={"ops": 4096})]
    assert read(window(old, program_ns=2e6)) is None
    # no device program of that name (a CPU trace has no module line)
    assert read(window([lookup(1, 4096, 2048, 262_000, 1 << 19)],
                       program_ns=None)) is None
    assert read(window([], program_ns=2e6)) is None


def test_mesh_fetch_share_counts_fetches_inside_the_fan_out_only():
    read = reader("mesh_fetch_share")
    spans = [lookup(1, 4096, 2048, 10, 128, dur=600),
             S("kernel.launch", 100, 2, 1), S("kernel.fetch", 300, 3, 1),
             S("kernel.scan", 200, 4), S("kernel.fetch", 150, 5, 4)]
    assert read(window(spans)) == pytest.approx(30.0)
    # the fan-out without the span (the parent's), or no fan-out
    assert read(window([lookup(1, 4096, 2048, 10, 128)])) is None
    assert read(window(spans[3:])) is None


def test_mesh_host_share_is_route_results_and_lookup_less_kernels():
    read = reader("mesh_host_share")
    spans = [S("shard.route", 50, 1),
             lookup(2, 4096, 2048, 10, 128, dur=600),
             S("kernel.launch", 100, 3, 2), S("kernel.fetch", 300, 4, 2),
             S("shard.results", 150, 5)]
    # 50 + 150 + (600 - 400) over 1000
    assert read(window(spans)) == pytest.approx(40.0)
    # without the routing span (the parent's program): nothing to read
    assert read(window(spans[1:])) is None
