"""The readers of the read path's spans and counters, on hand-made
windows, and in a traced CPU rehearsal of the cells that list them."""

import dataclasses
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import spec  # noqa: E402
from bench.window import Window  # noqa: E402
from bench_rehearsal import rehearse  # noqa: E402

NEW = ("export_share", "upload_kib_per_plan", "probe_wait_share",
       "scalar_read_share")


@dataclasses.dataclass
class S:
    """A span as the window holds it."""
    name: str
    dur: float
    span_id: int
    parent_id: int = None
    ts: float = 0.0
    attrs: dict = dataclasses.field(default_factory=dict)


def window(spans, host_ns=1000.0):
    return Window(host_ns=host_ns, spans=spans, compiles=0, config={},
                  keys=0, device_kind="cpu", devices=[0])


def reader(name):
    return spec._load_reader(name)


def test_export_share_is_export_time_over_the_window():
    w = window([S("plan.lookup_batch", 500, 1),
                S("snapshot.export", 200, 2, 1),
                S("snapshot.export", 50, 3, 1)])
    assert reader("export_share")(w) == pytest.approx(25.0)
    assert reader("export_share")(window([S("plan.execute", 9, 1)])) is None


def test_upload_kib_per_plan_sums_the_waves_over_the_plans():
    spans = [S("plan.execute", 10, 1), S("plan.execute", 10, 2),
             S("plan.wave", 5, 3, 1, attrs={"kind": "read",
                                           "upload_bytes": 10 * 1024}),
             S("plan.wave", 5, 4, 2, attrs={"kind": "write",
                                           "upload_bytes": 0}),
             S("plan.wave", 5, 5, 2, attrs={"kind": "read",
                                           "upload_bytes": 6 * 1024})]
    assert reader("upload_kib_per_plan")(window(spans)) == 8.0
    # a program without the counter: nothing to read
    old = [S("plan.execute", 10, 1),
           S("plan.wave", 5, 2, 1, attrs={"kind": "read"})]
    assert reader("upload_kib_per_plan")(window(old)) is None


def test_probe_wait_share_is_fetch_time_over_the_window():
    w = window([S("kernel.clht_probe", 300, 1),
                S("kernel.launch", 100, 2, 1), S("kernel.fetch", 150, 3, 1)],
               host_ns=600.0)
    assert reader("probe_wait_share")(w) == pytest.approx(25.0)
    assert reader("probe_wait_share")(window([])) is None


def test_scalar_read_share_counts_read_waves_only():
    spans = [S("plan.wave", 1, 1, attrs={"kind": "read", "width": 100,
                                         "scalar_reads": 10}),
             S("plan.wave", 1, 2, attrs={"kind": "read", "width": 300,
                                         "scalar_reads": 0}),
             S("plan.wave", 1, 3, attrs={"kind": "write", "width": 50,
                                         "scalar_reads": 0})]
    assert reader("scalar_read_share")(window(spans)) == pytest.approx(2.5)
    old = [S("plan.wave", 1, 1, attrs={"kind": "read", "width": 100})]
    assert reader("scalar_read_share")(window(old)) is None


@pytest.mark.parametrize("kernel", ["kernel.clht_probe", "kernel.scan"])
def test_read_host_share_ignores_launch_and_fetch_inside_a_kernel(kernel):
    """``kernel.launch`` and ``kernel.fetch`` start with ``kernel.`` but
    sit inside a kernel span, so the reader subtracts the kernel's time
    once; ``snapshot.upload`` sits outside it and stays host time."""
    base = [S("plan.lookup_batch", 800, 1),
            S("snapshot.upload", 200, 2, 1),
            S(kernel, 500, 3, 1)]
    nested = base + [S("kernel.launch", 200, 4, 3),
                     S("kernel.fetch", 250, 5, 3)]
    read = reader("read_host_share")
    assert read(window(base)) == read(window(nested)) == pytest.approx(30.0)


@pytest.mark.parametrize("workload,names", [
    ("clht-ycsb-a", NEW),
    ("masstree-ycsb-c", NEW[1:])])
def test_a_traced_rehearsal_reads_the_new_metrics(workload, names):
    rc, result, err = rehearse(workload, "--trace", "1", seed=3000000017)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True
    assert set(names) <= set(result["metrics"])
    if workload == "masstree-ycsb-c":
        # every read wave is over the kernel floor on a current export
        assert result["metrics"]["scalar_read_share"]["value"] == 0.0
