"""The ``array_write_share`` reader, on hand-made windows, and in a
traced CPU rehearsal of the one cell that lists it."""

import dataclasses
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import spec  # noqa: E402
from bench.window import Window  # noqa: E402
from bench_rehearsal import rehearse  # noqa: E402


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


def read(spans):
    return spec._load_reader("array_write_share")(window(spans))


def test_array_write_share_is_array_writes_over_write_wave_widths():
    spans = [S("plan.wave", 1, 1, attrs={"kind": "write", "width": 100,
                                         "array_writes": 100}),
             S("plan.wave", 1, 2, attrs={"kind": "write", "width": 60,
                                         "array_writes": 20}),
             S("plan.wave", 1, 3, attrs={"kind": "read", "width": 500,
                                         "array_writes": 0})]
    assert read(spans) == pytest.approx(75.0)


@pytest.mark.parametrize("attrs", [
    {"kind": "read", "width": 40, "array_writes": 0},
    {"kind": "write", "width": 40}],
    ids=["no_write_wave_in_the_window", "program_without_the_counter"])
def test_array_write_share_is_left_out_with_nothing_to_read(attrs):
    assert read([S("plan.wave", 1, 1, attrs=attrs)]) is None


def test_a_traced_clht_ycsb_a_rehearsal_reads_array_write_share():
    rc, result, err = rehearse("clht-ycsb-a", "--trace", "1",
                               seed=3000000019)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True
    share = result["metrics"]["array_write_share"]
    print("array_write_share", share)
    # every update of the traffic names a loaded key
    assert share["unit"] == "%" and share["value"] == pytest.approx(100.0)
