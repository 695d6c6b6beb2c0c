"""The ``delta_export_share`` reader, on hand-made windows, and in a
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
    return spec._load_reader("delta_export_share")(window(spans))


def test_delta_export_share_is_deltas_over_all_exports():
    spans = [S("plan.wave", 1, 1, attrs={"kind": "read", "exports": 3,
                                         "delta_exports": 2}),
             S("plan.wave", 1, 2, attrs={"kind": "read", "exports": 1,
                                         "delta_exports": 1}),
             S("plan.wave", 1, 3, attrs={"kind": "write", "exports": 0,
                                         "delta_exports": 0})]
    assert read(spans) == pytest.approx(75.0)


@pytest.mark.parametrize("attrs", [
    {"kind": "read", "exports": 0, "delta_exports": 0},
    {"kind": "read", "exports": 2}],
    ids=["no_export_in_the_window", "program_without_the_counter"])
def test_delta_export_share_is_left_out_with_nothing_to_read(attrs):
    assert read([S("plan.wave", 1, 1, attrs=attrs)]) is None


def test_a_traced_clht_ycsb_a_rehearsal_reads_delta_export_share():
    rc, result, err = rehearse("clht-ycsb-a", "--trace", "1",
                               seed=3000000017)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True
    # at the rehearsal's table size a plan's writes pass the delta's row
    # cut, so the share may read 0 here; it has to be read all the same
    assert 0.0 <= result["metrics"]["delta_export_share"]["value"] <= 100.0
