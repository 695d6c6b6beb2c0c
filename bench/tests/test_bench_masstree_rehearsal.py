"""The P-Masstree cell, rehearsed end to end on the CPU: a sound run reads
``correct`` true, and every fault the cell can have reads false."""

import pytest

from bench_rehearsal import rehearse


def test_masstree_cell_rehearsal_is_correct():
    rc, result, err = rehearse("masstree-ycsb-c", "--trace", "1")
    assert rc == 0, err[-3000:]
    assert result["correct"] is True and result["rehearsal"] is True
    assert result["device"]["count"] == 1
    assert {"device_idle_share", "window_compiles",
            "read_host_share"} <= set(result["metrics"])


@pytest.mark.parametrize("fault", ["alter_answer", "half_batch",
                                   "drop_flush"])
def test_masstree_fault_reads_incorrect(fault):
    rc, result, err = rehearse("masstree-ycsb-c", "--fault", fault)
    assert rc == 0, err[-3000:]
    assert result["correct"] is False
