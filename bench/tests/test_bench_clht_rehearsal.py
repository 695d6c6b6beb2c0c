"""P-CLHT cells, rehearsed end to end on the CPU: sound runs read
``correct`` true, and every fault the cells can have reads false."""

import pytest

from bench_rehearsal import rehearse


@pytest.mark.parametrize("workload", ["clht-ycsb-c", "clht-ycsb-a"])
def test_clht_cell_rehearsal_is_correct(workload):
    rc, result, err = rehearse(workload, "--trace", "1")
    assert rc == 0, err[-3000:]
    assert result["correct"] is True and result["rehearsal"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    assert {"device_idle_share", "window_compiles",
            "read_host_share"} <= set(result["metrics"])
    assert result["device"]["busy_s"] > 0


@pytest.mark.parametrize("workload,fault", [
    ("clht-ycsb-c", "alter_answer"), ("clht-ycsb-c", "half_batch"),
    ("clht-ycsb-c", "drop_flush"), ("clht-ycsb-a", "alter_answer"),
    ("clht-ycsb-a", "half_batch"), ("clht-ycsb-a", "drop_flush"),
    ("clht-ycsb-a", "stale_update")])
def test_clht_fault_reads_incorrect(workload, fault):
    rc, result, err = rehearse(workload, "--fault", fault)
    assert rc == 0, err[-3000:]
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())
