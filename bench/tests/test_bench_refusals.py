"""The benchmark refuses to measure where it cannot: no TPU, an unknown
cell, or a checkout that holds only the benchmark's own files."""

import os
import shutil

from bench_rehearsal import ROOT, rehearse


def test_refuses_without_a_tpu():
    rc, result, err = rehearse("clht-ycsb-c", rehearsal=False)
    assert rc != 0 and result is None
    assert "no TPU" in err


def test_refuses_an_unknown_workload():
    rc, result, err = rehearse("no-such-cell")
    assert rc != 0 and result is None


def test_refuses_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    rc, result, _ = rehearse("clht-ycsb-c", cwd=str(tmp_path),
                             run=str(tmp_path / "bench" / "run.py"))
    assert rc != 0 and result is None
