#!/usr/bin/env python3
"""Docs link-checker — the CI docs job.

Fails (exit 1) when:

* a relative markdown link in README.md, docs/, or a kernel package
  README resolves to a missing file;
* a ``kernels/<name>`` reference in the checked documents names
  neither a kernel package nor a module under src/repro/kernels/
  (dangling kernel-package references);
* one of the index/plan kernel packages (probe, clht_probe,
  art_probe, scan, partition, conflict) is missing its README.md;
* the top-level README.md, docs/ARCHITECTURE.md, docs/PMEM_MODEL.md,
  or docs/API.md is missing;
* docs/API.md stops documenting the public plan surface (the
  ``execute``/``Plan``/``Session``/``pipeline`` anchor terms) or
  loses the migration table from the pre-plan ``*_batch`` calls;
* docs/WORKLOADS.md stops documenting the adversarial-matrix surface
  (samplers, string-key encoding, deferral metric, crash sweep);
* docs/PMEM_MODEL.md stops documenting the fingerprint-lane /
  optimistic-read surface (fp64, pm_load_words, validation_points) or
  docs/ARCHITECTURE.md drops the kernel-table fp rows;
* docs/RECOVERY.md stops documenting the instant-recovery check (the
  powerfail-mid-plan method, the serving engine's recovery window) or
  docs/ARCHITECTURE.md drops the pipelined-tick section.
"""

from __future__ import annotations

import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
KERNELS = ROOT / "src" / "repro" / "kernels"
README_REQUIRED = ("probe", "clht_probe", "art_probe", "scan", "partition",
                   "conflict")
TOP_DOCS_REQUIRED = ("README.md", "docs/ARCHITECTURE.md",
                     "docs/PMEM_MODEL.md", "docs/API.md",
                     "docs/OBSERVABILITY.md", "docs/SHARDING.md",
                     "docs/WORKLOADS.md", "docs/RECOVERY.md")
# the public-surface anchors docs/API.md must keep documenting
API_DOC_ANCHORS = ("execute", "Plan", "Session", "pipeline",
                   "open_index", "lookup_batch", "scan_batch",
                   "write_batch")
# the telemetry surface docs/OBSERVABILITY.md must keep documenting
OBS_DOC_ANCHORS = ("obs.span", "plan.wave", "pmem.group_commit",
                   "recovery.time_to_first_served", "MetricsRegistry",
                   "Histogram", "--trace", "pipeline_depth",
                   "admit_queue_depth", "async_export_backlog",
                   "pipeline.coalesce")
# the recovery surface docs/RECOVERY.md must keep documenting
RECOVERY_DOC_ANCHORS = ("plan_prefix_states", "PMSnapshot",
                        "group_commit_boundaries", "force_kernel",
                        "AsyncExporter", "crash_and_recover",
                        "recovery.time_to_first_served",
                        "test_recovery.py")
# the scale-out surface docs/SHARDING.md must keep documenting
SHARDING_DOC_ANCHORS = ("ShardedIndex", "split_by_shard", "StreamDriver",
                        "crash_shard", "recover_shard", "mesh_lookup",
                        "shard.plan", "mesh_reads",
                        "masstree-4shard-ycsb-c")
# the adversarial-matrix surface docs/WORKLOADS.md must keep documenting
WORKLOADS_DOC_ANCHORS = ("zipf_ranks", "hotset_ranks", "encode_str",
                         "string_keys", "matrix_workload", "replay",
                         "deferred_plans", "prefix@55", "clwb_per_op",
                         "plan_crash_sweep", "test_matrix.py")
# the probe/persistence surface docs/PMEM_MODEL.md must keep documenting
PMEM_DOC_ANCHORS = ("fp64", "fp_partial", "FP_EMPTY", "pm_load_words",
                    "fp_false_positives", "optimistic_retries",
                    "write_version_", "validation_points",
                    "group_commit", "arm_crash")
# the kernel map docs/ARCHITECTURE.md must keep documenting
ARCH_DOC_ANCHORS = ("fingerprint lane", "probe64_fp", "leaf_fp",
                    "_optimistic_lookup", "_write_batch",
                    "_shard_refine", "PlanPipeline", "AsyncExporter",
                    "submit_if_stale", "pipelined=True")

LINK_RE = re.compile(r"\[[^\]]*\]\(([^)]+)\)")
KERNEL_REF_RE = re.compile(r"\bkernels/([A-Za-z0-9_]+)")


def doc_files():
    docs = [ROOT / "README.md"]
    docs += sorted((ROOT / "docs").glob("**/*.md"))
    docs += sorted(KERNELS.glob("*/README.md"))
    return [p for p in docs if p.exists()]


def check_file(path: pathlib.Path, kernel_pkgs: set) -> list:
    errors = []
    text = path.read_text()
    rel = path.relative_to(ROOT)
    for m in LINK_RE.finditer(text):
        target = m.group(1).strip()
        if target.startswith(("http://", "https://", "mailto:", "#")):
            continue
        target = target.split("#")[0]
        if not target:
            continue
        if not (path.parent / target).resolve().exists():
            errors.append(f"{rel}: dangling link -> {m.group(1)}")
    for m in KERNEL_REF_RE.finditer(text):
        if (m.group(1) not in kernel_pkgs
                and not (KERNELS / f"{m.group(1)}.py").exists()):
            errors.append(f"{rel}: dangling kernel-package reference -> "
                          f"kernels/{m.group(1)}")
    return errors


def main() -> int:
    kernel_pkgs = {p.name for p in KERNELS.iterdir() if p.is_dir()}
    errors = []
    files = doc_files()
    for rel in TOP_DOCS_REQUIRED:
        if not (ROOT / rel).exists():
            errors.append(f"{rel} is missing")
    for name in README_REQUIRED:
        if not (KERNELS / name / "README.md").exists():
            errors.append(f"src/repro/kernels/{name}/README.md is missing")
    api_doc = ROOT / "docs" / "API.md"
    if api_doc.exists():
        api_text = api_doc.read_text()
        for anchor in API_DOC_ANCHORS:
            if anchor not in api_text:
                errors.append(f"docs/API.md no longer documents "
                              f"{anchor!r} (public-surface drift)")
    obs_doc = ROOT / "docs" / "OBSERVABILITY.md"
    if obs_doc.exists():
        obs_text = obs_doc.read_text()
        for anchor in OBS_DOC_ANCHORS:
            if anchor not in obs_text:
                errors.append(f"docs/OBSERVABILITY.md no longer documents "
                              f"{anchor!r} (telemetry-surface drift)")
    shard_doc = ROOT / "docs" / "SHARDING.md"
    if shard_doc.exists():
        shard_text = shard_doc.read_text()
        for anchor in SHARDING_DOC_ANCHORS:
            if anchor not in shard_text:
                errors.append(f"docs/SHARDING.md no longer documents "
                              f"{anchor!r} (scale-out-surface drift)")
    wl_doc = ROOT / "docs" / "WORKLOADS.md"
    if wl_doc.exists():
        wl_text = wl_doc.read_text()
        for anchor in WORKLOADS_DOC_ANCHORS:
            if anchor not in wl_text:
                errors.append(f"docs/WORKLOADS.md no longer documents "
                              f"{anchor!r} (matrix-surface drift)")
    rec_doc = ROOT / "docs" / "RECOVERY.md"
    if rec_doc.exists():
        rec_text = rec_doc.read_text()
        for anchor in RECOVERY_DOC_ANCHORS:
            if anchor not in rec_text:
                errors.append(f"docs/RECOVERY.md no longer documents "
                              f"{anchor!r} (recovery-SLO drift)")
    pmem_doc = ROOT / "docs" / "PMEM_MODEL.md"
    if pmem_doc.exists():
        pmem_text = pmem_doc.read_text()
        for anchor in PMEM_DOC_ANCHORS:
            if anchor not in pmem_text:
                errors.append(f"docs/PMEM_MODEL.md no longer documents "
                              f"{anchor!r} (probe-surface drift)")
    arch_doc = ROOT / "docs" / "ARCHITECTURE.md"
    if arch_doc.exists():
        arch_text = arch_doc.read_text()
        for anchor in ARCH_DOC_ANCHORS:
            if anchor not in arch_text:
                errors.append(f"docs/ARCHITECTURE.md no longer documents "
                              f"{anchor!r} (kernel-map drift)")
    for path in files:
        errors.extend(check_file(path, kernel_pkgs))
    for e in errors:
        print(f"FAIL {e}")
    print(f"checked {len(files)} docs, {len(errors)} problem(s)")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
