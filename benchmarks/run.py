"""Benchmark harness — one section per paper table/figure.

  ycsb            Fig 4a (ordered), Fig 5 (unordered), §7.3 (WOART)
  matrix          adversarial workload matrix: Zipfian skew, hot-set
                  contention, string keys, sharded writes
                  (docs/WORKLOADS.md)
  counters        Table 4 / Fig 4c-d (clwb, fence, lines-touched)
  crash_recovery  §7.5 (targeted crash states; bug re-finding)
  chaos           instant-recovery SLOs: powerfail mid-plan, time to
                  first served request vs a DRAM-rebuild baseline
                  (docs/RECOVERY.md)
  loc_report      Table 1 (conversion effort)
  roofline_report framework §Roofline tables from the dry-run

``--only`` takes a comma-separated subset of section names.

Prints a ``name,value,derived`` CSV summary at the end.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from . import (chaos, counters, crash_recovery, loc_report, matrix,
               roofline_report, ycsb)


def _git_commit():
    """Current commit hash, or None outside a git checkout — used to
    keep the --json trajectory at one row per commit."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)))
    except OSError:
        return None
    return out.stdout.strip() if out.returncode == 0 else None


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller workloads (CI-speed)")
    ap.add_argument("--only", default=None,
                    help="run only these sections (comma-separated)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the summary rows as JSON "
                         "(BENCH_ycsb.json-style), accumulating the "
                         "perf trajectory across runs")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record the whole run with the obs tracer and "
                         "write a Chrome-trace JSON to PATH")
    ap.add_argument("--shards", type=int, default=8,
                    help="max shard count of the ycsb shard-scaling "
                         "sweep (0 or 1 disables it)")
    ap.add_argument("--streams", type=int, default=4,
                    help="client streams driving the sharded sweep")
    args = ap.parse_args()
    from repro import compile_cache
    compile_cache.configure()
    if args.trace:
        from repro import obs
        obs.reset()
        obs.enable()
    if args.json:
        # fail fast, not after minutes of benchmarking
        parent = os.path.dirname(os.path.abspath(args.json))
        os.makedirs(parent, exist_ok=True)
        with open(args.json, "a"):
            pass
    # full size chosen so the whole harness completes in ~10 min on
    # one CPU (the paper ran 64M keys on a 96-core Optane box; our
    # claims are relative orderings — see EXPERIMENTS.md)
    n_load = 4000 if args.quick else 10000
    n_run = 4000 if args.quick else 10000
    sections = {
        "ycsb": lambda: ycsb.run(n_load, n_run, shards=args.shards,
                                 streams=args.streams),
        "matrix": lambda: matrix.run(
            2000 if args.quick else 4000,
            2000 if args.quick else 4000,
            shards=args.shards, streams=args.streams),
        "counters": lambda: counters.run(
            n_load=2000 if args.quick else 5000,
            n_measure=500 if args.quick else 2000),
        "crash_recovery": lambda: crash_recovery.run(
            n_keys=40 if args.quick else 60,
            max_states=1000 if args.quick else 3000),
        "chaos": lambda: chaos.run(n_run, crash_samples=3),
        "loc_report": loc_report.run,
        "roofline_report": roofline_report.run,
    }
    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - set(sections)
        assert not unknown, f"unknown --only sections: {sorted(unknown)}"
    all_rows = []
    for name, fn in sections.items():
        if only is not None and name not in only:
            continue
        print(f"\n=== {name} " + "=" * (68 - len(name)))
        t0 = time.perf_counter()
        rows = fn() or []
        dt = time.perf_counter() - t0
        all_rows.extend(rows)
        print(f"--- {name} done in {dt:.1f}s")
    if args.trace:
        from repro import obs
        obs.disable()
        obs.write_trace(args.trace)
        errs = obs.validate_trace_file(args.trace)
        if errs:
            for e in errs:
                print(f"FAIL {e}")
            sys.exit(1)
        print(f"wrote trace to {args.trace} "
              f"({len(obs.spans())} spans, schema valid)")
    print("\nname,value,derived")
    flat = []
    for name, payload in all_rows:
        if isinstance(payload, dict):
            for k, v in payload.items():
                print(f"{name}.{k},{v},")
                flat.append({"name": f"{name}.{k}", "value": v})
        else:
            print(f"{name},{payload},")
            flat.append({"name": name, "value": payload})
    if args.json:
        # scheduler-quality summary: total plan waves and the
        # op-weighted mean wave width across every ycsb_mixed_plan row,
        # so BENCH_ycsb.json tracks conflict-wave scheduling over time
        wave_rows = [r for r in flat if "_waves" in r["name"]
                     and r["name"].startswith("ycsb_mixed_plan/")]
        width_rows = {r["name"].replace("_mean_wave_width", "_waves"):
                      r["value"] for r in flat
                      if r["name"].endswith("_mean_wave_width")}
        total_waves = sum(r["value"] for r in wave_rows)
        total_wave_ops = sum(r["value"] * width_rows.get(r["name"], 0)
                             for r in wave_rows)
        # top-level per-op latency columns, lifted from the merged
        # ycsb_latency/all row (0.0 when ycsb didn't run this pass)
        lat = {r["name"].split(".", 1)[1]: r["value"] for r in flat
               if r["name"].startswith("ycsb_latency/all.")}
        # shard-scaling headline: the modeled-makespan ratio of the
        # max-shard column over the 1-shard column (one per target)
        scaling = {r["name"].split("/", 1)[1].split(".", 1)[0]: r["value"]
                   for r in flat if r["name"].startswith("ycsb_sharded/")
                   and "_scaling_" in r["name"]}
        # instant-recovery headline: median speedup over the DRAM-
        # rebuild baseline across the recovery/* rows (0.0 without the
        # chaos section)
        rec = sorted(r["value"] for r in flat
                     if r["name"].startswith("recovery/")
                     and r["name"].endswith(".instant_recovery_speedup"))
        record = {
            "generated_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "commit": _git_commit(),
            "quick": bool(args.quick),
            "n_load": n_load,
            "n_run": n_run,
            "shards": args.shards,
            "streams": args.streams,
            "sharded_scaling": scaling,
            "recovery_speedup_median": rec[len(rec) // 2] if rec else 0.0,
            "plan_waves_total": total_waves,
            "plan_mean_wave_width": (total_wave_ops / total_waves
                                     if total_waves else 0.0),
            "lat_p50_us": lat.get("lat_p50_us", 0.0),
            "lat_p99_us": lat.get("lat_p99_us", 0.0),
            "rows": flat,
        }
        # accumulate: the file holds a list of run records (trajectory)
        history = []
        if os.path.getsize(args.json):
            try:
                with open(args.json) as f:
                    prev = json.load(f)
                history = prev if isinstance(prev, list) else [prev]
            except ValueError:
                print(f"warning: {args.json} held invalid JSON; restarting "
                      "the trajectory")
        # one trajectory row per (commit, shards, streams): a re-run
        # (or a partial --only run) replaces its own entry instead of
        # appending a duplicate, and sharded sweeps at different
        # geometries dedup independently exactly like single-stream rows
        if record["commit"] is not None:
            key = (record["commit"], record["shards"], record["streams"])
            dropped = len(history)
            history = [r for r in history
                       if (r.get("commit"), r.get("shards"),
                           r.get("streams")) != key]
            dropped -= len(history)
            if dropped:
                print(f"replacing {dropped} earlier run(s) of commit "
                      f"{record['commit'][:12]}")
        history.append(record)
        with open(args.json, "w") as f:
            json.dump(history, f, indent=1)
        print(f"wrote {len(flat)} rows to {args.json} "
              f"(run {len(history)} in trajectory)")


if __name__ == "__main__":
    main()
