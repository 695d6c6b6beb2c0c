#!/usr/bin/env python3
"""Smoke run of the batched index read path on the chip.

    python3 chip_smoke.py [--seed N]     # one TPU: P-CLHT, P-Masstree, P-ART
    python3 chip_smoke.py --chips 4      # four TPUs: the sharded mesh reads
    JAX_PLATFORMS=cpu python3 chip_smoke.py --keys-scale 0.004
                                         # CPU rehearsal at a tiny size

Each phase opens an index through ``repro.api.open_index``, loads it with
pipelined PUTs (``Session.pipeline``), and reads it back with plans of
batched GETs (and SCANs for P-Masstree) through ``Session.execute``.
Every answer is checked against a plain oracle (a dict, or a sorted
array for scans).  The kernel spans of each phase must cover every
query, so no read was served by the scalar path.  P-CLHT also
power-fails mid-plan, recovers, and reads back every acknowledged key.

The key counts are cut from the paper's 64M keys to what a few minutes
of host-side loading allows: the load path is per-operation Python.

The printed times are smoke readings, not benchmark numbers.  The last
line of standard output is one JSON object, printed only when every
phase passed: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
Without a TPU the script exits non-zero, except in the CPU rehearsal
(``--keys-scale`` below 1), whose result line says so.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro import compile_cache, obs  # noqa: E402
from repro.api import Plan, open_index  # noqa: E402
from repro.core import CrashPoint  # noqa: E402
from repro.kernels import backend  # noqa: E402

PAPER_KEYS = 64 << 20  # the paper's 64M 8-byte keys
BATCH = 4096           # GETs per plan
N_BATCHES = 8
N_SCANS = 1024         # YCSB-E: uniform scan length 1..100
MAX_SCAN = 100
KEY_MASK = (1 << 62) - 1


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(line: str) -> None:
    print(line, flush=True)


def make_keys(rng, n: int) -> np.ndarray:
    """``n`` distinct keys in [1, 2^62), in random order."""
    keys = np.unique(rng.integers(1, 1 << 62, size=n + n // 8 + 64))
    check(keys.size >= n, "too few distinct keys drawn")
    return rng.permutation(keys)[:n].astype(np.int64)


def value_of(keys: np.ndarray) -> np.ndarray:
    """The value stored under each key: nonzero, below 2^62."""
    return ((keys * np.int64(0x9E3779B1)) & KEY_MASK) | 1


def absent_keys(rng, present: np.ndarray, n: int) -> np.ndarray:
    cand = np.setdiff1d(rng.integers(1, 1 << 62, size=2 * n + 64), present)
    check(cand.size >= n, "too few absent keys drawn")
    return rng.permutation(cand)[:n].astype(np.int64)


def load(session, keys: np.ndarray, vals: np.ndarray) -> float:
    """Pipelined PUTs, one plan per ``BATCH`` ops; every PUT must be
    acknowledged.  Returns the host seconds."""
    t0 = time.perf_counter()
    for lo in range(0, keys.size, BATCH):
        with session.pipeline(depth=BATCH) as p:
            acks = [p.put(k, v) for k, v in zip(keys[lo:lo + BATCH].tolist(),
                                                vals[lo:lo + BATCH].tolist())]
        check(all(h.value for h in acks), f"unacknowledged PUT near {lo}")
    return time.perf_counter() - t0


def get_plan(keys: np.ndarray) -> Plan:
    plan = Plan()
    for k in keys.tolist():
        plan.get(k)
    return plan


def expect_gets(results, keys: np.ndarray, oracle: dict, what: str) -> None:
    want = [oracle.get(k) for k in keys.tolist()]
    bad = sum(r != w for r, w in zip(results, want))
    check(len(results) == len(want) and bad == 0,
          f"{what}: {bad} of {len(want)} GETs disagree with the oracle")


def timed_execute(session, plan: Plan):
    """Host seconds of one plan; results come back as host values, so
    the device work is complete when this returns."""
    t0 = time.perf_counter()
    res = session.execute(plan)
    return res, time.perf_counter() - t0


def kernel_queries(span_name: str) -> tuple:
    """(span count, queries the spans covered) since the last reset."""
    spans = obs.RECORDER.find(span_name)
    return len(spans), sum(int(s.attrs.get("batch", 0)) for s in spans)


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported by this backend" if peak is None else str(peak)


def read_batches(session, rng, keys, oracle, span: str, name: str) -> None:
    """``N_BATCHES`` plans of ``BATCH`` GETs, half present and half
    absent in a seeded mix; all of them through the kernel span."""
    absent = absent_keys(rng, keys, N_BATCHES * BATCH // 2)
    obs.reset()
    obs.enable()
    times = []
    for b in range(N_BATCHES):
        half = BATCH // 2
        q = np.concatenate([rng.choice(keys, half, replace=False),
                            absent[b * half:(b + 1) * half]])
        q = rng.permutation(q)
        res, dt = timed_execute(session, get_plan(q))
        expect_gets(res.results, q, oracle, f"{name} GET batch {b}")
        times.append(dt)
    obs.disable()
    n_spans, covered = kernel_queries(span)
    say(f"{name} get: first-call batch {times[0]!r} s, warm batches "
        f"{times[1:]!r} s ({BATCH} GETs each)")
    say(f"{name} get: {n_spans} {span} spans covering {covered} of "
        f"{N_BATCHES * BATCH} queries")
    check(covered == N_BATCHES * BATCH,
          f"{name}: {N_BATCHES * BATCH - covered} GETs bypassed {span}")


def phase_clht(rng, n: int, device) -> None:
    name = "P-CLHT"
    s = open_index("clht")
    keys = make_keys(rng, n)
    vals = value_of(keys)
    say(f"{name} load: {n} keys in {load(s, keys, vals)!r} s")
    oracle = dict(zip(keys.tolist(), vals.tolist()))
    read_batches(s, rng, keys, oracle, "kernel.clht_probe", name)
    snap = s.index.snapshot()
    say(f"{name} snapshot: {snap.arrays[0].shape[0]} bucket rows, longest "
        f"overflow chain {snap.cache['clht_probe'][3]} buckets")

    # powerfail inside a write plan, then RECIPE recovery
    extra = absent_keys(rng, keys, BATCH)
    plan = Plan()
    for k, v in zip(extra.tolist(), value_of(extra).tolist()):
        plan.put(k, v)
    s.pmem.arm_crash(after_stores=BATCH)
    try:
        s.execute(plan)
        crashed = False
    except CrashPoint:
        crashed = True
    s.pmem.disarm_crash()
    check(crashed, f"{name}: the armed powerfail never fired")
    s.crash()  # powerfail + recover
    say(f"{name} powerfail: crashed mid-plan after {BATCH} stores, "
        f"recovered")

    # every acknowledged key reads back; the un-acked plan is all-or-
    # nothing per key (absent, or its new value), never torn
    obs.reset()
    obs.enable()
    t0 = time.perf_counter()
    for lo in range(0, n, BATCH):
        q = keys[lo:lo + BATCH]
        res = s.execute(get_plan(q))
        expect_gets(res.results, q, oracle, f"{name} read-back near {lo}")
    res = s.execute(get_plan(extra))
    for got, want in zip(res.results, value_of(extra).tolist()):
        check(got in (None, want), f"{name}: torn un-acked PUT {got!r}")
    obs.disable()
    n_spans, covered = kernel_queries("kernel.clht_probe")
    say(f"{name} read-back: all {n} acknowledged keys read back in "
        f"{time.perf_counter() - t0!r} s; {n_spans} kernel.clht_probe "
        f"spans covering {covered} queries")
    check(covered == n + extra.size,
          f"{name}: read-back GETs bypassed kernel.clht_probe")
    say(f"{name} peak_bytes_in_use: {peak_bytes(device)}")


def scan_oracle(sorted_keys, sorted_vals, start: int, count: int) -> list:
    i = int(np.searchsorted(sorted_keys, start, side="left"))
    return list(zip(sorted_keys[i:i + count].tolist(),
                    sorted_vals[i:i + count].tolist()))


def phase_masstree(rng, n: int, device) -> None:
    name = "P-Masstree"
    s = open_index("masstree")
    keys = make_keys(rng, n)
    vals = value_of(keys)
    say(f"{name} load: {n} keys in {load(s, keys, vals)!r} s")
    oracle = dict(zip(keys.tolist(), vals.tolist()))
    read_batches(s, rng, keys, oracle, "kernel.scan", name)

    order = np.argsort(keys)
    sk, sv = keys[order], vals[order]
    # YCSB-E: start keys drawn from the loaded keys, half of them
    # shifted off a key so the lower bound is exercised too
    starts = rng.choice(keys, N_SCANS, replace=False)
    starts[::2] = np.minimum(starts[::2] + 1, KEY_MASK)
    counts = rng.integers(1, MAX_SCAN + 1, size=N_SCANS)
    plan = Plan()
    for st, c in zip(starts.tolist(), counts.tolist()):
        plan.scan(st, c)
    obs.reset()
    obs.enable()
    res, dt = timed_execute(s, plan)
    obs.disable()
    bad = sum(got != scan_oracle(sk, sv, st, c) for got, st, c in
              zip(res.results, starts.tolist(), counts.tolist()))
    check(bad == 0, f"{name}: {bad} of {N_SCANS} SCANs disagree")
    n_spans, covered = kernel_queries("kernel.scan")
    say(f"{name} scan: {N_SCANS} SCANs (length 1-{MAX_SCAN}) in {dt!r} s, "
        f"{res.scanned} rows; {n_spans} kernel.scan spans covering "
        f"{covered} scans")
    check(covered == N_SCANS, f"{name}: SCANs bypassed kernel.scan")
    say(f"{name} peak_bytes_in_use: {peak_bytes(device)}")


def phase_art(rng, n: int, device) -> None:
    name = "P-ART"
    s = open_index("art")
    keys = make_keys(rng, n)
    vals = value_of(keys)
    say(f"{name} load: {n} keys in {load(s, keys, vals)!r} s")
    oracle = dict(zip(keys.tolist(), vals.tolist()))
    read_batches(s, rng, keys, oracle, "kernel.art_probe", name)
    nodes = s.index.snapshot().arrays["children"].shape[0]
    say(f"{name} snapshot: {nodes} node pages")
    say(f"{name} peak_bytes_in_use: {peak_bytes(device)}")


def phase_mesh(rng, n: int, n_chips: int) -> None:
    """The sharded P-Masstree's fused all-GET read across the chips,
    against the per-shard path and the oracle."""
    import jax
    from repro.distributed import mesh
    name = f"P-Masstree x{n_chips} shards"
    s = open_index("masstree", shards=n_chips, mesh_reads=True)
    keys = make_keys(rng, n)
    vals = value_of(keys)
    say(f"{name} load: {n} keys in {load(s, keys, vals)!r} s")
    oracle = dict(zip(keys.tolist(), vals.tolist()))
    absent = absent_keys(rng, keys, N_BATCHES * BATCH // 2)
    obs.reset()
    obs.enable()
    times = []
    for b in range(N_BATCHES):
        half = BATCH // 2
        q = rng.permutation(np.concatenate(
            [rng.choice(keys, half, replace=False),
             absent[b * half:(b + 1) * half]]))
        plan = get_plan(q)
        res, dt = timed_execute(s, plan)
        check(res.mesh, f"{name}: batch {b} did not take the mesh path")
        per_shard = s.index.execute(plan, mesh=False)
        check(not per_shard.mesh and per_shard.results == res.results,
              f"{name}: mesh and per-shard results differ in batch {b}")
        expect_gets(res.results, q, oracle, f"{name} GET batch {b}")
        times.append(dt)
    obs.disable()
    spans = obs.RECORDER.find("shard.mesh_lookup")
    where = {sp.attrs.get("placement") for sp in spans}
    devices = s.index._mesh_cache[1].khi.sharding.device_set
    say(f"{name} get: first-call batch {times[0]!r} s, warm batches "
        f"{times[1:]!r} s ({BATCH} GETs each)")
    say(f"{name} get: {len(spans)} shard.mesh_lookup spans, placement "
        f"{sorted(where)}, stacked runs on {len(devices)} devices")
    check(len(spans) == N_BATCHES and where == {"devices"},
          f"{name}: mesh reads were not placed one shard per device")
    check(len(devices) == n_chips and devices <= set(jax.devices()),
          f"{name}: stacked runs span {len(devices)} devices")
    for d in sorted(devices, key=lambda d: d.id):
        say(f"{name} device {d.id} peak_bytes_in_use: {peak_bytes(d)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded mesh read phase")
    ap.add_argument("--keys-scale", type=float, default=1.0,
                    help="below 1: CPU rehearsal at a fraction of the "
                         "key counts")
    args = ap.parse_args(argv)
    rehearsal = args.keys_scale < 1.0

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not rehearsal:
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); "
              f"refusing to run", file=sys.stderr)
        return 2
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, JAX found "
          f"{len(devices)}")
    cache = compile_cache.configure()
    decision = backend.mode()
    say(f"device_kind: {dev.device_kind}")
    say(f"device_count: {len(devices)}")
    say(f"kernel mode: {decision}")
    say(f"compile cache: {cache}")
    if not rehearsal:
        check(decision == "compiled",
              f"kernels would run {decision} on {dev.platform}")

    def keys_for(log2: int) -> int:
        n = max(2 * BATCH, int((1 << log2) * args.keys_scale))
        say(f"key count: {n} (2^{log2} x {args.keys_scale!r}; the paper "
            f"loads {PAPER_KEYS}, cut {PAPER_KEYS / n:.0f}x for host "
            f"load time)")
        return n

    rng = np.random.default_rng(args.seed)
    if args.chips == 4:
        phase_mesh(rng, keys_for(18), 4)
    else:
        phase_clht(rng, keys_for(20), dev)
        phase_masstree(rng, keys_for(18), dev)
        phase_art(rng, keys_for(18), dev)
    result = {"ok": True, "device": {"platform": dev.platform,
                                     "kind": dev.device_kind,
                                     "count": len(devices)}}
    if rehearsal:
        result["rehearsal_keys_scale"] = args.keys_scale
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
