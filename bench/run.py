#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its per-layer metrics are
found by name from ``BENCHMARK.json``.  A run:

1. opens the index through ``repro.api.open_index`` and loads the
   configuration's keys with plans of PUTs through ``Session.execute``;
2. warms up on the cell's own traffic until no program has been lowered
   for a run of plans, so every shape the window uses is compiled;
3. measures a closed loop for ``--seconds``: one client sends a plan,
   waits for its results, and sends the next;
4. reads the peak device memory, then power-fails the PM model, recovers
   and reads every key back through the program;
5. replays every op of the load, the warm-up and the window on a dict
   and compares every result, and the read-back, with it.

With ``--trace 1`` the first seconds of the window also record the
program's spans and a profiler trace, and the result line carries the
per-layer metrics in place of the end-to-end ones.  Without a TPU the run exits non-zero and
prints no result; ``--rehearsal-keys`` allows a CPU run at a tiny key
count, marked ``"rehearsal": true``, which is never a measurement.
``--fault`` plants one of ``bench/faults.py``'s faults.
"""

from __future__ import annotations

import time

T_START_NS = time.perf_counter_ns()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import faults, replay, spec, traffic, xplane  # noqa: E402
from bench.window import Window  # noqa: E402

QUIET_PLANS = 8       # warm-up ends after this many plans lowered nothing
TRACE_SECONDS = 5     # a traced run traces the start of its window
MAX_WARM_PLANS = 400
READ_BACK_OPS = 1 << 18  # keys per read-back plan
STALLS_SHOWN = 5
LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class Refused(RuntimeError):
    """The run cannot be measured here; no result is printed."""


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class CompileCounter:
    """Programs lowered so far (a compile, or a persistent-cache hit):
    each one is a shape the warm-up had not covered yet."""

    def __init__(self) -> None:
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, _secs: float, **_kw) -> None:
        if name == LOWERED:
            self.n += 1


class StallWatch:
    """What the host did during each plan of the window, to tell a slow
    plan's cause: collector pauses, the client thread's involuntary
    context switches (another thread or process took its core), and the
    process's page faults."""

    def __init__(self) -> None:
        self.gc_ns = 0
        self._gc_t0 = 0
        self.rows = []  # per plan: gc ns, involuntary switches, faults
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter_ns()
        else:
            self.gc_ns += time.perf_counter_ns() - self._gc_t0

    @staticmethod
    def _usage():
        t = resource.getrusage(resource.RUSAGE_THREAD)
        p = resource.getrusage(resource.RUSAGE_SELF)
        return t.ru_nivcsw, p.ru_minflt + p.ru_majflt

    def before(self) -> None:
        self._at = (self.gc_ns, *self._usage())

    def after(self) -> None:
        now = (self.gc_ns, *self._usage())
        self.rows.append([b - a for a, b in zip(self._at, now)])

    def report(self, lat_ms: np.ndarray) -> None:
        rows = np.array(self.rows, np.int64).reshape(-1, 3)
        gc.callbacks.remove(self._on_gc)
        say(f"host during the window: collector "
            f"{float(rows[:, 0].sum()) / 1e6!r} ms, "
            f"{int(rows[:, 1].sum())} involuntary switches of the "
            f"client thread, {int(rows[:, 2].sum())} page faults")
        for i in np.argsort(lat_ms)[::-1][:STALLS_SHOWN].tolist():
            say(f"slow plan {i}: {float(lat_ms[i])!r} ms, collector "
                f"{float(rows[i, 0]) / 1e6!r} ms, {int(rows[i, 1])} "
                f"involuntary switches, {int(rows[i, 2])} page faults")


def op_percentile(plan_ms: np.ndarray, q: float) -> float:
    """Nearest-rank percentile over every op: each op carries its plan's
    time, and every plan holds the same number of ops, so the ops'
    percentile is the plans'."""
    return float(np.percentile(plan_ms, 100 * q, method="inverted_cdf"))


class Runner:
    def __init__(self, args, cell: spec.Cell):
        from repro.api import Plan, open_index
        from repro.core.plan import OpKind
        self.args, self.cell = args, cell
        self.Plan = Plan
        self.kinds = np.array([OpKind.GET, OpKind.UPDATE, OpKind.PUT],
                              np.int32)
        cfg = cell.config
        self.n_keys = args.rehearsal_keys or int(cfg["keys"])
        self.plan_ops = int(cell.traffic["plan_ops"])
        self.compiles = CompileCounter()
        self.session = open_index(cfg["index"], shards=int(cfg["shards"]),
                                  mesh_reads=bool(cfg["mesh_reads"]))
        self.history = []  # (codes, keys, aux, encoded results), in order
        if args.fault in faults.FROM_SETUP:
            faults.FAULTS[args.fault](self.session)

    def execute(self, codes, keys, aux):
        plan = self.Plan.from_arrays(self.kinds[codes], keys, aux)
        return self.session.execute(plan)

    def load(self) -> None:
        seed = self.args.seed
        self.keys = traffic.key_set(self.n_keys, seed)
        vals = traffic.make_values(traffic.rng_for(seed, "values"),
                                   self.n_keys)
        for lo in range(0, self.n_keys, self.plan_ops):
            k, v = self.keys[lo:lo + self.plan_ops], vals[lo:lo + self.plan_ops]
            codes = np.full(k.shape, traffic.PUT, np.int8)
            res = self.execute(codes, k, v)
            self.history.append((codes, k, v,
                                 replay.encode_results(res.results)))

    def plan(self, gen: traffic.Traffic) -> None:
        codes, keys, aux = gen.next_plan()
        res = self.execute(codes, keys, aux)
        self.history.append((codes, keys, aux,
                             replay.encode_results(res.results)))

    def warm_up(self) -> int:
        gen = traffic.Traffic(self.cell.traffic, self.keys,
                              traffic.rng_for(self.args.seed, "warm-up"))
        quiet = plans = 0
        while quiet < QUIET_PLANS:
            if plans >= MAX_WARM_PLANS:
                raise Refused(f"warm-up still lowering programs after "
                              f"{plans} plans")
            before = self.compiles.n
            self.plan(gen)
            plans += 1
            quiet = quiet + 1 if self.compiles.n == before else 0
        return plans

    def window(self, seconds: float, trace_dir: Optional[str]) -> dict:
        """The closed loop: returns per-plan submit and return times.
        With ``trace_dir``, the plans that start in the first
        ``TRACE_SECONDS`` are traced: program spans, a profiler trace,
        and a mark around each execute; ``traced`` counts them."""
        import jax
        from repro import obs
        gen = traffic.Traffic(self.cell.traffic, self.keys,
                              traffic.rng_for(self.args.seed, "window"))
        first = len(self.history)
        starts, ends = [], []
        limit_ns, traced = seconds * 1e9, 0
        self.stalls = StallWatch()
        tracing = trace_dir is not None
        if tracing:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            obs.reset()
            obs.enable()
        while True:
            codes, keys, aux = gen.next_plan()
            plan = self.Plan.from_arrays(self.kinds[codes], keys, aux)
            if tracing and starts and \
                    time.perf_counter_ns() - starts[0] >= TRACE_SECONDS * 1e9:
                obs.disable()
                jax.profiler.stop_trace()
                tracing, traced = False, len(starts)
            ctx = (jax.profiler.TraceAnnotation(xplane.MARK) if tracing
                   else contextlib.nullcontext())
            self.stalls.before()
            t0 = time.perf_counter_ns()
            with ctx:
                res = self.session.execute(plan)
            t1 = time.perf_counter_ns()
            self.stalls.after()
            starts.append(t0)
            ends.append(t1)
            self.history.append((codes, keys, aux,
                                 replay.encode_results(res.results)))
            if t1 - starts[0] >= limit_ns:
                break
        if tracing:
            obs.disable()
            jax.profiler.stop_trace()
            traced = len(starts)
        return {"first": first, "starts": np.array(starts, np.int64),
                "ends": np.array(ends, np.int64), "traced": traced}

    def read_back(self) -> np.ndarray:
        """Power-fail the PM model, recover, and read every key back in
        plans of ``READ_BACK_OPS``: a smaller first plan falls under a
        stale P-Masstree's rebuild floor (a quarter of its entries, so
        2^18 up to 2^20 keys) and reads key by key, and a plan of 2^20
        P-CLHT keys needs gigabytes of device scratch.  Where recovery
        or a read raises, every key counts as lost."""
        k = self.keys
        step = READ_BACK_OPS
        try:
            self.session.crash()
            out = []
            for lo in range(0, k.size, step):
                part = k[lo:lo + step]
                res = self.execute(np.zeros(part.shape, np.int8), part,
                                   np.zeros_like(part))
                out.append(replay.encode_results(res.results))
            return np.concatenate(out)
        except Exception as e:  # the program failed to recover: report it
            say(f"read-back after powerfail raised {e!r}")
            return np.full(k.shape, replay.LOST, np.int64)


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def reduce_trace(path: str, w: Window, host_starts, host_ends) -> dict:
    """Fill ``w``'s trace fields; returns ``device`` extras and the
    breakdown."""
    trace = xplane.read_trace(path)
    offset = xplane.clock_offset(trace.marks, host_starts)
    w.trace = trace
    w.lo = float(host_starts[0] + offset)
    w.hi = float(host_ends[-1] + offset)
    from repro import obs
    spans = [(obs.RECORDER.epoch + s.ts + offset,
              obs.RECORDER.epoch + s.ts + s.dur + offset, s.name)
             for s in w.spans]
    spans += [(m.start, m.end, xplane.MARK) for m in trace.marks]
    timeline = xplane.innermost_timeline(spans)
    busy, idle, ops = [], {}, {}
    for d in w.devices:
        b = w.busy_ns(d)
        busy.append(b)
        say(f"device {d}: busy {b / 1e9!r} s of {w.trace_ns / 1e9!r} s, "
            f"idle share {100 * (1 - b / w.trace_ns)!r} %")
        gap = xplane.gaps(trace.ops.get(d, []), w.lo, w.hi)
        for k, v in xplane.attribute(gap, timeline).items():
            idle[k] = idle.get(k, 0.0) + v / len(w.devices)
        for k, v in xplane.op_times(trace, d, w.lo, w.hi).items():
            ops[k] = ops.get(k, 0.0) + v / len(w.devices)
    return {"device": {"busy_s": float(np.mean(busy)) / 1e9,
                       "window_s": w.trace_ns / 1e9},
            "breakdown": {"device_ops": xplane.top(ops),
                          "idle_gaps": xplane.top(idle)}}


def compare(history, loaded, back, ref: replay.Reference, first: int):
    """(wrong results anywhere, wrong in the window, lost after the
    powerfail), against a dict replay of every op."""
    wrong = wrong_window = 0
    for i, (codes, keys, aux, got) in enumerate(history):
        bad = int(np.count_nonzero(ref.apply(codes, keys, aux) != got))
        wrong += bad
        if i >= first:
            wrong_window += bad
    lost = int(np.count_nonzero(ref.lookup(loaded) != back))
    return wrong, wrong_window, lost


def run(args) -> dict:
    cell = spec.load_cell(args.workload)
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearsal_keys:
        if platform != "cpu":
            raise Refused("--rehearsal-keys is for the CPU backend only")
    elif platform != "tpu":
        raise Refused(f"no TPU: JAX found {platform!r}")
    if len(devices) < cell.chips:
        raise Refused(f"{cell.name} needs {cell.chips} chips, JAX found "
                      f"{len(devices)}")
    used = devices[:cell.chips]
    from repro import compile_cache, obs
    say(f"compile cache: {compile_cache.configure()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    r = Runner(args, cell)
    t_init = time.perf_counter_ns()
    r.load()
    t_load = time.perf_counter_ns()
    first_gen = traffic.Traffic(cell.traffic, r.keys,
                                traffic.rng_for(args.seed, "first"))
    r.plan(first_gen)  # first export and upload, compiles when cold
    t_first = time.perf_counter_ns()
    warm_plans = r.warm_up()
    t_warm = time.perf_counter_ns()
    if args.fault and args.fault not in faults.FROM_SETUP:
        faults.FAULTS[args.fault](r.session)
    gc.collect()
    gc.freeze()

    tracing = bool(args.trace)
    trace_dir = tempfile.TemporaryDirectory() if tracing else None
    compiles0 = r.compiles.n
    win = r.window(args.seconds, trace_dir.name if tracing else None)
    window_compiles = r.compiles.n - compiles0
    starts, ends = win["starts"], win["ends"]
    setup_s = float(starts[0] - T_START_NS) / 1e9
    window_ns = float(ends[-1] - starts[0])
    lat_ms = (ends - starts) / 1e6
    window_plans = r.history[win["first"]:]
    n_ops = sum(p[0].size for p in window_plans)
    say(f"setup split: init {(t_init - T_START_NS) / 1e9!r} s, load "
        f"{(t_load - t_init) / 1e9!r} s ({r.n_keys} keys), first export "
        f"and upload {(t_first - t_load) / 1e9!r} s, warm-up "
        f"{(t_warm - t_first) / 1e9!r} s ({warm_plans} plans), to first "
        f"measured plan {setup_s!r} s")
    say(f"window: {len(window_plans)} plans, {n_ops} ops in "
        f"{window_ns / 1e9!r} s; plan latency min {float(lat_ms.min())!r} ms, "
        f"median {float(np.median(lat_ms))!r} ms, max {float(lat_ms.max())!r} ms; "
        f"{window_compiles} programs lowered inside the window")
    r.stalls.report(lat_ms)

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak(used)}
    breakdown = None
    if tracing:
        n = win["traced"]
        w = Window(host_ns=float(ends[n - 1] - starts[0]),
                   spans=list(obs.RECORDER.spans),
                   compiles=window_compiles, config=cell.config,
                   keys=r.n_keys,
                   device_kind=devices[0].device_kind,
                   devices=[d.id for d in used])
        extra = reduce_trace(xplane.newest_xplane(trace_dir.name), w,
                             starts[:n], ends[:n])
        trace_dir.cleanup()
        device.update(extra["device"])
        breakdown = extra["breakdown"]
        metrics = {}
        for m in cell.per_layer:
            value = m.read(w)
            if value is not None:
                metrics[m.name] = {"value": float(value), "unit": m.unit}
        obs.reset()
    else:
        e2e = {"ops_per_s": n_ops / (window_ns / 1e9),
               "op_p95_ms": op_percentile(lat_ms, 0.95),
               "setup_s": setup_s}
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}

    t_back = time.perf_counter_ns()
    back = r.read_back()
    history, keys = r.history, r.keys
    del r
    gc.unfreeze()
    gc.collect()
    t_ref = time.perf_counter_ns()
    wrong, wrong_window, lost = compare(history, keys, back,
                                        replay.Reference(), win["first"])
    say(f"after the window: powerfail and read-back "
        f"{(t_ref - t_back) / 1e9!r} s, reference replay and compare "
        f"{(time.perf_counter_ns() - t_ref) / 1e9!r} s")
    checks = {"wrong_results": {"value": wrong, "limit": 0},
              "lost_after_powerfail": {"value": lost, "limit": 0}}
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": int(n_ops), "failed": wrong_window,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if args.rehearsal_keys:
        result["rehearsal"] = True
    result["checks"] = checks
    say(f"host peak resident "
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20!r} GiB")
    for name, c in checks.items():
        say(f"check {name}: {c['value']} (limit {c['limit']})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    ap.add_argument("--rehearsal-keys", type=int, default=0,
                    help="CPU rehearsal at this many keys; never a "
                         "measurement")
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except (Refused, spec.SpecError) as e:
        say(f"bench: {e}; no result")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
