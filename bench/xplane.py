"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time.

What is read, per device:

* device operations: on a TPU, the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane; on the CPU backend (tests only), the XLA
  worker threads of ``/host:CPU``;
* whole programs: the ``XLA Modules`` line of a TPU plane (the CPU
  backend has none);
* the harness's own marks: host events named ``MARK``, one around each
  ``Session.execute``, which anchor the host clock to the trace's.

Busy time is the union of the operation intervals inside the window;
idle time is the rest.  Idle gaps are attributed to what the host was
doing in them: the innermost program span open there, mapped onto the
trace's clock through the marks.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

MARK = "bench.execute"
CLIENT = "bench.client"  # host time outside every execute
_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_CPU_WORKERS = ("tf_XLAPjRtCpuClient", "tf_XLAEigen")
_CPU_BOOKKEEPING = ("ThreadpoolListener", "ThunkExecutor", "end: ")

Interval = Tuple[float, float]


@dataclasses.dataclass
class Event:
    start: float  # ns, trace clock
    end: float
    name: str


@dataclasses.dataclass
class DeviceTrace:
    ops: Dict[int, List[Event]]
    programs: Dict[int, List[Event]]
    marks: List[Event]


def newest_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _events(line) -> Iterable[Event]:
    for e in line.events:
        yield Event(float(e.start_ns), float(e.start_ns + e.duration_ns),
                    e.name)


def read_trace(path: str) -> DeviceTrace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = collections.defaultdict(list)
    programs: Dict[int, List[Event]] = collections.defaultdict(list)
    marks: List[Event] = []
    tpu_seen = False
    cpu_ops: List[Event] = []
    for plane in data.planes:
        m = _TPU_PLANE.match(plane.name)
        if m:
            tpu_seen = True
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[dev].extend(_events(line))
                elif line.name == "XLA Modules":
                    programs[dev].extend(_events(line))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                worker = line.name.startswith(_CPU_WORKERS)
                for ev in _events(line):
                    if ev.name == MARK:
                        marks.append(ev)
                    elif (worker and ev.end > ev.start
                          and not ev.name.startswith(_CPU_BOOKKEEPING)):
                        cpu_ops.append(ev)
    if not tpu_seen:
        ops[0] = cpu_ops
    for table in (ops, programs):
        for evs in table.values():
            evs.sort(key=lambda e: e.start)
    marks.sort(key=lambda e: e.start)
    return DeviceTrace(dict(ops), dict(programs), marks)


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    return sum(e - s for s, e in
               clip(merge((ev.start, ev.end) for ev in events), lo, hi))


def gaps(events: Sequence[Event], lo: float, hi: float) -> List[Interval]:
    out, cur = [], lo
    for s, e in clip(merge((ev.start, ev.end) for ev in events), lo, hi):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def clock_offset(marks: Sequence[Event], host_starts: Sequence[int]
                 ) -> float:
    """Trace ns minus host ``perf_counter_ns`` at the same instant: the
    median over the marks, matched to the host's execute starts in
    order (both lists hold one entry per traced execute)."""
    if not marks or len(marks) != len(host_starts):
        raise ValueError(f"{len(marks)} trace marks for "
                         f"{len(host_starts)} traced executes")
    diffs = sorted(m.start - h for m, h in zip(marks, host_starts))
    return diffs[len(diffs) // 2]


def innermost_timeline(spans: Sequence[Tuple[float, float, str]],
                       outside: str = CLIENT
                       ) -> List[Tuple[float, str]]:
    """Change points ``(t, label)`` of the innermost open span, for
    properly nested spans ``(start, end, name)``; ``outside`` where none
    is open."""
    points: List[Tuple[float, str]] = []
    stack: List[Tuple[float, str]] = []
    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, _ = stack.pop()
            points.append((end, stack[-1][1] if stack else outside))
        points.append((s, name))
        stack.append((e, name))
    while stack:
        end, _ = stack.pop()
        points.append((end, stack[-1][1] if stack else outside))
    return points


def attribute(intervals: Sequence[Interval],
              timeline: Sequence[Tuple[float, str]],
              outside: str = CLIENT) -> Dict[str, float]:
    """ns of ``intervals`` under each label of ``timeline``."""
    times = [t for t, _ in timeline]
    out: Dict[str, float] = collections.defaultdict(float)
    for s, e in intervals:
        i = bisect.bisect_right(times, s) - 1
        cur = s
        while cur < e:
            label = timeline[i][1] if i >= 0 else outside
            nxt = times[i + 1] if i + 1 < len(times) else e
            stop = min(e, nxt)
            if stop > cur:
                out[label] += stop - cur
            cur = stop
            i += 1
    return dict(out)


def short_name(name: str) -> str:
    """An op's HLO text cut to its name, a program's name without its
    fingerprint: ``jit_f(123)`` -> ``jit_f``, ``%fusion.2 = s32[..] ...``
    -> ``%fusion.2``."""
    return re.sub(r"\(\d+\)$", "", name.split(" = ", 1)[0])


def op_times(trace: DeviceTrace, dev: int, lo: float, hi: float
             ) -> Dict[str, float]:
    """ns of each device operation in the window, named
    ``<program>/<op>`` where the program is known."""
    progs = trace.programs.get(dev, [])
    starts = [p.start for p in progs]
    out: Dict[str, float] = collections.defaultdict(float)
    for ev in trace.ops.get(dev, []):
        s, e = max(ev.start, lo), min(ev.end, hi)
        if e <= s:
            continue
        name = short_name(ev.name)
        i = bisect.bisect_right(starts, ev.start) - 1
        if i >= 0 and progs[i].end >= ev.start:
            name = f"{short_name(progs[i].name)}/{name}"
        out[name] += e - s
    return dict(out)


def program_ns(trace: DeviceTrace, dev: int, pattern: str, lo: float,
               hi: float) -> float:
    """Device ns of the programs whose name contains ``pattern``."""
    return sum(min(p.end, hi) - max(p.start, lo)
               for p in trace.programs.get(dev, [])
               if pattern in p.name and p.end > lo and p.start < hi)


def top(table: Dict[str, float], n: int = 10, scale: float = 1e-9
        ) -> List[list]:
    return [[k, v * scale] for k, v in
            sorted(table.items(), key=lambda kv: -kv[1])[:n]]


__all__ = ["CLIENT", "DeviceTrace", "Event", "MARK", "attribute", "busy_ns",
           "clip", "clock_offset", "gaps", "innermost_timeline", "merge",
           "newest_xplane", "op_times", "program_ns", "read_trace", "top"]
