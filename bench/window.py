"""What a per-layer metric reads: the traced window of one run.

A reader in ``bench/metrics/<name>.py`` is ``read(window) -> float | None``.
It returns None where it finds nothing to read (no such span, no such
program on the device), and the harness then leaves the metric out of
the result line.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

from . import xplane


@dataclasses.dataclass
class Window:
    host_ns: float                 # length on the host clock
    spans: Sequence                # the program's spans inside the window
    compiles: int                  # programs lowered inside the window
    config: dict
    keys: int                      # keys loaded
    device_kind: str
    devices: List[int]             # the chips the cell uses
    trace: Optional[xplane.DeviceTrace] = None
    lo: float = 0.0                # the window on the trace's clock
    hi: float = 0.0

    @property
    def trace_ns(self) -> float:
        return self.hi - self.lo

    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]

    def span_ns(self, name: str) -> float:
        return float(sum(s.dur for s in self.named(name)))

    def nested_ns(self, outer: str, prefix: str) -> float:
        """ns of spans named ``prefix...`` that run inside an ``outer``
        span, each counted once however deep it sits."""
        by_id = {s.span_id: s for s in self.spans}
        total = 0.0
        for s in self.spans:
            if not s.name.startswith(prefix):
                continue
            p = by_id.get(s.parent_id)
            while p is not None and p.name != outer:
                if p.name.startswith(prefix):
                    p = None  # an enclosing match already counts this time
                    break
                p = by_id.get(p.parent_id)
            if p is not None:
                total += s.dur
        return total

    def busy_ns(self, dev: int) -> float:
        if self.trace is None:
            return 0.0
        return xplane.busy_ns(self.trace.ops.get(dev, []), self.lo, self.hi)

    def program_ns(self, pattern: str) -> float:
        """Device ns of programs named like ``pattern``, summed over the
        cell's chips."""
        if self.trace is None:
            return 0.0
        return sum(xplane.program_ns(self.trace, d, pattern, self.lo,
                                     self.hi) for d in self.devices)


__all__ = ["Window"]
