"""Share of the traced window in which no operation ran on the device:
1 - (union of the device-op intervals / window), from the profiler
trace, the mean over the cell's chips (each chip's value is printed on
an earlier line of standard error).  Layer: device.  Moves
``ops_per_s``."""


def read(w):
    if w.trace is None or w.trace_ns <= 0:
        return None
    busy = sum(w.busy_ns(d) for d in w.devices) / len(w.devices)
    return 100.0 * (1.0 - busy / w.trace_ns)
