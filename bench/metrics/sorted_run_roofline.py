"""Share of its roofline the sorted-run lookup reaches: the least time
its bytes need at the chip's HBM bandwidth, over the device time of the
search program.  Bytes come from the operation's shapes
(``bench/shapes.sorted_lookup_bytes``: the queries, from the program's
``kernel.scan`` spans, where a point lookup has a window of 1, and the
run of every loaded key), never from the implementation.  Layer: kernel
(``kernels/scan``).  Moves ``ops_per_s``.

The program, as named in a v5e trace: the XLA module of the jitted
``scan_window`` (``kernels/scan/kernel.py``).
"""

from bench.peaks import peaks_for
from bench.shapes import sorted_lookup_bytes

PROGRAM = "scan_window"


def read(w):
    spans = w.named("kernel.scan")
    if any(int(s.attrs["window"]) != 1 for s in spans):
        return None  # range scans: not a lookup's bytes
    queries = sum(int(s.attrs["batch"]) for s in spans)
    device_ns = w.program_ns(PROGRAM)
    if not queries or device_ns <= 0:
        return None
    least_s = (sorted_lookup_bytes(queries, w.keys)
               / peaks_for(w.device_kind)["hbm_bytes_per_s"])
    return 100.0 * least_s / (device_ns / 1e9)
