"""Share of its roofline the P-CLHT probe reaches: the least time its
bytes need at the chip's HBM bandwidth, over the device time of the
fused gather+probe program.  Bytes come from the operation's shapes
(``bench/shapes.clht_lookup_bytes``: queries and chain depth, from the
program's ``kernel.clht_probe`` spans), never from the implementation.
Layer: kernel (``kernels/clht_probe``, ``kernels/probe``).  Moves
``ops_per_s``.

The program, as named in a v5e trace: the XLA module of the jitted
``_gather_probe`` (``kernels/clht_probe/ops.py``).
"""

from bench.peaks import peaks_for
from bench.shapes import clht_lookup_bytes

PROGRAM = "_gather_probe"


def read(w):
    spans = w.named("kernel.clht_probe")
    device_ns = w.program_ns(PROGRAM)
    if not spans or device_ns <= 0:
        return None
    nbytes = sum(clht_lookup_bytes(int(s.attrs["batch"]),
                                   int(s.attrs["depth"])) for s in spans)
    least_s = nbytes / peaks_for(w.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / (device_ns / 1e9)
