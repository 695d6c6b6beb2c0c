"""Share of its roofline the four-shard fan-out lookup reaches: the
least time its bytes need at one chip's HBM bandwidth, over the device
time of the fused probe program summed over the cell's chips.  Bytes
come from the operation's shapes (``bench/shapes.sorted_lookup_bytes``):
per ``shard.mesh_lookup`` span, its real queries (``ops``) searched over
its longest live shard run (``run_max``), never the padded query slots
or run.  Layer: kernel (``distributed/mesh``, ``kernels.scan.lower_bound``).
Moves ``ops_per_s``.

The program, as named in a v5e trace: the XLA module of the jitted
``mesh_probe`` (``distributed/mesh.py``), one per chip.
"""

from bench.peaks import peaks_for
from bench.shapes import sorted_lookup_bytes

PROGRAM = "mesh_probe"


def read(w):
    spans = w.named("shard.mesh_lookup")
    if not spans or any("run_max" not in s.attrs for s in spans):
        return None  # a program whose span does not give the run
    least_bytes = sum(sorted_lookup_bytes(int(s.attrs["ops"]),
                                          int(s.attrs["run_max"]))
                      for s in spans)
    device_ns = w.program_ns(PROGRAM)
    if not least_bytes or device_ns <= 0:
        return None
    least_s = least_bytes / peaks_for(w.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / (device_ns / 1e9)
