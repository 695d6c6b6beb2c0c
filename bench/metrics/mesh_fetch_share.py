"""Share of the window spent fetching the fan-out's results: the
``kernel.fetch`` spans inside ``shard.mesh_lookup`` (first to last
download of the gathered ``[S, q_pad]`` outputs, so the wait for the
device program too) over the window, on the host clock.  Layer:
kernels.  Moves ``ops_per_s``."""


def read(w):
    fetch = w.nested_ns("shard.mesh_lookup", "kernel.fetch")
    if fetch <= 0:
        return None  # no fan-out, or a program without the span
    return 100.0 * fetch / w.host_ns
