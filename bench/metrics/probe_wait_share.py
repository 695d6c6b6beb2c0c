"""Share of the window spent fetching read-kernel results: the
program's ``kernel.fetch`` spans (inside each read-kernel span of
``kernels/``, from the first download of an output to the last, so the
wait for the device program too) over the window, on the host clock.
Layer: kernels.  Moves ``ops_per_s``."""


def read(w):
    spans = w.named("kernel.fetch")
    if not spans:
        return None
    return 100.0 * sum(s.dur for s in spans) / w.host_ns
