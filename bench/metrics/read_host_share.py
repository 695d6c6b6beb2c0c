"""Share of the window the read dispatch spends on the host: the
program's ``plan.lookup_batch`` spans (``core/conditions._lookup_batch``)
less the ``kernel.*`` spans inside them, over the window on the host
clock.  What is left is the snapshot export and upload, the dispatch
floors and scalar fallbacks, and the building of the result lists.
Layer: read dispatch and snapshot export.  Moves ``ops_per_s``."""


def read(w):
    if not w.named("plan.lookup_batch"):
        return None
    host = (w.span_ns("plan.lookup_batch")
            - w.nested_ns("plan.lookup_batch", "kernel."))
    return 100.0 * host / w.host_ns
