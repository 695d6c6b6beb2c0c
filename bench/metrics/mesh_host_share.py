"""Share of the window the sharded read dispatch spends on the host:
``shard.route`` (routing and the per-shard split), ``shard.results``
(the per-op result list) and ``shard.mesh_lookup`` less the
``kernel.*`` spans inside it, over the window on the host clock.
Layer: read dispatch and snapshot export.  Moves ``ops_per_s``."""


def read(w):
    if not w.named("shard.route") or not w.named("shard.mesh_lookup"):
        return None
    host = (w.span_ns("shard.route") + w.span_ns("shard.results")
            + w.span_ns("shard.mesh_lookup")
            - w.nested_ns("shard.mesh_lookup", "kernel."))
    return 100.0 * host / w.host_ns
