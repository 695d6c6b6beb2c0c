"""Share of the window the snapshot export takes: the program's
``snapshot.export`` spans (``core/conditions.RecipeIndex.build_export``,
the index's ``export_arrays`` walk of its reachable state) over the
window, on the host clock.  Layer: read dispatch and snapshot export.
Moves ``ops_per_s``."""


def read(w):
    spans = w.named("snapshot.export")
    if not spans:
        return None
    return 100.0 * sum(s.dur for s in spans) / w.host_ns
