"""Share of the snapshot exports that were deltas: the ``delta_exports``
deltas of the program's ``plan.wave`` spans (``core/clht``: a stale
P-CLHT snapshot patched with only the bucket rows written since it was
taken) over their ``exports`` deltas, which count deltas and full
exports alike.  Layer: read dispatch and snapshot export.  Moves
``ops_per_s``."""


def read(w):
    waves = [s for s in w.named("plan.wave") if "delta_exports" in s.attrs]
    exports = sum(int(s.attrs["exports"]) for s in waves)
    if not exports:
        return None
    return 100.0 * sum(int(s.attrs["delta_exports"]) for s in waves) / exports
