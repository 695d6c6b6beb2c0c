"""Share of read keys answered by a per-key scalar lookup: the
``scalar_reads`` deltas of the program's read-kind ``plan.wave`` spans
(``core/conditions``: the dispatch floors, the dirty shards of a
refined read, indexes without an export) over those waves' widths.
Layer: read dispatch and snapshot export.  Moves ``ops_per_s``."""


def read(w):
    waves = [s for s in w.named("plan.wave")
             if s.attrs.get("kind") == "read" and "scalar_reads" in s.attrs]
    width = sum(int(s.attrs["width"]) for s in waves)
    if not width:
        return None
    return 100.0 * sum(int(s.attrs["scalar_reads"]) for s in waves) / width
