"""Share of the write ops that a shard run applied in array form: the
``array_writes`` deltas of the program's write-kind ``plan.wave`` spans
(``core/clht``: a P-CLHT stretch of updates of present keys, found and
stored as array operations) over those waves' widths.  Layer: write
path.  Moves ``ops_per_s``."""


def read(w):
    waves = [s for s in w.named("plan.wave")
             if s.attrs.get("kind") == "write" and "array_writes" in s.attrs]
    width = sum(int(s.attrs["width"]) for s in waves)
    if not width:
        return None
    return 100.0 * sum(int(s.attrs["array_writes"]) for s in waves) / width
