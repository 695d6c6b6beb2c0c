"""Host time per write op of the batched write path: the program's
``plan.write_batch`` spans (``core/conditions._write_batch``, the
``core/pmem`` group commit and the index's own writers) over the ops
they carried.  Layer: write path.  Moves ``ops_per_s``."""


def read(w):
    spans = w.named("plan.write_batch")
    ops = sum(int(s.attrs.get("width", 0)) for s in spans)
    if not ops:
        return None
    return sum(s.dur for s in spans) / ops / 1e3
