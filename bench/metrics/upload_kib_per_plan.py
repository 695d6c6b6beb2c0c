"""Host-to-device KiB per plan: the ``upload_bytes`` deltas the
program's ``plan.wave`` spans carry (``core/plan.py``: the prepared
snapshot exports and every query batch handed to the device), summed
and divided by the number of ``plan.execute`` spans.  Layer: read
dispatch and snapshot export.  Moves ``ops_per_s``."""


def read(w):
    waves = [s for s in w.named("plan.wave") if "upload_bytes" in s.attrs]
    plans = len(w.named("plan.execute"))
    if not waves or not plans:
        return None
    return sum(int(s.attrs["upload_bytes"]) for s in waves) / 1024 / plans
