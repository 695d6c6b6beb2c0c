"""Share of the window the plan scheduler takes: the program's
``plan.schedule`` spans (``core/plan.py``, wave leveling) over the
window, on the host clock.  Layer: plan API.  Moves ``ops_per_s``."""


def read(w):
    spans = w.named("plan.schedule")
    if not spans:
        return None
    return 100.0 * sum(s.dur for s in spans) / w.host_ns
