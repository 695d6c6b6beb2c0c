"""Faults planted under a run, for the controls and the tests of ``correct``.

Each fault breaks the program underneath the harness, at run time and in
this process only; ``bench/run.py --fault <name>`` plants one.  A sound
comparison reads ``correct`` false under every fault the cell can have:

* ``drop_flush``: group commits acknowledge without writing back, so a
  powerfail loses acknowledged writes (the control: it breaks the
  configurations' durability guarantee);
* ``alter_answer``: one lookup answer is altered where it is produced;
* ``half_batch``: every plan runs only its first half, the rest of its
  results left empty;
* ``stale_update``: writes are acknowledged and not applied, so the
  state is returned unchanged.

``drop_flush`` acts from the start, so the load is broken too; the others
act from the first measured plan.
"""

from __future__ import annotations

from typing import Callable, Dict


def drop_flush(session) -> None:
    pm = session.pmem
    pm._close_group = pm._abandon_group


def alter_answer(session) -> None:
    index = session.index
    probe = index._kernel_lookup
    left = [1]

    def altered(snapshot, queries):
        res = probe(snapshot, queries)
        if res is None or not left[0] or not res[0].size:
            return res
        left[0] -= 1
        found, vals = res[0].copy(), res[1].copy()
        found[0], vals[0] = True, vals[0] + 1
        return found, vals

    index._kernel_lookup = altered


def half_batch(session) -> None:
    from repro.api import Plan
    index = session.index
    execute = index.execute

    def first_half(plan, **kw):
        kinds, keys, aux = plan.arrays()
        half = len(plan) // 2
        res = execute(Plan.from_arrays(kinds[:half], keys[:half],
                                       aux[:half]), **kw)
        res.results = list(res.results) + [None] * (len(plan) - half)
        return res

    index.execute = first_half


def stale_update(session) -> None:
    session.index._write_batch = lambda ops, *a, **kw: [True] * len(ops)


FAULTS: Dict[str, Callable] = {
    "drop_flush": drop_flush, "alter_answer": alter_answer,
    "half_batch": half_batch, "stale_update": stale_update,
}
FROM_SETUP = {"drop_flush"}

__all__ = ["FAULTS", "FROM_SETUP"]
