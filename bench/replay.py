"""The plain reference: a dict that replays the same ops in the same order.

Semantics are the plan API's (docs/API.md): PUT inserts an absent key and
acknowledges False for a present one; UPDATE overwrites, and inserts an
absent key.

It imports nothing of the program and takes nothing the program made.
Results are compared as int64 arrays in one encoding on both sides: a
GET's value, or ``MISSING`` when the key is absent; a write's
acknowledgement as 1 or 0.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from .traffic import GET, PUT, UPDATE

MISSING = -1  # never a value: values are drawn from [1, 2^62)
LOST = -2     # a key the program could not read back at all


def encode_results(results: Sequence) -> np.ndarray:
    """The program's per-op results (value, None, or bool ack) as int64."""
    try:
        return np.array(results, np.int64)
    except TypeError:  # some None among them
        return np.array([MISSING if r is None else int(r) for r in results],
                        np.int64)


class Reference:
    """Key -> value, replayed op by op in program order.  Reads between
    writes look the dict's contents up as one sorted array."""

    def __init__(self) -> None:
        self.table: dict = {}
        self._sorted = None  # (keys, values) of the table, until a write

    def apply(self, codes: np.ndarray, keys: np.ndarray,
              aux: np.ndarray) -> np.ndarray:
        """Replay one plan; returns the expected encoded results."""
        if not (codes != GET).any():  # read-only plan: nothing changes
            return self.lookup(keys)
        self._sorted = None
        table = self.table
        out = np.empty(codes.shape[0], np.int64)
        for i, (c, k, v) in enumerate(zip(codes.tolist(), keys.tolist(),
                                          aux.tolist())):
            if c == GET:
                out[i] = table.get(k, MISSING)
            elif c == UPDATE:  # an absent key is inserted, as the API says
                out[i] = 1
                table[k] = v
            elif c == PUT:
                out[i] = k not in table
                if k not in table:
                    table[k] = v
            else:
                raise ValueError(f"unknown op code {c}")
        return out

    def lookup(self, keys: np.ndarray) -> np.ndarray:
        if self._sorted is None:
            k = np.fromiter(self.table.keys(), np.int64, len(self.table))
            v = np.fromiter(self.table.values(), np.int64, len(self.table))
            order = np.argsort(k)
            self._sorted = (k[order], v[order])
        k, v = self._sorted
        if not k.size:
            return np.full(keys.shape, MISSING, np.int64)
        i = np.minimum(np.searchsorted(k, keys), k.size - 1)
        return np.where(k[i] == keys, v[i], MISSING)


__all__ = ["LOST", "MISSING", "Reference", "encode_results"]
