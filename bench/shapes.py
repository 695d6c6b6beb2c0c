"""The least bytes each read operation needs, from its own shapes.

These count what the operation must move, whatever implements it: a
query's key in, the index words its search has to read, and the answer
out.  Widths are the stored ones: 8-byte keys, values and chain
pointers, 1-byte fingerprints, and a 1-byte found flag.
"""

from __future__ import annotations

import math

KEY = 8
VALUE = 8
POINTER = 8
FINGERPRINT = 1
FOUND = 1
CLHT_SLOTS = 3  # key/value slots per P-CLHT bucket


def clht_lookup_bytes(queries: int, depth: int) -> int:
    """P-CLHT probe: per query, ``depth`` buckets of the overflow chain,
    each with its slots' key, value and fingerprint and its chain
    pointer; the query key in; found flag and value out."""
    bucket = CLHT_SLOTS * (KEY + VALUE + FINGERPRINT) + POINTER
    return queries * (KEY + depth * bucket + FOUND + VALUE)


def search_steps(run_length: int) -> int:
    """Probes of a binary search for a lower bound over ``run_length``
    sorted entries: ceil(log2(run_length + 1))."""
    return max(1, math.ceil(math.log2(run_length + 1)))


def sorted_lookup_bytes(queries: int, run_length: int) -> int:
    """Sorted-run lookup: per query, one key at each search step, the
    one-entry window (key and value) at the lower bound; the query key
    in; found flag and value out."""
    return queries * (KEY + search_steps(run_length) * KEY
                      + (KEY + VALUE) + FOUND + VALUE)


__all__ = ["clht_lookup_bytes", "search_steps", "sorted_lookup_bytes"]
