"""Host front-end for shard routing + stable sort-by-shard.

``partition_writes`` is what ``RecipeIndex.write_batch`` calls: route
every op's key to a shard, then produce the stable sort-by-shard
permutation and per-shard run offsets.  Routing runs on the host by
default — the control plane owns native uint64, and a write batch is
consumed op-by-op there anyway (the same division kernels/clht_probe
draws for its bucket hash).  ``route_shards(use_kernel=True)`` runs
the Pallas lane-limb kernel instead, bit-identical, for TPU-resident
pipelines and the kernel-vs-ref tests.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from ...obs import RECORDER as _OBS
from .ref import mix64_ref, partition_ref, route_ref


def route_shards(keys: np.ndarray, n_shards: int, scheme: str = "hash", *,
                 use_kernel: bool = False) -> np.ndarray:
    """Shard id per key: [Q] int32 in [0, n_shards)."""
    keys = np.asarray(keys, np.int64)
    if not use_kernel or keys.size == 0:
        return route_ref(keys, n_shards, scheme)
    from ..probe import split64  # jax import deferred: jax-less fallback
    assert (n_shards & (n_shards - 1)) == 0
    bits = n_shards.bit_length() - 1
    from .kernel import SHARD_BLOCK, shard_route
    Q = keys.shape[0]
    if Q >= SHARD_BLOCK:
        pad = (-Q) % SHARD_BLOCK
    else:
        p = 8
        while p < Q:
            p <<= 1
        pad = p - Q
    q = np.pad(keys, (0, pad)) if pad else keys
    lo, hi = split64(q)
    import jax.numpy as jnp
    out = shard_route(jnp.asarray(lo), jnp.asarray(hi), bits=bits,
                      scheme=scheme)
    return np.asarray(out)[:Q]


def partition_writes(keys: np.ndarray, n_shards: int, scheme: str = "hash"
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(shards, order, offsets) for a write batch — see partition_ref."""
    keys = np.asarray(keys, np.int64)
    with _OBS.span("kernel.partition", batch=int(keys.size),
                   n_shards=n_shards):
        return partition_ref(keys, n_shards, scheme)


__all__ = ["mix64_ref", "partition_writes", "route_ref", "route_shards"]
