"""Batched CLHT probe — Pallas TPU kernel.

The paper's design point "one bucket = one cache line, probed with a
handful of SIMD compares" maps to TPU as "one probe window = one VMEM
lane row, compared on the VPU": each kernel instance takes a tile of
QB queries and their pre-gathered probe windows (bucket slots +
overflow-chain slots, padded to a 128-lane row — the XLA gather feeds
the kernel, the kernel does the wide compare + select).  This is the
data-plane lookup for the serving block table / prefix cache built on
P-CLHT (DESIGN.md §2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import backend

QUERY_BLOCK = 256


def _probe_kernel(q_ref, bk_ref, bv_ref, found_ref, val_ref):
    q = q_ref[...]  # [QB, 1]
    bk = bk_ref[...]  # [QB, W]
    bv = bv_ref[...]
    hit = bk == q  # VPU wide compare
    found = jnp.any(hit, axis=1, keepdims=True)
    # select the first hit's value: argmax over int mask
    idx = jnp.argmax(hit.astype(jnp.int32), axis=1)
    onehot = jax.lax.broadcasted_iota(jnp.int32, bk.shape, 1) == idx[:, None]
    val = jnp.sum(jnp.where(onehot, bv, 0), axis=1, keepdims=True)
    found_ref[...] = found
    val_ref[...] = jnp.where(found, val, 0)


def clht_probe(queries, bucket_keys, bucket_vals, *,
               query_block: int = QUERY_BLOCK):
    """queries: [Q] int64-as-int32-pairs? — int32 keys for the kernel
    (the 64-bit control plane hashes down to 32-bit tags for the data
    plane; tag collisions re-verify against the authoritative index).
    bucket_keys/vals: [Q, W] pre-gathered windows (W = 128 lanes).
    Returns (found [Q] int32, values [Q] int32)."""
    Q, W = bucket_keys.shape
    qb = min(query_block, Q)
    assert Q % qb == 0
    grid = (Q // qb,)
    found, vals = pl.pallas_call(
        _probe_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((qb, 1), lambda i: (i, 0)),
            pl.BlockSpec((qb, W), lambda i: (i, 0)),
            pl.BlockSpec((qb, W), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((qb, 1), lambda i: (i, 0)),
            pl.BlockSpec((qb, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, 1), jnp.bool_),
            jax.ShapeDtypeStruct((Q, 1), bucket_vals.dtype),
        ],
        interpret=backend.interpret(),
    )(queries.reshape(Q, 1), bucket_keys, bucket_vals)
    return found[:, 0], vals[:, 0]
