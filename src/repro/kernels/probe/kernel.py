"""64-bit-exact batched probe — Pallas TPU kernel.

The shared wide-compare engine of the batched read path: every query
carries a pre-gathered probe window (its hash bucket's slots plus the
whole overflow chain, or any other candidate set), and the kernel does
the VPU compare + first-hit select.  PM words are 64-bit but the VPU
lanes are 32-bit, so keys and values travel as (lo, hi) int32 halves
and a hit requires both halves to match — no tag collisions, results
are bit-identical to the scalar control-plane lookup.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import backend

# At most QUERY_BLOCK queries per grid step.  Interpret mode pays a
# fixed per-step cost, so there the block swallows a whole serving/YCSB
# batch in one step; compiled, it shrinks until the step's
# double-buffered blocks fit VMEM_BUDGET.
QUERY_BLOCK = 4096
VMEM_BUDGET = 12 << 20  # of the 16 MiB of scoped VMEM a v5e kernel gets


def _query_block(Q: int, W: int, n_windows: int, n_cols: int) -> int:
    """Queries per grid step for ``n_windows`` [Q, W] and ``n_cols``
    [Q, 1] int32 operands.  A block's rows pad to whole 128-lane rows
    in VMEM, and each operand is double-buffered.  Q is a power of two
    below QUERY_BLOCK and a multiple of it above (probe.ops.pad_queries),
    so halving keeps the block a divisor of Q."""
    qb = min(QUERY_BLOCK, Q)
    if backend.interpret():
        return qb
    lanes = -(-W // 128) * 128
    row_bytes = 2 * 4 * (n_windows * lanes + n_cols * 128)
    while qb > 8 and qb * row_bytes > VMEM_BUDGET:
        qb //= 2
    return qb


def _first_hit(hit):
    """One-hot of each row's first True lane (all False on a miss): a
    min over the lane iota masked by ``hit``, which the TPU compiler
    takes where an int32 ``argmax`` is refused."""
    lane = jax.lax.broadcasted_iota(jnp.int32, hit.shape, 1)
    first = jnp.min(jnp.where(hit, lane, hit.shape[1]), axis=1, keepdims=True)
    return lane == first


def _probe64_kernel(qlo_ref, qhi_ref, klo_ref, khi_ref, vlo_ref, vhi_ref,
                    found_ref, olo_ref, ohi_ref):
    qlo = qlo_ref[...]  # [QB, 1]
    qhi = qhi_ref[...]
    klo = klo_ref[...]  # [QB, W]
    khi = khi_ref[...]
    hit = (klo == qlo) & (khi == qhi)  # paired-half VPU wide compare
    found = jnp.any(hit, axis=1, keepdims=True)
    onehot = _first_hit(hit)
    olo = jnp.sum(jnp.where(onehot, vlo_ref[...], 0), axis=1, keepdims=True)
    ohi = jnp.sum(jnp.where(onehot, vhi_ref[...], 0), axis=1, keepdims=True)
    found_ref[...] = found
    olo_ref[...] = jnp.where(found, olo, 0)
    ohi_ref[...] = jnp.where(found, ohi, 0)


def _probe64_fp_kernel(qlo_ref, qhi_ref, qfp_ref, klo_ref, khi_ref,
                       vlo_ref, vhi_ref, wfp_ref, found_ref, olo_ref,
                       ohi_ref, nfp_ref, nfalse_ref):
    """probe64 with a fingerprint-lane pre-pass: a lane's 64-bit key
    halves are compared only where its 1-byte fingerprint matched the
    query's (fingerprint.fp64 on both sides, so a true hit always
    passes the filter).  Two extra outputs feed the probe-traffic
    model: per-query fingerprint-match and false-positive counts."""
    qlo = qlo_ref[...]  # [QB, 1]
    qhi = qhi_ref[...]
    qfp = qfp_ref[...]
    klo = klo_ref[...]  # [QB, W]
    khi = khi_ref[...]
    wfp = wfp_ref[...]
    # the fp pre-pass: empty slots carry FP_EMPTY=0 and a query fp is
    # never 0, so padding/empty lanes can never pass the filter
    fphit = wfp == qfp
    # full verification, gathered only for filter survivors
    hit = fphit & (klo == qlo) & (khi == qhi)
    found = jnp.any(hit, axis=1, keepdims=True)
    onehot = _first_hit(hit)
    olo = jnp.sum(jnp.where(onehot, vlo_ref[...], 0), axis=1, keepdims=True)
    ohi = jnp.sum(jnp.where(onehot, vhi_ref[...], 0), axis=1, keepdims=True)
    found_ref[...] = found
    olo_ref[...] = jnp.where(found, olo, 0)
    ohi_ref[...] = jnp.where(found, ohi, 0)
    nfp_ref[...] = jnp.sum(fphit.astype(jnp.int32), axis=1, keepdims=True)
    nfalse_ref[...] = jnp.sum((fphit & ~hit).astype(jnp.int32), axis=1,
                              keepdims=True)


@functools.partial(jax.jit, static_argnames=("query_block",))
def probe64_fp(qlo, qhi, qfp, klo, khi, vlo, vhi, wfp, *,
               query_block: Optional[int] = None):
    """Fingerprinted probe64.  qfp: [Q] int32 query fingerprints; wfp:
    [Q, W] int32 window fingerprints (fingerprint.fp64 of the window
    keys, 0 = empty).  Returns (found [Q] bool, value_lo, value_hi,
    n_fp_match [Q] int32, n_fp_false [Q] int32); found/values are
    bit-identical to ``probe64`` over the same windows.  The query
    block is sized from the shapes unless ``query_block`` is given."""
    Q, W = klo.shape
    qb = query_block or _query_block(Q, W, n_windows=5, n_cols=8)
    assert Q % qb == 0, (Q, qb)
    grid = (Q // qb,)
    win = pl.BlockSpec((qb, W), lambda i: (i, 0))
    col = pl.BlockSpec((qb, 1), lambda i: (i, 0))
    found, olo, ohi, nfp, nfalse = pl.pallas_call(
        _probe64_fp_kernel,
        grid=grid,
        in_specs=[col, col, col, win, win, win, win, win],
        out_specs=[col, col, col, col, col],
        out_shape=[
            jax.ShapeDtypeStruct((Q, 1), jnp.bool_),
            jax.ShapeDtypeStruct((Q, 1), jnp.int32),
            jax.ShapeDtypeStruct((Q, 1), jnp.int32),
            jax.ShapeDtypeStruct((Q, 1), jnp.int32),
            jax.ShapeDtypeStruct((Q, 1), jnp.int32),
        ],
        interpret=backend.interpret(),
    )(qlo.reshape(Q, 1), qhi.reshape(Q, 1), qfp.reshape(Q, 1),
      klo, khi, vlo, vhi, wfp)
    return (found[:, 0], olo[:, 0], ohi[:, 0], nfp[:, 0], nfalse[:, 0])


@functools.partial(jax.jit, static_argnames=("query_block",))
def probe64(qlo, qhi, klo, khi, vlo, vhi, *,
            query_block: Optional[int] = None):
    """qlo/qhi: [Q] int32 query-key halves; klo/khi/vlo/vhi: [Q, W] int32
    probe-window halves (0-padded).  Returns (found [Q] bool,
    value_lo [Q] int32, value_hi [Q] int32).  The query block is sized
    from the shapes unless ``query_block`` is given."""
    Q, W = klo.shape
    qb = query_block or _query_block(Q, W, n_windows=4, n_cols=5)
    assert Q % qb == 0, (Q, qb)
    grid = (Q // qb,)
    win = pl.BlockSpec((qb, W), lambda i: (i, 0))
    col = pl.BlockSpec((qb, 1), lambda i: (i, 0))
    found, olo, ohi = pl.pallas_call(
        _probe64_kernel,
        grid=grid,
        in_specs=[col, col, win, win, win, win],
        out_specs=[col, col, col],
        out_shape=[
            jax.ShapeDtypeStruct((Q, 1), jnp.bool_),
            jax.ShapeDtypeStruct((Q, 1), jnp.int32),
            jax.ShapeDtypeStruct((Q, 1), jnp.int32),
        ],
        interpret=backend.interpret(),
    )(qlo.reshape(Q, 1), qhi.reshape(Q, 1), klo, khi, vlo, vhi)
    return found[:, 0], olo[:, 0], ohi[:, 0]
