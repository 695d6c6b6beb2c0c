"""Plan-conflict detection — Pallas TPU kernel.

``conflict_any`` answers, for every op in a candidate set A, whether
it conflicts with ANY op in a reference set B (the pairwise rules of
``ref.py``).  Layout: A ops run down the sublane axis, the whole B set
lies along the lane axis, so one [A_block, B] compare-and-reduce per
grid step evaluates ``A_block * B`` pairs on the VPU.

Keys arrive as (lo, hi) int32 halves (kernels/probe ``split64``).
Same-key tests are half-pair equality; the scan-window order test
``key >= start`` needs a 64-bit unsigned compare, which decomposes as
``hi_a > hi_b or (hi_a == hi_b and lo_a >=u lo_b)`` — keys are 63-bit
non-negative words so the high halves compare correctly as int32, and
the low halves are bitcast to uint32 for the unsigned leg.

Padding slots use kind code ``NONE`` (5): every kind predicate is then
false, so padded rows/columns can never contribute a conflict — no key
sentinel needed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import backend
from .ref import DELETE, GET, PUT, SCAN, UPDATE

NONE = 5  # padding kind: conflicts with nothing

CAND_BLOCK = 512  # candidate (A) ops per grid step


def _conflict_any_kernel(ak_ref, alo_ref, ahi_ref, bk_ref, blo_ref,
                         bhi_ref, out_ref, *, writes_conflict: bool):
    ak = ak_ref[...]                      # [ab, 1] int32 kind codes
    bk = bk_ref[...]                      # [1, B]
    alo = jax.lax.bitcast_convert_type(alo_ref[...], jnp.uint32)
    blo = jax.lax.bitcast_convert_type(blo_ref[...], jnp.uint32)
    ahi = ahi_ref[...]                    # int32, non-negative (63-bit keys)
    bhi = bhi_ref[...]

    wa = (ak == PUT) | (ak == UPDATE) | (ak == DELETE)
    wb = (bk == PUT) | (bk == UPDATE) | (bk == DELETE)
    ga, gb = ak == GET, bk == GET
    sa, sb = ak == SCAN, bk == SCAN

    same = (alo == blo) & (ahi == bhi)                       # [ab, B]
    b_ge_a = (bhi > ahi) | ((bhi == ahi) & (blo >= alo))
    a_ge_b = (ahi > bhi) | ((ahi == bhi) & (alo >= blo))

    conf = same & ((ga & wb) | (wa & gb))
    conf |= sa & wb & b_ge_a             # b's write lands in a's window
    conf |= wa & sb & a_ge_b             # a's write lands in b's window
    if writes_conflict:
        conf |= same & wa & wb
    out_ref[...] = jnp.any(conf, axis=1, keepdims=True).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("writes_conflict",
                                             "cand_block"))
def conflict_any_kernel(a_kinds, a_klo, a_khi, b_kinds, b_klo, b_khi, *,
                        writes_conflict: bool = False,
                        cand_block: int = CAND_BLOCK):
    """a_*: [A] int32 candidate kinds + key halves; b_*: [B] reference
    set.  Returns [A] int32 0/1: candidate conflicts with some b op."""
    A, B = a_kinds.shape[0], b_kinds.shape[0]
    ab = min(cand_block, A)
    assert A % ab == 0, (A, ab)
    col = pl.BlockSpec((ab, 1), lambda i: (i, 0))
    row = pl.BlockSpec((1, B), lambda i: (0, 0))
    out = pl.pallas_call(
        functools.partial(_conflict_any_kernel,
                          writes_conflict=writes_conflict),
        grid=(A // ab,),
        in_specs=[col, col, col, row, row, row],
        out_specs=col,
        out_shape=jax.ShapeDtypeStruct((A, 1), jnp.int32),
        interpret=backend.interpret(),
    )(a_kinds.reshape(A, 1), a_klo.reshape(A, 1), a_khi.reshape(A, 1),
      b_kinds.reshape(1, B), b_klo.reshape(1, B), b_khi.reshape(1, B))
    return out[:, 0]
