"""Batched sorted-run search — XLA code over HBM-resident runs.

The shared ordered-index read engine: every ordered RECIPE index can
export its reachable entries as one sorted run of (key, value) pairs
(the page-major flattening of its leaf pages), and ``scan_window``
answers a whole batch of queries against that run with a vectorized
binary search.  Each lane runs the same lower-bound steps (the APEX
leaf-probe shape: locate the leaf slot, then read a bounded window),
then gathers a ``max_count``-wide window of consecutive entries
starting at its lower bound:

* point lookup  = window of 1 + host-side key-equality check;
* range scan    = window of ``count`` entries (YCSB-E's "scan N
  records from start key").

The run stays in HBM and XLA does the data-dependent gathers: a run of
deployment size does not fit a kernel's fast memory, and the TPU's
Pallas compiler refuses gathers with data-dependent indices.
``lower_bound`` is also the per-shard search of the mesh fan-out
(``distributed/mesh.py``).

PM words are 64-bit but the VPU lanes are 32-bit, so keys and values
travel as (lo, hi) int32 halves.  Ordering over split halves needs an
unsigned compare on the low word, which int32 lanes cannot do directly:
the host pre-biases ``lo ^ 0x80000000`` so signed lane compares realize
unsigned 64-bit order (keys are PM words < 2^63, so the high half is
already order-preserving as a signed int32).  Gathered keys are
un-biased before they are returned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..probe.ops import pack_outputs

_BIAS = -(1 << 31)  # XOR bias realizing unsigned int32 order


def lower_bound(khi, klo, n, qhi, qlo, *, steps: int):
    """First index in the run's live prefix ``[0, n)`` whose key is >=
    each query.  khi/klo: [N] key halves (klo biased); qhi/qlo: query
    halves of any shape; ``steps`` >= ceil(log2(n + 1)).  The carry is
    built from the queries, so under ``shard_map`` it varies along the
    same mesh axes as they do."""
    last = khi.shape[0] - 1

    def body(_, lohi):
        lo, hi = lohi
        act = lo < hi
        mid = (lo + hi) >> 1
        safe = jnp.clip(mid, 0, last)
        mhi, mlo = khi[safe], klo[safe]
        less = (mhi < qhi) | ((mhi == qhi) & (mlo < qlo))
        return (jnp.where(act & less, mid + 1, lo),
                jnp.where(act & ~less, mid, hi))

    zero = jnp.zeros_like(qhi)
    lo, _ = jax.lax.fori_loop(0, steps, body, (zero, zero + n))
    return lo


@functools.partial(jax.jit, static_argnames=("steps", "max_count"))
def scan_window(queries, klo, khi, vlo, vhi, n, *, steps: int,
                max_count: int):
    """queries: the one packed upload, [3, Q] int32 rows: query-key
    halves qlo (pre-biased) and qhi, and the requested window widths;
    klo/khi/vlo/vhi: [N] int32 halves of the sorted run (klo
    pre-biased); n: int32 live-entry count; steps: host-computed
    ceil(log2(n+1)).  Returns the one packed download, [5, Q, C] int32:
    valid (0/1), key_lo, key_hi, val_lo, val_hi — valid rows are prefix
    masks, keys come back un-biased."""
    qlo, qhi, counts = queries
    with jax.named_scope("search"):
        lo = lower_bound(khi, klo, n, qhi, qlo, steps=steps)
    off = jax.lax.broadcasted_iota(jnp.int32, (qlo.shape[0], max_count), 1)
    pos = lo[:, None] + off
    ok = (off < counts[:, None]) & (pos < n)
    safe = jnp.clip(pos, 0, klo.shape[0] - 1)
    return pack_outputs(ok,
                        jnp.where(ok, klo[safe] ^ _BIAS, 0),  # un-bias
                        jnp.where(ok, khi[safe], 0),
                        jnp.where(ok, vlo[safe], 0),
                        jnp.where(ok, vhi[safe], 0))
