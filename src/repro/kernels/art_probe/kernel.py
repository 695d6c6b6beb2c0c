"""Batched radix descent (P-ART and P-HOT) — XLA code over HBM pages.

A batch of queries descends the exported node pages together: at each
step, every lane gathers its current node's ``level`` word, picks the
key *unit* at that level, and hops through the node's child row.  The
unit width is set by the export: P-ART uses 8-bit units (qunits
[Q, 8], children [N, 256], at most 9 steps), P-HOT's nibble-span
compound nodes use 4-bit units (qunits [Q, 16], children [N, 16], at
most 17 steps) — the descent derives both from the array shapes.

Trusting ``level`` is exactly the scalar reader's stale-prefix
tolerance (paper §6.4): a node whose prefix header was left stale by an
interrupted path-compression SMO is traversed by level and the full
64-bit key is verified at the leaf, so batched results are
bit-identical to scalar ``lookup`` even mid-SMO or post-crash.
Keys/values travel as (lo, hi) int32 halves.

The node pages stay in HBM and XLA does the data-dependent gathers
(``children[node, unit]``): the pages of a deployment-size tree do not
fit a kernel's fast memory, and the TPU's Pallas compiler refuses
gathers with data-dependent indices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..probe.ops import pack_outputs

KEY_BYTES = 8


@jax.jit
def art_descend(queries, children, level, is_leaf, lfp, lklo, lkhi, lvlo,
                lvhi):
    """queries: the one packed upload, [U + 3, Q] int32 rows: U
    big-endian key units (U=8 bytes for P-ART, U=16 nibbles for P-HOT),
    then the key halves qlo and qhi and the partial-key fingerprint
    (fingerprint.fp_partial); children: [N, 2**unit_bits] int32 (-1
    none); level/is_leaf/lfp/leaf key-value halves: [N] int32 (lfp is
    the export's ``leaf_fp`` lane, 0 for non-leaf rows).  Returns the
    one packed download, [6, Q] int32 rows: found (0/1), value_lo,
    value_hi, n_leaf_checks, n_fp_match, n_fp_false — found and values
    are unchanged by the fingerprint pre-pass; the counts feed the
    probe-traffic model."""
    U = queries.shape[0] - 3  # key units per key (8 bytes or 16 nibbles)
    Q = queries.shape[1]
    qbytes = queries[:U].T
    qlo, qhi, qfp = queries[U:]
    node = jnp.zeros((Q,), jnp.int32)  # node 0 is the root
    active = jnp.ones((Q,), jnp.bool_)
    found = jnp.zeros((Q,), jnp.bool_)
    olo = jnp.zeros((Q,), jnp.int32)
    ohi = jnp.zeros((Q,), jnp.int32)
    nenc = jnp.zeros((Q,), jnp.int32)    # leaf encounters (fp compares)
    nfp = jnp.zeros((Q,), jnp.int32)     # fingerprint matches
    nfalse = jnp.zeros((Q,), jnp.int32)  # matches the full key rejects
    # levels strictly increase along any path, so U internal hops + the
    # leaf check bound the descent; finished lanes just idle
    for _ in range(U + 1):
        leaf = active & (is_leaf[node] != 0)
        # fingerprint pre-pass: the leaf's inline partial-key byte is
        # compared first; the full 64-bit key words are gathered only
        # on a match (a true hit always matches — same byte function
        # on both sides)
        fpmatch = leaf & (lfp[node] == qfp)
        # leaf verification: full 64-bit key AND live (non-tombstone) value
        hit = (fpmatch & (lklo[node] == qlo) & (lkhi[node] == qhi)
               & ((lvlo[node] != 0) | (lvhi[node] != 0)))
        found = found | hit
        olo = jnp.where(hit, lvlo[node], olo)
        ohi = jnp.where(hit, lvhi[node], ohi)
        nenc = nenc + leaf.astype(jnp.int32)
        nfp = nfp + fpmatch.astype(jnp.int32)
        nfalse = nfalse + (fpmatch & ~hit).astype(jnp.int32)
        active = active & ~leaf
        lvl = jnp.clip(level[node], 0, U - 1)
        byte = jnp.take_along_axis(qbytes, lvl[:, None], axis=1)[:, 0]
        child = children[node, byte]
        active = active & (child >= 0)
        node = jnp.where(active, child, node)
    return pack_outputs(found, olo, ohi, nenc, nfp, nfalse)
