"""Host wrapper: radix node-page exports -> art_descend calls.

Splits 64-bit leaf words into int32 halves, extracts big-endian key
units (8-bit bytes for P-ART, 4-bit nibbles for P-HOT — the export's
``unit_bits`` field selects), pads the query batch to a small family of
shapes, and recombines the halves of the result.

The descent carries the export's ``leaf_fp`` partial-key fingerprint
lane: each leaf's inline byte is compared before the full 64-bit key
words, and the filter's hit/false-positive counts plus the modeled PM
gather traffic fold into the caller's ``stats`` dict (see
kernels.probe.fingerprint.account).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ...obs import RECORDER as _OBS
from ..probe import (book_upload, combine64, fetch_packed, pad_queries,
                     split64, upload_packed)
from ..probe.fingerprint import account, fp_partial
from .kernel import art_descend
from .ref import leaf_fp_lane

KEY_BYTES = 8


def key_units(keys: np.ndarray, unit_bits: int = 8) -> np.ndarray:
    """[Q] int64 -> [Q, 64//unit_bits] int32 big-endian key units
    (core.art.key_byte for unit_bits=8, core.hot.nibble for 4)."""
    u = np.asarray(keys).astype(np.uint64)
    n_units = 64 // unit_bits
    shifts = np.uint64(unit_bits) * np.arange(n_units - 1, -1, -1,
                                              dtype=np.uint64)
    mask = np.uint64((1 << unit_bits) - 1)
    return ((u[:, None] >> shifts[None, :]) & mask).astype(np.int32)


def key_bytes(keys: np.ndarray) -> np.ndarray:
    """[Q] int64 -> [Q, 8] int32 big-endian bytes (core.art.key_byte)."""
    return key_units(keys, 8)


def _prepare(arrays: Dict[str, np.ndarray],
             stats: Optional[dict] = None) -> tuple:
    """Device-ready node pages: split leaf words, convert once, in a
    ``snapshot.upload`` span whose bytes are booked into ``stats``."""
    with _OBS.span("snapshot.upload", kernel="art_probe") as sp:
        lklo, lkhi = split64(arrays["leaf_key"])
        lvlo, lvhi = split64(arrays["leaf_val"])
        lfp = leaf_fp_lane(arrays).astype(np.int32)
        pages = [jnp.asarray(arrays["children"]),
                 jnp.asarray(arrays["level"], jnp.int32),
                 jnp.asarray(arrays["is_leaf"], jnp.int32),
                 jnp.asarray(lfp),
                 jnp.asarray(lklo), jnp.asarray(lkhi),
                 jnp.asarray(lvlo), jnp.asarray(lvhi)]
        book_upload(stats, sp, pages, wait=True)
    return (int(arrays.get("unit_bits", 8)), *pages)


def _descend(queries: np.ndarray, pages: tuple, *,
             fingerprints: bool = True, stats: Optional[dict] = None
             ) -> Tuple[np.ndarray, np.ndarray]:
    unit_bits, *node_pages = pages
    q = np.asarray(queries, np.int64)
    Q = q.shape[0]
    pad = pad_queries(Q)
    with _OBS.span("kernel.art_probe", batch=Q, padded=Q + pad,
                   unit_bits=unit_bits, fingerprints=fingerprints) as sp:
        with _OBS.span("kernel.launch") as lsp:
            if pad:
                q = np.pad(q, (0, pad))  # padded lanes miss at the leaf
            qlo, qhi = split64(q)
            qfp = fp_partial(q).astype(np.int32)
            host = np.concatenate([key_units(q, unit_bits).T,
                                   np.stack([qlo, qhi, qfp])])
            out = art_descend(upload_packed(host, lsp, stats), *node_pages)
        found, olo, ohi, nenc, nfp, nfalse = fetch_packed(out)[:, :Q]
        found = found.astype(bool)
        values = combine64(olo, ohi)
        # lanes = leaves actually reached (the radix descent has no
        # fixed window; internal hops are index words, not key lanes)
        lanes = int(nenc.sum())
        if fingerprints:
            cand = int(nfp.sum())
            false = int(nfalse.sum())
            account(stats, lanes=lanes, fp_candidates=cand,
                    fp_hits=cand - false, fp_false=false, fingerprints=True)
            if sp:
                sp.set(fp_candidates=cand, fp_false_positives=false)
        else:
            account(stats, lanes=lanes, fp_candidates=0, fp_hits=0,
                    fp_false=0, fingerprints=False)
    return found, np.where(found, values, 0)


def batched_lookup(queries: np.ndarray, arrays: Dict[str, np.ndarray], *,
                   fingerprints: bool = True, stats: Optional[dict] = None
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """queries: [Q] int64; arrays: PART/PHOT export_arrays output.
    Returns (found [Q] bool, values [Q] int64), bit-identical to the
    scalar ``lookup`` against the same snapshot."""
    return _descend(queries, _prepare(arrays, stats),
                    fingerprints=fingerprints, stats=stats)


def snapshot_lookup(snap, queries: np.ndarray, *, fingerprints: bool = True,
                    stats: Optional[dict] = None
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Batched lookup against an ``IndexSnapshot`` of PART or PHOT node
    pages; the split + device conversion is memoized on the snapshot."""
    pages = snap.cache.get("art_probe")
    if pages is None:
        pages = _prepare(snap.arrays, stats)
        snap.cache["art_probe"] = pages
    return _descend(queries, pages, fingerprints=fingerprints, stats=stats)
