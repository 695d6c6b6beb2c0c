"""Host wrapper: sorted (keys, vals) export -> scan_window calls.

Splits the 64-bit sorted run into int32 halves (low halves XOR-biased
so signed lane compares realize unsigned 64-bit order), pads query
batches to a small family of shapes, and re-assembles per-query result
rows.  A batch makes one upload (query halves and counts stacked
[3, Q]) and one download (the outputs stacked [5, Q, C]).
The prepared device form is memoized on the ``IndexSnapshot`` under the
``"scan"`` cache key, so steady-state batches pay gather + kernel only.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ...obs import RECORDER as _OBS
from ..probe import (book_upload, combine64, fetch_packed, split64,
                     upload_packed)
from ..probe.fingerprint import account, fp64
from .kernel import scan_window

# window widths are rounded up to whole lane rows so the family of
# traced shapes stays small (YCSB-E counts are 1..100 -> always 128)
SCAN_LANES = 128

# query batches are padded to whole QUERY_ROWS multiples (not the
# next-power-of-two family the lookup kernels use): scan batches are
# few-and-heavy, so one fixed row count per (run-shape, window) keeps
# the jit cache at a single entry while the padded-lane overhead stays
# far below one window gather; above QUERY_BLOCK, whole blocks
QUERY_ROWS = 512
QUERY_BLOCK = 4096

_BIAS = np.int32(-(1 << 31))
_EMPTY = ("scan-empty",)  # cache sentinel for an empty structure


def prepare_sorted(keys: np.ndarray, vals: np.ndarray,
                   stats: Optional[dict] = None) -> tuple:
    """Device-ready form of a sorted run: biased/split halves + the
    live count and lower-bound step budget.

    The run is zero-padded to a power of two so the traced
    shapes survive epoch changes (a write-heavy phase re-exports with
    a slightly different N every batch; without padding each would
    retrace).  The search interval is bounded by the live count and
    the window gather masks ``pos < n``, so the padding is never
    observed.  The whole preparation runs in a ``snapshot.upload``
    span; its bytes are booked into ``stats``."""
    with _OBS.span("snapshot.upload", kernel="scan") as sp:
        k = np.asarray(keys, np.int64)
        v = np.asarray(vals, np.int64)
        n = int(k.shape[0])
        n_pad = 128
        while n_pad < n:
            n_pad <<= 1
        if n_pad > n:
            k = np.pad(k, (0, n_pad - n))
            v = np.pad(v, (0, n_pad - n))
        klo, khi = split64(k)
        vlo, vhi = split64(v)
        steps = max(1, n_pad.bit_length())
        dev = [jnp.asarray(klo ^ _BIAS), jnp.asarray(khi),
               jnp.asarray(vlo), jnp.asarray(vhi), jnp.asarray(n, jnp.int32)]
        book_upload(stats, sp, dev, wait=True)
    return (*dev, n, steps)


def _run_kernel(queries: np.ndarray, counts: np.ndarray, prepared: tuple,
                *, lane_round: int = SCAN_LANES,
                stats: Optional[dict] = None):
    klo, khi, vlo, vhi, n_dev, n, steps = prepared
    q = np.asarray(queries, np.int64)
    c = np.asarray(counts, np.int32)
    Q = q.shape[0]
    C = max(1, int(c.max()) if c.size else 1)
    C = -(-C // lane_round) * lane_round
    pad = (-Q) % (QUERY_BLOCK if Q > QUERY_BLOCK else QUERY_ROWS)
    with _OBS.span("kernel.scan", batch=Q, padded=Q + pad, window=C):
        with _OBS.span("kernel.launch") as lsp:
            if pad:
                # padded queries carry count 0, so their rows come back
                # empty
                q = np.pad(q, (0, pad))
                c = np.pad(c, (0, pad))
            qlo, qhi = split64(q)
            packed = upload_packed(np.stack([qlo ^ _BIAS, qhi, c]), lsp,
                                   stats)
            out = scan_window(packed, klo, khi, vlo, vhi, n_dev,
                              steps=steps, max_count=C)
        valid, oklo, okhi, ovlo, ovhi = fetch_packed(out)[:, :Q]
        valid = valid.astype(bool)
        okeys = combine64(oklo, okhi)
        ovals = combine64(ovlo, ovhi)
    return valid, okeys, ovals


def sorted_lookup(queries: np.ndarray, prepared: tuple, *,
                  fingerprints: bool = True, stats: Optional[dict] = None
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Point lookups over a prepared sorted run: lower bound + window of
    1 + key-equality check.  Returns (found [Q] bool, values [Q] int64),
    bit-identical to a scalar binary search.

    The fingerprint lane of a sorted-run export is ``fp64(keys)`` by
    protocol, so the filter outcome at the lower-bound entry is exactly
    ``fp64(q) == fp64(okeys)`` — the accounting below reconstructs it
    from the gathered candidate keys (the search path itself touches
    index words, not key lanes, and is not fingerprinted)."""
    q = np.asarray(queries, np.int64)
    # lane_round=1: a lookup needs a window of exactly one entry — no
    # point gathering a full 128-lane scan row per query
    valid, okeys, ovals = _run_kernel(q, np.ones(q.shape[0], np.int32),
                                      prepared, lane_round=1, stats=stats)
    live = valid[:, 0]
    found = live & (okeys[:, 0] == q)
    lanes = int(live.sum())
    if fingerprints:
        # empty lanes gather key 0 whose fp is FP_EMPTY; query fps are
        # >= 1, so the lane mask is already folded into the compare
        fpmatch = live & (fp64(q) == fp64(okeys[:, 0]))
        cand = int(fpmatch.sum())
        false = int((fpmatch & ~found).sum())
        account(stats, lanes=lanes, fp_candidates=cand,
                fp_hits=cand - false, fp_false=false, fingerprints=True)
    else:
        account(stats, lanes=lanes, fp_candidates=0, fp_hits=0,
                fp_false=0, fingerprints=False)
    return found, np.where(found, ovals[:, 0], 0)


def sorted_scan(starts: np.ndarray, counts: np.ndarray, prepared: tuple,
                stats: Optional[dict] = None
                ) -> List[List[Tuple[int, int]]]:
    """Range scans over a prepared sorted run: per query, the first
    ``counts[i]`` entries with key >= starts[i] in ascending order."""
    valid, okeys, ovals = _run_kernel(starts, counts, prepared,
                                      stats=stats)
    out: List[List[Tuple[int, int]]] = []
    for row_ok, row_k, row_v in zip(valid, okeys, ovals):
        m = int(row_ok.sum())  # prefix mask: first m lanes are live
        out.append(list(zip(row_k[:m].tolist(), row_v[:m].tolist())))
    return out


Exporter = Callable[[], Optional[Tuple[np.ndarray, np.ndarray]]]


def _prepared_from(snap, exporter: Exporter, stats: Optional[dict]):
    prepared = snap.cache.get("scan")
    if prepared is None:
        arrays = exporter()
        prepared = (_EMPTY if arrays is None
                    else prepare_sorted(*arrays, stats=stats))
        snap.cache["scan"] = prepared
    return None if prepared is _EMPTY else prepared


def snapshot_lookup(snap, queries: np.ndarray, *, fingerprints: bool = True,
                    stats: Optional[dict] = None
                    ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Batched lookup against an ``IndexSnapshot`` whose ``arrays`` is
    the sorted {"keys", "vals"} export (P-Masstree / P-BwTree /
    P-CCEH / FAST&FAIR / Level hashing); the split + device conversion
    is memoized on the snapshot."""
    prepared = _prepared_from(
        snap, lambda: None if snap.arrays is None
        else (snap.arrays["keys"], snap.arrays["vals"]), stats)
    if prepared is None:
        return None
    return sorted_lookup(queries, prepared, fingerprints=fingerprints,
                         stats=stats)


def snapshot_scan(snap, starts: Sequence[int], counts: Sequence[int],
                  exporter: Exporter, stats: Optional[dict] = None
                  ) -> Optional[List[List[Tuple[int, int]]]]:
    """Batched range scans against an ``IndexSnapshot``; ``exporter``
    supplies the sorted run on first use (None for an empty structure)
    and the prepared form is memoized on the snapshot."""
    prepared = _prepared_from(snap, exporter, stats)
    if prepared is None:
        return None
    return sorted_scan(np.asarray(starts, np.int64),
                       np.asarray(counts, np.int64), prepared, stats)
