"""Batched P-CLHT lookup over the arrays PCLHT.export_arrays produces.

The probe-window gather lives in kernels/probe (shared with the other
index front-ends); this module contributes only what is CLHT-specific:
the splitmix64 bucket hash, mirrored bit-for-bit from core.clht._mix so
a batched query probes exactly the bucket the scalar reader would.  The
wide compare runs on full 64-bit keys via the paired-half probe64
kernel — results are bit-identical to scalar ``lookup``, including
values that exceed 32 bits.
"""

from __future__ import annotations

import functools
from collections.abc import Sequence
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...obs import RECORDER as _OBS
from ..probe import (book_upload, combine64, fetch_packed, pack_outputs,
                     pad_queries, probe64_lookup, split64, upload_packed)
from ..probe.fingerprint import account, fp64
from ..probe.kernel import probe64, probe64_fp

SLOTS = 3

_U64 = np.uint64


def mix64(keys: np.ndarray) -> np.ndarray:
    """Vectorized splitmix64 finalizer — must match core.clht._mix."""
    z = keys.astype(np.uint64) + _U64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> _U64(30))) * _U64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> _U64(27))) * _U64(0x94D049BB133111EB)
    return z ^ (z >> _U64(31))


def batched_lookup(queries: np.ndarray, keys: np.ndarray, vals: np.ndarray,
                   nxt: np.ndarray, *, n_buckets: int,
                   fps: Optional[np.ndarray] = None, fingerprints: bool = True,
                   stats: Optional[dict] = None
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """queries: [Q] int64; keys/vals: [R, SLOTS] int64 bucket-major slot
    arrays; nxt: [R] int64 chain row index (-1 none); fps: [R, SLOTS]
    uint8 fingerprint lane — the layout of PCLHT.export_arrays.
    Returns (found [Q] bool, values [Q] int64)."""
    q = np.asarray(queries, np.int64)
    bucket = (mix64(q) % _U64(n_buckets)).astype(np.int64)
    return probe64_lookup(q, bucket, np.asarray(nxt, np.int64),
                          keys, vals, fps=fps, fingerprints=fingerprints,
                          stats=stats)


@functools.partial(jax.jit, static_argnames=("depth", "use_fp"))
def _gather_probe(queries, klo, khi, vlo, vhi, fps, nxt, *,
                  depth: int, use_fp: bool):
    """Fused probe: the XLA gather chases each query's overflow chain
    (``depth`` = the snapshot's longest chain) and feeds the windows
    straight to the probe64 kernel — nothing materializes on the host.
    With ``use_fp`` the fingerprint lane is windowed alongside and the
    fingerprint-compare pre-pass kernel runs instead.  ``queries`` is
    the one packed upload, [4, Q] int32 rows (bucket, qlo, qhi, qfp);
    the result is the one packed download, [5, Q] int32 rows (found,
    value_lo, value_hi, n_fp_match, n_fp_false), or its first 3 rows
    without ``use_fp``."""
    bucket, qlo, qhi, qfp = queries
    with jax.named_scope("chain_walk"):
        rows = []
        cur = bucket
        for _ in range(depth):
            rows.append(cur)
            cur = jnp.where(cur >= 0, nxt[jnp.maximum(cur, 0)], -1)
        arrays = (klo, khi, vlo, vhi) + ((fps,) if use_fp else ())
        windows = []
        for arr in arrays:
            parts = [jnp.where(r[:, None] >= 0, arr[jnp.maximum(r, 0)], 0)
                     for r in rows]
            windows.append(jnp.concatenate(parts, axis=1))
    with jax.named_scope("probe"):
        if use_fp:
            return pack_outputs(*probe64_fp(qlo, qhi, qfp, *windows))
        return pack_outputs(*probe64(qlo, qhi, *windows))


def _chain_depth(nxt: np.ndarray) -> int:
    """The longest overflow chain, in rows, of an export's ``nxt``."""
    depth, cur = 1, nxt[nxt >= 0]
    while cur.size and depth < 64:
        depth += 1
        hops = nxt[cur]
        cur = hops[hops >= 0]
    return depth


def _prepare(snap, stats: Optional[dict]) -> tuple:
    """The per-epoch device form of a PCLHT export: int32 halves of the
    slot words, the fingerprint lane, the chain pointers, and the
    longest overflow chain."""
    with _OBS.span("snapshot.upload", kernel="clht_probe") as sp:
        keys, vals, nxt, n, fps = snap.arrays
        nxt = np.asarray(nxt, np.int64)
        depth = _chain_depth(nxt)
        halves = [jnp.asarray(h) for kv in (keys, vals) for h in split64(kv)]
        fps_dev = jnp.asarray(np.asarray(fps, np.int32))
        nxt_dev = jnp.asarray(nxt.astype(np.int32))
        book_upload(stats, sp, halves + [fps_dev, nxt_dev], wait=True)
    return halves, fps_dev, nxt_dev, depth, int(n)


# Rows of every scatter call (a block uploads 272 KiB).  One shape per
# table, so the first delta compiles the only program a delta ever runs;
# patch sizes bucketed by powers of two let a rarely seen bucket compile
# long after the warm-up.  A longer patch runs as several blocks.
PATCH_ROWS = 4096
# columns of a packed patch row: row index, then 3 slots each of the
# key and value halves and of the fingerprint lane, then the chain row
_PATCH_COLS = 1 + 5 * SLOTS + 1


@jax.jit
def _scatter_rows(arrays, patch):
    """Row scatter of one packed patch block into the device form.  The
    buffers are not donated: the stale snapshot's form stays intact for
    the reads that still probe it.  Padding rows index one past the
    table and are dropped."""
    rows = patch[:, 0]
    cols = [patch[:, 1 + i * SLOTS:1 + (i + 1) * SLOTS] for i in range(5)]
    cols.append(patch[:, 1 + 5 * SLOTS])
    return tuple(a.at[rows].set(c, mode="drop")
                 for a, c in zip(arrays, cols))


def patch_prepared(prepared: tuple, rows: np.ndarray, keys: np.ndarray,
                   vals: np.ndarray, nxt: np.ndarray, fps: np.ndarray, *,
                   relinked: bool, stats: Optional[dict]) -> tuple:
    """The device form of a delta export: ``prepared`` with bucket rows
    ``rows`` replaced by (``keys``, ``vals``, ``nxt``, ``fps``), the
    layout of ``PCLHT.export_arrays`` for those rows alone.  The rows
    are packed into blocks of ``PATCH_ROWS``, uploaded, and scattered
    one block per jitted call; the longest chain is measured again only
    when ``relinked`` says a chain pointer may have changed."""
    halves, fps_dev, nxt_dev, depth, n = prepared
    k = int(rows.size)
    with _OBS.span("snapshot.upload", kernel="clht_probe", delta=True,
                   rows=k) as sp:
        if not k:
            book_upload(stats, sp, [])
            return prepared
        blocks = -(-k // PATCH_ROWS)
        patch = np.zeros((blocks * PATCH_ROWS, _PATCH_COLS), np.int32)
        patch[:, 0] = nxt_dev.shape[0]  # padding: out of range, dropped
        patch[:k, 0] = rows
        for i, h in enumerate((*split64(keys), *split64(vals), fps)):
            patch[:k, 1 + i * SLOTS:1 + (i + 1) * SLOTS] = h
        patch[:k, 1 + 5 * SLOTS] = nxt
        patch_dev = [jnp.asarray(b) for b in np.split(patch, blocks)]
        book_upload(stats, sp, patch_dev)
        arrays = (*halves, fps_dev, nxt_dev)
        for block in patch_dev:
            arrays = _scatter_rows(arrays, block)
        *halves, fps_dev, nxt_dev = arrays
        if relinked:
            depth = _chain_depth(np.asarray(nxt_dev).astype(np.int64))
        if sp:
            jax.block_until_ready(nxt_dev)
    return halves, fps_dev, nxt_dev, depth, n


class DeviceExport(Sequence):
    """The host layout of ``PCLHT.export_arrays`` — (keys, vals, nxt,
    n_buckets, fps) — of a delta export, read back from its device
    form on first use.  A delta keeps no host copy of the table; the
    few callers that want one get this exact view of the epoch."""

    def __init__(self, prepared: tuple):
        self._prepared = prepared
        self._arrays: Optional[tuple] = None

    def _host(self) -> tuple:
        if self._arrays is None:
            halves, fps_dev, nxt_dev, _, n = self._prepared
            klo, khi, vlo, vhi = (np.asarray(h) for h in halves)
            self._arrays = (combine64(klo, khi), combine64(vlo, vhi),
                            np.asarray(nxt_dev).astype(np.int64), n,
                            np.asarray(fps_dev).astype(np.uint8))
        return self._arrays

    def __getitem__(self, i):
        return self._host()[i]

    def __len__(self) -> int:
        return 5


def snapshot_lookup(snap, queries: np.ndarray, *, fingerprints: bool = True,
                    stats: Optional[dict] = None
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Batched lookup against an ``IndexSnapshot`` of PCLHT arrays.

    Per epoch (memoized on the snapshot): split the table into int32
    halves (plus the export's fingerprint lane), ship it to the
    device, and measure the longest overflow chain.  Per batch: 64-bit
    bucket hash on the host (splitmix64 needs real uint64), then one
    fused gather+probe call — fingerprint pre-pass first when
    ``fingerprints`` is on, with filter counts folded into ``stats``.
    A batch makes one upload (bucket, key halves and fingerprint
    stacked [4, Q]) and one download (the outputs stacked [5, Q] or
    [3, Q]); padded rows are sliced off before anything is counted."""
    prepared = snap.cache.get("clht_probe")
    if prepared is None:
        prepared = snap.cache["clht_probe"] = _prepare(snap, stats)
    halves, fps_dev, nxt_dev, depth, n = prepared
    q = np.asarray(queries, np.int64)
    Q = q.shape[0]
    W = depth * SLOTS
    pad = pad_queries(Q)
    with _OBS.span("kernel.clht_probe", batch=Q, padded=Q + pad,
                   depth=depth, fingerprints=fingerprints) as sp:
        with _OBS.span("kernel.launch") as lsp:
            if pad:
                # padded queries are 0 == the empty-slot sentinel; they
                # probe bucket mix64(0) % n and the rows are sliced off
                q = np.pad(q, (0, pad))
            bucket = (mix64(q) % _U64(n)).astype(np.int32)
            qlo, qhi = split64(q)
            qfp = fp64(q).astype(np.int32)
            packed = upload_packed(np.stack([bucket, qlo, qhi, qfp]), lsp,
                                   stats)
            out = _gather_probe(packed, *halves, fps_dev, nxt_dev,
                                depth=depth, use_fp=fingerprints)
        out = fetch_packed(out)[:, :Q]
        found = out[0].astype(bool)
        values = combine64(out[1], out[2])
        if fingerprints:
            cand = int(out[3].sum())
            false = int(out[4].sum())
            account(stats, lanes=Q * W, fp_candidates=cand,
                    fp_hits=cand - false, fp_false=false, fingerprints=True)
            if sp:
                sp.set(fp_candidates=cand, fp_false_positives=false)
        else:
            account(stats, lanes=Q * W, fp_candidates=0, fp_hits=0,
                    fp_false=0, fingerprints=False)
    return found, np.where(found, values, 0)
