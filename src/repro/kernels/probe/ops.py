"""Host-side helpers shared by the batched index front-ends.

The control plane hands us int64 PM words (keys < 2^63, values up to
62 bits); the TPU data plane wants int32 lanes.  These helpers split
words into (lo, hi) halves, gather per-query probe windows by chasing
overflow chains, and pad query batches to the kernel's block multiple.
All of it is plain numpy: the gathers are snapshot-array indexing (the
XLA/VPU work is the wide compare in kernel.py), and 64-bit hashing
cannot run inside default-precision jax anyway.

The read kernels' transfer protocol is here too: every read-kernel call
stacks its per-query arrays into one int32 array on the host and
uploads it once (``upload_packed``), and its jitted program stacks its
outputs into one int32 array (``pack_outputs``) that the host downloads
once (``fetch_packed``).  Each transfer is a round trip to the device,
so the count, not the bytes, sets a small batch's wait.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...obs import RECORDER as _OBS
from .fingerprint import account, fp64
from .kernel import QUERY_BLOCK, probe64, probe64_fp

LANES = 128  # pad probe windows to whole VREG rows

_M32 = np.uint64(0xFFFFFFFF)


def split64(a: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """int64 words -> (lo, hi) int32 halves (bit-exact round trip)."""
    u = np.asarray(a).astype(np.uint64)
    lo = (u & _M32).astype(np.uint32).astype(np.int32)
    hi = (u >> np.uint64(32)).astype(np.uint32).astype(np.int32)
    return lo, hi


def combine64(lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """(lo, hi) int32 halves -> int64 words."""
    u = (np.asarray(hi).astype(np.int64) & 0xFFFFFFFF) << 32
    return u | (np.asarray(lo).astype(np.int64) & 0xFFFFFFFF)


def book_upload(stats: Optional[dict], sp, arrays: Sequence, *,
                wait: bool = False) -> None:
    """Count ``arrays`` (just handed to the device) as host->device
    bytes: their ``nbytes`` go to ``stats["upload_bytes"]`` and to the
    open span's ``bytes``.  With ``wait`` and tracing on, block until
    the transfer lands, so the span holds the copy and not only its
    dispatch; with tracing off nothing blocks."""
    nbytes = sum(int(a.nbytes) for a in arrays)
    if stats is not None:
        stats["upload_bytes"] += nbytes
    if sp:
        if wait:
            jax.block_until_ready(list(arrays))
        sp.set(bytes=nbytes, arrays=len(arrays))


def upload_packed(host: np.ndarray, sp, stats: Optional[dict] = None):
    """A read-kernel call's one upload: the packed int32 query array
    ``host`` goes to the device in a single transfer, booked as
    ``book_upload`` books it (the open span's ``arrays`` reads 1)."""
    dev = jnp.asarray(host)
    book_upload(stats, sp, [dev])
    return dev


def pack_outputs(*outs):
    """Inside a jitted read program: its outputs stacked into one int32
    array (``found``/``valid`` as 0/1), the call's single download."""
    return jnp.stack([o.astype(jnp.int32) for o in outs])


def fetch_packed(out) -> np.ndarray:
    """A read-kernel call's one download: ``out``, the program's packed
    int32 output, in a ``kernel.fetch`` span (which holds the wait for
    the program too)."""
    with _OBS.span("kernel.fetch", arrays=1):
        return np.asarray(out)


def gather_chain_windows(start: np.ndarray, nxt: np.ndarray,
                         slot_arrays: Sequence[np.ndarray],
                         *, max_chain: int = 64) -> List[np.ndarray]:
    """Per-query probe windows over chained rows.

    start: [Q] row index of each query's head bucket; nxt: [R] next-row
    index (-1 = end of chain); each of ``slot_arrays`` is a row-major
    [R, S] slot array (e.g. the lo/hi halves of keys and values) that
    gets windowed identically.  Follows every chain to its end (up to
    ``max_chain`` hops, matching the scalar reader's full-chain walk)
    and returns [Q, depth*S] windows, zero-padded where a chain ends
    early — so a wide compare over a window sees exactly the slots the
    scalar probe would."""
    rows: List[List[np.ndarray]] = [[] for _ in slot_arrays]
    cur = start.astype(np.int64)
    for _ in range(max_chain):
        live = cur >= 0
        if not live.any() and rows[0]:
            break
        safe = np.where(live, cur, 0)
        mask = live[:, None]
        for out, arr in zip(rows, slot_arrays):
            out.append(np.where(mask, arr[safe], 0))
        cur = np.where(live, nxt[safe], -1)
    windows = [np.concatenate(r, axis=1) for r in rows]
    pad = (-windows[0].shape[1]) % LANES
    if pad:
        windows = [np.pad(w, ((0, 0), (0, pad))) for w in windows]
    return windows


def pad_queries(n: int, block: int = QUERY_BLOCK) -> int:
    """Rows to add to the query batch before a jit'd probe.

    Above one block: round up to a whole number of blocks.  Below one
    block: round up to the next power of two, so the family of traced
    shapes stays small (serving batches drift by a few queries every
    step; retracing per distinct count would dwarf the probe itself)."""
    if n >= block:
        return (-n) % block
    p = 8
    while p < n:
        p <<= 1
    return p - n


@functools.partial(jax.jit, static_argnames=("use_fp",))
def _probe_packed(packed, *, use_fp: bool):
    """probe64 (or probe64_fp) over one packed upload: [Q, C + N*W]
    int32, the C query columns (qlo, qhi[, qfp]) and then the N windows
    (klo, khi, vlo, vhi[, wfp]) side by side.  Returns the outputs
    stacked, [3, Q] (or [5, Q])."""
    n_cols = 3 if use_fp else 2
    n_win = n_cols + 2
    W = (packed.shape[1] - n_cols) // n_win
    cols = [packed[:, i] for i in range(n_cols)]
    wins = [packed[:, n_cols + i * W:n_cols + (i + 1) * W]
            for i in range(n_win)]
    return pack_outputs(*(probe64_fp if use_fp else probe64)(*cols, *wins))


def probe64_windows(queries: np.ndarray, split_windows: Sequence[np.ndarray],
                    *, fp_window: Optional[np.ndarray] = None,
                    fingerprints: bool = True, stats: Optional[dict] = None
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Run probe64 over pre-gathered, pre-split windows.

    queries: [Q] int64; split_windows: (klo, khi, vlo, vhi), each
    [Q, W] int32.  Returns (found [Q] bool, values [Q] int64).

    With ``fp_window`` (the windowed fingerprint lane, [Q, W] with
    FP_EMPTY=0 where the key lane is 0-padded) and ``fingerprints``
    on, the fingerprint-compare pre-pass runs: full keys are verified
    only where the 1-byte lane matched.  Results are bit-identical
    either way (a true hit always fingerprint-matches); the filter's
    hit/false-positive counts and the modeled PM gather traffic fold
    into ``stats`` (see fingerprint.account)."""
    Q = queries.shape[0]
    klo, khi, vlo, vhi = split_windows
    W = int(klo.shape[1])
    use_fp = fingerprints and fp_window is not None
    pad = pad_queries(Q)
    with _OBS.span("kernel.probe64", batch=Q, padded=Q + pad, window=W,
                   fingerprints=use_fp) as sp:
        with _OBS.span("kernel.launch") as lsp:
            if pad:
                # padded queries are 0 == the empty-slot sentinel, so they
                # may "hit" padding slots — harmless, rows are sliced below
                queries = np.pad(queries, (0, pad))
                klo, khi, vlo, vhi = (np.pad(w, ((0, pad), (0, 0)))
                                      for w in (klo, khi, vlo, vhi))
            qlo, qhi = split64(queries)
            cols = [qlo, qhi]
            windows = [klo, khi, vlo, vhi]
            if use_fp:
                if pad:
                    fp_window = np.pad(fp_window, ((0, pad), (0, 0)))
                cols.append(fp64(queries).astype(np.int32))
                windows.append(fp_window.astype(np.int32))
            host = np.concatenate([np.stack(cols, axis=1), *windows],
                                  axis=1)
            out = _probe_packed(upload_packed(host, lsp, stats),
                                use_fp=use_fp)
        out = fetch_packed(out)[:, :Q]
        found = out[0].astype(bool)
        values = combine64(out[1], out[2])
        if use_fp:
            # counters over the real (un-padded) query rows only
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


def probe64_lookup(queries: np.ndarray, start: np.ndarray, nxt: np.ndarray,
                   keys: np.ndarray, vals: np.ndarray, *,
                   fps: Optional[np.ndarray] = None, fingerprints: bool = True,
                   stats: Optional[dict] = None
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Gather chain windows from int64 slot arrays and run probe64.

    queries: [Q] int64; start: [Q] head-row indices; nxt/keys/vals as in
    ``gather_chain_windows``; fps: the [R, S] fingerprint lane of the
    export (computed from ``keys`` when omitted).  Returns (found [Q]
    bool, values [Q] int64), bit-identical to a scalar chain walk +
    64-bit compare.  Epoch-cached callers pre-split the slot arrays
    once and use ``probe64_windows`` with int32 halves instead."""
    klo, khi = split64(keys)
    vlo, vhi = split64(vals)
    if fps is None and fingerprints:
        fps = fp64(keys)
    slot_arrays = (klo, khi, vlo, vhi) + ((fps,) if fps is not None else ())
    windows = gather_chain_windows(start, nxt, slot_arrays)
    fpw = windows[4] if fps is not None else None
    return probe64_windows(queries, windows[:4], fp_window=fpw,
                           fingerprints=fingerprints, stats=stats)
