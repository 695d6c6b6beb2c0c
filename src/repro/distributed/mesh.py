"""Device-mesh fan-out for sharded point lookups.

``ShardedIndex`` executes general plans as per-shard sub-plans (each
shard's own probe kernels against its own PMem).  For the all-GET hot
path — the YCSB-C chunk, the serving decode tick — this module fuses
all S shards' probes into ONE dispatch: every shard's sorted run is
padded and stacked on a leading shard axis, queries are grouped by
route and stacked the same way, and a vmapped lower-bound search
answers all shards at once.

Execution placement:

* with >= S local devices, the vmapped probe is wrapped in
  ``jax.shard_map`` over a 1-D ``("shard",)`` mesh, so each shard's
  run and queries live on — and are probed by — their own device;
* otherwise (the only path on a 1-device host) the plain ``jax.vmap``
  form runs the same program on one device, bit-identical.  Which one
  ran is visible: the ``shard.mesh_lookup`` span carries
  ``placement`` ("devices" or "one_device").

The jitted program is ``mesh_probe`` in either placement, so a device
trace names it ``jit_mesh_probe``.  As the read kernels of ``kernels/``
do, a call makes one upload (every shard's query halves stacked
``[S, 2, q_pad]``, onto one device; the jit reshards it) and one
download (the outputs stacked ``[S, 3, q_pad]``, gathered from every
shard's device).  Traced, ``mesh_lookup`` opens a ``kernel.launch``
span (query padding, splits, the upload, the call) and a
``kernel.fetch`` span (the download, so the wait for the program too).

64-bit keys are handled the same way kernels/scan handles them, with
its ``lower_bound``: split into int32 halves with the low half
XOR-biased, so signed lane compares realize unsigned 64-bit order
without requiring jax x64 mode.  Found/value semantics are
bit-identical to ``kernels.scan.sorted_lookup`` (lower bound +
key-equality check).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..obs import RECORDER as _OBS
from ..obs.recorder import NULL_SPAN

_BIAS = np.int32(-(1 << 31))

@dataclasses.dataclass
class StackedRuns:
    """Device-ready stacked sorted runs: one row per shard."""

    khi: object  # [S, N] int32 — key high halves (signed compare ok)
    klo: object  # [S, N] int32 — key low halves, XOR-biased
    vhi: object  # [S, N] int32 — value high halves
    vlo: object  # [S, N] int32 — value low halves
    n: object    # [S] int32 — live entries per shard
    n_pad: int   # padded run length (power of two)
    run_max: int  # longest live shard run
    steps: int   # binary-search step budget = log2(n_pad)
    n_shards: int


def _book_rows(stats: Optional[Sequence[dict]], row_bytes: int) -> None:
    """Book one shard row of ``row_bytes`` uploaded into each shard's
    ``upload_bytes``, so the per-shard counters sum to the upload."""
    for st in stats or ():
        st["upload_bytes"] += row_bytes


def build_stacked(runs: Sequence[Optional[Tuple[np.ndarray, np.ndarray]]],
                  stats: Optional[Sequence[dict]] = None) -> StackedRuns:
    """Stack per-shard sorted (keys, vals) runs (None = empty shard)
    into one [S, N] device form, N padded to a common power of two.
    With a device per shard, row s lives on device s.  Runs in a
    ``snapshot.upload`` span; ``stats`` (one probe_stats dict per
    shard) takes each shard's row of the upload."""
    with _OBS.span("snapshot.upload", kernel="mesh_lookup") as sp:
        stacked = _stack(runs)
        arrays = [stacked.khi, stacked.klo, stacked.vhi, stacked.vlo,
                  stacked.n]
        nbytes = sum(int(a.nbytes) for a in arrays)
        _book_rows(stats, nbytes // stacked.n_shards)
        if sp:
            import jax
            jax.block_until_ready(arrays)
            sp.set(bytes=nbytes)
    return stacked


def _stack(runs: Sequence[Optional[Tuple[np.ndarray, np.ndarray]]]
           ) -> StackedRuns:
    from ..kernels.probe import split64
    import jax
    import jax.numpy as jnp
    S = len(runs)
    n_live = [0 if r is None else int(r[0].shape[0]) for r in runs]
    n_pad = 128
    while n_pad < max(n_live + [1]):
        n_pad <<= 1
    khi = np.zeros((S, n_pad), np.int32)
    klo = np.zeros((S, n_pad), np.int32)
    vhi = np.zeros((S, n_pad), np.int32)
    vlo = np.zeros((S, n_pad), np.int32)
    for s, r in enumerate(runs):
        if r is None:
            continue
        k, v = r
        lo, hi = split64(np.asarray(k, np.int64))
        khi[s, :n_live[s]] = hi
        klo[s, :n_live[s]] = lo
        lo, hi = split64(np.asarray(v, np.int64))
        vhi[s, :n_live[s]] = hi
        vlo[s, :n_live[s]] = lo
    n = np.asarray(n_live, np.int32)
    if placement(S) == "devices":
        from jax.sharding import NamedSharding, PartitionSpec as P
        rows = NamedSharding(shard_mesh(S), P("shard"))
        put = lambda a: jax.device_put(a, rows)
    else:
        put = jnp.asarray
    return StackedRuns(
        khi=put(khi), klo=put(klo ^ _BIAS), vhi=put(vhi), vlo=put(vlo),
        n=put(n), n_pad=n_pad, run_max=max(n_live),
        steps=max(1, n_pad.bit_length()), n_shards=S)


def _probe_one_shard(khi, klo, vhi, vlo, n, q, *, steps: int):
    """Lower bound + equality over ONE shard's run: the per-device
    program ``shard_map``/``vmap`` replicate across the shard axis.
    ``q`` is the shard's [2, q_pad] rows (qhi, qlo biased); returns
    [3, q_pad] int32 rows (found, value hi, value lo)."""
    import jax.numpy as jnp
    from ..kernels.probe import pack_outputs
    from ..kernels.scan import lower_bound
    qhi, qlo = q
    lo = lower_bound(khi, klo, n, qhi, qlo, steps=steps)
    pos = jnp.clip(lo, 0, khi.shape[0] - 1)
    found = (lo < n) & (khi[pos] == qhi) & (klo[pos] == qlo)
    return pack_outputs(found, jnp.where(found, vhi[pos], 0),
                        jnp.where(found, vlo[pos], 0))


@functools.lru_cache(maxsize=8)
def shard_mesh(n_shards: int):
    """The 1-D ``("shard",)`` mesh over the first ``n_shards`` devices."""
    import jax
    return jax.make_mesh((n_shards,), ("shard",))


def mesh_probe(khi, klo, vhi, vlo, n, q, *, steps: int, mesh=None):
    """The all-shard probe over ``[S, ...]`` stacked inputs: the
    vmapped per-shard search, under ``shard_map`` on ``mesh`` (one
    device per shard) when one is given.  ``q`` is the one packed
    query upload, [S, 2, q_pad] int32 (qhi, qlo biased); the result is
    the one packed download, [S, 3, q_pad] int32 (found as 0/1, value
    hi, value lo), split by shard over the mesh."""
    import jax
    fn = jax.vmap(functools.partial(_probe_one_shard, steps=steps))
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        spec = P("shard")
        fn = jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * 6,
                           out_specs=spec)
    return fn(khi, klo, vhi, vlo, n, q)


@functools.lru_cache(maxsize=32)
def compiled_probe(steps: int, mesh=None):
    """``mesh_probe`` jitted for one step budget and placement, under
    its own name."""
    import jax
    probe = functools.partial(mesh_probe, steps=steps, mesh=mesh)
    probe.__name__ = mesh_probe.__name__
    return jax.jit(probe)


def placement(n_shards: int) -> str:
    """``"devices"`` when a real 1-D device mesh of ``n_shards`` is
    available (one device per shard), else ``"one_device"``."""
    import jax
    return "devices" if len(jax.devices()) >= n_shards > 1 else "one_device"


def mesh_lookup(stacked: StackedRuns,
                queries: Sequence[np.ndarray],
                stats: Optional[Sequence[dict]] = None,
                span=NULL_SPAN) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Probe all shards in one dispatch.  ``queries[s]`` is shard s's
    (possibly empty) int64 query vector; returns per-shard
    (found [Qs] bool, values [Qs] int64), bit-identical to probing each
    shard's sorted run with ``kernels.scan.sorted_lookup``.  One upload
    of the packed [S, 2, q_pad] queries and one download of the packed
    [S, 3, q_pad] outputs per call.  ``stats`` (one probe_stats dict
    per shard) takes each shard's query row of the upload; the
    caller's open ``span`` takes the pads (``q_pad``, ``n_pad``) and
    the longest live run (``run_max``)."""
    from ..kernels.probe import (combine64, fetch_packed, split64,
                                 upload_packed)
    S = stacked.n_shards
    assert len(queries) == S
    q_len = [int(np.asarray(q).shape[0]) for q in queries]
    with _OBS.span("kernel.launch") as lsp:
        q_pad = 8
        while q_pad < max(q_len + [1]):
            q_pad <<= 1
        host = np.zeros((S, 2, q_pad), np.int32)  # rows: qhi, qlo
        for s, q in enumerate(queries):
            if q_len[s]:
                lo, hi = split64(np.asarray(q, np.int64))
                host[s, 0, :q_len[s]] = hi
                host[s, 1, :q_len[s]] = lo
        host[:, 1] ^= _BIAS
        fn = compiled_probe(stacked.steps, shard_mesh(S)
                            if placement(S) == "devices" else None)
        packed = upload_packed(host, lsp)
        _book_rows(stats, int(packed.nbytes) // S)
        out = fn(stacked.khi, stacked.klo, stacked.vhi, stacked.vlo,
                 stacked.n, packed)
    out = fetch_packed(out)
    if span:
        span.set(q_pad=q_pad, n_pad=stacked.n_pad, run_max=stacked.run_max)
    found = out[:, 0].astype(bool)
    vals = combine64(out[:, 2], out[:, 1])
    return [(found[s, :q_len[s]], vals[s, :q_len[s]]) for s in range(S)]


__all__ = ["StackedRuns", "build_stacked", "compiled_probe",
           "mesh_lookup", "mesh_probe", "placement", "shard_mesh"]
