"""Host front-end for plan-conflict detection.

``conflict_any`` is the entry point the scheduler tests and TPU-
resident pipelines use: candidate ops against a reference op set,
True where a candidate cannot share a conflict-free wave with the set.
Like kernels/partition, the host numpy oracle is the default — wave
scheduling is control-plane work consumed op-run by op-run — and
``use_kernel=True`` runs the Pallas lane-blocked form, bit-identical,
for kernel-vs-ref tests and on-device schedules.
"""

from __future__ import annotations

import numpy as np

from ...obs import RECORDER as _OBS
from .ref import (DELETE, GET, PUT, SCAN, UPDATE, conflict_any_ref,
                  conflict_matrix_ref, is_write_kind, wave_levels_ref)


def _pad_pow2(n: int, block: int) -> int:
    """Smallest padded length: a multiple of ``block``, or the next
    power of two >= 8 below one block (mirrors partition/ops)."""
    if n >= block:
        return n + ((-n) % block)
    p = 8
    while p < n:
        p <<= 1
    return p


def conflict_any(kinds_a, keys_a, kinds_b, keys_b, *,
                 writes_conflict: bool = False, use_kernel: bool = False
                 ) -> np.ndarray:
    """[A] bool: does each candidate op conflict with any reference op."""
    kinds_a = np.asarray(kinds_a, np.int32)
    kinds_b = np.asarray(kinds_b, np.int32)
    keys_a = np.asarray(keys_a, np.int64)
    keys_b = np.asarray(keys_b, np.int64)
    with _OBS.span("kernel.conflict", batch=int(kinds_a.size),
                   ref=int(kinds_b.size), use_kernel=use_kernel):
        if not use_kernel or kinds_a.size == 0 or kinds_b.size == 0:
            return conflict_any_ref(kinds_a, keys_a, kinds_b, keys_b,
                                    writes_conflict=writes_conflict)
        from ..probe import split64  # jax import deferred: jax-less fallback
        from .kernel import CAND_BLOCK, NONE, conflict_any_kernel
        A, B = kinds_a.shape[0], kinds_b.shape[0]
        pa = _pad_pow2(A, CAND_BLOCK) - A
        pb = (-B) % 128  # lane axis: pad the reference set to full lanes
        ka = np.pad(kinds_a, (0, pa), constant_values=NONE)
        kb = np.pad(kinds_b, (0, pb), constant_values=NONE)
        alo, ahi = split64(np.pad(keys_a, (0, pa)))
        blo, bhi = split64(np.pad(keys_b, (0, pb)))
        import jax.numpy as jnp
        out = conflict_any_kernel(
            jnp.asarray(ka), jnp.asarray(alo), jnp.asarray(ahi),
            jnp.asarray(kb), jnp.asarray(blo), jnp.asarray(bhi),
            writes_conflict=writes_conflict)
        return np.asarray(out)[:A].astype(bool)


__all__ = ["DELETE", "GET", "PUT", "SCAN", "UPDATE", "conflict_any",
           "conflict_any_ref", "conflict_matrix_ref", "is_write_kind",
           "wave_levels_ref"]
