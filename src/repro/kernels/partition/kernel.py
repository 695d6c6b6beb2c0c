"""Shard routing — Pallas TPU kernel.

The VPU lanes are 32-bit, so the splitmix64 finalizer runs on (lo, hi)
uint32 half pairs with 16-bit-limb multiplies: a 64-bit multiply by a
constant C decomposes into four 16x16 partial products for the low
word (carries propagated explicitly) plus wrapping 32-bit products for
the high word — bits that would land at or above 2^64 wrap out of the
uint32 high lane exactly as they drop out of the mod-2^64 result, so
the route is bit-identical to the numpy uint64 oracle in ``ref.py``.

``prefix`` routing needs no arithmetic at all: keys are 63-bit words,
so the shard id is a shift of the high half.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import backend

SHARD_BLOCK = 4096  # queries per grid step (matches the probe kernels)


def _mul64_const(lo, hi, const: int):
    """(lo, hi) uint32 halves * 64-bit ``const``, mod 2^64."""
    low16 = jnp.uint32(0xFFFF)
    clo, chi = const & 0xFFFFFFFF, const >> 32
    a0, a1 = lo & low16, lo >> jnp.uint32(16)
    c0, c1 = jnp.uint32(clo & 0xFFFF), jnp.uint32(clo >> 16)
    p00 = a0 * c0
    p01 = a0 * c1
    p10 = a1 * c0
    # low word: p00 + ((p01 + p10) << 16), carries tracked via a 16-bit
    # middle column (mid fits uint32: ≤ 2*(2^16-1) + 2^16-1)
    mid = (p01 & low16) + (p10 & low16) + (p00 >> jnp.uint32(16))
    rlo = (p00 & low16) | ((mid & low16) << jnp.uint32(16))
    # high word: wrapping uint32 adds — overflow here is bit 64+, which
    # the mod-2^64 result discards anyway
    rhi = (a1 * c1 + (p01 >> jnp.uint32(16)) + (p10 >> jnp.uint32(16))
           + (mid >> jnp.uint32(16))
           + lo * jnp.uint32(chi) + hi * jnp.uint32(clo))
    return rlo, rhi


def _xorshift_right(lo, hi, s: int):
    """z ^= z >> s for 0 < s < 32 on (lo, hi) halves."""
    sl = jnp.uint32(s)
    lo2 = lo ^ ((lo >> sl) | (hi << jnp.uint32(32 - s)))
    hi2 = hi ^ (hi >> sl)
    return lo2, hi2


def _mix64_halves(lo, hi):
    """splitmix64 finalizer on uint32 half pairs (see core.clht._mix)."""
    # z = key + 0x9E3779B97F4A7C15
    clo = jnp.uint32(0x7F4A7C15)
    lo2 = lo + clo
    carry = (lo2 < clo).astype(jnp.uint32)
    hi2 = hi + jnp.uint32(0x9E3779B9) + carry
    lo, hi = lo2, hi2
    lo, hi = _xorshift_right(lo, hi, 30)
    lo, hi = _mul64_const(lo, hi, 0xBF58476D1CE4E5B9)
    lo, hi = _xorshift_right(lo, hi, 27)
    lo, hi = _mul64_const(lo, hi, 0x94D049BB133111EB)
    lo, hi = _xorshift_right(lo, hi, 31)
    return lo, hi


def _route_kernel(klo_ref, khi_ref, out_ref, *, bits: int, scheme: str):
    lo = jax.lax.bitcast_convert_type(klo_ref[...], jnp.uint32)
    hi = jax.lax.bitcast_convert_type(khi_ref[...], jnp.uint32)
    if bits == 0:
        out_ref[...] = jnp.zeros(lo.shape, jnp.int32)
        return
    if scheme == "hash":
        _, mhi = _mix64_halves(lo, hi)
        shard = mhi >> jnp.uint32(32 - bits)
    else:
        # prefix(@msb): route on key bits [msb, msb+1-bits).  msb=62
        # (plain 63-bit words) keeps the extraction in the high half;
        # narrower keyspaces (prefix@58: encoded string keys) may pull
        # it into the low half or straddle the halves.
        from .ref import prefix_msb
        s = prefix_msb(scheme) + 1 - bits
        assert s >= 0, (scheme, bits)
        mask = jnp.uint32((1 << bits) - 1)
        if s >= 32:  # fully in the high half
            shard = (hi >> jnp.uint32(s - 32)) & mask
        elif s + bits <= 32:  # fully in the low half
            shard = (lo >> jnp.uint32(s)) & mask
        else:  # straddles the halves (s in [2, 32) here since bits < 32)
            shard = ((hi << jnp.uint32(32 - s))
                     | (lo >> jnp.uint32(s))) & mask
    out_ref[...] = shard.astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("bits", "scheme", "query_block"))
def shard_route(klo, khi, *, bits: int, scheme: str = "hash",
                query_block: int = SHARD_BLOCK):
    """klo/khi: [Q] int32 key halves; returns [Q] int32 shard ids in
    [0, 2^bits).  ``scheme`` is 'hash' (splitmix64 top bits),
    'prefix' (key top bits), or 'prefix@<m>' (bits [m, m+1-bits) —
    narrow keyspaces such as encoded string keys)."""
    assert 0 <= bits <= 31
    Q = klo.shape[0]
    qb = min(query_block, Q)
    assert Q % qb == 0, (Q, qb)
    col = pl.BlockSpec((qb, 1), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_route_kernel, bits=bits, scheme=scheme),
        grid=(Q // qb,),
        in_specs=[col, col],
        out_specs=col,
        out_shape=jax.ShapeDtypeStruct((Q, 1), jnp.int32),
        interpret=backend.interpret(),
    )(klo.reshape(Q, 1), khi.reshape(Q, 1))
    return out[:, 0]
