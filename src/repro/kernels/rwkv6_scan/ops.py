"""jit'd wrapper: per-head dispatch of the WKV6 kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import wkv6


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv6_heads(r, k, v, logw, u, *, chunk: int = 128):
    """r,k,v,logw: [B,T,H,dh]; u: [H,dh]. Returns [B,T,H,dh]."""
    B, T, H, dh = r.shape
    o = jnp.zeros((B, T, H, dh), r.dtype)
    for h in range(H):  # heads share nothing; u differs per head
        oh = wkv6(r[:, :, h], k[:, :, h], v[:, :, h], logw[:, :, h],
                  u[h], chunk=chunk)
        o = o.at[:, :, h].set(oh)
    return o
