"""jit'd public wrapper: GQA layout handling around the Pallas kernel."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import flash_attention


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "q_block", "kv_block"))
def mha(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
        causal: bool = True, window: Optional[int] = None,
        q_block: int = 512, kv_block: int = 512) -> jnp.ndarray:
    """q: [B,T,H,dh]; k,v: [B,S,Hk,dh] (GQA: H % Hk == 0).
    Returns [B,T,H,dh]."""
    B, T, H, dh = q.shape
    S, Hk = k.shape[1], k.shape[2]
    rep = H // Hk
    kr = jnp.repeat(k, rep, axis=2) if rep > 1 else k
    vr = jnp.repeat(v, rep, axis=2) if rep > 1 else v
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, T, dh)
    kf = kr.transpose(0, 2, 1, 3).reshape(B * H, S, dh)
    vf = vr.transpose(0, 2, 1, 3).reshape(B * H, S, dh)
    o = flash_attention(qf, kf, vf, causal=causal, window=window,
                        q_block=min(q_block, T), kv_block=min(kv_block, S))
    return o.reshape(B, H, T, dh).transpose(0, 2, 1, 3)
