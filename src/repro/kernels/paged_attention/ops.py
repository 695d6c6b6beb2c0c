"""jit'd wrapper (GQA repeat + head folding) for paged decode attention."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import paged_attention


@jax.jit
def paged_mqa(q, pages_k, pages_v, block_table, seq_lens):
    """q: [B,H,dh]; pages_*: [NP,PS,Hk,dh] with H % Hk == 0."""
    B, H, dh = q.shape
    Hk = pages_k.shape[2]
    rep = H // Hk
    if rep > 1:
        pages_k = jnp.repeat(pages_k, rep, axis=2)
        pages_v = jnp.repeat(pages_v, rep, axis=2)
    return paged_attention(q, pages_k, pages_v, block_table, seq_lens)
