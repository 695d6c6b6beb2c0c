"""jit'd wrapper for the SSD kernel (folds batch × heads)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import ssd


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_heads(xh, dt, B_, C_, A, *, chunk: int = 128):
    """xh: [B,T,H,dh]; dt: [B,T,H]; B_,C_: [B,T,N]; A: [H].
    Returns [B,T,H,dh] (B_/C_ shared across heads, as in Mamba)."""
    B, T, H, dh = xh.shape
    N = B_.shape[-1]
    xf = xh.transpose(0, 2, 1, 3).reshape(B * H, T, dh)
    dtf = dt.transpose(0, 2, 1).reshape(B * H, T)
    Bf = jnp.broadcast_to(B_[:, None], (B, H, T, N)).reshape(B * H, T, N)
    Cf = jnp.broadcast_to(C_[:, None], (B, H, T, N)).reshape(B * H, T, N)
    Af = jnp.broadcast_to(A[None], (B, H)).reshape(B * H)
    y = ssd(xf, dtf, Bf, Cf, Af, chunk=chunk)
    return y.reshape(B, H, T, dh).transpose(0, 2, 1, 3)
