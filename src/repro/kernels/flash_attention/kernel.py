"""Flash attention forward — Pallas TPU kernel.

Tiling: grid (batch·heads, q_blocks, kv_blocks); kv is the innermost
(sequential) axis so the online-softmax running state (m, l, acc) lives
in VMEM scratch across kv steps.  Block shapes are MXU-aligned
(q_block × d_head and kv_block × d_head tiles, multiples of 128 on the
lane dim).  Causal/windowed blocks that are fully masked are skipped
with ``pl.when`` (the index map still visits them; the body is cheap).

HBM→VMEM movement per (q,kv) tile: q once per q block (revisited per
kv step from VMEM), k/v tiles streamed — the standard flash dataflow
re-thought for VMEM sizes: default 512×512 fp32 scratch ≈ 1 MiB, well
inside the ~16 MiB v5e VMEM budget with double buffering.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import backend

DEFAULT_Q_BLOCK = 512
DEFAULT_KV_BLOCK = 512
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  q_len: int, kv_len: int, q_block: int, kv_block: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # absolute positions (queries right-aligned when q_len < kv_len)
    q_pos = qi * q_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, kv_block), 0) + (kv_len - q_len)
    k_pos = ki * kv_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, kv_block), 1)

    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            mask = k_pos <= q_pos
            if window is not None:
                mask &= k_pos > q_pos - window
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    if causal:
        # skip blocks entirely above the diagonal / outside the window
        first_q = qi * q_block + (kv_len - q_len)
        last_q = first_q + q_block - 1
        first_k = ki * kv_block
        live = first_k <= last_q
        if window is not None:
            live &= (first_k + kv_block - 1) > (first_q - window)
        pl.when(live)(_body)
    else:
        _body()

    @pl.when(ki == n_kv - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, :, :] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    q_block: int = DEFAULT_Q_BLOCK,
                    kv_block: int = DEFAULT_KV_BLOCK) -> jnp.ndarray:
    """q: [BH, T, dh]; k,v: [BH, S, dh] (batch and heads pre-folded,
    kv heads pre-repeated).  Returns [BH, T, dh]."""
    BH, T, dh = q.shape
    S = k.shape[1]
    q_block = min(q_block, T)
    kv_block = min(kv_block, S)
    assert T % q_block == 0 and S % kv_block == 0, (T, S, q_block, kv_block)
    grid = (BH, T // q_block, S // kv_block)
    scale = 1.0 / (dh ** 0.5)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        q_len=T, kv_len=S, q_block=q_block, kv_block=kv_block)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_block, dh), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, kv_block, dh), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, kv_block, dh), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, dh), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, dh), jnp.float32),
        ],
        interpret=backend.interpret(),
    )(q, k, v)
