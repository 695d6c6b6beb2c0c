"""Paged decode attention — Pallas TPU kernel.

Serving decode reads a KV cache scattered across fixed-size pages whose
page table is the RECIPE P-CLHT block index (crash-consistent; a
restarted server keeps its pages).  Grid (B·H, n_pages) with the page
axis sequential: online-softmax state (m, l, acc) lives in VMEM scratch
while pages stream HBM→VMEM.  The page indirection is resolved by the
BlockSpec index_map reading a prefetched block table (scalar prefetch),
i.e. the gather happens in the DMA engine, not the VPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import backend

NEG_INF = -1e30


def _paged_kernel(table_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, page_size: int, n_heads: int):
    bh = pl.program_id(0)
    pi = pl.program_id(1)
    n_pages = pl.num_programs(1)
    b = bh // n_heads

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = lens_ref[b]
    page_live = (pi * page_size) < seq_len

    @pl.when(page_live)
    def _body():
        q = q_ref[0].astype(jnp.float32)  # [1, dh]
        k = k_ref[0, :, 0, :].astype(jnp.float32)  # [PS, dh]
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        dh = q.shape[-1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s / jnp.sqrt(jnp.float32(dh))  # [1, PS]
        pos = pi * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < seq_len, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(pi == n_pages - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, :, :] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_attention(q, kv_pages_k, kv_pages_v, block_table, seq_lens):
    """q: [B,H,dh]; kv pages: [NP,PS,H,dh]; block_table: [B,MAXP];
    seq_lens: [B].  Returns [B,H,dh]."""
    B, H, dh = q.shape
    NP, PS = kv_pages_k.shape[:2]
    MAXP = block_table.shape[1]
    grid = (B * H, MAXP)

    def q_map(bh, pi, table, lens):
        return (bh, 0, 0)

    def kv_map(bh, pi, table, lens):
        # DMA-level page indirection via the prefetched block table
        page = table[bh // H, pi]
        return (jnp.maximum(page, 0), 0, bh % H, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, dh), q_map),
            pl.BlockSpec((1, PS, 1, dh), kv_map),
            pl.BlockSpec((1, PS, 1, dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, dh), jnp.float32),
        ],
    )
    kern = functools.partial(_paged_kernel, page_size=PS, n_heads=H)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, 1, dh), q.dtype),
        interpret=backend.interpret(),
    )(block_table, seq_lens, q.reshape(B * H, 1, dh), kv_pages_k,
      kv_pages_v)
    return out.reshape(B, H, dh)
