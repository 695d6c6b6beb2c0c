"""Mamba SSD chunked scan — Pallas TPU kernel.

Grid (BH, n_chunks), sequential chunk axis; per-(batch,head) SSM state
[dh, N] carried in fp32 VMEM scratch.  Intra-chunk work is the
decay-masked (C·B) attention-form matmul of the SSD algorithm — MXU
work, not a sequential scan (the GPU kernel's warp-sequential scan has
no TPU analogue; this matmul form is the TPU-native restatement).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import backend


def _ssd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, o_ref, h_ref, *,
                chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)  # [C, dh]
    dt = dt_ref[0].astype(jnp.float32)  # [C, 1]
    Bm = b_ref[0].astype(jnp.float32)  # [C, N]
    Cm = c_ref[0].astype(jnp.float32)  # [C, N]
    A = a_ref[0, 0]  # scalar < 0
    C = x.shape[0]
    ldec = dt * A  # [C,1] log decay per step
    cum = jnp.cumsum(ldec, axis=0)  # [C,1]
    # intra: score[t,s] = C_t·B_s exp(cum_t - cum_s) dt_s   (s <= t)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    rel = cum - cum.T  # [C,C] = cum_t - cum_s
    t_pos = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    s_pos = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    w = jnp.where(s_pos <= t_pos, scores * jnp.exp(rel) * dt.T, 0.0)
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # inter: y_t += (C_t exp(cum_t)) · h_in^T      h_in: [dh, N]
    cdec = Cm * jnp.exp(cum)
    y = y + jax.lax.dot_general(cdec, h_ref[...], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    o_ref[0, :, :] = y.astype(o_ref.dtype)
    # state update: h = exp(total) h_in + sum_s exp(total-cum_s) dt_s x_s B_s^T
    total = cum[-1:, :]  # [1,1]
    xw = x * (jnp.exp(total - cum) * dt)  # [C, dh]
    h_new = jax.lax.dot_general(xw, Bm, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    h_ref[...] = jnp.exp(total) * h_ref[...] + h_new


def ssd(x, dt, B_, C_, A, *, chunk: int = 128):
    """x: [BH,T,dh]; dt: [BH,T]; B_,C_: [BH,T,N]; A: [BH] (<0).
    Returns y: [BH,T,dh]."""
    BH, T, dh = x.shape
    N = B_.shape[-1]
    chunk = min(chunk, T)
    assert T % chunk == 0
    grid = (BH, T // chunk)
    kern = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1), lambda b, c: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, dh), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, dh), x.dtype),
        scratch_shapes=[pltpu.VMEM((dh, N), jnp.float32)],
        interpret=backend.interpret(),
    )(x, dt[..., None], B_, C_, A.reshape(BH, 1))
