"""Per-kernel shape/dtype sweeps, each asserted against its pure-jnp
ref.py oracle in interpret mode (kernels target TPU; interpret executes
the kernel body on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention_ref, flash_attention, mha
from repro.kernels.rwkv6_scan import wkv6, wkv6_heads, wkv6_ref
from repro.kernels.mamba_scan import ssd, ssd_heads, ssd_ref
from repro.kernels.paged_attention import paged_attention, paged_attention_ref

RNG = np.random.default_rng(0)


def arr(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


# ----------------------------------------------------------------------
# flash attention
# ----------------------------------------------------------------------
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("BH,T,S,dh,causal,window,qb,kb", [
    (4, 256, 256, 64, True, None, 128, 128),
    (2, 128, 256, 64, True, None, 64, 64),  # right-aligned queries
    (2, 256, 256, 128, False, None, 128, 64),
    (2, 256, 256, 64, True, 96, 64, 64),  # sliding window
    (1, 512, 512, 64, True, None, 128, 256),
])
def test_flash_attention(BH, T, S, dh, causal, window, qb, kb, dtype, tol):
    q, k, v = arr((BH, T, dh), dtype), arr((BH, S, dh), dtype), \
        arr((BH, S, dh), dtype)
    o = flash_attention(q, k, v, causal=causal, window=window,
                        q_block=qb, kv_block=kb)
    ref = attention_ref(q[:, None], k[:, None], v[:, None],
                        causal=causal, window=window)[:, 0]
    assert o.shape == ref.shape
    err = float(jnp.max(jnp.abs(o.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert err < tol, (err, tol)


def test_flash_attention_gqa_wrapper():
    B, T, H, Hk, dh = 2, 128, 8, 2, 64
    q = arr((B, T, H, dh))
    k, v = arr((B, T, Hk, dh)), arr((B, T, Hk, dh))
    o = mha(q, k, v, q_block=64, kv_block=64)
    kr = jnp.repeat(k, H // Hk, axis=2).transpose(0, 2, 1, 3)
    vr = jnp.repeat(v, H // Hk, axis=2).transpose(0, 2, 1, 3)
    ref = attention_ref(q.transpose(0, 2, 1, 3), kr, vr, causal=True)
    err = float(jnp.max(jnp.abs(o.transpose(0, 2, 1, 3) - ref)))
    assert err < 1e-5, err


# ----------------------------------------------------------------------
# rwkv6 wkv
# ----------------------------------------------------------------------
@pytest.mark.parametrize("BH,T,dh,chunk", [
    (3, 128, 64, 32), (2, 256, 64, 128), (2, 64, 128, 64), (1, 96, 32, 32),
])
def test_wkv6(BH, T, dh, chunk):
    r, k, v = arr((BH, T, dh)), arr((BH, T, dh)), arr((BH, T, dh))
    logw = -jnp.asarray(RNG.uniform(0.001, 0.15, size=(BH, T, dh)),
                        jnp.float32)
    u = arr((dh,))
    o = wkv6(r, k, v, logw, u, chunk=chunk)
    ref, _ = wkv6_ref(r, k, v, logw, u)
    assert float(jnp.max(jnp.abs(o - ref))) < 5e-4


def test_wkv6_heads_wrapper():
    B, T, H, dh = 2, 64, 3, 32
    r, k, v = arr((B, T, H, dh)), arr((B, T, H, dh)), arr((B, T, H, dh))
    logw = -jnp.asarray(RNG.uniform(0.01, 0.1, size=(B, T, H, dh)),
                        jnp.float32)
    u = arr((H, dh))
    o = wkv6_heads(r, k, v, logw, u, chunk=32)
    for h in range(H):
        ref, _ = wkv6_ref(r[:, :, h], k[:, :, h], v[:, :, h],
                          logw[:, :, h], u[h])
        assert float(jnp.max(jnp.abs(o[:, :, h] - ref))) < 5e-4


# ----------------------------------------------------------------------
# mamba ssd
# ----------------------------------------------------------------------
@pytest.mark.parametrize("BH,T,dh,N,chunk", [
    (3, 128, 64, 16, 32), (2, 256, 128, 16, 128), (2, 64, 64, 8, 64),
])
def test_ssd(BH, T, dh, N, chunk):
    x = arr((BH, T, dh))
    dt = jnp.asarray(RNG.uniform(0.001, 0.4, size=(BH, T)), jnp.float32)
    B_, C_ = arr((BH, T, N)), arr((BH, T, N))
    A = -jnp.asarray(RNG.uniform(0.3, 1.5, size=(BH,)), jnp.float32)
    y = ssd(x, dt, B_, C_, A, chunk=chunk)
    ref, _ = ssd_ref(x, dt, B_, C_, A)
    assert float(jnp.max(jnp.abs(y - ref))) < 5e-4


def test_ssd_heads_wrapper():
    B, T, H, dh, N = 2, 64, 2, 32, 8
    xh = arr((B, T, H, dh))
    dt = jnp.asarray(RNG.uniform(0.01, 0.3, size=(B, T, H)), jnp.float32)
    B_, C_ = arr((B, T, N)), arr((B, T, N))
    A = -jnp.asarray(RNG.uniform(0.5, 1.0, size=(H,)), jnp.float32)
    y = ssd_heads(xh, dt, B_, C_, A, chunk=32)
    for h in range(H):
        ref, _ = ssd_ref(xh[:, :, h], dt[:, :, h], B_, C_,
                         jnp.broadcast_to(A[h], (B,)))
        assert float(jnp.max(jnp.abs(y[:, :, h] - ref))) < 5e-4


# ----------------------------------------------------------------------
# clht probe
# ----------------------------------------------------------------------
def test_clht_probe_end_to_end_with_index():
    """Control-plane P-CLHT → exported arrays → Pallas batched lookup,
    bit-identical to the scalar reader (full 64-bit keys and values)."""
    from repro.core import PMem, PCLHT
    from repro.kernels.clht_probe import batched_lookup
    pmem = PMem()
    ht = PCLHT(pmem, n_buckets=64, grow=False)
    keys = [int(k) for k in RNG.integers(1, 1 << 60, size=100)]
    for k in dict.fromkeys(keys):
        ht.insert(k, k * 3)
    ek, ev, enxt, nb, efps = ht.export_arrays()
    live = list(dict.fromkeys(keys))
    misses = [int(k) for k in RNG.integers(1, 1 << 60, size=50)]
    queries = np.asarray(live + misses, np.int64)
    found, vals = batched_lookup(queries, ek, ev, enxt, n_buckets=nb)
    for q, f, v in zip(queries, found, vals):
        ref = ht.lookup(int(q))
        assert (ref is not None) == bool(f)
        if ref is not None:
            assert ref == int(v)


# ----------------------------------------------------------------------
# probe64 (shared 64-bit paired-half compare)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("Q,W", [(256, 128), (512, 24), (1024, 9)])
def test_probe64_matches_oracle(Q, W):
    from repro.kernels.probe import probe64, split64, combine64
    wk = RNG.integers(0, 1 << 62, size=(Q, W)).astype(np.int64)
    wv = RNG.integers(1, 1 << 62, size=(Q, W)).astype(np.int64)
    hit_col = RNG.integers(0, W, size=Q)
    take = RNG.random(Q) < 0.5
    q = np.where(take, wk[np.arange(Q), hit_col],
                 np.int64((1 << 62) + 7))  # guaranteed miss
    qlo, qhi = split64(q)
    klo, khi = split64(wk)
    vlo, vhi = split64(wv)
    f, olo, ohi = probe64(*map(jnp.asarray, (qlo, qhi, klo, khi, vlo, vhi)),
                          query_block=256)
    f = np.asarray(f)
    got = combine64(np.asarray(olo), np.asarray(ohi))
    # oracle: first column where the full 64-bit key matches
    hit = wk == q[:, None]
    exp_found = hit.any(axis=1)
    exp_val = np.where(exp_found, wv[np.arange(Q), hit.argmax(axis=1)], 0)
    assert np.array_equal(f, exp_found)
    assert np.array_equal(got, exp_val)


def test_probe64_half_collisions_do_not_hit():
    """Keys agreeing in one 32-bit half only must not match."""
    from repro.kernels.probe import probe64, split64
    q = np.asarray([(5 << 32) | 9], np.int64)
    wk = np.asarray([[(5 << 32) | 8, (4 << 32) | 9, 0, 0, 0, 0, 0, 0]],
                    np.int64)
    wv = np.full_like(wk, 77)
    qlo, qhi = split64(q)
    klo, khi = split64(wk)
    vlo, vhi = split64(wv)
    f, _, _ = probe64(*map(jnp.asarray, (qlo, qhi, klo, khi, vlo, vhi)))
    assert not bool(np.asarray(f)[0])


# ----------------------------------------------------------------------
# art probe (batched radix descent)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n_keys,key_bits", [(300, 60), (200, 16), (50, 8)])
def test_art_descend_matches_ref_and_scalar(n_keys, key_bits):
    """Kernel vs pure-numpy ref vs the authoritative scalar reader,
    over trees with short keys (dense top bytes) and long random keys
    (deep descents + path compression)."""
    from repro.core import PMem, PART
    from repro.kernels.art_probe import batched_lookup, descend_ref
    art = PART(PMem())
    keys = list(dict.fromkeys(
        int(k) for k in RNG.integers(1, 1 << key_bits, size=n_keys)))
    for k in keys:
        art.insert(k, (k % 1000003) + 1)
    for k in keys[::5]:
        art.delete(k)  # tombstoned leaves must read as misses
    arrays = art.export_arrays()
    queries = np.asarray(
        keys + [int(k) for k in RNG.integers(1, 1 << key_bits, size=100)],
        np.int64)
    found, vals = batched_lookup(queries, arrays)
    rf, rv = descend_ref(queries, arrays)
    assert np.array_equal(found, rf)
    assert np.array_equal(vals, np.where(rf, rv, 0))
    for q, f, v in zip(queries, found, vals):
        ref = art.lookup(int(q))
        assert (ref is not None) == bool(f), int(q)
        if ref is not None:
            assert ref == int(v)


# ----------------------------------------------------------------------
# paged attention
# ----------------------------------------------------------------------
@pytest.mark.parametrize("B,H,dh,NP,PS,MAXP", [
    (3, 4, 64, 16, 32, 4), (2, 2, 128, 8, 16, 4), (4, 8, 64, 32, 64, 8),
])
def test_paged_attention(B, H, dh, NP, PS, MAXP):
    q = arr((B, H, dh))
    pk, pv = arr((NP, PS, H, dh)), arr((NP, PS, H, dh))
    table = jnp.asarray(
        RNG.permutation(NP)[:B * MAXP].reshape(B, MAXP)
        if NP >= B * MAXP else
        RNG.integers(0, NP, size=(B, MAXP)), jnp.int32)
    lens = jnp.asarray(RNG.integers(1, PS * MAXP, size=(B,)), jnp.int32)
    o = paged_attention(q, pk, pv, table, lens)
    ref = paged_attention_ref(q, pk, pv, table, lens)
    assert float(jnp.max(jnp.abs(o - ref))) < 1e-5
