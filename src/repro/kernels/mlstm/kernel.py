"""Pallas TPU chunkwise mLSTM kernel.

One head per call (vmap over batch*heads outside). Grid = (n_chunks,),
sequential on TPU, with the inter-chunk state (C (dk,dv), n (dk,), m ())
living in VMEM scratch that persists across grid steps — the TPU-native
replacement for the CUDA recurrent kernel: within a chunk the quadratic
(L, L) gate-decay matrix runs on the MXU; across chunks only the O(dk·dv)
state is carried.

VMEM working set per step: (L,dk)+(L,dv) tiles + (L,L) decay + (dk,dv)
state — e.g. L=64, dk=dv=1024 → ~4.5MB f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interp


def _mlstm_kernel(q_ref, k_ref, v_ref, i_ref, f_ref, h_ref,
                  C_scr, n_scr, m_scr, *, L: int):
    c = pl.program_id(0)

    @pl.when(c == 0)
    def _init():
        C_scr[...] = jnp.zeros_like(C_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        m_scr[...] = jnp.zeros_like(m_scr)

    q = q_ref[...].astype(jnp.float32)          # (L, dk)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)          # (L, dv)
    ii = i_ref[...].astype(jnp.float32)         # (L, 1)
    ff = f_ref[...].astype(jnp.float32)         # (L, 1) log-sigmoid forget

    C0 = C_scr[...]
    n0 = n_scr[...]                             # (1, dk)
    m0 = m_scr[...]                             # (1, 1)

    # Gate prefix scans as masked (L, L) reductions: row t sees s <= t.
    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = col <= row

    def as_row(x):                              # (L, 1) -> (1, L)
        return jnp.sum(jnp.where(row == col, x, 0.0), axis=0, keepdims=True)

    ff_r, ii_r = as_row(ff), as_row(ii)
    b = jnp.sum(jnp.where(causal, ff_r, 0.0), axis=1, keepdims=True)  # (L, 1)
    b_r = as_row(b)
    a_max = jnp.max(jnp.where(causal, ii_r - b_r, -jnp.inf), axis=1,
                    keepdims=True)              # cummax of i - b
    m_t = jnp.maximum(m0 + b, b + a_max)        # (L, 1)

    w0 = jnp.exp(m0 + b - m_t)                  # (L, 1)
    h_inter = (q @ C0) * w0                     # (L, dv)
    d_inter = jnp.sum(q * n0, axis=1, keepdims=True) * w0   # (L, 1)

    # intra-chunk decay matrix D[t,s] = exp(b_t - b_s + i_s - m_t), s<=t
    D = jnp.where(causal, jnp.exp(b - b_r + ii_r - m_t), 0.0)

    scores = (q @ k.T) * D                      # (L, L)
    h_intra = scores @ v
    d_intra = jnp.sum(scores, axis=1, keepdims=True)
    denom = jnp.maximum(jnp.abs(d_inter + d_intra), jnp.exp(-m_t))
    h_ref[...] = ((h_inter + h_intra) / denom).astype(h_ref.dtype)

    # state to end of chunk
    F = b[L - 1:L]                              # (1, 1)
    m_new = jnp.maximum(m0 + F, F + a_max[L - 1:L])
    wC0 = jnp.exp(m0 + F - m_new)
    kw = k * jnp.exp(F - b + ii - m_new)        # (L, dk)
    C_scr[...] = C0 * wC0 + kw.T @ v
    n_scr[...] = n0 * wC0 + jnp.sum(kw, axis=0, keepdims=True)
    m_scr[...] = m_new


def mlstm_chunk_pallas(q, k, v, i_raw, f_log, *, chunk: int = 64,
                       interpret: bool | None = None):
    """q,k: (S, dk); v: (S, dv); gates (S,). Single head. Returns (S, dv).

    Gates travel as (S, 1) columns in (L, 1) blocks: Mosaic refuses a
    rank-1 block that is not a multiple of its 128 tile."""
    S, dk = q.shape
    dv = v.shape[1]
    L = min(chunk, S)
    while S % L:
        L //= 2
    nc = S // L

    return pl.pallas_call(
        functools.partial(_mlstm_kernel, L=L),
        grid=(nc,),
        in_specs=[
            pl.BlockSpec((L, dk), lambda c: (c, 0)),
            pl.BlockSpec((L, dk), lambda c: (c, 0)),
            pl.BlockSpec((L, dv), lambda c: (c, 0)),
            pl.BlockSpec((L, 1), lambda c: (c, 0)),
            pl.BlockSpec((L, 1), lambda c: (c, 0)),
        ],
        out_specs=pl.BlockSpec((L, dv), lambda c: (c, 0)),
        out_shape=jax.ShapeDtypeStruct((S, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((dk, dv), jnp.float32),
            pltpu.VMEM((1, dk), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interp.resolve(interpret),
    )(q, k, v, i_raw[:, None], f_log[:, None])
