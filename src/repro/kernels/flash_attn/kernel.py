"""Pallas TPU flash-attention forward (causal + sliding window).

Grid (batch*heads, q_blocks, kv_blocks), kv innermost; streaming
(m, l, acc) scratch per q block — the classic log-sum-exp recurrence.
With a sliding window the fully-masked kv blocks are skipped via
``pl.when`` so compute is O(S·w) per head, matching the windowed archs'
roofline. VMEM tiles: (QB, hd) + (KB, hd) + (QB, KB) scores, hd whole.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interp

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  qb: int, kb: int, nkb: int, seq_kv: int, scale: float,
                  causal: bool, window):
    qi_blk = pl.program_id(1)
    kv_blk = pl.program_id(2)

    @pl.when(kv_blk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi_blk * qb
    k_start = kv_blk * kb

    # block-level reachability: skip fully-masked kv blocks — this is
    # what makes windowed attention O(S·w) instead of O(S²).
    reachable = jnp.bool_(True)
    if causal:
        reachable &= k_start <= q_start + qb - 1
    if window is not None:
        reachable &= k_start + kb - 1 >= q_start - (window - 1)

    @pl.when(reachable)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale        # (QB, hd)
        k = k_ref[0].astype(jnp.float32)                # (KB, hd)
        s = q @ k.T                                     # (QB, KB)
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + q_start
        ki = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + k_start
        mask = ki < seq_kv
        if causal:
            mask &= ki <= qi
        if window is not None:
            mask &= (qi - ki) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + p @ v_ref[0].astype(
            jnp.float32)
        m_scr[...] = m_new

    @pl.when(kv_blk == nkb - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window=None,
                           qb: int = 128, kb: int = 128, scale=None,
                           interpret: bool | None = None):
    """q,k,v: (BH, S, hd) — batch*heads flattened (kv already repeated).
    Returns (BH, S, hd)."""
    BH, S, hd = q.shape
    Skv = k.shape[1]
    scale = scale or hd ** -0.5
    qb = min(qb, S)
    kb = min(kb, Skv)
    Sp = ((S + qb - 1) // qb) * qb
    Kp = ((Skv + kb - 1) // kb) * kb

    def pad(x, size):
        if x.shape[1] == size:
            return x
        return jnp.pad(x, ((0, 0), (0, size - x.shape[1]), (0, 0)))

    qp, kp, vp = pad(q, Sp), pad(k, Kp), pad(v, Kp)
    nqb, nkb = Sp // qb, Kp // kb

    out = pl.pallas_call(
        functools.partial(_flash_kernel, qb=qb, kb=kb, nkb=nkb, seq_kv=Skv,
                          scale=scale, causal=causal, window=window),
        grid=(BH, nqb, nkb),
        in_specs=[
            pl.BlockSpec((1, qb, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, kb, hd), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, kb, hd), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, qb, hd), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qb,), jnp.float32),
            pltpu.VMEM((qb,), jnp.float32),
            pltpu.VMEM((qb, hd), jnp.float32),
        ],
        interpret=interp.resolve(interpret),
    )(qp, kp, vp)
    return out[:, :S]
