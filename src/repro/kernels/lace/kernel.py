"""Pallas TPU kernels for LACE (fused logit-adjusted softmax CE).

Three kernels (forward, backward-dfeats, backward-dW), each tiling the
vocab so the (tokens, V) logits never leave VMEM:

* ``fwd``: grid (token_blocks, vocab_blocks), vocab innermost; streaming
  (m, s, ll) scratch per token block; emits per-token nll and lse.
* ``bwd_dfeats``: same grid; recomputes z per vocab tile from the saved
  lse, accumulates dfeats[t] += g_tile @ W_tile^T over consecutive inner
  vocab steps.
* ``bwd_dw``: grid (vocab_blocks, token_blocks), tokens innermost;
  accumulates dW[v] += feats_tile^T @ g_tile.

Tile sizes: token_block x d feats tiles and d x vocab_block weight tiles;
d is kept whole (<= 8k: W tile bf16 fits VMEM at vocab_block 256). For
larger d a d-tiled variant would be needed — none of the assigned archs
exceeds d=8192.

``lace2_fwd/bwd_pallas`` (bottom) are the fused dual-prior variants:
one ``f @ w`` per vocab tile feeds BOTH adjusted LSE streams (eq. 14's
P_s and eq. 15's P_k), and the fused backward shares the recomputed
logits between the two softmax cotangents — two (m, s, ll) scratch
streams in the forward, two df outputs in one pass in the backward.

Per-token vectors (labels, lse, nll, token scales) travel as (N, 1)
columns in (tb, 1) blocks and per-vocab prior rows as (1, V) rows in
(1, vb) blocks: Mosaic refuses rank-1 blocks whose XLA layout tile (1024
for an s32[2048]) differs from its own (128), so every operand is 2-D.

Validated against :mod:`repro.kernels.lace.ref` in interpret mode (CPU);
on TPU the same ``pallas_call``s lower to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interp

NEG_INF = -1e30


def _stream_update(z, hit, m_scr, s_scr, ll_scr):
    """Fold one (TB, VB) logits tile into a streaming (m, s, ll) LSE."""
    ll_scr[...] += jnp.sum(jnp.where(hit, z, 0.0), axis=1, keepdims=True)
    m_old = m_scr[...]
    m_new = jnp.maximum(m_old, jnp.max(z, axis=1, keepdims=True))
    s_scr[...] = s_scr[...] * jnp.exp(m_old - m_new) + jnp.sum(
        jnp.exp(z - m_new), axis=1, keepdims=True)
    m_scr[...] = m_new


def _stream_init(m_scr, s_scr, ll_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    s_scr[...] = jnp.zeros_like(s_scr)
    ll_scr[...] = jnp.zeros_like(ll_scr)


def _stream_finish(m_scr, s_scr, ll_scr, nll_ref, lse_ref):
    lse = m_scr[...] + jnp.log(s_scr[...])
    lse_ref[...] = lse
    nll_ref[...] = lse - ll_scr[...]


def _fwd_kernel(feats_ref, w_ref, labels_ref, lp_ref, nll_ref, lse_ref,
                m_scr, s_scr, ll_scr, *, vb: int, nvb: int, tau: float):
    v = pl.program_id(1)

    @pl.when(v == 0)
    def _init():
        _stream_init(m_scr, s_scr, ll_scr)

    f = feats_ref[...].astype(jnp.float32)          # (TB, d)
    w = w_ref[...].astype(jnp.float32)              # (d, VB)
    z = f @ w + tau * lp_ref[...].astype(jnp.float32)   # (TB, VB)
    col = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1) + v * vb
    _stream_update(z, col == labels_ref[...], m_scr, s_scr, ll_scr)

    @pl.when(v == nvb - 1)
    def _finish():
        _stream_finish(m_scr, s_scr, ll_scr, nll_ref, lse_ref)


def _softmax_cotangent(zb, v, vb, labels_ref, lp_ref, lse_ref, gw_ref, tau):
    """Per-token-weighted ``softmax(z) - onehot`` for one logits tile."""
    z = zb + tau * lp_ref[...].astype(jnp.float32)
    p = jnp.exp(z - lse_ref[...])
    col = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1) + v * vb
    onehot = (col == labels_ref[...]).astype(jnp.float32)
    return (p - onehot) * gw_ref[...]


def _bwd_dfeats_kernel(feats_ref, w_ref, labels_ref, lp_ref, lse_ref,
                       gw_ref, df_ref, *, vb: int, nvb: int, tau: float):
    v = pl.program_id(1)

    @pl.when(v == 0)
    def _init():
        df_ref[...] = jnp.zeros_like(df_ref)

    f = feats_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    g = _softmax_cotangent(f @ w, v, vb, labels_ref, lp_ref, lse_ref,
                           gw_ref, tau)
    df_ref[...] += (g @ w.T).astype(df_ref.dtype)


def _bwd_dw_kernel(feats_ref, w_ref, labels_ref, lp_ref, lse_ref,
                   gw_ref, dw_ref, *, vb: int, ntb: int, tau: float):
    t = pl.program_id(1)
    v = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    f = feats_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    g = _softmax_cotangent(f @ w, v, vb, labels_ref, lp_ref, lse_ref,
                           gw_ref, tau)
    dw_ref[...] += (f.T @ g).astype(dw_ref.dtype)


def _pad_to(x, size, axis, value=0):
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _col(x, n, value=0):
    """Per-token (N,) vector -> zero-padded (n, 1) column."""
    return _pad_to(x, n, 0, value=value)[:, None]


def _row(x, n, value=0):
    """Per-vocab (V,) vector -> padded (1, n) row."""
    return _pad_to(x, n, 0, value=value)[None, :]


def _specs(tb, d, vb, *, n_tok, n_voc, vocab_outer=False):
    """BlockSpecs for (feats, w, labels, n_voc prior rows, n_tok token
    columns). Grid is (t, v), or (v, t) when ``vocab_outer``."""
    def ix(fn):
        return (lambda v, t: fn(t, v)) if vocab_outer else fn

    return ([pl.BlockSpec((tb, d), ix(lambda t, v: (t, 0))),
             pl.BlockSpec((d, vb), ix(lambda t, v: (0, v))),
             pl.BlockSpec((tb, 1), ix(lambda t, v: (t, 0)))]
            + [pl.BlockSpec((1, vb), ix(lambda t, v: (0, v)))] * n_voc
            + [pl.BlockSpec((tb, 1), ix(lambda t, v: (t, 0)))] * n_tok)


def _tok_out(tb):
    return pl.BlockSpec((tb, 1), lambda t, v: (t, 0))


def _scratch3(tb):
    from jax.experimental.pallas import tpu as pltpu
    return [pltpu.VMEM((tb, 1), jnp.float32) for _ in range(3)]


def _padded(N, V, tb, vb):
    return ((N + tb - 1) // tb) * tb, ((V + vb - 1) // vb) * vb


def _dw_call(feats_p, w_p, labels_p, lp_p, lse_p, gw_p, *, tb, vb, tau,
             interpret):
    """dW = feats^T @ g over (vocab, token) tiles, tokens innermost."""
    Np, d = feats_p.shape
    Vp = w_p.shape[1]
    ntb, nvb = Np // tb, Vp // vb
    return pl.pallas_call(
        functools.partial(_bwd_dw_kernel, vb=vb, ntb=ntb, tau=tau),
        grid=(nvb, ntb),
        in_specs=_specs(tb, d, vb, n_tok=2, n_voc=1, vocab_outer=True),
        out_specs=pl.BlockSpec((d, vb), lambda v, t: (0, v)),
        out_shape=jax.ShapeDtypeStruct((d, Vp), jnp.float32),
        interpret=interp.resolve(interpret),
    )(feats_p, w_p, labels_p, lp_p, lse_p, gw_p)


def lace_fwd_pallas(feats, w_head, labels, log_prior, *, tau: float = 1.0,
                    tb: int = 128, vb: int = 256,
                    interpret: bool | None = None):
    """feats (N,d), w_head (d,V), labels (N,), log_prior (V,) ->
    (nll (N,), lse (N,)). Single prior row; vmap for groups."""
    N, d = feats.shape
    V = w_head.shape[1]
    Np, Vp = _padded(N, V, tb, vb)
    nll, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, vb=vb, nvb=Vp // vb, tau=tau),
        grid=(Np // tb, Vp // vb),
        in_specs=_specs(tb, d, vb, n_tok=0, n_voc=1),
        out_specs=[_tok_out(tb)] * 2,
        out_shape=[jax.ShapeDtypeStruct((Np, 1), jnp.float32)] * 2,
        scratch_shapes=_scratch3(tb),
        interpret=interp.resolve(interpret),
    )(_pad_to(feats, Np, 0), _pad_to(w_head, Vp, 1), _col(labels, Np, -1),
      _row(log_prior, Vp, NEG_INF))
    return nll[:N, 0], lse[:N, 0]


def lace_bwd_pallas(feats, w_head, labels, log_prior, lse, token_scale, *,
                    tau: float = 1.0, tb: int = 128, vb: int = 256,
                    interpret: bool | None = None):
    """token_scale (N,): weight_i * g / w_sum. Returns (dfeats, dW) f32."""
    N, d = feats.shape
    V = w_head.shape[1]
    Np, Vp = _padded(N, V, tb, vb)
    args = (_pad_to(feats, Np, 0), _pad_to(w_head, Vp, 1),
            _col(labels, Np, -1), _row(log_prior, Vp, NEG_INF),
            _col(lse, Np), _col(token_scale, Np))

    df = pl.pallas_call(
        functools.partial(_bwd_dfeats_kernel, vb=vb, nvb=Vp // vb, tau=tau),
        grid=(Np // tb, Vp // vb),
        in_specs=_specs(tb, d, vb, n_tok=2, n_voc=1),
        out_specs=pl.BlockSpec((tb, d), lambda t, v: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((Np, d), jnp.float32),
        interpret=interp.resolve(interpret),
    )(*args)
    dw = _dw_call(*args, tb=tb, vb=vb, tau=tau, interpret=interpret)
    return df[:N], dw[:, :V]


# ---------------------------------------------------------------------------
# lace2 — fused dual-prior kernels (one z tile, two LSE streams)
# ---------------------------------------------------------------------------


def _fwd2_kernel(feats_ref, w_ref, labels_ref, lps_ref, lpk_ref,
                 nlls_ref, nllk_ref, lses_ref, lsek_ref,
                 ms_scr, ss_scr, lls_scr, mk_scr, sk_scr, llk_scr,
                 *, vb: int, nvb: int, tau: float):
    v = pl.program_id(1)
    streams = ((lps_ref, ms_scr, ss_scr, lls_scr, nlls_ref, lses_ref),
               (lpk_ref, mk_scr, sk_scr, llk_scr, nllk_ref, lsek_ref))

    @pl.when(v == 0)
    def _init():
        for _, m_scr, s_scr, ll_scr, _, _ in streams:
            _stream_init(m_scr, s_scr, ll_scr)

    f = feats_ref[...].astype(jnp.float32)          # (TB, d)
    w = w_ref[...].astype(jnp.float32)              # (d, VB)
    zb = f @ w                                      # ONE matmul per tile
    col = jax.lax.broadcasted_iota(jnp.int32, zb.shape, 1) + v * vb
    hit = col == labels_ref[...]

    for lp_ref, m_scr, s_scr, ll_scr, _, _ in streams:
        z = zb + tau * lp_ref[...].astype(jnp.float32)
        _stream_update(z, hit, m_scr, s_scr, ll_scr)

    @pl.when(v == nvb - 1)
    def _finish():
        for _, m_scr, s_scr, ll_scr, nll_ref, lse_ref in streams:
            _stream_finish(m_scr, s_scr, ll_scr, nll_ref, lse_ref)


def _bwd2_dfeats_kernel(feats_ref, w_ref, labels_ref, lps_ref, lpk_ref,
                        lses_ref, lsek_ref, gws_ref, gwk_ref,
                        dfs_ref, dfk_ref, *, vb: int, nvb: int, tau: float):
    v = pl.program_id(1)

    @pl.when(v == 0)
    def _init():
        dfs_ref[...] = jnp.zeros_like(dfs_ref)
        dfk_ref[...] = jnp.zeros_like(dfk_ref)

    f = feats_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    zb = f @ w                                      # ONE matmul per tile
    for lp_ref, lse_ref, gw_ref, df_ref in (
            (lps_ref, lses_ref, gws_ref, dfs_ref),
            (lpk_ref, lsek_ref, gwk_ref, dfk_ref)):
        g = _softmax_cotangent(zb, v, vb, labels_ref, lp_ref, lse_ref,
                               gw_ref, tau)
        df_ref[...] += (g @ w.T).astype(df_ref.dtype)


def lace2_fwd_pallas(feats, w_head, labels, log_prior_s, log_prior_k, *,
                     tau: float = 1.0, tb: int = 128, vb: int = 256,
                     interpret: bool | None = None):
    """Both adjusted NLL/LSE streams from one logits pass.

    feats (N,d), w_head (d,V), labels (N,), log_prior_s/_k (V,) ->
    (nll_s, nll_k, lse_s, lse_k), each (N,). Single prior row per side;
    vmap for groups (per-client P_k rows become the mapped axis).
    """
    N, d = feats.shape
    V = w_head.shape[1]
    Np, Vp = _padded(N, V, tb, vb)
    outs = pl.pallas_call(
        functools.partial(_fwd2_kernel, vb=vb, nvb=Vp // vb, tau=tau),
        grid=(Np // tb, Vp // vb),
        in_specs=_specs(tb, d, vb, n_tok=0, n_voc=2),
        out_specs=[_tok_out(tb)] * 4,
        out_shape=[jax.ShapeDtypeStruct((Np, 1), jnp.float32)] * 4,
        scratch_shapes=_scratch3(tb) + _scratch3(tb),
        interpret=interp.resolve(interpret),
    )(_pad_to(feats, Np, 0), _pad_to(w_head, Vp, 1), _col(labels, Np, -1),
      _row(log_prior_s, Vp, NEG_INF), _row(log_prior_k, Vp, NEG_INF))
    return tuple(o[:N, 0] for o in outs)


def lace2_bwd_pallas(feats, w_head, labels, log_prior_s, log_prior_k,
                     lse_s, lse_k, token_scale_s, token_scale_k, *,
                     tau: float = 1.0, tb: int = 128, vb: int = 256,
                     interpret: bool | None = None):
    """Fused dual backward: (df_s, df_k, dW_s), all f32.

    token_scale_s/_k (N,): per-token ``weight_i * cotangent_side`` — the
    two sides may carry different loss cotangents. The df pass shares one
    recomputed logits tile between both softmax cotangents; dW is emitted
    for the server side only (the split step discards the client head
    grad), reusing the single-prior dW kernel.
    """
    N, d = feats.shape
    V = w_head.shape[1]
    Np, Vp = _padded(N, V, tb, vb)
    feats_p = _pad_to(feats, Np, 0)
    w_p = _pad_to(w_head, Vp, 1)
    labels_p = _col(labels, Np, -1)
    lps_p = _row(log_prior_s, Vp, NEG_INF)
    lses_p = _col(lse_s, Np)
    gws_p = _col(token_scale_s, Np)

    df_s, df_k = pl.pallas_call(
        functools.partial(_bwd2_dfeats_kernel, vb=vb, nvb=Vp // vb, tau=tau),
        grid=(Np // tb, Vp // vb),
        in_specs=_specs(tb, d, vb, n_tok=4, n_voc=2),
        out_specs=[pl.BlockSpec((tb, d), lambda t, v: (t, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((Np, d), jnp.float32)] * 2,
        interpret=interp.resolve(interpret),
    )(feats_p, w_p, labels_p, lps_p, _row(log_prior_k, Vp, NEG_INF),
      lses_p, _col(lse_k, Np), gws_p, _col(token_scale_k, Np))
    dw_s = _dw_call(feats_p, w_p, labels_p, lps_p, lses_p, gws_p, tb=tb,
                    vb=vb, tau=tau, interpret=interpret)
    return df_s[:N], df_k[:N], dw_s[:, :V]
