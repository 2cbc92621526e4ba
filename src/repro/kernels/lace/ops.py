"""LACE — fused Logit-Adjusted Cross-Entropy, production ops.

``lace_loss`` computes the paper's adjusted CE (eqs. 14/15) **without
materializing the (N, V) logits**: a custom-vjp whose forward and
backward scan over token chunks, keeping only (G, chunk, V) logits live.
Inside each chunk the label log-prob is picked with an iota-mask (not a
gather), so vocab-sharded logits never force an all-gather under GSPMD.

Shapes: feats (G, N, d) — G parallel groups (SCALA clients) sharded over
the data axis, N tokens per group chunked sequentially; w_head (d, V);
labels/weights (G, N); prior_rows (K, V) with prior_ids (G,) selecting
each group's prior row (server loss: K=1; client loss: K=G). The ops are
shape-polymorphic in G — the sparse-slot and in-shard-gather paths call
them with a *gathered* subset of the client groups (G = cohort or the
shard-local K_active), with ``prior_ids`` indexing the gathered priors —
so group-axis mismatches are validated statically up front
(:func:`_check_args`) instead of broadcasting silently.

``impl='pallas'`` routes the inner chunk computation to the TPU kernel in
:mod:`repro.kernels.lace.kernel` (validated in interpret mode on CPU).

``lace2_*`` (bottom of this module) is the fused dual-prior boundary:
both SCALA losses (eq. 14 with P_s, eq. 15 with P_k) and their combined
VJP from ONE ``feats @ w_head`` product per chunk — see the section
banner below for the three entry points and the bitwise discipline.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def _pick_chunk(n: int, target: int) -> int:
    """The scan chunk along the token axis: always ``min(n, target)``.

    Non-divisible N is handled by padding the token axis up to the next
    chunk multiple with zero-weight tokens (:func:`_pad_tokens`) — NOT
    by shrinking the chunk, which used to degrade to chunk=1 for prime
    N (one (G, 1, V) matmul per token)."""
    return min(n, target)


def _pad_tokens(c, feats, labels, weights):
    """Pad the token axis to the next multiple of the chunk ``c``.

    Padding tokens carry weight 0 and label 0: they contribute exactly
    zero to the weighted NLL sum, the weight sum, and every gradient
    (the backward's per-token cotangent is scaled by the weight), so
    loss values and grads match the unpadded math. Returns
    (feats, labels, weights, n_orig) — ``weights`` materialized even
    when the caller passed None, so the zero-weight rows are explicit.
    """
    G, N, _ = feats.shape
    if weights is None:
        weights = jnp.ones((G, N), jnp.float32)
    pad = (-N) % c
    if pad:
        feats = jnp.pad(feats, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        weights = jnp.pad(weights, ((0, 0), (0, pad)))
    return feats, labels, weights, N


def _check_args(feats, w_head, labels, prior_rows, prior_ids, weights):
    """Static shape validation for the fused ops.

    The group axis G varies call-to-call (full K, a gathered cohort, a
    shard-local subset), and numpy broadcasting would happily accept a
    (K,)-sized ``prior_ids`` against cohort-sized feats — producing
    wrong per-group adjustments with no error. Fail loudly instead.
    """
    if feats.ndim != 3:
        raise ValueError(f"feats must be (G, N, d), got {feats.shape}")
    G, N, d = feats.shape
    if w_head.ndim != 2 or w_head.shape[0] != d:
        raise ValueError(f"w_head must be (d={d}, V), got {w_head.shape}")
    if labels.shape != (G, N):
        raise ValueError(f"labels must be (G, N)=({G}, {N}), got "
                         f"{labels.shape}")
    if weights is not None and weights.shape != (G, N):
        raise ValueError(f"weights must be (G, N)=({G}, {N}), got "
                         f"{weights.shape}")
    if prior_ids is not None:
        if prior_rows is None:
            raise ValueError("prior_ids given without prior_rows")
        if prior_ids.shape != (G,):
            raise ValueError(
                f"prior_ids must be (G,)=({G},), got {prior_ids.shape} — "
                "gathered-group callers must gather the prior ids (or "
                "rows) alongside the feats")
    if prior_rows is not None and prior_rows.shape[-1] != w_head.shape[1]:
        raise ValueError(f"prior_rows vocab dim {prior_rows.shape[-1]} != "
                         f"head vocab dim {w_head.shape[1]}")


def _chunk_logits(f_c, w_head, lp_c, tau):
    """f_c: (G, c, d); w_head: (d, V); lp_c: (G, 1, V) or None."""
    z = jnp.einsum("gcd,dv->gcv", f_c.astype(jnp.float32),
                   w_head.astype(jnp.float32))
    if lp_c is not None:
        z = z + tau * lp_c
    return z


def _nll_from_logits(z, labels_c):
    """z: (G,c,V); labels: (G,c). iota-mask label pick (gather-free)."""
    m = jnp.max(z, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(z - m), axis=-1)) + m[..., 0]
    iota = jax.lax.broadcasted_iota(jnp.int32, z.shape, 2)
    ll = jnp.sum(jnp.where(iota == labels_c[..., None], z, 0.0), axis=-1)
    return lse - ll


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def lace_loss(feats, w_head, labels, prior_rows, prior_ids, weights,
              tau: float = 1.0, eps: float = 1e-8, chunk: int = 4096):
    loss, _ = _lace_fwd(feats, w_head, labels, prior_rows, prior_ids,
                        weights, tau, eps, chunk)
    return loss


def _prep(feats, labels, prior_rows, prior_ids, weights, tau, eps):
    G, N, d = feats.shape
    if weights is None:
        weights = jnp.ones((G, N), jnp.float32)
    if prior_rows is not None:
        lp_rows = jnp.log(prior_rows.astype(jnp.float32) + eps)
        if prior_ids is None:
            lp = jnp.broadcast_to(lp_rows[:1], (G,) + lp_rows.shape[1:])
        else:
            lp = lp_rows[prior_ids]                       # (G, V)
        lp = lp[:, None, :]                               # (G, 1, V)
    else:
        lp = None
    return weights, lp


def _fwd_impl(feats, w_head, labels, prior_rows, prior_ids, weights,
              tau, eps, chunk, mean):
    _check_args(feats, w_head, labels, prior_rows, prior_ids, weights)
    res_in = (feats, w_head, labels, prior_rows, prior_ids, weights)
    G, N0, d = feats.shape
    c = _pick_chunk(N0, chunk)
    feats, labels, weights, _ = _pad_tokens(c, feats, labels, weights)
    N = feats.shape[1]
    weights_f, lp = _prep(feats, labels, prior_rows, prior_ids, weights,
                          tau, eps)
    nc = N // c

    fc = feats.reshape(G, nc, c, d).swapaxes(0, 1)       # (nc, G, c, d)
    lc = labels.reshape(G, nc, c).swapaxes(0, 1)
    wc = weights_f.reshape(G, nc, c).swapaxes(0, 1)

    def body(carry, inp):
        nll_sum, w_sum = carry
        f_c, l_c, w_c = inp
        z = _chunk_logits(f_c, w_head, lp, tau)
        nll = _nll_from_logits(z, l_c)
        return (nll_sum + jnp.sum(nll * w_c), w_sum + jnp.sum(w_c)), None

    (nll_sum, w_sum), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (fc, lc, wc))
    out = nll_sum / jnp.maximum(w_sum, 1e-8) if mean else nll_sum
    return out, res_in + (w_sum,)


def _bwd_impl(tau, eps, chunk, mean, res, g):
    feats, w_head, labels, prior_rows, prior_ids, weights, w_sum = res
    G, N0, d = feats.shape
    V = w_head.shape[1]
    c = _pick_chunk(N0, chunk)
    feats_p, labels_p, weights_p, _ = _pad_tokens(c, feats, labels, weights)
    N = feats_p.shape[1]
    weights_f, lp = _prep(feats_p, labels_p, prior_rows, prior_ids,
                          weights_p, tau, eps)
    nc = N // c

    fc = feats_p.reshape(G, nc, c, d).swapaxes(0, 1)
    lc = labels_p.reshape(G, nc, c).swapaxes(0, 1)
    wc = weights_f.reshape(G, nc, c).swapaxes(0, 1)
    scale = g / jnp.maximum(w_sum, 1e-8) if mean else g

    def body(dw, inp):
        f_c, l_c, w_c = inp
        z = _chunk_logits(f_c, w_head, lp, tau)
        m = jnp.max(z, axis=-1, keepdims=True)
        ez = jnp.exp(z - m)
        p = ez / jnp.sum(ez, axis=-1, keepdims=True)
        iota = jax.lax.broadcasted_iota(jnp.int32, z.shape, 2)
        onehot = (iota == l_c[..., None]).astype(jnp.float32)
        gi = (p - onehot) * (w_c * scale)[..., None]      # (G,c,V)
        df_c = jnp.einsum("gcv,dv->gcd", gi, w_head.astype(jnp.float32))
        dw = dw + jnp.einsum("gcd,gcv->dv", f_c.astype(jnp.float32), gi)
        return dw, df_c

    dw, dfc = jax.lax.scan(body, jnp.zeros((d, V), jnp.float32), (fc, lc, wc))
    dfeats = dfc.swapaxes(0, 1).reshape(G, N, d)[:, :N0].astype(feats.dtype)
    zeros_prior = (None if prior_rows is None
                   else jnp.zeros_like(prior_rows))
    f0 = lambda a: (None if a is None else
                    np.zeros(a.shape, jax.dtypes.float0)
                    if jnp.issubdtype(a.dtype, jnp.integer)
                    else jnp.zeros_like(a))
    return (dfeats, dw.astype(w_head.dtype), f0(labels), zeros_prior,
            f0(prior_ids), f0(weights))


def _lace_fwd(*a):
    return _fwd_impl(*a, True)


def _lace_bwd(tau, eps, chunk, res, g):
    return _bwd_impl(tau, eps, chunk, True, res, g)


lace_loss.defvjp(_lace_fwd, _lace_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def lace_nll_sum(feats, w_head, labels, prior_rows, prior_ids, weights,
                 tau: float = 1.0, eps: float = 1e-8, chunk: int = 4096):
    """Weighted *sum* of adjusted NLLs (no normalization) — the local term
    combined across shards by :func:`lace_loss_dp`."""
    out, _ = _fwd_impl(feats, w_head, labels, prior_rows, prior_ids,
                       weights, tau, eps, chunk, False)
    return out


def _lace_sum_fwd(*a):
    return _fwd_impl(*a, False)


def _lace_sum_bwd(tau, eps, chunk, res, g):
    return _bwd_impl(tau, eps, chunk, False, res, g)


lace_nll_sum.defvjp(_lace_sum_fwd, _lace_sum_bwd)


def lace_loss_dp(feats, w_head, labels, prior_rows, prior_ids, weights,
                 tau: float = 1.0, eps: float = 1e-8, chunk: int = 4096,
                 group_axes=("pod", "data"), token_axes=("model",)):
    """shard_map-wrapped LACE for the replicated-head ("dp") profile.

    Under GSPMD the chunked-CE backward re-all-reduces the (d, V)
    head-weight gradient partial on every chunk trip (§Perf iteration 3).
    Here the loss is computed per-shard on local tokens and combined with
    two scalar psums; the head-weight gradient is psummed exactly once by
    the shard_map transpose. Exact same value/grads as ``lace_loss``.

    feats (G, N, d) with G sharded over ``group_axes`` and N over
    ``token_axes``; w_head replicated. Falls back to ``lace_loss`` when
    there is no ambient mesh (CPU tests / host training).
    """
    from repro.sharding.logical import ambient_mesh, in_shard_map

    mesh = ambient_mesh()
    if mesh is None or in_shard_map():
        return lace_loss(feats, w_head, labels, prior_rows, prior_ids,
                         weights, tau, eps, chunk)
    present = lambda axes: tuple(a for a in axes if a in mesh.axis_names)
    grp = present(group_axes)
    tok = present(token_axes)
    red = grp + tok
    if not red:
        return lace_loss(feats, w_head, labels, prior_rows, prior_ids,
                         weights, tau, eps, chunk)
    P = jax.sharding.PartitionSpec
    g_spec = grp if len(grp) > 1 else (grp[0] if grp else None)
    t_spec = tok if len(tok) > 1 else (tok[0] if tok else None)
    gt = P(g_spec, t_spec)
    gtd = P(g_spec, t_spec, None)

    per_client_prior = prior_ids is not None
    pr_spec = P(g_spec, None) if per_client_prior else P(None, None)

    def local(f_l, w_l, l_l, pr_l, wt_l):
        ids = (jnp.arange(f_l.shape[0]) if per_client_prior else None)
        nll = lace_nll_sum(f_l, w_l, l_l, pr_l, ids, wt_l, tau, eps, chunk)
        wsum = (jnp.sum(wt_l) if wt_l is not None
                else jnp.float32(l_l.size))
        return (jax.lax.psum(nll, red),
                jax.lax.psum(jnp.asarray(wsum, jnp.float32), red))

    in_specs = (gtd, P(None, None), gt,
                pr_spec if prior_rows is not None else P(),
                gt if weights is not None else P())
    fn = jax.shard_map(
        lambda f, w, l, pr, wt: local(
            f, w, l, pr if prior_rows is not None else None,
            wt if weights is not None else None),
        mesh=mesh, in_specs=in_specs, out_specs=(P(), P()),
        check_vma=False)  # scan carries start unvarying; values exact
    dummy = jnp.zeros((), jnp.float32)
    nll, wsum = fn(feats, w_head, labels,
                   prior_rows if prior_rows is not None else dummy[None, None],
                   weights if weights is not None else dummy[None, None])
    return nll / jnp.maximum(wsum, 1e-8)


# ---------------------------------------------------------------------------
# lace2 — fused dual-prior boundary (eq. 14 + eq. 15 in ONE pass)
# ---------------------------------------------------------------------------
#
# SCALA evaluates the adjusted CE twice per step: once with the server's
# concatenated prior P_s (eq. 14) and once with the per-client priors P_k
# (eq. 15). The two losses share everything except the prior shift: the
# ``feats @ w_head`` product, its transpose in the backward, and the
# per-chunk streaming machinery. ``lace2_*`` computes both NLLs (and the
# combined VJP) from ONE matmul per chunk — halving the dominant FLOPs.
#
# Three entry points:
#   * ``lace2_loss`` / ``lace2_nll_sum`` — custom-VJP pair ops returning
#     ``(out_s, out_k)``; the backward folds both cotangents into a single
#     ``dfeats``/``dw_head`` accumulation (one df matmul, one dW matmul).
#   * ``lace2_grads`` — direct value-and-grad for the engine's split step,
#     which needs the two feature cotangents SEPARATELY (they enter the
#     trunk pullback with different loss cotangents): returns
#     ``(out_s, out_k, df_s, df_k, dw_s, w_sum)`` in 4 matmuls where the
#     two-pass path spends 8.
#   * ``lace2_grads_dp`` — ambient-mesh shard-map wrapper mirroring
#     :func:`lace_loss_dp` (scalar psums + one dW psum).
#
# Bitwise discipline: every op below reuses the single-prior primitives
# (`_chunk_logits`-equivalent add order, `_nll_from_logits` reductions,
# `_bwd_impl`'s ``(w_c * scale)`` placement and accumulation order) so the
# fused f32 results are bit-identical to two independent lace calls.


def _check_args2(feats, w_head, labels, prior_rows_s, prior_ids_s,
                 prior_rows_k, prior_ids_k, weights):
    _check_args(feats, w_head, labels, prior_rows_s, prior_ids_s, weights)
    _check_args(feats, w_head, labels, prior_rows_k, prior_ids_k, weights)


def _prep2(feats, labels, prior_rows_s, prior_ids_s, prior_rows_k,
           prior_ids_k, weights, tau, eps):
    """Dual-prior variant of :func:`_prep`: one weights array, two lp."""
    weights, lp_s = _prep(feats, labels, prior_rows_s, prior_ids_s,
                          weights, tau, eps)
    _, lp_k = _prep(feats, labels, prior_rows_k, prior_ids_k,
                    weights, tau, eps)
    return weights, lp_s, lp_k


def _chunk_views(feats, labels, weights, c):
    """(G, N, ·) -> chunk-major (nc, G, c, ·) scan views."""
    G, N, d = feats.shape
    nc = N // c
    fc = feats.reshape(G, nc, c, d).swapaxes(0, 1)
    lc = labels.reshape(G, nc, c).swapaxes(0, 1)
    wc = weights.reshape(G, nc, c).swapaxes(0, 1)
    return fc, lc, wc, nc


def _w_sum_scan(wc):
    """Chunk-ordered weight-sum accumulation — same op sequence as the
    ``w_sum`` carry in :func:`_fwd_impl`, so the mean denominators (and
    the scales derived from them) are bit-identical to the two-pass path."""
    def body(ws, w_c):
        return ws + jnp.sum(w_c), None
    w_sum, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), wc)
    return w_sum


def _side_adjust(z_base, lp, tau):
    """Apply one prior's shift — the same ``z + tau * lp`` as
    :func:`_chunk_logits` (identity when the side has no prior)."""
    return z_base if lp is None else z_base + tau * lp


def _side_nll_stats(z, l_c):
    """max/exp/sum stats shared between the NLL value and softmax grads.

    Value path matches :func:`_nll_from_logits` op-for-op; ``ez``/``se``
    are reused by the backward's softmax (as in :func:`_bwd_impl`).
    """
    m = jnp.max(z, axis=-1, keepdims=True)
    ez = jnp.exp(z - m)
    se = jnp.sum(ez, axis=-1)
    lse = jnp.log(se) + m[..., 0]
    iota = jax.lax.broadcasted_iota(jnp.int32, z.shape, 2)
    onehot = (iota == l_c[..., None]).astype(jnp.float32)
    ll = jnp.sum(jnp.where(iota == l_c[..., None], z, 0.0), axis=-1)
    nll = lse - ll
    p = ez / se[..., None]
    return nll, p, onehot


def _fwd2_chunk_sums(z, l_c, w_c, lp_s, lp_k, tau):
    """Both sides' weighted NLL sums for one chunk's shared logits ``z``.

    The one value path of the fused ops: :func:`lace2_nll_sum` /
    :func:`lace2_loss` and :func:`lace2_grads` all reduce through it, so
    their loss values are the same computation, not two that agree."""
    nll_s = _nll_from_logits(_side_adjust(z, lp_s, tau), l_c)
    nll_k = _nll_from_logits(_side_adjust(z, lp_k, tau), l_c)
    return jnp.sum(nll_s * w_c), jnp.sum(nll_k * w_c)


def _fwd2_impl(feats, w_head, labels, prior_rows_s, prior_ids_s,
               prior_rows_k, prior_ids_k, weights, tau, eps, chunk, mean):
    _check_args2(feats, w_head, labels, prior_rows_s, prior_ids_s,
                 prior_rows_k, prior_ids_k, weights)
    res_in = (feats, w_head, labels, prior_rows_s, prior_ids_s,
              prior_rows_k, prior_ids_k, weights)
    G, N0, d = feats.shape
    c = _pick_chunk(N0, chunk)
    feats_p, labels_p, weights_p, _ = _pad_tokens(c, feats, labels, weights)
    weights_f, lp_s, lp_k = _prep2(feats_p, labels_p, prior_rows_s,
                                   prior_ids_s, prior_rows_k, prior_ids_k,
                                   weights_p, tau, eps)
    fc, lc, wc, _ = _chunk_views(feats_p, labels_p, weights_f, c)

    def body(carry, inp):
        nll_s_sum, nll_k_sum, w_sum = carry
        f_c, l_c, w_c = inp
        z = _chunk_logits(f_c, w_head, None, tau)        # ONE matmul
        sum_s, sum_k = _fwd2_chunk_sums(z, l_c, w_c, lp_s, lp_k, tau)
        return (nll_s_sum + sum_s, nll_k_sum + sum_k,
                w_sum + jnp.sum(w_c)), None

    zero = jnp.zeros((), jnp.float32)
    (nll_s_sum, nll_k_sum, w_sum), _ = jax.lax.scan(
        body, (zero, zero, zero), (fc, lc, wc))
    if mean:
        den = jnp.maximum(w_sum, 1e-8)
        out = (nll_s_sum / den, nll_k_sum / den)
    else:
        out = (nll_s_sum, nll_k_sum)
    return out, res_in + (w_sum,)


def _bwd2_impl(tau, eps, chunk, mean, res, g):
    (feats, w_head, labels, prior_rows_s, prior_ids_s, prior_rows_k,
     prior_ids_k, weights, w_sum) = res
    g_s, g_k = g
    G, N0, d = feats.shape
    V = w_head.shape[1]
    c = _pick_chunk(N0, chunk)
    feats_p, labels_p, weights_p, _ = _pad_tokens(c, feats, labels, weights)
    N = feats_p.shape[1]
    weights_f, lp_s, lp_k = _prep2(feats_p, labels_p, prior_rows_s,
                                   prior_ids_s, prior_rows_k, prior_ids_k,
                                   weights_p, tau, eps)
    fc, lc, wc, _ = _chunk_views(feats_p, labels_p, weights_f, c)
    den = jnp.maximum(w_sum, 1e-8)
    scale_s = g_s / den if mean else g_s
    scale_k = g_k / den if mean else g_k

    def body(dw, inp):
        f_c, l_c, w_c = inp
        z = _chunk_logits(f_c, w_head, None, tau)        # ONE matmul
        _, p_s, onehot = _side_nll_stats(_side_adjust(z, lp_s, tau), l_c)
        _, p_k, _ = _side_nll_stats(_side_adjust(z, lp_k, tau), l_c)
        gi = ((p_s - onehot) * (w_c * scale_s)[..., None]
              + (p_k - onehot) * (w_c * scale_k)[..., None])
        df_c = jnp.einsum("gcv,dv->gcd", gi, w_head.astype(jnp.float32))
        dw = dw + jnp.einsum("gcd,gcv->dv", f_c.astype(jnp.float32), gi)
        return dw, df_c

    dw, dfc = jax.lax.scan(body, jnp.zeros((d, V), jnp.float32), (fc, lc, wc))
    dfeats = dfc.swapaxes(0, 1).reshape(G, N, d)[:, :N0].astype(feats.dtype)
    f0 = lambda a: (None if a is None else
                    np.zeros(a.shape, jax.dtypes.float0)
                    if jnp.issubdtype(a.dtype, jnp.integer)
                    else jnp.zeros_like(a))
    zp = lambda a: None if a is None else jnp.zeros_like(a)
    return (dfeats, dw.astype(w_head.dtype), f0(labels), zp(prior_rows_s),
            f0(prior_ids_s), zp(prior_rows_k), f0(prior_ids_k), f0(weights))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def lace2_loss(feats, w_head, labels, prior_rows_s, prior_ids_s,
               prior_rows_k, prior_ids_k, weights,
               tau: float = 1.0, eps: float = 1e-8, chunk: int = 4096):
    """Both weighted-mean adjusted NLLs from one matmul per chunk.

    Returns ``(loss_s, loss_k)`` — the eq. 14 (prior ``_s``) and eq. 15
    (prior ``_k``) losses over the SAME feats/labels/weights. Either
    prior may be None (plain CE for that side). The custom backward
    folds both cotangents into one ``dfeats``/``dw_head`` accumulation.
    """
    out, _ = _lace2_fwd(feats, w_head, labels, prior_rows_s, prior_ids_s,
                        prior_rows_k, prior_ids_k, weights, tau, eps, chunk)
    return out


def _lace2_fwd(*a):
    return _fwd2_impl(*a, True)


def _lace2_bwd(tau, eps, chunk, res, g):
    return _bwd2_impl(tau, eps, chunk, True, res, g)


lace2_loss.defvjp(_lace2_fwd, _lace2_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def lace2_nll_sum(feats, w_head, labels, prior_rows_s, prior_ids_s,
                  prior_rows_k, prior_ids_k, weights,
                  tau: float = 1.0, eps: float = 1e-8, chunk: int = 4096):
    """Weighted *sums* of both adjusted NLLs (no normalization) — the
    local pair combined across shards by the dp paths."""
    out, _ = _fwd2_impl(feats, w_head, labels, prior_rows_s, prior_ids_s,
                        prior_rows_k, prior_ids_k, weights, tau, eps,
                        chunk, False)
    return out


def _lace2_sum_fwd(*a):
    return _fwd2_impl(*a, False)


def _lace2_sum_bwd(tau, eps, chunk, res, g):
    return _bwd2_impl(tau, eps, chunk, False, res, g)


lace2_nll_sum.defvjp(_lace2_sum_fwd, _lace2_sum_bwd)


def lace2_grads(feats, w_head, labels, prior_rows_s, prior_ids_s,
                prior_rows_k, prior_ids_k, weights,
                tau: float = 1.0, eps: float = 1e-8, chunk: int = 4096,
                mean: bool = True, scale: Optional[jax.Array] = None):
    """One-pass values AND grads for the engine's split boundary.

    The split step needs the two feature cotangents SEPARATELY (the
    server/client trunk pullbacks take different loss cotangents), so the
    pair ops' combined backward doesn't fit; this direct form computes,
    in a single scan with ONE logits matmul per chunk::

        out_s, out_k      eq. 14 / eq. 15 losses (mean or raw sums)
        df_s, df_k        d out_side / d feats (unit cotangent)
        dw_s              d out_s / d w_head (server side only — the
                          two-pass engine discards the client head grad)
        w_sum             the weight denominator (chunk-ordered)

    With ``mean=True`` each side's grads carry the ``1 / max(w_sum, eps)``
    scale exactly where :func:`_bwd_impl` applies it. ``scale`` overrides
    the per-token scale for both sides (dp callers pass ``1 / w_global``);
    ``mean=False, scale=None`` yields unit-cotangent raw-sum grads, the
    contract of the engine's ``lace_dp`` branch. 4 matmul-equivalents vs.
    8 for the two-pass path.
    """
    _check_args2(feats, w_head, labels, prior_rows_s, prior_ids_s,
                 prior_rows_k, prior_ids_k, weights)
    G, N0, d = feats.shape
    V = w_head.shape[1]
    c = _pick_chunk(N0, chunk)
    feats_p, labels_p, weights_p, _ = _pad_tokens(c, feats, labels, weights)
    N = feats_p.shape[1]
    weights_f, lp_s, lp_k = _prep2(feats_p, labels_p, prior_rows_s,
                                   prior_ids_s, prior_rows_k, prior_ids_k,
                                   weights_p, tau, eps)
    fc, lc, wc, _ = _chunk_views(feats_p, labels_p, weights_f, c)
    w_sum = _w_sum_scan(wc)
    if scale is None:
        one = jnp.ones((), jnp.float32)
        scale = one / jnp.maximum(w_sum, 1e-8) if mean else one

    def body(carry, inp):
        nll_s_sum, nll_k_sum, dw = carry
        f_c, l_c, w_c = inp
        z = _chunk_logits(f_c, w_head, None, tau)        # ONE matmul
        sum_s, sum_k = _fwd2_chunk_sums(z, l_c, w_c, lp_s, lp_k, tau)
        # the barrier keeps XLA from fusing the value path into the
        # softmax below, which would reorder its reductions
        zg = jax.lax.optimization_barrier(z)
        _, p_s, onehot = _side_nll_stats(_side_adjust(zg, lp_s, tau), l_c)
        _, p_k, _ = _side_nll_stats(_side_adjust(zg, lp_k, tau), l_c)
        gi_s = (p_s - onehot) * (w_c * scale)[..., None]
        gi_k = (p_k - onehot) * (w_c * scale)[..., None]
        w32 = w_head.astype(jnp.float32)
        df_s_c = jnp.einsum("gcv,dv->gcd", gi_s, w32)
        df_k_c = jnp.einsum("gcv,dv->gcd", gi_k, w32)
        dw = dw + jnp.einsum("gcd,gcv->dv", f_c.astype(jnp.float32), gi_s)
        return (nll_s_sum + sum_s, nll_k_sum + sum_k, dw), (df_s_c, df_k_c)

    zero = jnp.zeros((), jnp.float32)
    (nll_s_sum, nll_k_sum, dw), (dfc_s, dfc_k) = jax.lax.scan(
        body, (zero, zero, jnp.zeros((d, V), jnp.float32)), (fc, lc, wc))
    unchunk = lambda a: (a.swapaxes(0, 1).reshape(G, N, d)[:, :N0]
                         .astype(feats.dtype))
    if mean:
        den = jnp.maximum(w_sum, 1e-8)
        out_s, out_k = nll_s_sum / den, nll_k_sum / den
    else:
        out_s, out_k = nll_s_sum, nll_k_sum
    return (out_s, out_k, unchunk(dfc_s), unchunk(dfc_k),
            dw.astype(w_head.dtype), w_sum)


def lace2_grads_dp(feats, w_head, labels, prior_rows_s, prior_ids_s,
                   prior_rows_k, prior_ids_k, weights,
                   tau: float = 1.0, eps: float = 1e-8, chunk: int = 4096,
                   group_axes=("pod", "data"), token_axes=("model",)):
    """Ambient-mesh fused boundary, mirroring :func:`lace_loss_dp`.

    Per-shard :func:`lace2_grads` over local tokens, combined with scalar
    psums for the losses/denominator and ONE dW psum (vs. the per-chunk
    re-all-reduce GSPMD emits for the chunked backward). ``df_s``/``df_k``
    stay shard-local, matching the sharded feats. Falls back to
    :func:`lace2_grads` when there is no ambient mesh (where bitwise
    parity with the two-pass path is test-enforced); under a mesh the
    grads are the mathematically exact global-mean grads via explicit
    psums.
    """
    from repro.sharding.logical import ambient_mesh, in_shard_map

    mesh = ambient_mesh()
    if mesh is None or in_shard_map():
        out = lace2_grads(feats, w_head, labels, prior_rows_s, prior_ids_s,
                          prior_rows_k, prior_ids_k, weights, tau, eps, chunk)
        return out[:5]
    present = lambda axes: tuple(a for a in axes if a in mesh.axis_names)
    grp = present(group_axes)
    tok = present(token_axes)
    red = grp + tok
    if not red:
        out = lace2_grads(feats, w_head, labels, prior_rows_s, prior_ids_s,
                          prior_rows_k, prior_ids_k, weights, tau, eps, chunk)
        return out[:5]
    P = jax.sharding.PartitionSpec
    g_spec = grp if len(grp) > 1 else (grp[0] if grp else None)
    t_spec = tok if len(tok) > 1 else (tok[0] if tok else None)
    gt = P(g_spec, t_spec)
    gtd = P(g_spec, t_spec, None)

    has_s = prior_rows_s is not None
    has_k = prior_rows_k is not None
    per_client_k = prior_ids_k is not None
    ps_spec = P(None, None)
    pk_spec = P(g_spec, None) if per_client_k else P(None, None)

    def local(f_l, w_l, l_l, prs_l, prk_l, wt_l):
        ids_k = jnp.arange(f_l.shape[0]) if per_client_k else None
        nll_s, nll_k, df_s, df_k, dw_s, ws_l = lace2_grads(
            f_l, w_l, l_l, prs_l if has_s else None, None,
            prk_l if has_k else None, ids_k,
            wt_l, tau, eps, chunk, mean=False, scale=None)
        den = jnp.maximum(
            jax.lax.psum(jnp.asarray(ws_l, jnp.float32), red), 1e-8)
        inv = jnp.ones((), jnp.float32) / den
        # unit-cotangent raw-sum grads -> global-mean grads (linear rescale)
        rescale = lambda a: (a.astype(jnp.float32) * inv).astype(a.dtype)
        return (jax.lax.psum(nll_s, red) * inv,
                jax.lax.psum(nll_k, red) * inv,
                rescale(df_s), rescale(df_k),
                jax.lax.psum(rescale(dw_s).astype(jnp.float32),
                             red).astype(dw_s.dtype))

    dummy = jnp.zeros((), jnp.float32)
    in_specs = (gtd, P(None, None), gt,
                ps_spec if has_s else P(),
                pk_spec if has_k else P(),
                gt if weights is not None else P())
    fn = jax.shard_map(
        lambda f, w, l, prs, prk, wt: local(
            f, w, l, prs if has_s else None, prk if has_k else None,
            wt if weights is not None else None),
        mesh=mesh, in_specs=in_specs,
        out_specs=(P(), P(), gtd, gtd, P(None, None)),
        check_vma=False)  # scan carries start unvarying; values exact
    return fn(feats, w_head, labels,
              prior_rows_s if has_s else dummy[None, None],
              prior_rows_k if has_k else dummy[None, None],
              weights if weights is not None else dummy[None, None])


# ---------------------------------------------------------------------------
# convenience wrappers
# ---------------------------------------------------------------------------


def lace_loss_flat(feats, w_head, labels, *, prior_rows=None, prior_ids=None,
                   weights=None, tau: float = 1.0, eps: float = 1e-8,
                   chunk: int = 4096):
    """(N, d) single-group convenience wrapper."""
    f = feats[None]
    l = labels[None]
    w = None if weights is None else weights[None]
    ids = None if prior_ids is None else prior_ids[None]
    return lace_loss(f, w_head, l, prior_rows, ids, w, tau, eps, chunk)
