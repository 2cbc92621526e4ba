"""The split-step engine: ONE implementation of the SCALA local iteration.

Every SCALA step — naive, fused-LACE, manual-SPMD — is the same five-stage
pipeline (paper Alg. 2 lines 9-20); this module implements it once and
parameterizes the two points where the variants actually differ:

  stage 1  label priors        P_k per client, P_s concatenated (eqs. 5-6,
                               the log-prior terms of eqs. 14-15)
  stage 2  client forward      vmap over the stacked client axis (eq. 4;
                               client-parallel on the mesh)
  stage 3  server forward+vjp  one forward of the server half (eq. 6), one
                               linearization reused by both losses
  stage 4  dual pullbacks      P_s-adjusted loss -> d w_s (eqs. 14, 7);
                               P_k-adjusted loss -> G_k -> d w_k via each
                               client's chain rule (eqs. 15, 8-9)
  stage 5  parameter update    an :class:`repro.optim.Optimizer` with lr
                               from :mod:`repro.optim.schedules` (the paper
                               uses plain SGD, eq. 7/9; the engine threads
                               any optimizer state through the params tree)

The variation points:

* **loss backend** (stage 3-4 flavor):

  - ``"logits"``  — materialize full (tokens, V) logits through
    ``model.server_fwd`` and use :func:`repro.core.losses.softmax_xent`.
    Reference semantics; fine for CIFAR-scale heads.
  - ``"lace"``    — run ``model.server_trunk`` to features and fuse
    head-matmul + adjusted CE with the chunked LACE op
    (:mod:`repro.kernels.lace`), never materializing logits; required for
    the 262k-vocab archs.
  - ``"lace_dp"`` — the replicated-weight manual-SPMD profile: the whole
    step runs inside one ``shard_map`` and the engine inserts the minimal
    collective schedule (histogram psums for the priors, two scalar loss
    psums, ONE psum of the server grad tree, one per-client grad psum over
    the inner axis), keeping the per-step wire cost at the DDP lower bound
    of 2x|w_s| + 2x|w_c|.

* **boundary flavor** (stage 3-4 pass count, :data:`BOUNDARIES`): the
  paper's dual objective evaluates the adjusted CE twice per step — once
  with the concatenated prior P_s (eq. 14) and once with the per-client
  priors P_k (eq. 15). ``boundary="dual"`` runs them as two independent
  ``value_and_grad`` evaluations; ``boundary="fused"`` (default) computes
  both NLLs and both cotangents in ONE pass over a shared
  ``features @ w_head`` product (:func:`repro.kernels.lace.ops.lace2_grads`
  for the LACE backends, :func:`repro.core.losses.dual_adjusted_xent`
  over the shared materialized logits for ``"logits"``), halving the
  loss-stage FLOPs. All gradients — hence parameter updates and the
  whole training trajectory — are bit-identical f32 to the dual path
  (test-enforced per backend). The reported LACE loss *metrics* sit
  within 1 ulp: the fused values match the plain ``lace_loss`` forward
  bitwise, while the dual baseline reads them through
  ``value_and_grad``, whose residual-saving scan compiles to slightly
  different roundings. The one dual fallback is ``"logits"`` with
  ``label_smoothing > 0``, where the mirrored backward is only
  ulp-accurate.

* **optimizer / schedule** (stage 5): any :class:`repro.optim.Optimizer`;
  client state is vmapped per client so every state leaf carries the
  stacked (C, ...) axis and shards exactly like the client params.

On top of the per-step engine, :func:`make_round_runner` /
:func:`scala_round_scan` compile T local iterations *plus* the FL phase
into a single ``lax.scan``-based XLA program — one dispatch per round
instead of T+1. The FL phase itself is pluggable via the federation
layer (:mod:`repro.fed`): an ``Aggregator`` picks the per-client
aggregation weights (FedAvg, data-size weighted, BESplit-style
bias-compensated, GAS-style staleness-decayed), a
``ParticipationScheduler`` samples the per-round client subset as a 0/1
mask over the static client axis (priors and logit adjustments are then
recomputed per subset), ``slot_gather=True`` packs that subset into a
dense ``[K_active]`` compute axis (subset-cost rounds at static
shapes), ``server_optimizer=`` adds FedOpt over the server half's round
delta, and ``opt_state_policy`` fixes what happens to client optimizer
state at the round boundary (carry | reset | average — see
:func:`make_round_runner`). Asynchronous execution — per-client
snapshots, sampled completion delays, staleness-weighted delayed
aggregation per arrival cohort — lives in :mod:`repro.fed.runtime` and
reuses the same engine step and sparse-slot gather.

The legacy entry points in :mod:`repro.core.scala` are thin wrappers over
:func:`local_step` with plain SGD.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ScalaConfig
from repro.core import losses
from repro.core.label_stats import client_and_concat_priors, histogram
from repro.core.split import redistribute, stack_client_params, weighted_mean
from repro.optim import optimizers, schedules

BACKENDS = ("logits", "lace", "lace_dp")

#: compute-precision policies for the split step. ``"f32"`` is exact
#: (legacy HLO); ``"bf16"`` runs the client forward, the concat-
#: activation server trunk, and both backward passes in bfloat16 while
#: the master params, optimizer state, label priors / logit
#: adjustments, loss reductions, and the FL aggregation stay float32
#: (the LACE kernels upcast per chunk, so the fused loss composes
#: unchanged). Halves the live activation set AND the split-boundary
#: wire traffic.
PRECISIONS = ("f32", "bf16")

#: split-boundary loss flavors. ``"dual"`` evaluates the eq. (14) and
#: eq. (15) objectives as two independent ``value_and_grad`` passes over
#: the head (the paper's literal two-loss schedule); ``"fused"``
#: (default) computes both NLLs and both feature cotangents in one pass
#: over a shared ``features @ w_head`` product — halving the loss-stage
#: matmul count. Gradients (and therefore the training trajectory) are
#: bit-identical f32 to ``"dual"`` for every backend; LACE loss metrics
#: are 1-ulp (see the module docstring). ``"logits"`` with
#: ``label_smoothing > 0`` silently falls back to the dual schedule
#: (the mirrored backward is only ulp-accurate there).
BOUNDARIES = ("dual", "fused")


# ---------------------------------------------------------------------------
# model adapter
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class SplitModel:
    """Functional adapter: the two halves of a split model.

    client_fwd(wc, batch) -> acts dict with key 'x' (+ optional 'memory',
    'positions'); server_fwd(ws, acts) -> (logits, aux_loss).

    For the fused (LACE) backends, additionally:
    server_trunk(ws, acts) -> (features, aux) — everything *except* the
    classifier head — and head_weight(ws) -> (d, V) so the loss can fuse
    head-matmul + adjusted CE without materializing logits.
    """

    client_fwd: Callable[[Any, Dict[str, Any]], Dict[str, Any]]
    server_fwd: Callable[[Any, Dict[str, Any]], Any]
    num_classes: int
    server_trunk: Optional[Callable[[Any, Dict[str, Any]], Any]] = None
    head_weight: Optional[Callable[[Any], Any]] = None
    head_grad_merge: Optional[Callable[[Any, Any], Any]] = None
    # replicated-head ("dp") profile: route the fused loss through the
    # shard_map LACE so the head grad is psummed once (§Perf iteration 3)
    dp_loss: bool = False


# ---------------------------------------------------------------------------
# mixed precision
# ---------------------------------------------------------------------------


def cast_floats(tree, dtype):
    """Cast every floating leaf of a pytree to ``dtype`` (ints/keys pass
    through untouched)."""
    return jax.tree.map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating) else a, tree)


def cast_to_compute(model: SplitModel, precision: str) -> SplitModel:
    """Wrap a :class:`SplitModel` with a compute-precision policy.

    ``"f32"`` returns the model unchanged. ``"bf16"`` casts the param
    halves and float batch inputs to bfloat16 *inside* each wrapped
    forward, so activations and both backward passes run in bf16 while
    the master params stay f32 — and because the cast sits inside the
    differentiated functions, its transpose upcasts the cotangents and
    every param gradient lands back in f32. The fused-loss hooks
    (``head_weight``) hand the LACE ops a bf16 head; the ops upcast per
    chunk, so loss values and logit adjustments are still computed in
    f32 (``head_grad_merge`` receives the chunk-accumulated f32 partial
    cast to the head dtype, exactly as before).
    """
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}; expected "
                         f"{PRECISIONS}")
    if precision == "f32":
        return model
    bf16 = jnp.bfloat16

    def client_fwd(wc, batch):
        return model.client_fwd(cast_floats(wc, bf16),
                                cast_floats(batch, bf16))

    def server_fwd(ws, acts):
        return model.server_fwd(cast_floats(ws, bf16), acts)

    kw = {}
    if model.server_trunk is not None:
        kw["server_trunk"] = (
            lambda ws, acts: model.server_trunk(cast_floats(ws, bf16), acts))
    if model.head_weight is not None:
        kw["head_weight"] = (
            lambda ws: cast_floats(model.head_weight(ws), bf16))
    return dataclasses.replace(model, client_fwd=client_fwd,
                               server_fwd=server_fwd, **kw)


# ---------------------------------------------------------------------------
# small shared pieces
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MeshAxes:
    """Mesh-axis roles for the manual-SPMD ("lace_dp") backend: the client
    axis is sharded over ``client``, each client's batch over ``inner``."""

    client: Tuple[str, ...] = ()
    inner: Tuple[str, ...] = ()

    @property
    def all(self) -> Tuple[str, ...]:
        return self.client + self.inner


def mesh_axes(mesh) -> MeshAxes:
    names = set(mesh.axis_names)
    return MeshAxes(client=tuple(a for a in ("pod", "data") if a in names),
                    inner=tuple(a for a in ("model",) if a in names))


def _flat(a):
    return a.reshape((-1,) + a.shape[2:])


def _prior_for_tokens(p, labels_shape):
    """Broadcast a (..., N) prior against token labels (...,) -> (..., 1s, N)."""
    extra = len(labels_shape) - (p.ndim - 1)
    return p.reshape(p.shape[:-1] + (1,) * extra + (p.shape[-1],))


def default_ce_chunk(num_classes: int) -> int:
    # larger chunks -> fewer head-grad all-reduce trips in the chunked
    # CE loop (the gW partial is re-reduced every trip); cap the global
    # chunk so logits stay ~2^32 elements (§Perf iteration 3)
    return max(4096, (1 << 32) // max(1, num_classes))


def _priors(labels, weights, N, scala: ScalaConfig, axes: Optional[MeshAxes]):
    """Stage 1: (P_k (C,N), P_s (N,)) — local stats, or psummed on a mesh."""
    if axes is None:
        return client_and_concat_priors(labels, N, weights,
                                        eps=scala.prior_eps)
    # manual-SPMD: local histogram -> psums (paper eq. 14/15)
    C_l = labels.shape[0]
    hist_k = jax.vmap(lambda l, w: histogram(l, N, w))(
        labels.reshape(C_l, -1),
        (jnp.ones((C_l, labels[0].size), jnp.float32) if weights is None
         else weights.reshape(C_l, -1)))                   # (C_l, N)
    if axes.inner:
        hist_k = jax.lax.psum(hist_k, axes.inner)          # full client hist
    hist_s = jax.lax.psum(hist_k.sum(0), axes.client) \
        if axes.client else hist_k.sum(0)
    p_k = hist_k / jnp.maximum(hist_k.sum(-1, keepdims=True), 1e-8)
    p_s = hist_s / jnp.maximum(hist_s.sum(), 1e-8)
    return p_k, p_s


def _server_vjp(fwd, ws, acts):
    """Stage 3: linearize the server fn (server_fwd or server_trunk) wrt
    (w_s, x[, memory]) with positions closed over. Returns
    ((out, aux), vjp, has_mem)."""
    x = acts["x"]
    has_mem = "memory" in acts
    positions = acts["positions"][0] if "positions" in acts else None

    if has_mem:
        def f(ws, xf, memf):
            a = {"x": xf, "memory": memf}
            if positions is not None:
                a["positions"] = positions
            return fwd(ws, a)
        out, vjp = jax.vjp(f, ws, _flat(x), _flat(acts["memory"]))
    else:
        def f(ws, xf):
            a = {"x": xf}
            if positions is not None:
                a["positions"] = positions
            return fwd(ws, a)
        out, vjp = jax.vjp(f, ws, _flat(x))
    return out, vjp, has_mem


def _dual_pullbacks(vjp, g_s, g_k, aux_dtype, has_mem):
    """Stage 4a: one pullback per loss — P_s cotangent charges w_s (the aux
    loss rides with it), P_k cotangent yields the activation grads G_k."""
    one = jnp.ones((), aux_dtype)
    zero = jnp.zeros((), aux_dtype)
    if has_mem:
        d_ws, _, _ = vjp((g_s, one))
        _, g_x, g_mem = vjp((g_k, zero))
    else:
        d_ws, _ = vjp((g_s, one))
        _, g_x = vjp((g_k, zero))
        g_mem = None
    return d_ws, g_x, g_mem


def _client_pullback(model: SplitModel, wc, batch, acts, g_x, g_mem, has_mem):
    """Stage 4b (eq. 9): each client backprops its own G_k through its half."""
    g_x = g_x.reshape(acts["x"].shape)
    if g_mem is not None:
        g_mem = g_mem.reshape(acts["memory"].shape)

    def one(w, b, gx_k, gmem_k):
        def f(wk):
            a = model.client_fwd(wk, b)
            if has_mem:
                return a["x"], a["memory"]
            return a["x"]
        _, cvjp = jax.vjp(f, w)
        ct = (gx_k, gmem_k) if has_mem else gx_k
        return cvjp(ct)[0]

    if has_mem:
        return jax.vmap(one)(wc, batch, g_x, g_mem)
    return jax.vmap(lambda w, b, g: one(w, b, g, None))(wc, batch, g_x)


# ---------------------------------------------------------------------------
# the pipeline: stages 1-4 -> raw gradients
# ---------------------------------------------------------------------------


def split_step_grads(model: SplitModel, params, batch, scala: ScalaConfig, *,
                     backend: str = "logits",
                     boundary: str = "fused",
                     ce_chunk: Optional[int] = None,
                     axes: Optional[MeshAxes] = None,
                     mask=None,
                     precision: str = "f32"):
    """Stages 1-4 of the SCALA local iteration for any loss backend.

    params: {'client': stacked (C,...), 'server': ...}; batch leaves
    (C, B_k, ...). Returns (grads, metrics) with grads mirroring params —
    no parameter update applied. ``axes`` must be set iff
    ``backend == "lace_dp"`` (the caller wraps this in ``shard_map``).

    ``boundary`` (:data:`BOUNDARIES`) picks the loss-stage schedule:
    ``"fused"`` (default) evaluates eq. (14) and eq. (15) — values and
    cotangents — in one pass over a shared logits product; ``"dual"``
    keeps the literal two ``value_and_grad`` passes. Gradients are
    bit-identical f32 per backend; LACE loss metrics are 1-ulp
    (``"logits"`` falls back to dual when ``label_smoothing > 0``).

    ``precision`` (:data:`PRECISIONS`) selects the compute policy via
    :func:`cast_to_compute`: ``"bf16"`` runs stages 2-4 in bfloat16
    against the f32 master params; stage 1 (priors), the loss
    reductions, and stage 5 (updates) stay f32.

    ``mask`` is an optional (C,) 0/1 participation mask (the client count
    stays static; see :mod:`repro.fed.participation`). It folds into the
    per-token loss weights, so masked-out clients contribute zero to the
    stage-1 histograms — the concatenated prior P_s and the per-client
    priors P_k are recomputed over the participating *subset*, exactly
    the paper's partial-participation setting — zero to both losses, and
    zero gradient to their own client halves. Under ``lace_dp`` the mask
    is the *local* (C_l,) shard of the global mask.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected {BACKENDS}")
    if boundary not in BOUNDARIES:
        raise ValueError(
            f"unknown boundary {boundary!r}; expected {BOUNDARIES}")
    if (backend == "lace_dp") != (axes is not None):
        raise ValueError("backend 'lace_dp' requires mesh axes (and only it)")
    if backend != "logits" and model.server_trunk is None:
        raise ValueError(f"backend {backend!r} needs model.server_trunk/"
                         "head_weight (fused LACE path)")
    model = cast_to_compute(model, precision)

    N = model.num_classes
    labels = batch["labels"]
    weights = batch.get("weights")
    C = labels.shape[0]

    if mask is not None:
        mw = mask.astype(jnp.float32).reshape((C,) + (1,) * (labels.ndim - 1))
        base_w = (jnp.ones(labels.shape, jnp.float32) if weights is None
                  else jnp.broadcast_to(weights, labels.shape))
        weights = base_w * mw

    # --- stage 1: label statistics (clients upload Y_k with A_k) ---
    p_k, p_s = _priors(labels, weights, N, scala, axes)

    # --- stage 2: parallel client forward (client-parallel == vmap) ---
    acts = jax.vmap(lambda w, b: model.client_fwd(w, b))(params["client"],
                                                         batch)
    x = acts["x"]                                   # (C, B_k, ..., d)

    # --- stages 3-4: backend-specific dual losses over a shared vjp ---
    if backend == "logits":
        (logits, aux), vjp, has_mem = _server_vjp(model.server_fwd,
                                                  params["server"], acts)
        labels_f = _flat(labels)
        weights_f = _flat(weights) if weights is not None else None

        # both sides' priors, prepared once and shared between eq. (14)
        # and eq. (15) — the per-client prior broadcast over each
        # client's token dims
        ps_use = p_s if scala.adjust_server else None
        pk_tok = _prior_for_tokens(p_k, labels.shape)        # (C,1..,N)
        pk_flat = _flat(jnp.broadcast_to(
            pk_tok, labels.shape[:2] + (1,) * (labels.ndim - 2) + (N,)))
        pk_use = pk_flat if scala.adjust_client else None

        # the mirrored one-pass backward is bitwise only at ls == 0; the
        # smoothed objective keeps the autodiff schedule
        if boundary == "fused" and scala.label_smoothing == 0.0:
            loss_s, loss_k, g_s, g_k = losses.dual_adjusted_xent(
                logits, labels_f, weights=weights_f, prior_s=ps_use,
                prior_k=pk_use, tau=scala.tau,
                label_smoothing=scala.label_smoothing,
                prior_eps=scala.prior_eps)
        else:
            def server_loss(lg):
                return losses.softmax_xent(
                    lg, labels_f, weights=weights_f, prior=ps_use,
                    tau=scala.tau, label_smoothing=scala.label_smoothing,
                    prior_eps=scala.prior_eps)

            loss_s, g_s = jax.value_and_grad(server_loss)(logits)

            def client_loss(lg):
                return losses.softmax_xent(
                    lg, labels_f, weights=weights_f, prior=pk_use,
                    tau=scala.tau, label_smoothing=scala.label_smoothing,
                    prior_eps=scala.prior_eps)

            loss_k, g_k = jax.value_and_grad(client_loss)(logits)

        d_ws, g_x, g_mem = _dual_pullbacks(vjp, g_s, g_k, aux.dtype, has_mem)
        metrics = {"loss_server": loss_s, "loss_client": loss_k, "aux": aux,
                   "accuracy": losses.accuracy(logits, labels_f, weights_f)}
    else:
        from repro.kernels.lace.ops import (lace2_grads, lace2_grads_dp,
                                            lace_loss, lace_loss_dp,
                                            lace_nll_sum)

        if ce_chunk is None:
            ce_chunk = default_ce_chunk(N)
        (feats, aux), vjp, has_mem = _server_vjp(model.server_trunk,
                                                 params["server"], acts)
        d = feats.shape[-1]
        feats_g = feats.reshape(C, -1, d)           # (C, bk*s_out, d)
        labels_g = labels.reshape(C, -1)
        weights_g = None if weights is None else weights.reshape(C, -1)
        w_head = model.head_weight(params["server"])

        ps_rows = p_s[None] if scala.adjust_server else None
        pk_rows = p_k if scala.adjust_client else None
        pk_ids = jnp.arange(C) if scala.adjust_client else None

        if backend == "lace" and boundary == "fused":
            lace2 = lace2_grads_dp if model.dp_loss else lace2_grads
            loss_s, loss_k, gf_s, gf_k, gW_s = lace2(
                feats_g, w_head, labels_g, ps_rows, None, pk_rows, pk_ids,
                weights_g, scala.tau, scala.prior_eps, ce_chunk)[:5]
        elif backend == "lace":
            lace = lace_loss_dp if model.dp_loss else lace_loss

            # eq. (14): concatenated prior P_s for the server update
            def loss_s_fn(fg, wh):
                return lace(fg, wh, labels_g, ps_rows, None, weights_g,
                            scala.tau, scala.prior_eps, ce_chunk)

            loss_s, (gf_s, gW_s) = jax.value_and_grad(
                loss_s_fn, argnums=(0, 1))(feats_g, w_head)

            # eq. (15): per-client priors P_k for the gradients G_k
            def loss_k_fn(fg):
                return lace(fg, w_head, labels_g, pk_rows, pk_ids,
                            weights_g, scala.tau, scala.prior_eps, ce_chunk)

            loss_k, gf_k = jax.value_and_grad(loss_k_fn)(feats_g)
        else:                                        # "lace_dp"
            # differentiate LOCAL nll sums only (never through a psum: with
            # vma checking off, the psum transpose would re-reduce an
            # already-replicated cotangent and over-count by |axes|); the
            # global normalization is applied to values/grads afterwards.
            wsum_local = (jnp.sum(weights_g) if weights_g is not None
                          else jnp.float32(labels_g.size))
            w_global = jnp.maximum(jax.lax.psum(
                jnp.asarray(wsum_local, jnp.float32), axes.all), 1e-8)

            if boundary == "fused":
                nll_s, nll_k, gf_s, gf_k, gW_s, _ = lace2_grads(
                    feats_g, w_head, labels_g, ps_rows, None, pk_rows,
                    pk_ids, weights_g, scala.tau, scala.prior_eps,
                    ce_chunk, mean=False)
            else:
                def nll_s_fn(fg, wh):
                    return lace_nll_sum(fg, wh, labels_g, ps_rows, None,
                                        weights_g, scala.tau,
                                        scala.prior_eps, ce_chunk)

                nll_s, (gf_s, gW_s) = jax.value_and_grad(
                    nll_s_fn, argnums=(0, 1))(feats_g, w_head)

                def nll_k_fn(fg):
                    return lace_nll_sum(fg, w_head, labels_g, pk_rows,
                                        pk_ids, weights_g, scala.tau,
                                        scala.prior_eps, ce_chunk)

                nll_k, gf_k = jax.value_and_grad(nll_k_fn)(feats_g)

            loss_s = jax.lax.psum(nll_s, axes.all) / w_global
            gf_s = gf_s / w_global
            gW_s = gW_s / w_global
            loss_k = jax.lax.psum(nll_k, axes.all) / w_global
            gf_k = gf_k / w_global

        gf_s_t = gf_s.reshape(feats.shape).astype(feats.dtype)
        gf_k_t = gf_k.reshape(feats.shape).astype(feats.dtype)
        d_ws, g_x, g_mem = _dual_pullbacks(vjp, gf_s_t, gf_k_t, aux.dtype,
                                           has_mem)
        d_ws = model.head_grad_merge(d_ws, gW_s)
        metrics = {"loss_server": loss_s, "loss_client": loss_k, "aux": aux}

    # --- stage 4 reductions (manual-SPMD only) ---
    rdt = (jnp.dtype(scala.grad_reduce_dtype)
           if axes is not None and scala.grad_reduce_dtype else None)
    if axes is not None:
        # the ONE server-grad reduction: every leaf is a local partial
        # (the psum transpose passes the global cotangent through, so
        # grads wrt replicated weights are per-shard contributions);
        # optionally compressed to bf16 (halves the remaining wire traffic).
        if rdt is not None:
            d_ws = jax.tree.map(lambda g: g.astype(rdt), d_ws)
        d_ws = jax.lax.psum(d_ws, axes.all)

    d_wc = _client_pullback(model, params["client"], batch, acts, g_x, g_mem,
                            has_mem)
    if axes is not None and axes.inner:
        # each client's batch is itself sharded over the inner axis
        if rdt is not None:
            d_wc = jax.tree.map(lambda g: g.astype(rdt), d_wc)
        d_wc = jax.lax.psum(d_wc, axes.inner)
    if axes is not None:
        metrics["aux"] = jax.lax.pmean(metrics["aux"], axes.all)

    return {"client": d_wc, "server": d_ws}, metrics


# ---------------------------------------------------------------------------
# stage 5: updates — plain-SGD compat and real optimizers
# ---------------------------------------------------------------------------


def sgd_apply(params, grads, lr):
    """The paper's eq. (7)/(9) update, in param dtype (legacy-exact)."""
    return jax.tree.map(lambda w, g: w - lr * g.astype(w.dtype),
                        params, grads)


@dataclass(frozen=True)
class TrainState:
    """Engine state threaded through steps/rounds: params, per-half
    optimizer state (client state vmapped so every leaf carries the
    stacked (C, ...) axis), and the global step driving the lr schedule."""

    params: Any
    opt_state: Any
    step: Any


jax.tree_util.register_dataclass(
    TrainState, data_fields=("params", "opt_state", "step"), meta_fields=())


def init_train_state(params, optimizer: optimizers.Optimizer) -> TrainState:
    return TrainState(
        params=params,
        opt_state={"client": jax.vmap(optimizer.init)(params["client"]),
                   "server": optimizer.init(params["server"])},
        step=jnp.zeros((), jnp.int32))


def _apply_updates(opt: optimizers.Optimizer, state: TrainState, grads,
                   lr) -> TrainState:
    new_s, st_s = opt.update(grads["server"], state.opt_state["server"],
                             state.params["server"], lr)
    new_c, st_c = jax.vmap(lambda g, s, p: opt.update(g, s, p, lr))(
        grads["client"], state.opt_state["client"], state.params["client"])
    return TrainState(params={"client": new_c, "server": new_s},
                      opt_state={"client": st_c, "server": st_s},
                      step=state.step + 1)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def _dp_specs(mesh, axes: MeshAxes, tree):
    """Client-half leaves are sharded over the client axes, server-half
    (and scalars) replicated."""
    from jax.sharding import PartitionSpec as P

    return {"client": jax.tree.map(lambda _: P(axes.client or None),
                                   tree["client"]),
            "server": jax.tree.map(lambda _: P(), tree["server"])}


def pin_dp_layout(mesh, state: TrainState) -> TrainState:
    """Pin the ``lace_dp`` layout on a round's output state: the client
    half split over the client mesh axes, the server half replicated.
    The masked round's FL-phase average runs outside the shard_map, and
    without the pin GSPMD replicates the re-stacked client half on every
    device."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.sharding.logical import place

    axes = mesh_axes(mesh)
    specs = TrainState(params=_dp_specs(mesh, axes, state.params),
                       opt_state=_dp_specs(mesh, axes, state.opt_state),
                       step=P())
    return place(state, jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                     is_leaf=lambda s: isinstance(s, P)))


def client_shard_count(mesh) -> int:
    """How many ways the stacked client axis splits on this mesh — the
    product of the client mesh-axis sizes (:func:`mesh_axes`). 1 on a
    mesh with no client axes (pure tensor parallelism)."""
    axes = mesh_axes(mesh)
    sizes = dict(mesh.shape)
    n = 1
    for a in axes.client:
        n *= sizes[a]
    return n


def local_step(model: SplitModel, params, batch, scala: ScalaConfig, *,
               backend: str = "logits", boundary: str = "fused",
               lr: Optional[float] = None,
               ce_chunk: Optional[int] = None, mesh=None, batch_specs=None,
               precision: str = "f32"):
    """One stateless SCALA local iteration with plain SGD (eqs. 7/9) —
    the legacy-shaped entry point behind :mod:`repro.core.scala`.

    Returns (new_params, metrics). For ``backend="lace_dp"`` pass the mesh
    and a PartitionSpec pytree matching ``batch``; the whole step
    (gradients + update) then runs inside one ``shard_map``.
    """
    lr = scala.lr if lr is None else lr

    if backend == "lace_dp":
        from jax.sharding import PartitionSpec as P

        if mesh is None or batch_specs is None:
            raise ValueError("backend 'lace_dp' needs mesh and batch_specs")
        axes = mesh_axes(mesh)
        p_specs = _dp_specs(mesh, axes, params)
        m_specs = {"loss_server": P(), "loss_client": P(), "aux": P()}

        def body(p, b):
            grads, metrics = split_step_grads(model, p, b, scala,
                                              backend="lace_dp",
                                              boundary=boundary,
                                              ce_chunk=ce_chunk, axes=axes,
                                              precision=precision)
            return sgd_apply(p, grads, lr), metrics

        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(p_specs, batch_specs),
                           out_specs=(p_specs, m_specs), check_vma=False)
        return fn(params, batch)

    grads, metrics = split_step_grads(model, params, batch, scala,
                                      backend=backend, boundary=boundary,
                                      ce_chunk=ce_chunk,
                                      precision=precision)
    return sgd_apply(params, grads, lr), metrics


def make_split_step(model: SplitModel, scala: ScalaConfig, *,
                    backend: str = "lace",
                    boundary: str = "fused",
                    optimizer: Optional[optimizers.Optimizer] = None,
                    schedule: Optional[Callable] = None,
                    ce_chunk: Optional[int] = None,
                    mesh=None, batch_specs=None,
                    precision: str = "f32"):
    """Build the stateful engine step: (TrainState, batch[, mask]) ->
    (TrainState, metrics), jit/scan-compatible.

    ``optimizer`` defaults to plain SGD (the paper's eq. 7/9) and
    ``schedule`` to a constant ``scala.lr``; any combination from
    :mod:`repro.optim` works, with the lr driven by ``state.step`` (one
    increment per local iteration). ``precision`` is the compute policy
    of :func:`split_step_grads` (``"bf16"`` = bf16 forward/backward
    against f32 master params and f32 updates).

    The optional third ``mask`` argument is a (C,) 0/1 participation mask
    (see :func:`split_step_grads`); for ``lace_dp`` it is passed into the
    ``shard_map`` sharded over the client mesh axes.
    """
    opt = optimizer if optimizer is not None else optimizers.sgd()
    sched = schedule if schedule is not None else schedules.constant(scala.lr)

    if backend == "lace_dp":
        from jax.sharding import PartitionSpec as P

        if mesh is None or batch_specs is None:
            raise ValueError("backend 'lace_dp' needs mesh and batch_specs")
        axes = mesh_axes(mesh)

        def step(state: TrainState, batch, mask=None):
            p_specs = _dp_specs(mesh, axes, state.params)
            # vmapped client opt state carries the (C, ...) axis on every
            # leaf, so it shards exactly like the client params
            s_specs = TrainState(
                params=p_specs,
                opt_state=_dp_specs(mesh, axes, state.opt_state),
                step=P())
            m_specs = {"loss_server": P(), "loss_client": P(), "aux": P()}

            def body(st, b, *m):
                grads, metrics = split_step_grads(
                    model, st.params, b, scala, backend="lace_dp",
                    boundary=boundary, ce_chunk=ce_chunk, axes=axes,
                    mask=m[0] if m else None, precision=precision)
                return _apply_updates(opt, st, grads, sched(st.step)), metrics

            # the (C,) mask, when present, shards like the client axis
            args = (state, batch) if mask is None else (state, batch, mask)
            in_specs = ((s_specs, batch_specs) if mask is None
                        else (s_specs, batch_specs, P(axes.client or None)))
            fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                               out_specs=(s_specs, m_specs),
                               check_vma=False)
            return fn(*args)

        return step

    def step(state: TrainState, batch, mask=None):
        grads, metrics = split_step_grads(model, state.params, batch, scala,
                                          backend=backend, boundary=boundary,
                                          ce_chunk=ce_chunk,
                                          mask=mask, precision=precision)
        return _apply_updates(opt, state, grads, sched(state.step)), metrics

    return step


# ---------------------------------------------------------------------------
# FL phase + the scan-compiled round
# ---------------------------------------------------------------------------


def scala_aggregate(params, data_sizes=None):
    """FL phase (eq. 10): FedAvg the client halves, redistribute.

    ``data_sizes`` may contain zero-participation clients; normalization
    is mask-safe (see :func:`repro.core.split.normalize_client_weights`).
    """
    return {"client": redistribute(params["client"], data_sizes),
            "server": params["server"]}


OPT_STATE_POLICIES = ("carry", "reset", "average")


def slot_gather_indices(mask, k_active: int):
    """Participating slot ids, ascending, from a (C,) 0/1 mask with a
    *static* subset size ``k_active`` (the sparse-slot compute path).

    Cumsum compaction, O(C) work / O(log C) depth — not the historical
    O(C log C) sort-of-a-stable-argsort: each participating slot's
    target position is its rank among the ones (prefix sum), positions
    past ``k_active`` drop. If the mask has *fewer* than ``k_active``
    ones the remaining positions fill with the lowest absent slot ids —
    they run compute but carry zero aggregation weight, which is safe
    but wasteful (every :mod:`repro.fed.participation` scheduler
    guarantees a fixed subset size, so this is the degenerate case). A
    final O(k log k) sort over the ``k_active`` survivors restores the
    global ascending order, keeping the result bit-identical to the
    sort-based compaction on EVERY mask (test-enforced on random masks
    in ``tests/test_arrival.py``).
    """
    on = mask > 0
    n_on = jnp.sum(on, dtype=jnp.int32)
    rank = jnp.cumsum(on, dtype=jnp.int32) - 1          # position if on
    fill = n_on + jnp.cumsum(~on, dtype=jnp.int32) - 1  # position if off
    pos = jnp.where(on, rank, fill)
    pos = jnp.where(pos < k_active, pos, k_active)      # OOB -> dropped
    C = mask.shape[0]
    idx = jnp.zeros((k_active,), jnp.int32).at[pos].set(
        jnp.arange(C, dtype=jnp.int32), mode="drop")
    return jnp.sort(idx)


def gather_rows(tree, idx):
    """Pack rows ``idx`` of every (C, ...) leaf into a dense leading axis."""
    return jax.tree.map(lambda a: jnp.take(a, idx, axis=0), tree)


def scatter_rows(full_tree, sub_tree, idx):
    """Write dense-axis results back into rows ``idx`` of the full leaves."""
    return jax.tree.map(lambda f, s: f.at[idx].set(s.astype(f.dtype)),
                        full_tree, sub_tree)


def _gather_clients(state: TrainState, idx) -> TrainState:
    """Pack the participating client slots into a dense [K_active] axis
    (server half shared by reference)."""
    return TrainState(
        params={"client": gather_rows(state.params["client"], idx),
                "server": state.params["server"]},
        opt_state={"client": gather_rows(state.opt_state["client"], idx),
                   "server": state.opt_state["server"]},
        step=state.step)


def _scatter_clients(state: TrainState, sub: TrainState, idx) -> TrainState:
    """Write the dense [K_active] results back into the static slots.

    Absent slots keep their params AND their optimizer state untouched
    (the masked path instead "ticks" absent slots' stateful moments with
    zero grads — see :func:`make_round_runner`)."""
    return TrainState(
        params={"client": scatter_rows(state.params["client"],
                                       sub.params["client"], idx),
                "server": sub.params["server"]},
        opt_state={"client": scatter_rows(state.opt_state["client"],
                                          sub.opt_state["client"], idx),
                   "server": sub.opt_state["server"]},
        step=sub.step)


def _round_boundary_opt_state(opt: optimizers.Optimizer, opt_state,
                              new_params, weights, policy: str):
    """Client optimizer state at the round boundary (policy semantics in
    :func:`make_round_runner`); the server half always carries."""
    if policy == "carry":
        return opt_state
    if policy == "reset":
        return {"client": jax.vmap(opt.init)(new_params["client"]),
                "server": opt_state["server"]}
    # "average": aggregate the per-client state exactly like the client
    # params, then redistribute so every slot restarts from the averaged
    # moments (computed in f32, cast back to the leaf dtype).
    def avg(a):
        wb = weights.reshape((-1,) + (1,) * (a.ndim - 1)).astype(jnp.float32)
        m = (a.astype(jnp.float32) * wb).sum(axis=0).astype(a.dtype)
        return jnp.broadcast_to(m[None], a.shape)

    return {"client": jax.tree.map(avg, opt_state["client"]),
            "server": opt_state["server"]}


def make_round_runner(model: SplitModel, scala: ScalaConfig, *,
                      backend: str = "logits",
                      boundary: str = "fused",
                      optimizer: Optional[optimizers.Optimizer] = None,
                      schedule: Optional[Callable] = None,
                      ce_chunk: Optional[int] = None,
                      aggregate: bool = True,
                      unroll=1,
                      aggregator=None,
                      participation=None,
                      opt_state_policy: str = "carry",
                      slot_gather: bool = False,
                      server_optimizer: Optional[optimizers.Optimizer] = None,
                      server_lr: float = 1.0,
                      mesh=None, batch_specs=None,
                      precision: str = "f32",
                      faults=None, guards=None):
    """Build the fused round program: T local iterations (``lax.scan``
    over the engine step) + the pluggable FL phase, all in one jittable
    fn. All backends are supported, including ``lace_dp`` (pass ``mesh``
    and ``batch_specs``): the manual-SPMD shard_map step's specs are
    step-invariant, so the whole sharded round scans into one program.

    Federation layer (:mod:`repro.fed`):

    * ``aggregator`` — an :class:`repro.fed.aggregators.Aggregator`
      deciding the per-client FL-phase weights. Default:
      ``fed.weighted()``, data-size-proportional FedAvg — exactly the
      legacy ``scala_aggregate`` behavior.
    * ``participation`` — a
      :class:`repro.fed.participation.ParticipationScheduler` sampling
      the per-round client subset as a (C,) 0/1 mask over the *static*
      stacked client axis. The mask threads through
      :func:`split_step_grads`, so priors / logit adjustments are
      recomputed over the participating subset each round, and through
      the aggregator, which excludes absent clients. ``None`` (default)
      = full participation with no masking (legacy-exact HLO).

    Client optimizer state at the round boundary (``opt_state_policy``):

    * ``"carry"``   — per-slot state persists across rounds (legacy
      behavior). After the FL phase every slot holds the same params but
      its own moments: momentum/Adam statistics act per *slot*, not per
      logical client — cheap, and the right default when slots are
      anonymous.
    * ``"reset"``   — client state re-initialized to zeros each round:
      every client restarts cold from the aggregated model, matching the
      FL/SFL baseline semantics (:mod:`repro.core.baselines`).
    * ``"average"`` — client state is aggregated with the same weights
      as the params and redistributed: moments follow the averaged model
      (FedOpt-style server-side statistics).

    The server half's optimizer state always carries — the server model
    is never averaged (only the client halves federate, eq. 10).

    Sparse-slot compute (``slot_gather=True``): the participating slots
    are gathered into a dense ``[K_active]`` axis *before* the local
    scan and scattered back afterwards, so a ``frac``-participation
    round costs ~``frac`` of the full-K compute while every shape stays
    static (``K_active`` is the scheduler's fixed subset size,
    ``participation.subset_size``). Requires a participation scheduler
    and is a no-op when the subset is the full slot set. Semantics match
    the masked round exactly for the losses, the priors (the gathered
    subset IS the participating subset), the gradients, and the FL
    phase; the one divergence is stateful-optimizer moments of *absent*
    clients under ``opt_state_policy="carry"`` — the masked round ticks
    them with zero gradients (momentum keeps decaying), the gathered
    round freezes them. On the ``lace_dp`` backend the gather happens
    *in-shard*: the whole round runs inside one ``shard_map`` and each
    shard of the client mesh axes packs its own participating slots into
    a dense local ``[K_active / n_shards]`` axis, with the FL phase as a
    local (edge) weighted partial + one psum (server fold). Requires a
    shards-balanced scheduler (``uniform:FRAC:SHARDS`` with SHARDS a
    multiple of the client shard count) and a stateless prior-free
    aggregator exposing ``shard_local`` (fedavg / weighted /
    hierarchical).

    Server-side FedOpt (``server_optimizer=``): after the round, the
    *server* half's round delta ``w_s_start - w_s_end`` is treated as a
    pseudo-gradient and ``server_optimizer`` is applied to it from
    ``w_s_start`` at ``server_lr`` (round-scale state: momentum/Adam
    moments over rounds, not local iterations). Plain SGD at
    ``server_lr=1.0`` reproduces the default (the in-round updates land
    unchanged). The optimizer's state lives in ``fed_state["server_opt"]``
    — build it with :func:`repro.fed.init_fed_state`.

    Returns ``round_fn(state, round_batches, data_sizes=None,
    fed_state=None)``; round_batches leaves (T, C, Bk, ...). With
    ``fed_state=None`` (requires stateless aggregator + scheduler and no
    server optimizer) it returns ``(TrainState, metrics)`` — the legacy
    signature. With a ``fed_state`` dict from
    :func:`repro.fed.init_fed_state` it returns
    ``(TrainState, fed_state', metrics)``, threading scheduler PRNG keys,
    aggregator round ages, and server-optimizer state across rounds.

    ``unroll`` is forwarded to ``lax.scan``. The default (1) keeps the
    HLO small — right for the deep production archs. XLA:CPU executes
    while-loop bodies with reduced parallelism, so for CPU-scale models
    pass ``unroll=True`` (full unroll): still one dispatch per round,
    no loop serialization (see benchmarks/round_loop.py).

    ``precision`` (:data:`PRECISIONS`) is the engine step's compute
    policy: ``"bf16"`` runs forward/backward in bfloat16 against f32
    master params while the priors, both loss reductions, the stage-5
    updates, and the FL-phase aggregation all stay f32.

    Fault tolerance (:mod:`repro.fed.faults` / :mod:`repro.fed.guards`):

    * ``faults`` — a :class:`repro.fed.faults.FaultModel` injecting
      deterministic failures: dropped/stalled clients leave the
      participation mask *before* the local scan (priors recompute over
      the survivors via the mask-fold path) and corrupted clients have
      their trained client-half update poisoned in transit after the
      scan. Needs ``fed_state['faults']`` (the fault PRNG key).
    * ``guards`` — a :class:`repro.fed.guards.GuardPolicy` screening
      each client's update before aggregation. If any participant is
      rejected, the local phase is *re-run* under ``lax.cond`` with the
      survivor mask, so the eq. 14/15 priors and logit adjustments match
      a round the rejected clients never joined. With zero rejections
      the guarded round is bit-identical to the unguarded one. Norm
      clipping (``clip:TAU``) additionally needs ``fed_state['guard']``.
    """
    from repro import fed as _fed
    from repro.fed import faults as _faults
    from repro.fed import guards as _guards

    if opt_state_policy not in OPT_STATE_POLICIES:
        raise ValueError(f"unknown opt_state_policy {opt_state_policy!r}; "
                         f"expected {OPT_STATE_POLICIES}")
    if slot_gather:
        if participation is None:
            raise ValueError("slot_gather needs a participation scheduler "
                             "(the static K_active comes from its "
                             "subset_size)")
        if participation.subset_size is None:
            raise ValueError(
                f"slot_gather needs a scheduler with a static subset_size; "
                f"{participation.name!r} has none — without it the gather "
                "would silently degrade to full-K masked compute")
    if faults is not None:
        faults = _faults.make_faults(faults)
    if guards is not None:
        guards = _guards.make_guards(guards)
    robust = (faults is not None) or (guards is not None)
    if robust and not aggregate:
        raise ValueError("faults/guards act on the FL phase; they need "
                         "aggregate=True")
    opt = optimizer if optimizer is not None else optimizers.sgd()
    agg = aggregator if aggregator is not None else _fed.weighted()
    stateful = _fed.is_stateful(agg, participation)
    k_active = (participation.subset_size or participation.num_clients
                if participation is not None else None)
    do_gather = (slot_gather and participation is not None
                 and k_active < participation.num_clients)
    dp_gather = do_gather and backend == "lace_dp"
    if dp_gather and robust:
        raise ValueError(
            "faults/guards are not supported with the in-shard lace_dp "
            "slot_gather round (its FL phase runs inside shard_map); use "
            "the masked lace_dp round or a sparse single-host backend")
    if dp_gather:
        # in-shard gather: each shard of the client mesh axes packs ITS
        # OWN participating slots into a dense local [K_active/n] axis,
        # inside one whole-round shard_map. Needs a shards-balanced
        # scheduler so the local subset size is static and equal.
        if mesh is None or batch_specs is None:
            raise ValueError("backend 'lace_dp' needs mesh and batch_specs")
        n_shards = client_shard_count(mesh)
        if getattr(participation, "shards", 1) % n_shards:
            raise ValueError(
                f"lace_dp slot_gather needs a shards-balanced participation "
                f"scheduler: scheduler shards "
                f"{getattr(participation, 'shards', 1)} must be a multiple "
                f"of the {n_shards} client mesh shards (use "
                f"'uniform:FRAC:{n_shards}')")
        if k_active % n_shards or participation.num_clients % n_shards:
            raise ValueError(
                f"subset size {k_active} and client count "
                f"{participation.num_clients} must divide over the "
                f"{n_shards} client shards")
        if agg.shard_local is None or agg.stateful or agg.needs_priors:
            raise ValueError(
                f"aggregator {agg.name!r} cannot run inside the sharded "
                "client axis; lace_dp slot_gather needs a stateless, "
                "prior-free, shard-decomposable aggregator (fedavg / "
                "weighted / hierarchical)")
        if opt_state_policy == "average":
            raise ValueError("opt_state_policy 'average' is not supported "
                             "with lace_dp slot_gather; use 'carry' or "
                             "'reset'")
    step = make_split_step(model, scala, backend=backend, boundary=boundary,
                           optimizer=opt, schedule=schedule,
                           ce_chunk=ce_chunk,
                           mesh=mesh, batch_specs=batch_specs,
                           precision=precision)

    if dp_gather:
        from jax.sharding import PartitionSpec as P

        from repro.sharding.logical import round_specs as _round_specs

        axes = mesh_axes(mesh)
        k_l = k_active // n_shards
        sched = (schedule if schedule is not None
                 else schedules.constant(scala.lr))
        rb_specs = _round_specs(batch_specs)
        cspec = P(axes.client or None)
        m_specs = {"loss_server": P(), "loss_client": P(), "aux": P()}

        def dp_round(state: TrainState, round_batches, mask, sizes):
            s_specs = TrainState(
                params=_dp_specs(mesh, axes, state.params),
                opt_state=_dp_specs(mesh, axes, state.opt_state),
                step=P())

            def body(st, rb, mask_l, sizes_l):
                idx = slot_gather_indices(mask_l, k_l)
                sub = _gather_clients(st, idx)
                sub_b = jax.tree.map(lambda a: jnp.take(a, idx, axis=1), rb)

                def step_body(s, b):
                    grads, mets = split_step_grads(
                        model, s.params, b, scala, backend="lace_dp",
                        boundary=boundary, ce_chunk=ce_chunk, axes=axes,
                        precision=precision)
                    return _apply_updates(opt, s, grads,
                                          sched(s.step)), mets

                sub, ms = jax.lax.scan(step_body, sub, sub_b, unroll=unroll)
                st = _scatter_clients(st, sub, idx)
                metrics = jax.tree.map(lambda a: a[-1], ms)
                if aggregate:
                    # two-tier FL phase: local weighted partial per shard
                    # (the edge fold), one psum for the server fold
                    w_l = agg.shard_local(mask_l, sizes_l, axes.client,
                                          n_shards)
                    raw = w_l * mask_l
                    denom = raw.sum()
                    if axes.client:
                        denom = jax.lax.psum(denom, axes.client)
                    w_n = raw / jnp.maximum(denom, 1e-8)
                    part = weighted_mean(st.params["client"], w_n)
                    avg = (jax.tree.map(
                        lambda a: jax.lax.psum(a, axes.client), part)
                        if axes.client else part)
                    K_l = jax.tree.leaves(
                        st.params["client"])[0].shape[0]
                    params = {"client": stack_client_params(avg, K_l),
                              "server": st.params["server"]}
                    opt_state = st.opt_state
                    if opt_state_policy == "reset":
                        opt_state = {
                            "client": jax.vmap(opt.init)(params["client"]),
                            "server": st.opt_state["server"]}
                    st = TrainState(params=params, opt_state=opt_state,
                                    step=st.step)
                return st, metrics

            fn = jax.shard_map(
                body, mesh=mesh,
                in_specs=(s_specs, rb_specs, cspec, cspec),
                out_specs=(s_specs, m_specs), check_vma=False)
            return fn(state, round_batches, mask, sizes)

    def round_fn(state: TrainState, round_batches, data_sizes=None,
                 fed_state=None):
        if fed_state is None:
            if stateful:
                raise ValueError(
                    f"aggregator {agg.name!r} / participation scheduler are "
                    "stateful; pass fed_state (repro.fed.init_fed_state)")
            if server_optimizer is not None:
                raise ValueError(
                    "server_optimizer needs fed_state — build it with "
                    "repro.fed.init_fed_state(..., server_optimizer=, "
                    "server_params=)")
            if faults is not None:
                raise ValueError(
                    "faults need fed_state['faults'] (the fault PRNG key) — "
                    "build fed_state with repro.fed.init_fed_state(..., "
                    "faults=...)")
            if guards is not None and guards.clip > 0:
                raise ValueError(
                    "guard norm clipping is stateful (running median) — "
                    "build fed_state with repro.fed.init_fed_state(..., "
                    "guards=...)")
            sched_state, agg_state, so_state = (), (), ()
            fault_key, guard_state = None, ()
        else:
            sched_state, agg_state = fed_state["sched"], fed_state["agg"]
            so_state = fed_state.get("server_opt", ())
            if server_optimizer is not None and "server_opt" not in fed_state:
                raise ValueError(
                    "server_optimizer needs fed_state['server_opt'] — build "
                    "fed_state with repro.fed.init_fed_state(..., "
                    "server_optimizer=, server_params=)")
            fault_key = fed_state.get("faults")
            if faults is not None and fault_key is None:
                raise ValueError(
                    "faults need fed_state['faults'] — build fed_state with "
                    "repro.fed.init_fed_state(..., faults=...)")
            guard_state = fed_state.get("guard", ())
            if guards is not None and guards.clip > 0 and guard_state == ():
                raise ValueError(
                    "guard norm clipping needs fed_state['guard'] — build "
                    "fed_state with repro.fed.init_fed_state(..., "
                    "guards=...)")
        ws_start = state.params["server"]
        start = state  # round-start state: guard delta / clip reference

        if participation is not None:
            mask, sched_state = participation.sample(sched_state)
        else:
            mask = None

        C_all = jax.tree.leaves(state.params["client"])[0].shape[0]
        new_fault_key = fault_key
        corrupt_m = corrupt_key = None
        if faults is not None:
            new_fault_key, k_ev = jax.random.split(fault_key)
            k_masks, corrupt_key = jax.random.split(k_ev)
            fmasks = _faults.sample_fault_masks(faults, k_masks, C_all)
            # sync semantics: dropped AND stalled clients never deliver
            # an update this round — they leave the participating subset
            # before the scan, so the eq. 14/15 priors recompute over
            # the survivors via the mask-fold path
            alive = (1.0 - fmasks["drop"]) * (1.0 - fmasks["stall"])
            mask = alive if mask is None else mask * alive
            corrupt_m = fmasks["corrupt"] * mask

        def local_phase(mask_, fold_scan_mask):
            if do_gather:
                idx = slot_gather_indices(mask_, k_active)
                sub = _gather_clients(start, idx)
                sub_batches = jax.tree.map(
                    lambda a: jnp.take(a, idx, axis=1), round_batches)
                if fold_scan_mask:
                    # faulty rounds can have fewer than K_active real
                    # participants: fill slots must not pollute priors
                    sub_mask = jnp.take(mask_, idx)
                    body = lambda s, b: step(s, b, sub_mask)
                else:
                    # no mask inside the scan: every gathered slot
                    # participates, so the stage-1 priors are the
                    # participating-subset priors
                    body = step
                sub, ms = jax.lax.scan(body, sub, sub_batches,
                                       unroll=unroll)
                st = _scatter_clients(start, sub, idx)
            else:
                body = (lambda s, b: step(s, b, mask_)) \
                    if mask_ is not None else step
                st, ms = jax.lax.scan(body, start, round_batches,
                                      unroll=unroll)
            mets = jax.tree.map(lambda a: a[-1], ms)
            if corrupt_m is not None:
                # the update is corrupted in transit, AFTER training
                cp = _faults.corrupt_update(faults, corrupt_key,
                                            st.params["client"], corrupt_m)
                st = TrainState(params={"client": cp,
                                        "server": st.params["server"]},
                                opt_state=st.opt_state, step=st.step)
            return st, mets

        if dp_gather:
            sizes = (data_sizes if data_sizes is not None
                     else jnp.ones((participation.num_clients,),
                                   jnp.float32))
            state, metrics = dp_round(state, round_batches, mask, sizes)
        else:
            state, metrics = local_phase(
                mask, fold_scan_mask=faults is not None)

        agg_mask = mask
        accept = factor = norms = rejected = None
        new_guard_state = guard_state
        if guards is not None:
            base = (mask if mask is not None
                    else jnp.ones((C_all,), jnp.float32))
            delta = jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                state.params["client"], start.params["client"])
            accept, factor, norms, new_guard_state = _guards.screen(
                guards, delta, base, guard_state)
            survivor = base * accept
            rejected = base.sum() - survivor.sum()

            def recompute(_):
                # >=1 rejection: re-run the local phase over the
                # survivors so the priors / logit adjustments match a
                # round the rejected clients never joined
                return local_phase(survivor, fold_scan_mask=True)

            state, metrics = jax.lax.cond(
                rejected > 0, recompute, lambda _: (state, metrics), None)
            if guards.clip > 0:
                # re-derive the clip factors from the final (possibly
                # recomputed) updates; median state keeps pass-1 norms
                delta2 = jax.tree.map(
                    lambda a, b: (a.astype(jnp.float32)
                                  - b.astype(jnp.float32)),
                    state.params["client"], start.params["client"])
                _, factor, _, _ = _guards.screen(guards, delta2, survivor,
                                                 guard_state)
            # survivor == base bitwise when nothing was rejected
            agg_mask = survivor

        if aggregate and not dp_gather:
            C = jax.tree.leaves(state.params["client"])[0].shape[0]
            p_k = p_global = None
            if agg.needs_priors:
                p_k, p_global = _fed.aggregation_priors(
                    model.num_classes, round_batches["labels"],
                    round_batches.get("weights"), client_axis=1)
            ctx = _fed.AggContext(num_clients=C, mask=agg_mask,
                                  data_sizes=data_sizes, p_k=p_k,
                                  p_global=p_global)
            w, agg_state = agg.client_weights(ctx, agg_state)
            pc = state.params["client"]
            if guards is not None and guards.clip > 0:
                pc = _guards.apply_clip(start.params["client"], pc, factor)
            if accept is not None:
                # 0-weight x NaN = NaN: rejected rows must be zeroed
                # out of the average, not just down-weighted
                pc = jax.tree.map(
                    lambda p: jnp.where(
                        accept.reshape((-1,) + (1,) * (p.ndim - 1)) > 0,
                        p, jnp.zeros((), p.dtype)), pc)
            new_client_avg = weighted_mean(pc, w)
            params = {"client": stack_client_params(new_client_avg, C),
                      "server": state.params["server"]}
            opt_state = _round_boundary_opt_state(opt, state.opt_state,
                                                  params, w,
                                                  opt_state_policy)
            state = TrainState(params=params, opt_state=opt_state,
                               step=state.step)

        if guards is not None:
            metrics = dict(metrics)
            metrics["guard_accept"] = accept
            metrics["guard_norm"] = norms
            metrics["guard_rejected"] = rejected

        if server_optimizer is not None:
            # FedOpt on the server half: round delta as pseudo-gradient
            delta = jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                ws_start, state.params["server"])
            new_ws, so_state = server_optimizer.update(delta, so_state,
                                                       ws_start, server_lr)
            state = TrainState(params={"client": state.params["client"],
                                       "server": new_ws},
                               opt_state=state.opt_state, step=state.step)

        if backend == "lace_dp":
            state = pin_dp_layout(mesh, state)
        if fed_state is None:
            return state, metrics
        out_fed = {"sched": sched_state, "agg": agg_state}
        if "server_opt" in fed_state:
            out_fed["server_opt"] = so_state
        if "faults" in fed_state:
            out_fed["faults"] = (new_fault_key if faults is not None
                                 else fed_state["faults"])
        if "guard" in fed_state:
            out_fed["guard"] = (new_guard_state if guards is not None
                                else fed_state["guard"])
        return state, out_fed, metrics

    return round_fn


def scala_round_scan(model: SplitModel, state: TrainState, round_batches,
                     scala: ScalaConfig, data_sizes=None, *,
                     backend: str = "logits",
                     boundary: str = "fused",
                     optimizer: Optional[optimizers.Optimizer] = None,
                     schedule: Optional[Callable] = None,
                     ce_chunk: Optional[int] = None,
                     unroll=1, precision: str = "f32"):
    """One-shot convenience over :func:`make_round_runner`: T local
    iterations + aggregation as a single scanned program. For a training
    loop, build the runner once and jit it instead."""
    runner = make_round_runner(model, scala, backend=backend,
                               boundary=boundary,
                               optimizer=optimizer, schedule=schedule,
                               ce_chunk=ce_chunk, unroll=unroll,
                               precision=precision)
    return runner(state, round_batches, data_sizes)


def split_ce(model: SplitModel, wc, ws, batch):
    """Plain CE through the split — ONE client's forward into the server
    half, no concatenation and no logit adjustment. The local objective
    shared by the SFL baseline family (:mod:`repro.core.baselines`)."""
    acts = model.client_fwd(wc, batch)
    logits, aux = model.server_fwd(ws, acts)
    return losses.softmax_xent(logits, batch["labels"]) + aux


def init_scala_params(key, init_client, init_server, num_clients: int):
    """Build the stacked-client SCALA param layout from per-half inits."""
    kc, ks = jax.random.split(key)
    return {"client": stack_client_params(init_client(kc), num_clients),
            "server": init_server(ks)}
