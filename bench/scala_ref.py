"""Plain float32 reference of one SCALA round (SCALA paper, Alg. 1).

For the ``m`` participating clients, each of the round's ``T`` local
steps:

1. every client runs its half on its own minibatch (client forward);
2. the server concatenates the clients' activations and runs its half
   to the logits;
3. the server loss is eq. 14, the weighted mean cross-entropy of the
   logits adjusted by ``tau * log(P_s + eps)``, with ``P_s`` the label
   histogram of the concatenated batch; the client loss is eq. 15, the
   same mean with each token's logits adjusted by its own client's
   prior ``P_k``;
4. the server gradient is that of eq. 14; each client's gradient is
   eq. 15's, pulled back through the server half and then through the
   client's own half;
5. SGD on both halves.

After the ``T`` steps the client halves are averaged with weights
proportional to the clients' data sizes (eq. 10).

``model`` is a module with ``client_forward(wc, x, config, q)`` and
``server_logits(ws, acts, config, q)``; ``q`` is applied to every
matmul operand (the identity for the reference, a lower-precision
round trip for the control). Nothing here imports the system under
test.
"""
from __future__ import annotations

from functools import cache, partial

import jax
import jax.numpy as jnp


def identity(a):
    return a


@cache
def quantizer(dtype):
    """Round matmul operands to ``dtype`` in the forward pass, with a
    per-tensor scale where the format's range is narrow (fp8); gradients
    pass straight through (the backward matmuls see the rounded forward
    operands, the cotangents unrounded)."""
    dtype = jnp.dtype(dtype)
    top = float(jnp.finfo(dtype).max)

    @jax.custom_vjp
    def q(a):
        if top > 1e30:                       # float32's range: no scale
            return a.astype(dtype).astype(jnp.float32)
        s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / top
        return ((a / s).astype(dtype).astype(jnp.float32)) * s

    q.defvjp(lambda a: (q(a), None), lambda _, g: (g,))
    return q


def _local_step(model, config, q, tau, eps, lr, wcs, ws, x, labels, w):
    m = labels.shape[0]
    V = config["sizes"]["vocab_size"]
    acts, client_vjp = jax.vjp(
        lambda wcs: jax.vmap(lambda p, xi: model.client_forward(
            p, xi, config, q))(wcs, x), wcs)
    flat = acts.reshape((-1,) + acts.shape[2:])
    lab = labels.reshape(m, -1)
    wt = w.reshape(m, -1).astype(jnp.float32)
    hist = jax.vmap(lambda l, ww: jnp.zeros((V,), jnp.float32).at[l].add(ww))(
        lab, wt)
    p_k = hist / jnp.maximum(hist.sum(-1, keepdims=True), 1e-30)
    p_s = hist.sum(0) / jnp.maximum(hist.sum(), 1e-30)
    wsum = jnp.maximum(wt.sum(), 1e-8)

    def losses(ws, flat):
        z = model.server_logits(ws, flat, config, q).reshape(m, -1, V)

        def mean_nll(adj):
            za = z + tau * jnp.log(adj + eps)
            nll = (jax.nn.logsumexp(za, -1)
                   - jnp.take_along_axis(za, lab[..., None], -1)[..., 0])
            return (nll * wt).sum() / wsum

        return mean_nll(p_s[None, None]), mean_nll(p_k[:, None])

    (loss_s, loss_k), vjp = jax.vjp(losses, ws, flat)
    g_ws = vjp((jnp.float32(1), jnp.float32(0)))[0]
    g_flat = vjp((jnp.float32(0), jnp.float32(1)))[1]
    g_wcs = client_vjp(g_flat.reshape(acts.shape))[0]
    sgd = lambda p, g: p - lr * g
    return (jax.tree.map(sgd, wcs, g_wcs), jax.tree.map(sgd, ws, g_ws),
            loss_s, loss_k)


@partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5), donate_argnums=(6,))
def _round(model, config, q, tau, eps, lr, canon, rb):
    m = rb["sizes"].shape[0]
    wcs = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (m,) + a.shape),
                       canon["client"])
    ws = canon["server"]
    losses = []
    for t in range(rb["labels"].shape[0]):
        wcs, ws, ls, lk = _local_step(model, config, q, tau, eps, lr, wcs, ws,
                                      rb[model.INPUT][t], rb["labels"][t],
                                      rb["weights"][t])
        losses.append((ls, lk))
    a = rb["sizes"].astype(jnp.float32)
    a = a / a.sum()
    wc = jax.tree.map(lambda p: jnp.tensordot(
        a, p, axes=1, precision=jax.lax.Precision.HIGHEST), wcs)
    return {"client": wc, "server": ws}, losses[-1]


def reference_round(model, config, canon, rb, *, tau, eps, lr,
                    q=identity):
    """One round from the global ``canon`` weights on the participants'
    batches ``rb`` (leaves (T, m, rows, ...), ``sizes`` (m,)). Returns
    (new global canon weights, (eq. 14 loss, eq. 15 loss) of the last
    local step)."""
    return _round(model, Frozen(config), q, float(tau), float(eps),
                  float(lr), canon, rb)


class Frozen(dict):
    """A configuration dict that ``jax.jit`` can take as a static arg."""

    def __hash__(self):
        return hash(_freeze(self))

    def __eq__(self, other):
        return _freeze(self) == _freeze(other)


def _freeze(v):
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    return v
