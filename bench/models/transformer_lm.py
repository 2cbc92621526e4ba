"""Plain float32 reference of one SCALA round on a dense decoder LM split
after ``split_layer`` blocks (embedding + first blocks on each client,
the rest of the trunk, the final norm and an untied head on the server).

Written from the published description (Qwen1.5: pre-norm RMSNorm
blocks, rotary attention with q/k/v biases in the half-split "NeoX"
layout, SwiGLU feed-forward) and the SCALA paper (eq. 14 server loss
under the concatenated label prior, eq. 15 client losses under each
client's prior, SGD, eq. 10 aggregation). It imports nothing of the
system under test. Departures from the published model, both the
program's documented design: the head is untied from the embedding
(the split puts them on different sides), and there is no dropout.

Canonical layout (what ``init_weights`` makes)::

    {"client": {"embed": (V, d), "layers": {name: (split, ...)}},
     "server": {"layers": {name: (L - split, ...)},
                "final_norm": (d,), "head": (d, V)}}

Leaves under ``layers`` are stacked over their layer axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
INPUT = "tokens"


def dims(config):
    s = config["sizes"]
    return (s["num_layers"], s["d_model"], s["num_heads"], s["head_dim"],
            s["d_ff"], s["vocab_size"], s["split_layer"])


def _kv(config):
    return config["sizes"]["num_kv_heads"]


# ---------------------------------------------------------------------------
# weights (from the seed, on the device, in one jitted call)
# ---------------------------------------------------------------------------


def _layer_weights(key, n, d, h, kv, hd, ff):
    ks = jax.random.split(key, 10)

    def dense(k, shape, fan_in):
        return jax.random.normal(k, (n,) + shape, jnp.float32) * fan_in ** -0.5

    def small(k, shape, scale):
        return jax.random.normal(k, (n,) + shape, jnp.float32) * scale

    return {
        "norm1": 1.0 + small(ks[0], (d,), 0.05),
        "wq": dense(ks[1], (d, h, hd), d), "bq": small(ks[2], (h, hd), 0.02),
        "wk": dense(ks[3], (d, kv, hd), d), "bk": small(ks[4], (kv, hd), 0.02),
        "wv": dense(ks[5], (d, kv, hd), d), "bv": small(ks[6], (kv, hd), 0.02),
        "wo": dense(ks[7], (h, hd, d), h * hd),
        "norm2": 1.0 + small(ks[8], (d,), 0.05),
        **_ffn_weights(ks[9], n, d, ff),
    }


def _ffn_weights(key, n, d, ff):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"gate": jax.random.normal(k1, (n, d, ff)) * d ** -0.5,
            "up": jax.random.normal(k2, (n, d, ff)) * d ** -0.5,
            "down": jax.random.normal(k3, (n, ff, d)) * ff ** -0.5}


def init_weights(config, key):
    L, d, h, hd, ff, V, split = dims(config)
    kv = _kv(config)
    kc, ks, ke, kh = jax.random.split(key, 4)
    return {
        "client": {"embed": jax.random.normal(ke, (V, d)) * 0.02,
                   "layers": _layer_weights(kc, split, d, h, kv, hd, ff)},
        "server": {"layers": _layer_weights(ks, L - split, d, h, kv, hd, ff),
                   "final_norm": jnp.ones((d,), jnp.float32),
                   "head": jax.random.normal(kh, (d, V)) * d ** -0.5},
    }


# ---------------------------------------------------------------------------
# the program's parameter layout (models/transformer.py) <-> canonical
# ---------------------------------------------------------------------------


def _block(p):
    return {"norm1": {"scale": p["norm1"]},
            "mixer": {k: p[k] for k in ("wq", "wk", "wv", "wo",
                                        "bq", "bk", "bv")},
            "norm2": {"scale": p["norm2"]},
            "ffn": {k: p[k] for k in ("up", "down", "gate")}}


def _unblock(b):
    return {"norm1": b["norm1"]["scale"], "norm2": b["norm2"]["scale"],
            **b["mixer"], **b["ffn"]}


def to_program(config, canon, slots: int):
    c = canon["client"]
    split = dims(config)[-1]
    client = {"embed": {"tok": c["embed"]},
              "blocks": {f"blk{i}": _block(jax.tree.map(lambda a: a[i],
                                                        c["layers"]))
                         for i in range(split)}}
    client = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (slots,) + a.shape), client)
    s = canon["server"]
    server = {"prologue": {}, "groups": {"blk0": _block(s["layers"])},
              "final_norm": {"scale": s["final_norm"]},
              "head": {"out": s["head"]}}
    return {"client": client, "server": server}


def from_program(config, params):
    """Canonical weights of the global model (client slot 0)."""
    split = dims(config)[-1]
    c = jax.tree.map(lambda a: a[0], params["client"])
    layers = [_unblock(c["blocks"][f"blk{i}"]) for i in range(split)]
    s = params["server"]
    return {"client": {"embed": c["embed"]["tok"],
                       "layers": jax.tree.map(lambda *a: jnp.stack(a),
                                              *layers)},
            "server": {"layers": _unblock(s["groups"]["blk0"]),
                       "final_norm": s["final_norm"]["scale"],
                       "head": s["head"]["out"]}}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _mm(spec, a, b, q):
    return jnp.einsum(spec, q(a), q(b), precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (B, S, H, hd): rotate the halves (x1, x2) by position angles."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(p, x, config, q):
    sz = config["sizes"]
    eps, theta = sz["norm_eps"], sz["rope_theta"]
    h = _rms(x, p["norm1"], eps)
    qh = _rope(_mm("bsd,dhk->bshk", h, p["wq"], q) + p["bq"], theta)
    kh = _rope(_mm("bsd,dhk->bshk", h, p["wk"], q) + p["bk"], theta)
    vh = _mm("bsd,dhk->bshk", h, p["wv"], q) + p["bv"]
    rep = qh.shape[2] // kh.shape[2]        # grouped queries share k/v heads
    kh, vh = jnp.repeat(kh, rep, axis=2), jnp.repeat(vh, rep, axis=2)
    S = x.shape[1]
    scores = _mm("bqhk,bshk->bhqs", qh, kh, q) * qh.shape[-1] ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = _mm("bhqs,bshk->bqhk", probs, vh, q)
    x = x + _mm("bqhk,hkd->bqd", att, p["wo"], q)
    h = _rms(x, p["norm2"], eps)
    ff = jax.nn.silu(_mm("bsd,df->bsf", h, p["gate"], q)) * _mm(
        "bsd,df->bsf", h, p["up"], q)
    return x + _mm("bsf,fd->bsd", ff, p["down"], q)


def _stack(layers, x, config, q):
    def body(x, p):
        return _layer(p, x, config, q), None

    return jax.lax.scan(body, x, layers)[0]


def client_forward(wc, tokens, config, q):
    return _stack(wc["layers"], wc["embed"][tokens], config, q)


def server_logits(ws, x, config, q):
    x = _stack(ws["layers"], x, config, q)
    x = _rms(x, ws["final_norm"], config["sizes"]["norm_eps"])
    return _mm("bsd,dv->bsv", x, ws["head"], q)


# ---------------------------------------------------------------------------
# matmul FLOPs the round requires
# ---------------------------------------------------------------------------


def round_flops(config, expect) -> float:
    """Matmul FLOPs one round requires: participating tokens only.

    Per token and local step, with F the forward FLOPs of a layer
    (2 x multiply-adds: q/o projections 4 d h hd, k/v 4 d kv hd, SwiGLU
    6 d ff, causal attention 2 h hd (S + 1) for the average (S + 1) / 2
    keys):

    * client layers: forward, weight gradients and input gradients
      (the first layer's input gradient is the embedding's): 3 F each;
    * server layers: forward and weight gradients under the eq. 14
      cotangent, and input gradients under both the eq. 14 and the
      eq. 15 cotangents: 4 F. The first server layer's input gradient
      under eq. 14 (through its q/k/v projections) is not needed. The
      attention core has no weights: forward plus 2 x forward per
      cotangent;
    * boundary: logits, then under eq. 14 the head and feature
      gradients and under eq. 15 the feature gradient: 4 x 2 d V.

    Norms, softmax, rotary and the SGD/aggregation passes are not
    matmuls and are not counted.
    """
    L, d, h, hd, ff, V, split = dims(config)
    kv = _kv(config)
    S = expect["seq_len"]
    proj = 2 * ((2 * h + 2 * kv) * d * hd + 3 * d * ff)
    att = 2 * h * hd * (S + 1)
    client = split * 3 * (proj + att)
    server = (L - split) * (4 * proj + 5 * att) - 2 * (h + 2 * kv) * d * hd
    boundary = 4 * 2 * d * V
    tokens = expect["rows_per_step"] * expect["local_iters"]
    return float(tokens * (client + server + boundary))
