"""jit'd wrapper: (B,S,H,hd) GQA layout -> flash kernel layout and back."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attn.kernel import flash_attention_pallas


@functools.partial(jax.jit, static_argnames=("causal", "window", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    interpret: bool | None = None):
    """q: (B,S,H,hd); k/v: (B,S,KV,hd) — KV heads repeated as needed."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    if KV != H:
        reps = H // KV
        k = jnp.repeat(k, reps, axis=2)
        v = jnp.repeat(v, reps, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    out = flash_attention_pallas(qf, kf, vf, causal=causal, window=window,
                                 interpret=interpret)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
