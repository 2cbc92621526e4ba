"""jit'd wrapper: batched multi-head mLSTM over the chunk kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.mlstm.kernel import mlstm_chunk_pallas


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunkwise(q, k, v, i_raw, f_log, *, chunk: int = 64,
                    interpret: bool | None = None):
    """q,k,v: (B,S,H,hd); i_raw/f_log: (B,S,H). Returns h (B,S,H,hd)."""
    def per_head(q1, k1, v1, i1, f1):
        return mlstm_chunk_pallas(q1, k1, v1, i1, f1, chunk=chunk,
                                  interpret=interpret)

    # vmap over batch then heads (head axis moved in front of seq)
    fn = jax.vmap(jax.vmap(per_head))
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    it = i_raw.transpose(0, 2, 1)
    ft = f_log.transpose(0, 2, 1)
    h = fn(qt, kt, vt, it, ft)
    return h.transpose(0, 2, 1, 3)
