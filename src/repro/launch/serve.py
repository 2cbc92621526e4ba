"""Serving driver: fused prefill + continuous batching on the merged
global model.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --reduced \
      --batch 8 --prompt-len 32 --gen 16 --slots 4 --pages 64

Thin driver over :class:`repro.api.ServeSpec` / :func:`repro.api
.build_serve`: restores a federated training checkpoint (or fresh-inits
from ``--seed``), warms the compile caches, serves the request batch
with continuous batching, and prints tokens/s plus per-request latency
percentiles. ``--reference`` runs the token-by-token decode baseline
(:func:`generate`) instead — the oracle the serving equivalence tests
compare against.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as T

# decode_step jits, keyed by the (hashable) ModelConfig — compiled once
# per config, shared across every generate() call
_DECODE_JIT = {}


def _decode_fn(cfg):
    fn = _DECODE_JIT.get(cfg)
    if fn is None:
        fn = _DECODE_JIT[cfg] = jax.jit(
            lambda p, b, c, i: T.decode_step(p, b, c, i, cfg))
    return fn


def generate(params, cfg, prompt_tokens, max_len: int, gen: int,
             extra_batch=None, temperature: float = 0.0, seed: int = 0,
             key=None):
    """Token-by-token reference path (prefill through the decode step).

    Greedy/temperature sampling; prompt_tokens: (B, P). ``key`` is the
    sampling stream (defaults to ``PRNGKey(seed)`` — pass an explicit
    key to keep it distinct from a param-init stream on the same seed).
    """
    B, P = prompt_tokens.shape
    cache = T.init_decode_cache(cfg, B, max_len)
    decode = _decode_fn(cfg)

    if key is None:
        key = jax.random.PRNGKey(seed)
    tok = prompt_tokens[:, :1]
    gen_toks = []
    for i in range(P + gen - 1):
        batch = {"tokens": tok}
        if extra_batch:
            batch.update(extra_batch)
        logits, cache = decode(params, batch, cache, jnp.int32(i))
        if i + 1 < P:
            tok = prompt_tokens[:, i + 1:i + 2]
        else:
            if temperature > 0:
                key, sub = jax.random.split(key)
                tok = jax.random.categorical(
                    sub, logits[:, 0] / temperature)[:, None]
            else:
                tok = jnp.argmax(logits[:, 0], axis=-1)[:, None]
            gen_toks.append(tok)
    return jnp.concatenate([prompt_tokens] + gen_toks, axis=1)


def _percentile(xs, q):
    return float(np.percentile(np.asarray(xs), q)) if len(xs) else 0.0


def main():
    from repro.api import ServeSpec, build_serve
    from repro.launch.compile_cache import use_compile_cache
    from repro.serve import Request

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=8,
                    help="number of requests")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=0,
                    help="cache length (0 = prompt-len + gen)")
    ap.add_argument("--pages", type=int, default=0,
                    help="page-pool size (0 = dense cache)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--static", action="store_true",
                    help="admission barrier (A/B against continuous)")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-step", type=int, default=None)
    ap.add_argument("--reference", action="store_true",
                    help="token-by-token baseline instead of the engine")
    args = ap.parse_args()
    use_compile_cache()

    max_len = args.max_len or (args.prompt_len + args.gen)
    key = jax.random.PRNGKey(args.seed)
    total_new = args.batch * args.gen

    if args.reference:
        from repro.configs import get_config

        cfg = get_config(args.arch)
        if args.reduced:
            cfg = cfg.reduced()
        params = T.init_params(key, cfg)
        prompts = jax.random.randint(
            jax.random.fold_in(key, 1), (args.batch, args.prompt_len), 0,
            cfg.vocab_size)
        sample_key = jax.random.fold_in(key, 2)   # != the param-init stream
        # warm up: same (B, 1) token and (B, max_len) cache shapes as the
        # timed run, so tok/s excludes compile
        generate(params, cfg, prompts[:, :2], max_len, 1,
                 temperature=args.temperature, key=sample_key)
        t0 = time.time()
        out = generate(params, cfg, prompts, max_len, args.gen,
                       temperature=args.temperature, key=sample_key)
        dt = time.time() - t0
        print(f"[reference] generated {out.shape} in {dt:.1f}s "
              f"({total_new / dt:.1f} tok/s batched)")
        print("sample row:", np.asarray(out[0, :32]).tolist())
        return

    spec = ServeSpec(
        arch=args.arch, reduced=args.reduced,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_step=args.checkpoint_step,
        slots=args.slots, max_len=max_len, pages=args.pages,
        page_size=args.page_size, temperature=args.temperature,
        seed=args.seed, admission="static" if args.static else "continuous")
    program = build_serve(spec)
    engine = program.engine

    prompts = np.asarray(jax.random.randint(
        jax.random.fold_in(key, 1), (args.batch, args.prompt_len), 0,
        program.cfg.vocab_size))
    engine.warmup([args.prompt_len])

    reqs = [Request(i, prompts[i], args.gen) for i in range(args.batch)]
    t0 = time.time()
    results = engine.serve(reqs)
    dt = time.time() - t0
    lats = [r.latency for r in results.values()]
    print(f"[{spec.admission}] {args.batch} reqs x {args.gen} tok on "
          f"{spec.slots} slots"
          + (f" ({spec.pages}x{spec.page_size}-token pages)"
             if spec.pages else " (dense cache)")
          + f": {dt:.1f}s ({total_new / dt:.1f} tok/s, "
          f"latency p50={_percentile(lats, 50):.2f}s "
          f"p99={_percentile(lats, 99):.2f}s, "
          f"cache {engine.state_bytes() / 1e6:.1f} MB)")
    print("sample row:", results[0].tokens[:32].tolist())


if __name__ == "__main__":
    main()
