#!/usr/bin/env python3
"""Chip smoke run: SCALA training and serving of full-width qwen1.5-0.5b.

    python chip_smoke.py              # one TPU chip: train, then serve
    python chip_smoke.py --chips 4    # four chips: lace_dp against lace

The default run drives the repo's main path once through the entry points
a user calls, at the published width of qwen1.5-0.5b (24 layers, d_model
1024, 16x64 heads, d_ff 2816, vocab 151,936) with random weights from a
seed:

* **train** -- the spec ``launch/train.py`` builds from its CLI
  (``spec_from_args``), run by ``api.Trainer``: masked participation
  ``uniform:0.5``, backend ``lace``, boundary ``fused``, SGD, K=4
  clients, 3 rounds;
* **serve** -- the trained params saved with ``repro.checkpoint.save``
  (as ``train.py --checkpoint-dir`` does), restored through
  ``api.build_serve`` and served by the continuous-batching engine.

``--chips 4`` runs only the ``lace_dp`` backend (one client per chip on
``launch.mesh.make_host_mesh()``) and its comparison with backend
``lace`` on one chip of the same process.

Every check that fails ends the run with a non-zero exit and no result
line. A run that finds no TPU stops before any work; nothing falls back
to the CPU, to Pallas interpret mode or to a reference. The last line of
a passing run is one JSON object naming the device JAX reports.
Seconds and bytes printed on earlier lines describe this smoke run only;
they are not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from importlib import metadata
from pathlib import Path

ARCH = "qwen1.5-0.5b"
FULL_WIDTH = dict(num_layers=24, d_model=1024, num_heads=16, head_dim=64,
                  d_ff=2816, vocab_size=151936)
TRAIN_ARGV = ["--arch", ARCH, "--participation", "uniform:0.5",
              "--clients", "4", "--seq", "256", "--server-batch", "8",
              "--local-iters", "2", "--rounds", "3", "--optimizer", "sgd",
              "--boundary", "fused"]
PROMPTS, PROMPT_LEN, NEW_TOKENS, SLOTS = 8, 64, 16, 4

# Round 0's losses against their plain references at the initial params
# (the engine reports the last local iteration, one SGD step later).
ROUND0_RTOL = 0.10
# Engine prefill vs ServeProgram.predict last-position logits, relative
# L2 error: both run bf16 activations through 24 layers at different
# batch shapes, so per-op rounding (2^-9) accumulates to ~1e-2.
PREFILL_RTOL = 2e-2
# --chips 4: lace_dp vs lace, both under matmul precision "highest".
# Losses: the dp program sums per-shard NLLs and psums them, a different
# reduction order over bf16 activations (2^-8 = 3.9e-3 per rounding).
DP_LOSS_RTOL = 1e-2
# Client leaf update (params after 2 rounds minus init): the dp server
# gradient is psummed in bf16 (ScalaConfig.grad_reduce_dtype), so the
# server half and, through it, the next step's client gradients differ
# by a few bf16 roundings of the update.
DP_UPDATE_RTOL = 5e-2


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def rel_l2(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def on_tpu(tree) -> bool:
    import jax

    return all(d.platform == "tpu"
               for x in jax.tree.leaves(tree) for d in x.devices())


def train_spec(argv):
    from repro.launch import train as train_cli

    return train_cli.spec_from_args(train_cli.build_parser().parse_args(argv))


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def round0_references(trainer):
    """Plain losses of the initial global model on round 0's last local
    batch of the participating clients: (plain CE, eq. 14 adjusted CE).

    The batches are the ones ``Trainer.step`` draws first (the LM driver's
    host RNG is ``default_rng(seed)``); the mask is the one the compiled
    round samples from the initial scheduler state."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.core import losses
    from repro.core.label_stats import client_and_concat_priors
    from repro.data.loader import lm_round_batches

    spec, sc = trainer.spec, trainer.spec.scala
    cfg = spec.model_config()
    data = api.build_lm_data(cfg, sc.num_clients, spec.data.docs_per_client,
                             spec.data.seq, spec.seed)
    rb = lm_round_batches(data, np.arange(sc.num_clients), sc.server_batch,
                          sc.local_iters, np.random.default_rng(spec.seed))
    mask, _ = spec.fed.make_participation(spec.slots).sample(
        trainer.state.fed["sched"])
    part = np.flatnonzero(np.asarray(mask))
    check(len(part) > 0, "round 0 samples no participating client")
    tokens, labels, weights = (jnp.asarray(rb[k][-1][part])
                               for k in ("tokens", "labels", "weights"))

    @jax.jit
    def refs(state):
        C, bk, S = tokens.shape
        logits = trainer.program.predict(
            state, {"tokens": tokens.reshape(C * bk, S)})
        logits = logits.reshape(C, bk, S, -1)
        _, p_s = client_and_concat_priors(labels, cfg.vocab_size, weights,
                                          eps=sc.prior_eps)
        plain = losses.softmax_xent(logits, labels, weights=weights)
        adjusted = losses.softmax_xent(logits, labels, weights=weights,
                                       prior=p_s, tau=sc.tau,
                                       prior_eps=sc.prior_eps)
        return plain, adjusted

    plain, adjusted = refs(trainer.state)
    return float(plain), float(adjusted), part


def train_phase(argv, dev):
    """Build and run the SCALA spec of ``launch/train.py``; returns the
    trainer after its last round."""
    import jax
    import numpy as np

    from repro import api

    spec = train_spec(argv)
    ex = spec.execution
    check((ex.mode, ex.backend, ex.boundary) == ("masked", "lace", "fused"),
          f"train spec is {ex.mode}/{ex.backend}/{ex.boundary}")
    cfg = spec.model_config()
    log(f"train: {cfg.name} layers={cfg.num_layers} d={cfg.d_model} "
        f"heads={cfg.num_heads}x{cfg.head_dim} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size}; K={spec.scala.num_clients} "
        f"participation={spec.fed.participation} backend={ex.backend} "
        f"boundary={ex.boundary} optimizer={spec.optim.name} "
        f"seq={spec.data.seq} server_batch={spec.scala.server_batch} "
        f"local_iters={spec.scala.local_iters} rounds={spec.rounds}")

    t0 = time.perf_counter()
    trainer = api.Trainer(spec)
    jax.block_until_ready(trainer.state)
    log(f"train: build + init {time.perf_counter() - t0:.3f} s "
        "(smoke-run information, not a benchmark number)")

    plain0, adjusted0, part = round0_references(trainer)
    ln_v = math.log(cfg.vocab_size)
    log(f"train: round 0 participants {part.tolist()}; initial model's "
        f"plain CE {plain0:.4f} (ln V = {ln_v:.4f}), eq. 14 adjusted CE "
        f"{adjusted0:.4f}")
    check(abs(plain0 - ln_v) <= ROUND0_RTOL * ln_v,
          f"initial plain CE {plain0} not within {ROUND0_RTOL:.0%} of "
          f"ln V = {ln_v}")

    params = trainer.state.inner.params
    client0 = np.asarray(jax.tree.leaves(params["client"])[0][0])
    server0 = np.asarray(jax.tree.leaves(params["server"])[0])

    seconds = []
    for r in range(spec.rounds):
        t = time.perf_counter()
        m = trainer.step()
        jax.block_until_ready(trainer.state)
        seconds.append(time.perf_counter() - t)
        log(f"train: round {r} loss_s={m['loss_server']:.6f} "
            f"loss_c={m['loss_client']:.6f} ({seconds[-1]:.3f} s"
            f"{', compile included' if r == 0 else ''})")
        check(math.isfinite(m["loss_server"])
              and math.isfinite(m["loss_client"]),
              f"round {r} loss is not finite: {m}")
    loss_s0 = trainer.history[0]["loss_server"]
    check(abs(loss_s0 - adjusted0) <= ROUND0_RTOL * adjusted0,
          f"round 0 loss_s {loss_s0} not within {ROUND0_RTOL:.0%} of the "
          f"plain eq. 14 reference {adjusted0}")

    params = trainer.state.inner.params
    d_client = float(np.max(np.abs(
        np.asarray(jax.tree.leaves(params["client"])[0][0]) - client0)))
    d_server = float(np.max(np.abs(
        np.asarray(jax.tree.leaves(params["server"])[0]) - server0)))
    log(f"train: max |update| client leaf {d_client:.3e}, server leaf "
        f"{d_server:.3e}")
    check(d_client > 0 and d_server > 0, "a param half did not change")
    check(on_tpu(trainer.state), "the train state is not on the TPU")

    steady = seconds[1:]
    stats = dev.memory_stats() or {}
    log("train: smoke-run information, not benchmark numbers: round 0 "
        f"{seconds[0]:.3f} s (compile included), steady rounds "
        f"{[round(s, 3) for s in steady]} s, peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}")
    return trainer


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def serve_phase(ckpt_dir: str, reduced: bool, dev):
    """Restore the checkpoint through ``api.build_serve`` and serve greedy
    requests with the continuous-batching engine."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.launch.serve import generate
    from repro.serve import Request

    t0 = time.perf_counter()
    program = api.build_serve(api.ServeSpec(
        arch=ARCH, reduced=reduced, checkpoint_dir=ckpt_dir, slots=SLOTS))
    check(on_tpu(program.params), "the served params are not on the TPU")
    cfg, engine = program.cfg, program.engine
    V = cfg.vocab_size
    log(f"serve: restored {cfg.name} from the checkpoint in "
        f"{time.perf_counter() - t0:.3f} s; {PROMPTS} greedy requests, "
        f"prompt {PROMPT_LEN}, {NEW_TOKENS} new tokens, {SLOTS} slots")

    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(program.spec.seed), (PROMPTS, PROMPT_LEN), 0, V),
        np.int32)
    engine.record_logits = True
    t = time.perf_counter()
    results = engine.serve(
        [Request(i, prompts[i], NEW_TOKENS) for i in range(PROMPTS)],
        wall_clock=False)
    log(f"serve: engine finished in {time.perf_counter() - t:.3f} s "
        "(compile included; smoke-run information)")

    check(sorted(results) == list(range(PROMPTS)),
          f"completed requests {sorted(results)}")
    new = np.stack([results[i].tokens[PROMPT_LEN:] for i in range(PROMPTS)])
    for i in range(PROMPTS):
        r = results[i]
        check(r.evicted is None and len(r.tokens) == PROMPT_LEN + NEW_TOKENS,
              f"request {i}: {len(r.tokens)} tokens, evicted={r.evicted}")
    check(bool(((new >= 0) & (new < V)).all()), "a token is out of vocab")

    prefill = np.stack([results[i].logits[0] for i in range(PROMPTS)])
    predicted = np.asarray(
        program.predict({"tokens": jnp.asarray(prompts)})[:, -1],
        np.float32)
    err = rel_l2(prefill, predicted)
    log(f"serve: engine prefill vs ServeProgram.predict last-position "
        f"logits: relative L2 error {err:.3e} (limit {PREFILL_RTOL})")
    check(np.isfinite(prefill).all(), "prefill logits are not finite")
    check(err <= PREFILL_RTOL, f"prefill logits differ from predict by "
          f"{err} > {PREFILL_RTOL}")

    ref = np.asarray(generate(program.params, cfg, jnp.asarray(prompts),
                              program.spec.max_len, NEW_TOKENS))
    agree = float(np.mean(ref[:, PROMPT_LEN:] == new))
    stats = dev.memory_stats() or {}
    log(f"serve: greedy tokens agreeing with launch/serve.generate: "
        f"{agree:.4f} (information); peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}")


# ---------------------------------------------------------------------------
# --chips 4: lace_dp against lace
# ---------------------------------------------------------------------------


def dp_phase(argv):
    """Run the train spec with backend ``lace_dp`` (one client per chip)
    and the same spec, init and batches with ``lace`` on one chip."""
    import jax
    import numpy as np

    from repro import api
    from repro.configs.base import InputShape
    from repro.launch import input_specs as ispec
    from repro.launch.mesh import make_host_mesh
    from repro.sharding.logical import rules_for, tree_specs

    spec = train_spec(argv)
    cfg, sc = spec.model_config(), spec.scala
    spec_dp = dataclasses.replace(spec, execution=dataclasses.replace(
        spec.execution, backend="lace_dp"))
    mesh = make_host_mesh()
    K = sc.num_clients
    check(mesh.shape["data"] == K, f"mesh {dict(mesh.shape)} has not one "
          f"client per chip for K={K}")
    shape = InputShape(name="chip_smoke", seq_len=spec.data.seq,
                       global_batch=sc.server_batch, mode="train")
    b_sh, b_ax = ispec.train_batch_specs(cfg, shape, K)
    b_specs = tree_specs(b_ax, b_sh, mesh, rules_for(cfg.sharding_profile))
    log(f"dp: {cfg.name} K={K} on mesh {dict(mesh.shape)}, "
        f"{spec.rounds} rounds, matmul precision 'highest' on both sides")

    def run(s, **kw):
        tr = api.Trainer(s, **kw)
        leaf0 = np.asarray(jax.tree.leaves(tr.state.inner.params["client"])[0])
        t = time.perf_counter()
        hist = tr.run()
        jax.block_until_ready(tr.state)
        dt = time.perf_counter() - t
        leaf = jax.tree.leaves(tr.state.inner.params["client"])[0]
        return tr, hist, leaf, leaf0, dt

    with jax.default_matmul_precision("highest"):
        tr, hist_dp, leaf, leaf0, dt = run(spec_dp, mesh=mesh,
                                           batch_specs=b_specs)
        shard_devs = {s.device for s in leaf.addressable_shards}
        shard_rows = {s.data.shape[0] for s in leaf.addressable_shards}
        log(f"dp: client leaf {leaf.shape} sharding {leaf.sharding}; "
            f"{len(shard_devs)} devices, rows per shard {shard_rows}; "
            f"{dt:.3f} s for {spec.rounds} rounds, compile included")
        check(len(shard_devs) == K and shard_rows == {1},
              "the client axis is not spread one client per device")
        upd_dp = np.asarray(leaf)[0] - leaf0[0]
        del tr, leaf

        tr, hist_ref, leaf, leaf0_ref, dt = run(spec)
        log(f"dp: lace reference on {sorted({str(d) for d in leaf.devices()})}"
            f": {dt:.3f} s, compile included")
        check(np.array_equal(leaf0, leaf0_ref), "the two inits differ")
        upd_ref = np.asarray(leaf)[0] - leaf0_ref[0]
        del tr, leaf

    for r, (a, b) in enumerate(zip(hist_dp, hist_ref)):
        for k in ("loss_server", "loss_client"):
            e = abs(a[k] - b[k]) / abs(b[k])
            log(f"dp: round {r} {k} lace_dp={a[k]:.6f} lace={b[k]:.6f} "
                f"rel {e:.3e} (limit {DP_LOSS_RTOL})")
            check(math.isfinite(a[k]) and e <= DP_LOSS_RTOL,
                  f"round {r} {k}: lace_dp {a[k]} vs lace {b[k]}")
    err = rel_l2(upd_dp, upd_ref)
    log(f"dp: client leaf update after {spec.rounds} rounds: relative L2 "
        f"error {err:.3e} (limit {DP_UPDATE_RTOL}), |update| "
        f"{float(np.linalg.norm(upd_ref)):.3e}")
    check(float(np.linalg.norm(upd_ref)) > 0, "the client leaf did not move")
    check(err <= DP_UPDATE_RTOL, f"client leaf update differs by {err}")


# ---------------------------------------------------------------------------


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run only the lace_dp path and its one-chip "
                         "comparison")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX found platform "
                         f"{dev.platform!r} ({len(devices)} device(s)); "
                         "there is no CPU fallback")
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro" / "__init__.py").is_file():
        raise SystemExit(f"chip_smoke: no repro package under {src}; run "
                         "it from a checkout of the repository")
    sys.path.insert(0, str(src))
    from repro.launch.compile_cache import use_compile_cache

    log(f"device kind {dev.device_kind!r}, count {len(devices)}, platform "
        f"{dev.platform}")
    log(f"jax {jax.__version__}, jaxlib {metadata.version('jaxlib')}, "
        f"libtpu {metadata.version('libtpu')}")
    log(f"compile cache: {use_compile_cache()}")

    spec = train_spec(TRAIN_ARGV)
    cfg = spec.model_config()
    check(not spec.reduced and all(getattr(cfg, k) == v
                                   for k, v in FULL_WIDTH.items()),
          f"{cfg.name} is not the published width {FULL_WIDTH}")

    if args.chips == 4:
        check(len(devices) == 4, f"--chips 4 needs 4 devices, found "
              f"{len(devices)}")
        dp_phase(TRAIN_ARGV + ["--rounds", "2"])
        log("dp: passed")
    else:
        trainer = train_phase(TRAIN_ARGV, dev)
        log("train: passed")
        ckpt = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
        try:
            from repro.checkpoint import save

            save(ckpt, trainer.round - 1, trainer.state.inner.params)
            del trainer      # frees the train state before serving
            gc.collect()
            serve_phase(ckpt, spec.reduced, dev)
        finally:
            shutil.rmtree(ckpt, ignore_errors=True)
        log("serve: passed")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
