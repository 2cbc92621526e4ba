"""End-to-end SCALA training driver (host-scale).

Trains a transformer LM with the full SCALA protocol — partial client
participation, eq. (3) batch sizing, T local iterations with concatenated
activations + dual logit-adjusted losses, FedAvg every round — on
synthetic domain-skewed token data.

Since the ``repro.api`` redesign this driver is a thin CLI adapter:
argparse populates a declarative :class:`repro.api.ExperimentSpec`
(optimizer, federation, execution mode, data — the same spec tree the
benchmarks and sweep manifests use) and hands it to
:class:`repro.api.Trainer`. The spec is the unit of reproducibility:

* ``--dump-config [PATH]`` — write the resolved spec as JSON (stdout if
  no PATH) and exit without training;
* ``--config PATH`` — load a spec JSON and run it verbatim (the other
  spec-level flags are ignored). ``--dump-config`` output fed back via
  ``--config`` reproduces the identical run (test-enforced).

Participation comes in two modes, selected by ``--participation``:

* a bare fraction (``--participation 0.25``) — legacy host-side subset
  sampling (execution mode ``"subset"``): each round stacks only the
  C = r*K sampled clients;
* a scheduler spec (``full`` | ``uniform:FRAC`` |
  ``dirichlet:FRAC[:ALPHA]``) — the fed layer's in-program mode
  (``"masked"``, or ``"sparse"`` with ``--slot-gather``): all K clients
  stay stacked and a per-round 0/1 mask (sampled inside the compiled
  round) selects the subset, recomputing priors / logit adjustments per
  subset. Note the batch-size semantics differ: eq. (3) splits
  ``--server-batch`` across all K *slots* before masking, so the
  participating subset sees ~FRAC * server_batch tokens per local step
  (vs the full server_batch across the C participants in fraction
  mode). Scale ``--server-batch`` by 1/FRAC for parity.

``--aggregator`` picks the FL-phase weighting (fedavg | weighted |
bias_compensated[:GAMMA] | staleness_weighted[:DECAY]) and
``--opt-state-policy`` the client optimizer state's round-boundary
behavior (carry | reset | average). ``--server-optimizer`` adds FedOpt
on the server half (the round delta as a pseudo-gradient at
``--server-lr``). ``--async`` switches to the asynchronous event
runtime (:mod:`repro.fed.runtime`) with ``--delay-spec`` / ``--cohort``
/ ``--staleness-decay`` / ``--mix-rate``; ``--delay-spec zero --cohort
K`` reproduces the synchronous rounds exactly. ``--snapshots delta
--ring-size R`` stores async snapshots as a ring of recent server
versions instead of a dense (K, ...) per-client copy — O(cohort + ring)
resident state, bit-identical updates (README §Scaling the client axis,
``benchmarks/BENCH_scale.json``) — and ``--lr-scale cohort`` rescales
the client schedule by cohort/clients.

Dispatch-efficiency knobs (README §Performance,
``benchmarks/BENCH_dispatch.json``): ``--precision bf16`` runs the
engine compute in bfloat16 against f32 master params,
``--boundary dual`` reverts the one-pass fused eq. 14/15 loss stage to
the literal two ``value_and_grad`` passes (gradients are bit-identical
either way; see ``benchmarks/BENCH_boundary.json``),
``--rounds-per-call R`` fuses R whole rounds into one compiled dispatch
(bit-identical to unfused rounds at f32; keep 1 while debugging), and
``--no-donate`` disables the in-place (donated) round-state update.

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --reduced \
      --rounds 20 --clients 16 --participation uniform:0.25 --seq 128 \
      --aggregator bias_compensated --optimizer momentum \
      --schedule cosine --warmup 10

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --reduced \
      --rounds 40 --clients 16 --async --cohort 4 \
      --delay-spec lognormal:1:1.5 --staleness-decay 0.5

  PYTHONPATH=src python -m repro.launch.train --config sweep/run_003.json
"""
from __future__ import annotations

import argparse
import dataclasses
import sys

import jax

from repro import api
from repro.checkpoint import save
from repro.configs import ScalaConfig
from repro.core import engine
from repro.launch.compile_cache import use_compile_cache


def spec_from_args(args) -> api.ExperimentSpec:
    """Resolve the CLI surface into the declarative experiment spec."""
    # participation: bare fraction (legacy host-side "subset" mode) or a
    # fed scheduler spec (in-program "masked"/"sparse")
    try:
        part_frac = float(args.participation)
        scheduler_spec = None
    except ValueError:
        part_frac = 1.0
        scheduler_spec = args.participation

    mode = "subset" if scheduler_spec is None else "masked"
    if args.slot_gather:
        mode = "sparse"
    if args.async_mode:
        mode = "async"

    server_opt = (None if args.server_optimizer == "none"
                  else api.OptimSpec(
                      name=api.OPTIMIZER_ALIASES.get(args.server_optimizer,
                                                     args.server_optimizer),
                      lr=args.server_lr, momentum=args.momentum,
                      weight_decay=args.weight_decay))
    return api.ExperimentSpec(
        arch=args.arch, reduced=args.reduced, method="scala",
        rounds=args.rounds, seed=args.seed,
        scala=ScalaConfig(
            num_clients=args.clients, participation=part_frac,
            local_iters=args.local_iters, server_batch=args.server_batch,
            lr=args.lr, adjust_server=not args.no_adjust,
            adjust_client=not args.no_adjust),
        optim=api.OptimSpec(name=args.optimizer, momentum=args.momentum,
                            weight_decay=args.weight_decay,
                            schedule=args.schedule, warmup=args.warmup),
        fed=api.FedSpec(aggregator=args.aggregator,
                        participation=scheduler_spec,
                        opt_state_policy=args.opt_state_policy,
                        faults=args.faults or None,
                        guards=args.guards or None),
        execution=api.ExecutionSpec(
            mode=mode, backend="lace", delay=args.delay_spec,
            cohort=args.cohort, staleness_decay=args.staleness_decay,
            mix_rate=args.mix_rate, server_optimizer=server_opt,
            unroll=args.unroll, precision=args.precision,
            boundary=args.boundary,
            rounds_per_call=args.rounds_per_call,
            donate=not args.no_donate,
            snapshots=args.snapshots, ring_size=args.ring_size,
            lr_scale=args.lr_scale, arrival=args.arrival,
            opt_paging=args.opt_paging,
            deadline=args.deadline, backoff=args.backoff),
        data=api.DataSpec(kind="lm_synthetic", seq=args.seq,
                          docs_per_client=args.docs_per_client))


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="",
                    help="run a spec JSON (from --dump-config / a sweep "
                         "manifest) verbatim; spec-level flags are ignored")
    ap.add_argument("--dump-config", nargs="?", const="-", default=None,
                    metavar="PATH",
                    help="write the resolved ExperimentSpec JSON (stdout "
                         "if no PATH) and exit without training")
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--participation", default="0.25",
                    help="bare fraction (legacy host-side subset sampling) "
                         "or scheduler spec: full | uniform:FRAC | "
                         "dirichlet:FRAC[:ALPHA] (in-program masking)")
    ap.add_argument("--aggregator", default="weighted",
                    help="FL-phase weighting spec: fedavg | weighted | "
                         "bias_compensated[:GAMMA] | "
                         "staleness_weighted[:DECAY]")
    ap.add_argument("--opt-state-policy", default="carry",
                    choices=engine.OPT_STATE_POLICIES,
                    help="client optimizer state at the round boundary "
                         "(see engine.make_round_runner)")
    ap.add_argument("--slot-gather", action="store_true",
                    help="sparse-slot compute: gather the scheduler's "
                         "fixed subset into a dense axis before the local "
                         "scan (needs a scheduler spec --participation)")
    ap.add_argument("--server-optimizer", default="none",
                    choices=("none", "sgd", "momentum", "adamw", "fedavgm",
                             "fedadam"),
                    help="FedOpt on the server half's round/event delta")
    ap.add_argument("--server-lr", type=float, default=1.0)
    ap.add_argument("--async", dest="async_mode", action="store_true",
                    help="asynchronous event runtime (fed.make_async_runner)"
                         " instead of barrier rounds")
    ap.add_argument("--delay-spec", default="lognormal:1:1",
                    help="completion-delay model for --async: zero | "
                         "constant[:D] | uniform:LO:HI | "
                         "lognormal[:MEDIAN[:SIGMA]]")
    ap.add_argument("--cohort", type=int, default=0,
                    help="arrivals per async event (0 = clients/4, min 1)")
    ap.add_argument("--staleness-decay", type=float, default=0.5,
                    help="per-version decay of stale arrivals' weights")
    ap.add_argument("--mix-rate", type=float, default=1.0,
                    help="global-model mixing rate toward the cohort average")
    ap.add_argument("--snapshots", default="dense",
                    choices=("dense", "delta"),
                    help="async snapshot storage: dense keeps a (K, ...) "
                         "per-client copy of the client half; delta keeps "
                         "only a ring of recent server states "
                         "(O(cohort + ring) resident bytes)")
    ap.add_argument("--ring-size", type=int, default=64,
                    help="retained server versions for --snapshots delta")
    ap.add_argument("--lr-scale", default="none",
                    choices=("none", "cohort"),
                    help="async learning-rate scaling: cohort multiplies "
                         "the schedule by cohort/clients")
    ap.add_argument("--arrival", default="sort",
                    choices=("sort", "topk", "topk:sharded"),
                    help="async cohort-pop algorithm: sort = per-event "
                         "(K,) lexsort; topk = O(K)-work top-k pop "
                         "(bit-identical); topk:sharded adds a per-shard "
                         "pop + small merge on the client mesh")
    ap.add_argument("--opt-paging", default="none",
                    choices=("none", "host"),
                    help="host = page per-client optimizer moments to a "
                         "host store and gather only the arrival cohort "
                         "per event (delta+carry with any optimizer)")
    ap.add_argument("--local-iters", type=int, default=5)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--server-batch", type=int, default=16)
    ap.add_argument("--docs-per-client", type=int, default=32)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--optimizer", default="sgd",
                    choices=("sgd", "momentum", "adamw"))
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--weight-decay", type=float, default=0.0)
    ap.add_argument("--schedule", default="constant",
                    choices=("constant", "cosine"))
    ap.add_argument("--warmup", type=int, default=0,
                    help="warmup steps (local iterations) for --schedule cosine")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-adjust", action="store_true",
                    help="ablation: plain SFL (no logit adjustments)")
    ap.add_argument("--no-scan", action="store_true",
                    help="per-step Python round loop instead of the fused "
                         "scan program (A/B baseline)")
    ap.add_argument("--unroll", type=int, default=-1,
                    help="scan unroll factor: -1 = auto (full unroll on "
                         "CPU, where XLA runs while-loop bodies with "
                         "reduced parallelism; rolled elsewhere to keep "
                         "the HLO small), 0 = full unroll, N = factor")
    ap.add_argument("--precision", default="f32", choices=("f32", "bf16"),
                    help="engine compute policy: bf16 forward/backward "
                         "against f32 master params (priors, losses, "
                         "updates, aggregation stay f32)")
    ap.add_argument("--boundary", default="fused",
                    choices=("dual", "fused"),
                    help="split-boundary loss schedule: 'fused' computes "
                         "the eq. 14/15 pair in one pass over a shared "
                         "logits matmul (default; gradient-bitwise vs. "
                         "dual), 'dual' keeps two value_and_grad passes")
    ap.add_argument("--rounds-per-call", type=int, default=1,
                    help="rounds fused into one jitted dispatch (outer "
                         "lax.scan over whole rounds; keep 1 when "
                         "debugging or checkpointing every round)")
    ap.add_argument("--no-donate", action="store_true",
                    help="disable buffer donation of the round state "
                         "(donation updates params/opt-state in place; "
                         "disable only for debugging aliasing issues)")
    ap.add_argument("--faults", default="",
                    help="deterministic fault injection spec: comma-joined "
                         "drop:P | corrupt:P[:MODE[:SCALE]] (MODE nan | inf "
                         "| noise) | stall:P[:FACTOR] — chaos testing at "
                         "spec level (fed.faults)")
    ap.add_argument("--guards", default="",
                    help="aggregation guard spec: nonfinite and/or "
                         "clip:TAU[:BETA] — rejected clients shrink the "
                         "cohort and the eq. 14/15 logit adjustments are "
                         "recomputed over the survivors (fed.guards)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="async only: bound each event's cohort barrier; "
                         "clients not finished by (earliest finish + "
                         "DEADLINE) miss the event and are requeued with "
                         "exponential backoff")
    ap.add_argument("--backoff", type=float, default=2.0,
                    help="requeue delay multiplier per consecutive miss "
                         "(with --deadline)")
    ap.add_argument("--checkpoint-dir", default="",
                    help="save params-only checkpoints (servable via "
                         "launch/serve.py) at round-fusion boundaries")
    ap.add_argument("--state-dir", default="",
                    help="save FULL crash-recovery checkpoints (params + "
                         "optimizer + fed/async state + host RNG) via "
                         "Trainer.save at round-fusion boundaries; resume "
                         "with --resume")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest complete checkpoint from "
                         "--state-dir before training and run only the "
                         "remaining rounds (bit-identical continuation)")
    return ap


def _run_no_scan(spec: api.ExperimentSpec, args):
    """A/B baseline: per-step Python round loop (legacy federation
    settings only) — the one path that bypasses the fused program."""
    import time

    import jax.numpy as jnp
    import numpy as np

    from repro.api.build import text_split_init
    from repro.data.loader import lm_round_batches, sample_clients

    if (spec.execution.mode != "subset"
            or spec.fed.aggregator != "weighted"
            or spec.fed.opt_state_policy != "carry"
            or spec.execution.server_optimizer is not None
            or spec.execution.rounds_per_call != 1):
        raise SystemExit("--no-scan supports only the legacy federation "
                         "settings (fraction participation, no "
                         "--slot-gather, weighted aggregator, carry "
                         "opt-state policy, no server optimizer, "
                         "--rounds-per-call 1)")
    cfg = spec.model_config()
    sc = spec.scala
    data = api.build_lm_data(cfg, sc.num_clients, spec.data.docs_per_client,
                             spec.data.seq, spec.seed)
    C = sc.clients_per_round
    model, params = text_split_init(spec, C)
    opt = spec.optim.make()
    sched = spec.optim.make_schedule(spec.rounds * sc.local_iters,
                                     default_lr=sc.lr)
    state = engine.init_train_state(params, opt)
    # the shared donated jit wrapper from repro.api: even the legacy
    # per-step loop updates params/opt-state in place instead of copying
    step = api.donated_jit(
        engine.make_split_step(model, sc, backend="lace", optimizer=opt,
                               schedule=sched,
                               precision=spec.execution.precision),
        donate=spec.execution.donate)
    rng = np.random.default_rng(spec.seed)
    for rnd in range(spec.rounds):
        t0 = time.time()
        selected = sample_clients(sc.num_clients, C, rng)
        batches = lm_round_batches(data, selected, sc.server_batch,
                                   sc.local_iters, rng)
        sizes = jnp.asarray(batches.pop("sizes"))
        metrics = None
        for t in range(sc.local_iters):
            batch_t = {k: jnp.asarray(v[t]) for k, v in batches.items()}
            state, metrics = step(state, batch_t)
        state = dataclasses.replace(
            state, params=engine.scala_aggregate(state.params, sizes))
        print(f"round {rnd:3d} "
              f"loss_s={float(metrics['loss_server']):.4f} "
              f"loss_c={float(metrics['loss_client']):.4f} "
              f"({time.time() - t0:.1f}s)", flush=True)
        if args.checkpoint_dir:
            save(args.checkpoint_dir, rnd, state.params)
    print("done")
    return state.params


def main(argv=None):
    args = build_parser().parse_args(argv)
    use_compile_cache()

    try:
        if args.config:
            with open(args.config) as f:
                spec = api.ExperimentSpec.from_json(f.read())
        else:
            spec = spec_from_args(args)
    except ValueError as e:
        raise SystemExit(str(e))

    if args.dump_config is not None:
        try:
            spec.validate()        # a dumped manifest must be runnable
        except ValueError as e:
            raise SystemExit(str(e))
        payload = spec.to_json()
        if args.dump_config == "-":
            print(payload)
        else:
            with open(args.dump_config, "w") as f:
                f.write(payload + "\n")
            print(f"wrote {args.dump_config}", file=sys.stderr)
        return spec

    if args.no_scan:
        if spec.execution.mode == "async":
            raise SystemExit("--async compiles whole events; drop --no-scan")
        return _run_no_scan(spec, args)

    try:
        spec.validate()
    except ValueError as e:
        raise SystemExit(str(e))

    cfg = spec.model_config()
    print(f"arch={cfg.name} layers={cfg.num_layers} d={cfg.d_model} "
          f"vocab={cfg.vocab_size}")
    assert cfg.frontend is None, "LM driver supports text archs"

    if args.resume and not args.state_dir:
        raise SystemExit("--resume needs --state-dir (the directory "
                         "Trainer.save wrote full-state checkpoints to)")

    trainer = api.Trainer(spec)
    meta = trainer.program.metadata
    n_params = sum(x.size for x in jax.tree.leaves(
        trainer.state.inner.params["server"]))
    print(f"server params: {n_params/1e6:.1f}M, "
          f"mode: {meta['mode']} (slots: {meta['slots']}), "
          f"participation: {spec.fed.participation or spec.scala.participation}, "
          f"aggregator: {spec.fed.aggregator}, "
          f"opt-state: {spec.fed.opt_state_policy}, "
          f"optimizer: {spec.optim.spec}, schedule: {spec.optim.schedule}")
    if meta["mode"] == "async":
        extra_async = (f" deadline={spec.execution.deadline} "
                       f"backoff={spec.execution.backoff}"
                       if spec.execution.deadline else "")
        print(f"async: delay={spec.execution.delay} "
              f"cohort={spec.execution.resolve_cohort(meta['slots'])}"
              f"/{meta['slots']} "
              f"staleness_decay={spec.execution.staleness_decay} "
              f"mix_rate={spec.execution.mix_rate}{extra_async}")
    if spec.fed.faults or spec.fed.guards:
        print(f"fault tolerance: faults={spec.fed.faults or 'none'} "
              f"guards={spec.fed.guards or 'none'}")

    start = 0
    if args.resume:
        start = trainer.resume(args.state_dir)
        print(f"resumed at round {start} from {args.state_dir}")

    label = "event" if meta["mode"] == "async" else "round"
    rpc = meta["rounds_per_call"]

    def on_round(rnd, metrics, dt):
        extra = ""
        if "t_event" in metrics:
            extra = (f" t={metrics['t_event']:.2f}"
                     f" stale={metrics['staleness_mean']:.2f}")
        if "guard_rejected" in metrics:
            extra += f" rej={metrics['guard_rejected']:.0f}"
        print(f"{label} {rnd:3d} loss_s={metrics['loss_server']:.4f} "
              f"loss_c={metrics['loss_client']:.4f}{extra} ({dt:.1f}s)",
              flush=True)
        # under round fusion trainer.state only advances per chunk, so a
        # per-round save would write the chunk-boundary params R times
        # under R wrong labels; save once per chunk, at the round the
        # params actually correspond to
        at_boundary = (rnd + 1) % rpc == 0 or rnd == spec.rounds - 1
        if args.checkpoint_dir and at_boundary:
            save(args.checkpoint_dir, rnd, trainer.state.inner.params)
        if args.state_dir and at_boundary:
            trainer.save(args.state_dir)

    trainer.run(spec.rounds - start, on_round=on_round)
    print("done")
    return trainer


# --- legacy kwarg-style helpers (deprecated; warn once per process) -------


def _legacy_build_data(cfg, num_clients: int, docs_per_client: int, seq: int,
                       seed: int):
    return api.build_lm_data(cfg, num_clients, docs_per_client, seq, seed)


def _legacy_build_schedule(args, total_steps: int):
    return api.OptimSpec(name="sgd", lr=args.lr, schedule=args.schedule,
                         warmup=args.warmup).make_schedule(total_steps)


_DEPRECATED_HELPERS = {
    "build_data": (_legacy_build_data, "repro.api.build_lm_data (or an "
                                       "api.DataSpec inside api.Trainer)"),
    "build_schedule": (_legacy_build_schedule,
                       "repro.api.OptimSpec.make_schedule"),
}


def __getattr__(name):
    if name in _DEPRECATED_HELPERS:
        fn, use = _DEPRECATED_HELPERS[name]
        api.warn_once(f"repro.launch.train.{name}", use)
        return fn
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


if __name__ == "__main__":
    main()
