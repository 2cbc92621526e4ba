"""``build(spec)`` — one builder for every round program.

Dispatches an :class:`repro.api.ExperimentSpec` across the masked /
sparse-slot / async SCALA rounds *and* the FL/SFL baselines, returning a
:class:`RoundProgram`: an ``init()`` for the full program state (params,
optimizer state, federation/async state), one jitted ``step`` with a
*uniform* signature regardless of mode, and a jitted ``predict`` for
evaluation. The old constructors (``engine.make_round_runner``,
``fed.make_async_runner``, ``baselines.make_fl_round`` /
``make_sfl_round``) remain the internal layer this builder calls — the
program it builds is bit-identical to direct construction with the same
keys (test-enforced in ``tests/test_api.py``).

PRNG choreography (kept exactly as the pre-API drivers', so existing
benchmark numbers and examples reproduce):

* params init — ``PRNGKey(seed)`` (CNN: ``A.init_params`` then split;
  text: per-half ``T.init_params`` via ``engine.init_scala_params``);
* federation / async state — ``PRNGKey(seed + 1)`` for
  ``lm_synthetic`` (the ``launch/train.py`` convention),
  ``fold_in(PRNGKey(seed), 11)`` for ``image_synthetic`` (the
  ``benchmarks/common.run_experiment`` convention).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.api.specs import (SCALA_METHODS, SFL_METHODS, ExperimentSpec)


@dataclass(frozen=True)
class ProgramState:
    """The full state one :class:`RoundProgram` step threads: ``inner``
    is the method's own state (engine :class:`TrainState`, an FL
    baseline's global params, an SFL state dict) and ``fed`` the
    federation carry (sync fed-state dict, :class:`AsyncFedState`, FL
    baseline state, or ``()``)."""

    inner: Any
    fed: Any = ()


jax.tree_util.register_dataclass(ProgramState,
                                 data_fields=("inner", "fed"),
                                 meta_fields=())


@dataclass(frozen=True)
class RoundProgram:
    """A built experiment: state factory + jitted step + metadata.

    * ``init() -> ProgramState`` — params, optimizer state, and
      federation/async state from the spec's seed;
    * ``step(state, batches, sizes) -> (state, metrics)`` — ONE
      dispatch: one round (or async event) by default; with
      ``execution.rounds_per_call = R > 1`` the step is an outer
      ``lax.scan`` over R whole rounds — ``batches``/``sizes`` leaves
      gain a leading (R,) axis and metrics come back stacked. With
      ``execution.donate`` (the default) the ``state`` argument's
      buffers are donated: the state you pass in is dead after the
      call — keep only the returned state;
    * ``predict(state, batch) -> logits`` — the current global model's
      forward (slot-0 client half + server half for split methods;
      always full f32, independent of ``execution.precision``);
    * ``metadata`` — static facts a driver wants without re-deriving:
      ``mode``, ``slots``, ``thread_fed``, ``backend``, ``method``,
      ``precision``, ``rounds_per_call``, ``donate``.
    """

    spec: ExperimentSpec
    model: Any
    init: Callable[[], ProgramState]
    step: Callable[..., Any]
    predict: Callable[..., Any]
    metadata: Dict[str, Any]


def donated_jit(fn, donate: bool = True):
    """jit a round/step function with its state argument (argnum 0)
    donated, so the stacked client params, optimizer moments, and
    federation state update in place instead of being copied every
    dispatch. The one rule donation imposes: the state you pass in is
    dead after the call — keep only the returned state. This wrapper is
    THE jit every driver should use for a step; the legacy
    ``launch/train.py --no-scan`` branch shares it too.
    """
    return jax.jit(fn, donate_argnums=(0,)) if donate else jax.jit(fn)


def _fuse_rounds(step, unroll):
    """Fuse ``R = rounds_per_call`` whole rounds into one XLA program:
    an outer ``lax.scan`` over the per-round step. ``batches`` / ``sizes``
    leaves carry a leading (R,) axis (R is read from the shapes, so one
    jitted program handles full chunks and the remainder chunk alike via
    shape-specialized recompilation); metrics come back stacked (R, ...)
    and are pulled to host once per chunk by the Trainer."""

    def fused(state, batches, sizes):
        R = jax.tree.leaves(batches)[0].shape[0]
        if unroll is True or R == 1:
            # trace-time unroll: a plain round chain with static slices.
            # Structurally identical to R sequential dispatches (lax.scan
            # — even fully unrolled — compiles the body a hair
            # differently: one-ulp conv drift and an extra carry copy),
            # so the fused chunk stays bit-identical to sequential
            # rounds and XLA updates the round state in place.
            ms = []
            for r in range(R):
                state, m = step(state,
                                jax.tree.map(lambda a: a[r], batches),
                                jax.tree.map(lambda a: a[r], sizes))
                ms.append(m)
            return state, jax.tree.map(lambda *xs: jnp.stack(xs), *ms)

        def body(st, inp):
            b, sz = inp
            return step(st, b, sz)

        return jax.lax.scan(body, state, (batches, sizes), unroll=unroll)

    return fused


def _fed_key(spec: ExperimentSpec):
    key = jax.random.PRNGKey(spec.seed)
    if spec.data.kind == "image_synthetic":
        return jax.random.fold_in(key, 11)
    return jax.random.PRNGKey(spec.seed + 1)


def _broadcast_slots(tree, slots: int):
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (slots,) + a.shape), tree)


def _server_optimizer(spec: ExperimentSpec):
    so = spec.execution.server_optimizer
    return (None, 1.0) if so is None else (so.make(), so.lr)


# ---------------------------------------------------------------------------
# per-family model + params
# ---------------------------------------------------------------------------


def _cnn_split_init(spec: ExperimentSpec):
    from repro.core.scala import alexnet_split_model
    from repro.models import alexnet as A

    model = alexnet_split_model(spec.split,
                                num_classes=spec.data.num_classes)
    key = jax.random.PRNGKey(spec.seed)
    full = A.init_params(key, num_classes=spec.data.num_classes,
                         width=spec.width)
    wc, ws = A.split_params(full, spec.split)
    return model, wc, ws, full, key


def text_split_init(spec: ExperimentSpec, slots: int):
    from repro.core import engine
    from repro.core.scala import transformer_split_model
    from repro.models import transformer as T

    cfg = spec.model_config()
    model = transformer_split_model(cfg)
    params = engine.init_scala_params(
        jax.random.PRNGKey(spec.seed),
        lambda k: T.init_params(k, cfg)["client"],
        lambda k: T.init_params(k, cfg)["server"],
        slots)
    return model, params


# ---------------------------------------------------------------------------
# the builder
# ---------------------------------------------------------------------------


def build(spec: ExperimentSpec, *, mesh=None, batch_specs=None,
          jit: bool = True) -> RoundProgram:
    """Validate ``spec`` and build its :class:`RoundProgram`.

    ``mesh`` / ``batch_specs`` are required iff
    ``spec.execution.backend == "lace_dp"`` (forwarded to the engine's
    manual-SPMD round). ``jit=False`` returns the un-jitted step
    (HLO inspection, nesting inside an outer jit).
    """
    spec.validate()
    ex = spec.execution
    if ex.backend == "lace_dp" and (mesh is None or batch_specs is None):
        raise ValueError("backend 'lace_dp' needs build(spec, mesh=, "
                         "batch_specs=)")
    if ex.arrival == "topk:sharded" and mesh is None:
        raise ValueError("arrival 'topk:sharded' pops per client-mesh "
                         "shard; it needs build(spec, mesh=)")

    if spec.method in SCALA_METHODS:
        program = _build_scala(spec, mesh=mesh, batch_specs=batch_specs)
    elif spec.method in SFL_METHODS:
        program = _build_sfl(spec)
    else:
        program = _build_fl(spec)

    program.metadata.update(precision=ex.precision,
                            boundary=ex.boundary,
                            rounds_per_call=ex.rounds_per_call,
                            donate=ex.donate)
    if program.metadata.get("host_paged"):
        # the host-paged step is a two-phase host loop (predict the pop,
        # gather the cohort's moments from the host store, run the jitted
        # event, scatter back) — it jits and donates its event internally
        # and cannot be wrapped in an outer jit or fused across rounds.
        if not jit:
            raise ValueError("opt_paging='host' builds a host-side "
                             "two-phase step (its event is jitted "
                             "internally); jit=False is not supported")
        init = program.init
        if ex.donate:
            # same donation-safety copy as the jitted path below: the
            # paged event donates the state, so every init() must hand
            # out fresh buffers.
            _raw_init = program.init
            init = lambda: jax.tree.map(jnp.copy, _raw_init())
        return dataclasses.replace(program, init=init,
                                   predict=jax.jit(program.predict))
    step = program.step
    if ex.rounds_per_call > 1:
        step = _fuse_rounds(step, ex.resolve_unroll())
    if program.metadata.get("client_major"):
        # the FL/SFL baselines consume client-major (C, T, ...) batches;
        # the driver-facing layout stays iteration-major (T, C, ...), so
        # transpose once per dispatch HERE — outside the fused-rounds
        # scan — instead of re-transposing every round inside the chunk
        inner_step = step
        a0, a1 = (1, 2) if ex.rounds_per_call > 1 else (0, 1)
        step = lambda st, b, s: inner_step(
            st, jax.tree.map(lambda a: jnp.swapaxes(a, a0, a1), b), s)
    if jit:
        step = donated_jit(step, donate=ex.donate)
        init = program.init
        if ex.donate:
            # the un-donated init closes over the built param buffers, so
            # two init() calls would share them — and a donated step may
            # neither receive the same buffer twice (the async snapshots
            # alias the stacked client half) nor consume a buffer a
            # previous init() handed out. A leaf-wise copy makes every
            # init() a fresh, donation-safe state.
            _raw_init = program.init
            init = lambda: jax.tree.map(jnp.copy, _raw_init())
        program = dataclasses.replace(program, step=step, init=init,
                                      predict=jax.jit(program.predict))
    elif step is not program.step:
        program = dataclasses.replace(program, step=step)
    return program


def _build_scala(spec: ExperimentSpec, *, mesh=None,
                 batch_specs=None) -> RoundProgram:
    from repro import fed
    from repro.core import engine

    ex, fd, sc = spec.execution, spec.fed, spec.scala
    if spec.method == "scala_noadj":
        sc = dataclasses.replace(sc, adjust_server=False, adjust_client=False)
    slots = spec.slots
    cfg = spec.model_config()

    opt = spec.optim.make()
    sched = spec.optim.make_schedule(spec.rounds * sc.local_iters,
                                     default_lr=sc.lr)
    agg = fd.make_aggregator()
    scheduler = (fd.make_participation(slots)
                 if ex.mode in ("masked", "sparse") and fd.participation
                 else None)
    server_opt, server_lr = _server_optimizer(spec)
    faults = fd.make_faults()
    guards = fd.make_guards()
    unroll = ex.resolve_unroll()

    # delta snapshots carry the global client half over ONE param slot
    # (the ring replaces the per-client stacking) — the logical client
    # count stays `slots` everywhere else (versions, delays, batches)
    delta = ex.mode == "async" and ex.snapshots == "delta"
    param_slots = 1 if delta else slots
    if cfg.family == "cnn":
        model, wc, ws, _, _ = _cnn_split_init(spec)
        params = {"client": _broadcast_slots(wc, param_slots), "server": ws}
    else:
        model, params = text_split_init(spec, param_slots)
    # the initial params live on the host: init() places a fresh device
    # copy per call, so the program never holds a second resident copy
    # of the (K, ...) stacked state beside the one it trains
    params = jax.device_get(params)

    if ex.mode == "async":
        delays = ex.make_delays()
        cohort = ex.resolve_cohort(slots)
        paged = ex.opt_paging == "host"
        round_fn = fed.make_async_runner(
            model, sc, backend=ex.backend, boundary=ex.boundary,
            optimizer=opt, schedule=sched,
            delays=delays, cohort=cohort,
            staleness_decay=ex.staleness_decay, mix_rate=ex.mix_rate,
            aggregator=agg, server_optimizer=server_opt,
            server_lr=server_lr, opt_state_policy=fd.opt_state_policy,
            unroll=unroll, precision=ex.precision,
            snapshots=ex.snapshots, ring_size=ex.ring_size,
            lr_scale=ex.lr_scale, num_clients=slots,
            arrival=ex.arrival, paged_opt=paged,
            mesh=mesh, batch_specs=batch_specs,
            deadline=ex.deadline, backoff=ex.backoff,
            faults=faults, guards=guards)
        pager = (fed.HostOptPager(
            opt, jax.tree.map(lambda a: a[0], params["client"]), slots)
            if paged else None)
        sched_mesh = mesh if ex.arrival == "topk:sharded" else None

        def init() -> ProgramState:
            dev = jax.tree.map(jnp.asarray, params)
            afed = fed.init_async_state(
                _fed_key(spec), dev["client"], delays, aggregator=agg,
                server_optimizer=server_opt, server_params=dev["server"],
                snapshots=ex.snapshots, ring_size=ex.ring_size,
                num_clients=slots, mesh=sched_mesh, guards=guards)
            if pager is not None:
                pager.reset()
            return ProgramState(inner=engine.init_train_state(dev, opt),
                                fed=afed)

        if paged:
            import numpy as np

            # predict the arrival pop OUTSIDE the event with the same
            # deterministic pop the event applies internally, so the
            # host gather/scatter indices match the event's cohort
            # exactly. np.asarray blocks until the pop has consumed the
            # schedule scalars, making the event's donation safe.
            pop = jax.jit(fed.make_arrival_pop(cohort, ex.arrival,
                                               mesh=sched_mesh))

            def ev_fn(state: ProgramState, batches, sizes, cohort_opt):
                inner, afed, metrics, new_co = round_fn(
                    state.inner, state.fed, batches, sizes, cohort_opt)
                return ProgramState(inner=inner, fed=afed), metrics, new_co

            ev = donated_jit(ev_fn, donate=ex.donate)

            def step(state: ProgramState, batches, sizes):
                idx = np.asarray(
                    pop(state.fed.finish_time, state.fed.version)[0])
                cohort_opt = pager.gather(idx)
                new_state, metrics, new_co = ev(state, batches, sizes,
                                                cohort_opt)
                pager.scatter(idx, new_co)
                return new_state, metrics
        else:
            def step(state: ProgramState, batches, sizes):
                inner, afed, metrics = round_fn(state.inner, state.fed,
                                                batches, sizes)
                return ProgramState(inner=inner, fed=afed), metrics

        thread_fed = True
    else:
        round_fn = engine.make_round_runner(
            model, sc, backend=ex.backend, boundary=ex.boundary,
            optimizer=opt, schedule=sched,
            unroll=unroll, aggregator=agg, participation=scheduler,
            opt_state_policy=fd.opt_state_policy,
            slot_gather=ex.mode == "sparse", server_optimizer=server_opt,
            server_lr=server_lr, mesh=mesh, batch_specs=batch_specs,
            precision=ex.precision, faults=faults, guards=guards)
        thread_fed = (scheduler is not None or agg.stateful
                      or server_opt is not None or faults is not None
                      or (guards is not None and guards.stateful))

        def init() -> ProgramState:
            dev = jax.tree.map(jnp.asarray, params)
            fed_state = (fed.init_fed_state(_fed_key(spec), agg, scheduler,
                                            num_clients=slots,
                                            server_optimizer=server_opt,
                                            server_params=dev["server"],
                                            faults=faults, guards=guards)
                         if thread_fed else ())
            return ProgramState(inner=engine.init_train_state(dev, opt),
                                fed=fed_state)

        if thread_fed:
            def step(state: ProgramState, batches, sizes):
                inner, fed_state, metrics = round_fn(state.inner, batches,
                                                     sizes, state.fed)
                return ProgramState(inner=inner, fed=fed_state), metrics
        else:
            def step(state: ProgramState, batches, sizes):
                inner, metrics = round_fn(state.inner, batches, sizes)
                return ProgramState(inner=inner, fed=state.fed), metrics

    def predict(state: ProgramState, batch):
        wc0 = jax.tree.map(lambda a: a[0], state.inner.params["client"])
        acts = model.client_fwd(wc0, batch)
        logits, _ = model.server_fwd(state.inner.params["server"], acts)
        return logits

    return RoundProgram(
        spec=spec, model=model, init=init, step=step, predict=predict,
        metadata=dict(method=spec.method, mode=ex.mode, slots=slots,
                      backend=ex.backend, thread_fed=thread_fed,
                      snapshots=ex.snapshots, arrival=ex.arrival,
                      host_paged=ex.opt_paging == "host"))


def _build_fl(spec: ExperimentSpec) -> RoundProgram:
    from repro.core import baselines as B
    from repro.models import alexnet as A

    fd = spec.fed
    slots = spec.slots
    agg = fd.make_aggregator() if fd.aggregator != "weighted" \
        else None
    server_opt, server_lr = _server_optimizer(spec)

    def fwd(p, x):
        return A.forward(p, x, spec.split)

    def feats(p, x):
        return A.features(p, x)

    model = B.FedModel(forward=fwd, num_classes=spec.data.num_classes,
                       features=feats)
    key = jax.random.PRNGKey(spec.seed)
    w0 = A.init_params(key, num_classes=spec.data.num_classes,
                       width=spec.width)
    round_fn = B.make_fl_round(spec.method, model,
                               lr=spec.optim.resolve_lr(spec.scala.lr),
                               aggregator=agg, server_optimizer=server_opt,
                               server_lr=server_lr,
                               precision=spec.execution.precision)

    def init() -> ProgramState:
        return ProgramState(
            inner=w0,
            fed=B.init_fl_state(spec.method, w0, slots,
                                server_optimizer=server_opt))

    # client-major step: batches arrive (C, T, ...) — the (T, C) -> (C, T)
    # transpose is hoisted into build()'s dispatch wrapper, so a fused
    # rounds_per_call chunk transposes ONCE instead of once per round
    def step(state: ProgramState, batches, sizes):
        w, fl_state = round_fn(state.inner, batches, sizes, state.fed)
        return ProgramState(inner=w, fed=fl_state), {}

    def predict(state: ProgramState, batch):
        return model.forward(state.inner, batch["x"])

    return RoundProgram(
        spec=spec, model=model, init=init, step=step, predict=predict,
        metadata=dict(method=spec.method, mode="subset", slots=slots,
                      backend="logits", thread_fed=True, client_major=True))


def _build_sfl(spec: ExperimentSpec) -> RoundProgram:
    import numpy as np

    from repro.core import baselines as B
    from repro.models import alexnet as A

    fd = spec.fed
    slots = spec.slots
    agg = fd.make_aggregator() if fd.aggregator != "weighted" \
        else None
    model, wc, ws, _, key = _cnn_split_init(spec)

    state0 = {"wc": _broadcast_slots(wc, slots), "ws": ws}
    aux_head_fwd = None
    if spec.method == "sfl_localloss":
        probe = A.client_forward_from_split(
            wc, jnp.zeros((1, 32, 32, 3)), spec.split)
        feat_dim = int(np.prod(probe.shape[1:]))
        aux0 = {"w": jax.random.normal(
            key, (feat_dim, spec.data.num_classes)) * 0.05}
        state0["aux"] = _broadcast_slots(aux0, slots)

        def aux_head_fwd(p, feats):
            return feats.reshape(feats.shape[0], -1) @ p["w"]

    round_fn = B.make_sfl_round(spec.method, model,
                                lr=spec.optim.resolve_lr(spec.scala.lr),
                                aux_head_fwd=aux_head_fwd, aggregator=agg,
                                precision=spec.execution.precision)

    def init() -> ProgramState:
        return ProgramState(inner=state0, fed=())

    # client-major step — see _build_fl: the batch transpose is hoisted
    # into build()'s dispatch wrapper
    def step(state: ProgramState, batches, sizes):
        return ProgramState(inner=round_fn(state.inner, batches, sizes),
                            fed=state.fed), {}

    def predict(state: ProgramState, batch):
        wc0 = jax.tree.map(lambda a: a[0], state.inner["wc"])
        acts = model.client_fwd(wc0, batch)
        logits, _ = model.server_fwd(state.inner["ws"], acts)
        return logits

    return RoundProgram(
        spec=spec, model=model, init=init, step=step, predict=predict,
        metadata=dict(method=spec.method, mode="subset", slots=slots,
                      backend="logits", thread_fed=False,
                      client_major=True))
