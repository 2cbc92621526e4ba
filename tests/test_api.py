"""repro.api: the declarative experiment layer.

The acceptance bars:

(a) every registered aggregator / participation / delay / optimizer
    compact spec string parses, and the whole ExperimentSpec tree
    round-trips losslessly through to_dict()/from_dict() JSON;
(b) ``api.build(spec)`` produces bit-identical first-round results to
    direct constructor calls (``engine.make_round_runner`` /
    ``fed.make_async_runner`` / ``baselines.make_fl_round``) for one
    config in each execution mode (masked, sparse, async, fl-baseline);
(c) incoherent spec combinations are rejected at *spec* time with
    targeted errors;
(d) ``train.py --dump-config`` output fed back via ``--config``
    reproduces the identical run (same per-round metrics);
(e) the legacy kwarg-style train.py helpers warn once per process;
(f) the Trainer's image rounds, gathered on the device from the clients'
    resident rows, are bitwise the host's ``round_batches`` and leave the
    host RNG where it left it.
"""
import copy
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, fed, optim
from repro.configs import ScalaConfig
from repro.core import baselines as B
from repro.core import engine
from repro.core.scala import alexnet_split_model
from repro.models import alexnet as A
from repro.optim import schedules


def _roundtrip(spec: api.ExperimentSpec) -> api.ExperimentSpec:
    return api.ExperimentSpec.from_dict(json.loads(json.dumps(spec.to_dict())))


def _tree_bitwise_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _image_spec(**overrides):
    kw = dict(
        arch="alexnet-cifar", method="scala", rounds=2, seed=0,
        scala=ScalaConfig(num_clients=4, participation=0.5, local_iters=2,
                          server_batch=24, lr=0.05),
        data=api.DataSpec(kind="image_synthetic", n_train=200, alpha=2))
    kw.update(overrides)
    return api.ExperimentSpec(**kw)


def _image_batches(key, T_steps=2, C=4, Bk=5, num_classes=10):
    kx, ky = jax.random.split(key)
    return {"x": jax.random.normal(kx, (T_steps, C, Bk, 32, 32, 3)),
            "labels": jax.random.randint(ky, (T_steps, C, Bk), 0,
                                         num_classes),
            "weights": jnp.ones((T_steps, C, Bk), jnp.float32)}


# --------------------------------------------------------------------------
# (a) spec-string parsing + lossless JSON round-trip, per registry
# --------------------------------------------------------------------------


AGG_SPECS = ("fedavg", "weighted", "bias_compensated", "bias_compensated:1.5",
             "staleness_weighted", "staleness_weighted:0.25", "staleness")
PART_SPECS = ("full", "uniform:0.25", "uniform:0.5", "dirichlet:0.3",
              "dirichlet:0.3:0.25")
DELAY_SPECS = ("zero", "constant", "constant:2", "uniform:0.5:2",
               "lognormal", "lognormal:1:1.5", "lognormal:2:0.5")
OPT_SPECS = ("sgd", "sgd:0.05", "momentum", "momentum:0.1:0.8",
             "adamw", "adamw:0.001:0.01", "fedavgm:0.9", "fedadam:0.01")


@pytest.mark.parametrize("spec_str", AGG_SPECS)
def test_aggregator_spec_roundtrip(spec_str):
    agg = fed.make_aggregator(spec_str)
    assert agg.name in fed.AGGREGATORS
    part = "uniform:0.5" if agg.stateful else None
    spec = _image_spec(fed=api.FedSpec(aggregator=spec_str,
                                       participation=part))
    assert _roundtrip(spec) == spec
    assert _roundtrip(spec).fed.aggregator == spec_str   # verbatim


@pytest.mark.parametrize("spec_str", PART_SPECS)
def test_participation_spec_roundtrip(spec_str):
    sched = fed.make_participation(spec_str, 4)
    assert sched.name in fed.SCHEDULERS
    spec = _image_spec(fed=api.FedSpec(participation=spec_str))
    assert _roundtrip(spec) == spec
    assert _roundtrip(spec).fed.participation == spec_str


@pytest.mark.parametrize("spec_str", DELAY_SPECS)
def test_delay_spec_roundtrip(spec_str):
    model = fed.make_delays(spec_str)
    assert model.name in fed.DELAY_MODELS
    spec = _image_spec(execution=api.ExecutionSpec(mode="async",
                                                   delay=spec_str, cohort=2))
    assert _roundtrip(spec) == spec
    assert _roundtrip(spec).execution.delay == spec_str


@pytest.mark.parametrize("spec_str", OPT_SPECS)
def test_optimizer_spec_roundtrip(spec_str):
    o = api.OptimSpec.parse(spec_str)
    assert o.name in api.OPTIMIZERS
    o.make()                                             # registry-buildable
    # as the local optimizer AND as the server FedOpt sub-spec
    spec = _image_spec(
        optim=o,
        execution=api.ExecutionSpec(
            mode="masked",
            server_optimizer=api.OptimSpec.parse(spec_str, default_lr=1.0)))
    back = _roundtrip(spec)
    assert back == spec
    assert back.optim == o
    assert back.execution.server_optimizer.lr is not None


def test_optimizer_alias_canonicalization():
    assert api.OptimSpec.parse("fedadam:0.01") == api.OptimSpec(
        name="adamw", lr=0.01)
    assert api.OptimSpec.parse("fedavgm:0.9:0.95") == api.OptimSpec(
        name="momentum", lr=0.9, momentum=0.95)
    # unset lr defers to scala.lr
    assert api.OptimSpec.parse("sgd").resolve_lr(0.05) == 0.05
    assert api.OptimSpec.parse("sgd:0.1").resolve_lr(0.05) == 0.1
    # the canonical compact rendering (used by train.py's startup line)
    assert api.OptimSpec().spec == "sgd"
    assert api.OptimSpec.parse("fedadam:0.01").spec == "adamw:0.01:0.0"
    assert api.OptimSpec.parse("momentum:0.1:0.8").spec == "momentum:0.1:0.8"


def test_lm_spec_roundtrip_full_tree():
    spec = api.ExperimentSpec(
        arch="qwen1.5-0.5b", reduced=True, rounds=3, seed=7,
        scala=ScalaConfig(num_clients=8, local_iters=2, server_batch=8),
        optim=api.OptimSpec(name="momentum", schedule="cosine", warmup=4),
        fed=api.FedSpec(aggregator="bias_compensated:2.0",
                        participation="dirichlet:0.3:0.25",
                        opt_state_policy="average"),
        execution=api.ExecutionSpec(mode="sparse", backend="lace",
                                    server_optimizer=api.OptimSpec.parse(
                                        "fedadam:0.01", default_lr=1.0)),
        data=api.DataSpec(kind="lm_synthetic", seq=32, docs_per_client=4))
    spec.validate()
    assert _roundtrip(spec) == spec
    assert api.ExperimentSpec.from_json(spec.to_json()) == spec


# --------------------------------------------------------------------------
# (b) builder equivalence: api.build == direct constructors, bit-identical
# --------------------------------------------------------------------------


def _direct_scala_setup(spec):
    """The pre-api construction path, with the api's documented keys."""
    model = alexnet_split_model(spec.split, num_classes=spec.data.num_classes)
    key = jax.random.PRNGKey(spec.seed)
    full = A.init_params(key, num_classes=spec.data.num_classes,
                         width=spec.width)
    wc, ws = A.split_params(full, spec.split)
    slots = spec.slots
    params = {"client": jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (slots,) + a.shape), wc),
        "server": ws}
    return model, params, ws


@pytest.mark.parametrize("mode", ("masked", "sparse"))
def test_build_matches_direct_sync_round(mode):
    spec = _image_spec(
        fed=api.FedSpec(participation="uniform:0.5"),
        execution=api.ExecutionSpec(mode=mode, unroll=0))
    program = api.build(spec)
    batches = _image_batches(jax.random.PRNGKey(3))
    sizes = jnp.asarray([5.0, 5.0, 5.0, 5.0])

    state = program.init()
    out_state, metrics = program.step(state, batches, sizes)

    model, params, ws = _direct_scala_setup(spec)
    scheduler = fed.make_participation("uniform:0.5", spec.slots)
    round_fn = jax.jit(engine.make_round_runner(
        model, spec.scala, backend="logits", unroll=True,
        participation=scheduler, slot_gather=mode == "sparse"))
    fed_state = fed.init_fed_state(
        jax.random.fold_in(jax.random.PRNGKey(spec.seed), 11),
        fed.make_aggregator("weighted"), scheduler, num_clients=spec.slots)
    ref_state = engine.init_train_state(params, optim.sgd())
    ref_state, ref_fed, ref_metrics = round_fn(ref_state, batches, sizes,
                                               fed_state)

    _tree_bitwise_equal(out_state.inner.params, ref_state.params)
    _tree_bitwise_equal(out_state.fed, ref_fed)
    _tree_bitwise_equal(metrics, ref_metrics)


def test_build_matches_direct_async_event():
    spec = _image_spec(
        execution=api.ExecutionSpec(mode="async", delay="lognormal:1:1",
                                    cohort=2, staleness_decay=0.5,
                                    unroll=0))
    program = api.build(spec)
    batches = _image_batches(jax.random.PRNGKey(3))
    sizes = jnp.asarray([5.0, 5.0, 5.0, 5.0])

    state = program.init()
    out_state, metrics = program.step(state, batches, sizes)

    model, params, ws = _direct_scala_setup(spec)
    delays = fed.make_delays("lognormal:1:1")
    async_fn = jax.jit(fed.make_async_runner(
        model, spec.scala, backend="logits", delays=delays, cohort=2,
        staleness_decay=0.5, schedule=schedules.constant(spec.scala.lr),
        unroll=True))
    afed = fed.init_async_state(
        jax.random.fold_in(jax.random.PRNGKey(spec.seed), 11),
        params["client"], delays)
    ref_state = engine.init_train_state(params, optim.sgd())
    ref_state, ref_afed, ref_metrics = async_fn(ref_state, afed, batches,
                                                sizes)

    _tree_bitwise_equal(out_state.inner.params, ref_state.params)
    _tree_bitwise_equal(out_state.fed.client_params, ref_afed.client_params)
    np.testing.assert_array_equal(np.asarray(out_state.fed.version),
                                  np.asarray(ref_afed.version))
    _tree_bitwise_equal(metrics, ref_metrics)


def test_build_matches_direct_fl_baseline():
    spec = _image_spec(method="fedavg",
                       execution=api.ExecutionSpec(mode="subset"))
    program = api.build(spec)
    batches = _image_batches(jax.random.PRNGKey(3),
                             C=spec.scala.clients_per_round)
    sizes = jnp.asarray([5.0, 5.0])

    state = program.init()
    out_state, _ = program.step(state, batches, sizes)

    def fwd(p, x):
        return A.forward(p, x, spec.split)

    model = B.FedModel(forward=fwd, num_classes=10, features=None)
    w0 = A.init_params(jax.random.PRNGKey(spec.seed), num_classes=10,
                       width=spec.width)
    round_fn = jax.jit(B.make_fl_round("fedavg", model, lr=spec.scala.lr))
    rb = jax.tree.map(lambda a: jnp.swapaxes(a, 0, 1), batches)
    w_ref, _ = round_fn(w0, rb, sizes, {})

    _tree_bitwise_equal(out_state.inner, w_ref)


def test_trainer_runs_each_mode_smoke():
    # the full host loop (data synthesis + batches + eval) per mode
    for mode, part in (("subset", None), ("masked", "uniform:0.5"),
                       ("sparse", "uniform:0.5"), ("async", None)):
        spec = _image_spec(rounds=1,
                           fed=api.FedSpec(participation=part),
                           execution=api.ExecutionSpec(mode=mode, cohort=2,
                                                       unroll=0))
        trainer = api.Trainer(spec)
        history = trainer.run()
        assert len(history) == 1 and "loss_server" in history[0]
        res = trainer.evaluate()
        assert 0.0 <= res["acc"] <= 1.0 and 0.0 <= res["balanced_acc"] <= 1.0


# --------------------------------------------------------------------------
# (c) incoherent specs are rejected at spec time
# --------------------------------------------------------------------------


def test_validate_lace_dp_sparse_async_needs_shardable_aggregation():
    # the in-shard gather runs aggregation per client shard, so the
    # lace_dp sparse/async paths accept only stateless prior-free
    # shard-decomposable aggregators (and no cross-slot opt averaging);
    # a decomposable spec validates fine
    for mode in ("sparse", "async"):
        part = "uniform:0.5" if mode == "sparse" else None
        api.ExperimentSpec(
            arch="qwen1.5-0.5b", reduced=True,
            fed=api.FedSpec(participation=part),
            execution=api.ExecutionSpec(mode=mode,
                                        backend="lace_dp")).validate()
        for fed_kw, msg in (
                (dict(aggregator="bias_compensated"), "shard-decomposable"),
                (dict(opt_state_policy="average"), "average")):
            spec = api.ExperimentSpec(
                arch="qwen1.5-0.5b", reduced=True,
                fed=api.FedSpec(participation=part, **fed_kw),
                execution=api.ExecutionSpec(mode=mode, backend="lace_dp"))
            with pytest.raises(ValueError, match=msg):
                spec.validate()


def test_validate_rejects_async_with_participation():
    spec = api.ExperimentSpec(
        arch="qwen1.5-0.5b", reduced=True,
        fed=api.FedSpec(participation="uniform:0.5"),
        execution=api.ExecutionSpec(mode="async", backend="lace"))
    with pytest.raises(ValueError, match="arrival cohort IS"):
        spec.validate()


def test_validate_rejects_sparse_without_participation():
    spec = api.ExperimentSpec(arch="qwen1.5-0.5b", reduced=True,
                              execution=api.ExecutionSpec(mode="sparse",
                                                          backend="lace"))
    with pytest.raises(ValueError, match="needs a participation spec"):
        spec.validate()


def test_validate_rejects_stateful_aggregator_without_identities():
    for mode, part in (("subset", None), ("masked", None)):
        spec = _image_spec(fed=api.FedSpec(aggregator="staleness_weighted",
                                           participation=part),
                           execution=api.ExecutionSpec(mode=mode))
        with pytest.raises(ValueError, match="stable client identities"):
            spec.validate()
    spec = _image_spec(fed=api.FedSpec(aggregator="staleness_weighted"),
                       execution=api.ExecutionSpec(mode="async", cohort=2))
    with pytest.raises(ValueError, match="double-decays"):
        spec.validate()


def test_validate_rejects_incoherent_baselines():
    with pytest.raises(ValueError, match="only supports.*'subset'"):
        _image_spec(method="fedavg",
                    fed=api.FedSpec(participation="uniform:0.5"),
                    execution=api.ExecutionSpec(mode="masked")).validate()
    with pytest.raises(ValueError, match="CNN"):
        api.ExperimentSpec(arch="qwen1.5-0.5b", reduced=True,
                           method="fedavg",
                           execution=api.ExecutionSpec(mode="subset"),
                           ).validate()
    with pytest.raises(ValueError, match="not supported by the SFL"):
        _image_spec(method="splitfed_v1",
                    execution=api.ExecutionSpec(
                        mode="subset",
                        server_optimizer=api.OptimSpec.parse(
                            "fedadam:0.01"))).validate()


def test_validate_rejects_data_model_mismatch():
    with pytest.raises(ValueError, match="needs the CNN family"):
        api.ExperimentSpec(
            arch="qwen1.5-0.5b", reduced=True,
            data=api.DataSpec(kind="image_synthetic")).validate()
    with pytest.raises(ValueError, match="needs a text arch"):
        api.ExperimentSpec(arch="alexnet-cifar",
                           data=api.DataSpec(kind="lm_synthetic")).validate()
    with pytest.raises(ValueError, match="at most one"):
        _image_spec(data=api.DataSpec(kind="image_synthetic", alpha=2,
                                      beta=0.1)).validate()
    with pytest.raises(ValueError, match="only supports backend 'logits'"):
        _image_spec(execution=api.ExecutionSpec(mode="masked",
                                                backend="lace")).validate()


def test_bad_spec_strings_raise_at_construction():
    with pytest.raises(ValueError, match="unknown execution mode"):
        api.ExecutionSpec(mode="nope")
    with pytest.raises(ValueError, match="unknown backend"):
        api.ExecutionSpec(backend="nope")
    with pytest.raises(ValueError, match="unknown delay model"):
        api.ExecutionSpec(delay="nope")
    with pytest.raises(ValueError, match="unknown aggregator"):
        api.FedSpec(aggregator="nope")
    with pytest.raises(ValueError, match="takes no spec arguments"):
        api.FedSpec(aggregator="fedavg:2.0")
    with pytest.raises(ValueError, match="unknown participation"):
        api.FedSpec(participation="nope:0.5")
    with pytest.raises(ValueError, match="unknown opt_state_policy"):
        api.FedSpec(opt_state_policy="nope")
    with pytest.raises(ValueError, match="unknown optimizer"):
        api.OptimSpec(name="nope")
    with pytest.raises(ValueError, match="bad optimizer spec"):
        api.OptimSpec.parse("sgd:0.1:extra")
    with pytest.raises(ValueError, match="unknown data kind"):
        api.DataSpec(kind="nope")
    with pytest.raises(ValueError, match="unknown method"):
        _image_spec(method="nope").validate()


# --------------------------------------------------------------------------
# (d) train.py: --dump-config output replayed via --config is identical
# --------------------------------------------------------------------------


SMOKE_ARGS = ["--arch", "qwen1.5-0.5b", "--reduced", "--rounds", "2",
              "--clients", "2", "--participation", "uniform:0.5",
              "--local-iters", "1", "--seq", "16", "--server-batch", "4",
              "--docs-per-client", "4"]


def test_train_dump_config_roundtrip_reproduces_run(tmp_path, capsys):
    from repro.launch import train

    cfg_path = str(tmp_path / "spec.json")
    spec = train.main(SMOKE_ARGS + ["--dump-config", cfg_path])
    assert api.ExperimentSpec.from_json(
        open(cfg_path).read()) == spec          # dump is the resolved spec

    direct = train.main(SMOKE_ARGS)
    replayed = train.main(["--config", cfg_path])
    assert direct.spec == replayed.spec == spec
    assert direct.history == replayed.history   # identical run, per round
    assert len(direct.history) == 2


def test_train_spec_from_args_modes():
    from repro.launch import train

    ap = train.build_parser()
    spec = train.spec_from_args(ap.parse_args(SMOKE_ARGS))
    assert spec.execution.mode == "masked"
    spec = train.spec_from_args(ap.parse_args(SMOKE_ARGS + ["--slot-gather"]))
    assert spec.execution.mode == "sparse"
    spec = train.spec_from_args(ap.parse_args(
        ["--participation", "0.5", "--async", "--cohort", "2"]))
    assert spec.execution.mode == "async" and spec.fed.participation is None
    spec = train.spec_from_args(ap.parse_args(["--participation", "0.5"]))
    assert spec.execution.mode == "subset"
    assert spec.scala.participation == 0.5
    spec = train.spec_from_args(ap.parse_args(
        ["--server-optimizer", "fedadam", "--server-lr", "0.01"]))
    so = spec.execution.server_optimizer
    assert so.name == "adamw" and so.lr == 0.01


# --------------------------------------------------------------------------
# (e) legacy kwarg-style helpers warn once per process
# --------------------------------------------------------------------------


def test_train_legacy_helpers_warn_once():
    from repro.api import deprecation
    from repro.launch import train

    deprecation._WARNED.discard("repro.launch.train.build_schedule")
    with pytest.warns(DeprecationWarning, match="repro.api"):
        sched = train.build_schedule
    # same helper again: silent (once per process)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        sched2 = train.build_schedule
    assert sched is sched2

    deprecation._WARNED.discard("repro.launch.train.build_data")
    with pytest.warns(DeprecationWarning, match="build_lm_data"):
        bd = train.build_data
    cfg = api.ExperimentSpec(arch="qwen1.5-0.5b",
                             reduced=True).model_config()
    docs = bd(cfg, 2, 3, 8, seed=0)
    assert len(docs) == 2 and docs[0].shape == (3, 9)

    with pytest.raises(AttributeError):
        train.not_a_helper


# --------------------------------------------------------------------------
# (f) image rounds gathered on the device from the resident pool
# --------------------------------------------------------------------------


def _ragged_data(seed, sizes=(7, 30, 3, 16)):
    """Unequal client sizes, so eq. (3) pads the smaller clients' rows."""
    from repro.data.loader import FederatedData

    rng = np.random.default_rng(seed)
    xs = [rng.normal(size=(n, 32, 32, 3)).astype(np.float32) for n in sizes]
    ys = [rng.integers(0, 10, size=n) for n in sizes]
    return FederatedData(xs=xs, ys=ys)


def _pool_spec(mode, rpc=1):
    part = "uniform:0.5" if mode in ("masked", "sparse") else None
    return _image_spec(width=0.125, fed=api.FedSpec(participation=part),
                       execution=api.ExecutionSpec(mode=mode, unroll=0,
                                                   rounds_per_call=rpc))


def _host_round(tr, rng):
    """One round as the host assembled it before the pool: the trainer's
    client draw, then ``round_batches``, on ``rng``."""
    from repro.data.loader import round_batches, sample_clients

    spec, sc = tr.spec, tr.spec.scala
    selected = (np.arange(sc.num_clients) if spec.execution.in_program
                else sample_clients(sc.num_clients, sc.clients_per_round,
                                    rng))
    budget = (round(sc.server_batch / sc.participation)
              if spec.execution.mode in ("masked", "sparse")
              else sc.server_batch)
    return round_batches(tr._data, selected, budget, sc.local_iters, rng)


def _assert_bitwise(got, want):
    got = np.asarray(got)
    assert (got.dtype, got.shape) == (want.dtype, want.shape)
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("mode,rpc", [("masked", 1), ("sparse", 1),
                                      ("subset", 1), ("sparse", 2)])
def test_device_gather_is_round_batches_bitwise(mode, rpc):
    tr = api.Trainer(_pool_spec(mode, rpc))
    tr._data = _ragged_data(1)
    for _ in range(2):
        rng = copy.deepcopy(tr._rng)
        want = [_host_round(tr, rng) for _ in range(rpc)]
        if rpc > 1:
            want = {k: np.stack([w[k] for w in want]) for k in want[0]}
        else:
            (want,) = want
        batches, sizes = tr._next_round_batches(rpc if rpc > 1 else None)
        assert (want["weights"] == 0).any()       # padding rows present
        assert set(batches) == {"x", "labels", "weights"}
        for k, v in batches.items():
            _assert_bitwise(v, want[k])
        _assert_bitwise(sizes, want["sizes"])
        assert tr._rng.bit_generator.state == rng.bit_generator.state


def test_replaced_data_rebuilds_the_pool_and_counts_uploads():
    from repro.data.loader import pool_arrays

    tr = api.Trainer(_pool_spec("sparse"))
    assert (tr.upload_bytes, tr.resident_bytes, tr.gathered_rounds) == (
        0, 0, 0)
    tr.step()
    first_pool = sum(a.nbytes for a in pool_arrays(tr._data))
    assert tr.resident_bytes == first_pool
    per_round = tr.upload_bytes - first_pool
    # rows (T, K, Bk) int32 and sizes (K,) float32
    assert 0 < per_round < first_pool / 100

    tr._data = _ragged_data(2)            # as bench/harness.Job does
    rng = copy.deepcopy(tr._rng)
    want = _host_round(tr, rng)
    batches, sizes = tr._next_round_batches()
    for k, v in batches.items():
        _assert_bitwise(v, want[k])
    new_pool = sum(a.nbytes for a in pool_arrays(tr._data))
    assert tr.resident_bytes == new_pool
    round_bytes = (want["labels"].size * 4) + want["sizes"].nbytes
    assert tr.upload_bytes == (first_pool + per_round + new_pool
                               + round_bytes)
    tr.step()
    tr.step()
    assert tr.resident_bytes == new_pool     # the same data: no rebuild
    assert tr.upload_bytes == first_pool + per_round + new_pool + 3 * round_bytes
    assert tr.round == 3 and tr.gathered_rounds == 4


def test_lm_rounds_gather_nothing():
    spec = api.ExperimentSpec(
        arch="qwen1.5-0.5b", reduced=True, rounds=2, seed=0,
        scala=ScalaConfig(num_clients=2, participation=0.5, local_iters=1,
                          server_batch=2),
        fed=api.FedSpec(participation="uniform:0.5"),
        execution=api.ExecutionSpec(mode="masked"),
        data=api.DataSpec(kind="lm_synthetic", seq=8, docs_per_client=2))
    tr = api.Trainer(spec)
    tr.run()
    assert tr.round == 2
    assert (tr.gathered_rounds, tr.resident_bytes) == (0, 0)
    assert tr._pool is None
