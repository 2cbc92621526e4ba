"""Federation-layer tests: aggregators, participation scheduling, the
round runner's fed composition, per-subset prior recomputation, and the
opt-state round-boundary policies.

The acceptance bar for the refactor: (a) the default round runner (no
fed args) stays allclose-identical to the legacy Python-loop round;
(b) a masked round (participation=uniform(0.5)) with the
bias-compensated aggregator runs jitted end-to-end on every backend
(the "lace_dp" leg lives in the slow subprocess test at the bottom).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import tiny_cfg
from repro import fed, optim
from repro.configs import ScalaConfig
from repro.core import engine
from repro.core.label_stats import client_and_concat_priors
from repro.core.scala import (alexnet_split_model, scala_round,
                              transformer_split_model)
from repro.core.split import fedavg as split_fedavg
from repro.core.split import normalize_client_weights
from repro.models import alexnet as A
from repro.models import transformer as T


def _tree_allclose(a, b, atol=2e-5, rtol=1e-4):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(x, y, atol=atol, rtol=rtol)


def _setup_alexnet(key, C=4, Bk=6, num_classes=10):
    model = alexnet_split_model("s2", num_classes=num_classes)
    full = A.init_params(key, num_classes=num_classes, width=0.125)
    wc, ws = A.split_params(full, "s2")
    params = {"client": jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (C,) + a.shape), wc),
        "server": ws}
    kx, ky = jax.random.split(jax.random.fold_in(key, 1))
    batch = {"x": jax.random.normal(kx, (C, Bk, 32, 32, 3)),
             "labels": jax.random.randint(ky, (C, Bk), 0, num_classes),
             "weights": jnp.ones((C, Bk), jnp.float32)}
    return model, params, batch


def _alexnet_round_batches(key, T_steps=3, C=4, Bk=6, num_classes=10):
    kx, ky = jax.random.split(key)
    return {"x": jax.random.normal(kx, (T_steps, C, Bk, 32, 32, 3)),
            "labels": jax.random.randint(ky, (T_steps, C, Bk), 0,
                                         num_classes),
            "weights": jnp.ones((T_steps, C, Bk), jnp.float32)}


# --------------------------------------------------------------------------
# mask-safe normalization (core/split) — the scala_aggregate NaN fix
# --------------------------------------------------------------------------


def test_normalize_weights_zero_participation_clients():
    w = normalize_client_weights(jnp.array([3.0, 0.0, 1.0, 0.0]))
    np.testing.assert_allclose(w, [0.75, 0.0, 0.25, 0.0], rtol=1e-6)
    assert np.isfinite(np.asarray(w)).all()


def test_normalize_weights_all_zero_falls_back_uniform():
    w = normalize_client_weights(jnp.zeros((4,)))
    np.testing.assert_allclose(w, [0.25] * 4, rtol=1e-6)

    # masked: fall back to uniform over the participating subset
    w = normalize_client_weights(jnp.zeros((4,)),
                                 mask=jnp.array([1.0, 0.0, 1.0, 0.0]))
    np.testing.assert_allclose(w, [0.5, 0.0, 0.5, 0.0], rtol=1e-6)

    # mask AND weights all zero: still finite (uniform over everyone)
    w = normalize_client_weights(jnp.zeros((4,)), mask=jnp.zeros((4,)))
    assert np.isfinite(np.asarray(w)).all()
    np.testing.assert_allclose(np.asarray(w).sum(), 1.0, rtol=1e-6)


def test_scala_aggregate_zero_sizes_no_nans():
    _, params, _ = _setup_alexnet(jax.random.PRNGKey(0), C=3)
    # distinct per-slot params so averaging is observable
    params = {"client": jax.tree.map(
        lambda a: a * jnp.arange(1.0, 4.0).reshape(
            (3,) + (1,) * (a.ndim - 1)), params["client"]),
        "server": params["server"]}

    agg = engine.scala_aggregate(params, jnp.array([2.0, 0.0, 1.0]))
    for leaf in jax.tree.leaves(agg["client"]):
        assert np.isfinite(np.asarray(leaf)).all()
    # zero-participation client excluded from the average
    want = jax.tree.map(
        lambda a: (2.0 * a[0] + 1.0 * a[2]) / 3.0, params["client"])
    _tree_allclose(jax.tree.map(lambda a: a[0], agg["client"]), want)

    # all-zero sizes: uniform mean, never NaN / all-zero params
    agg0 = engine.scala_aggregate(params, jnp.zeros((3,)))
    want0 = jax.tree.map(lambda a: a.mean(axis=0), params["client"])
    _tree_allclose(jax.tree.map(lambda a: a[0], agg0["client"]), want0)


# --------------------------------------------------------------------------
# participation schedulers
# --------------------------------------------------------------------------


def test_full_scheduler_is_all_ones_and_stateless():
    part = fed.full(5)
    assert not part.stateful
    mask, state = part.sample(part.init(jax.random.PRNGKey(0)))
    np.testing.assert_array_equal(np.asarray(mask), np.ones(5))


@pytest.mark.parametrize("frac,m", [(0.5, 4), (0.25, 2), (0.01, 1)])
def test_uniform_scheduler_subset_size(frac, m):
    part = fed.uniform(8, frac)
    state = part.init(jax.random.PRNGKey(0))
    masks = []
    for _ in range(6):
        mask, state = part.sample(state)
        assert float(mask.sum()) == m
        assert set(np.unique(np.asarray(mask))) <= {0.0, 1.0}
        masks.append(np.asarray(mask))
    # the subset varies round to round (w.h.p. for these sizes)
    assert any(not np.array_equal(masks[0], mk) for mk in masks[1:])


def test_uniform_scheduler_deterministic_given_key():
    part = fed.uniform(8, 0.5)
    m1, _ = part.sample(part.init(jax.random.PRNGKey(3)))
    m2, _ = part.sample(part.init(jax.random.PRNGKey(3)))
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))


def test_dirichlet_scheduler_subset_size_and_jit():
    part = fed.dirichlet(10, 0.3, alpha=0.2)
    state = part.init(jax.random.PRNGKey(1))
    sample = jax.jit(part.sample)
    for _ in range(4):
        mask, state = sample(state)
        assert float(mask.sum()) == 3
        assert set(np.unique(np.asarray(mask))) <= {0.0, 1.0}


def test_make_participation_specs():
    assert fed.make_participation("full", 8).name == "full"
    p = fed.make_participation("uniform:0.25", 8)
    assert p.name == "uniform" and p.num_clients == 8
    p = fed.make_participation("dirichlet:0.5:1.0", 8)
    assert p.name == "dirichlet"
    with pytest.raises(ValueError, match="unknown participation"):
        fed.make_participation("nope", 8)
    with pytest.raises(ValueError, match="uniform spec"):
        fed.make_participation("uniform", 8)


# --------------------------------------------------------------------------
# aggregators
# --------------------------------------------------------------------------


def test_weighted_aggregator_matches_split_fedavg():
    key = jax.random.PRNGKey(2)
    stacked = {"w": jax.random.normal(key, (4, 3, 2))}
    sizes = jnp.array([5.0, 1.0, 2.0, 2.0])
    agg = fed.weighted()
    ctx = fed.AggContext(num_clients=4, data_sizes=sizes)
    avg, _ = agg.aggregate(stacked, ctx)
    _tree_allclose(avg, split_fedavg(stacked, sizes), atol=1e-7)


def test_fedavg_aggregator_uniform_over_subset():
    stacked = {"w": jnp.arange(4.0).reshape(4, 1)}
    mask = jnp.array([1.0, 0.0, 1.0, 0.0])
    agg = fed.fedavg()
    avg, _ = agg.aggregate(stacked,
                           fed.AggContext(num_clients=4, mask=mask,
                                          data_sizes=jnp.array(
                                              [9.0, 9.0, 9.0, 9.0])))
    # uniform over participants {0, 2}, data sizes ignored
    np.testing.assert_allclose(np.asarray(avg["w"]), [1.0], rtol=1e-6)


def test_bias_compensated_downweights_skewed_client():
    # client 0's round labels match the global prior; client 1's don't
    p_k = jnp.array([[0.5, 0.5], [1.0, 0.0]])
    p_global = jnp.array([0.5, 0.5])
    agg = fed.bias_compensated(gamma=2.0)
    assert agg.needs_priors
    w, _ = agg.client_weights(
        fed.AggContext(num_clients=2, p_k=p_k, p_global=p_global), ())
    w = np.asarray(w)
    assert w[0] > w[1] > 0
    np.testing.assert_allclose(w.sum(), 1.0, rtol=1e-6)
    # gamma=0 recovers the data-size weighting
    w0, _ = fed.bias_compensated(gamma=0.0).client_weights(
        fed.AggContext(num_clients=2, p_k=p_k, p_global=p_global,
                       data_sizes=jnp.array([1.0, 3.0])), ())
    np.testing.assert_allclose(np.asarray(w0), [0.25, 0.75], rtol=1e-6)


def test_staleness_weighted_ages_and_decay():
    agg = fed.staleness_weighted(decay=0.5)
    assert agg.stateful
    state = agg.init(3)
    np.testing.assert_array_equal(np.asarray(state["age"]), np.zeros(3))

    # round 1: only client 0 participates -> ages [0, 1, 1]
    w, state = agg.client_weights(
        fed.AggContext(num_clients=3, mask=jnp.array([1.0, 0.0, 0.0])),
        state)
    np.testing.assert_allclose(np.asarray(w), [1.0, 0.0, 0.0], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(state["age"]), [0.0, 1.0, 1.0])

    # round 2: absent again -> ages grow
    _, state = agg.client_weights(
        fed.AggContext(num_clients=3, mask=jnp.array([1.0, 0.0, 0.0])),
        state)
    np.testing.assert_array_equal(np.asarray(state["age"]), [0.0, 2.0, 2.0])

    # round 3: everyone returns; clients 1/2 decayed by 0.5^2
    w, state = agg.client_weights(
        fed.AggContext(num_clients=3, mask=jnp.ones(3)), state)
    np.testing.assert_allclose(np.asarray(w),
                               np.array([1.0, 0.25, 0.25]) / 1.5, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(state["age"]), np.zeros(3))


def test_make_aggregator_registry():
    for name in fed.AGGREGATORS:
        # hierarchical has no bare form — the edge count is mandatory
        spec = "hierarchical:2" if name == "hierarchical" else name
        assert fed.make_aggregator(spec).name == name
    with pytest.raises(ValueError, match="unknown aggregator"):
        fed.make_aggregator("nope")


# --------------------------------------------------------------------------
# per-subset prior recomputation (the paper's partial-participation core)
# --------------------------------------------------------------------------


def test_masked_priors_equal_subset_priors():
    key = jax.random.PRNGKey(4)
    C, Bk, N = 5, 16, 7
    labels = jax.random.randint(key, (C, Bk), 0, N)
    weights = jnp.ones((C, Bk), jnp.float32)
    mask = jnp.array([1.0, 0.0, 1.0, 1.0, 0.0])
    sub = jnp.array([0, 2, 3])

    # priors with mask folded into the weights (what the engine does)
    p_k_m, p_s_m = client_and_concat_priors(labels, N,
                                            weights * mask[:, None])
    # priors computed on ONLY the participating clients' labels
    p_k_s, p_s_s = client_and_concat_priors(labels[sub], N, weights[sub])

    np.testing.assert_allclose(np.asarray(p_s_m), np.asarray(p_s_s),
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(p_k_m[sub]), np.asarray(p_k_s),
                               atol=1e-7)
    # masked-out clients degrade to the uniform prior (zero histogram)
    np.testing.assert_allclose(np.asarray(p_k_m[1]), np.full(N, 1.0 / N),
                               atol=1e-7)


def test_masked_step_equals_substacked_step():
    """split_step_grads with a mask == the step on the physically
    re-stacked participating subset: losses, server grads, and the
    participants' client grads; absentees get exactly zero grads."""
    model, params, batch = _setup_alexnet(jax.random.PRNGKey(5))
    sc = ScalaConfig(lr=0.05)
    mask = jnp.array([1.0, 0.0, 1.0, 0.0])
    sub = jnp.array([0, 2])

    g_m, m_m = engine.split_step_grads(model, params, batch, sc,
                                       backend="logits", mask=mask)
    params_s = {"client": jax.tree.map(lambda a: a[sub], params["client"]),
                "server": params["server"]}
    batch_s = jax.tree.map(lambda a: a[sub], batch)
    g_s, m_s = engine.split_step_grads(model, params_s, batch_s, sc,
                                       backend="logits")

    np.testing.assert_allclose(m_m["loss_server"], m_s["loss_server"],
                               rtol=1e-6)
    np.testing.assert_allclose(m_m["loss_client"], m_s["loss_client"],
                               rtol=1e-6)
    _tree_allclose(g_m["server"], g_s["server"], atol=1e-6)
    _tree_allclose(jax.tree.map(lambda a: a[sub], g_m["client"]),
                   g_s["client"], atol=1e-6)
    for leaf in jax.tree.leaves(g_m["client"]):
        np.testing.assert_array_equal(
            np.asarray(leaf[jnp.array([1, 3])]), 0.0)


# --------------------------------------------------------------------------
# round runner on the fed layer
# --------------------------------------------------------------------------


def test_default_runner_matches_legacy_python_round():
    """Acceptance: fedavg weights + full participation == pre-refactor
    make_round_runner == legacy Python-loop round (allclose, fp32)."""
    key = jax.random.PRNGKey(6)
    model, params, _ = _setup_alexnet(key)
    sc = ScalaConfig(lr=0.05)
    rb = _alexnet_round_batches(jax.random.fold_in(key, 1))
    sizes = jnp.array([3.0, 1.0, 2.0, 4.0])

    p_ref, m_ref = scala_round(model, params, rb, sc, sizes)
    state0 = engine.init_train_state(params, optim.sgd())

    # default fed path (aggregator=None -> weighted, no scheduler)
    st_def, m_def = jax.jit(engine.make_round_runner(
        model, sc, backend="logits"))(state0, rb, sizes)
    _tree_allclose(st_def.params, p_ref)
    np.testing.assert_allclose(m_def["loss_server"], m_ref["loss_server"],
                               rtol=1e-5)

    # explicit full-participation scheduler + weighted aggregator
    agg, part = fed.weighted(), fed.full(4)
    runner = jax.jit(engine.make_round_runner(
        model, sc, backend="logits", aggregator=agg, participation=part))
    fs = fed.init_fed_state(jax.random.PRNGKey(0), agg, part)
    st_exp, _, m_exp = runner(state0, rb, sizes, fs)
    _tree_allclose(st_exp.params, st_def.params, atol=1e-6)

    # fedavg == weighted when the sizes are uniform
    agg_f = fed.fedavg()
    runner_f = jax.jit(engine.make_round_runner(
        model, sc, backend="logits", aggregator=agg_f, participation=part))
    fs_f = fed.init_fed_state(jax.random.PRNGKey(0), agg_f, part)
    st_f, _, _ = runner_f(state0, rb, jnp.ones((4,)), fs_f)
    st_u, _ = jax.jit(engine.make_round_runner(
        model, sc, backend="logits"))(state0, rb, None)
    _tree_allclose(st_f.params, st_u.params, atol=1e-6)


def test_masked_round_jitted_logits_backend():
    """Acceptance: participation=uniform(0.5) + bias_compensated runs
    jitted end-to-end (logits backend) and changes only via the subset."""
    key = jax.random.PRNGKey(7)
    model, params, _ = _setup_alexnet(key)
    sc = ScalaConfig(lr=0.05)
    rb = _alexnet_round_batches(jax.random.fold_in(key, 1))
    sizes = jnp.array([3.0, 1.0, 2.0, 4.0])

    agg, part = fed.bias_compensated(), fed.uniform(4, 0.5)
    runner = jax.jit(engine.make_round_runner(
        model, sc, backend="logits", aggregator=agg, participation=part))
    state = engine.init_train_state(params, optim.sgd())
    fs = fed.init_fed_state(jax.random.PRNGKey(1), agg, part)
    for _ in range(2):
        state, fs, metrics = runner(state, rb, sizes, fs)
    assert np.isfinite(float(metrics["loss_server"]))
    assert np.isfinite(float(metrics["loss_client"]))
    for leaf in jax.tree.leaves(state.params):
        assert np.isfinite(np.asarray(leaf)).all()
    # FL phase applied: all slots re-unified
    c0 = jax.tree.leaves(state.params["client"])[0]
    np.testing.assert_allclose(np.asarray(c0[0]), np.asarray(c0[1]))


def test_masked_round_jitted_lace_backend():
    """Acceptance: the same masked round on the fused-LACE backend."""
    cfg = tiny_cfg()
    model = transformer_split_model(cfg)
    C, Bk, S, T_steps = 4, 2, 8, 2
    params = engine.init_scala_params(
        jax.random.PRNGKey(8),
        lambda k: T.init_params(k, cfg)["client"],
        lambda k: T.init_params(k, cfg)["server"], C)
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    rb = {"tokens": jax.random.randint(ks[0], (T_steps, C, Bk, S), 0,
                                       cfg.vocab_size),
          "labels": jax.random.randint(ks[1], (T_steps, C, Bk, S), 0,
                                       cfg.vocab_size),
          "weights": jnp.ones((T_steps, C, Bk, S), jnp.float32)}
    sc = ScalaConfig(lr=0.05)

    agg, part = fed.bias_compensated(), fed.uniform(C, 0.5)
    runner = jax.jit(engine.make_round_runner(
        model, sc, backend="lace", ce_chunk=8, aggregator=agg,
        participation=part))
    state = engine.init_train_state(params, optim.sgd())
    fs = fed.init_fed_state(jax.random.PRNGKey(2), agg, part)
    state, fs, metrics = runner(state, rb, None, fs)
    assert int(state.step) == T_steps
    assert np.isfinite(float(metrics["loss_server"]))
    for leaf in jax.tree.leaves(state.params):
        assert np.isfinite(np.asarray(leaf)).all()


def test_stateful_runner_requires_fed_state():
    model, params, _ = _setup_alexnet(jax.random.PRNGKey(10))
    sc = ScalaConfig(lr=0.05)
    rb = _alexnet_round_batches(jax.random.PRNGKey(11))
    runner = engine.make_round_runner(
        model, sc, backend="logits", participation=fed.uniform(4, 0.5))
    state = engine.init_train_state(params, optim.sgd())
    with pytest.raises(ValueError, match="fed_state"):
        runner(state, rb, None)
    with pytest.raises(ValueError, match="opt_state_policy"):
        engine.make_round_runner(model, sc, opt_state_policy="nope")


# --------------------------------------------------------------------------
# opt-state round-boundary policies
# --------------------------------------------------------------------------


def _run_policy_round(policy, key=jax.random.PRNGKey(12)):
    model, params, _ = _setup_alexnet(key)
    sc = ScalaConfig(lr=0.05)
    rb = _alexnet_round_batches(jax.random.fold_in(key, 1))
    sizes = jnp.array([3.0, 1.0, 2.0, 4.0])
    opt = optim.momentum(beta=0.9)
    runner = jax.jit(engine.make_round_runner(
        model, sc, backend="logits", optimizer=opt,
        opt_state_policy=policy))
    state, _ = runner(engine.init_train_state(params, opt), rb, sizes)
    return state


def test_opt_state_policy_carry_keeps_per_slot_momentum():
    state = _run_policy_round("carry")
    leaves = jax.tree.leaves(state.opt_state["client"])
    assert any(float(jnp.abs(l).max()) > 0 for l in leaves)
    # per-slot moments differ (each client saw different data)
    l0 = leaves[0]
    assert float(jnp.abs(l0[0] - l0[1]).max()) > 0


def test_opt_state_policy_reset_zeroes_client_momentum():
    state = _run_policy_round("reset")
    for leaf in jax.tree.leaves(state.opt_state["client"]):
        np.testing.assert_array_equal(np.asarray(leaf), 0.0)
    # the server half always carries
    assert any(float(jnp.abs(l).max()) > 0
               for l in jax.tree.leaves(state.opt_state["server"]))


def test_opt_state_policy_average_redistributes_momentum():
    carry = _run_policy_round("carry")
    avg = _run_policy_round("average")
    w = normalize_client_weights(jnp.array([3.0, 1.0, 2.0, 4.0]))
    for lc, la in zip(jax.tree.leaves(carry.opt_state["client"]),
                      jax.tree.leaves(avg.opt_state["client"])):
        # every slot holds the weighted mean of the carried moments
        wb = np.asarray(w).reshape((-1,) + (1,) * (lc.ndim - 1))
        want = (np.asarray(lc, np.float32) * wb).sum(axis=0)
        for c in range(la.shape[0]):
            np.testing.assert_allclose(np.asarray(la[c]), want,
                                       atol=1e-6, rtol=1e-5)
    # params are unaffected by the opt-state policy
    _tree_allclose(carry.params, avg.params, atol=0, rtol=0)


# --------------------------------------------------------------------------
# baselines on the fed layer
# --------------------------------------------------------------------------


def test_fl_round_accepts_fed_aggregator():
    from repro.core import baselines as B

    num_classes = 6
    model = B.FedModel(
        forward=lambda p, x: x.reshape(x.shape[0], -1) @ p["w"],
        num_classes=num_classes)
    key = jax.random.PRNGKey(13)
    w = {"w": jax.random.normal(key, (12, num_classes)) * 0.1}
    C, T_steps, Bk = 3, 2, 4
    rbs = {"x": jax.random.normal(jax.random.fold_in(key, 1),
                                  (C, T_steps, Bk, 12)),
           "labels": jax.random.randint(jax.random.fold_in(key, 2),
                                        (C, T_steps, Bk), 0, num_classes)}
    sizes = jnp.array([2.0, 1.0, 1.0])
    round_fn = B.make_fl_round("fedavg", model, lr=0.1,
                               aggregator=fed.bias_compensated())
    w2, _ = round_fn(w, rbs, sizes, {})
    for leaf in jax.tree.leaves(w2):
        assert np.isfinite(np.asarray(leaf)).all()


def test_baseline_aggregation_priors_exclude_padded_rows():
    """Zero-weight padding rows (loader pads every client to bk_max)
    must not count as class-0 samples in the aggregation priors."""
    from repro.core.baselines import _aggregation_priors

    labels = jnp.array([[[2, 2, 0, 0]], [[1, 1, 1, 1]]])   # (C=2, T=1, Bk=4)
    weights = jnp.array([[[1.0, 1.0, 0.0, 0.0]],           # client 0 padded
                         [[1.0, 1.0, 1.0, 1.0]]])
    p_k, p_global = _aggregation_priors(3, {"labels": labels,
                                            "weights": weights})
    np.testing.assert_allclose(np.asarray(p_k[0]), [0.0, 0.0, 1.0],
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(p_global),
                               [0.0, 4.0 / 6.0, 2.0 / 6.0], atol=1e-7)
    # without weights the padding would leak in as class 0
    p_k_u, _ = _aggregation_priors(3, {"labels": labels})
    assert float(p_k_u[0, 0]) > 0


def test_sfl_round_accepts_fed_aggregator():
    from repro.core import baselines as B

    model, params, _ = _setup_alexnet(jax.random.PRNGKey(14), C=3)
    key = jax.random.PRNGKey(15)
    C, T_steps, Bk = 3, 2, 4
    rbs = {"x": jax.random.normal(key, (C, T_steps, Bk, 32, 32, 3)),
           "labels": jax.random.randint(jax.random.fold_in(key, 1),
                                        (C, T_steps, Bk), 0, 10)}
    state = {"wc": params["client"], "ws": params["server"]}
    round_fn = B.make_sfl_round("splitfed_v1", model, lr=0.05,
                                aggregator=fed.bias_compensated())
    out = round_fn(state, rbs, jnp.array([2.0, 1.0, 1.0]))
    for leaf in jax.tree.leaves(out):
        assert np.isfinite(np.asarray(leaf)).all()


# --------------------------------------------------------------------------
# "lace_dp" backend: the shard_map step inside the scanned round
# --------------------------------------------------------------------------

_DP_ROUND_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "src")
import json
import jax
import jax.numpy as jnp
import numpy as np

from repro import fed, optim
from repro.configs import ScalaConfig, get_config
from repro.configs.base import InputShape
from repro.core import engine
from repro.core.scala import transformer_split_model
from repro.launch import input_specs as ispec
from repro.models import transformer as T
from repro.sharding.logical import RULES_DP, tree_specs

cfg = get_config("qwen1.5-0.5b").reduced()
C, BK, S, TS = 2, 2, 16, 3
model = transformer_split_model(cfg)
key = jax.random.PRNGKey(0)
full = T.init_params(key, cfg)
params = {
    "client": jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (C,) + a.shape), full["client"]),
    "server": full["server"],
}
tokens = jax.random.randint(jax.random.PRNGKey(1), (TS, C, BK, S), 0,
                            cfg.vocab_size)
rb = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=-1),
      "weights": jnp.ones((TS, C, BK, S), jnp.float32)}
sizes = jnp.asarray([2.0, 1.0])
sc = ScalaConfig(num_clients=C, participation=1.0, lr=0.05,
                 grad_reduce_dtype=None)
st0 = engine.init_train_state(params, optim.sgd())

# reference: the single-program lace backend, same scanned round
r_lace = jax.jit(engine.make_round_runner(model, sc, backend="lace",
                                          ce_chunk=8))
st_l, m_l = r_lace(st0, rb, sizes)

mesh = jax.make_mesh((2, 2), ("data", "model"))
shape = InputShape(name="t", seq_len=S, global_batch=C * BK, mode="train")
b_sh, b_ax = ispec.train_batch_specs(cfg, shape, C)
b_specs = tree_specs(b_ax, b_sh, mesh, RULES_DP)

# (a) dp shard_map step inside the scan == lace scanned round
r_dp = jax.jit(engine.make_round_runner(model, sc, backend="lace_dp",
                                        ce_chunk=8, mesh=mesh,
                                        batch_specs=b_specs))
st_d, m_d = r_dp(st0, rb, sizes)
err = {}
err["params"] = max(
    float(jnp.max(jnp.abs(a - b)) / (1e-8 + float(jnp.max(jnp.abs(a)))))
    for a, b in zip(jax.tree.leaves(st_l.params), jax.tree.leaves(st_d.params)))
err["loss"] = abs(float(m_l["loss_server"]) - float(m_d["loss_server"]))

# (b) masked dp round: uniform(0.5) + bias_compensated, jitted end-to-end
agg, part = fed.bias_compensated(), fed.uniform(C, 0.5)
r_m = jax.jit(engine.make_round_runner(model, sc, backend="lace_dp",
                                       ce_chunk=8, mesh=mesh,
                                       batch_specs=b_specs, aggregator=agg,
                                       participation=part))
fs = fed.init_fed_state(jax.random.PRNGKey(5), agg, part)
st_m, fs2, m_m = r_m(st0, rb, sizes, fs)
err["masked_finite"] = int(all(
    bool(jnp.isfinite(l).all()) for l in jax.tree.leaves(st_m.params))
    and bool(jnp.isfinite(jnp.asarray(m_m["loss_server"]))))
# the client half comes back split over the data axis: read it on host
leaf = jax.tree.leaves(st_m.params["client"])[0]
err["masked_client_sharded"] = int(
    leaf.sharding.spec[0] == "data"
    and {s.data.shape[0] for s in leaf.addressable_shards} == {1})
leaf_m = np.asarray(leaf)
err["masked_slots_unified"] = int(bool(np.allclose(leaf_m[0], leaf_m[1])))
print("RESULT " + json.dumps(err))
"""


@pytest.mark.slow
def test_dp_backend_round_scan_matches_lace_and_runs_masked():
    """Satellite: the lace_dp shard_map step wrapped inside
    make_round_runner's scan matches the lace scanned round; acceptance:
    the masked bias-compensated round runs jitted on lace_dp too."""
    import json as _json
    import os as _os
    import subprocess
    import sys as _sys

    env = dict(_os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([_sys.executable, "-c", _DP_ROUND_SCRIPT],
                         capture_output=True, text=True, env=env,
                         cwd=_os.path.dirname(_os.path.dirname(
                             _os.path.abspath(__file__))), timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, out.stdout[-2000:]
    err = _json.loads(line[0][len("RESULT "):])
    assert err["params"] < 5e-4, err
    assert err["loss"] < 1e-5, err
    assert err["masked_finite"] == 1, err
    assert err["masked_slots_unified"] == 1, err
    assert err["masked_client_sharded"] == 1, err


# --------------------------------------------------------------------------
# hierarchical (edge -> server) aggregation
# --------------------------------------------------------------------------


def test_hierarchical_weighted_tiers_equal_flat_weighted():
    """edge='weighted', top='weighted' is exactly flat data-size
    weighting: w_k = (n_k/S_e) * (S_e/tot) = n_k/tot."""
    mask = jnp.array([1, 0, 1, 1, 0, 1, 1, 1], jnp.float32)
    sizes = jnp.arange(1.0, 9.0)
    ctx = fed.AggContext(num_clients=8, mask=mask, data_sizes=sizes)
    for edges in (1, 2, 4, 8):
        w_h, _ = fed.hierarchical(edges).client_weights(ctx, ())
        w_f, _ = fed.weighted().client_weights(ctx, ())
        np.testing.assert_allclose(np.asarray(w_h), np.asarray(w_f),
                                   atol=1e-6)


def test_hierarchical_top_fedavg_equalizes_regions():
    """top='fedavg' gives every non-empty edge equal say regardless of
    data mass; an empty edge gets exactly zero."""
    mask = jnp.array([1, 1, 1, 1, 0, 0, 1, 1], jnp.float32)
    sizes = jnp.array([100.0, 100.0, 1.0, 1.0, 50.0, 50.0, 1.0, 1.0])
    ctx = fed.AggContext(num_clients=8, mask=mask, data_sizes=sizes)
    w, _ = fed.hierarchical(4, top="fedavg").client_weights(ctx, ())
    w = np.asarray(w)
    # 3 non-empty edges at 1/3 each; edge 2 (slots 4-5) empty
    np.testing.assert_allclose(w.reshape(4, 2).sum(axis=1),
                               [1 / 3, 1 / 3, 0.0, 1 / 3], atol=1e-6)
    # within edge 0 the data-size split still applies
    np.testing.assert_allclose(w[0] / w[1], 1.0, atol=1e-6)
    np.testing.assert_allclose(w.sum(), 1.0, atol=1e-6)


def test_hierarchical_all_empty_falls_back_flat():
    ctx = fed.AggContext(num_clients=4, mask=jnp.zeros((4,)),
                         data_sizes=jnp.ones((4,)))
    w, _ = fed.hierarchical(2).client_weights(ctx, ())
    assert np.isfinite(np.asarray(w)).all()
    np.testing.assert_allclose(np.asarray(w).sum(), 1.0, atol=1e-6)


def test_hierarchical_spec_and_validation():
    agg = fed.make_aggregator("hierarchical:4")
    assert agg.name == "hierarchical" and agg.shard_local is not None
    assert fed.make_aggregator("hierarchical:2:fedavg:fedavg").name \
        == "hierarchical"
    with pytest.raises(ValueError, match="tiers"):
        fed.hierarchical(2, edge="nope")
    with pytest.raises(ValueError, match="edges"):
        fed.hierarchical(0)
    with pytest.raises(ValueError, match="divide"):
        fed.hierarchical(3).client_weights(
            fed.AggContext(num_clients=4, mask=jnp.ones((4,))), ())
    with pytest.raises(ValueError):
        fed.make_aggregator("hierarchical")


@pytest.mark.parametrize("spec", ["fedavg", "weighted", "hierarchical:4"])
def test_shard_local_decomposition_matches_flat_weights(spec):
    """The shard_local contract: concatenating each shard's local raw
    weights, masking, and renormalizing globally reproduces the flat
    client_weights — for every shard count the slots divide over."""
    agg = fed.make_aggregator(spec)
    C = 8
    mask = jnp.array([1, 0, 1, 1, 0, 1, 1, 1], jnp.float32)
    sizes = jnp.arange(2.0, 10.0)
    w_flat, _ = agg.client_weights(
        fed.AggContext(num_clients=C, mask=mask, data_sizes=sizes), ())
    for n_shards in (1, 2, 4):
        # vmap with an axis name stands in for the sharded client axis:
        # the psum inside shard_local reduces over the shard blocks
        # exactly as it would inside the real shard_map
        blocks = jax.vmap(
            lambda m, s: agg.shard_local(m, s, ("c",), n_shards),
            axis_name="c")(mask.reshape(n_shards, -1),
                           sizes.reshape(n_shards, -1))
        raw = blocks.reshape(-1) * mask
        w = raw / raw.sum()
        np.testing.assert_allclose(np.asarray(w), np.asarray(w_flat),
                                   atol=1e-6)
    with pytest.raises(ValueError, match="divide"):
        fed.hierarchical(2).shard_local(mask[:2], sizes[:2], (), n_shards=4)


def test_shard_local_absent_on_stateful_and_prior_aggregators():
    assert fed.bias_compensated().shard_local is None
    assert fed.staleness_weighted().shard_local is None


# --------------------------------------------------------------------------
# shards-balanced uniform participation
# --------------------------------------------------------------------------


def test_uniform_shards_balanced_blocks():
    part = fed.make_participation("uniform:0.5:4", 16)
    assert part.shards == 4 and part.subset_size == 8
    state = part.init(jax.random.PRNGKey(0))
    for _ in range(5):
        mask, state = part.sample(state)
        blocks = np.asarray(mask).reshape(4, 4)
        # every contiguous block contributes exactly m/shards clients
        np.testing.assert_array_equal(blocks.sum(axis=1), np.full(4, 2))
    # subset size rounds UP to a shard multiple
    p2 = fed.uniform(16, 0.3, shards=4)   # 4.8 -> 8? no: ceil to mult of 4
    assert p2.subset_size % 4 == 0
    assert fed.make_participation("uniform:0.25", 8).shards == 1
    with pytest.raises(ValueError, match="shards"):
        fed.uniform(6, 0.5, shards=4)


def test_uniform_shards_one_matches_legacy_subset_size():
    assert fed.uniform(8, 0.5, shards=1).subset_size \
        == fed.uniform(8, 0.5).subset_size


# --------------------------------------------------------------------------
# "lace_dp" sparse-slot and async events (in-shard gather)
# --------------------------------------------------------------------------

_DP_SPARSE_ASYNC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "src")
import json
import jax
import jax.numpy as jnp
import numpy as np

from repro import fed, optim
from repro.configs import ScalaConfig, get_config
from repro.configs.base import InputShape
from repro.core import engine
from repro.core.scala import transformer_split_model
from repro.launch import input_specs as ispec
from repro.models import transformer as T
from repro.sharding.logical import RULES_DP, tree_specs

cfg = get_config("qwen1.5-0.5b").reduced()
C, BK, S, TS = 4, 1, 16, 2
model = transformer_split_model(cfg)
key = jax.random.PRNGKey(0)
full = T.init_params(key, cfg)
params = {
    "client": jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (C,) + a.shape), full["client"]),
    "server": full["server"],
}
tokens = jax.random.randint(jax.random.PRNGKey(1), (TS, C, BK, S), 0,
                            cfg.vocab_size)
rb = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=-1),
      "weights": jnp.ones((TS, C, BK, S), jnp.float32)}
sizes = jnp.asarray([2.0, 1.0, 3.0, 1.0])
sc = ScalaConfig(num_clients=C, participation=1.0, lr=0.05,
                 grad_reduce_dtype=None)
st0 = engine.init_train_state(params, optim.sgd())

mesh = jax.make_mesh((2, 2), ("data", "model"))
n_shards = engine.client_shard_count(mesh)
assert n_shards == 2, n_shards
shape = InputShape(name="t", seq_len=S, global_batch=C * BK, mode="train")
b_sh, b_ax = ispec.train_batch_specs(cfg, shape, C)
b_specs = tree_specs(b_ax, b_sh, mesh, RULES_DP)
res = {}

# (a) lace_dp sparse-slot round == the masked lace round, same masks
agg = fed.weighted()
part = fed.make_participation("uniform:0.5:2", C)
r_sparse = jax.jit(engine.make_round_runner(
    model, sc, backend="lace_dp", ce_chunk=8, mesh=mesh,
    batch_specs=b_specs, aggregator=agg, participation=part,
    slot_gather=True))
r_masked = jax.jit(engine.make_round_runner(
    model, sc, backend="lace", ce_chunk=8, aggregator=agg,
    participation=part))
fs_s = fed.init_fed_state(jax.random.PRNGKey(5), agg, part)
fs_m = fed.init_fed_state(jax.random.PRNGKey(5), agg, part)
st_s, st_m = st0, st0
for _ in range(2):
    st_s, fs_s, m_s = r_sparse(st_s, rb, sizes, fs_s)
    st_m, fs_m, m_m = r_masked(st_m, rb, sizes, fs_m)
res["sparse_params"] = max(
    float(jnp.max(jnp.abs(a - b)) / (1e-8 + float(jnp.max(jnp.abs(a)))))
    for a, b in zip(jax.tree.leaves(st_s.params),
                    jax.tree.leaves(st_m.params)))
res["sparse_loss"] = abs(float(m_s["loss_server"])
                         - float(m_m["loss_server"]))

# (b) lace_dp async at zero delays + full cohort == the lace async
dm = fed.make_delays("zero")
r_async_dp = jax.jit(fed.make_async_runner(
    model, sc, backend="lace_dp", ce_chunk=8, delays=dm, cohort=C,
    mesh=mesh, batch_specs=b_specs))
r_async = jax.jit(fed.make_async_runner(
    model, sc, backend="lace", ce_chunk=8, delays=dm, cohort=C))
af_d = fed.init_async_state(jax.random.PRNGKey(6), params["client"], dm)
af_r = fed.init_async_state(jax.random.PRNGKey(6), params["client"], dm)
sa_d, sa_r = st0, st0
for _ in range(2):
    sa_d, af_d, ma_d = r_async_dp(sa_d, af_d, rb, sizes)
    sa_r, af_r, ma_r = r_async(sa_r, af_r, rb, sizes)
res["async_params"] = max(
    float(jnp.max(jnp.abs(a - b)) / (1e-8 + float(jnp.max(jnp.abs(a)))))
    for a, b in zip(jax.tree.leaves(sa_d.params),
                    jax.tree.leaves(sa_r.params)))
res["async_loss"] = abs(float(ma_d["loss_server"])
                        - float(ma_r["loss_server"]))
res["async_versions_ok"] = int(
    (jnp.asarray(af_d.version) == 2).all() and int(af_d.server_version) == 2)

# (c) lace_dp async delta snapshots == lace_dp dense, sparse cohort
dm2 = fed.make_delays("zero")
for snapshots, slots in (("dense", C), ("delta", 1)):
    p = {"client": jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (slots,) + a.shape),
        full["client"]), "server": full["server"]}
    st = engine.init_train_state(p, optim.sgd())
    af = fed.init_async_state(jax.random.PRNGKey(7), p["client"], dm2,
                              snapshots=snapshots, ring_size=4,
                              num_clients=C)
    rr = jax.jit(fed.make_async_runner(
        model, sc, backend="lace_dp", ce_chunk=8, delays=dm2, cohort=2,
        snapshots=snapshots, ring_size=4, num_clients=C, mesh=mesh,
        batch_specs=b_specs))
    for _ in range(3):
        st, af, mm = rr(st, af, rb, sizes)
    if snapshots == "dense":
        ref_c = np.asarray(jax.tree.leaves(st.params["client"])[0])[0]
        ref_v = jnp.asarray(af.version)
    else:
        res["delta_params"] = float(np.max(np.abs(
            np.asarray(jax.tree.leaves(st.params["client"])[0])[0] - ref_c)))
        res["delta_versions_ok"] = int(
            (jnp.asarray(af.version) == ref_v).all())
print("RESULT " + json.dumps(res))
"""


@pytest.mark.slow
def test_dp_sparse_and_async_match_single_program():
    """Tentpole (b): the lace_dp in-shard gather — the sparse-slot round
    matches the masked lace round for the same masks, the lace_dp async
    event at zero delays + full cohort matches the single-program async,
    and delta snapshots agree with dense inside the shard_map too."""
    import json as _json
    import os as _os
    import subprocess
    import sys as _sys

    env = dict(_os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([_sys.executable, "-c", _DP_SPARSE_ASYNC_SCRIPT],
                         capture_output=True, text=True, env=env,
                         cwd=_os.path.dirname(_os.path.dirname(
                             _os.path.abspath(__file__))), timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, out.stdout[-2000:]
    res = _json.loads(line[0][len("RESULT "):])
    assert res["sparse_params"] < 5e-4, res
    assert res["sparse_loss"] < 1e-4, res
    assert res["async_params"] < 5e-4, res
    assert res["async_loss"] < 1e-4, res
    assert res["async_versions_ok"] == 1, res
    assert res["delta_params"] < 1e-6, res
    assert res["delta_versions_ok"] == 1, res
