"""Fault tolerance: injection, guarded aggregation, deadlines, recovery.

The acceptance bars (ISSUE 10):

(a) with guards enabled and ZERO faults injected, round outputs are
    bit-identical (f32) to the unguarded path — masked, sparse, and
    async modes, logits + lace backends inline and lace_dp masked via
    the multi-device subprocess leg;
(b) with NaN/Inf corruption injected, training stays finite and the
    surviving-subset priors / logit adjustments match a reference round
    computed from the post-rejection participation mask (the
    SCALA-specific part: a rejected client changes the eq. 14/15
    correction exactly as if it had never participated);
(c) async deadlines: a loose deadline reproduces the legacy barrier
    bitwise; a tight one proceeds with the partial cohort and requeues
    the missed clients with exponential backoff;
(d) ``Trainer.save``/``resume`` round-trips the FULL program state —
    params, optimizer moments, async/delta/ring state, retries, fault
    keys, host RNG — bit-identically, and the checkpoint layer survives
    a torn write by falling back to the previous step;
(e) ``ServeEngine`` evicts slots past their request deadline or the
    engine token budget, freeing slots/pages for the arrival queue.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, fed, optim
from repro.configs import ScalaConfig
from repro.core import engine
from repro.fed.faults import FaultModel, make_faults
from repro.fed.guards import GuardPolicy, make_guards


def _tree_equal(a, b, what=""):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb), what
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=what)


def _linear_split_model(d_in=4, d_mid=3, num_classes=3):
    def client_fwd(wc, batch):
        return {"x": batch["x"] @ wc["w"]}

    def server_fwd(ws, acts):
        return acts["x"] @ ws["w"], jnp.zeros((), jnp.float32)

    def server_trunk(ws, acts):          # features == acts; head is ws["w"]
        return acts["x"], jnp.zeros((), jnp.float32)

    def head_grad_merge(d_ws, g_w):
        return {"w": d_ws["w"] + g_w.astype(d_ws["w"].dtype)}

    return engine.SplitModel(client_fwd=client_fwd, server_fwd=server_fwd,
                             num_classes=num_classes,
                             server_trunk=server_trunk,
                             head_weight=lambda ws: ws["w"],
                             head_grad_merge=head_grad_merge)


def _linear_setup(key, slots, d_in=4, d_mid=3, num_classes=3):
    from repro.core.split import stack_client_params

    kc, ks = jax.random.split(key)
    wc = {"w": jax.random.normal(kc, (d_in, d_mid))}
    ws = {"w": jax.random.normal(ks, (d_mid, num_classes))}
    return {"client": stack_client_params(wc, slots), "server": ws}


def _linear_round_batches(key, T_steps, C, Bk=4, d_in=4, num_classes=3):
    kx, ky = jax.random.split(key)
    return {"x": jax.random.normal(kx, (T_steps, C, Bk, d_in)),
            "labels": jax.random.randint(ky, (T_steps, C, Bk), 0,
                                         num_classes)}


def _fixed_mask_scheduler(mask):
    """A stateless scheduler that emits ``mask`` every round — the
    reference for "as if the rejected clients never participated"."""
    mask = jnp.asarray(mask, jnp.float32)
    return fed.ParticipationScheduler(
        name="fixed", num_clients=mask.shape[0], stateful=False,
        init=lambda key=None: (), sample=lambda s: (mask, s),
        subset_size=None)


K = 6
MODEL = _linear_split_model()
SC = ScalaConfig(num_clients=K, participation=1.0, local_iters=2, lr=0.05)
PARAMS = _linear_setup(jax.random.PRNGKey(0), K)
RB = _linear_round_batches(jax.random.PRNGKey(1), T_steps=2, C=K)
SIZES = jnp.arange(1.0, K + 1.0)


# --------------------------------------------------------------------------
# spec parsing + rejection of incoherent combinations
# --------------------------------------------------------------------------


def test_fault_spec_grammar():
    fm = make_faults("drop:0.1,corrupt:0.05:nan,stall:0.02")
    assert fm.drop == 0.1 and fm.corrupt == 0.05 and fm.stall == 0.02
    assert fm.corrupt_mode == "nan" and fm.any_faults
    fm2 = make_faults("corrupt:0.2:noise:3.5,stall:0.1:50")
    assert fm2.corrupt_mode == "noise" and fm2.noise_scale == 3.5
    assert fm2.stall_factor == 50.0
    assert make_faults(None) is None
    assert make_faults(fm) is fm                    # passthrough
    assert isinstance(make_faults("drop:0"), FaultModel)
    for bad in ("", "drop", "drop:2", "corrupt:0.1:huh", "stall:0.1:0.5",
                "explode:0.1"):
        with pytest.raises(ValueError):
            make_faults(bad)


def test_guard_spec_grammar():
    gp = make_guards("nonfinite,clip:10.0:0.25")
    assert gp.nonfinite and gp.clip == 10.0 and gp.beta == 0.25
    assert gp.stateful
    assert not make_guards("nonfinite").stateful
    assert make_guards(None) is None
    assert make_guards(gp) is gp
    assert isinstance(make_guards("clip:5"), GuardPolicy)
    for bad in ("", "clip:0", "clip:-1", "median"):
        with pytest.raises(ValueError):
            make_guards(bad)


def test_incoherent_combinations_rejected():
    dm = fed.make_delays("zero")
    # deadline outside async, at spec level
    sp = api.ExperimentSpec(
        arch="alexnet-cifar", method="scala",
        scala=ScalaConfig(num_clients=4),
        data=api.DataSpec(kind="image_synthetic"),
        execution=api.ExecutionSpec(mode="masked", deadline=1.0))
    with pytest.raises(ValueError, match="deadline"):
        sp.validate()
    # faults/guards in host-subset mode, at spec level
    sp2 = api.ExperimentSpec(
        arch="alexnet-cifar", method="scala",
        scala=ScalaConfig(num_clients=4),
        data=api.DataSpec(kind="image_synthetic"),
        fed=api.FedSpec(faults="drop:0.1"),
        execution=api.ExecutionSpec(mode="subset"))
    with pytest.raises(ValueError, match="subset"):
        sp2.validate()
    # lace_dp async + robust, at constructor level
    with pytest.raises(ValueError, match="lace_dp"):
        fed.make_async_runner(MODEL, SC, delays=dm, cohort=2,
                              backend="lace_dp", guards="nonfinite")
    # paged optimizer state + robust
    with pytest.raises(ValueError, match="paged"):
        fed.make_async_runner(MODEL, SC, delays=dm, cohort=2,
                              snapshots="delta", paged_opt=True,
                              faults="drop:0.1")
    # sparse lace_dp gather + robust
    with pytest.raises(ValueError, match="lace_dp"):
        engine.make_round_runner(MODEL, SC, backend="lace_dp",
                                 slot_gather=True,
                                 participation=fed.uniform(K, 0.5),
                                 guards="nonfinite")
    # bad deadline / backoff values
    with pytest.raises(ValueError, match="deadline"):
        fed.make_async_runner(MODEL, SC, delays=dm, cohort=2, deadline=0.0)
    with pytest.raises(ValueError, match="backoff"):
        fed.make_async_runner(MODEL, SC, delays=dm, cohort=2, deadline=1.0,
                              backoff=0.5)


# --------------------------------------------------------------------------
# (a) guards on + zero faults == unguarded, bitwise
# --------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["logits", "lace"])
@pytest.mark.parametrize("sparse", [False, True], ids=["masked", "sparse"])
def test_guarded_zero_fault_bitwise_sync(backend, sparse):
    opt = optim.momentum(beta=0.9)
    part = fed.uniform(K, 0.5)
    agg = fed.weighted()
    kw = dict(backend=backend, optimizer=opt, aggregator=agg,
              participation=part, slot_gather=sparse)
    plain = jax.jit(engine.make_round_runner(MODEL, SC, **kw))
    guarded = jax.jit(engine.make_round_runner(
        MODEL, SC, guards="nonfinite,clip:1e6", **kw))

    st_p = engine.init_train_state(PARAMS, opt)
    st_g = st_p
    fs_p = fed.init_fed_state(jax.random.PRNGKey(5), agg, part)
    fs_g = fed.init_fed_state(jax.random.PRNGKey(5), agg, part,
                              guards="nonfinite,clip:1e6")
    for _ in range(3):
        st_p, fs_p, m_p = plain(st_p, RB, SIZES, fs_p)
        st_g, fs_g, m_g = guarded(st_g, RB, SIZES, fs_g)
    _tree_equal(st_p.params, st_g.params, "params")
    _tree_equal(st_p.opt_state, st_g.opt_state, "opt_state")
    _tree_equal(fs_p["sched"], fs_g["sched"], "sched state")
    for k in m_p:
        _tree_equal(m_p[k], m_g[k], f"metric {k}")
    assert float(np.asarray(m_g["guard_rejected"])) == 0.0


@pytest.mark.parametrize("backend", ["logits", "lace"])
@pytest.mark.parametrize("snapshots", ["dense", "delta"])
def test_guarded_zero_fault_bitwise_async(backend, snapshots):
    dm = fed.make_delays("lognormal:1:1")
    # delta snapshots store no per-client moments; keep sgd there
    opt = optim.sgd() if snapshots == "delta" else optim.momentum(beta=0.9)
    kw = dict(backend=backend, optimizer=opt, delays=dm, cohort=2,
              snapshots=snapshots, ring_size=3, num_clients=K)
    plain = jax.jit(fed.make_async_runner(MODEL, SC, **kw))
    guarded = jax.jit(fed.make_async_runner(MODEL, SC, guards="nonfinite",
                                            **kw))
    st_p = engine.init_train_state(PARAMS, opt)
    st_g = st_p
    af_p = fed.init_async_state(jax.random.PRNGKey(7), PARAMS["client"], dm,
                                snapshots=snapshots, ring_size=3)
    af_g = fed.init_async_state(jax.random.PRNGKey(7), PARAMS["client"], dm,
                                snapshots=snapshots, ring_size=3,
                                guards="nonfinite")
    for _ in range(4):
        st_p, af_p, m_p = plain(st_p, af_p, RB, SIZES)
        st_g, af_g, m_g = guarded(st_g, af_g, RB, SIZES)
    _tree_equal(st_p.params, st_g.params, "params")
    _tree_equal(st_p.opt_state, st_g.opt_state, "opt_state")
    _tree_equal(af_p.client_params, af_g.client_params, "snapshots")
    _tree_equal((af_p.finish_time, af_p.version, af_p.server_version,
                 af_p.ring, af_p.ring_versions),
                (af_g.finish_time, af_g.version, af_g.server_version,
                 af_g.ring, af_g.ring_versions), "schedule scalars")
    for k in m_p:
        _tree_equal(m_p[k], m_g[k], f"metric {k}")
    assert float(np.asarray(m_g["guard_rejected"])) == 0.0


# --------------------------------------------------------------------------
# (b) NaN corruption: rejection + survivor-recomputed priors
# --------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["nan", "inf"])
def test_corruption_rejected_and_priors_match_survivor_reference(mode):
    """The SCALA-specific acceptance bar: a corrupt (rejected) client
    must change the eq. 14/15 priors / logit adjustments exactly as if
    it had never participated — i.e. the guarded faulty round equals a
    clean round run with the post-rejection mask as its participation.
    """
    opt = optim.momentum(beta=0.9)
    # corrupt HALF the cohort so the recompute branch definitely fires
    faulty = jax.jit(engine.make_round_runner(
        MODEL, SC, backend="lace", optimizer=opt, aggregator=fed.weighted(),
        faults=f"corrupt:0.5:{mode}", guards="nonfinite"))
    st0 = engine.init_train_state(PARAMS, opt)
    fs = fed.init_fed_state(jax.random.PRNGKey(3), fed.weighted(),
                            num_clients=K, faults=f"corrupt:0.5:{mode}",
                            guards="nonfinite")
    st_f, fs_f, m_f = faulty(st0, RB, SIZES, fs)

    accept = np.asarray(m_f["guard_accept"])
    rejected = float(np.asarray(m_f["guard_rejected"]))
    assert rejected >= 1, "corruption at 50% should reject someone"
    assert rejected == K - accept.sum()
    for leaf in jax.tree_util.tree_leaves(st_f.params):
        assert bool(jnp.isfinite(leaf).all()), "NaN leaked into the params"

    # reference: no faults, no guards — the survivors ARE the cohort
    part = _fixed_mask_scheduler(accept)
    ref = jax.jit(engine.make_round_runner(
        MODEL, SC, backend="lace", optimizer=opt, aggregator=fed.weighted(),
        participation=part))
    fs_r = fed.init_fed_state(jax.random.PRNGKey(3), fed.weighted(), part)
    st_r, _, m_r = ref(st0, RB, SIZES, fs_r)
    _tree_equal(st_f.params, st_r.params, "survivor-masked params")
    np.testing.assert_array_equal(np.asarray(m_f["loss_server"]),
                                  np.asarray(m_r["loss_server"]))


def test_chaos_training_stays_finite_and_learns():
    """drop + NaN corruption at ~10% of the cohort for 8 rounds: every
    round's aggregate stays finite and the loss still goes down."""
    opt = optim.momentum(beta=0.9)
    runner = jax.jit(engine.make_round_runner(
        MODEL, SC, backend="lace", optimizer=opt, aggregator=fed.weighted(),
        faults="drop:0.1,corrupt:0.1:nan", guards="nonfinite"))
    st = engine.init_train_state(PARAMS, opt)
    fs = fed.init_fed_state(jax.random.PRNGKey(11), fed.weighted(),
                            num_clients=K, faults="drop:0.1,corrupt:0.1:nan",
                            guards="nonfinite")
    losses = []
    for r in range(8):
        rb = _linear_round_batches(jax.random.fold_in(jax.random.PRNGKey(2),
                                                      r), T_steps=2, C=K)
        st, fs, m = runner(st, rb, SIZES, fs)
        losses.append(float(np.asarray(m["loss_server"])))
        for leaf in jax.tree_util.tree_leaves(st.params):
            assert bool(jnp.isfinite(leaf).all()), f"round {r}"
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_clip_guard_bounds_update_norm():
    """A noise-corrupted client's huge update gets clipped to the
    running-median budget instead of dominating the aggregate."""
    spec = "corrupt:0.2:noise:1000.0"

    def run(guards):
        opt = optim.sgd()
        runner = jax.jit(engine.make_round_runner(
            MODEL, SC, backend="lace", optimizer=opt,
            aggregator=fed.weighted(), faults=spec, guards=guards))
        st = engine.init_train_state(PARAMS, opt)
        fs = fed.init_fed_state(jax.random.PRNGKey(13), fed.weighted(),
                                num_clients=K, faults=spec, guards=guards)
        before = jax.tree_util.tree_leaves(st.params)
        for _ in range(3):
            st, fs, m = runner(st, RB, SIZES, fs)
        after = jax.tree_util.tree_leaves(st.params)
        drift = float(sum(jnp.sum((a - b) ** 2)
                          for a, b in zip(after, before)) ** 0.5)
        return drift, fs

    drift_plain, _ = run(None)
    drift_clip, fs = run("nonfinite,clip:2.0")
    # corrupted updates are ~1000x the clean norm: unguarded, they
    # dominate the aggregate; clipped against the running median, the
    # trajectory stays orders of magnitude closer to the clean one
    assert drift_clip < drift_plain / 100.0, (drift_clip, drift_plain)
    assert float(np.asarray(fs["guard"]["med"])) > 0.0   # median warmed up


# --------------------------------------------------------------------------
# (c) async deadlines + exponential backoff
# --------------------------------------------------------------------------


def test_loose_deadline_matches_legacy_bitwise():
    dm = fed.make_delays("lognormal:1:1")
    opt = optim.momentum(beta=0.9)
    kw = dict(backend="lace", optimizer=opt, delays=dm, cohort=2,
              num_clients=K)
    legacy = jax.jit(fed.make_async_runner(MODEL, SC, **kw))
    bounded = jax.jit(fed.make_async_runner(MODEL, SC, deadline=1e6, **kw))
    st_l = engine.init_train_state(PARAMS, opt)
    st_b = st_l
    af_l = fed.init_async_state(jax.random.PRNGKey(17), PARAMS["client"], dm)
    af_b = af_l
    for _ in range(4):
        st_l, af_l, m_l = legacy(st_l, af_l, RB, SIZES)
        st_b, af_b, m_b = bounded(st_b, af_b, RB, SIZES)
        assert float(np.asarray(m_b["deadline_missed"])) == 0.0
    _tree_equal(st_l.params, st_b.params, "params")
    _tree_equal((af_l.finish_time, af_l.version, af_l.key),
                (af_b.finish_time, af_b.version, af_b.key), "schedule")
    np.testing.assert_array_equal(np.asarray(af_b.retries), np.zeros(K))


def test_tight_deadline_partial_cohort_and_backoff():
    dm = fed.make_delays("lognormal:1:1")
    opt = optim.sgd()
    bounded = jax.jit(fed.make_async_runner(
        MODEL, SC, backend="lace", optimizer=opt, delays=dm, cohort=3,
        num_clients=K, deadline=0.05, backoff=3.0))
    st = engine.init_train_state(PARAMS, opt)
    af = fed.init_async_state(jax.random.PRNGKey(19), PARAMS["client"], dm)
    ft_before = np.asarray(af.finish_time).copy()
    missed_total = 0
    for _ in range(4):
        st, af, m = bounded(st, af, RB, SIZES)
        missed_total += int(np.asarray(m["deadline_missed"]))
        t_event = float(np.asarray(m["t_event"]))
        # the event never waits for the full cohort barrier
        assert t_event <= float(np.sort(ft_before)[0]) + 0.05 + 1e-6
        ft_before = np.asarray(af.finish_time).copy()
    assert missed_total > 0, "deadline=0.05 should miss arrivals"
    retries = np.asarray(af.retries)
    assert retries.max() >= 1, "missed clients must accrue retries"
    for leaf in jax.tree_util.tree_leaves(st.params):
        assert bool(jnp.isfinite(leaf).all())
    # requeued clients got fresh finite finish times (not +inf stalls)
    assert bool(np.isfinite(np.asarray(af.finish_time)).all())


def test_stall_fault_with_deadline_schedule_advances():
    """stall:P alone would park clients at huge finish times; the
    deadline lets events proceed with whoever arrived."""
    dm = fed.make_delays("lognormal:1:1")
    opt = optim.sgd()
    runner = jax.jit(fed.make_async_runner(
        MODEL, SC, backend="lace", optimizer=opt, delays=dm, cohort=2,
        num_clients=K, deadline=5.0, faults="stall:0.5:100",
        guards="nonfinite"))
    st = engine.init_train_state(PARAMS, opt)
    af = fed.init_async_state(jax.random.PRNGKey(23), PARAMS["client"], dm,
                              guards="nonfinite")
    for _ in range(4):
        st, af, m = runner(st, af, RB, SIZES)
    assert int(np.asarray(af.server_version)) == 4
    assert float(np.asarray(m["t_event"])) < 1e4
    for leaf in jax.tree_util.tree_leaves(st.params):
        assert bool(jnp.isfinite(leaf).all())


# --------------------------------------------------------------------------
# lace_dp masked guards: multi-device subprocess leg
# --------------------------------------------------------------------------


_DP_GUARD_SCRIPT = r"""
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
C, BK, S, TS = 2, 2, 16, 2
model = transformer_split_model(cfg)
full = T.init_params(jax.random.PRNGKey(0), cfg)
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

mesh = jax.make_mesh((2, 2), ("data", "model"))
shape = InputShape(name="t", seq_len=S, global_batch=C * BK, mode="train")
b_sh, b_ax = ispec.train_batch_specs(cfg, shape, C)
b_specs = tree_specs(b_ax, b_sh, mesh, RULES_DP)

agg, part = fed.weighted(), fed.uniform(C, 0.5)
kw = dict(backend="lace_dp", ce_chunk=8, mesh=mesh, batch_specs=b_specs,
          aggregator=agg, participation=part)
plain = jax.jit(engine.make_round_runner(model, sc, **kw))
guarded = jax.jit(engine.make_round_runner(model, sc,
                                           guards="nonfinite", **kw))
fs_p = fed.init_fed_state(jax.random.PRNGKey(5), agg, part)
fs_g = fed.init_fed_state(jax.random.PRNGKey(5), agg, part,
                          guards="nonfinite")
st_p, fs_p, m_p = plain(st0, rb, sizes, fs_p)
st_g, fs_g, m_g = guarded(st0, rb, sizes, fs_g)
bitwise = int(all(
    bool(jnp.array_equal(a, b))
    for a, b in zip(jax.tree.leaves(st_p.params),
                    jax.tree.leaves(st_g.params))))
print("RESULT " + json.dumps({
    "bitwise": bitwise,
    "rejected": float(np.asarray(m_g["guard_rejected"])),
}))
"""


@pytest.mark.slow
def test_dp_masked_guards_zero_fault_bitwise_subprocess():
    """lace_dp (shard_map) masked round with guards on and zero faults
    is bitwise the unguarded lace_dp round — the third backend of the
    acceptance matrix."""
    import subprocess
    import sys

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _DP_GUARD_SCRIPT],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, out.stdout[-2000:]
    res = json.loads(line[-1][len("RESULT "):])
    assert res["bitwise"] == 1
    assert res["rejected"] == 0.0


# --------------------------------------------------------------------------
# (d) crash-recoverable training
# --------------------------------------------------------------------------


def _tiny_image_spec(**over):
    kw = dict(
        arch="alexnet-cifar", method="scala", rounds=4, seed=0,
        scala=ScalaConfig(num_clients=4, participation=0.5, local_iters=2,
                          server_batch=24, lr=0.05),
        data=api.DataSpec(kind="image_synthetic", n_train=200, alpha=2))
    kw.update(over)
    return api.ExperimentSpec(**kw)


@pytest.mark.slow
def test_trainer_resume_bitwise_async_delta_chaos(tmp_path):
    """Kill-and-restore mid-run under async + delta snapshots + faults +
    guards + deadline: the resumed trainer's final state and history are
    bitwise the uninterrupted run's — ring snapshots, schedule scalars,
    retries, fault keys, guard state, host RNG included."""
    def mk():
        return _tiny_image_spec(
            fed=api.FedSpec(faults="drop:0.2,corrupt:0.1:nan",
                            guards="nonfinite,clip:10.0"),
            execution=api.ExecutionSpec(mode="async", snapshots="delta",
                                        ring_size=2, cohort=2, deadline=5.0,
                                        backoff=2.0))

    straight = api.Trainer(mk())
    straight.run(4)

    d = str(tmp_path / "ckpt")
    first = api.Trainer(mk())
    first.run(2)
    first.save(d)
    resumed = api.Trainer(mk())                 # fresh process stand-in
    assert resumed.resume(d) == 2
    resumed.run(2)

    _tree_equal(straight.state, resumed.state, "full ProgramState")
    assert straight.history == resumed.history


def test_trainer_resume_bitwise_sync_masked(tmp_path):
    spec_kw = dict(fed=api.FedSpec(faults="drop:0.2", guards="nonfinite"),
                   execution=api.ExecutionSpec(mode="masked"))
    straight = api.Trainer(_tiny_image_spec(**spec_kw))
    straight.run(4)

    d = str(tmp_path / "ckpt")
    first = api.Trainer(_tiny_image_spec(**spec_kw))
    first.run(3)
    first.save(d)
    resumed = api.Trainer(_tiny_image_spec(**spec_kw))
    assert resumed.resume(d) == 3
    resumed.run(1)
    _tree_equal(straight.state, resumed.state, "full ProgramState")
    assert straight.history == resumed.history
    # the resumed round was gathered from a pool the new trainer built
    assert resumed.gathered_rounds == 1 and resumed.resident_bytes > 0
    assert (resumed._rng.bit_generator.state
            == straight._rng.bit_generator.state)


def test_trainer_resume_bitwise_sparse_fused(tmp_path):
    """Sparse rounds, two per call, gathered on the device: the resumed
    run is bitwise the uninterrupted one."""
    def mk():
        return _tiny_image_spec(
            fed=api.FedSpec(participation="uniform:0.5"),
            execution=api.ExecutionSpec(mode="sparse", rounds_per_call=2))

    straight = api.Trainer(mk())
    straight.run(4)
    d = str(tmp_path / "ckpt")
    first = api.Trainer(mk())
    first.run(2)
    first.save(d)
    resumed = api.Trainer(mk())
    assert resumed.resume(d) == 2
    resumed.run(2)
    _tree_equal(straight.state, resumed.state, "full ProgramState")
    assert straight.history == resumed.history
    assert (straight.gathered_rounds, resumed.gathered_rounds) == (4, 2)


def test_checkpoint_atomic_and_corrupt_fallback(tmp_path):
    from repro import checkpoint as C

    tree = {"a": np.arange(6.0).reshape(2, 3), "b": {"c": np.ones(4)}}
    d = str(tmp_path)
    C.save(d, 1, tree)
    tree2 = jax.tree.map(lambda a: a * 2, tree)
    C.save(d, 2, tree2)
    assert C.all_steps(d) == [1, 2]
    # no stray temp files after an atomic save
    assert not [f for f in os.listdir(d) if ".tmp" in f]

    # torn write: truncate the latest -> restore falls back to step 1
    with open(os.path.join(d, "ckpt_00000002.npz"), "r+b") as f:
        f.truncate(10)
    with pytest.warns(UserWarning, match="unreadable"):
        got = C.restore(d, tree)
    _tree_equal(got, tree, "fallback restore")
    # an explicitly pinned corrupt step raises instead of substituting
    with pytest.raises(Exception):
        C.restore(d, tree, step=2)


def test_trainer_resume_skips_torn_pair(tmp_path):
    spec_kw = dict(execution=api.ExecutionSpec(mode="masked"),
                   fed=api.FedSpec(participation="uniform:0.5"))
    d = str(tmp_path / "ckpt")
    t = api.Trainer(_tiny_image_spec(**spec_kw))
    t.run(1)
    t.save(d)
    t.run(1)
    t.save(d)
    # crash mid-save of the newest checkpoint: npz exists, meta torn
    with open(os.path.join(d, "meta_00000002.json"), "w") as f:
        f.write('{"round": 2, "hist')
    fresh = api.Trainer(_tiny_image_spec(**spec_kw))
    assert fresh.resume(d) == 1

    # host-paged optimizer state cannot be checkpointed -> targeted error
    sp = _tiny_image_spec(
        fed=api.FedSpec(opt_state_policy="carry"),
        execution=api.ExecutionSpec(mode="async", cohort=2, arrival="topk",
                                    snapshots="delta", ring_size=2,
                                    opt_paging="host"))
    paged = api.Trainer(sp)
    with pytest.raises(ValueError, match="host"):
        paged.save(str(tmp_path / "paged"))


# --------------------------------------------------------------------------
# (e) serving: per-request deadline / token-budget eviction
# --------------------------------------------------------------------------


def test_serve_deadline_and_budget_eviction():
    from helpers import tiny_cfg
    from repro.models import transformer as T
    from repro.serve import Request, ServeEngine

    cfg = dataclasses.replace(tiny_cfg(), dtype="float32",
                              param_dtype="float32")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (3, 4),
                                            0, cfg.vocab_size))

    # baseline: no deadlines -> behavior unchanged, nothing evicted
    eng = ServeEngine(params, cfg, slots=2, max_len=64)
    base = eng.serve([Request(i, prompts[i], 6) for i in range(3)],
                     wall_clock=False)
    assert all(r.evicted is None for r in base.values())
    assert all(len(r.tokens) == 4 + 6 for r in base.values())

    # single slot: rid0's deadline evicts it mid-generation and rid1
    # takes the freed slot (no head-of-line blocking)
    eng2 = ServeEngine(params, cfg, slots=1, max_len=64)
    res = eng2.serve([Request(0, prompts[0], 20, deadline=3.0),
                      Request(1, prompts[1], 4)], wall_clock=False)
    assert res[0].evicted == "deadline"
    assert 1 <= len(res[0].tokens) - 4 < 20      # partial sequence back
    assert res[1].evicted is None and len(res[1].tokens) - 4 == 4
    assert res[1].t_admit >= res[0].t_finish

    # engine-wide token budget: capped request evicted at the cap, and
    # its generated prefix is bitwise the uncapped generation's
    eng3 = ServeEngine(params, cfg, slots=2, max_len=64, token_budget=3)
    res3 = eng3.serve([Request(0, prompts[0], 10),
                       Request(1, prompts[1], 2)], wall_clock=False)
    assert res3[0].evicted == "budget" and len(res3[0].tokens) == 4 + 3
    assert res3[1].evicted is None
    np.testing.assert_array_equal(res3[0].tokens, base[0].tokens[:7])

    # paged cache: eviction returns the pages to the pool
    eng4 = ServeEngine(params, cfg, slots=2, max_len=64, pages=8,
                       page_size=4)
    res4 = eng4.serve([Request(0, prompts[0], 20, deadline=2.0),
                       Request(1, prompts[1], 20, deadline=2.0),
                       Request(2, prompts[2], 3, arrival=1.0)],
                      wall_clock=False)
    assert res4[0].evicted == "deadline" and res4[1].evicted == "deadline"
    assert res4[2].evicted is None
    assert len(eng4._free_pages) == 8 and len(eng4._free_slots) == 2

    with pytest.raises(ValueError, match="deadline"):
        eng.serve([Request(9, prompts[0], 2, deadline=0.0)],
                  wall_clock=False)
    with pytest.raises(ValueError, match="token_budget"):
        ServeEngine(params, cfg, slots=1, max_len=64, token_budget=0)
