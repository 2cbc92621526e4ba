"""The manual-SPMD ("dp") SCALA step must match the GSPMD fused step
bit-for-bit (same math, different collective schedule).

Runs in a subprocess with 8 forced host devices so the shard_map path is
exercised on a real (data=4, model=2) mesh.
"""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import json
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ScalaConfig, get_config
from repro.core.scala import (scala_local_step_fused,
                              scala_local_step_fused_dp,
                              transformer_split_model)
from repro.launch import input_specs as ispec
from repro.models import transformer as T
from repro.sharding.logical import RULES_DP, tree_specs, tree_shardings

cfg = get_config("qwen1.5-0.5b").reduced()
assert cfg.sharding_profile == "dp"
C, BK, S = 4, 4, 32
model = transformer_split_model(cfg)
key = jax.random.PRNGKey(0)
full = T.init_params(key, cfg)
params = {
    "client": jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (C,) + a.shape), full["client"]),
    "server": full["server"],
}
kb = jax.random.PRNGKey(1)
tokens = jax.random.randint(kb, (C, BK, S), 0, cfg.vocab_size)
labels = jnp.roll(tokens, -1, axis=-1)
weights = jnp.ones((C, BK, S), jnp.float32)
batch = {"tokens": tokens, "labels": labels, "weights": weights}
# exact-reduction mode for the equivalence check (production default
# compresses the grad psum to bf16)
sc = ScalaConfig(num_clients=C, participation=1.0, lr=0.05,
                 grad_reduce_dtype=None)

# reference: no mesh, plain fused step
ref_params, ref_m = jax.jit(
    lambda p, b: scala_local_step_fused(model, p, b, sc))(params, batch)

mesh = jax.make_mesh((4, 2), ("data", "model"))
from dataclasses import replace
from repro.configs.base import InputShape
shape = InputShape(name="t", seq_len=S, global_batch=C * BK, mode="train")
b_sh, b_ax = ispec.train_batch_specs(cfg, shape, C)
b_specs = tree_specs(b_ax, b_sh, mesh, RULES_DP)
with jax.set_mesh(mesh):
    dp_params, dp_m = jax.jit(
        lambda p, b: scala_local_step_fused_dp(model, p, b, sc, mesh,
                                               b_specs))(params, batch)

err = {}
for k in ("client", "server"):
    a = jax.tree.leaves(ref_params[k]); b = jax.tree.leaves(dp_params[k])
    err[k] = max(float(jnp.max(jnp.abs(x - y)) /
                       (1e-8 + float(jnp.max(jnp.abs(x)))))
                 for x, y in zip(a, b))
err["loss_server"] = abs(float(ref_m["loss_server"]) -
                         float(dp_m["loss_server"]))
err["loss_client"] = abs(float(ref_m["loss_client"]) -
                         float(dp_m["loss_client"]))
print("RESULT " + json.dumps(err))
"""


@pytest.mark.slow
def test_dp_step_matches_fused():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, out.stdout[-2000:]
    err = json.loads(line[0][len("RESULT "):])
    assert err["loss_server"] < 1e-5, err
    assert err["loss_client"] < 1e-5, err
    assert err["client"] < 5e-4, err
    assert err["server"] < 5e-4, err


# The in-shard gather's mesh-dependent validation: the client shard
# count comes from the mesh, so the shards-balanced-scheduler and
# divisibility checks only fire on a real multi-device mesh (the
# single-device suite can't reach them). Construction-only — no compute.
GATHER_VALIDATION_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "src")
import jax
import jax.numpy as jnp

from repro import fed
from repro.configs import ScalaConfig, get_config
from repro.configs.base import InputShape
from repro.core import engine
from repro.core.scala import transformer_split_model
from repro.launch import input_specs as ispec
from repro.sharding.logical import RULES_DP, tree_specs

cfg = get_config("qwen1.5-0.5b").reduced()
C, S = 4, 16
model = transformer_split_model(cfg)
sc = ScalaConfig(num_clients=C, participation=1.0, lr=0.05)
mesh = jax.make_mesh((2, 2), ("data", "model"))
assert engine.client_shard_count(mesh) == 2
assert engine.client_shard_count(jax.make_mesh((1, 4),
                                               ("data", "model"))) == 1
shape = InputShape(name="t", seq_len=S, global_batch=C, mode="train")
b_sh, b_ax = ispec.train_batch_specs(cfg, shape, C)
b_specs = tree_specs(b_ax, b_sh, mesh, RULES_DP)

def expect(msg, **kw):
    kw.setdefault("mesh", mesh)
    kw.setdefault("batch_specs", b_specs)
    try:
        engine.make_round_runner(model, sc, backend="lace_dp",
                                 slot_gather=True, aggregator=kw.pop(
                                     "aggregator", fed.weighted()), **kw)
    except ValueError as e:
        assert msg in str(e), (msg, str(e))
    else:
        raise AssertionError(f"no ValueError containing {msg!r}")

# a legacy (shards=1) scheduler cannot balance 2 client shards
expect("shards-balanced", participation=fed.uniform(C, 0.5))
# per-shard aggregation needs a shard-decomposable aggregator
expect("shard-decomposable",
       participation=fed.make_participation("uniform:0.5:2", C),
       aggregator=fed.bias_compensated())
# cross-slot opt-state averaging cannot span shards
expect("'average'", participation=fed.make_participation(
    "uniform:0.5:2", C), opt_state_policy="average")
# the balanced config constructs fine
engine.make_round_runner(model, sc, backend="lace_dp", slot_gather=True,
                         aggregator=fed.weighted(), mesh=mesh,
                         batch_specs=b_specs,
                         participation=fed.make_participation(
                             "uniform:0.5:2", C))
print("RESULT ok")
"""


@pytest.mark.slow
def test_dp_slot_gather_mesh_validation():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", GATHER_VALIDATION_SCRIPT],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "RESULT ok" in out.stdout, out.stdout[-2000:]
