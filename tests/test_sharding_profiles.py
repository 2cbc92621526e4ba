"""Unit tests for the sharding-profile rules and the constrain helper."""
import jax
import jax.numpy as jnp

import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.sharding.logical import (RULES, RULES_DP, RULES_FSDP, constrain,
                                    rules_for, spec_for)


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


MESH = FakeMesh({"data": 16, "model": 16})
MESH3 = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_tp_rules_shard_weights():
    # (d_model, ffn) weight: embed->data (FSDP), ffn->model (TP)
    spec = spec_for(("embed", "ffn"), (4096, 11008), MESH, RULES)
    assert spec == P("data", "model")


def test_dp_rules_replicate_weights_and_shard_batch_everywhere():
    assert spec_for(("embed", "ffn"), (4096, 11008), MESH, RULES_DP) == P()
    spec = spec_for(("client", "per_client_batch", "seq"),
                    (16, 16, 4096), MESH, RULES_DP)
    assert spec == P("data", "model")
    spec3 = spec_for(("client", "per_client_batch", "seq"),
                     (32, 16, 4096), MESH3, RULES_DP)
    assert spec3 == P(("pod", "data"), "model")
    # indivisible per-client batch falls back to replicated for that dim
    spec_f = spec_for(("client", "per_client_batch", "seq"),
                      (32, 8, 4096), MESH3, RULES_DP)
    assert spec_f == P(("pod", "data"))


def test_fsdp_rules_shard_embed_over_everything():
    spec = spec_for(("embed", "ffn"), (4096, 11008), MESH, RULES_FSDP)
    assert spec == P(("data", "model"))
    spec3 = spec_for(("embed", "ffn"), (8192, 24576), MESH3, RULES_FSDP)
    assert spec3 == P(("pod", "data", "model"))
    # indivisible embed dim falls back down the candidate list
    spec_small = spec_for(("embed",), (48,), MESH, RULES_FSDP)
    assert spec_small == P("data")


def test_rules_for_dispatch():
    assert rules_for("tp") is RULES
    assert rules_for("dp") is RULES_DP
    assert rules_for("fsdp") is RULES_FSDP
    assert rules_for("anything-else") is RULES


def test_every_arch_declares_a_known_profile():
    for a in ASSIGNED_ARCHS:
        assert get_config(a).sharding_profile in ("tp", "dp", "fsdp"), a


def test_constrain_is_noop_without_mesh():
    x = jnp.ones((8, 4))
    y = jax.jit(lambda t: constrain(t, ("pod", "data"), None))(x)
    assert (y == x).all()


def test_constrain_applies_under_set_mesh():
    mesh = jax.make_mesh((1,), ("data",))
    with jax.set_mesh(mesh):
        def f(t):
            return constrain(t, ("pod", "data"), None)
        out = jax.jit(f)(jnp.ones((8, 4)))
    assert out.shape == (8, 4)


def test_constrain_drops_indivisible_dims():
    mesh = jax.make_mesh((1,), ("data",))
    with jax.set_mesh(mesh):
        # dim 7 % data-size... size 1 divides everything; use name miss
        out = jax.jit(lambda t: constrain(t, "absent_axis", None))(
            jnp.ones((7, 3)))
    assert out.shape == (7, 3)
