import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from jax.sharding import AbstractMesh
from repro.sharding.logical import spec_for, tree_specs

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_basic_param_specs():
    # ffn weight: embed -> data (FSDP), ffn -> model (TP)
    assert spec_for(("embed", "ffn"), (4096, 12800), MESH) == P("data", "model")
    # attention q: heads -> model
    assert spec_for(("embed", "heads", "head_dim"), (4096, 32, 128), MESH) \
        == P("data", "model")
    # vocab head
    assert spec_for(("embed", "vocab"), (1024, 151936), MESH) == P("data", "model")


def test_divisibility_fallback():
    # whisper: 6 heads don't divide 16 -> replicated
    assert spec_for(("embed", "heads", "head_dim"), (384, 6, 64), MESH) \
        == P("data")
    # embed 384 divides 16? 384/16=24 yes -> data kept
    # xlstm 4 kv heads -> replicated
    assert spec_for(("kv_heads",), (4,), MESH) == P()


def test_no_axis_reuse_within_spec():
    # experts take model; expert_ffn falls back to data; embed then gets nothing
    spec = spec_for(("experts", "embed", "expert_ffn"), (16, 6144, 10752), MESH)
    assert spec == P("model", "data")


def test_client_axis_multipod():
    assert spec_for(("client", "per_client_batch", "seq"),
                    (32, 8, 4096), MESH3) == P(("pod", "data"))
    assert spec_for(("client", "per_client_batch", "seq"),
                    (16, 16, 4096), MESH) == P("data")


def test_cache_batch_fallback_to_seq():
    # long_500k: batch=1 unshardable, cache_seq picks up data
    spec = spec_for(("cache_batch", "cache_seq", "kv_heads", "head_dim"),
                    (1, 524288, 8, 128), MESH)
    assert spec == P(None, "data")
    # decode_32k: batch 128 shards fine, seq replicated (data used)
    spec = spec_for(("cache_batch", "cache_seq", "kv_heads", "head_dim"),
                    (128, 32768, 8, 128), MESH)
    assert spec == P("data", "model")  # seq falls back to model


def test_layers_never_sharded():
    assert spec_for(("layers", "embed", "ffn"), (22, 1024, 2816), MESH) \
        == P(None, "data", "model")


def test_tree_specs_structure():
    axes = {"a": ("embed", "ffn"), "b": {"c": ("vocab",)}}
    shapes = {"a": jax.ShapeDtypeStruct((64, 128), jnp.float32),
              "b": {"c": jax.ShapeDtypeStruct((160,), jnp.float32)}}
    specs = tree_specs(axes, shapes, MESH)
    assert specs["a"] == P("data", "model")
    assert specs["b"]["c"] == P("model")


def test_trailing_nones_trimmed():
    s = spec_for(("heads", "head_dim"), (32, 128), MESH)
    assert s == P("model")
