"""The Pallas kernels compile for a described TPU v5e at real widths.

Nothing runs: each test lowers one kernel with ``interpret=False`` for
one chip of a described ``v5e:2x2`` topology and compiles it with the
installed TPU compiler, which refuses what Mosaic cannot lower (block
shapes off the layout tile, too much VMEM). Widths: qwen1.5-0.5b's
split boundary (2048 tokens, d=1024, V=151,936) and attention (16 heads
x 2048 x 64), xlstm-1.3b's mLSTM head (S=2048, head dim 512).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attn.kernel import flash_attention_pallas
from repro.kernels.lace.kernel import lace2_bwd_pallas, lace2_fwd_pallas
from repro.kernels.mlstm.kernel import mlstm_chunk_pallas

N, D, V = 2048, 1024, 151936


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler log files
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to test
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described chip's compile cannot be read back from a persistent
        # cache; keep these compiles out of one an entry point turned on
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def test_lace2_fwd_compiles_for_v5e(one_chip):
    c = _compile(lambda f, w, l, ps, pk: lace2_fwd_pallas(
        f, w, l, ps, pk, interpret=False),
        ((N, D), jnp.float32), ((D, V), jnp.float32), ((N,), jnp.int32),
        ((V,), jnp.float32), ((V,), jnp.float32), sharding=one_chip)
    assert "tpu_custom_call" in c.as_text()


def test_lace2_bwd_compiles_for_v5e(one_chip):
    c = _compile(lambda f, w, l, ps, pk, ls, lk, gs, gk: lace2_bwd_pallas(
        f, w, l, ps, pk, ls, lk, gs, gk, interpret=False),
        ((N, D), jnp.float32), ((D, V), jnp.float32), ((N,), jnp.int32),
        ((V,), jnp.float32), ((V,), jnp.float32),
        *[((N,), jnp.float32)] * 4, sharding=one_chip)
    assert "tpu_custom_call" in c.as_text()


def test_flash_attention_compiles_for_v5e(one_chip):
    c = _compile(lambda q, k, v: flash_attention_pallas(
        q, k, v, interpret=False),
        *[((16, 2048, 64), jnp.bfloat16)] * 3, sharding=one_chip)
    assert "tpu_custom_call" in c.as_text()


def test_mlstm_chunk_compiles_for_v5e(one_chip):
    c = _compile(lambda q, k, v, i, f: mlstm_chunk_pallas(
        q, k, v, i, f, interpret=False),
        *[((2048, 512), jnp.float32)] * 3, *[((2048,), jnp.float32)] * 2,
        sharding=one_chip)
    assert "tpu_custom_call" in c.as_text()
