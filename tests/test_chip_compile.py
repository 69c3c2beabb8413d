"""Compile rehearsals for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts: block shapes off the
(8, 128) tiling, kernels over the VMEM budget, programs over HBM. These
tests compile the three Pallas kernels at glm4-9b's published widths, and
one glm4-9b decode step at the one-chip share `chip_smoke.py` serves (20
of 40 layers, max_batch 8, max_seq 2048), for one chip of a `v5e:2x2`
topology. Nothing runs, so they say nothing about results or times.

The topology is described only inside the module fixture: only one process
at a time may load the TPU library, and pytest-xdist workers import every
test module.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.models import RuntimeFlags, build_model

GLM4 = get_config("glm4-9b")
H, K, DH, D = GLM4.n_heads, GLM4.n_kv_heads, GLM4.head_dim, GLM4.d_model
V5E_HBM_BYTES = 16e9  # one v5e chip (Google Cloud, "TPU v5e")


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: entries written for a described chip cannot be read back
    here, and the next compile would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler, or its library is held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("seq", [2048, 15])
def test_flash_attention_compiles(one_chip, seq):
    q = _spec((1, H, seq, DH), jnp.bfloat16, one_chip)
    kv = _spec((1, K, seq, DH), jnp.bfloat16, one_chip)
    compiled = flash_attention.lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_attention_compiles(one_chip):
    B, Sc = 8, 4096
    q = _spec((B, H, DH), jnp.bfloat16, one_chip)
    kv = _spec((B, K, Sc, DH), jnp.bfloat16, one_chip)
    kv_pos = _spec((B, Sc), jnp.int32, one_chip)
    pos = _spec((B,), jnp.int32, one_chip)
    compiled = decode_attention.lower(q, kv, kv, kv_pos, pos).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_rmsnorm_compiles(one_chip):
    x = _spec((2048, D), jnp.bfloat16, one_chip)
    g = _spec((D,), jnp.bfloat16, one_chip)
    compiled = rmsnorm.lower(x, g).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_glm4_decode_step_fits_one_chip(one_chip):
    cfg = dataclasses.replace(GLM4, n_layers=20)
    model = build_model(cfg, RuntimeFlags(remat=False))

    def on_chip(tree):
        return jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip), tree)

    params = on_chip(
        jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    )
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(8, 2048)[0]))
    tok = _spec((8,), jnp.int32, one_chip)
    compiled = jax.jit(model.decode).lower(params, cache, tok, tok).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes < V5E_HBM_BYTES
