"""Compile rehearsals for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts: block shapes off the
(8, 128) tiling, kernels over the VMEM budget, programs over HBM. These
tests compile the three Pallas kernels at glm4-9b's published widths, and
the engine's glm4-9b decode step and admission programs at the one-chip
share `chip_smoke.py` serves (20 of 40 layers, max_batch 8, max_seq 2048),
for one chip of a `v5e:2x2` topology. Nothing runs, so they say nothing
about results or times.

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
from repro.serving.engine import decode_step, prefill_step, splice_slot

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


def _glm4_on_chip(one_chip):
    """glm4-9b-l20's model, and its parameters and max_batch 8 x max_seq
    2048 cache as shapes on the described chip."""
    cfg = dataclasses.replace(GLM4, n_layers=20)
    model = build_model(cfg, RuntimeFlags(remat=False))

    def on_chip(tree):
        return jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip), tree)

    params = on_chip(
        jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    )
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(8, 2048)[0]))
    return model, params, cache, on_chip


def test_glm4_decode_step_fits_one_chip(one_chip):
    model, params, cache, _ = _glm4_on_chip(one_chip)
    tok = _spec((8,), jnp.int32, one_chip)
    # the engine's served program: decode and greedy argmax in one
    compiled = decode_step.lower(model, params, cache, tok, tok).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes < V5E_HBM_BYTES


def test_glm4_admission_splices_in_place(one_chip):
    """The engine's admission programs for a 15-token prompt: the prefill
    with its greedy token, then the splice, whose output is the donated
    cache's own buffers."""
    model, params, cache, on_chip = _glm4_on_chip(one_chip)
    prompt = _spec((1, 15), jnp.int32, one_chip)
    prefill_step.lower(model, params, prompt).compile()
    _, _, cache1 = jax.eval_shape(
        lambda p, x: prefill_step(model, p, x), params, prompt)
    _, caxes = model.init_cache(1, 1)
    axes = tuple(ax.index("kv_batch") for ax in jax.tree.leaves(caxes))
    compiled = splice_slot.lower(
        cache, on_chip(cache1), _spec((), jnp.int32, one_chip),
        batch_axes=axes).compile()
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert compiled.memory_analysis().alias_size_in_bytes == cache_bytes
