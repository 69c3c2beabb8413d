"""Compile rehearsals for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts: block shapes off the
(8, 128) tiling, kernels over the VMEM budget, programs over HBM. These
tests compile the three Pallas kernels at glm4-9b's published widths, and
the engine's glm4-9b decode step and admission programs at the one-chip
share `chip_smoke.py` serves (20 of 40 layers, max_batch 8, max_seq 2048),
for one chip of a `v5e:2x2` topology; and the decode step on the packed
Q/K/V projection the engine serves (`pack_self_attention`). Nothing runs,
so they say nothing about results or times.

The topology is described only inside the module fixture: only one process
at a time may load the TPU library, and pytest-xdist workers import every
test module.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.models import RuntimeFlags, build_model
from repro.models.attention import pack_self_attention, packed_blocks
from repro.serving.engine import decode_step, prefill_step, splice_slot
from repro.sharding import DECODE_RULES, use_mesh

GLM4 = get_config("glm4-9b")
H, K, DH, D = GLM4.n_heads, GLM4.n_kv_heads, GLM4.head_dim, GLM4.d_model
V5E_HBM_BYTES = 16e9  # one v5e chip (Google Cloud, "TPU v5e")


@pytest.fixture(scope="module")
def v5e():
    """A described v5e:2x2, with the persistent compilation cache off:
    entries written for a described chip cannot be read back here, and the
    next compile would warn."""
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
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e):
    """One chip of the described v5e:2x2."""
    return SingleDeviceSharding(v5e.devices[0])


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


def _glm4_on_chip(one_chip, max_seq=2048):
    """glm4-9b-l20's model, and its parameters and max_batch 8 x max_seq
    cache as shapes on the described chip."""
    cfg = dataclasses.replace(GLM4, n_layers=20)
    model = build_model(cfg, RuntimeFlags(remat=False))

    def on_chip(tree):
        return jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip), tree)

    params = on_chip(
        jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    )
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(8, max_seq)[0]))
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


def _scan_body(hlo: str) -> list:
    """The instructions of the compiled program's layer scan body."""
    name = re.search(r"while\(.*?body=%([\w.\-]+)", hlo).group(1)
    lines = hlo.splitlines()
    start = next(i for i, ln in enumerate(lines) if ln.startswith(f"%{name} "))
    body = []
    for ln in lines[start + 1:]:
        if ln.startswith("}"):
            return body
        body.append(ln)
    raise AssertionError("unterminated scan body")


def test_glm4_packed_decode_step_projects_qkv_with_one_dot(one_chip):
    """At the served shapes (max_batch 8, max_seq 64), the decode step on
    packed params has two dots fewer than on the unpacked ones: one for
    Q, K and V instead of three."""
    model, params, cache, on_chip = _glm4_on_chip(one_chip, max_seq=64)
    packed = on_chip(jax.eval_shape(pack_self_attention, params))
    assert packed_blocks(packed) == 20
    tok = _spec((8,), jnp.int32, one_chip)

    def dots(p):
        text = decode_step.lower(model, p, cache, tok, tok).as_text()
        return text.count("stablehlo.dot_general")

    assert dots(params) - dots(packed) == 2


def test_glm4_packed_decode_step_streams_every_weight(one_chip):
    """In the compiled scan body no layer's weight is copied out of its
    stack before its dot, as the unpacked (d, heads, dh) Q/K/V weights
    are: every projection is read where it lies, as the MLP's are."""
    model, params, cache, on_chip = _glm4_on_chip(one_chip, max_seq=64)
    packed = on_chip(jax.eval_shape(pack_self_attention, params))
    tok = _spec((8,), jnp.int32, one_chip)

    def staged(p):
        """Per-layer weight slices the scan body materialises."""
        body = "\n".join(_scan_body(
            decode_step.lower(model, p, cache, tok, tok).compile().as_text()))
        layers = p["layers"]
        shapes = {"[" + ",".join(map(str, (1,) + a.shape[1:])) + "]"
                  for blk in (layers["attn"], layers["mlp"])
                  for a in blk.values() if a.ndim >= 3}
        return {s for s in shapes if f"= bf16{s}" in body}

    assert staged(packed) == set()
    assert staged(params) == {"[1,4096,32,128]", "[1,4096,2,128]"}


@pytest.mark.parametrize("layers, kv_heads, n_packed", [
    (20, 2, 0),  # glm4-9b-l20: 2 KV heads over 4 shards, left unpacked
    (20, 4, 20),  # one KV head a shard: packs
])
def test_packing_keeps_the_kv_head_sharding(v5e, layers, kv_heads, n_packed):
    """Under a mesh whose rules split KV heads 4 ways, a block packs only
    where each shard of the packed columns holds whole KV heads' groups."""
    mesh = Mesh(np.array(v5e.devices).reshape(1, 4), ("data", "model"))
    cfg = dataclasses.replace(GLM4, n_layers=layers, n_kv_heads=kv_heads)
    model = build_model(cfg, RuntimeFlags(remat=False))
    params = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    with use_mesh(mesh, DECODE_RULES):
        packed = jax.eval_shape(pack_self_attention, params)
    assert packed_blocks(packed) == n_packed
    assert packed_blocks(jax.eval_shape(pack_self_attention, params)) == layers
