"""The Nemotron-4 block's own pieces against Hugging Face's `nemotron`
model type, the scopes the served decode step carries, and glm4-9b's
served logits, which the norm and rotary fields must leave as they were."""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import RuntimeFlags, build_model
from repro.models.common import norm
from repro.models.rope import apply_rope
from repro.serving import GenRequest, InferenceEngine
from repro.serving.engine import decode_step

DATA = os.path.join(os.path.dirname(__file__), "data")


def _nemotron_hf():
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip("transformers.models.nemotron.modeling_nemotron")
    return torch, hf


def test_layernorm1p_matches_transformers():
    torch, hf = _nemotron_hf()
    cfg = get_config("nemotron-4-15b", smoke=True)
    d = cfg.d_model
    rng = np.random.default_rng(0)
    x = rng.normal(2.0, 3.0, (3, 5, d)).astype(np.float32)
    gamma = rng.normal(0.0, 0.2, d).astype(np.float32)
    beta = rng.normal(0.0, 0.5, d).astype(np.float32)
    ln = hf.NemotronLayerNorm1P(d, eps=cfg.norm_eps)
    with torch.no_grad():
        ln.weight.copy_(torch.from_numpy(gamma))
        ln.bias.copy_(torch.from_numpy(beta))
        want = ln(torch.from_numpy(x)).numpy()
    got = norm(jnp.asarray(x), {"n": gamma, "n_bias": beta}, "n", cfg)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_partial_rope_matches_transformers():
    torch, hf = _nemotron_hf()
    from transformers import NemotronConfig

    cfg = get_config("nemotron-4-15b")  # head_dim 128, 64 dims rotate
    B, S, H, D = 2, 11, 3, cfg.head_dim
    assert cfg.rope_dim == 64
    hcfg = NemotronConfig(hidden_size=H * D, num_attention_heads=H,
                          num_key_value_heads=H, rope_theta=cfg.rope_theta,
                          partial_rotary_factor=cfg.partial_rotary_factor)
    rng = np.random.default_rng(1)
    q = rng.normal(size=(B, S, H, D)).astype(np.float32)
    k = rng.normal(size=(B, S, H, D)).astype(np.float32)
    pos = np.stack([np.arange(S), np.arange(S) + 40]).astype(np.int64)
    with torch.no_grad():
        tq = torch.from_numpy(q).transpose(1, 2)  # (B, H, S, D)
        tk = torch.from_numpy(k).transpose(1, 2)
        cos, sin = hf.NemotronRotaryEmbedding(hcfg)(tq, torch.from_numpy(pos))
        wq, wk = hf.apply_rotary_pos_emb(tq, tk, cos, sin)
    for x, want in ((q, wq), (k, wk)):
        got = apply_rope(jnp.asarray(x), jnp.asarray(pos, jnp.int32), D,
                         cfg.rope_theta, cfg.rope_dim)
        np.testing.assert_allclose(np.asarray(got),
                                   want.transpose(1, 2).numpy(),
                                   rtol=1e-5, atol=1e-5)
    # the second half of each head passes through untouched
    got = apply_rope(jnp.asarray(q), jnp.asarray(pos, jnp.int32), D,
                     cfg.rope_theta, cfg.rope_dim)
    np.testing.assert_array_equal(np.asarray(got)[..., 64:], q[..., 64:])


def test_glm4_smoke_logits_are_unchanged():
    """glm4-9b keeps the default fields (RMSNorm, RoPE over the whole
    head), so the engine serves it the logits it served before the fields
    existed: prefill, then two steps of decode_step through the cache.
    The recorded logits were written by the program before the change."""
    with open(os.path.join(DATA, "glm4_smoke_logits.json")) as f:
        rec = json.load(f)
    cfg = dataclasses.replace(get_config("glm4-9b", smoke=True), dtype="float32")
    assert (cfg.norm, cfg.partial_rotary_factor) == ("rms", 1.0)
    model = build_model(cfg, RuntimeFlags(remat=False))
    params, _ = model.init(jax.random.PRNGKey(0))
    prompt = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, 7, dtype=np.int32))
    res = InferenceEngine(model, params, max_batch=2, max_seq=16).generate(
        [GenRequest(uid=0, prompt=prompt, max_new_tokens=3, keep_logits=True)])[0]
    assert res.tokens == rec["tokens"]
    np.testing.assert_allclose(np.stack([np.asarray(x) for x in res.logits]),
                               np.asarray(rec["logits"], np.float32),
                               rtol=1e-6, atol=1e-6)


def test_decode_step_ops_carry_the_block_scopes():
    """The compiled decode step's op metadata names each part of the block,
    so a device trace can put decode time down to a layer type."""
    cfg = get_config("nemotron-4-15b", smoke=True)
    model = build_model(cfg, RuntimeFlags(remat=False))
    params = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(2, 16)[0])
    tok = jax.ShapeDtypeStruct((2,), jnp.int32)
    hlo = decode_step.lower(model, params, cache, tok, tok).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in ("attn_norm", "attention", "rope", "mlp_norm", "mlp",
                  "final_norm", "lm_head"):
        assert any(f"/{scope}/" in n for n in names), scope
    assert any("/attention/rope/" in n for n in names)
