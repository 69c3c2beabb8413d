"""Shared fixtures. Tests run on the single real CPU device — the 512-device
dry-run flag is set ONLY inside repro.launch.dryrun, never here."""

import dataclasses
import os

# keep tests single-device and deterministic
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import pytest

ASSIGNED_ARCHS = [
    "qwen1.5-110b",
    "qwen2-vl-72b",
    "mixtral-8x22b",
    "seamless-m4t-large-v2",
    "glm4-9b",
    "nemotron-4-15b",
    "zamba2-7b",
    "mistral-large-123b",
    "xlstm-1.3b",
    "llama4-scout-17b-a16e",
]

_model_cache = {}


def smoke_model(name: str, **rt_kw):
    """Session-cached (model, params) for a smoke config in float32."""
    from repro.configs import get_config
    from repro.models import RuntimeFlags, build_model

    rt = RuntimeFlags(remat=False, mamba_chunk=4, mlstm_chunk=4, **rt_kw)
    key = (name, tuple(sorted(rt_kw.items())))
    if key not in _model_cache:
        cfg = dataclasses.replace(get_config(name, smoke=True), dtype="float32")
        model = build_model(cfg, rt)
        params, axes = model.init(jax.random.PRNGKey(0))
        _model_cache[key] = (model, params, axes)
    return _model_cache[key]


def sample_inputs(model, batch=2, seq=12, extra=0, key=0):
    """(inputs-for-forward, labels) matching the arch's input modality."""
    cfg = model.cfg
    S = seq + extra
    toks = jax.random.randint(jax.random.PRNGKey(key), (batch, S), 0, cfg.vocab_size)
    if cfg.n_encoder_layers:
        emb = (
            jax.random.normal(jax.random.PRNGKey(key + 1), (batch, S, cfg.d_model))
            * 0.02
        )
        return {"enc_embeds": emb, "dec_tokens": toks}, toks
    if cfg.embeds_input:
        emb = (
            jax.random.normal(jax.random.PRNGKey(key + 1), (batch, S, cfg.d_model))
            * 0.02
        )
        return emb, toks
    return toks, toks


@pytest.fixture(params=ASSIGNED_ARCHS)
def arch_name(request):
    return request.param
