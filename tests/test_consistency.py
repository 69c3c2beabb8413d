"""Prefill/decode vs full-forward consistency for every assigned arch.

This is the strongest end-to-end correctness check in the suite: the
chunked-parallel forms (flash attention, SSD scan, chunked mLSTM) must
agree with the step recurrences/cached decode to float32 tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import pad_kv, sample_inputs, smoke_model

TOL = 2e-3
S, EXTRA, B = 12, 3, 2


def test_prefill_matches_forward(arch_name):
    model, params, _ = smoke_model(arch_name)
    inputs, _ = sample_inputs(model, batch=B, seq=S)
    logits_full, _ = model.forward(params, inputs)
    lg, _ = model.prefill(params, inputs)
    np.testing.assert_allclose(
        np.asarray(lg), np.asarray(logits_full[:, -1]), rtol=TOL, atol=TOL
    )


def test_decode_matches_forward(arch_name):
    model, params, _ = smoke_model(arch_name)
    inputs, labels = sample_inputs(model, batch=B, seq=S, extra=EXTRA)
    logits_full, _ = model.forward(params, inputs)

    def slice_prompt(x, n):
        if isinstance(x, dict):
            return {
                "enc_embeds": x["enc_embeds"],
                "dec_tokens": x["dec_tokens"][:, :n],
            }
        return x[:, :n]

    lg, cache = model.prefill(params, slice_prompt(inputs, S))
    cache = pad_kv(cache, EXTRA)
    for i in range(EXTRA):
        pos = jnp.full((B,), S + i, jnp.int32)
        if isinstance(inputs, dict):
            tok = inputs["dec_tokens"][:, S + i]
        elif inputs.ndim == 3:
            tok = inputs[:, S + i]  # frontend embeds
        else:
            tok = inputs[:, S + i]
        lg, cache = model.decode(params, cache, tok, pos)
        np.testing.assert_allclose(
            np.asarray(lg),
            np.asarray(logits_full[:, S + i]),
            rtol=TOL,
            atol=TOL,
            err_msg=f"{arch_name} decode step {i}",
        )


def test_sliding_window_ring_cache():
    """Dense arch forced into the long_500k window variant: decode with a
    ring cache must equal full forward with the same window mask."""
    model, params, _ = smoke_model("glm4-9b", window_override=8)
    W = 8
    toks = jax.random.randint(jax.random.PRNGKey(9), (B, 20), 0, 1024)
    logits_full, _ = model.forward(params, toks)  # window applied in-stack
    cache, _ = model.init_cache(B, W)
    # feed tokens one by one through decode only (pure ring)
    for t in range(20):
        pos = jnp.full((B,), t, jnp.int32)
        lg, cache = model.decode(params, cache, toks[:, t], pos)
        if t >= 1:
            np.testing.assert_allclose(
                np.asarray(lg), np.asarray(logits_full[:, t]),
                rtol=5e-3, atol=5e-3, err_msg=f"t={t}",
            )
