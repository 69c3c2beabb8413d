"""Each configuration's float32 reference against the served program at
smoke size on the CPU: the engine's prefill, then decode through the
batched cache, with the benchmark's own weights."""

import copy
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from benchlib import harness, spec  # noqa: E402
from repro.models import RuntimeFlags, build_model  # noqa: E402
from repro.serving import GenRequest, InferenceEngine  # noqa: E402

SMALL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "d_ff": 128,
         "vocab_size": 256}


def small_config(name: str, dtype: str) -> dict:
    """The configuration file with its widths cut for the CPU."""
    conf = copy.deepcopy(spec.config(name))
    kv = 2 if conf["model"]["n_kv_heads"] < 4 else 1
    conf["overrides"] = {**conf["overrides"], **SMALL, "n_kv_heads": kv,
                         "vocab_pad_multiple": 64, "dtype": dtype}
    conf["model"].update(SMALL, n_kv_heads=kv, head_dim=16, dtype=dtype)
    return conf


@pytest.mark.parametrize("name", ["glm4-9b-l20", "nemotron-4-15b-l8"])
def test_reference_matches_prefill_then_cached_decode(name):
    conf = small_config(name, "float32")
    m = conf["model"]
    model = build_model(harness.program_config(conf), RuntimeFlags(remat=False))
    ref = spec.reference_module(conf["reference"])
    params = ref.make_params(harness.key_from_seed(7), m)
    harness._check_layout(model, params)

    rng = np.random.default_rng(0)
    n_in, n_out = [5, 9, 3], 6
    prompts = [rng.integers(0, m["vocab_size"], n, dtype=np.int32) for n in n_in]
    eng = InferenceEngine(model, params, max_batch=2, max_seq=16)
    # three requests over two slots: slots at mixed positions, one refilled
    res = eng.generate([GenRequest(uid=i, prompt=jnp.asarray(p),
                                   max_new_tokens=n_out, keep_logits=True)
                        for i, p in enumerate(prompts)])
    for i, p in enumerate(prompts):
        toks = np.concatenate([p, np.asarray(res[i].tokens[:-1], np.int32)])
        want = np.asarray(ref.logits(params, m, jnp.asarray(toks[None]), len(p) - 1))[0]
        got = np.stack([np.asarray(x, np.float32) for x in res[i].logits])
        assert got.shape == want.shape == (n_out, m["vocab_size"])
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_weights_are_a_function_of_the_seed():
    m = small_config("glm4-9b-l20", "bfloat16")["model"]
    ref = spec.reference_module("dense_decoder")
    a = ref.make_params(harness.key_from_seed(2**33 + 5), m)
    b = ref.make_params(harness.key_from_seed(2**33 + 5), m)
    c = ref.make_params(harness.key_from_seed(5), m)
    same = jax.tree.map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree.leaves(same))
    assert not bool((a["layers"]["mlp"]["w1"] == c["layers"]["mlp"]["w1"]).all())
    assert a["embed"].dtype == jnp.bfloat16
    # biases and gains are drawn, so a path that drops one shows
    assert float(jnp.std(a["layers"]["attn"]["bq"].astype(jnp.float32))) > 0.3
    assert float(jnp.std(a["layers"]["attn_norm"].astype(jnp.float32))) > 0.1
