"""The serving layout of self-attention: Q, K and V packed into one matrix.

`InferenceEngine` serves a copy of its params in which each self-attention
block's wq, wk and wv are one leaf wqkv (`pack_self_attention`). These
tests hold the packed layout to its definition, hold an engine on packed
params to the tokens and logits of one on the caller's own params, and
count the blocks packed at the benchmark's served shapes.
"""

import dataclasses
import weakref

import jax
import numpy as np
import pytest

from conftest import smoke_model
from repro.configs import get_config
from repro.models import RuntimeFlags, build_model
from repro.models.attention import pack_self_attention, packed_blocks
from repro.serving import GenRequest, InferenceEngine
from repro.serving import calibrate
from repro.serving import engine as engine_mod

# float32 smoke models: the packed dot sums the same products as the three
# separate ones, so logits differ by float32 rounding only
LOGIT_ATOL = 1e-4

UNPACKED = ("wq", "wk", "wv", "bq", "bk", "bv")


def _requests(m, n_reqs=3):
    """Requests of several lengths: with two slots, the third is admitted
    into a freed slot beside a request still decoding."""
    reqs = []
    for i in range(n_reqs):
        dec = jax.random.randint(jax.random.PRNGKey(10 + i), (5 + i,), 0,
                                 m.cfg.vocab_size)
        prompt = dec
        if m.is_encdec:
            enc = jax.random.normal(jax.random.PRNGKey(20 + i),
                                    (8, m.cfg.d_model)) * 0.02
            prompt = {"enc_embeds": enc, "dec_tokens": dec}
        reqs.append(GenRequest(uid=i, prompt=prompt, max_new_tokens=3 + i,
                               keep_logits=True))
    return reqs


def _serve_both(m, p, monkeypatch):
    """(packed engine, its results, unpacked engine, its results)."""
    enc_len = 8 if m.is_encdec else 0
    packed = InferenceEngine(m, p, max_batch=2, max_seq=24, enc_len=enc_len)
    got = packed.generate(_requests(m))
    with monkeypatch.context() as mp:
        mp.setattr(engine_mod, "pack_self_attention", lambda params: params)
        plain = InferenceEngine(m, p, max_batch=2, max_seq=24,
                                enc_len=enc_len)
    want = plain.generate(_requests(m))
    return packed, got, plain, want


def _assert_same_results(got, want):
    for uid, w in want.items():
        assert got[uid].tokens == w.tokens, uid
        assert len(got[uid].logits) == len(w.logits)
        for a, b in zip(got[uid].logits, w.logits):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0, atol=LOGIT_ATOL)


@pytest.mark.parametrize("name, blocks", [
    ("glm4-9b", None),  # dense, QKV bias
    ("nemotron-4-15b", None),  # partial RoPE after the projection, no bias
    ("qwen2-vl-72b", None),  # vlm: M-RoPE after the projection
    ("mixtral-8x22b", None),  # moe
    ("zamba2-7b", 1),  # hybrid: one weight-shared attention block
])
def test_packed_engine_matches_unpacked(name, blocks, monkeypatch):
    """Through `submit` and `step` over two slots, an engine on packed
    params serves the greedy tokens of one on the unpacked params, with
    logits equal to float32 rounding."""
    m, p, _ = smoke_model(name)
    packed, got, plain, want = _serve_both(m, p, monkeypatch)
    assert packed.packed_attn_blocks == (blocks or m.cfg.n_layers)
    assert plain.packed_attn_blocks == 0
    assert packed.counters.slot_steps > packed.counters.steps  # slots shared
    _assert_same_results(got, want)


def test_encdec_packs_self_attention_and_leaves_cross_attention(monkeypatch):
    """Encoder and decoder self-attention pack; cross-attention, whose K/V
    read the encoder memory, keeps its own arrays; the results match."""
    m, p, _ = smoke_model("seamless-m4t-large-v2")
    packed, got, _, want = _serve_both(m, p, monkeypatch)
    sp = packed.params
    assert "wqkv" in sp["enc_layers"]["attn"]
    assert "wqkv" in sp["dec_layers"]["self_attn"]
    cross = p["dec_layers"]["cross_attn"]
    assert sp["dec_layers"]["cross_attn"].keys() == cross.keys()
    assert all(sp["dec_layers"]["cross_attn"][k] is w for k, w in cross.items())
    assert packed.packed_attn_blocks == m.cfg.n_encoder_layers + m.cfg.n_layers
    _assert_same_results(got, want)


def test_packed_layout():
    """The columns of KV head k hold its G query heads (h = k * G + g),
    then its K, then its V, and the biases the same; every other leaf is
    the input's own array."""
    m, p, _ = smoke_model("glm4-9b")
    cfg = m.cfg
    L, d, dh = cfg.n_layers, cfg.d_model, cfg.head_dim
    K, G = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    assert G > 1 and K > 1 and cfg.qkv_bias
    sp = pack_self_attention(p)
    a, pa = p["layers"]["attn"], sp["layers"]["attn"]
    assert set(pa) == set(a) - set(UNPACKED) | {"wqkv", "bqkv"}
    assert pa["wqkv"].shape == (L, d, K * (G + 2) * dh)
    assert pa["bqkv"].shape == (L, K * (G + 2) * dh)
    w = np.asarray(pa["wqkv"]).reshape(L, d, K, G + 2, dh)
    b = np.asarray(pa["bqkv"]).reshape(L, K, G + 2, dh)
    for k in range(K):
        for g in range(G):
            np.testing.assert_array_equal(w[:, :, k, g],
                                          a["wq"][:, :, k * G + g])
            np.testing.assert_array_equal(b[:, k, g], a["bq"][:, k * G + g])
        np.testing.assert_array_equal(w[:, :, k, G], a["wk"][:, :, k])
        np.testing.assert_array_equal(w[:, :, k, G + 1], a["wv"][:, :, k])
        np.testing.assert_array_equal(b[:, k, G], a["bk"][:, k])
        np.testing.assert_array_equal(b[:, k, G + 1], a["bv"][:, k])

    def kept(path, leaf):
        return jax.tree_util.keystr(path), leaf

    want = dict(kept(pth, x) for pth, x in jax.tree_util.tree_leaves_with_path(p)
                if pth[-1].key not in UNPACKED)
    have = dict(kept(pth, x) for pth, x in jax.tree_util.tree_leaves_with_path(sp)
                if pth[-1].key not in ("wqkv", "bqkv"))
    assert want.keys() == have.keys()
    assert all(have[k] is want[k] for k in want), "a leaf was copied"


def test_packing_is_idempotent():
    m, p, _ = smoke_model("nemotron-4-15b")
    sp = pack_self_attention(p)
    again = pack_self_attention(sp)
    assert again["layers"]["attn"]["wqkv"] is sp["layers"]["attn"]["wqkv"]
    assert packed_blocks(again) == packed_blocks(sp) == m.cfg.n_layers
    assert packed_blocks(p) == 0


@pytest.mark.parametrize("name, layers, cols", [
    ("glm4-9b", 20, 2 * (16 + 2) * 128),  # glm4-9b-l20
    ("nemotron-4-15b", 8, 8 * (6 + 2) * 128),  # nemotron-4-15b-pp4
])
def test_packed_attn_blocks_at_served_shapes(name, layers, cols):
    """The benchmark's configurations as shapes: every layer packs."""
    cfg = dataclasses.replace(get_config(name), n_layers=layers)
    model = build_model(cfg, RuntimeFlags(remat=False))
    params = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    sp = jax.eval_shape(pack_self_attention, params)
    assert packed_blocks(sp) == layers
    assert sp["layers"]["attn"]["wqkv"].shape == (layers, cfg.d_model, cols)


def test_params_set_again_are_packed_again():
    """Params handed to a built engine are packed too; None drops the
    packed copy."""
    m, p, _ = smoke_model("glm4-9b")
    eng = InferenceEngine(m, p, max_batch=1, max_seq=16)
    eng.params = None
    assert eng.params is None and eng.packed_attn_blocks == 0
    eng.params = p
    assert eng.packed_attn_blocks == m.cfg.n_layers
    assert eng.params["embed"] is p["embed"]


def test_measure_service_time_holds_one_engine(monkeypatch):
    """Calibration builds one engine and resets it between repeats, so
    at most one packed copy of the params is alive."""
    alive = weakref.WeakSet()
    most = []

    class Counted(InferenceEngine):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            alive.add(self)
            most.append(len(alive))

    monkeypatch.setattr(calibrate, "InferenceEngine", Counted)
    m, p, _ = smoke_model("glm4-9b")
    t = calibrate.measure_service_time(m, p, n_input=4, n_output=3,
                                       max_seq=16, repeats=3)
    assert most == [1]
    assert t["total_s"] > 0
