"""Plain float32 reference of the Nemotron-4 decoder block, and the
benchmark's seeded weights in the parameter layout the served program
takes.

It imports nothing of the program. It follows the block as arXiv:2402.16819
(Nemotron-4 15B, Table 1 and section 2) describes it and as Hugging Face's
`nemotron` model type computes it (NemotronLayerNorm1P,
apply_rotary_pos_emb with partial_rotary_factor, NemotronMLP):

    x = embed[tokens]
    per layer:
        h = ln1p(x; g_attn, b_attn)
        q, k, v = h Wq, h Wk, h Wv                        no biases
        q, k = rope(q), rope(k)     rotate-half over the first
                                    r = partial_rotary_factor * dh dims,
                                    inv_freq = theta ** (-i / (r / 2));
                                    dims r.. pass through
        grouped-query causal softmax(q k^T / sqrt(dh)) v, query head h
        reads kv head h // (H / K)
        x = x + o Wo
        h = ln1p(x; g_mlp, b_mlp)
        x = x + relu(h W1)^2 W2                           no gate
    logits = ln1p(x; g_final, b_final) W_head             untied head

    ln1p(x; g, b) = (x - mean(x)) / sqrt(var(x) + eps) * (1 + g) + b

`m["norm"] == "rms"` puts RMSNorm with the gain (1 + g) and no bias in
ln1p's place, and a `partial_rotary_factor` of 1 rotates the whole head:
the departures the tests hold the program apart from.

Every matrix product runs in float32 at HIGHEST precision: on a TPU a
float32 product otherwise runs in bfloat16. Layers are scanned, each
layer's weights widened to float32 inside the scan; the head runs in blocks
of vocabulary columns. So the reference holds one layer and one head block
in float32 beside the bfloat16 weights.

`quant="fp8"` is the control: the same computation with every matrix
product's operands rounded to float8_e4m3fn (scaled per tensor for weights
and per row for activations, accumulation in float32), the precision step
below the configuration's bfloat16.

`program_mismatch` holds the program's configuration against the file's
sizes and block; `prefill_counts` and `decode_counts` give the operations
and bytes of one served call.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn

# seeded weight scales: fan-in normal for projections, a small embedding,
# and norm gains (1 + g) and biases b with g and b far enough from 0 that a
# dropped bias or a missing 1 shows
GAIN_STD = 0.2
BIAS_STD = 0.2
EMBED_STD = 0.02
BLOCK_BYTES = 512 * 2**20  # float32 temporaries of the weight draw and head

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}
NORMS = ("attn_norm", "mlp_norm")


def _dims(m: dict):
    return (m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"],
            m["head_dim"], m["d_ff"], m["vocab_size"])


def program_mismatch(cfg, m: dict) -> dict:
    """Where the program's configuration `cfg` (a repro ModelConfig, read
    by attribute) runs otherwise than the sizes and block `m` state:
    key -> (program, file). Empty when it runs this block as stated. A
    program without the norm or rotary fields runs RMSNorm over the whole
    head, and says so here."""
    have = {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "d_ff": cfg.d_ff,
            "vocab_size": cfg.padded_vocab, "qkv_bias": cfg.qkv_bias,
            "mlp": cfg.activation, "rope_theta": cfg.rope_theta,
            "partial_rotary_factor": getattr(cfg, "partial_rotary_factor", 1.0),
            "norm": getattr(cfg, "norm", "rms"), "norm_eps": cfg.norm_eps,
            "tie_embeddings": cfg.tie_embeddings, "dtype": cfg.dtype}
    diff = {k: (v, m.get(k)) for k, v in have.items() if m.get(k) != v}
    if cfg.family != "dense":
        diff["family"] = (cfg.family, "dense")
    if cfg.window:
        diff["window"] = (cfg.window, None)
    return diff


# ------------------------------------------------- operations and bytes
#
# Counts are of the work a served call needs, not of what the program
# happens to do. FLOPs: the matrix products over the non-embedding weights
# for each real token, attention over each token's real context (QK^T and
# PV, causal), and the head for each row of logits the call returns.
# Bytes: every weight streamed once (the embedding only as the rows
# gathered), K/V read at the slots' real lengths, K/V written.


def layer_matmul_params(m: dict) -> int:
    d, H, K, dh, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                      m["head_dim"], m["d_ff"])
    return d * H * dh + 2 * d * K * dh + H * dh * d + 2 * d * f


def layer_params(m: dict) -> int:
    """Matrices, and the gain and bias of the two norms."""
    return layer_matmul_params(m) + 4 * m["d_model"]


def _streamed_weight_bytes(m: dict, rows_gathered: int) -> int:
    d, V, L = m["d_model"], m["vocab_size"], m["n_layers"]
    n = L * layer_params(m) + d * V + 2 * d + rows_gathered * d
    return n * ITEMSIZE[m["dtype"]]


def _kv_bytes_per_token(m: dict) -> int:
    return m["n_layers"] * 2 * m["n_kv_heads"] * m["head_dim"] * ITEMSIZE[m["dtype"]]


def prefill_counts(m: dict, s: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of a batch-1 prefill of s tokens that returns the
    last position's logits."""
    L, H, dh, d, V = (m["n_layers"], m["n_heads"], m["head_dim"],
                      m["d_model"], m["vocab_size"])
    pairs = s * (s + 1) // 2  # causal (query, key) pairs
    flops = L * (2 * layer_matmul_params(m) * s + 4 * H * dh * pairs) + 2 * d * V
    nbytes = _streamed_weight_bytes(m, s) + s * _kv_bytes_per_token(m)
    return float(flops), float(nbytes)


def decode_counts(m: dict, positions: Sequence[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step over the active slots, the new
    token of slot b at position positions[b] (it attends to
    positions[b] cached tokens and to itself)."""
    L, H, dh, d, V = (m["n_layers"], m["n_heads"], m["head_dim"],
                      m["d_model"], m["vocab_size"])
    B = len(positions)
    ctx = sum(int(p) + 1 for p in positions)
    flops = L * (2 * layer_matmul_params(m) * B + 4 * H * dh * ctx) + 2 * d * V * B
    cached = sum(int(p) for p in positions)
    nbytes = _streamed_weight_bytes(m, B) + (cached + B) * _kv_bytes_per_token(m)
    return float(flops), float(nbytes)


# --------------------------------------------------------------- weights


def _freeze(m: dict) -> tuple:
    return tuple(sorted(m.items()))


def _block(n: int, cols: int) -> int:
    """Largest divisor of n whose (rows, cols) float32 block fits
    BLOCK_BYTES."""
    cap = max(1, BLOCK_BYTES // (4 * cols))
    return max(b for b in range(1, min(n, cap) + 1) if n % b == 0)


def param_shapes(m: dict) -> dict:
    """The program's parameter tree for this block, as shapes."""
    L, d, H, K, dh, f, V = _dims(m)
    layers = {"attn": {"wq": (L, d, H, dh), "wk": (L, d, K, dh),
                       "wv": (L, d, K, dh), "wo": (L, H, dh, d)},
              "mlp": {"w1": (L, d, f), "w2": (L, f, d)}}
    for name in NORMS:
        layers[name] = layers[name + "_bias"] = (L, d)
    return {"embed": (V, d), "final_norm": (d,), "final_norm_bias": (d,),
            "lm_head": (d, V), "layers": layers}


def make_params(key: jax.Array, m: dict) -> dict:
    """Seeded weights on the default device, in m["dtype"], by one jitted
    program; float32 draws are made a layer or a block of rows at a time."""
    return _make_params(key, _freeze(m))


@functools.partial(jax.jit, static_argnums=1)
def _make_params(key, mf):
    m = dict(mf)
    dt = jnp.dtype(m["dtype"])
    L, d, H, K, dh, f, V = _dims(m)

    def normal(k, shape, std):
        return (jax.random.normal(k, shape, F32) * std).astype(dt)

    def rows(k, n, cols, std):
        b = _block(n, cols)
        out = jax.lax.map(lambda kk: normal(kk, (b, cols), std),
                          jax.random.split(k, n // b))
        return out.reshape(n, cols)

    def norm(k, name):
        kg, kb = jax.random.split(k)
        return {name: normal(kg, (d,), GAIN_STD),
                name + "_bias": normal(kb, (d,), BIAS_STD)}

    def layer(k):
        ks = jax.random.split(k, 8)
        return {"attn": {"wq": normal(ks[0], (d, H, dh), d ** -0.5),
                         "wk": normal(ks[1], (d, K, dh), d ** -0.5),
                         "wv": normal(ks[2], (d, K, dh), d ** -0.5),
                         "wo": normal(ks[3], (H, dh, d), (H * dh) ** -0.5)},
                "mlp": {"w1": normal(ks[4], (d, f), d ** -0.5),
                        "w2": normal(ks[5], (f, d), f ** -0.5)},
                **norm(ks[6], "attn_norm"), **norm(ks[7], "mlp_norm")}

    k_emb, k_head, k_norm, k_layers = jax.random.split(key, 4)
    return {"embed": rows(k_emb, V, d, EMBED_STD),
            "lm_head": rows(k_head, d, V, d ** -0.5),
            **norm(k_norm, "final_norm"),
            "layers": jax.lax.map(layer, jax.random.split(k_layers, L))}


# ---------------------------------------------------------------- forward


def _fp8(x: jax.Array, axis) -> jax.Array:
    """Round to float8_e4m3fn with a per-slice scale, back in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(eq: str, a: jax.Array, w: jax.Array, quant: Optional[str],
        w_is_weight: bool = True) -> jax.Array:
    if quant == "fp8":
        a = _fp8(a, axis=-1)
        w = _fp8(w, axis=None) if w_is_weight else _fp8(w, axis=-1)
    return jnp.einsum(eq, a, w, precision=HIGHEST, preferred_element_type=F32)


def _norm(m, x, g, b):
    if m["norm"] == "rms":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + m["norm_eps"]) * (1.0 + g)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + m["norm_eps"]) * (1.0 + g) + b


def _rope(m, x):
    """x: (B, S, n, dh), positions 0..S-1; rotate-half over the first r
    dims of each head, the rest unchanged."""
    S, dh = x.shape[1], x.shape[-1]
    r = int(dh * m["partial_rotary_factor"])
    half = r // 2
    inv = 1.0 / (m["rope_theta"] ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv  # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:r]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., r:]], -1)


def _layer(m, quant, x, lp):
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    B, S, _ = x.shape
    H, K, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    G = H // K
    a = lp["attn"]
    h = _norm(m, x, lp["attn_norm"], lp["attn_norm_bias"])
    q = _rope(m, _mm("bsd,dnh->bsnh", h, a["wq"], quant))
    k = _rope(m, _mm("bsd,dnh->bsnh", h, a["wk"], quant))
    v = _mm("bsd,dnh->bsnh", h, a["wv"], quant)
    k = jnp.repeat(k, G, axis=2)  # query head n reads kv head n // G
    v = jnp.repeat(v, G, axis=2)
    s = _mm("bqnh,bsnh->bnqs", q, k, quant, w_is_weight=False) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _mm("bnqs,bsnh->bqnh", p, v, quant, w_is_weight=False)
    x = x + _mm("bqnh,nhd->bqd", o, a["wo"], quant)
    h = _norm(m, x, lp["mlp_norm"], lp["mlp_norm_bias"])
    u = jnp.square(jax.nn.relu(_mm("bsd,df->bsf", h, lp["mlp"]["w1"], quant)))
    return x + _mm("bsf,fd->bsd", u, lp["mlp"]["w2"], quant), None


def logits(params: dict, m: dict, tokens: jax.Array, out_start: int,
           quant: Optional[str] = None) -> jax.Array:
    """float32 logits (B, S - out_start, V) at positions out_start..S-1 of
    tokens (B, S): the logits that predict tokens out_start+1..S."""
    return _logits(params, tokens, _freeze(m), int(out_start), quant)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _logits(params, tokens, mf, out_start, quant):
    m = dict(mf)
    x = params["embed"][tokens].astype(F32)
    x, _ = jax.lax.scan(functools.partial(_layer, m, quant), x,
                        params["layers"])
    h = _norm(m, x[:, out_start:], params["final_norm"].astype(F32),
              params["final_norm_bias"].astype(F32))
    w = params["lm_head"]
    d, V = w.shape
    b = _block(V, d)

    def block(i):
        wb = jax.lax.dynamic_slice_in_dim(w, i * b, b, axis=1).astype(F32)
        return _mm("btd,dv->btv", h, wb, quant)

    out = jax.lax.map(block, jnp.arange(V // b))  # (nb, B, T, b)
    return jnp.moveaxis(out, 0, 2).reshape(h.shape[0], h.shape[1], V)
