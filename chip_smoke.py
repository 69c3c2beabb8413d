#!/usr/bin/env python3
"""Smoke test of the serving path on one TPU chip at glm4-9b's published widths.

    python chip_smoke.py

Runs in one process and stops with a non-zero exit at the first failure:

1. device   The first JAX device must be a TPU. On anything else the script
            exits 1 before doing any work.
2. kernels  flash_attention, decode_attention and rmsnorm compiled for the
            chip (interpret=False) at glm4-9b widths, each compared with its
            oracle in repro.kernels.ref.
3. serve    glm4-9b at published widths in bf16, cut to 20 of its 40 layers
            (one stage of a two-chip pipeline), random weights from a seed.
            `repro.launch.serve.serve` makes the params on the device with
            one jitted program, calibrates, warms up an
            InferenceEngine(max_batch=8, max_seq=2048) and serves a seeded
            Poisson trace of Table-I requests (15 tokens in, 15 out) through
            ICCServer(policy="priority"). Every request sent is served or
            dropped, every served one has 15 tokens and finite logits, and
            one served request's logits (prefill, then decode through the
            cache) agree with model.forward over the same tokens.
4. report   Init, warm-up and serve seconds, tokens produced, peak device
            bytes, and the attention path the served model took.

The compile cache goes where JAX_COMPILATION_CACHE_DIR says, else to
.jax_cache/ at the repo root. The last line of stdout is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.decode_attention import decode_attention  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.kernels.rmsnorm import rmsnorm  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.serve import serve  # noqa: E402
from repro.models.attention import core_for_keys  # noqa: E402

N_LAYERS = 20  # of glm4-9b's 40: one stage of a two-chip pipeline
MAX_BATCH, MAX_SEQ = 8, 2048
N_INPUT = N_OUTPUT = 15  # Table I
MIN_REQUESTS = 16

# Kernel vs oracle, bf16 in and out. bf16 keeps 8 significant bits, so one
# rounding moves a value of magnitude m by up to m * 2**-9; the kernels and
# the oracles round at different points (softmax weights, the normalised
# row before the gain), each output at most twice, so |err| <= 2e-2 * (1 +
# |ref|) covers them. tests/test_kernels.py holds the interpreted kernels to
# the same bound.
KERNEL_TOL = 2e-2

# Served logits vs model.forward, relative L2 error over the compared
# positions. Both are bf16 programs over the same weights that round at
# different points (cached K/V, the two-part decode softmax, matmul
# shapes), so their logits differ by bf16 rounding noise that grows slowly
# with depth: at glm4-9b widths XLA:CPU gives 0.96e-2 at 2 layers and
# 1.1e-2 at 4. 3e-2 leaves room for 20 layers. Decode RoPE one position
# off gives 0.2-0.3 on the same check, and an 8-bit float rounds 16x
# coarser than bf16.
LOGITS_REL_TOL = 3e-2


def require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def device_phase() -> dict:
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        sys.exit(f"[device] needs a TPU, JAX found {d.platform} "
                 f"({d.device_kind}); nothing was run")
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def _close(name: str, got: jax.Array, want: jax.Array) -> None:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)))
    print(f"[kernels] {name}: max |err| {err!r}")
    np.testing.assert_allclose(got, want, rtol=KERNEL_TOL, atol=KERNEL_TOL,
                               err_msg=name)


def kernel_phase(cfg, *, interpret: bool = False, seqs=(2048, 15),
                 batch: int = 8, cache_len: int = 4096, rows: int = 2048):
    """Each Pallas kernel at `cfg`'s head and model widths against its
    oracle: prefill flash attention at each of `seqs`, decode attention
    over a (batch, cache_len) cache, RMSNorm over `rows` rows."""
    H, K, dh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model

    def rand(i, shape):
        return jax.random.normal(
            jax.random.PRNGKey(i), shape, jnp.float32
        ).astype(jnp.bfloat16)

    for S in seqs:
        q, k, v = rand(0, (1, H, S, dh)), rand(1, (1, K, S, dh)), rand(2, (1, K, S, dh))
        _close(f"flash_attention B1 H{H} K{K} S{S} dh{dh}",
               flash_attention(q, k, v, interpret=interpret),
               ref.flash_attention_ref(q, k, v))

    q = rand(3, (batch, H, dh))
    k, v = rand(4, (batch, K, cache_len, dh)), rand(5, (batch, K, cache_len, dh))
    # slots hold different lengths, the rest of each row is empty
    lens = jnp.linspace(1, cache_len, batch).astype(jnp.int32)
    slots = jnp.arange(cache_len, dtype=jnp.int32)[None, :]
    kv_pos = jnp.where(slots < lens[:, None], slots, -1)
    pos = lens - 1
    _close(f"decode_attention B{batch} H{H} K{K} Sc{cache_len} dh{dh}",
           decode_attention(q, k, v, kv_pos, pos, interpret=interpret),
           ref.decode_attention_ref(q, k, v, kv_pos, pos))

    x = rand(6, (rows, d))
    g = (1.0 + 0.1 * rand(7, (d,)).astype(jnp.float32)).astype(jnp.bfloat16)
    _close(f"rmsnorm {rows}x{d}",
           rmsnorm(x, g, eps=cfg.norm_eps, interpret=interpret),
           ref.rmsnorm_ref(x, g, cfg.norm_eps))


def logits_error(model, params, prompt: jax.Array, res) -> float:
    """Relative L2 error of a served request's logits — prefill, then each
    decode step through the cache — against model.forward over the prompt
    and the tokens fed back."""
    toks = jnp.concatenate([prompt, jnp.asarray(res.tokens[:-1], jnp.int32)])
    full, _ = jax.jit(model.forward)(params, toks[None])
    want = full[0, prompt.shape[0] - 1:].astype(jnp.float32)
    got = jnp.stack(res.logits).astype(jnp.float32)
    require(got.shape == want.shape, f"logits {got.shape} vs {want.shape}")
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def serve_phase(cfg, *, max_batch: int = MAX_BATCH,
                max_seq: int = MAX_SEQ) -> dict:
    """Serve a seeded Table-I trace of `cfg` through the normal path and
    check what came out."""
    print(f"[serve] {cfg.name} d_model={cfg.d_model} heads={cfg.n_heads}/"
          f"{cfg.n_kv_heads} head_dim={cfg.head_dim} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} n_layers={cfg.n_layers} dtype={cfg.dtype}")
    rep = serve(cfg, policies=("priority",), n_input=N_INPUT,
                n_output=N_OUTPUT, max_batch=max_batch, max_seq=max_seq,
                keep_logits=True)
    run = rep.runs[0]
    sent, served, dropped = len(run.trace), len(run.results), run.stats.n_dropped
    print(f"[serve] sent={sent} served={served} dropped={dropped} "
          f"satisfied={run.stats.n_satisfied}")
    require(sent >= MIN_REQUESTS, f"{sent} requests sent, want >= {MIN_REQUESTS}")
    require(run.stats.n_total == sent and served + dropped == sent,
            f"served {served} + dropped {dropped} != sent {sent}")
    require(served > 0, "no request served")
    for uid, res in run.results.items():
        require(res.n_tokens == N_OUTPUT and len(res.logits) == N_OUTPUT,
                f"request {uid}: {res.n_tokens} tokens, "
                f"{len(res.logits)} logits rows, want {N_OUTPUT}")
        require(bool(jnp.isfinite(jnp.stack(res.logits)).all()),
                f"request {uid}: non-finite logits")

    uid = min(run.results)
    prompt = next(r.req.prompt for r in run.trace if r.req.uid == uid)
    err = logits_error(rep.model, rep.params, prompt, run.results[uid])
    print(f"[serve] request {uid}: served logits vs model.forward, "
          f"relative L2 error {err!r} (limit {LOGITS_REL_TOL})")
    require(err <= LOGITS_REL_TOL, f"logits error {err} > {LOGITS_REL_TOL}")
    return {
        "init_s": rep.init_s,
        "warmup_s": run.warmup_s,
        "serve_s": run.serve_s,
        "tokens": sum(r.n_tokens for r in run.results.values()),
        "attention": (
            f"prefill {core_for_keys(N_INPUT)} jnp "
            "(models/attention.attention_core), decode jnp cache attention "
            "(models/attention.decode_attention); no Pallas kernel"
        ),
    }


def report_phase(info: dict) -> None:
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[report] init_s={info['init_s']!r} "
          f"warmup_compile_s={info['warmup_s']!r} serve_s={info['serve_s']!r}")
    print(f"[report] tokens_produced={info['tokens']} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')}")
    print(f"[report] attention: {info['attention']}")


def main() -> None:
    if not repro.__file__.startswith(os.path.join(ROOT, "src") + os.sep):
        sys.exit(f"repro imported from {repro.__file__}, not this checkout")
    device = device_phase()
    print(f"[cache] compilation cache: {use_compile_cache()}")
    cfg = dataclasses.replace(get_config("glm4-9b"), n_layers=N_LAYERS)
    print("[serve] reduced: n_layers 40->20, one stage of a two-chip pipeline")
    kernel_phase(cfg)
    report_phase(serve_phase(cfg))
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
