"""Public model API: one `Model` facade over every assigned family.

    model = build_model(get_config("mixtral-8x22b", smoke=True))
    params, axes = model.init(jax.random.PRNGKey(0))
    loss, aux   = model.loss(params, batch)              # train
    logits, cache = model.prefill(params, prompt)        # serving
    logits, cache = model.decode(params, cache, tok, pos)

Inputs (`batch`, `prompt`) follow `launch.specs.input_specs` layouts:
decoder-only: tokens (B, S) int32 — or frontend embeds (B, S, d) for
vlm/audio; enc-dec: dict(enc_embeds, dec_tokens).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding import constrain
from .common import DTYPES, RuntimeFlags
from . import encdec, transformer

__all__ = ["Model", "build_model", "cross_entropy_loss"]


def cross_entropy_loss(
    logits: jax.Array,  # (B, S, V)
    labels: jax.Array,  # (B, S) int32
    vocab_size: int,
) -> jax.Array:
    """Mean token NLL; vocab-sharding-safe (one-hot einsum contraction, no
    cross-shard gather). Padded vocab tail is never a label."""
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    # bf16 one-hot (exact 0/1) with f32 accumulation: halves the largest
    # transient of the loss without precision loss on the picked logit.
    oh = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.bfloat16)
    oh = constrain(oh, ("batch", "seq", "vocab"))
    ll = jnp.einsum(
        "bsv,bsv->bs", oh, logits.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )
    return jnp.mean(lse - ll)


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    rt: RuntimeFlags

    @property
    def is_encdec(self) -> bool:
        return self.cfg.n_encoder_layers > 0

    # ------------------------------------------------------------- params
    def init(self, key: jax.Array, dtype=None) -> Tuple[dict, dict]:
        if self.is_encdec:
            return encdec.init_encdec_params(self.cfg, key, dtype)
        return transformer.init_decoder_params(self.cfg, key, dtype)

    # -------------------------------------------------------------- train
    def forward(self, params: dict, batch: Any) -> Tuple[jax.Array, dict]:
        """-> (logits, aux). batch: tokens/embeds, or dict for enc-dec."""
        if self.is_encdec:
            return encdec.encdec_forward(
                params, self.cfg, self.rt, batch["enc_embeds"], batch["dec_tokens"]
            )
        return transformer.decoder_forward(params, self.cfg, self.rt, batch)

    def loss(self, params: dict, batch: Any) -> Tuple[jax.Array, dict]:
        """Next-token LM loss (+ MoE aux terms). For decoder-only, batch is
        a dict {tokens/(embeds), labels}; enc-dec adds enc_embeds."""
        if self.is_encdec:
            logits, aux = encdec.encdec_forward(
                params, self.cfg, self.rt, batch["enc_embeds"], batch["dec_tokens"]
            )
        else:
            inputs = batch["embeds"] if "embeds" in batch else batch["tokens"]
            logits, aux = transformer.decoder_forward(
                params, self.cfg, self.rt, inputs
            )
        loss = cross_entropy_loss(logits, batch["labels"], self.cfg.padded_vocab)
        if aux:
            loss = loss + 0.01 * aux.get("moe_lb_loss", 0.0) \
                        + 0.001 * aux.get("moe_z_loss", 0.0)
        return loss, aux

    # ------------------------------------------------------------ serving
    def init_cache(
        self, batch: int, cache_len: int, enc_len: int = 0, dtype=None
    ) -> Tuple[dict, dict]:
        if self.is_encdec:
            return encdec.init_encdec_cache(
                self.cfg, batch, cache_len, enc_len or cache_len, dtype
            )
        return transformer.init_decode_cache(self.cfg, batch, cache_len, dtype)

    def prefill(self, params: dict, prompt: Any) -> Tuple[jax.Array, dict]:
        """-> (last-position logits (B, V), cache)."""
        if self.is_encdec:
            return encdec.encdec_prefill(
                params, self.cfg, self.rt, prompt["enc_embeds"], prompt["dec_tokens"]
            )
        return transformer.decoder_prefill(params, self.cfg, self.rt, prompt)

    def decode(
        self, params: dict, cache: dict, token: jax.Array, pos: jax.Array
    ) -> Tuple[jax.Array, dict]:
        """One token for every sequence in the batch -> (logits, cache)."""
        if self.is_encdec:
            return encdec.encdec_decode(params, self.cfg, cache, token, pos)
        return transformer.decoder_decode(params, self.cfg, cache, token, pos)


def build_model(cfg: ModelConfig, rt: Optional[RuntimeFlags] = None) -> Model:
    return Model(cfg, rt or RuntimeFlags())
