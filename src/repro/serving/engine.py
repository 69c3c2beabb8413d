"""Continuous-batching inference engine.

A fixed pool of `max_batch` decode slots over one batched cache; requests
are prefill'ed individually (batch-1) and spliced into a free slot, decode
advances all active slots in lock-step. This is the standard
continuous-batching serving loop (Orca-style), sized for CPU smoke models
here and for the sharded meshes via the same jitted functions.

The slot bookkeeping (`active`, `pos`, `last_tok`) lives on the host as
numpy arrays. A decode tick is one compiled program, `decode_step`: the
model's decode and each slot's greedy token. After one wait for it, the
step reads all slots' tokens in one device-to-host copy. Only a slot whose
request samples (`temperature > 0`) leaves that path: `sample_token` draws
its token from its logits, and the host reads it on its own. So a greedy
step makes two host syncs whatever the slot count.

Every `submit` and `step` runs inside `jax.profiler.TraceAnnotation`
spans (`engine.prefill` with its children `.dispatch`, `.sync`,
`engine.splice` and `.update`; `engine.step` with `.dispatch`, `.sync`,
`.readback` and `.update`; both `.update` spans are host work only).
They are written into the profiler's trace, on the device's clock, only
while a profiler runs, and cost well under a microsecond each otherwise. `InferenceEngine.counters` counts steps,
prefills, active slots stepped, device-to-host syncs and slot-steps that
took the host sampling path, and admissions that did.

An admission is two compiled programs and one host wait: `prefill_step`
(the model's batch-1 prefill and its greedy first token), then
`splice_slot`, which writes that request's cache into its slot of the
batched cache in place (the batched cache is donated). The slot is a
traced int32, so both compile once per prompt length, not per slot. After
one wait for both, `submit` reads the first token once. A request that
samples draws its first token with `sample_token` from the prefill's
logits instead (`EngineCounters.sampled_prefills`).

Slot splicing is generic across cache families (attention KV, Mamba/xLSTM
states, enc-dec cross KV): the logical-axes tree from `model.init_cache`
marks each leaf's batch dim ("kv_batch"), so insertion is a
`dynamic_update_index_in_dim` along that axis.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..models.model import Model

__all__ = [
    "EngineCounters", "GenRequest", "GenResult", "InferenceEngine",
    "SamplingParams",
]


def sample_token(
    logits: jax.Array, sp: SamplingParams, uid: int, position: int
) -> jax.Array:
    """Sample one token from (V,) logits. Deterministic in
    (seed, uid, position) so batched == sequential results hold."""
    if sp.temperature <= 0.0:
        return jnp.argmax(logits).astype(jnp.int32)
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(sp.seed), uid), position
    )
    scaled = logits.astype(jnp.float32) / sp.temperature
    if sp.top_k > 0:
        vals, idx = jax.lax.top_k(scaled, sp.top_k)
        choice = jax.random.categorical(key, vals)
        return idx[choice].astype(jnp.int32)
    return jax.random.categorical(key, scaled).astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=0)
def decode_step(
    model: Model, params: dict, cache: dict, tokens: jax.Array,
    pos: jax.Array,
):
    """One decode tick as one program: the model's decode and each slot's
    greedy token. -> ((logits (B, V), next tokens (B,) int32), cache), the
    cache second as in `Model.decode`. The benchmark finds the program in
    the device trace by its name, `jit_decode_step`."""
    logits, cache = model.decode(params, cache, tokens, pos)
    return (logits, jnp.argmax(logits, axis=-1).astype(jnp.int32)), cache


@functools.partial(jax.jit, static_argnums=0)
def prefill_step(model: Model, params: dict, prompt: Any):
    """One admission's prefill as one program: the model's batch-1 prefill
    and the greedy first token. -> (logits (1, V), token () int32, cache).
    It compiles once per prompt length. The benchmark finds the program in
    the device trace by its name, `jit_prefill_step`."""
    logits, cache = model.prefill(params, prompt)
    return logits, jnp.argmax(logits[0]).astype(jnp.int32), cache


@functools.partial(jax.jit, static_argnames="batch_axes", donate_argnums=0)
def splice_slot(cache: dict, cache1: dict, slot: jax.Array,
                batch_axes: tuple) -> dict:
    """Write a batch-1 prefill cache into slot `slot` of the batched cache,
    in place (`cache` is donated). `batch_axes` holds each leaf's batch
    axis, in the order of `jax.tree.leaves(cache)`. Dims shorter than the
    batched cache's (the prompt's kv seq) are padded with -1 (int32 leaves:
    positions, so "empty") or 0."""

    def ins(full, one, bax):
        one = jnp.squeeze(one, axis=bax)
        # pad any capacity-sized dims (kv seq) up to the full buffer
        target = full.shape[:bax] + full.shape[bax + 1 :]
        pads = []
        for have, want in zip(one.shape, target):
            assert have <= want, (one.shape, full.shape)
            pads.append((0, want - have))
        if any(p[1] for p in pads):
            cv = -1 if one.dtype == jnp.int32 else 0
            one = jnp.pad(one, pads, constant_values=cv)
        return jax.lax.dynamic_update_index_in_dim(full, one, slot, axis=bax)

    leaves, tree = jax.tree.flatten(cache)
    return tree.unflatten(
        ins(full, one, bax) for full, one, bax
        in zip(leaves, tree.flatten_up_to(cache1), batch_axes)
    )


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    temperature: float = 0.0  # 0 = greedy
    top_k: int = 0  # 0 = full distribution
    seed: int = 0


@dataclasses.dataclass
class GenRequest:
    uid: int
    prompt: Any  # (S,) int32 tokens | dict for enc-dec | (S, d) embeds
    max_new_tokens: int
    eos_token: Optional[int] = None
    sampling: SamplingParams = SamplingParams()
    keep_logits: bool = False  # keep each step's (V,) logits on the result


@dataclasses.dataclass
class GenResult:
    uid: int
    tokens: List[int]
    prefill_s: float = 0.0
    decode_s: float = 0.0
    # (V,) device arrays, prefill first, when the request set keep_logits
    logits: List[jax.Array] = dataclasses.field(default_factory=list)

    @property
    def n_tokens(self) -> int:
        return len(self.tokens)


@dataclasses.dataclass
class EngineCounters:
    """What the engine did since its last `reset()`."""

    steps: int = 0
    prefills: int = 0
    slot_steps: int = 0  # active slots summed over steps
    # block_until_ready calls and reads of device values in steps
    step_host_syncs: int = 0
    # slot-steps whose request samples, so whose token the host drew
    sampled_slot_steps: int = 0
    # admissions whose request samples, so whose first token the host drew
    sampled_prefills: int = 0


class InferenceEngine:
    def __init__(
        self,
        model: Model,
        params: dict,
        max_batch: int = 8,
        max_seq: int = 256,
        enc_len: int = 0,
    ):
        self.model = model
        self.params = params
        self.M = max_batch
        self.Sc = max_seq
        self._enc_len = enc_len
        cache, caxes = model.init_cache(max_batch, max_seq, enc_len=enc_len)
        self._cache = cache
        batch_axes = jax.tree.map(
            lambda ax: ax.index("kv_batch") if "kv_batch" in ax else 0, caxes
        )
        self._clear_slots()
        # (params, cache, tokens, pos) -> ((logits, next tokens), cache)
        self._decode = functools.partial(decode_step, model)
        # (params, prompt) -> (logits, first token, batch-1 cache)
        self._prefill = functools.partial(prefill_step, model)
        # (cache, cache1, slot) -> cache, the old cache donated
        self._splice_slot = functools.partial(
            splice_slot, batch_axes=tuple(jax.tree.leaves(batch_axes))
        )

    # ------------------------------------------------------------- slots
    def _clear_slots(self) -> None:
        """Host-side slot bookkeeping, results and counters, all empty.
        `pos` and `last_tok` keep fixed dtypes and shapes, so the decode
        program compiles once."""
        self.active = np.zeros((self.M,), bool)
        self.pos = np.zeros((self.M,), np.int32)
        self.last_tok = np.zeros((self.M,), np.int32)
        self.results: Dict[int, GenResult] = {}
        self._slot_req: List[Optional[GenRequest]] = [None] * self.M
        self._remaining = [0] * self.M
        self.counters = EngineCounters()

    def reset(self) -> None:
        """Clear all slots, results and counters (cache contents become
        irrelevant: slot positions mark everything invalid)."""
        self._clear_slots()
        cache, _ = self.model.init_cache(
            self.M, self.Sc, enc_len=self._enc_len
        )
        self._cache = cache

    def warmup(self, sample_prompt: Any) -> None:
        """Trace+compile `prefill_step`, `splice_slot` and `decode_step` for
        this engine's shapes (and this prompt length) so the first timed
        request doesn't pay compilation."""
        self.generate([GenRequest(uid=-987654, prompt=sample_prompt,
                                  max_new_tokens=2)])
        self.reset()

    def free_slots(self) -> List[int]:
        return np.flatnonzero(~self.active).tolist()

    def active_uids(self) -> List[int]:
        """uids of the requests currently occupying decode slots."""
        return [r.uid for r in self._slot_req if r is not None]

    @property
    def n_active(self) -> int:
        return int(self.active.sum())

    # ----------------------------------------------------------- serving
    def submit(self, req: GenRequest) -> int:
        """Prefill + occupy a slot. Returns the slot index.

        Two compiled programs (`prefill_step`, `splice_slot`), one wait for
        both, one read of the first token; the slot bookkeeping after it is
        host work."""
        slots = self.free_slots()
        if not slots:
            raise RuntimeError("no free slot")
        slot = slots[0]
        with TraceAnnotation("engine.prefill"):
            t0 = time.perf_counter()
            if isinstance(req.prompt, dict):
                prompt = {k: v[None] for k, v in req.prompt.items()}
                plen = prompt["dec_tokens"].shape[1]
            else:
                prompt = req.prompt[None]
                plen = prompt.shape[1]
            with TraceAnnotation("engine.prefill.dispatch"):
                logits, tok, cache1 = self._prefill(self.params, prompt)
                if req.sampling.temperature > 0.0:
                    tok = sample_token(logits[0], req.sampling, req.uid, 0)
                    self.counters.sampled_prefills += 1
            with TraceAnnotation("engine.splice"):
                self._cache = self._splice_slot(self._cache, cache1,
                                                np.int32(slot))
            # JAX returns before the device finishes: time the admission's
            # results, then read the first token once
            with TraceAnnotation("engine.prefill.sync"):
                jax.block_until_ready((tok, self._cache))
                tok = int(tok)
            with TraceAnnotation("engine.prefill.update"):  # host only
                self.pos[slot] = plen
                self.last_tok[slot] = tok
            self.active[slot] = True
            self._slot_req[slot] = req
            self._remaining[slot] = req.max_new_tokens - 1
            res = GenResult(req.uid, [tok], prefill_s=time.perf_counter() - t0)
            if req.keep_logits:
                res.logits.append(logits[0])
            self.results[req.uid] = res
            if self._remaining[slot] <= 0 or tok == req.eos_token:
                self._finish(slot)
            self.counters.prefills += 1
        return slot

    def _finish(self, slot: int) -> None:
        self.active[slot] = False
        self._slot_req[slot] = None
        self._remaining[slot] = 0

    def step(self) -> int:
        """One lock-step decode tick for all active slots. Returns #active.

        One compiled program (`decode_step`), one wait for it, one read of
        every slot's token; the slot bookkeeping after it is host work."""
        if self.n_active == 0:
            return 0
        c = self.counters
        with TraceAnnotation("engine.step"):
            t0 = time.perf_counter()
            with TraceAnnotation("engine.step.dispatch"):
                (logits, nxt), self._cache = self._decode(
                    self.params, self._cache, self.last_tok, self.pos
                )
                # slots whose request samples draw from their logits
                sampled = {}
                for slot, req in enumerate(self._slot_req):
                    if req is not None and req.sampling.temperature > 0.0:
                        sampled[slot] = sample_token(
                            logits[slot], req.sampling, req.uid,
                            len(self.results[req.uid].tokens),
                        )
            # JAX returns before the device finishes: time the step's results
            with TraceAnnotation("engine.step.sync"):
                jax.block_until_ready((nxt, self._cache))
                c.step_host_syncs += 1
            dt = time.perf_counter() - t0
            with TraceAnnotation("engine.step.readback"):
                nxt = np.array(nxt)  # every slot's token in one copy
                c.step_host_syncs += 1
                for slot, t in sampled.items():
                    nxt[slot] = int(t)
                c.step_host_syncs += len(sampled)
                c.sampled_slot_steps += len(sampled)
            with TraceAnnotation("engine.step.update"):  # host only
                n_stepped = self.n_active
                self.pos += self.active
                self.last_tok = np.where(self.active, nxt, self.last_tok)
                for slot in np.flatnonzero(self.active).tolist():
                    req = self._slot_req[slot]
                    tok = int(nxt[slot])
                    res = self.results[req.uid]
                    res.tokens.append(tok)
                    res.decode_s += dt
                    if req.keep_logits:
                        res.logits.append(logits[slot])
                    self._remaining[slot] -= 1
                    if self._remaining[slot] <= 0 or tok == req.eos_token:
                        self._finish(slot)
            c.steps += 1
            c.slot_steps += n_stepped
        return self.n_active

    def generate(self, reqs: List[GenRequest]) -> Dict[int, GenResult]:
        """Convenience: run a request list to completion (batched greedily)."""
        pending = list(reqs)
        while pending or self.n_active:
            while pending and self.free_slots():
                self.submit(pending.pop(0))
            if self.n_active:
                self.step()
        return {r.uid: self.results[r.uid] for r in reqs}
