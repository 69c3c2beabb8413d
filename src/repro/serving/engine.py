"""Continuous-batching inference engine.

A fixed pool of `max_batch` decode slots over one batched cache; requests
are prefill'ed individually (batch-1) and spliced into a free slot, decode
advances all active slots in lock-step (one fused `decode_step` per tick).
This is the standard continuous-batching serving loop (Orca-style), sized
for CPU smoke models here and for the sharded meshes via the same jitted
functions.

Every `submit` and `step` runs inside `jax.profiler.TraceAnnotation`
spans (`engine.prefill` with its children `.dispatch`, `.sync`,
`engine.splice` and `.update`; `engine.step` with `.dispatch`, `.sync`,
`.update` and `.readback`). They are written into the profiler's trace,
on the device's clock, only while a profiler runs, and cost well under a
microsecond each otherwise. `InferenceEngine.counters` counts steps,
prefills, active slots stepped and device-to-host syncs.

Slot splicing is generic across cache families (attention KV, Mamba/xLSTM
states, enc-dec cross KV): the logical-axes tree from `model.init_cache`
marks each leaf's batch dim ("kv_batch"), so insertion is a
`dynamic_update_index_in_dim` along that axis.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
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
    # block_until_ready calls and int() reads of device values in steps
    step_host_syncs: int = 0


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
        self._batch_axis = jax.tree.map(
            lambda ax: ax.index("kv_batch") if "kv_batch" in ax else 0, caxes
        )
        # slot bookkeeping (host side)
        self.active = [False] * max_batch
        self.pos = jnp.zeros((max_batch,), jnp.int32)
        self.last_tok = jnp.zeros((max_batch,), jnp.int32)
        self.results: Dict[int, GenResult] = {}
        self._slot_req: List[Optional[GenRequest]] = [None] * max_batch
        self._remaining = [0] * max_batch
        self.counters = EngineCounters()

        self._decode = jax.jit(model.decode)
        self._prefill = jax.jit(model.prefill)

    # ------------------------------------------------------------- slots
    def reset(self) -> None:
        """Clear all slots, results and counters (cache contents become
        irrelevant: slot positions mark everything invalid)."""
        self.active = [False] * self.M
        self.pos = jnp.zeros((self.M,), jnp.int32)
        self.last_tok = jnp.zeros((self.M,), jnp.int32)
        self.results = {}
        self._slot_req = [None] * self.M
        self._remaining = [0] * self.M
        self.counters = EngineCounters()
        cache, _ = self.model.init_cache(
            self.M, self.Sc, enc_len=self._enc_len
        )
        self._cache = cache

    def warmup(self, sample_prompt: Any) -> None:
        """Trace+compile prefill/decode/splice for this engine's shapes so
        the first timed request doesn't pay compilation."""
        self.generate([GenRequest(uid=-987654, prompt=sample_prompt,
                                  max_new_tokens=2)])
        self.reset()

    def free_slots(self) -> List[int]:
        return [i for i, a in enumerate(self.active) if not a]

    def active_uids(self) -> List[int]:
        """uids of the requests currently occupying decode slots."""
        return [r.uid for r in self._slot_req if r is not None]

    @property
    def n_active(self) -> int:
        return sum(self.active)

    def _splice(self, cache1: dict, slot: int, prompt_len: int) -> None:
        """Insert a batch-1 prefill cache into slot `slot`."""

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

        self._cache = jax.tree.map(ins, self._cache, cache1, self._batch_axis)

    # ----------------------------------------------------------- serving
    def submit(self, req: GenRequest) -> int:
        """Prefill + occupy a slot. Returns the slot index."""
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
                logits, cache1 = self._prefill(self.params, prompt)
            with TraceAnnotation("engine.prefill.sync"):
                tok = int(sample_token(logits[0], req.sampling, req.uid, 0))
            with TraceAnnotation("engine.splice"):
                self._splice(cache1, slot, plen)
            with TraceAnnotation("engine.prefill.update"):
                self.pos = self.pos.at[slot].set(plen)
                self.last_tok = self.last_tok.at[slot].set(tok)
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
        """One lock-step decode tick for all active slots. Returns #active."""
        if self.n_active == 0:
            return 0
        with TraceAnnotation("engine.step"):
            t0 = time.perf_counter()
            with TraceAnnotation("engine.step.dispatch"):
                logits, self._cache = self._decode(
                    self.params, self._cache, self.last_tok, self.pos
                )
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                # per-slot stochastic sampling where requested (greedy is
                # fused)
                for slot in range(self.M):
                    req = self._slot_req[slot]
                    if req is not None and req.sampling.temperature > 0.0:
                        t = sample_token(
                            logits[slot], req.sampling, req.uid,
                            len(self.results[req.uid].tokens),
                        )
                        nxt = nxt.at[slot].set(t)
            # JAX returns before the device finishes: time the step's results
            c = self.counters
            with TraceAnnotation("engine.step.sync"):
                jax.block_until_ready((nxt, self._cache))
                c.step_host_syncs += 1
            dt = time.perf_counter() - t0
            with TraceAnnotation("engine.step.update"):
                self.pos = self.pos + jnp.asarray(
                    [1 if a else 0 for a in self.active], jnp.int32
                )
                self.last_tok = jnp.where(
                    jnp.asarray(self.active), nxt, self.last_tok
                )
            n_stepped = self.n_active
            with TraceAnnotation("engine.step.readback"):
                for slot in range(self.M):
                    if not self.active[slot]:
                        continue
                    req = self._slot_req[slot]
                    tok = int(nxt[slot])
                    c.step_host_syncs += 1
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
