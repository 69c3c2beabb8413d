"""Whether what the timed path served is correct.

After the window has closed and the engine's state is freed, a sample of
the finished requests, drawn from the seed and holding the longest, is run
through the plain float32 reference once each: its prompt followed by the
tokens the server produced. At every served position the reference's best
logit and its logit for the served token are read; the number compared is
the widest gap between the two over the sample. Greedy decoding serves the
program's own best token, so a correct bf16 program reads gaps of bf16
rounding only (near ties), and a wrong prefill, splice, cached decode or
head reads gaps of the logits' own spread.

The control puts the reference computed in fp8 in the program's place: at
each position it takes the token the fp8 computation puts first and reads
that token's gap in the float32 reference.
"""

from __future__ import annotations

import dataclasses
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Row = Tuple[np.ndarray, Sequence[int]]  # (prompt, served tokens)


def sample_uids(lengths: Dict[int, int], k: int, seed: int) -> List[int]:
    """k uids of finished requests (uid -> prompt + output length), drawn
    from the seed, the longest always among them."""
    uids = sorted(lengths)
    if len(uids) <= k:
        return uids
    longest = max(uids, key=lambda u: (lengths[u], -u))
    rest = [u for u in uids if u != longest]
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 2])
    pick = rng.choice(len(rest), size=k - 1, replace=False)
    return sorted([longest] + [rest[i] for i in pick])


@jax.jit
def _gaps(ref_logits: jax.Array, chosen: jax.Array) -> jax.Array:
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, chosen[..., None], axis=-1)[..., 0]
    return best - got


@dataclasses.dataclass
class GapReading:
    max_gap: float
    tokens: int
    per_row: List[float]


def widest_gap(ref, params: dict, m: dict, rows: Sequence[Row], batch: int,
               quant: Optional[str] = None) -> GapReading:
    """Widest gap of the served tokens (quant None), or of the tokens the
    reference computed at `quant` puts first, over rows run `batch` at a
    time (one compile per shape: rows are grouped by their lengths)."""
    groups: Dict[Tuple[int, int], List[int]] = defaultdict(list)
    for i, (prompt, served) in enumerate(rows):
        groups[(len(prompt), len(served))].append(i)
    per_row = [0.0] * len(rows)
    n_tok = 0
    for (n_in, n_out), idx in groups.items():
        for s in range(0, len(idx), batch):
            part = idx[s:s + batch]
            padded = part + [part[-1]] * (batch - len(part))
            toks = np.stack([np.concatenate([rows[i][0],
                                             np.asarray(rows[i][1][:-1], np.int32)])
                             for i in padded]).astype(np.int32)
            served = np.stack([np.asarray(rows[i][1], np.int32) for i in padded])
            ref_logits = ref.logits(params, m, jnp.asarray(toks), n_in - 1)
            if quant is None:
                chosen = jnp.asarray(served)
            else:
                chosen = jnp.argmax(
                    ref.logits(params, m, jnp.asarray(toks), n_in - 1, quant),
                    axis=-1).astype(jnp.int32)
            gaps = np.asarray(_gaps(ref_logits, chosen))
            del ref_logits
            for j, i in enumerate(part):
                per_row[i] = float(gaps[j].max())
            n_tok += len(part) * n_out
    return GapReading(max(per_row) if per_row else float("nan"), n_tok, per_row)
