"""Whether what the timed path served is what the model says.

Once the window has closed and the program's state is freed, a sample of
the requests it finished, drawn from the seed and holding the longest of
them, is run through the configuration's plain reference: each prompt with
its served tokens, in one pass.  At every served position the reference's
best logit is compared with the logit of the token that was served; the
number compared is the widest such gap over the sample
(``max_logit_gap``).  A greedy server in exact arithmetic reads 0; bf16
rounding lets it pick a near-tie's runner-up now and then, by a small gap.

The control reads the same number for the reference computed in fp8: at
each position, the gap of the token that the fp8 computation puts first.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

BUCKET = 512


def sample(done: Sequence[Tuple[np.ndarray, List[int]]], seed: int,
           tokens: int, requests: int) -> List[int]:
    """Indices into ``done`` (prompt, served tokens): the longest request,
    then others in an order drawn from the seed until ``tokens`` served
    tokens and ``requests`` requests are in (a fault that touches some
    slots and not others then escapes a sample of k with odds 2**-k)."""
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: (len(done[i][0]) + len(done[i][1]), -i))
    rng = np.random.default_rng([int(seed) % 2 ** 63, 2])
    rest = [i for i in rng.permutation(len(done)) if i != longest]
    out, n = [longest], len(done[longest][1])
    for i in rest:
        if n >= tokens and len(out) >= requests:
            break
        out.append(int(i))
        n += len(done[i][1])
    return out


def gaps(ref, params, a: Dict[str, Any], items, T: int, G: int,
         control: bool = False) -> Dict[str, Any]:
    """Per request: the widest gap of a served token below the reference's
    best logit, how many served tokens were not the reference's first
    choice, and (``control``) the widest gap of the fp8 computation's
    first choice."""
    import jax
    import jax.numpy as jnp

    def one(p, seq, read, served, valid, fp8: bool):
        lg = ref.logits_at(p, seq, read, a)
        best = lg.max(-1)
        got = jnp.take_along_axis(lg, served[:, None], axis=1)[:, 0]
        gap = jnp.where(valid, best - got, 0.0)
        flips = jnp.sum(valid & (jnp.argmax(lg, -1) != served))
        out = {"gap": gap.max(), "flips": flips}
        if fp8:
            first = jnp.argmax(ref.logits_at(p, seq, read, a, fp8=True), -1)
            cg = best - jnp.take_along_axis(lg, first[:, None], axis=1)[:, 0]
            out["control_gap"] = jnp.where(valid, cg, 0.0).max()
        return out

    fn = jax.jit(lambda p, s, r, v, m: one(p, s, r, v, m, control))
    rows = []
    for prompt, served in items:
        served = np.asarray(served, np.int32)
        full = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        n = len(served)
        # one program per 512 positions of sequence, up to the cache size
        seq = np.zeros((min(T, -(-len(full) // BUCKET) * BUCKET),), np.int32)
        seq[:len(full)] = full
        read = np.minimum(np.arange(G) + len(prompt) - 1, len(full) - 1)
        sv = np.zeros((G,), np.int32)
        sv[:n] = served
        valid = np.arange(G) < n
        r = jax.device_get(fn(params, seq, read.astype(np.int32), sv, valid))
        rows.append({k: float(v) for k, v in r.items()} | {"tokens": n})
    out = {"requests": len(rows), "tokens": sum(r["tokens"] for r in rows),
           "max_logit_gap": max((r["gap"] for r in rows), default=None),
           "flips": int(sum(r["flips"] for r in rows))}
    if control:
        out["control_max_logit_gap"] = max((r["control_gap"] for r in rows),
                                           default=None)
    return out
