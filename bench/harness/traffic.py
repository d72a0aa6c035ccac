"""The one generator of traffic: a mix file's parameters and ``--seed`` in,
requests out.

Every seed serves the same multiset of sizes, drawn once from the mix's own
``sizes.seed``, so that two seeds offer the same work; ``--seed`` draws
everything else: the order in which the sizes come, the arrival times, and
the token ids (and the weights, elsewhere).

- A closed loop takes the multiset in an order drawn from ``--seed``.
- An open loop sends it in blocks of ``arrivals.block`` requests, one block
  per ``block / rate_per_s`` seconds, so the offered rate is exact over
  every block.  A block holds one request of each of ``block`` strata of
  the multiset by output length (which one is drawn from the seed), in an
  order drawn from the seed, at arrival times drawn uniformly inside the
  block: a Poisson process conditioned on its count per block.  A tail
  read over some tens of requests then follows the system, not how many
  long requests one seed happened to bunch together.
- ``arrivals.profile`` (optional) modulates the rate: pieces
  ``[seconds, relative rate]`` repeated for the whole run and scaled to a
  mean of 1, so ``[[2, 3], [4, 0]]`` sends every six seconds' requests in
  bursts of two seconds at the same mean rate.

A mix file holds:

- ``loop``: ``"open"`` (arrivals at ``rate_per_s`` from due times that do
  not wait for the server) or ``"closed"`` (``concurrency`` requests kept
  in the system: a new one is sent when one finishes);
- ``arrivals`` (open loop): ``block`` and optionally ``profile``, above;
- ``shared_prefix`` (optional): ``count`` prefixes of ``length`` tokens,
  one picked per request by a Zipf law of exponent ``zipf_s``, put in
  front of the request's own prompt;
- ``prompt`` and ``output``: lognormal lengths, ``median`` and ``sigma``,
  clipped to ``[min, max]``;
- ``sizes``: the seed and count of the fixed multiset;
- ``serving``: the deployment's knobs for this traffic (slots, ``max_len``,
  page size, chunk width, prefill jobs, prefix cache);
- ``rehearsal``: the knobs of a CPU rehearsal, whose lengths are scaled by
  its ``max_len`` over the served one.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np


def _lognormal(rng, spec: Dict[str, Any], n: int) -> np.ndarray:
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def sizes(mix: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """The fixed multiset: prefix index, own prompt length and output
    length of ``sizes.count`` requests."""
    n = mix["sizes"]["count"]
    rng = np.random.default_rng(mix["sizes"]["seed"])
    sp = mix.get("shared_prefix")
    if sp:
        w = 1.0 / np.arange(1, sp["count"] + 1) ** sp["zipf_s"]
        prefix = rng.choice(sp["count"], size=n, p=w / w.sum())
    else:
        prefix = np.full(n, -1)
    return {"prefix": prefix,
            "prompt": _lognormal(rng, mix["prompt"], n),
            "output": _lognormal(rng, mix["output"], n)}


def blocks(output: np.ndarray, block: int, rng) -> Tuple[np.ndarray, np.ndarray]:
    """An open loop's order of the multiset and each request's offset in
    units of the mean gap: block ``b`` takes one member of each stratum by
    output length, drawn from ``rng``, in a drawn order, at uniform times
    in ``[b * block, (b + 1) * block)``."""
    n = len(output)
    if n % block:
        raise ValueError(f"sizes.count {n} is not a multiple of the block {block}")
    per = n // block
    strata = np.argsort(output, kind="stable").reshape(block, per)
    pick = np.stack([s[rng.permutation(per)] for s in strata], 1)  # (per, block)
    order = np.concatenate([row[rng.permutation(block)] for row in pick])
    at = np.sort(rng.uniform(0.0, block, (per, block)), axis=1)
    offset = (at + block * np.arange(per)[:, None]).reshape(-1)
    return order, offset


class Profile:
    """Wall time from operational time under a rate that repeats the pieces
    ``[seconds, relative rate]``, scaled to a mean of 1."""

    def __init__(self, pieces: Optional[Sequence[Sequence[float]]]):
        pieces = [(float(s), float(r)) for s, r in (pieces or [[1.0, 1.0]])]
        self.period = sum(s for s, _ in pieces)
        mean = sum(s * r for s, r in pieces) / self.period
        self.pieces = [(s, r / mean) for s, r in pieces]

    def wall(self, u: float) -> float:
        k, u = divmod(u, self.period)
        t = k * self.period
        for s, r in self.pieces:
            if r > 0 and u <= s * r:
                return t + u / r
            u -= s * r
            t += s
        return t


class Traffic:
    """Request ``i`` of a run: its due offset from the start of traffic
    (open loop), its prompt and its output length."""

    def __init__(self, mix: Dict[str, Any], seed: int, vocab: int,
                 rehearse: bool = False, rate: Optional[float] = None):
        self.serving = dict(mix["serving"])
        self.scale = 1.0
        if rehearse:
            r = mix["rehearsal"]
            mix = {**mix, **{k: v for k, v in r.items() if k != "serving"}}
            self.serving.update(r["serving"])
            self.scale = self.serving["max_len"] / mix["serving"]["max_len"]
        self.mix = mix
        self.loop = mix["loop"]
        self.rate = rate if rate is not None else mix.get("rate_per_s")
        self.vocab = int(vocab)
        self.seed = int(seed) % 2 ** 63
        s = sizes(mix)
        rng = np.random.default_rng([self.seed, 0])
        self.n = len(s["prompt"])
        self.offset = None
        if self.loop == "open":
            arr = mix["arrivals"]
            self.order, self.offset = blocks(s["output"], arr["block"], rng)
            self.profile = Profile(arr.get("profile"))
        else:
            self.order = rng.permutation(self.n)
        self.prefix = s["prefix"]
        self.prompt = self._scaled(s["prompt"], 1)
        self.output = self._scaled(s["output"], 1)
        sp = mix.get("shared_prefix")
        self.prefixes: List[np.ndarray] = []
        if sp:
            plen = self._scaled(np.array([sp["length"]]), 1)[0]
            self.prefixes = [self._tokens(rng, plen)
                             for _ in range(sp["count"])]

    def _scaled(self, x: np.ndarray, lo: int) -> np.ndarray:
        if self.scale == 1.0:
            return x
        return np.maximum(lo, np.rint(x * self.scale)).astype(np.int64)

    def _tokens(self, rng, n: int) -> np.ndarray:
        return rng.integers(1, self.vocab, (int(n),)).astype(np.int32)

    def due(self, i: int) -> Optional[float]:
        """Seconds from the start of traffic to request ``i``'s due time
        (open loop; the schedule repeats after ``n`` requests)."""
        if self.offset is None:
            return None
        u = float(self.offset[i % self.n]) + (i // self.n) * self.n
        return self.profile.wall(u / self.rate)

    def request(self, i: int) -> Tuple[Optional[float], np.ndarray, int]:
        """(due offset in seconds or None, prompt, max_new) of request i."""
        j = self.order[i % self.n]
        rng = np.random.default_rng([self.seed, 1, i])
        p = self.prefix[j]
        head = self.prefixes[p] if p >= 0 else np.zeros((0,), np.int32)
        out = int(self.output[j])
        # what a scaled rehearsal would push past the cache is cut from the
        # request's own prompt (the served mixes' bounds never reach it)
        room = self.serving["max_len"] + 1 - out - len(head)
        own = self._tokens(rng, min(int(self.prompt[j]), room))
        return self.due(i), np.concatenate([head, own]), out
