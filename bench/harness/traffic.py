"""The one traffic generator: it reads a mix's parameters from
``bench/traffic/<mix>.json`` and makes the requests from ``--seed``.

Every seed gets the same sizes in the same order; the seed draws the
tokens (and the weights), not the work.  Lengths are drawn from
``strata`` fixed quantiles of their distribution, visited in a
low-discrepancy order (each stretch of consecutive requests spreads over
the whole distribution), so a window that ends at a time and not at a
request count still holds nearly the same mix of sizes in every run: a
seed that changed the order would change which sizes a window holds,
and the p99 of a window is read from its few largest admissions.

A length is lognormal: ``{"median": m, "sigma": s, "min": lo, "max": hi}``
gives ``clip(m * exp(s * z_q), lo, hi)`` at the quantile ``q`` of the
standard normal.

A mix with ``"system": {"tokens": n}`` puts one system prompt of ``n``
tokens, drawn once from the seed, in front of every request's own
prompt (``prompt`` is then the user's part).  A mix with ``"rate_per_s"``
is an open loop: its requests are sent at arrival times, whatever is
still running.  The gaps between arrivals are a Poisson process's at
that rate, stratified: each block of ``arrival_strata`` gaps holds the
exponential's quantiles at (i + 0.5) / k, scaled so that their mean is
exactly ``1 / rate_per_s``, in an order drawn from the seed.  Every
seed offers the same load over each block, and the order gives a
Poisson stream's bursts, which differ from seed to seed.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np

# independent numpy streams under one seed
STREAM_TOKENS, STREAM_WARMUP, STREAM_SAMPLE = 1, 3, 4
STREAM_SYSTEM, STREAM_MIGRATED, STREAM_ARRIVALS = 5, 6, 7


@dataclasses.dataclass
class Request:
    idx: int
    prompt: List[int]
    max_new: int


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), stream])


def quantile_lengths(dist: Dict, strata: int) -> List[int]:
    """The ``strata`` lengths at quantiles (i + 0.5) / strata."""
    nd = NormalDist()
    out = []
    for i in range(strata):
        z = nd.inv_cdf((i + 0.5) / strata)
        x = dist["median"] * math.exp(dist["sigma"] * z)
        out.append(int(min(max(round(x), dist["min"]), dist["max"])))
    return out


def spread_order(k: int, shift: int = 0) -> List[int]:
    """0..k-1 in van der Corput order (bit-reversed), rotated by
    ``shift``: every run of consecutive entries spans the range."""
    bits = max(1, (k - 1).bit_length())
    rev = sorted(range(1 << bits),
                 key=lambda i: int(format(i, f"0{bits}b")[::-1], 2))
    order = [i for i in rev if i < k]
    return order[shift % k:] + order[:shift % k]


class Generator:
    """Requests of one mix under one seed, in the order they are sent."""

    def __init__(self, mix: Dict, seed: int, vocab: int,
                 stream: int = STREAM_TOKENS):
        self.vocab = int(vocab)
        self.strata = int(mix.get("strata", 16))
        self._tok = rng(seed, stream)
        self._prompt_q = quantile_lengths(mix["prompt"], self.strata)
        self._output_q = quantile_lengths(mix["output"], self.strata)
        self._block: List = []
        self._n = 0
        sysp = mix.get("system")
        self.system = (rng(seed, STREAM_SYSTEM).integers(
            0, self.vocab, size=int(sysp["tokens"])).tolist() if sysp else [])

    def _tokens(self, n: int) -> List[int]:
        return self._tok.integers(0, self.vocab, size=n).tolist()

    def _next_sizes(self):
        if not self._block:
            # prompt and output lengths paired across the distribution
            p = spread_order(self.strata)
            o = spread_order(self.strata, shift=5)
            self._block = [(self._prompt_q[i], self._output_q[j])
                           for i, j in zip(p, o)][::-1]
        return self._block.pop()

    def next(self) -> Request:
        plen, max_new = self._next_sizes()
        req = Request(self._n, self.system + self._tokens(plen), max_new)
        self._n += 1
        return req

    def take(self, n: int) -> List[Request]:
        return [self.next() for _ in range(n)]


class Arrivals:
    """The gaps between an open loop's arrivals under one seed, in
    seconds, in the order they are sent."""

    def __init__(self, mix: Dict, seed: int):
        k = int(mix.get("arrival_strata", 64))
        gaps = [-math.log(1.0 - (i + 0.5) / k) for i in range(k)]
        scale = k / (float(mix["rate_per_s"]) * sum(gaps))
        self._gaps = [g * scale for g in gaps]
        self._order = rng(seed, STREAM_ARRIVALS)
        self._block: List[float] = []

    def next(self) -> float:
        if not self._block:
            perm = self._order.permutation(len(self._gaps))
            self._block = [self._gaps[i] for i in perm][::-1]
        return self._block.pop()


def warmup_rounds(mix: Dict, seed: int, vocab: int) -> List[List[Request]]:
    """Set-up traffic, in two rounds that each run to their end before
    the next starts: the mix's own sizes under another stream, with few
    new tokens each, so that they reach every shape the window uses."""
    w = mix.get("warmup", {})
    gen = Generator(mix, seed, vocab, stream=STREAM_WARMUP)
    reqs = gen.take(int(w.get("requests", 8)))
    for r in reqs:
        r.max_new = int(w.get("max_new", 3))
    half = (len(reqs) + 1) // 2
    return [rnd for rnd in (reqs[:half], reqs[half:]) if rnd]
