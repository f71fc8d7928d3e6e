"""The generator: every seed sends the same sizes in the same order,
with its own tokens."""

import hashlib
import json
import math

import pytest

import tiny
from harness.traffic import (Arrivals, Generator, quantile_lengths,
                             spread_order, warmup_rounds)

MIX = {"strata": 16,
       "prompt": {"median": 48, "sigma": 0.6, "min": 16, "max": 160},
       "output": {"median": 320, "sigma": 0.5, "min": 96, "max": 800}}


def sizes(seed, n):
    return [(len(r.prompt), r.max_new)
            for r in Generator(MIX, seed, 49152).take(n)]


def test_same_seed_same_requests():
    a = Generator(MIX, 2 ** 40 + 3, 49152).take(20)
    b = Generator(MIX, 2 ** 40 + 3, 49152).take(20)
    assert [(r.prompt, r.max_new) for r in a] == [(r.prompt, r.max_new)
                                                  for r in b]


def test_seeds_share_the_sizes_not_the_tokens():
    assert sizes(1, 40) == sizes(2 ** 33 + 1, 40)
    a = Generator(MIX, 1, 49152).next()
    b = Generator(MIX, 2, 49152).next()
    assert a.prompt != b.prompt


def test_every_block_holds_every_quantile():
    s = sizes(1, 32)
    for block in (s[:16], s[16:]):
        assert sorted(p for p, _ in block) == sorted(
            quantile_lengths(MIX["prompt"], 16))
        assert sorted(o for _, o in block) == sorted(
            quantile_lengths(MIX["output"], 16))


def test_spread_order_spans_the_range_early():
    order = spread_order(16)
    assert sorted(order) == list(range(16))
    for n in (2, 4, 8):
        # any first n entries fall one in each n-th of the range
        assert sorted(i * n // 16 for i in order[:n]) == list(range(n))
    assert sorted(spread_order(10)) == list(range(10))


def test_lengths_within_limits_and_median():
    q = quantile_lengths(MIX["prompt"], 16)
    assert min(q) >= 16 and max(q) <= 160
    assert q[7] <= 48 <= q[8]


def test_warmup_rounds():
    mix = dict(MIX, warmup={"requests": 3, "max_new": 2})
    w = warmup_rounds(mix, 5, 1000)
    assert [len(r) for r in w] == [2, 1]
    assert all(r.max_new == 2 for rnd in w for r in rnd)
    # the window's sizes, its own tokens
    first = Generator(mix, 5, 1000).next()
    assert len(w[0][0].prompt) == len(first.prompt)
    assert w[0][0].prompt != first.prompt


def digest(mix, seeds=(1, 2 ** 31 + 77, 2 ** 40 + 3)):
    h = hashlib.sha256()
    for seed in seeds:
        for r in Generator(mix, seed, 49152).take(40):
            h.update(json.dumps([r.idx, r.prompt, r.max_new]).encode())
        for rnd in warmup_rounds(mix, seed, 49152):
            for r in rnd:
                h.update(json.dumps([r.idx, r.prompt, r.max_new]).encode())
    return h.hexdigest()


def test_codegen_requests_unchanged():
    """The codegen mix's requests, window and warm-up, under three seeds,
    as the generator made them before it learnt system prompts and
    arrival times."""
    mix = json.loads((tiny.BENCH / "traffic" / "codegen.json").read_text())
    assert digest(mix) == ("d3337385f836f82e5b0888b12071cfab"
                           "4b13bfede0a788739dfb7dfec45fbf9d")


CHAT = dict(MIX, system={"tokens": 32}, rate_per_s=4.0, arrival_strata=64)


def test_system_prompt_is_shared_and_drawn_from_the_seed():
    a = Generator(CHAT, 7, 1000).take(5)
    w = warmup_rounds(dict(CHAT, warmup={"requests": 2}), 7, 1000)
    b = Generator(CHAT, 8, 1000).next()
    head = a[0].prompt[:32]
    assert all(r.prompt[:32] == head for r in a + w[0])
    assert b.prompt[:32] != head
    plain = Generator(MIX, 7, 1000).take(5)
    assert [len(r.prompt) - 32 for r in a] == [len(r.prompt) for r in plain]


def test_arrivals_are_the_exponentials_quantiles_at_the_rate():
    """Each block of 64 gaps holds the exponential's 64 quantiles, scaled
    to a mean of 1 / rate, in an order drawn from the seed: one seed sends
    the same gaps again, another the same set in another order, and the
    order has a Poisson stream's bursts."""
    k = 64
    arr = Arrivals(CHAT, 7)
    gaps = [arr.next() for _ in range(2 * k)]
    want = [-math.log(1 - (i + 0.5) / k) for i in range(k)]
    scale = k / (4.0 * sum(want))
    for block in (gaps[:k], gaps[k:]):
        assert sum(block) / k == pytest.approx(1 / 4.0)
        assert sorted(block) == pytest.approx(sorted(x * scale
                                                     for x in want))
    assert gaps[:k] != gaps[k:]
    again = Arrivals(CHAT, 7)
    assert [again.next() for _ in range(2 * k)] == gaps
    other = Arrivals(CHAT, 2 ** 33 + 5)
    assert [other.next() for _ in range(k)] != gaps[:k]
    # two arrivals close together, as a Poisson stream sends them (a
    # low-discrepancy order keeps every pair of gaps above 0.7 of the mean)
    assert min(a + b for a, b in zip(gaps, gaps[1:])) < 0.5 / 4.0
