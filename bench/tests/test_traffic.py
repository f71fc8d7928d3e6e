"""The generator: every seed sends the same sizes in the same order,
with its own tokens."""

import tiny  # noqa: F401
from harness.traffic import (Generator, quantile_lengths, spread_order,
                             warmup_rounds)

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
