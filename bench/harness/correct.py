"""What decides ``correct`` for a served model.

Once the window has closed, a sample of the finished requests, drawn from
the seed and holding the longest, is run once through the float32
reference over each prompt with its served tokens.  At every served token
the gap by which its reference logit lies below the reference's best is
read; the widest gap is compared with the cell's limit
(``bench/limits/<cell>.json``).  Served tokens are greedy, so a sound
program reads a gap near zero: a bf16 near-tie at most.

The control (``read_gaps(..., control=True)``) reads the same sequences
through the reference computed in float8: at each position the gap of
the token the float8 logits put first.

The reference is the configuration's family's (``harness/family.py``).
"""

from __future__ import annotations

import gc
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

import numpy as np

from harness import family
from harness.traffic import STREAM_MIGRATED, STREAM_SAMPLE, rng

LIMITS = Path(__file__).resolve().parents[1] / "limits"


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_limits(cell: str) -> Dict:
    return json.loads((LIMITS / f"{cell}.json").read_text())


def sample(finished: Sequence[Tuple[List[int], List[int]]], seed: int,
           n: int, migrated: Sequence[int] = (), n_migrated: int = 0,
           ) -> List[Tuple[List[int], List[int]]]:
    """``n`` of the finished (prompt, served) pairs: the one with the most
    served tokens, then ``n_migrated`` of those whose indices are in
    ``migrated`` (streams moved off a lost worker), and the rest, each
    drawn from the seed."""
    if not finished:
        return []
    longest = min(range(len(finished)),
                  key=lambda i: (-len(finished[i][1]), i))
    keep = [longest]
    moved = [i for i in migrated if i not in keep]
    pick = rng(seed, STREAM_MIGRATED).permutation(len(moved))
    keep += sorted(moved[i] for i in pick[:n_migrated])
    rest = [i for i in range(len(finished)) if i not in keep]
    pick = rng(seed, STREAM_SAMPLE).permutation(len(rest))
    keep += sorted(rest[i] for i in pick[:max(n - len(keep), 0)])
    return [finished[i] for i in keep]


def read_gaps(cfg: Dict, seed: int,
              picked: Sequence[Tuple[List[int], List[int]]],
              length: int, control: bool = False) -> Dict[str, float]:
    """Widest reference-logit gap of the served tokens (and, with
    ``control``, of the float8 reference's first choices) over ``picked``.
    ``length`` pads every sequence to one shape."""
    import jax
    import jax.numpy as jnp

    reference = family.module("reference", cfg)
    t0 = time.perf_counter()
    weights = reference.make_weights(cfg, seed)
    seqs = [list(p) + list(s[:-1]) for p, s in picked]
    tokens = reference.pack(seqs, length)
    jax.block_until_ready(weights)
    t = time.perf_counter()
    say(f"reference weights made in {t - t0:.1f} s")
    x = reference.hidden(cfg, weights, tokens)
    x.block_until_ready()
    say(f"reference layers over {tokens.shape} in "
        f"{time.perf_counter() - t:.1f} s")
    xc = (reference.hidden(cfg, weights, tokens, prec="fp8") if control
          else None)
    widest = {"logit_gap": 0.0, "control_gap": 0.0}
    n_tok = 0
    for i, (p, s) in enumerate(picked):
        # the logits at position len(p) - 1 + j chose served token j;
        # every read is over all positions, one shape for every sequence
        at = slice(len(p) - 1, len(p) - 1 + len(s))
        served = np.zeros(length, np.int32)
        served[at] = s
        ref = reference.logits(cfg, weights, x[i])
        widest["logit_gap"] = max(widest["logit_gap"], float(
            reference.gap(ref, served)[at].max()))
        if control:
            low = reference.logits(cfg, weights, xc[i], prec="fp8")
            first = np.asarray(jnp.argmax(low, axis=-1))
            widest["control_gap"] = max(widest["control_gap"], float(
                reference.gap(ref, first)[at].max()))
            del low
        del ref
        n_tok += len(s)
    say(f"reference logits and gaps in {time.perf_counter() - t:.1f} s")
    out = {"tokens": n_tok, "logit_gap": widest["logit_gap"]}
    if control:
        out["control_gap"] = widest["control_gap"]
    del x, xc
    del weights
    gc.collect()
    return out
