"""The plain reference of the dense decoder family, in float32.

It imports nothing of the program.  It makes its own weights from the
seed by the convention the configuration's weights follow (below), runs
the whole sequence through every layer in straightforward ``jax.numpy``
under ``jax.default_matmul_precision("highest")``, one layer at a time so
that it fits beside the weights, and reads the logits at the positions it
is asked for.

Weights from a seed: the leaves in this order, each drawn with its own
key of ``jax.random.split(jax.random.PRNGKey(seed), n_leaves)``::

    embed (V, d)                       normal * 0.02
    layers/ln1/gamma (L, d)            ones      [layers/ln1/beta: zeros]
    layers/attn/wq, wk, wv, wo         normal / sqrt(fan_in)
    layers/ln2/gamma                   ones      [layers/ln2/beta: zeros]
    layers/ffn/wi, wd  or  wg, wu, wd  normal / sqrt(fan_in)
    ln_f/gamma                         ones      [ln_f/beta: zeros]
    lm_head (d, V)                     normal / sqrt(d)

(``beta`` only under LayerNorm; fan_in is the second-to-last axis.)  A
normal leaf is the float32 draw, kept apart from its scale, times the
scale, rounded to the weights' dtype.  ``V`` is the vocabulary rounded up
to a multiple of 128; logits past the vocabulary are not read.

``prec="fp8"`` is the control: every matmul's operands rounded to
float8_e4m3 (weights by output column, activations by row, each scaled
to the format's range) before a float32 product.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def padded_vocab(cfg: Dict) -> int:
    return -(-cfg["vocab_size"] // 128) * 128


def leaf_specs(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    """(name, shape, init) of every weight, in the order keys are dealt."""
    d, f, L = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    dh = cfg.get("head_dim") or d // cfg["n_heads"]
    hq, hkv = cfg["n_heads"] * dh, cfg["n_kv_heads"] * dh
    v = padded_vocab(cfg)
    ln = cfg["norm"] == "layernorm"

    def norm(prefix, lead):
        out = [(prefix + "gamma", lead + (d,), "ones")]
        if ln:
            out.append((prefix + "beta", lead + (d,), "zeros"))
        return out

    specs = [("embed", (v, d), "embed")]
    specs += norm("layers/ln1/", (L,))
    specs += [("layers/attn/wq", (L, d, hq), "normal"),
              ("layers/attn/wk", (L, d, hkv), "normal"),
              ("layers/attn/wv", (L, d, hkv), "normal"),
              ("layers/attn/wo", (L, hq, d), "normal")]
    specs += norm("layers/ln2/", (L,))
    if cfg["act"] in ("swiglu", "geglu"):
        specs += [("layers/ffn/wg", (L, d, f), "normal"),
                  ("layers/ffn/wu", (L, d, f), "normal")]
    else:
        specs += [("layers/ffn/wi", (L, d, f), "normal")]
    specs += [("layers/ffn/wd", (L, f, d), "normal")]
    specs += norm("ln_f/", ())
    if not cfg.get("tie_embeddings"):
        specs += [("lm_head", (d, v), "normal")]
    return specs


@functools.partial(jax.jit, static_argnums=(1, 3))
def _draw(key, shape, scale, dtype):
    z = jax.lax.optimization_barrier(jax.random.normal(key, shape))
    return (z * scale).astype(dtype)


def make_weights(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    """Every weight on the device, by flat name, in ``param_dtype``."""
    dtype = jnp.dtype(cfg["param_dtype"])
    specs = leaf_specs(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(specs))
    out = {}
    for (name, shape, init), key in zip(specs, keys):
        if init == "ones":
            out[name] = jnp.ones(shape, dtype)
        elif init == "zeros":
            out[name] = jnp.zeros(shape, dtype)
        else:
            scale = 0.02 if init == "embed" else 1.0 / math.sqrt(shape[-2])
            out[name] = _draw(key, shape, jnp.float32(scale), dtype)
    return out


# -- the forward, one layer at a time ------------------------------------- #


def _q8(x, axis):
    """x rounded to float8_e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(a, w, prec):
    if prec == "fp8":
        a, w = _q8(a, -1), _q8(w, 0)
    return a @ w


def _norm(cfg, x, w, prefix):
    eps = cfg["norm_eps"]
    g = w[prefix + "gamma"]
    if cfg["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * g + w[prefix + "beta"]
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _rope(x, cos, sin):
    # x (B, S, H, dh); rotate the two halves of each head
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _act(name, x):
    if name == "swiglu":
        return jax.nn.silu(x)
    c = math.sqrt(2.0 / math.pi)            # GELU, tanh form
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def _layer(cfg, prec, x, w, cos, sin):
    b, s, d = x.shape
    dh = cfg.get("head_dim") or d // cfg["n_heads"]
    hq, hkv = cfg["n_heads"], cfg["n_kv_heads"]
    h = _norm(cfg, x, w, "ln1/")
    q = _mm(h, w["attn/wq"], prec).reshape(b, s, hq, dh)
    k = _mm(h, w["attn/wk"], prec).reshape(b, s, hkv, dh)
    v = _mm(h, w["attn/wv"], prec).reshape(b, s, hkv, dh)
    if cfg["rope_theta"] > 0:
        q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, s, hq * dh)
    x = x + _mm(o, w["attn/wo"], prec)
    h = _norm(cfg, x, w, "ln2/")
    if cfg["act"] in ("swiglu", "geglu"):
        u = _act(cfg["act"], _mm(h, w["ffn/wg"], prec)) * _mm(
            h, w["ffn/wu"], prec)
    else:
        u = _act(cfg["act"], _mm(h, w["ffn/wi"], prec))
    return x + _mm(u, w["ffn/wd"], prec)


def _layer_weights(weights, i):
    return {k[len("layers/"):]: jax.lax.dynamic_index_in_dim(
        v, i, keepdims=False).astype(jnp.float32)
        for k, v in weights.items() if k.startswith("layers/")}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _run_layer(cfg_items, prec, x, weights, i):
    cfg = dict(cfg_items)
    s = x.shape[1]
    dh = cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]
    inv = 1.0 / (cfg["rope_theta"] ** (jnp.arange(0, dh, 2,
                                                  dtype=jnp.float32) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    return _layer(cfg, prec, x, _layer_weights(weights, i),
                  jnp.cos(ang), jnp.sin(ang))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _embed(cfg_items, prec, tokens, weights):
    return weights["embed"][tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _logits(cfg_items, prec, x, weights):
    """Logits over the vocabulary at every position of one sequence
    ``x`` (S, d)."""
    cfg = dict(cfg_items)
    fw = {k: v.astype(jnp.float32) for k, v in weights.items()
          if k.startswith("ln_f/")}
    h = _norm(cfg, x, fw, "ln_f/")
    head = weights.get("lm_head")
    head = (weights["embed"].T if head is None else head)
    head = head[:, :cfg["vocab_size"]].astype(jnp.float32)
    return _mm(h, head, prec)


@jax.jit
def _gap(logits, chosen):
    """How far the logit of ``chosen`` (S,) lies below the best, at each
    position (chosen by a one-hot compare: no gather)."""
    hit = jnp.arange(logits.shape[-1])[None, :] == chosen[:, None]
    got = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
    return jnp.max(logits, axis=-1) - got


def _hashable(cfg: Dict):
    keep = ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
            "n_layers", "vocab_size", "norm", "norm_eps", "act",
            "rope_theta", "tie_embeddings")
    return tuple((k, cfg.get(k)) for k in keep)


def hidden(cfg: Dict, weights, tokens: np.ndarray, prec: str = "f32"):
    """The final hidden states (B, S, d) of ``tokens`` (B, S)."""
    items = _hashable(cfg)
    with jax.default_matmul_precision("highest"):
        x = _embed(items, prec, jnp.asarray(tokens), weights)
        for i in range(cfg["n_layers"]):
            x = _run_layer(items, prec, x, weights, jnp.int32(i))
    return x


def logits(cfg: Dict, weights, x, prec: str = "f32"):
    """Logits (S, V) of one sequence's final hidden states ``x`` (S, d)."""
    with jax.default_matmul_precision("highest"):
        return _logits(_hashable(cfg), prec, x, weights)


def gap(logits_: jax.Array, chosen: np.ndarray) -> np.ndarray:
    """Per position, the gap of ``chosen`` below the best logit."""
    return np.asarray(_gap(logits_, jnp.asarray(chosen, jnp.int32)))


def pack(seqs: Sequence[Sequence[int]], length: int) -> np.ndarray:
    """Sequences right-padded with token 0 to ``length`` (the causal mask
    keeps the padding out of every real position)."""
    out = np.zeros((len(seqs), length), np.int32)
    for i, s in enumerate(seqs):
        out[i, :len(s)] = s
    return out
