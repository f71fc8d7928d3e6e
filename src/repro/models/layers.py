"""Shared building blocks for all model families.

Parameters are declared through a light *param table*: a nested dict of
``LeafSpec(shape, axes, init)`` where ``axes`` are logical dimension names
("d_model", "heads_dh", "d_ff", "experts", ...).  The sharding layer maps
logical names to mesh axes, so models never mention mesh axes directly.

Attention comes in three exact variants:

  * ``flash_attention``  — chunked running-softmax (memory-bounded, jnp;
    the TPU path swaps in the Pallas kernel via kernels/ops.py),
  * ``decode_attention`` — single-token query over a padded KV cache,
  * cross/prefix masks for enc-dec and VLM prefix-LM.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------- #
# param tables
# ---------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"      # normal | zeros | ones | embed
    scale: Optional[float] = None

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


ParamTable = Dict[str, Any]  # nested dict of LeafSpec


@functools.partial(jax.jit, static_argnums=(1, 3))
def _scaled_normal(key: jax.Array, shape, scale: float, dtype) -> jax.Array:
    # one program per leaf: eagerly, the float32 draw and its scaled copy
    # would both sit in device memory beside the weights built so far.
    # The barrier keeps XLA from folding ``scale`` into the draw's own
    # constant factor, which would round differently from the eager form
    draw = jax.lax.optimization_barrier(jax.random.normal(key, shape))
    return (draw * scale).astype(dtype)


def _init_leaf(key: jax.Array, spec: LeafSpec, dtype) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
    scale = spec.scale if spec.scale is not None else 1.0 / math.sqrt(fan_in)
    if spec.init == "embed":
        scale = spec.scale if spec.scale is not None else 0.02
    return _scaled_normal(key, tuple(spec.shape), scale, jnp.dtype(dtype))


def materialize(key: jax.Array, table: ParamTable, dtype=jnp.float32) -> Any:
    """Instantiate a param table into a pytree of initialized arrays."""
    flat = _flatten_table(table)
    keys = jax.random.split(key, len(flat))
    leaves = {name: _init_leaf(k, spec, dtype) for (name, spec), k in zip(flat.items(), keys)}
    return _unflatten_like(table, leaves)


def axes_of(table: ParamTable) -> Any:
    flat = _flatten_table(table)
    leaves = {name: spec.axes for name, spec in flat.items()}
    return _unflatten_like(table, leaves)


def shapes_of(table: ParamTable, dtype=jnp.float32) -> Any:
    """ShapeDtypeStructs for dry-run lowering (no allocation)."""
    flat = _flatten_table(table)
    leaves = {
        name: jax.ShapeDtypeStruct(spec.shape, dtype) for name, spec in flat.items()
    }
    return _unflatten_like(table, leaves)


def _flatten_table(table: ParamTable, prefix: str = "") -> Dict[str, LeafSpec]:
    out: Dict[str, LeafSpec] = {}
    for k, v in table.items():
        name = f"{prefix}{k}"
        if isinstance(v, LeafSpec):
            out[name] = v
        else:
            out.update(_flatten_table(v, prefix=name + "/"))
    return out


def _unflatten_like(table: ParamTable, leaves: Dict[str, Any], prefix: str = "") -> Any:
    out: Dict[str, Any] = {}
    for k, v in table.items():
        name = f"{prefix}{k}"
        if isinstance(v, LeafSpec):
            out[k] = leaves[name]
        else:
            out[k] = _unflatten_like(v, leaves, prefix=name + "/")
    return out


# ---------------------------------------------------------------------- #
# norms & activations
# ---------------------------------------------------------------------- #


def rmsnorm(x: jax.Array, gamma: jax.Array, eps: float = 1e-5,
            fast: bool = False) -> jax.Array:
    if fast:
        # f32 only inside the reduction; the residual stream is never
        # materialized in f32 and cotangents stay in compute dtype
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True,
                      dtype=jnp.float32)
        return x * jax.lax.rsqrt(ms + eps).astype(x.dtype) * gamma.astype(x.dtype)
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms * gamma.astype(jnp.float32)).astype(x.dtype)


def layernorm(x: jax.Array, gamma: jax.Array, beta: jax.Array, eps: float = 1e-5,
              fast: bool = False) -> jax.Array:
    if fast:
        mu = jnp.mean(x, axis=-1, keepdims=True, dtype=jnp.float32)
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True, dtype=jnp.float32)
        inv = jax.lax.rsqrt(ms - mu * mu + eps).astype(x.dtype)
        return ((x - mu.astype(x.dtype)) * inv * gamma.astype(x.dtype)
                + beta.astype(x.dtype))
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(x.dtype)


def apply_norm(cfg, x: jax.Array, p: Dict[str, jax.Array]) -> jax.Array:
    fast = getattr(cfg, "fast_norms", False)
    if cfg.norm == "layernorm":
        return layernorm(x, p["gamma"], p["beta"], cfg.norm_eps, fast=fast)
    return rmsnorm(x, p["gamma"], cfg.norm_eps, fast=fast)


def norm_table(cfg) -> Dict[str, LeafSpec]:
    t = {"gamma": LeafSpec((cfg.d_model,), ("d_model",), "ones")}
    if cfg.norm == "layernorm":
        t["beta"] = LeafSpec((cfg.d_model,), ("d_model",), "zeros")
    return t


def stacked(table: Dict[str, Any], n: int) -> Dict[str, Any]:
    """Prepend a scan ("layers") dim to every leaf of a layer table."""
    out: Dict[str, Any] = {}
    for k, v in table.items():
        if isinstance(v, LeafSpec):
            out[k] = LeafSpec((n,) + v.shape, ("layers",) + v.axes, v.init, v.scale)
        else:
            out[k] = stacked(v, n)
    return out


def act_fn(name: str) -> Callable[[jax.Array], jax.Array]:
    if name in ("swiglu",):
        return jax.nn.silu
    if name in ("geglu", "gelu"):
        return lambda x: jax.nn.gelu(x, approximate=True)
    if name == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(name)


# ---------------------------------------------------------------------- #
# rotary embeddings (partial-dim aware)
# ---------------------------------------------------------------------- #


def rope_freqs(dim: int, theta: float, positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables: positions (T,) -> (T, dim/2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., T, H, D) rotated on the leading `2*cos.shape[-1]` of D."""
    rot = 2 * cos.shape[-1]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(xr, 2, axis=-1)
    c = cos[..., :, None, :]
    s = sin[..., :, None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return jnp.concatenate([out, xp], axis=-1).astype(x.dtype)


def sinusoidal_positions(n: int, dim: int) -> np.ndarray:
    """Whisper-style fixed sinusoidal position table (n, dim)."""
    pos = np.arange(n)[:, None]
    idx = np.arange(dim // 2)[None, :]
    ang = pos / np.power(10000.0, 2 * idx / dim)
    return np.concatenate([np.sin(ang), np.cos(ang)], axis=-1).astype(np.float32)


# ---------------------------------------------------------------------- #
# attention (exact, chunked running-softmax)
# ---------------------------------------------------------------------- #


def _mask_value(dtype):
    return jnp.asarray(-0.7 * jnp.finfo(jnp.float32).max, jnp.float32)


def flash_attention(
    q: jax.Array,                 # (B, Tq, Hq, D)
    k: jax.Array,                 # (B, Tk, Hkv, D)
    v: jax.Array,                 # (B, Tk, Hkv, Dv)
    causal: bool = True,
    prefix_len: int = 0,          # prefix-LM: first `prefix_len` keys visible to all
    scale: Optional[float] = None,
    q_chunk: int = 2048,
    k_chunk: int = 1024,
    q_offset: Optional[int] = None,
) -> jax.Array:
    """Exact attention with running softmax, chunked over BOTH q and k.

    Peak live logits are O(q_chunk * k_chunk) per head instead of
    O(Tq * Tk) — the pure-jnp realization of the flash algorithm (the
    Pallas kernel in kernels/flash_attention.py is the TPU fast path).
    GQA is handled by broadcasting K/V to the query heads *before*
    chunking: broadcasting a replicated tensor onto a head-sharded layout
    is communication-free under GSPMD, whereas reshaping the sharded query
    head dim into (kv, group) would force a regather.
    """
    b, tq, hq, d = q.shape
    _, tk, hkv, dv = v.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    scale = (d ** -0.5) if scale is None else scale
    q_off = (tk - tq) if q_offset is None else q_offset
    q_chunk = min(q_chunk, tq)
    k_chunk = min(k_chunk, tk)

    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)

    nq = (tq + q_chunk - 1) // q_chunk
    nk = (tk + k_chunk - 1) // k_chunk
    if nq * q_chunk - tq:
        q = jnp.pad(q, ((0, 0), (0, nq * q_chunk - tq), (0, 0), (0, 0)))
    if nk * k_chunk - tk:
        k = jnp.pad(k, ((0, 0), (0, nk * k_chunk - tk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, nk * k_chunk - tk), (0, 0), (0, 0)))
    qs = jnp.moveaxis(q.reshape(b, nq, q_chunk, hq, d), 1, 0)      # (nq,B,qc,H,D)
    ks = jnp.moveaxis(k.reshape(b, nk, k_chunk, hq, d), 1, 0)      # (nk,B,kc,H,D)
    vs = jnp.moveaxis(v.reshape(b, nk, k_chunk, hq, dv), 1, 0)

    def q_body(_, q_xs):
        qc, qidx = q_xs
        q_pos = qidx * q_chunk + jnp.arange(q_chunk) + q_off

        def k_body(carry, k_xs):
            acc, m, l = carry
            kc, vc, kidx = k_xs
            k_pos = kidx * k_chunk + jnp.arange(k_chunk)
            s = jnp.einsum("bqhd,bkhd->bhqk", qc, kc)
            s = (s * scale).astype(jnp.float32)
            valid = k_pos[None, :] < tk
            if causal:
                vis = (k_pos[None, :] <= q_pos[:, None]) | (k_pos[None, :] < prefix_len)
                valid = valid & vis
            s = jnp.where(valid[None, None, :, :], s, _mask_value(s.dtype))
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vc.dtype), vc)
            acc = acc * corr[..., None].astype(acc.dtype) + pv.astype(jnp.float32)
            return (acc, m_new, l), None

        acc0 = jnp.zeros((b, hq, q_chunk, dv), jnp.float32)
        m0 = jnp.full((b, hq, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, hq, q_chunk), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            k_body, (acc0, m0, l0), (ks, vs, jnp.arange(nk))
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)  # (B, H, qc, Dv)

    _, outs = jax.lax.scan(q_body, None, (qs, jnp.arange(nq)))      # (nq,B,H,qc,Dv)
    out = jnp.moveaxis(outs, 0, 2).reshape(b, hq, nq * q_chunk, dv)[:, :, :tq]
    return jnp.moveaxis(out, 1, 2)  # (B, Tq, Hq, Dv)


def decode_attention(
    q: jax.Array,        # (B, Hq, D) or (B, T, Hq, D): new tokens' queries
    k_cache: jax.Array,  # (B, S, Hkv, D)
    v_cache: jax.Array,  # (B, S, Hkv, Dv)
    length: jax.Array,   # (B,) or (B, T): valid cache lengths (incl. current)
    scale: Optional[float] = None,
) -> jax.Array:
    """Queries of new tokens over a padded KV cache: one token per
    sequence, or T, where row t attends to the first ``length[b, t]``
    cache positions.  A lane's T rows sit beside its query heads in one
    matmul row axis."""
    rows = q.ndim == 4
    if not rows:
        q, length = q[:, None], length[:, None]
    b, s, hkv, d = k_cache.shape
    t, hq = q.shape[1:3]
    g = hq // hkv
    scale = (d ** -0.5) if scale is None else scale
    qg = q.reshape(b, t, hkv, g, d)
    logits = jnp.einsum("bthgd,bshd->bhtgs", qg * scale,
                        k_cache).astype(jnp.float32)
    mask = (jnp.arange(s)[None, None, None, None, :]
            < length[:, None, :, None, None])
    logits = jnp.where(mask, logits, _mask_value(logits.dtype))
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhtgs,bshd->bthgd", probs.astype(v_cache.dtype), v_cache)
    out = out.reshape(b, t, hq, -1)
    return out if rows else out[:, 0]


def update_cache(cache: jax.Array, new: jax.Array, pos: jax.Array) -> jax.Array:
    """Insert `new` (B, Hkv, D) at per-batch position `pos` (B,) of a
    (B, S, Hkv, D) cache."""
    b = cache.shape[0]
    one = new[:, None]  # (B, 1, Hkv, D)

    def upd(c, n, p):
        return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), (p, 0, 0))

    return jax.vmap(upd)(cache, one, pos)


# ---------------------------------------------------------------------- #
# embedding / head with vocab padding mask
# ---------------------------------------------------------------------- #


def embed_tokens(embedding: jax.Array, tokens: jax.Array, compute_dtype) -> jax.Array:
    return embedding.astype(compute_dtype)[tokens]


def lm_logits(
    x: jax.Array, head: jax.Array, logical_vocab: int, compute_dtype
) -> jax.Array:
    """Project to (padded) vocab and mask padded columns to -inf."""
    logits = jnp.einsum("btd,dv->btv", x.astype(compute_dtype), head.astype(compute_dtype))
    padded_vocab = head.shape[-1]
    if padded_vocab != logical_vocab:
        col = jnp.arange(padded_vocab)
        logits = jnp.where(col[None, None, :] < logical_vocab, logits, -1e30)
    return logits
