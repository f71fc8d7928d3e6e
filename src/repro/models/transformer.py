"""Dense transformer family: starcoder2 / phi3 / minicpm3 (MLA) / gemma-style.

One parameterized implementation covers:

  * GQA attention with RoPE (full or partial rotary), optional head
    padding for tensor parallelism (padded heads are zero-init and
    mathematically inert — their wo rows are zero),
  * Multi-head Latent Attention (MiniCPM3): low-rank q/kv projections;
    training materializes per-head K/V, decoding caches only the latent
    ``c_kv`` + shared rope key and uses the absorbed-matmul form,
  * gated (SwiGLU/GeGLU) and classic (GELU) FFN,
  * prefix-LM masking (PaliGemma's bidirectional image prefix),
  * scan-over-layers with stacked params (compile time independent of
    depth) and optional activation-checkpoint (remat) policy.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.memory.codecs import SCALE_SUFFIX, int8_dequantize, int8_quantize
from repro.models import layers as L


# ---------------------------------------------------------------------- #
# param tables
# ---------------------------------------------------------------------- #


def attention_table(cfg: ArchConfig) -> Dict[str, Any]:
    d = cfg.d_model
    dh = cfg.resolved_head_dim
    hq = cfg.padded_heads
    hkv = cfg.padded_kv_heads
    if cfg.mla is not None:
        m = cfg.mla
        qk_dim = m.qk_nope_dim + m.qk_rope_dim
        return {
            "w_dq": L.LeafSpec((d, m.q_lora_rank), ("d_model", "q_lora")),
            "q_norm": L.LeafSpec((m.q_lora_rank,), ("q_lora",), "ones"),
            "w_uq": L.LeafSpec((m.q_lora_rank, hq * qk_dim), ("q_lora", "heads_dh")),
            "w_dkv": L.LeafSpec(
                (d, m.kv_lora_rank + m.qk_rope_dim), ("d_model", "kv_lora")
            ),
            "kv_norm": L.LeafSpec((m.kv_lora_rank,), ("kv_lora",), "ones"),
            "w_uk": L.LeafSpec(
                (m.kv_lora_rank, hq * m.qk_nope_dim), ("kv_lora", "heads_dh")
            ),
            "w_uv": L.LeafSpec(
                (m.kv_lora_rank, hq * m.v_head_dim), ("kv_lora", "heads_dh")
            ),
            "wo": L.LeafSpec((hq * m.v_head_dim, d), ("heads_dh", "d_model")),
        }
    kv_axis = "kv_heads_dh" if cfg.kv_sharded else "kv_heads_rep"
    return {
        "wq": L.LeafSpec((d, hq * dh), ("d_model", "heads_dh")),
        "wk": L.LeafSpec((d, hkv * dh), ("d_model", kv_axis)),
        "wv": L.LeafSpec((d, hkv * dh), ("d_model", kv_axis)),
        "wo": L.LeafSpec((hq * dh, d), ("heads_dh", "d_model")),
    }


def ffn_table(cfg: ArchConfig, d_ff: Optional[int] = None) -> Dict[str, Any]:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    if cfg.act in ("swiglu", "geglu"):
        return {
            "wg": L.LeafSpec((d, f), ("d_model", "d_ff")),
            "wu": L.LeafSpec((d, f), ("d_model", "d_ff")),
            "wd": L.LeafSpec((f, d), ("d_ff", "d_model")),
        }
    return {
        "wi": L.LeafSpec((d, f), ("d_model", "d_ff")),
        "wd": L.LeafSpec((f, d), ("d_ff", "d_model")),
    }


def layer_table(cfg: ArchConfig) -> Dict[str, Any]:
    return {
        "ln1": L.norm_table(cfg),
        "attn": attention_table(cfg),
        "ln2": L.norm_table(cfg),
        "ffn": ffn_table(cfg),
    }


def param_table(cfg: ArchConfig) -> Dict[str, Any]:
    v = cfg.padded_vocab
    t: Dict[str, Any] = {
        "embed": L.LeafSpec((v, cfg.d_model), ("vocab", "d_model"), "embed"),
        "layers": L.stacked(layer_table(cfg), cfg.n_layers),
        "ln_f": L.norm_table(cfg),
    }
    if not cfg.tie_embeddings:
        t["lm_head"] = L.LeafSpec((cfg.d_model, v), ("d_model", "vocab"))
    return t


def init(key: jax.Array, cfg: ArchConfig):
    params = L.materialize(key, param_table(cfg), jnp.dtype(cfg.param_dtype))
    return _zero_padded_heads(params, cfg)


def param_axes(cfg: ArchConfig):
    return L.axes_of(param_table(cfg))


def param_shapes(cfg: ArchConfig):
    return L.shapes_of(param_table(cfg), jnp.dtype(cfg.param_dtype))


def _zero_padded_heads(params, cfg: ArchConfig):
    """Zero the wo rows of padded heads so they are mathematically inert."""
    extra = cfg.padded_heads - cfg.n_heads
    if extra == 0:
        return params
    dh = cfg.mla.v_head_dim if cfg.mla is not None else cfg.resolved_head_dim
    wo = params["layers"]["attn"]["wo"]
    mask = jnp.arange(cfg.padded_heads * dh) < cfg.n_heads * dh
    params["layers"]["attn"]["wo"] = wo * mask[None, :, None].astype(wo.dtype)
    return params


# ---------------------------------------------------------------------- #
# blocks
# ---------------------------------------------------------------------- #


def _rope_tables(cfg: ArchConfig, positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    if cfg.mla is not None:
        dim = cfg.mla.qk_rope_dim
    else:
        dim = cfg.rope_dim or cfg.resolved_head_dim
    return L.rope_freqs(dim, cfg.rope_theta, positions)


def attention_block(
    p: Dict[str, jax.Array],
    x: jax.Array,                 # (B, T, D)
    cfg: ArchConfig,
    cos: jax.Array,
    sin: jax.Array,
    prefix_len: int = 0,
    causal: bool = True,
) -> jax.Array:
    b, t, d = x.shape
    cd = jnp.dtype(cfg.compute_dtype)
    xc = x.astype(cd)
    hq = cfg.padded_heads
    if cfg.mla is not None:
        m = cfg.mla
        qk_dim = m.qk_nope_dim + m.qk_rope_dim
        cq = L.rmsnorm(xc @ p["w_dq"].astype(cd), p["q_norm"], cfg.norm_eps)
        q = (cq @ p["w_uq"].astype(cd)).reshape(b, t, hq, qk_dim)
        q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim :]
        dkv = xc @ p["w_dkv"].astype(cd)
        ckv = L.rmsnorm(dkv[..., : m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
        k_rope = dkv[..., m.kv_lora_rank :][:, :, None, :]  # (B,T,1,rope)
        q_rope = L.apply_rope(q_rope, cos, sin)
        k_rope = L.apply_rope(k_rope, cos, sin)
        k_nope = (ckv @ p["w_uk"].astype(cd)).reshape(b, t, hq, m.qk_nope_dim)
        v = (ckv @ p["w_uv"].astype(cd)).reshape(b, t, hq, m.v_head_dim)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, t, hq, m.qk_rope_dim))], axis=-1
        )
        out = L.flash_attention(
            q_full, k_full, v, causal=causal, prefix_len=prefix_len,
            scale=qk_dim ** -0.5,
        )
        return (out.reshape(b, t, hq * m.v_head_dim) @ p["wo"].astype(cd)).astype(x.dtype)

    dh = cfg.resolved_head_dim
    hkv = cfg.padded_kv_heads
    q = (xc @ p["wq"].astype(cd)).reshape(b, t, hq, dh)
    k = (xc @ p["wk"].astype(cd)).reshape(b, t, hkv, dh)
    v = (xc @ p["wv"].astype(cd)).reshape(b, t, hkv, dh)
    if cfg.rope_theta > 0:
        q = L.apply_rope(q, cos, sin)
        k = L.apply_rope(k, cos, sin)
    out = L.flash_attention(q, k, v, causal=causal, prefix_len=prefix_len)
    return (out.reshape(b, t, hq * dh) @ p["wo"].astype(cd)).astype(x.dtype)


def ffn_block(p: Dict[str, jax.Array], x: jax.Array, cfg: ArchConfig) -> jax.Array:
    cd = jnp.dtype(cfg.compute_dtype)
    xc = x.astype(cd)
    act = L.act_fn(cfg.act)
    if cfg.act in ("swiglu", "geglu"):
        h = act(xc @ p["wg"].astype(cd)) * (xc @ p["wu"].astype(cd))
    else:
        h = act(xc @ p["wi"].astype(cd))
    return (h @ p["wd"].astype(cd)).astype(x.dtype)


def decoder_layer(
    lp: Dict[str, Any],
    x: jax.Array,
    cfg: ArchConfig,
    cos: jax.Array,
    sin: jax.Array,
    prefix_len: int = 0,
) -> jax.Array:
    x = x + attention_block(
        lp["attn"], L.apply_norm(cfg, x, lp["ln1"]), cfg, cos, sin, prefix_len
    )
    x = x + ffn_block(lp["ffn"], L.apply_norm(cfg, x, lp["ln2"]), cfg)
    return x


# ---------------------------------------------------------------------- #
# full-sequence forward (train / prefill)
# ---------------------------------------------------------------------- #


def forward(
    params: Dict[str, Any],
    batch: Dict[str, jax.Array],
    cfg: ArchConfig,
    remat: bool = True,
    prefix_embeds: Optional[jax.Array] = None,
    mesh=None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Token (+ optional prefix embedding) sequence -> next-token logits."""
    if cfg.seq_parallel and mesh is not None:
        if cfg.mla is not None:
            return _forward_mla_seqpar(params, batch, cfg, mesh)
        return _forward_gqa_seqpar(params, batch, cfg, mesh)
    tokens = batch["tokens"]
    cd = jnp.dtype(cfg.compute_dtype)
    x = L.embed_tokens(params["embed"], tokens, cd)
    prefix_len = 0
    if prefix_embeds is not None:
        prefix_len = prefix_embeds.shape[1]
        x = jnp.concatenate([prefix_embeds.astype(cd), x], axis=1)
    t = x.shape[1]
    positions = jnp.arange(t)
    cos, sin = _rope_tables(cfg, positions)

    def body(h, lp):
        return decoder_layer(lp, h, cfg, cos, sin, prefix_len), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["layers"], unroll=cfg.scan_unroll)
    x = L.apply_norm(cfg, x, params["ln_f"])
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = L.lm_logits(x, head, cfg.vocab_size, cd)
    if prefix_len:
        logits = logits[:, prefix_len:]
    return logits, {}


# ---------------------------------------------------------------------- #
# decode (serve) path
# ---------------------------------------------------------------------- #


def cache_table(cfg: ArchConfig, batch: int, max_len: int) -> Dict[str, Any]:
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "ckv": L.LeafSpec(
                (cfg.n_layers, batch, max_len, m.kv_lora_rank),
                ("layers", "batch", "kv_seq", None),
                "zeros",
            ),
            "k_rope": L.LeafSpec(
                (cfg.n_layers, batch, max_len, m.qk_rope_dim),
                ("layers", "batch", "kv_seq", None),
                "zeros",
            ),
        }
    dh = cfg.resolved_head_dim
    return {
        "k": L.LeafSpec(
            (cfg.n_layers, batch, max_len, cfg.padded_kv_heads, dh),
            ("layers", "batch", "kv_seq", None, None),
            "zeros",
        ),
        "v": L.LeafSpec(
            (cfg.n_layers, batch, max_len, cfg.padded_kv_heads, dh),
            ("layers", "batch", "kv_seq", None, None),
            "zeros",
        ),
    }


def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    return L.materialize(jax.random.PRNGKey(0), cache_table(cfg, batch, max_len), dtype)


def cache_axes(cfg: ArchConfig, batch: int = 1, max_len: int = 1):
    return L.axes_of(cache_table(cfg, batch, max_len))


def _mla_decode_attention(
    p: Dict[str, jax.Array],
    x: jax.Array,            # (B, D) current token embedding (normed)
    ckv_cache: jax.Array,    # (B, S, kv_lora)
    krope_cache: jax.Array,  # (B, S, rope_dim)
    cfg: ArchConfig,
    pos: jax.Array,          # scalar position
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Absorbed-matmul MLA decode: attention in latent space.

    scores_h = q_nope_h^T W_uk_h c_kv  +  q_rope_h^T k_rope
    out_h    = (probs · c_kv) W_uv_h
    The per-head K/V are never materialized; cache is rank+rope wide.
    """
    m = cfg.mla
    cd = x.dtype
    b = x.shape[0]
    hq = cfg.padded_heads
    qk_dim = m.qk_nope_dim + m.qk_rope_dim
    cq = L.rmsnorm(x @ p["w_dq"].astype(cd), p["q_norm"], cfg.norm_eps)
    q = (cq @ p["w_uq"].astype(cd)).reshape(b, hq, qk_dim)
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim :]
    cos, sin = _rope_tables(cfg, pos[None])
    q_rope = L.apply_rope(q_rope[:, None], cos, sin)[:, 0]

    dkv = x @ p["w_dkv"].astype(cd)
    ckv_new = L.rmsnorm(dkv[..., : m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    krope_new = L.apply_rope(dkv[:, None, None, m.kv_lora_rank :], cos, sin)[:, 0, 0]

    ckv_cache = jax.lax.dynamic_update_slice_in_dim(
        ckv_cache, ckv_new[:, None].astype(ckv_cache.dtype), pos, axis=1
    )
    krope_cache = jax.lax.dynamic_update_slice_in_dim(
        krope_cache, krope_new[:, None].astype(krope_cache.dtype), pos, axis=1
    )

    w_uk = p["w_uk"].astype(cd).reshape(m.kv_lora_rank, hq, m.qk_nope_dim)
    q_abs = jnp.einsum("bhn,rhn->bhr", q_nope, w_uk)  # (B, H, kv_lora)
    s = jnp.einsum("bhr,bsr->bhs", q_abs, ckv_cache.astype(cd))
    s = s + jnp.einsum("bhp,bsp->bhs", q_rope, krope_cache.astype(cd))
    s = (s * (qk_dim ** -0.5)).astype(jnp.float32)
    mask = jnp.arange(ckv_cache.shape[1])[None, None, :] <= pos
    s = jnp.where(mask, s, L._mask_value(s.dtype))
    probs = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhs,bsr->bhr", probs.astype(cd), ckv_cache.astype(cd))
    w_uv = p["w_uv"].astype(cd).reshape(m.kv_lora_rank, hq, m.v_head_dim)
    out = jnp.einsum("bhr,rhv->bhv", ctx, w_uv).reshape(b, hq * m.v_head_dim)
    return out @ p["wo"].astype(cd), ckv_cache, krope_cache


def decode_step(
    params: Dict[str, Any],
    cache: Dict[str, Any],
    tokens: jax.Array,        # (B,) current token ids
    pos: jax.Array,           # scalar: current position in the cache
    cfg: ArchConfig,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """One decode step for the whole batch; scan over stacked layers."""
    cd = jnp.dtype(cfg.compute_dtype)
    x = L.embed_tokens(params["embed"], tokens, cd)  # (B, D)
    b = x.shape[0]
    hq = cfg.padded_heads
    dh = cfg.resolved_head_dim
    cos, sin = _rope_tables(cfg, pos[None] if jnp.ndim(pos) == 0 else pos)

    def body(h, xs):
        lp, lcache = xs
        xin = L.apply_norm(cfg, h[:, None], lp["ln1"])[:, 0]
        if cfg.mla is not None:
            attn_out, ckv, krope = _mla_decode_attention(
                lp["attn"], xin, lcache["ckv"], lcache["k_rope"], cfg, pos
            )
            new_cache = {"ckv": ckv, "k_rope": krope}
        else:
            p = lp["attn"]
            q = (xin @ p["wq"].astype(cd)).reshape(b, hq, dh)
            knew = (xin @ p["wk"].astype(cd)).reshape(b, cfg.padded_kv_heads, dh)
            vnew = (xin @ p["wv"].astype(cd)).reshape(b, cfg.padded_kv_heads, dh)
            if cfg.rope_theta > 0:
                q = L.apply_rope(q[:, None], cos, sin)[:, 0]
                knew = L.apply_rope(knew[:, None], cos, sin)[:, 0]
            kc = jax.lax.dynamic_update_slice_in_dim(
                lcache["k"], knew[:, None].astype(lcache["k"].dtype), pos, axis=1
            )
            vc = jax.lax.dynamic_update_slice_in_dim(
                lcache["v"], vnew[:, None].astype(lcache["v"].dtype), pos, axis=1
            )
            lengths = jnp.full((b,), pos + 1, jnp.int32)
            attn_out = L.decode_attention(q, kc, vc, lengths).reshape(b, hq * dh)
            attn_out = attn_out.astype(cd) @ p["wo"].astype(cd)
            new_cache = {"k": kc, "v": vc}
        h = h + attn_out.astype(h.dtype)
        xff = L.apply_norm(cfg, h[:, None], lp["ln2"])[:, 0]
        h = h + ffn_block(lp["ffn"], xff[:, None], cfg)[:, 0]
        return h, new_cache

    x, new_cache = jax.lax.scan(body, x, (params["layers"], cache),
                                unroll=cfg.scan_unroll)
    x = L.apply_norm(cfg, x[:, None], params["ln_f"])[:, 0]
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = L.lm_logits(x[:, None], head, cfg.vocab_size, cd)[:, 0]
    return logits, new_cache


# ---------------------------------------------------------------------- #
# paged decode (pool-resident page tables)
# ---------------------------------------------------------------------- #

# The pool page that takes the K/V rows the paged step must discard: rows
# fed PAD_TOKEN (an admission chunk's pad) and rows past a lane's last
# slot.  Idle scheduler lanes point their whole table at it; the page
# pool never hands it out.
TRASH_PAGE = 0
PAD_TOKEN = -1


def paged_decode_step(
    params: Dict[str, Any],
    pools: Dict[str, jax.Array],  # cache leaves as (L, P, page_tokens, *rest)
    tables: jax.Array,            # (B, nP) int32: logical page -> pool slot
    pos: jax.Array,               # (B,) per-lane write cursor
    tokens: jax.Array,            # (B, T) token ids to consume at pos..pos+T-1
    cfg: ArchConfig,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Multi-token decode straight on the shared page pool.

    The KV cache never exists contiguously: reads gather through each
    lane's page-table row and writes scatter into ``pool[phys, offset]``,
    so admitting / parking / resuming a stream moves table entries, not
    KV bytes.  Row t of a lane consumes ``tokens[b, t]`` at position
    ``pos[b] + t`` and outputs the greedy token *after* it: T = 1 is the
    decode step, T = ``spec_k`` + 1 speculative verification (inputs
    [next_token, candidate_1, ..], the caller commits the accepted
    prefix), and a long T admission prefill.

    Layer-major over the T rows: one scan over the layers carries all
    B x T rows, so each layer's weights are read once per call and its
    projections and FFN are ``(B*T, .)`` matmuls.  Per layer the B x T
    new K/V rows land in the pool in one scatter, each lane's pages come
    back in one gather ``(B, s_pad, ..)``, and row t attends to the keys
    at positions <= ``pos[b] + t`` — its own chunk's earlier rows
    included, later ones masked.

    Exactness contract (what the differential oracle tests check):
    every op acts on each row alone — the B x T rows only widen the row
    axis of the matmuls, norms, softmaxes and argmax that a single-token
    step runs (at T = 1 they are its ops) — and the scatter/gather is
    pure data movement, so each row computes what a single-token step at
    its position computes, up to the rounding of its matmuls.  That is
    not bit for bit: XLA:CPU fuses a one-row matmul into its consumer as
    an element-by-element loop and runs a matmul of two or more rows as
    a library GEMM, and the two can differ in the last bit.  A batch-1
    ``decode_step`` is the one-row kind; a decode step over several
    lanes and an admission-prefill chunk are the many-row kind, so their
    K/V and logits can differ from it in the last bit, and the emitted
    tokens equal single-token greedy decode except where such a
    difference decides a near-tie of the two top logits.

    Rows fed ``PAD_TOKEN`` (an admission chunk's pad) or whose
    position falls past the lane's last slot write their K/V into the
    pool's :data:`TRASH_PAGE`, so no scatter ever holds a pad
    row and a real row at one slot; their outputs are garbage for the
    caller to drop.  Other rows beyond a lane's committed length
    (rejected candidates) write into the lane's *own* pages, where later
    real writes overwrite them and the length mask never reads them.

    Quantized pools (``DevicePagePool(quantized=True)``) carry an int8
    buffer per KV leaf plus a ``<leaf>__scale`` float32 companion; the
    step quantizes each new row per channel on write and dequantizes the
    gathered pages before attention — the tolerance story lives in the
    int8 codec gate, the exactness contract above applies to the
    plain-dtype pools only.

    Returns ``(out (B, T) int32 argmax tokens, new_pools)``.
    """
    cd = jnp.dtype(cfg.compute_dtype)
    b, t = tokens.shape
    n = b * t                      # rows, lane-major: row b*T + t
    hq = cfg.padded_heads
    dh = cfg.resolved_head_dim
    quantized = any(k.endswith(SCALE_SUFFIX) for k in pools)
    first = next(iter(pools.values()))
    page_tokens = first.shape[2]
    s_pad = tables.shape[1] * page_tokens

    p_bt = pos[:, None] + jnp.arange(t, dtype=pos.dtype)[None, :]   # (B, T)
    wp = jnp.minimum(p_bt, s_pad - 1)
    phys = jnp.take_along_axis(tables, wp // page_tokens, axis=1)
    phys = jnp.where((tokens == PAD_TOKEN) | (p_bt >= s_pad), TRASH_PAGE,
                     phys)
    phys, off = phys.reshape(n), (wp % page_tokens).reshape(n)
    x = L.embed_tokens(params["embed"], tokens.reshape(n), cd)   # (B*T, D)
    cos, sin = _rope_tables(cfg, p_bt.reshape(n))

    def pool_write_read(pool, name, new_rows):
        """Scatter the (B*T, *rest) new rows into ``pool[name]`` at
        [phys, off] and gather the (B, s_pad, *rest) view back through
        the tables — through the int8 + scale pair in quantized pools."""
        if not quantized:
            buf = pool[name].at[phys, off].set(
                new_rows.astype(pool[name].dtype))
            out = jnp.take(buf, tables, axis=0)
            return {name: buf}, out.reshape((b, s_pad) + out.shape[3:])
        qv, sv = int8_quantize(new_rows, axis=-1)
        buf = pool[name].at[phys, off].set(qv)
        sbuf = pool[name + SCALE_SUFFIX].at[phys, off].set(sv[..., 0])
        out = int8_dequantize(jnp.take(buf, tables, axis=0),
                              jnp.take(sbuf, tables, axis=0)[..., None])
        return ({name: buf, name + SCALE_SUFFIX: sbuf},
                out.reshape((b, s_pad) + out.shape[3:]))

    def body(h, xs):
        lp, pool = xs
        xin = L.apply_norm(cfg, h[:, None], lp["ln1"])[:, 0]
        p = lp["attn"]
        if cfg.mla is not None:
            m = cfg.mla
            qk_dim = m.qk_nope_dim + m.qk_rope_dim
            cq = L.rmsnorm(xin @ p["w_dq"].astype(cd), p["q_norm"],
                           cfg.norm_eps)
            q = (cq @ p["w_uq"].astype(cd)).reshape(n, hq, qk_dim)
            q_nope = q[..., : m.qk_nope_dim]
            q_rope = L.apply_rope(q[..., m.qk_nope_dim:][:, None],
                                  cos[:, None], sin[:, None])[:, 0]
            dkv = xin @ p["w_dkv"].astype(cd)
            ckv_new = L.rmsnorm(dkv[..., : m.kv_lora_rank], p["kv_norm"],
                                cfg.norm_eps)
            krope_new = L.apply_rope(dkv[:, None, None, m.kv_lora_rank:],
                                     cos[:, None], sin[:, None])[:, 0, 0]
            upd_ckv, ckv_c = pool_write_read(pool, "ckv", ckv_new)
            upd_kr, kr_c = pool_write_read(pool, "k_rope", krope_new)
            w_uk = p["w_uk"].astype(cd).reshape(
                m.kv_lora_rank, hq, m.qk_nope_dim)
            # a lane's T rows of heads side by side: (B, T*H, .)
            q_abs = jnp.einsum("bhn,rhn->bhr", q_nope, w_uk).reshape(
                b, t * hq, m.kv_lora_rank)
            s = jnp.einsum("bhr,bsr->bhs", q_abs, ckv_c.astype(cd))
            s = s + jnp.einsum("bhp,bsp->bhs",
                               q_rope.reshape(b, t * hq, m.qk_rope_dim),
                               kr_c.astype(cd))
            s = (s * (qk_dim ** -0.5)).astype(jnp.float32)
            last = jnp.repeat(p_bt, hq, axis=1)[:, :, None]      # (B, T*H, 1)
            s = jnp.where(jnp.arange(s_pad)[None, None, :] <= last, s,
                          L._mask_value(s.dtype))
            probs = jax.nn.softmax(s, axis=-1)
            ctx = jnp.einsum("bhs,bsr->bhr", probs.astype(cd),
                             ckv_c.astype(cd)).reshape(n, hq, m.kv_lora_rank)
            w_uv = p["w_uv"].astype(cd).reshape(
                m.kv_lora_rank, hq, m.v_head_dim)
            attn_out = jnp.einsum("bhr,rhv->bhv", ctx, w_uv).reshape(
                n, hq * m.v_head_dim)
            attn_out = attn_out @ p["wo"].astype(cd)
            new_pool = {**upd_ckv, **upd_kr}
        else:
            q = (xin @ p["wq"].astype(cd)).reshape(n, hq, dh)
            knew = (xin @ p["wk"].astype(cd)).reshape(
                n, cfg.padded_kv_heads, dh)
            vnew = (xin @ p["wv"].astype(cd)).reshape(
                n, cfg.padded_kv_heads, dh)
            if cfg.rope_theta > 0:
                q = L.apply_rope(q[:, None], cos[:, None], sin[:, None])[:, 0]
                knew = L.apply_rope(knew[:, None], cos[:, None],
                                    sin[:, None])[:, 0]
            upd_k, kc = pool_write_read(pool, "k", knew)
            upd_v, vc = pool_write_read(pool, "v", vnew)
            attn_out = L.decode_attention(
                q.reshape(b, t, hq, dh), kc, vc, p_bt + 1).reshape(n, hq * dh)
            attn_out = attn_out.astype(cd) @ p["wo"].astype(cd)
            new_pool = {**upd_k, **upd_v}
        h = h + attn_out.astype(h.dtype)
        xff = L.apply_norm(cfg, h[:, None], lp["ln2"])[:, 0]
        h = h + ffn_block(lp["ffn"], xff[:, None], cfg)[:, 0]
        return h, new_pool

    x, pools = jax.lax.scan(body, x, (params["layers"], pools),
                            unroll=cfg.scan_unroll)
    x = L.apply_norm(cfg, x[:, None], params["ln_f"])[:, 0]
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = L.lm_logits(x[:, None], head, cfg.vocab_size, cd)[:, 0]
    return logits.argmax(axis=-1).astype(jnp.int32).reshape(b, t), pools


# ---------------------------------------------------------------------- #
# Ulysses-style sequence-parallel MLA prefill (beyond-paper, §Perf)
# ---------------------------------------------------------------------- #
#
# Baseline TP prefill pays two full-T activation psums per layer
# (b*T*D each).  MLA's low-rank latents make a cheaper schedule possible:
#
#   * activations stay SEQUENCE-sharded over `model` through the network,
#   * q heads are exchanged with all_to_all (t_local x all-heads  <->
#     full-T x local-heads): bytes ~ b*T*H*dqk / tp per device,
#   * K/V are NEVER exchanged per-head: only the (kv_lora + rope) latent
#     stream is all-gathered (b*T*288 bytes — 30x smaller than one psum),
#     then expanded to the shard's OWN heads locally,
#   * attention output projection uses the (small, low-rank-era) wo
#     replicated: no psum,
#   * FFN stays tensor-parallel, but its down-proj psum now carries only
#     t_local rows: 1/tp of the baseline psum bytes.
#
# Net per-layer collective bytes drop from ~2*b*T*D (psums) to
# ~b*T*(H*(dqk+dv)/tp + latent + D/tp): ~20x less for minicpm3-4b at
# tp=16 (see EXPERIMENTS.md §Perf iteration log).


def _seqpar_layer_specs(cfg: ArchConfig, mesh):
    """shard_map in_specs for the stacked layer params: attention weights
    replicated (low-rank => small), FFN tensor-parallel."""
    from jax.sharding import PartitionSpec as P

    def conv(axes):
        entries = []
        for name in axes:
            if name == "d_ff" and not cfg.replicate_ffn:
                entries.append("model")
            else:
                entries.append(None)
        return P(*entries)

    return jax.tree_util.tree_map(
        conv, L.axes_of(L.stacked(layer_table(cfg), cfg.n_layers)),
        is_leaf=lambda x: isinstance(x, tuple),
    )


def _mla_attn_ulysses(p, x, cfg: ArchConfig, t_loc: int):
    """One MLA attention block on a sequence shard (inside shard_map)."""
    m = cfg.mla
    cd = x.dtype
    b = x.shape[0]
    tp = jax.lax.psum(1, "model")
    ti = jax.lax.axis_index("model")
    hq = cfg.padded_heads
    h_loc = hq // tp
    qk_dim = m.qk_nope_dim + m.qk_rope_dim

    pos = ti * t_loc + jnp.arange(t_loc)
    cos, sin = L.rope_freqs(m.qk_rope_dim, cfg.rope_theta, pos)

    # local projections (all heads, local tokens)
    cq = L.rmsnorm(x @ p["w_dq"], p["q_norm"], cfg.norm_eps)
    q = (cq @ p["w_uq"]).reshape(b, t_loc, hq, qk_dim)
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim :]
    q_rope = L.apply_rope(q_rope, cos, sin)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)

    dkv = x @ p["w_dkv"]
    ckv = L.rmsnorm(dkv[..., : m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = L.apply_rope(dkv[:, :, None, m.kv_lora_rank :], cos, sin)[:, :, 0]

    # exchange: q -> (b, T, h_loc, qk); latents -> full T (tiny)
    q = jax.lax.all_to_all(q, "model", split_axis=2, concat_axis=1, tiled=True)
    ckv_full = jax.lax.all_gather(ckv, "model", axis=1, tiled=True)
    krope_full = jax.lax.all_gather(k_rope, "model", axis=1, tiled=True)

    # expand ONLY this shard's heads from the latent stream
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, hq, m.qk_nope_dim)
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, hq, m.v_head_dim)
    w_uk_loc = jax.lax.dynamic_slice_in_dim(w_uk, ti * h_loc, h_loc, axis=1)
    w_uv_loc = jax.lax.dynamic_slice_in_dim(w_uv, ti * h_loc, h_loc, axis=1)
    k_nope = jnp.einsum("btr,rhn->bthn", ckv_full, w_uk_loc)
    v = jnp.einsum("btr,rhv->bthv", ckv_full, w_uv_loc)
    t_full = k_nope.shape[1]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(krope_full[:, :, None, :],
                                  (b, t_full, h_loc, m.qk_rope_dim))], axis=-1)

    out = L.flash_attention(q, k, v, causal=True, scale=qk_dim ** -0.5)
    # back to (b, t_loc, all heads, dv); wo is replicated: no psum
    out = jax.lax.all_to_all(out, "model", split_axis=1, concat_axis=2, tiled=True)
    return out.reshape(b, t_loc, hq * m.v_head_dim) @ p["wo"]


def _ffn_tp_island(p, x, cfg: ArchConfig):
    """Tensor-parallel FFN fed by a sequence shard.

    T and F cannot both shard over the same mesh axis, so the schedule is
    all-gather(x: t_loc->T, bf16) -> column/row TP -> reduce-scatter the
    output back to t_loc rows.  AG+RS in bf16 still moves ~2x less than
    the baseline full-T fp32 psum, and the attention path's psums are
    gone entirely (see _mla_attn_ulysses / _gqa_attn_ulysses).
    """
    act = L.act_fn(cfg.act)
    if cfg.replicate_ffn:
        # full FFN weights on every shard: pure local math on t_loc rows
        if cfg.act in ("swiglu", "geglu"):
            return (act(x @ p["wg"]) * (x @ p["wu"])) @ p["wd"]
        return act(x @ p["wi"]) @ p["wd"]
    xf = jax.lax.all_gather(x, "model", axis=1, tiled=True)   # (b, T, D)
    if cfg.act in ("swiglu", "geglu"):
        h = act(xf @ p["wg"]) * (xf @ p["wu"])
    else:
        h = act(xf @ p["wi"])
    part = h @ p["wd"]                                         # partial over F
    return jax.lax.psum_scatter(part, "model", scatter_dimension=1, tiled=True)


def _forward_mla_seqpar(params, batch, cfg: ArchConfig, mesh):
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    tokens = batch["tokens"]
    cd = jnp.dtype(cfg.compute_dtype)
    x = L.embed_tokens(params["embed"], tokens, cd)

    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    xspec = P(dp_axes if dp_axes else None, "model", None)
    lspecs = _seqpar_layer_specs(cfg, mesh)

    layers_c = jax.tree_util.tree_map(lambda a: a.astype(cd), params["layers"])

    def island(x_loc, layers):
        t_loc = x_loc.shape[1]

        def body(h, lp):
            h = h + _mla_attn_ulysses(
                lp["attn"], L.apply_norm(cfg, h, lp["ln1"]), cfg, t_loc)
            h = h + _ffn_tp_island(lp["ffn"], L.apply_norm(cfg, h, lp["ln2"]), cfg)
            return h, None

        x_loc, _ = jax.lax.scan(body, x_loc, layers, unroll=cfg.scan_unroll)
        return x_loc

    x = shard_map(
        island, mesh=mesh,
        in_specs=(xspec, lspecs), out_specs=xspec, check_rep=False,
    )(x, layers_c)

    x = L.apply_norm(cfg, x, params["ln_f"])
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = L.lm_logits(x, head, cfg.vocab_size, cd)
    return logits, {}


def _gqa_attn_ulysses(p, x, cfg: ArchConfig, t_loc: int):
    """Ulysses attention for plain GQA (inside shard_map, inference).

    q: local tokens x ALL heads (replicated wq) -> all_to_all to full-T x
    local heads.  K/V: GQA's few kv heads are all-gathered full-T (tiny:
    kv=4 => 67 MB vs the 3.2 GB baseline psum).  wo replicated: no psum.
    """
    cd = x.dtype
    b = x.shape[0]
    tp = jax.lax.psum(1, "model")
    ti = jax.lax.axis_index("model")
    hq = cfg.padded_heads
    hkv = cfg.padded_kv_heads
    h_loc = hq // tp
    dh = cfg.resolved_head_dim

    pos = ti * t_loc + jnp.arange(t_loc)
    cos, sin = L.rope_freqs(cfg.rope_dim or dh, cfg.rope_theta, pos)

    q = (x @ p["wq"]).reshape(b, t_loc, hq, dh)
    k = (x @ p["wk"]).reshape(b, t_loc, hkv, dh)
    v = (x @ p["wv"]).reshape(b, t_loc, hkv, dh)
    if cfg.rope_theta > 0:
        q = L.apply_rope(q, cos, sin)
        k = L.apply_rope(k, cos, sin)

    q = jax.lax.all_to_all(q, "model", split_axis=2, concat_axis=1, tiled=True)
    k = jax.lax.all_gather(k, "model", axis=1, tiled=True)   # (b, T, hkv, dh)
    v = jax.lax.all_gather(v, "model", axis=1, tiled=True)

    # map this shard's q heads to their kv group: contiguous q-head blocks
    # of size hq/hkv share one kv head; slice the kv heads we need
    g = hq // hkv
    kv_start = (ti * h_loc) // g
    kv_count = max(1, h_loc // g) if h_loc >= g else 1
    # simplest exact mapping: gather per-local-head kv index
    head_ids = ti * h_loc + jnp.arange(h_loc)
    kv_ids = head_ids // g
    k_loc = jnp.take(k, kv_ids, axis=2)                      # (b, T, h_loc, dh)
    v_loc = jnp.take(v, kv_ids, axis=2)
    out = L.flash_attention(q, k_loc, v_loc, causal=True)
    out = jax.lax.all_to_all(out, "model", split_axis=1, concat_axis=2, tiled=True)
    return out.reshape(b, t_loc, hq * dh) @ p["wo"]


def _forward_gqa_seqpar(params, batch, cfg: ArchConfig, mesh):
    """Sequence-parallel prefill for non-MLA dense archs (inference)."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    tokens = batch["tokens"]
    cd = jnp.dtype(cfg.compute_dtype)
    x = L.embed_tokens(params["embed"], tokens, cd)

    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    xspec = P(dp_axes if dp_axes else None, "model", None)
    lspecs = _seqpar_layer_specs(cfg, mesh)
    layers_c = jax.tree_util.tree_map(lambda a: a.astype(cd), params["layers"])

    def island(x_loc, layers):
        t_loc = x_loc.shape[1]

        def body(h, lp):
            h = h + _gqa_attn_ulysses(
                lp["attn"], L.apply_norm(cfg, h, lp["ln1"]), cfg, t_loc)
            h = h + _ffn_tp_island(lp["ffn"], L.apply_norm(cfg, h, lp["ln2"]), cfg)
            return h, None

        x_loc, _ = jax.lax.scan(body, x_loc, layers, unroll=cfg.scan_unroll)
        return x_loc

    x = shard_map(island, mesh=mesh, in_specs=(xspec, lspecs),
                  out_specs=xspec, check_rep=False)(x, layers_c)
    x = L.apply_norm(cfg, x, params["ln_f"])
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = L.lm_logits(x, head, cfg.vocab_size, cd)
    return logits, {}
