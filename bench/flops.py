"""Operations and bytes of the dense decoder family, from a configuration's
shapes alone (logical sizes: no lane padding, no padded vocabulary).

A configuration is the dict of a ``bench/configs/<name>.json`` file.  The
counts stand for what the algorithm needs, so they stay the same whatever
implements the step:

* ``flops_per_token(cfg, pos)``: one token at position ``pos`` through
  every layer and the LM head, attending over ``pos + 1`` keys;
* ``decode_bytes(cfg, positions)``: one T=1 decode step over lanes at
  ``positions``: every weight read once (the embedding only for the rows
  looked up), the K/V rows at each lane's live positions read, and one
  K/V row a lane written.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable

BF16 = 2


def head_dim(cfg: Dict) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]


def layer_params(cfg: Dict) -> Dict[str, int]:
    """Parameters of one decoder layer, by part."""
    d, f = cfg["d_model"], cfg["d_ff"]
    dh = head_dim(cfg)
    q = cfg["n_heads"] * dh
    kv = cfg["n_kv_heads"] * dh
    gated = cfg["act"] in ("swiglu", "geglu")
    norm = (2 if cfg["norm"] == "layernorm" else 1) * d
    return {
        "attn": d * q + 2 * d * kv + q * d,
        "ffn": (3 if gated else 2) * d * f,
        "norms": 2 * norm,
    }


def param_counts(cfg: Dict) -> Dict[str, int]:
    d, v = cfg["d_model"], cfg["vocab_size"]
    per_layer = sum(layer_params(cfg).values())
    norm = (2 if cfg["norm"] == "layernorm" else 1) * d
    return {
        "per_layer": per_layer,
        "layers": cfg["n_layers"] * per_layer,
        "embed": v * d,
        "head": 0 if cfg.get("tie_embeddings") else d * v,
        "final_norm": norm,
    }


def flops_per_token(cfg: Dict, pos: int) -> float:
    """Model operations of one token at position ``pos`` (0-based): two per
    multiply-add of every matmul weight, the LM head included, plus
    Q.K^T and P.V over the ``pos + 1`` visible keys."""
    lp = layer_params(cfg)
    matmul = cfg["n_layers"] * (lp["attn"] + lp["ffn"])
    matmul += cfg["d_model"] * cfg["vocab_size"]
    attn = 2 * 2 * cfg["n_layers"] * cfg["n_heads"] * head_dim(cfg) * (pos + 1)
    return 2.0 * matmul + attn


def kv_row_bytes(cfg: Dict) -> int:
    """K and V of one position over every layer."""
    return cfg["n_layers"] * 2 * cfg["n_kv_heads"] * head_dim(cfg) * BF16


def weight_bytes(cfg: Dict, rows: int) -> int:
    """Every weight once; of the embedding only ``rows`` looked-up rows."""
    pc = param_counts(cfg)
    return BF16 * (pc["layers"] + pc["head"] + pc["final_norm"]
                   + rows * cfg["d_model"])


def decode_bytes(cfg: Dict, positions: Iterable[int]) -> int:
    """Bytes one T=1 decode step needs over lanes at ``positions``."""
    positions = list(positions)
    kv = kv_row_bytes(cfg)
    live = sum(p + 1 for p in positions)       # keys read, the new one too
    return weight_bytes(cfg, len(positions)) + kv * (live + len(positions))


def decode_flops(cfg: Dict, positions: Iterable[int]) -> float:
    return sum(flops_per_token(cfg, p) for p in positions)


def load_peaks(device_kind: str, path: Path = None) -> Dict[str, float]:
    """The peaks of ``device_kind`` from ``bench/peaks.json``; an unknown
    kind is an error, never a default."""
    path = path or Path(__file__).resolve().parent / "peaks.json"
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
