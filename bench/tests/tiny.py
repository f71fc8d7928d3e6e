"""A tiny configuration and mix for running the harness on the CPU."""

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "starcoder2-7b.codegen"


def spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell():
    return next(w for w in spec()["workloads"] if w["name"] == CELL)


def config():
    """The cell's configuration at a CPU size: every width cut, the
    family's mechanisms (GQA, LayerNorm, GELU, RoPE) kept."""
    cfg = json.loads((BENCH / "configs" / "starcoder2-7b.json").read_text())
    cfg.update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
               d_ff=128, vocab_size=500)
    cfg["serving"] = dict(cfg["serving"], slots=4, max_len=128)
    return cfg


def mix():
    m = json.loads((BENCH / "traffic" / "codegen.json").read_text())
    m["clients"] = 4
    m["prompt"] = dict(m["prompt"], median=20, max=40)
    m["output"] = dict(m["output"], median=24, min=8, max=60)
    return m


DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def fleet_config(workers=2):
    """phi3's fleet configuration at a CPU size: the dense family's
    mechanisms (MHA, RMSNorm, SwiGLU, RoPE) kept, every width cut."""
    cfg = json.loads((BENCH / "configs" / "phi3-mini-3.8b.json").read_text())
    cfg.update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
               d_ff=128, vocab_size=500)
    cfg["serving"] = dict(cfg["serving"], workers=workers, slots=2,
                          max_len=128, pool_pages=None, ckpt_every=4,
                          fast_bytes=8 << 20, shared_capacity=64 << 20)
    return cfg


def fleet_mix(rate=6.0, kill=None):
    """An open-loop chat mix at a CPU size: one shared system prompt,
    lognormal user parts and outputs, seeded arrivals at ``rate`` a
    second, and with ``kill`` the loss of a worker."""
    m = {"rate_per_s": rate, "arrival_strata": 64, "strata": 16,
         "system": {"tokens": 16},
         "prompt": {"median": 12, "sigma": 0.6, "min": 4, "max": 24},
         "output": {"median": 16, "sigma": 0.5, "min": 6, "max": 40},
         "warmup": {"requests": 4, "max_new": 6}}
    if kill is not None:
        m["kill"] = kill
    return m


def fleet_cell():
    """A fleet cell on the CPU; it borrows the codegen cell's limits (a
    bf16 program held to the float32 reference, as here)."""
    return dict(cell(), chips=2)
