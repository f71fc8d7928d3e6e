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
