"""The metrics of one run.

End-to-end metrics are taken by the harness itself from its own clock;
their names say what they are (``tokens_per_s``, ``setup_s``,
``itl_p<q>_ms``, ``ttft_p<q>_ms``), so a new percentile needs only a
new entry in ``BENCHMARK.json``.  Each per-layer metric is read by its
own ``bench/metrics/<name>.py``, whose ``read(ctx)`` returns a number or
``None`` when there is nothing to read; ``None`` leaves the metric out of
the result.
"""

from __future__ import annotations

import functools
import importlib.util
import re
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

import flops
from harness import tracefile


class Context:
    """What a metric may read about one run."""

    def __init__(self, cfg, window, setup_s, chips, device):
        self.cfg, self.window, self.setup_s = cfg, window, setup_s
        self.chips, self.device = chips, device

    @property
    def window_s(self) -> float:
        return self.window.t1 - self.window.t0

    @functools.cached_property
    def peaks(self) -> Dict[str, float]:
        return flops.load_peaks(self.device["kind"])

    @functools.cached_property
    def trace(self) -> Optional[Dict]:
        if not self.window.trace:
            return None
        return tracefile.reduce(self.window.trace)

    def tokens(self) -> int:
        return sum(len(s.tokens) for s in self.window.streams)

    def gaps(self) -> List[float]:
        """Every gap between consecutive output tokens of a stream."""
        out: List[float] = []
        for s in self.window.streams:
            out.extend(np.diff(s.times).tolist())
        return out

    def ttfts(self) -> List[float]:
        return [s.times[0] - s.due for s in self.window.streams if s.times]

    def traced_steps(self) -> List[List[int]]:
        a, b = self.window.trace_steps
        return self.window.steps[a:b]

    def traced_prefill_tokens(self) -> int:
        a, b = self.window.trace_prefills
        return sum(t1 - t0 for t0, t1 in self.window.prefills[a:b])

    def traced_flops(self) -> float:
        """Model operations of every token computed in the traced
        interval: decoded tokens at their positions and prefilled prompt
        tokens (tokens the prefix cache served are not computed and do
        not count)."""
        a, b = self.window.trace_steps
        total = sum(flops.decode_flops(self.cfg, pos)
                    for pos in self.window.steps[a:b])
        a, b = self.window.trace_prefills
        for t0, t1 in self.window.prefills[a:b]:
            total += sum(flops.flops_per_token(self.cfg, p)
                         for p in range(t0, t1))
        return total


def _pct(values: List[float], q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if values else None


def end_to_end_value(name: str, ctx: Context) -> Optional[float]:
    if name == "setup_s":
        return ctx.setup_s
    if name == "tokens_per_s":
        return ctx.tokens() / ctx.window_s
    m = re.fullmatch(r"(itl|ttft)_p(\d+)_ms", name)
    if m:
        vals = ctx.gaps() if m.group(1) == "itl" else ctx.ttfts()
        v = _pct(vals, float(m.group(2)))
        return None if v is None else 1e3 * v
    raise KeyError(f"the harness has no end-to-end metric {name!r}")


def _applies(metric: Dict, cell: Dict) -> bool:
    return cell["name"] in metric.get("workloads", [cell["name"]])


def end_to_end(spec: Dict, cell: Dict, ctx: Context) -> Dict:
    out = {}
    for m in spec["end_to_end"]:
        if _applies(m, cell):
            v = end_to_end_value(m["name"], ctx)
            if v is not None:
                out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def reader(directory: Path, name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{re.sub(r'[^0-9A-Za-z_]', '_', name)}",
        directory / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer(spec: Dict, cell: Dict, ctx: Context, directory: Path) -> Dict:
    out = {}
    for m in spec["per_layer"]:
        if _applies(m, cell):
            v = reader(directory, m["name"])(ctx)
            if v is not None:
                out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
