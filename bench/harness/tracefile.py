"""From a JAX profiler trace to the numbers the per-layer metrics read.

Two steps, kept apart so that the second can be checked on a small
recorded trace without JAX:

``extract(trace_dir, step_module)`` reads the ``.xplane.pb`` the
profiler wrote and keeps, in nanoseconds on the profiler's clock: every
device program run (the ``XLA Modules`` line of each TPU plane), every
device operation (the ``XLA Ops`` line), the harness's own host spans
(``bench.*`` annotations), and the module name of the scheduler's step.
The span ``bench.window`` bounds the traced interval.

``reduce(tr)`` turns that into: the interval's length, the device's busy
time (the union of operation intervals, averaged over the chips), the
time and count of each of the step's two shapes, the operations that
took most time, and the longest idle gaps, each named by the innermost
harness span that holds it.

The step's T=1 and T=8 shapes are one jit in the program, so their runs
carry one module name (each compiled shape with its own id).  A chip runs
the calls it is sent in the order they were sent, and the interval opens
and closes between scheduler steps, when no step call is pending; so the
k-th run of the step's module in the interval is the k-th call that the
harness's ``bench.call_*`` spans record there, and that call's span names
its shape.  Each module id has to come out as one shape, and the counts
have to agree, or the reduction fails.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
# the host spans around the step's calls, by shape
CALLS = {"decode": "bench.call_decode", "prefill": "bench.call_prefill"}


def extract(trace_dir: str, step_module: str) -> Dict:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = {"devices": {}, "host": [], "step_module": step_module}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules",
                       "XLA Ops": "ops"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    dev[key].append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
            if dev["modules"] or dev["ops"]:
                out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        out["host"].append([ev.name, int(ev.start_ns),
                                            int(ev.duration_ns)])
    return out


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(ev, lo, hi) -> Optional[Tuple[int, int]]:
    s, e = max(ev[1], lo), min(ev[1] + ev[2], hi)
    return (s, e) if e > s else None


def _label(host: List, s: int, e: int) -> str:
    """The innermost harness span that holds the middle of [s, e)."""
    mid = (s + e) // 2
    best = None
    for name, hs, hd in host:
        if name != WINDOW and hs <= mid < hs + hd:
            if best is None or hd < best[1]:
                best = (name, hd)
    return best[0] if best else "bench.outside_spans"


def _short(name: str) -> str:
    """An operation's name without the HLO text that follows it."""
    return name.split(" = ")[0].lstrip("%")


def _leaves(ops: List) -> List:
    """The operations that hold no other (a loop's event spans the
    operations of its body; counting both would count the time twice)."""
    ops = sorted(ops, key=lambda ev: (ev[1], -ev[2]))
    out = []
    for i, ev in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt[1] >= ev[1] + ev[2]:
            out.append(ev)
    return out


def _shapes(tr: Dict, mods: List, lo: int, hi: int) -> Dict[str, str]:
    """The shape (``CALLS`` key) of each of the step module's ids, by
    matching its runs in [lo, hi) with the harness's calls there, in
    order."""
    keys = {v: k for k, v in CALLS.items()}
    calls = sorted((h for h in tr["host"]
                    if h[0] in keys and lo <= h[1] < hi), key=lambda h: h[1])
    runs = [ev for ev in mods if ev[0].split("(")[0] == tr["step_module"]
            and lo <= ev[1] < hi]
    if len(runs) != len(calls):
        raise ValueError(f"{len(runs)} runs of {tr['step_module']} in the "
                         f"interval against {len(calls)} calls")
    shape: Dict[str, str] = {}
    for ev, call in zip(runs, calls):
        if shape.setdefault(ev[0], keys[call[0]]) != keys[call[0]]:
            raise ValueError(f"{ev[0]} ran as both shapes")
    return shape


def reduce(tr: Dict, top: int = 10) -> Dict:
    spans = [h for h in tr["host"] if h[0] == WINDOW]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW} span")
    lo = spans[0][1]
    hi = lo + spans[0][2]
    window_ns = hi - lo
    busy_total = 0
    programs = {k: {"calls": 0, "seconds": 0.0} for k in CALLS}
    op_time: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[str, float]] = []
    for dev in tr["devices"].values():
        mods = sorted(dev["modules"], key=lambda ev: ev[1])
        shape = _shapes(tr, mods, lo, hi)
        evs = dev["ops"] or mods
        busy = union([c for c in (_clip(ev, lo, hi) for ev in evs) if c])
        busy_total += sum(e - s for s, e in busy)
        for ev in mods:
            key = shape.get(ev[0])
            # a run belongs to the interval when it starts inside it
            if key and lo <= ev[1] < hi:
                programs[key]["calls"] += 1
                programs[key]["seconds"] += ev[2] * 1e-9
        starts = [m[1] for m in mods]
        for ev in _leaves(dev["ops"]):
            c = _clip(ev, lo, hi)
            if not c:
                continue
            owner = "other"
            i = bisect.bisect_right(starts, ev[1]) - 1
            if i >= 0 and ev[1] < mods[i][1] + mods[i][2]:
                owner = shape.get(mods[i][0]) or _short(mods[i][0])
            op_time[f"{owner}/{_short(ev[0])}"] += (c[1] - c[0]) * 1e-9
        edge = lo
        for s, e in busy + [(hi, hi)]:
            if s > edge:
                gaps.append((_label(tr["host"], edge, s), (s - edge) * 1e-9))
            edge = max(edge, e)
    n = max(len(tr["devices"]), 1)
    gaps.sort(key=lambda g: -g[1])
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_total * 1e-9 / n,
        "chips": len(tr["devices"]),
        "programs": programs,
        "device_ops": [[k, v] for k, v in ops[:top]],
        "idle_gaps": [[k, v] for k, v in gaps[:top]],
    }
