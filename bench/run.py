#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  ``BENCHMARK.json`` names the cell's
configuration (``bench/configs/<config>.json``), traffic mix
(``bench/traffic/<mix>.json``) and metrics; each per-layer metric is read
by ``bench/metrics/<metric>.py``; the limit of the correctness check is
in ``bench/limits/<cell>.json``.  Nothing here names a cell.

Set-up (weights from the seed, the warm-up of every shape the window
uses) is timed as ``setup_s``, from this process's start; then the cell's
traffic runs for ``--seconds``.  Both run in a child process, so that
the chips are free and the program's state is gone when this process
then checks the window's output against the float32 reference.  With
``--trace 0`` the metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from a profiler
trace of part of the window.  Earlier lines (standard error) give the
device, the compile cache, the compilations inside the window and the
check; the last line of standard output is one JSON object.  Without a
TPU, or with fewer chips than the cell asks for, it exits 1 and prints
no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pickle  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from harness import metrics as M  # noqa: E402


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_cell(name: str):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = next(c for c in spec["configs"] if c["name"] == cell["config"])
    cfg = json.loads((ROOT / config["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    return spec, cell, cfg, mix


def serve(cell: dict, cfg: dict, mix: dict, seed: int, seconds: float,
          trace: bool, device, on_built=None):
    """Set-up and the measured window; returns the window's record and
    the device."""
    from harness import local

    w = local.run_window(cfg, mix, seed, seconds, trace, on_built=on_built)
    say(f"window {w.t1 - w.t0:.3f} s; {len(w.streams)} requests sent, "
        f"{sum(len(s.tokens) for s in w.streams)} tokens; "
        f"compilations inside the window: {w.compiles}")
    return w, device


def report(cell: dict, cfg: dict, mix: dict, spec: dict, seed: int,
           trace: bool, device: dict, w, t_start: float) -> dict:
    """The check against the reference and the metrics of a window."""
    from harness import correct, local

    setup_s = w.t0 - t_start
    say(f"set-up {setup_s:.3f} s")
    lim = correct.load_limits(cell["name"])
    done = [s for s in w.streams if len(s.tokens) >= s.req.max_new]
    picked = correct.sample([(s.req.prompt, s.tokens) for s in done], seed,
                            int(lim["sample"]))
    t = time.perf_counter()
    got = correct.read_gaps(cfg, local.model_seed(seed), picked,
                            cfg["serving"]["max_len"])
    checks = {"logit_gap": {"value": got["logit_gap"],
                            "limit": lim["logit_gap"]}}
    ok = bool(picked) and all(c["value"] <= c["limit"]
                              for c in checks.values())
    say(f"check: {len(picked)} requests, {got['tokens']} served tokens "
        f"against the float32 reference in {time.perf_counter() - t:.1f} s")
    say(f"device: {device['platform']} {device['kind']} x{device['count']}")
    ctx = M.Context(cfg=cfg, window=w, setup_s=setup_s, chips=cell["chips"],
                    device=device)
    result = {"correct": ok, "attempted": len(w.streams), "failed": 0}
    dev = dict(device, memory_peak_bytes=w.peak_bytes)
    if trace:
        result["metrics"] = M.per_layer(spec, cell, ctx, BENCH / "metrics")
        if ctx.trace is not None:
            dev["busy_s"] = ctx.trace["busy_s"]
            dev["window_s"] = ctx.trace["window_s"]
            result["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                                   "idle_gaps": ctx.trace["idle_gaps"]}
    else:
        result["metrics"] = M.end_to_end(spec, cell, ctx)
    result["device"] = dev
    for name, c in checks.items():
        say(f"{name}: {c['value']!r} (limit {c['limit']!r})")
    result["checks"] = checks
    return result


def run(cell: dict, cfg: dict, mix: dict, spec: dict, seed: int,
        seconds: float, trace: bool, device, t_start: float = T_START,
        on_built=None) -> dict:
    """Window and check in this one process (the tests' way in; ``main``
    puts the window in a process of its own)."""
    w, device = serve(cell, cfg, mix, seed, seconds, trace, device,
                      on_built=on_built)
    return report(cell, cfg, mix, spec, seed, trace, device, w, t_start)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--window-to", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    spec, cell, cfg, mix = load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    from harness.device import NoAccelerator, enable_cache, stamp

    if args.window_to:
        # the child: set-up and the window; the chips are free again for
        # the check once it has exited
        try:
            device = stamp(cell["chips"])
            enable_cache()
            w, device = serve(cell, cfg, mix, args.seed, args.seconds,
                              bool(args.trace), device)
        except NoAccelerator as e:
            say(str(e))
            return 1
        with open(args.window_to, "wb") as f:
            pickle.dump((w, device), f)
        return 0
    with tempfile.TemporaryDirectory(prefix="bench_run_") as tmp:
        out = os.path.join(tmp, "window.pkl")
        rc = subprocess.run(
            [sys.executable, str(Path(__file__).resolve())]
            + list(argv if argv is not None else sys.argv[1:])
            + ["--window-to", out], stdout=sys.stderr).returncode
        if rc != 0:
            return rc
        with open(out, "rb") as f:
            w, device = pickle.load(f)
    enable_cache()
    result = report(cell, cfg, mix, spec, args.seed, bool(args.trace),
                    device, w, T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
