#!/usr/bin/env python3
"""The readings a cell's correctness limit is set from, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed: the cell's set-up and a window of ``--seconds`` at the
cell's own load (``bench/run.py``'s child), then, in a process of its
own once the window's has exited, on the same sample of finished
requests that a benchmark run checks: the widest gap of the served
tokens below the float32 reference's best (the program's reading, the
lower one), and the widest gap of the tokens that the reference computed
in float8 puts first (the control's reading, the upper one).  One JSON
line a seed on standard output.  The benchmark's own runs do not run
this.
"""

from __future__ import annotations

import argparse
import json
import os
import pickle
import subprocess
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))


def readings(workload: str, seed: int, window: str) -> dict:
    """Both readings of one window (run in a process of its own)."""
    sys.path.insert(0, str(BENCH.parent / "src"))
    import run
    from harness import correct, local
    from harness.device import enable_cache

    spec, cell, cfg, mix = run.load_cell(workload)
    enable_cache()
    with open(window, "rb") as f:
        w, _ = pickle.load(f)
    lim = correct.load_limits(cell["name"])
    done = [s for s in w.streams if len(s.tokens) >= s.req.max_new]
    picked = correct.sample([(s.req.prompt, s.tokens) for s in done], seed,
                            int(lim["sample"]))
    got = correct.read_gaps(cfg, local.model_seed(seed), picked,
                            cfg["serving"]["max_len"], control=True)
    return dict(got, seed=seed, requests=len(picked), finished=len(done),
                compiles=w.compiles)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--window", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.window:
        print(json.dumps(readings(args.workload, args.seeds[0],
                                  args.window)), flush=True)
        return 0
    for seed in args.seeds:
        with tempfile.TemporaryDirectory(prefix="bench_control_") as tmp:
            out = os.path.join(tmp, "window.pkl")
            common = ["--workload", args.workload, "--seed", str(seed)]
            rc = subprocess.run(
                [sys.executable, str(BENCH / "run.py")] + common
                + ["--seconds", str(args.seconds), "--trace", "0",
                   "--window-to", out], stdout=sys.stderr).returncode
            if rc != 0:
                return rc
            rc = subprocess.run(
                [sys.executable, str(Path(__file__).resolve()),
                 "--workload", args.workload, "--seeds", str(seed),
                 "--window", out]).returncode
            if rc != 0:
                return rc
    return 0


if __name__ == "__main__":
    sys.exit(main())
