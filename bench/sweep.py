#!/usr/bin/env python3
"""The sweep that finds the highest arrival rate an open-loop fleet cell
sustains, on the chips of this machine.

    python3 bench/sweep.py --config <name> --traffic <mix> --chips <n> \
        --seed <n> --seconds <s> --rates <r> [<r> ...]

Without ``--chips`` (the CPU) the workers look for no TPU.  ``--workers``
launches that many workers in place of the configuration's: a stand-in
on fewer chips.

The fleet of ``bench/configs/<name>.json`` is launched and warmed once
(``harness/fleet.py``); then, for each rate, one open-loop window of
``--seconds`` of the cell's mix at that rate and with no worker lost,
under seed ``--seed`` + i for the i-th rate (so that no window finds the
prompts of another in the prefix cache), after which every request
still running is waited for, so that windows do not overlap.  One JSON
line a rate on standard output: requests sent and finished, the
requests unfinished at the window's middle and at its end (a backlog
that grows from the one to the other means the rate is above what the
fleet sustains), the harness's end-to-end metrics of the window
(``harness/metrics.py``: delivered tokens a second, the tails of the
time to first token and of the gap between tokens), and each worker's
peak bytes in use and the programs it compiled in the window and its
drain.  The benchmark's own runs do not run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))


METRICS = ("tokens_per_s", "ttft_p50_ms", "ttft_p95_ms", "ttft_p99_ms",
           "itl_p50_ms", "itl_p99_ms")


def window(fe, cfg, mix, seed, seconds, chips, device) -> dict:
    from harness import metrics
    from harness.fleet import FleetWindow, OpenLoop

    loop = OpenLoop(fe, mix, seed, cfg["vocab_size"], seconds)
    t0 = loop.run()
    t1 = time.perf_counter()
    mid = t0 + seconds / 2

    def unfinished(at):
        return sum(1 for s in loop.streams if s.due <= at
                   and not (s.times and len(s.tokens) >= s.req.max_new
                            and s.times[-1] <= at))

    w = FleetWindow(t0=t0, t1=t1, streams=loop.streams, steps=[],
                    prefills=[], compiles=0)
    ctx = metrics.Context(cfg=cfg, window=w, setup_s=0.0, chips=chips,
                          device=device)
    out = {"rate_per_s": mix["rate_per_s"], "seed": seed,
           "sent": len(loop.streams),
           "finished": sum(1 for s in loop.streams
                           if len(s.tokens) >= s.req.max_new),
           "unfinished_mid": unfinished(mid), "unfinished_end":
           unfinished(t1), "generator_late_ms": 1e3 * loop.late}
    out.update((m, metrics.end_to_end_value(m, ctx)) for m in METRICS)
    t = time.perf_counter()
    fe.wait(timeout=600.0)
    out["drain_s"] = time.perf_counter() - t
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--chips", type=int, default=None)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--workers", type=int, default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH.parent / "src"))
    from harness import fleet
    from harness.traffic import warmup_rounds

    cfg = json.loads((BENCH / "configs" / f"{args.config}.json").read_text())
    mix = json.loads((BENCH / "traffic" / f"{args.traffic}.json")
                     .read_text())
    mix = {k: v for k, v in mix.items() if k != "kill"}
    if args.workers is not None:
        cfg["serving"] = dict(cfg["serving"], workers=args.workers)
    t = time.perf_counter()
    with fleet.launched(cfg, args.seed, args.chips) as fe:
        device = fleet.device_of(fe, args.chips)
        fleet.warm(fe, warmup_rounds(mix, args.seed, cfg["vocab_size"]))
        print(f"fleet up and warm in {time.perf_counter() - t:.1f} s",
              file=sys.stderr, flush=True)
        before = fleet.worker_extras(fe)
        for i, rate in enumerate(args.rates):
            res = window(fe, cfg, dict(mix, rate_per_s=rate),
                         args.seed + i, args.seconds, args.chips, device)
            after = fleet.worker_extras(fe)
            res.update(peak_bytes=[a["peak_bytes"] for a in after],
                       compiles=[a["compiles"] - b["compiles"]
                                 for a, b in zip(after, before)])
            before = after
            print(json.dumps(dict(res, device=device)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
