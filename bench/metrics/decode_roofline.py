"""Share of its roofline that the T=1 decode step reaches: the least
time the chip could take for the traced steps (the larger of their bytes
over peak bandwidth and their operations over peak FLOP/s, from
the family's counts, ``bench/flops.py`` for the dense family, at the
configuration's logical sizes) over the device time of those steps.  The
decode step is bound by bandwidth."""

import sys


def read(ctx):
    t = ctx.trace
    steps = [pos for pos in ctx.traced_steps() if pos]
    if t is None or not t["programs"]["decode"]["calls"] or not steps:
        return None
    p = t["programs"]["decode"]
    # steps with no active lane send nothing to the device
    n = min(len(steps), p["calls"])
    n_steps = len(steps)
    count = ctx.flops
    need_bytes = sum(count.decode_bytes(ctx.cfg, s) for s in steps) / n_steps
    need_flops = sum(count.decode_flops(ctx.cfg, s) for s in steps) / n_steps
    t_bytes = need_bytes / ctx.peaks["hbm_bytes_per_s"]
    t_flops = need_flops / ctx.peaks["bf16_flops"]
    bound = "bandwidth" if t_bytes >= t_flops else "compute"
    if len(steps) != p["calls"]:
        print(f"decode_roofline: {len(steps)} steps against {p['calls']} "
              f"decode runs in the trace", file=sys.stderr)
    print(f"decode_roofline: bound by {bound}; {need_bytes:.0f} bytes and "
          f"{need_flops:.0f} operations a step over {n} steps",
          file=sys.stderr)
    return 100.0 * max(t_bytes, t_flops) / (p["seconds"] / p["calls"])
