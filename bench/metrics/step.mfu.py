"""Model FLOPs over the chips' peak, from ``bench/flops.py``: every token
computed in the traced interval (the decode steps and prefill chunks the
harness recorded there; tokens served from the prefix cache are not
computed and do not count) over the interval's seconds, so that the
profiler's own start and stop stay out of it."""


def read(ctx):
    t = ctx.trace
    if t is None or t["window_s"] <= 0:
        return None
    peak = ctx.peaks["bf16_flops"] * ctx.chips
    return 100.0 * ctx.traced_flops() / (peak * t["window_s"])
