"""Share of the traced interval in which no operation ran on the device:
1 - busy / interval, busy being the union of the device's operation
intervals, averaged over the chips."""


def read(ctx):
    t = ctx.trace
    if t is None or t["window_s"] <= 0 or not t["chips"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
