"""Mean device time of one T=1 decode step over every slot, from the
trace (the step program's runs that the harness called at T=1)."""


def read(ctx):
    t = ctx.trace
    if t is None or not t["programs"]["decode"]["calls"]:
        return None
    p = t["programs"]["decode"]
    return 1e3 * p["seconds"] / p["calls"]
