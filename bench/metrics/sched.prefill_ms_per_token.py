"""Device time of the T=8 prefill chunks in the trace (the
step program's runs that the harness called at T=8) per prompt token
prefilled in the traced interval."""


def read(ctx):
    t = ctx.trace
    tokens = ctx.traced_prefill_tokens()
    if t is None or not t["programs"]["prefill"]["calls"] or not tokens:
        return None
    return 1e3 * t["programs"]["prefill"]["seconds"] / tokens
