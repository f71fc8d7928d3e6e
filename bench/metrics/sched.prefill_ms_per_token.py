"""Device time of the admission prefill chunks in the trace (the step
program's runs that the harness called at one chunk of
``PREFILL_CHUNK`` = 128 rows, B=1) per prompt token prefilled in the
traced interval."""


def read(ctx):
    t = ctx.trace
    tokens = ctx.traced_prefill_tokens()
    if t is None or not t["programs"]["prefill"]["calls"] or not tokens:
        return None
    return 1e3 * t["programs"]["prefill"]["seconds"] / tokens
