"""Device busy time inside the `fused_round` step annotations over their
duration, from the profiler trace of the traced loops."""


def read(ctx):
    return ctx.trace["round_busy_pct"] if ctx.trace else None
