"""The slowest `fused_round` phase of the window."""


def read(ctx):
    if not ctx.window_rounds:
        return None
    return 1e3 * max(r["fused_s"] for r in ctx.window_rounds)
