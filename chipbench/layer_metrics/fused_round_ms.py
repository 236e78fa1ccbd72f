"""Mean `fused_round` phase wall per round of the window."""


def read(ctx):
    if not ctx.window_rounds:
        return None
    return 1e3 * sum(r["fused_s"] for r in ctx.window_rounds) / len(ctx.window_rounds)
