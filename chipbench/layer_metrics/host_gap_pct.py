"""Share of the window's wall outside the trainer's `fused_round` phase:
round init, host bookkeeping, telemetry, everything between rounds."""


def read(ctx):
    if not ctx.window_rounds:
        return None
    inside = sum(r["fused_s"] for r in ctx.window_rounds)
    return 100.0 * (1.0 - inside / ctx.window_wall_s)
