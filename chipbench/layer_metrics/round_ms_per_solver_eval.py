"""Wall of the window's `fused_round` phases per unit of solver work:
milliseconds per model evaluation of one lockstep client (`func_evals` +
`ls_evals` of the `solver_work` series, averaged over the K clients). A
faster kernel moves it; a search that takes fewer probes moves
`solver_evals_per_step` and leaves this."""


def read(ctx):
    recs = ctx.series.get("solver_work", [])
    evals = sum(
        sum(r["value"]["func_evals"]) + sum(r["value"]["ls_evals"]) for r in recs
    )
    if not evals or not ctx.window_rounds:
        return None
    wall_ms = 1e3 * sum(r["fused_s"] for r in ctx.window_rounds)
    return wall_ms / (evals / ctx.cfg.n_clients)
