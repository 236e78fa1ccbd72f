"""Forward-only Armijo probes (`ls_evals` of the `solver_work` series)
per client step of the window."""


def read(ctx):
    recs = ctx.series.get("solver_work", [])
    steps = ctx.window_samples / ctx.cfg.batch
    if not recs or not steps:
        return None
    return sum(sum(r["value"]["ls_evals"]) for r in recs) / steps
