"""L-BFGS inner iterations (`n_iter` of the `solver_work` series) per
client step of the window: at most `lbfgs_max_iter`; lower means steps
that end at the entry check or stop early."""


def read(ctx):
    recs = ctx.series.get("solver_work", [])
    steps = ctx.window_samples / ctx.cfg.batch
    if not recs or not steps:
        return None
    return sum(sum(r["value"]["n_iter"]) for r in recs) / steps
