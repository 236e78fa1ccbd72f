"""Model evaluations one client step costs: gradient evaluations
(`func_evals`) plus forward-only Armijo probes (`ls_evals`) of the
window's rounds (`solver_work` series) over its K x lockstep steps. An
amount of work, not a quality: fewer means the search worked less."""


def read(ctx):
    recs = ctx.series.get("solver_work", [])
    steps = ctx.window_samples / ctx.cfg.batch
    if not recs or not steps:
        return None
    evals = sum(
        sum(r["value"]["func_evals"]) + sum(r["value"]["ls_evals"]) for r in recs
    )
    return evals / steps
