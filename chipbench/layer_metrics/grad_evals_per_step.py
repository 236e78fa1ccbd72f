"""Gradient evaluations the device ran per client step (`grad_evals` of
the `solver_work` series): the entry evaluation and every re-evaluation
the client's block ran, whether or not the client kept the result. At
least `func_evals` per step; what it reads above that is work thrown
away. The program before the counter logs no `grad_evals`: then
nothing is read."""


def read(ctx):
    recs = ctx.series.get("solver_work", [])
    steps = ctx.window_samples / ctx.cfg.batch
    if not recs or not steps or any("grad_evals" not in r["value"] for r in recs):
        return None
    return sum(sum(r["value"]["grad_evals"]) for r in recs) / steps
