"""Curvature pairs the L-BFGS history ring took per client step
(`hist_pushes` of the `solver_work` series): each a new row of the ring
and of the compact direction's carried `S Yᵀ`, `Y Yᵀ`. At most
`lbfgs_iters_per_step` − 1 in batch mode, whose first iteration of a
step pushes nothing; lower means pairs refused by the curvature test or
fewer iterations. The program before the counter logs no `hist_pushes`:
then nothing is read."""


def read(ctx):
    recs = ctx.series.get("solver_work", [])
    steps = ctx.window_samples / ctx.cfg.batch
    if not recs or not steps or any("hist_pushes" not in r["value"] for r in recs):
        return None
    return sum(sum(r["value"]["hist_pushes"]) for r in recs) / steps
