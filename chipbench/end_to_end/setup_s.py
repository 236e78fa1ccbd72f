"""Process start to the start of the window: imports, data from the seed,
`Trainer(cfg)`, compilation or cache load of the cell's round programs,
the warm-up loop(s) with their checks."""


def read(ctx):
    return ctx.setup_s
