"""Client training samples consumed in the window's whole loops over the
wall of those loops (host clock; each round closed by the trainer's own
device->host fetch). Everything a schedule pays is inside the wall."""


def read(ctx):
    if not ctx.window_rounds or ctx.window_wall_s <= 0:
        return None
    return ctx.window_samples / ctx.window_wall_s
