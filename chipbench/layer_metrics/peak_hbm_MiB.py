"""Largest `peak_bytes_in_use` over the mesh devices after the window."""


def read(ctx):
    peak = max(ctx.memory_peak_bytes, default=0)
    return peak / 2**20 if peak else None
