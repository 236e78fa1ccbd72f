"""1 - union of device-op intervals / traced window, from the profiler
trace of the traced loops (chipbench/trace_reduce.py)."""


def read(ctx):
    return ctx.trace["idle_pct"] if ctx.trace else None
