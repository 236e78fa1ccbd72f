"""Compile requests during set-up that the persistent cache did not
serve (`jax.monitoring`, chipbench/compile_log.py)."""


def read(ctx):
    return ctx.compile_setup["compiled"]
