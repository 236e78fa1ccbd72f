"""Device self time inside the scope `fedtpu.round_tail`, in ms a round
program of the traced loop, averaged over its rounds and device planes
(chipbench/phase_join.py). The scope is opened at
`engine/steps.py build_round_fn`: `param_ok` and the scheduler's
drift."""

from chipbench import phase_join


def read(ctx):
    return phase_join.device_ms(ctx, "round_tail")
