"""Device self time inside the scope `fedtpu.carry_mask`, in ms a round
program of the traced loop, averaged over its rounds and device planes
(chipbench/phase_join.py). The scope is opened at
`optim/lbfgs.py lbfgs_step`, around the solver's `while_loop`: the
freeze select over its carry and the body's unscoped vector work."""

from chipbench import phase_join


def read(ctx):
    return phase_join.device_ms(ctx, "carry_mask")
