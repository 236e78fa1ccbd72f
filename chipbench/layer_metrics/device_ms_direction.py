"""Device self time inside the scope `fedtpu.direction`, in ms a round
program of the traced loop, averaged over its rounds and device planes
(chipbench/phase_join.py). The scope is opened at
`optim/lbfgs.py lbfgs_step`: the L-BFGS direction's contractions
over both histories."""

from chipbench import phase_join


def read(ctx):
    return phase_join.device_ms(ctx, "direction")
