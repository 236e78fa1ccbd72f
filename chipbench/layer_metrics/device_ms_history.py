"""Device self time inside the scope `fedtpu.history`, in ms a round
program of the traced loop, averaged over its rounds and device planes
(chipbench/phase_join.py). The scope is opened at
`optim/history.py ring_push`: the one-row write of both history
rings."""

from chipbench import phase_join


def read(ctx):
    return phase_join.device_ms(ctx, "history")
