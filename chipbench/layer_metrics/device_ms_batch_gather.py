"""Device self time inside the scope `fedtpu.batch_gather`, in ms a round
program of the traced loop, averaged over its rounds and device planes
(chipbench/phase_join.py). The scope is opened at
`engine/steps.py _gather_batch`: the `take_along_axis` pair of a
lockstep step."""

from chipbench import phase_join


def read(ctx):
    return phase_join.device_ms(ctx, "batch_gather")
