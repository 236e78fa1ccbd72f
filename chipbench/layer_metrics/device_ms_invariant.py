"""Device self time inside the scope `fedtpu.invariant`, in ms a round
program of the traced loop, averaged over its rounds and device planes
(chipbench/phase_join.py). The scope is opened at
`engine/steps.py _client_train_step`, around `partition/stage.py
stage_invariant`: what of the client step's objective no probe can
change, once a lockstep step."""

from chipbench import phase_join


def read(ctx):
    return phase_join.device_ms(ctx, "invariant")
