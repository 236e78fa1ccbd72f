"""Device self time inside the scope `fedtpu.eval`, in ms a round
program of the traced loop, averaged over its rounds and device planes
(chipbench/phase_join.py). The scope is opened at
`engine/steps.py _client_eval_fn`: the folded test sweep."""

from chipbench import phase_join


def read(ctx):
    return phase_join.device_ms(ctx, "eval")
