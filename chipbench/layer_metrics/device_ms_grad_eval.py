"""Device self time inside the scope `fedtpu.grad_eval`, in ms a round
program of the traced loop, averaged over its rounds and device planes
(chipbench/phase_join.py). The scope is opened at
`optim/lbfgs.py lbfgs_step`: the `value_and_grad` of the entry
evaluation and of the re-evaluation."""

from chipbench import phase_join


def read(ctx):
    return phase_join.device_ms(ctx, "grad_eval")
