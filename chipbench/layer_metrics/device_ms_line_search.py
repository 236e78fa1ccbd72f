"""Device self time inside the scope `fedtpu.line_search`, in ms a round
program of the traced loop, averaged over its rounds and device planes
(chipbench/phase_join.py). The scope is opened at
`optim/lbfgs.py lbfgs_step`, around the Armijo call of
`optim/linesearch.py`: the search and its forward-only probes."""

from chipbench import phase_join


def read(ctx):
    return phase_join.device_ms(ctx, "line_search")
