"""Device self time of the round programs' operations in no
`fedtpu.<phase>` scope, which neither their own metadata nor their users
or container place (chipbench/phase_join.py), in ms a round program of
the traced loop, averaged over its rounds and device planes. Work that a
refactor moves out of every scope shows here."""

from chipbench import phase_join


def read(ctx):
    return phase_join.device_ms(ctx, phase_join.UNATTRIBUTED)
