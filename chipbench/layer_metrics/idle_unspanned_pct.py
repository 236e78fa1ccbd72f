"""Share of the device's idle time inside the traced window during which
no host span of the program (`fedtpu:<phase>`, the trainer's
`MetricsRecorder.phase` annotations) was open: the part of the chip's
idle time that the program's own spans cannot explain. Over every
device plane of the trace (chipbench/trace_reduce.py's window, ops and
idle intervals); nothing where the trace has no device plane."""

from chipbench.trace_reduce import (
    OP_LINE, WINDOW_SPAN, _clip, _length, _union, is_device_plane,
)

SPAN_PREFIX = "fedtpu:"


def read(ctx):
    if not ctx.events:
        return None
    windows = [e for e in ctx.events if e.name == WINDOW_SPAN
               and not is_device_plane(e.plane)]
    if not windows:
        return None
    w = max(windows, key=lambda e: e.dur_ns)
    lo, hi = w.start_ns, w.start_ns + w.dur_ns
    ops: dict = {}
    spans = []
    for e in ctx.events:
        if is_device_plane(e.plane):
            if e.line == OP_LINE:
                ops.setdefault(e.plane, []).append([e.start_ns, e.start_ns + e.dur_ns])
        elif e.name.startswith(SPAN_PREFIX):
            spans.append([e.start_ns, e.start_ns + e.dur_ns])
    if not ops:
        return None
    spanned = _clip(_union(spans), lo, hi)
    idle_ns = unspanned_ns = 0
    for intervals in ops.values():
        busy = _clip(_union(intervals), lo, hi)
        edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
        idle = [[s, e] for s, e in zip(edges[::2], edges[1::2]) if e > s]
        idle_ns += _length(idle)
        # idle minus spanned = (idle | spanned) minus spanned
        unspanned_ns += _length(_union(idle + spanned)) - _length(spanned)
    return 100.0 * unspanned_ns / idle_ns if idle_ns else 0.0
