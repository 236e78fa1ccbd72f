"""From a profiler trace to the device's busy and idle time.

The reduction works on plain `Event` tuples so it can be checked on a
hand-built trace (tests/chipbench); `load_xplane` turns the profiler's
`.xplane.pb` into them with nothing but jax.

What the TPU trace looks like (looked at by hand, PR 24): each chip is a
plane `/device:TPU:<n>`; its line `XLA Ops` holds one event per executed
HLO op, containers (`while`, `conditional`, `call`) enclosing the ops of
their bodies; `XLA Modules` holds one event per program launch and
`Steps` the profiler's own step grouping. Host threads are lines of the
plane `/host:CPU`; `jax.profiler.TraceAnnotation` and
`StepTraceAnnotation` appear there under their own names, on the same
clock as the device lines.

* busy: the union of the op intervals of a device plane, clipped to the
  window; averaged over the device planes that ran anything.
* window: the host span named `WINDOW_SPAN` that the harness opens
  around the traced loops (else the extent of the device ops).
* a gap is a maximal interval of the window in which no op ran on that
  device; it is split at the boundaries of the host's `step_name` spans
  (the trainer's `StepTraceAnnotation("fused_round")`) and each piece is
  labelled `inside_round` or `between_rounds`.
* an op's time is its SELF time: its duration minus what the ops nested
  in it cover, so a `while` does not swallow its body.
"""

from __future__ import annotations

import collections
import glob
import os

Event = collections.namedtuple("Event", "plane line name start_ns dur_ns")

WINDOW_SPAN = "chipbench_window"
OP_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    """The newest `.xplane.pb` under a `jax.profiler.trace` directory."""
    found = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def load_xplane(path: str) -> list:
    """Every event of the trace as an `Event`."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(
                    Event(plane.name, line.name, ev.name,
                          int(ev.start_ns), int(ev.duration_ns))
                )
    return out


def op_name(hlo: str) -> str:
    """`%fusion.7 = f32[6,64]{1,0} fusion(...)` -> `fusion.7 f32[6,64]{1,0} fusion(...`:
    the TPU trace names an op by its whole HLO line; keep its name and
    the head of its result shape."""
    lhs, sep, rhs = hlo.partition(" = ")
    return f"{lhs.lstrip('%')} {rhs[:48]}".rstrip() if sep else hlo


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name.upper()


def summarize(events: list, top: int = 8) -> dict:
    """Planes, lines, event counts and the commonest names: what one
    reads before trusting `reduce` on a new runtime."""
    acc: dict = {}
    for e in events:
        d = acc.setdefault(
            (e.plane, e.line),
            {"events": 0, "dur_s": 0.0, "names": collections.Counter()},
        )
        d["events"] += 1
        d["dur_s"] += e.dur_ns / 1e9
        d["names"][e.name] += 1
    return {
        f"{plane} | {line}": {
            "events": d["events"],
            "dur_s": d["dur_s"],
            "names": d["names"].most_common(top),
        }
        for (plane, line), d in sorted(acc.items())
    }


def _union(intervals: list) -> list:
    """Sorted, merged `[start, end)` intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals: list, lo: int, hi: int) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi]


def _length(intervals: list) -> int:
    return sum(e - s for s, e in intervals)


def _self_times(line_events: list) -> dict:
    """Self time in ns by op name over one line's (possibly nested) events."""
    acc: collections.Counter = collections.Counter()
    stack: list = []  # [end_ns, name, self_ns]

    def close(upto: int) -> None:
        while stack and stack[-1][0] <= upto:
            _, name, self_ns = stack.pop()
            acc[op_name(name)] += max(self_ns, 0)

    for e in sorted(line_events, key=lambda e: (e.start_ns, -e.dur_ns)):
        close(e.start_ns)
        if stack:
            stack[-1][2] -= e.dur_ns
        stack.append([e.start_ns + e.dur_ns, e.name, e.dur_ns])
    close(1 << 62)
    return acc


def reduce(
    events: list,
    step_name: str = "fused_round",
    step_labels: list | None = None,
    top: int = 10,
) -> dict:
    """Busy, idle, labelled gaps and top ops of a traced window.

    `step_labels[i]` names the i-th `step_name` span of the window (the
    harness passes the round's group); absent, spans are numbered.
    """
    dev: dict = collections.defaultdict(list)
    host = []
    for e in events:
        if is_device_plane(e.plane):
            if e.line == OP_LINE:
                dev[e.plane].append(e)
        else:
            host.append(e)
    windows = [e for e in host if e.name == WINDOW_SPAN]
    if windows:
        w = max(windows, key=lambda e: e.dur_ns)
        lo, hi = w.start_ns, w.start_ns + w.dur_ns
    elif dev:
        lo = min(e.start_ns for es in dev.values() for e in es)
        hi = max(e.start_ns + e.dur_ns for es in dev.values() for e in es)
    else:
        lo = hi = 0
    steps = _clip(
        sorted([e.start_ns, e.start_ns + e.dur_ns]
               for e in host if e.name == step_name),
        lo, hi,
    )
    labels = [
        str(step_labels[i]) if step_labels and i < len(step_labels) else str(i)
        for i in range(len(steps))
    ]
    step_ns = _length(steps)

    busy_ns, busy_in_steps_ns = [], []
    gaps: list = []  # (seconds, label)
    ops: collections.Counter = collections.Counter()
    for plane in sorted(dev):
        es = dev[plane]
        busy = _clip(_union([[e.start_ns, e.start_ns + e.dur_ns] for e in es]), lo, hi)
        busy_ns.append(_length(busy))
        in_steps = 0
        for s, e in steps:
            in_steps += _length(_clip(busy, s, e))
        busy_in_steps_ns.append(in_steps)
        # idle intervals of this device: the window minus its busy union
        edge = lo
        idle = []
        for s, e in busy:
            if s > edge:
                idle.append([edge, s])
            edge = max(edge, e)
        if hi > edge:
            idle.append([edge, hi])
        for s, e in idle:
            pos, following = s, "end"
            for i, (a, b) in enumerate(steps):
                if b <= pos:
                    continue
                if a >= e:
                    following = labels[i]
                    break
                if a > pos:
                    gaps.append(((min(a, e) - pos) / 1e9, f"between_rounds:{labels[i]}"))
                    pos = min(a, e)
                if pos < min(b, e):
                    gaps.append(((min(b, e) - pos) / 1e9, f"inside_round:{labels[i]}"))
                    pos = min(b, e)
            if pos < e:
                gaps.append(((e - pos) / 1e9, f"between_rounds:{following}"))
        for name, ns in _self_times(
            [x for x in es if x.start_ns < hi and x.start_ns + x.dur_ns > lo]
        ).items():
            ops[name] += ns
    n = len(busy_ns)
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy_ns) / n / 1e9 if n else 0.0
    by_kind: collections.Counter = collections.Counter()
    for sec, label in gaps:
        by_kind[label.split(":")[0] + ".total"] += sec / max(n, 1)
    longest = sorted(g for g in gaps if g[0] >= 1e-6)[::-1][: max(top - len(by_kind), 0)]
    return {
        "devices": n,
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s) if window_s > 0 and n else None,
        "round_busy_pct": (
            100.0 * sum(busy_in_steps_ns) / n / step_ns if n and step_ns else None
        ),
        "steps": len(steps),
        "device_ops": [
            [name, ns / max(n, 1) / 1e9] for name, ns in ops.most_common(top)
        ],
        "idle_gaps": [[k, v] for k, v in by_kind.most_common()]
        + [[label, sec] for sec, label in longest],
    }
