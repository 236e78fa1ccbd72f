"""Device seconds by `fedtpu.<phase>` scope of the round programs a
traced window ran: the benchmark's one join of a profiler trace with the
text of the programs that ran in it.

The program opens `jax.named_scope("fedtpu.<phase>")` scopes at the
phase boundaries of its round program. A scope is HLO metadata
(`op_name`), never an operation. A profiler trace names an executed op
by its HLO line (`%fusion.7 = f32[6]{0} fusion(...)`) and gives its
times, not its `op_name`, so the join is by INSTRUCTION NAME:
`op_phase_table` reads `{instruction: phase}` off the optimized HLO text
of a compiled round program (`Trainer.round_program_text(gid)`),
`inferred_phases` places what the compiler made without metadata, and
each op's SELF time (a `while` does not swallow its body) is summed by
phase.

* An op's phase is the LAST `fedtpu.<phase>` anywhere in its `op_name`:
  scopes nest (`history` inside `direction`), and transformed ops wrap
  the scope instead of following it (`transpose(jvp(fedtpu.grad_eval))/
  mul`), so the string is searched, not split on `/`. An op with none is
  `unattributed`. A fusion that merges ops of two scopes carries its
  root's metadata, so its whole time goes to the root's phase.
* Programs of one window reuse instruction names (`fusion.28` is in
  group 2's round program and in group 8's), so names are never merged
  across programs: every `XLA Modules` launch is matched to the host's
  `fused_round` step span it overlaps most, the longest launch of a span
  is that round's program, its ops are the `XLA Ops` events inside it, and
  they are joined with that round's text alone. The caller labels the
  spans in order.
* Nothing is reported (`refused` says why) where the join cannot be
  trusted: no round launch on a device plane; spans and labels that do
  not pair; a round without its program's text; a text with no
  `fedtpu.` scope (the persistent compile cache keys executables without
  their metadata, so an executable an older build cached carries that
  build's); or less than `MIN_COVERAGE` of a launch's self time found
  in its text by instruction name (the text is not the executable that
  ran).

Nothing of the program is imported: the yardstick does not move with
the program it measures.
"""

from __future__ import annotations

import collections
import json
import os
import re
import time
import traceback

from chipbench import spec
from chipbench.trace_reduce import (
    OP_LINE, _length, _self_times, _union, is_device_plane, op_name,
)

PREFIX = "fedtpu."
UNATTRIBUTED = "unattributed"
MODULE_LINE = "XLA Modules"
STEP_SPAN = "fused_round"  # the trainer's StepTraceAnnotation of a round program
MIN_COVERAGE = 0.97

_SCOPE = re.compile(re.escape(PREFIX) + r"([a-z_]+)")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\) -> .*\{$")
_REF = re.compile(r"%([\w.\-]+)")
_CALLED = re.compile(
    r"(?:body|condition|to_apply|calls|true_computation|false_computation)"
    r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}"
)


def phase_of(op_name: str) -> str:
    """The last `fedtpu.<phase>` in an `op_name`, else `unattributed`."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else UNATTRIBUTED


def instruction_name(event_name: str) -> str:
    """`%fusion.7 = f32[6]{0} fusion(...)` -> `fusion.7`: the TPU trace
    names an op by its whole HLO line, `trace_reduce` by its instruction
    and the head of its shape (`op_name`)."""
    return op_name(event_name).partition(" ")[0]


def op_phase_table(hlo_text: str) -> dict:
    """`{instruction name: phase}` over every instruction of an
    optimized HLO module's text (`compiled.as_text()`)."""
    table = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        op = _OP_NAME.search(line)
        table[m.group(1)] = phase_of(op.group(1)) if op else UNATTRIBUTED
    return table


def inferred_phases(hlo_text: str, table: dict) -> dict:
    """A phase for instructions the COMPILER made, which carry no
    metadata at all and so no scope of their own (layout and aliasing
    copies, the loops a scatter is expanded into): `{instruction: phase}`
    for those that can be placed, by two rules in this order.

    1. Its users: if the instructions that read it name exactly one
       phase, it works for that phase (a copy of the parameter matrix
       made for one `grad_eval` fusion is `grad_eval`'s cost).
    2. Its container: else the phase of the instruction that calls the
       computation it sits in (the body of a `while` that the scatter
       expander made inside `history` is `history`'s).

    An instruction whose `op_name` exists but names no scope is user
    code outside every scope: it stays `unattributed`, to be seen."""
    unplaced, users, home, caller = set(), collections.defaultdict(set), {}, {}
    computation = None
    for line in hlo_text.splitlines():
        c = _COMPUTATION.match(line)
        if c is not None:
            computation = c.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        name, rhs = m.group(1), line[m.end():]
        home[name] = computation
        if _OP_NAME.search(line) is None:
            unplaced.add(name)
        for one, many in _CALLED.findall(rhs):
            for called in _REF.findall(many) if many else [one]:
                caller[called] = name
        for ref in _REF.findall(rhs):
            users[ref].add(name)
    inferred: dict = {}

    def phase(name):
        return inferred.get(name) or table.get(name, UNATTRIBUTED)

    changed = True
    while changed:  # a copy of a copy resolves on the second pass
        changed = False
        for name in sorted(unplaced - set(inferred)):
            found = {phase(u) for u in users[name]} - {UNATTRIBUTED}
            if len(found) != 1:
                found = {phase(caller.get(home[name], ""))} - {UNATTRIBUTED}
            if len(found) == 1:
                inferred[name] = found.pop()
                changed = True
    return inferred


def round_launches(events: list, step_name: str = STEP_SPAN) -> list:
    """For each host `step_name` span, in time order, `{plane: (launch,
    ops)}`: on every device plane, the longest of the `XLA Modules`
    launches that overlap this span more than any other, and the `XLA
    Ops` events lying inside that launch. A span in which no device
    launched anything maps to `{}`."""
    spans = sorted(
        (e.start_ns, e.start_ns + e.dur_ns)
        for e in events
        if not is_device_plane(e.plane) and e.name == step_name
    )
    lines: dict = collections.defaultdict(lambda: {OP_LINE: [], MODULE_LINE: []})
    for e in events:
        if is_device_plane(e.plane) and e.line in (OP_LINE, MODULE_LINE):
            lines[e.plane][e.line].append(e)
    out: list = [{} for _ in spans]
    for plane in sorted(lines):
        held: dict = collections.defaultdict(list)  # span index -> its launches
        for m in lines[plane][MODULE_LINE]:
            a, b = m.start_ns, m.start_ns + m.dur_ns
            overlap = [min(b, hi) - max(a, lo) for lo, hi in spans]
            if overlap and max(overlap) > 0:
                held[overlap.index(max(overlap))].append(m)
        for i, inside in held.items():
            launch = max(inside, key=lambda m: m.dur_ns)
            a, b = launch.start_ns, launch.start_ns + launch.dur_ns
            ops = [e for e in lines[plane][OP_LINE]
                   if e.start_ns >= a and e.start_ns + e.dur_ns <= b]
            out[i][plane] = (launch, ops)
    return out


def _launch_phases(per_plane: dict, table: dict, inferred: dict, top: int) -> dict:
    """One round program's launch, by phase: self seconds averaged over
    the device planes that ran it."""

    def phase(name):
        own = table.get(name, UNATTRIBUTED)
        return own if own != UNATTRIBUTED else inferred.get(name, UNATTRIBUTED)

    by_op: collections.Counter = collections.Counter()
    busy_ns = launch_ns = 0
    for launch, ops in per_plane.values():
        launch_ns += launch.dur_ns
        busy_ns += _length(_union([[e.start_ns, e.start_ns + e.dur_ns] for e in ops]))
        for name, ns in _self_times(ops).items():
            by_op[instruction_name(name)] += ns
    by_phase: collections.Counter = collections.Counter()
    for name, ns in by_op.items():
        by_phase[phase(name)] += ns
    total_ns = sum(by_op.values())
    per = len(per_plane) * 1e9

    def rows(names):
        return [[name, phase(name), by_op[name] / per, name in inferred] for name in names]

    ranked = [name for name, _ in by_op.most_common()]
    return {
        "devices": len(per_plane),
        "launch": next(iter(per_plane.values()))[0].name,
        "launch_s": launch_ns / per,
        "busy_s": busy_ns / per,
        # a phase absent from the program reads 0, as it costs nothing
        "seconds": {p: ns / per for p, ns in sorted(by_phase.items())},
        "coverage": (
            sum(ns for name, ns in by_op.items() if name in table) / total_ns
            if total_ns else 0.0
        ),
        "inferred_s": sum(ns for name, ns in by_op.items() if name in inferred) / per,
        "top": rows(ranked[:top]),
        "unattributed_top": rows(
            [name for name in ranked if phase(name) == UNATTRIBUTED][: top // 2]
        ),
    }


def join(events: list, text_of, labels: list, step_name: str = STEP_SPAN,
         top: int = 20) -> dict:
    """Device seconds by phase of each round program launched in a
    window. `labels[i]` names the program of the i-th `step_name` span,
    `text_of(label)` returns its optimized HLO text (or None), asked once
    per label and only when the window has launches to join.

    Returns `{"rounds": [...], "mean": {phase: s}, "refused": None}`:
    per round its label, `devices`, `launch` (the module's name),
    `launch_s`, `busy_s` (the union of its ops' intervals, computed apart
    from the self times, so the phases sum to it), `seconds` by phase
    with `unattributed` among them, `coverage`, `inferred_s`, the `top`
    instructions `[name, phase, seconds, inferred]` and the costliest
    `unattributed_top`; `mean` is `seconds` averaged over the rounds.
    Where the join cannot be trusted, `{"rounds": [], "mean": {},
    "refused": <why>}` (module docstring)."""

    def refuse(why):
        return {"rounds": [], "mean": {}, "refused": why}

    launches = round_launches(events, step_name)
    if not any(launches):
        return refuse(f"no {step_name!r} span holds a launch on a device plane")
    if len(launches) != len(labels) or not all(launches):
        return refuse(f"{len(launches)} {step_name!r} span(s), "
                      f"{sum(map(bool, launches))} with a launch, {len(labels)} label(s)")
    tables: dict = {}
    rounds = []
    for label, per_plane in zip(labels, launches):
        if label not in tables:
            text = text_of(label)
            if text is None:
                return refuse(f"no program text for round {label!r}")
            table = op_phase_table(text)
            if not any(p != UNATTRIBUTED for p in table.values()):
                return refuse(
                    f"the program text of round {label!r} carries no {PREFIX!r} "
                    f"scope in its {len(table)} instructions: an executable "
                    "cached by a build without the scopes keeps that build's "
                    "metadata; run against an empty compile cache"
                )
            tables[label] = (table, inferred_phases(text, table))
        r = _launch_phases(per_plane, *tables[label], top)
        if r["coverage"] < MIN_COVERAGE:
            return refuse(
                f"{100 * r['coverage']:.2f}% of round {label!r}'s device self "
                f"time is found in its program text (at least "
                f"{100 * MIN_COVERAGE:.0f}% needed): not the executable that ran"
            )
        rounds.append(dict(r, label=label))
    phases = sorted({p for r in rounds for p in r["seconds"]})
    mean = {
        p: sum(r["seconds"].get(p, 0.0) for r in rounds) / len(rounds) for p in phases
    }
    return {"rounds": rounds, "mean": mean, "refused": None}


def for_run(ctx) -> dict | None:
    """The join of a benchmark run's traced loop (`chipbench/run.py
    RunContext`), made once and kept on the context for every reader of
    the run; None where it cannot be made or is refused.

    The traced loop is the one after the window; its rounds are
    labelled by the trainer's own `fused_round` records of that loop, as
    `run.py` labels the trace's rounds, and each group's text comes from
    the trainer's `round_program_text` (a program without it reads
    nothing). What the join found, and the seconds it and the text
    lowerings took, go to `phase_join.json` beside the run's other
    details."""
    if not hasattr(ctx, "_phase_join"):
        ctx._phase_join = _join_run(ctx)
    return ctx._phase_join


def _join_run(ctx) -> dict | None:
    text_of = getattr(ctx.trainer, "round_program_text", None)
    if text_of is None or not ctx.events or not ctx.window_rounds:
        return None
    traced = max(r["nloop"] for r in ctx.window_rounds) + 1
    labels = [
        r["group"] for r in ctx.trainer.recorder.series.get("step_time", [])
        if r["value"]["phase"] == STEP_SPAN and r.get("nloop") == traced
    ]
    text_s = 0.0

    def timed_text(gid):
        nonlocal text_s
        t0 = time.perf_counter()
        try:
            return text_of(gid)
        except Exception:  # a reader reports nothing rather than end the run
            traceback.print_exc()
            return None
        finally:
            text_s += time.perf_counter() - t0

    t0 = time.perf_counter()
    got = join(ctx.events, timed_text, labels)
    detail = dict(got, nloop=traced, labels=labels, text_s=text_s,
                  join_s=time.perf_counter() - t0 - text_s)
    out = os.path.join(spec.REPO, "chiprun_out", "chipbench", ctx.cell.name)
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "phase_join.json"), "w") as f:
        json.dump(detail, f, indent=1)
    print(f"phase_join: loop {traced} rounds {labels} text_s={text_s:.3f} "
          f"join_s={detail['join_s']:.3f} refused={got['refused']} "
          f"mean_ms={ {p: round(1e3 * s, 3) for p, s in got['mean'].items()} }",
          flush=True)
    return None if got["refused"] else got


def device_ms(ctx, phase: str) -> float | None:
    """Milliseconds of device self time in `phase` (or `unattributed`)
    a round program of the traced loop spends, averaged over its rounds."""
    got = for_run(ctx)
    return None if got is None else 1e3 * got["mean"].get(phase, 0.0)
