"""Phase scopes inside the round program, and device seconds by phase.

The round program (engine/steps.py `build_round_fn`) is ONE XLA program
per partition round; a profiler trace names what ran in it only as the
compiler does (`%maximum_select_fusion.3`). The phases of `PHASES` are
opened as `jax.named_scope("fedtpu.<phase>")` at the phase boundaries of
the program's source; a scope is HLO metadata (`op_name`), never an
operation, so the programs compute what they computed.

jax's `ProfileData` exposes an event's name (the whole HLO line) and its
times, not its `op_name`. The join that needs nothing but jax is by HLO
INSTRUCTION NAME: `op_phase_table` reads `{instruction: phase}` off the
optimized HLO text of the compiled round program, and
`device_seconds_by_phase` sums the trace's self times through it. Its
one known blur: a fusion that merges ops of two scopes carries its root
instruction's metadata, so its whole time goes to the root's phase.
Instructions the compiler makes itself carry no metadata; those are
placed by `inferred_phases` (their users, else their container), and
the seconds placed that way are reported apart.

An op's phase is the LAST `fedtpu.<phase>` anywhere in its `op_name`:
scopes nest (`history` inside `direction`), and transformed ops wrap the
scope instead of following it (`transpose(jvp(fedtpu.grad_eval))/mul`,
`vmap(fedtpu.grad_eval)/jvp()/tanh`), so the string is searched, not
split on `/`.

A warm compile cache hides new scopes: the cache key ignores metadata
(`jax_compilation_cache_include_metadata_in_key` is False), so an
executable cached by a build without the scopes is loaded as it was
stored. A table without any `fedtpu.` scope is therefore refused
(`StaleMetadataError`), not reported as 100% unattributed.

Module-level imports stay jax-free (the `report`/`watch` verbs import
`obs/` on hosts without a backend).
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, Iterable, List

PREFIX = "fedtpu."
UNATTRIBUTED = "unattributed"

# the closed list of phase scopes; each is opened at ONE site per shared
# body, `round_tail` at its two (inside the round's scan and after it)
# (docs/OBSERVABILITY.md §Phase scopes and --profile-dir)
PHASES = (
    "batch_gather",  # step_all's take_along_axis pair (engine/steps.py)
    "invariant",  # what of the client step's objective no probe can
    # change, once a lockstep step (partition/stage.py): the forward
    # below the first active layer, the frozen leaves' relayouts
    "grad_eval",  # lbfgs_step's value_and_grad sites: entry and reeval
    "direction",  # where(first_ever, -g, direction_fn(...)): the contractions
    "history",  # ring_push: a [R, 128] slab into lanes, read back and
    # written in place, per history and client (optim/history.py)
    "line_search",  # the Armijo call; its probes are forward-only
    "carry_mask",  # the L-BFGS loop's freeze select over its carry (the
    # histories are not in it) and the body's few unscoped vector ops
    "exchange",  # the consensus body (_consensus_local)
    "eval",  # the client_eval sweep (_client_eval_fn)
    "round_tail",  # param_ok, drift
)

Event = collections.namedtuple("Event", "plane line name start_ns dur_ns")

OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"

_SCOPE = re.compile(re.escape(PREFIX) + r"([a-z_]+)")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\) -> .*\{$")
_REF = re.compile(r"%([\w.\-]+)")
_CALLED = re.compile(
    r"(?:body|condition|to_apply|calls|true_computation|false_computation)"
    r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}"
)


class StaleMetadataError(RuntimeError):
    """The compiled round program carries no `fedtpu.` scope."""


def scope(name: str):
    """`jax.named_scope(name)` for `name` = `"fedtpu.<phase>"`, a phase
    of `PHASES`. Sites spell the whole name, so `grep -rn "fedtpu\\."`
    finds where each scope is opened."""
    if not name.startswith(PREFIX) or name[len(PREFIX):] not in PHASES:
        raise ValueError(f"unknown phase scope {name!r}; PHASES = {PHASES}")
    import jax

    return jax.named_scope(name)


def scoped(name: str, fn):
    """`fn` with every op it traces under the scope `name`."""

    def wrapper(*args):
        with scope(name):
            return fn(*args)

    return wrapper


def phase_of(op_name: str) -> str:
    """The last `fedtpu.<phase>` in an `op_name`, else `unattributed`."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else UNATTRIBUTED


def instruction_name(event_name: str) -> str:
    """`%fusion.7 = f32[6]{0} fusion(...)` -> `fusion.7`: the TPU trace
    names an op by its whole HLO line."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def op_phase_table(hlo_text: str) -> Dict[str, str]:
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


def inferred_phases(hlo_text: str, table: Dict[str, str]) -> Dict[str, str]:
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
    inferred: Dict[str, str] = {}

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


def find_xplane(trace_dir: str) -> str:
    """The newest `.xplane.pb` under a `jax.profiler.trace` directory."""
    found = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def load_device_events(xplane_path: str) -> List[Event]:
    """The `XLA Ops` and `XLA Modules` events of every device plane."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if not _is_device_plane(plane.name):
            continue
        for line in plane.lines:
            if line.name not in (OP_LINE, MODULE_LINE):
                continue
            for ev in line.events:
                out.append(
                    Event(plane.name, line.name, ev.name,
                          int(ev.start_ns), int(ev.duration_ns))
                )
    return out


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name.upper()


def _self_times(ops: List[Event]) -> collections.Counter:
    """Self time in ns by instruction over one line's nested events: a
    `while` keeps only what the ops of its body do not cover."""
    acc: collections.Counter = collections.Counter()
    stack: list = []  # [end_ns, instruction, self_ns]

    def close(upto: int) -> None:
        while stack and stack[-1][0] <= upto:
            _, name, self_ns = stack.pop()
            acc[name] += max(self_ns, 0)

    for e in sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns)):
        close(e.start_ns)
        if stack:
            stack[-1][2] -= e.dur_ns
        stack.append([e.start_ns + e.dur_ns, instruction_name(e.name), e.dur_ns])
    close(1 << 62)
    return acc


def _union_ns(ops: List[Event]) -> int:
    total, edge = 0, -(1 << 62)
    for e in sorted(ops, key=lambda e: e.start_ns):
        end = e.start_ns + e.dur_ns
        if end > edge:
            total += end - max(e.start_ns, edge)
            edge = end
    return total


def device_seconds_by_phase(
    events: Iterable[Event],
    table: Dict[str, str],
    inferred: Dict[str, str] | None = None,
    top: int = 20,
) -> dict:
    """Device seconds of one profiler window, by phase.

    On every device plane that ran anything: the `XLA Ops` events lying
    inside the window's LONGEST `XLA Modules` launch (the round program;
    round init and helper launches are left out), their self time summed
    by `table[instruction]`, else by `inferred[instruction]`
    (`inferred_phases`; the seconds placed that way are also reported as
    `inferred_s`), else under `unattributed`. Averaged over those
    planes. `busy_s` is the union of the same events' intervals,
    computed apart from the self times, so `sum(phases) + unattributed
    == busy_s` is a check. `top` rows are `[instruction, phase, seconds,
    inferred]`; `unattributed_top` lists what is left unexplained.
    """
    if not any(p != UNATTRIBUTED for p in table.values()):
        raise StaleMetadataError(
            "the compiled round program carries no 'fedtpu.' scope in its "
            f"{len(table)} instructions. The persistent compile cache keys "
            "executables without their metadata "
            "(jax_compilation_cache_include_metadata_in_key=False), so an "
            "executable cached by a build that had no phase scopes is "
            "loaded as it was stored. Point JAX_COMPILATION_CACHE_DIR at "
            "an empty directory and run again; no phase table was written"
        )
    inferred = inferred or {}

    def phase(name):
        own = table.get(name, UNATTRIBUTED)
        return own if own != UNATTRIBUTED else inferred.get(name, UNATTRIBUTED)

    planes: dict = collections.defaultdict(lambda: {OP_LINE: [], MODULE_LINE: []})
    for e in events:
        if _is_device_plane(e.plane) and e.line in (OP_LINE, MODULE_LINE):
            planes[e.plane][e.line].append(e)
    by_op: collections.Counter = collections.Counter()
    busy_ns = module_ns = n = 0
    module = None
    for plane in sorted(planes):
        ops, modules = planes[plane][OP_LINE], planes[plane][MODULE_LINE]
        if modules:
            launch = max(modules, key=lambda e: e.dur_ns)
            lo, hi = launch.start_ns, launch.start_ns + launch.dur_ns
            ops = [e for e in ops if e.start_ns >= lo and e.start_ns + e.dur_ns <= hi]
        if not ops:
            continue
        n += 1
        if modules:
            module = module or launch.name
            module_ns += launch.dur_ns
        busy_ns += _union_ns(ops)
        by_op.update(_self_times(ops))
    by_phase: collections.Counter = collections.Counter()
    for name, ns in by_op.items():
        by_phase[phase(name)] += ns
    per = max(n, 1) * 1e9

    def rows(names):
        return [
            [name, phase(name), by_op[name] / per, name in inferred]
            for name in names
        ]

    ranked = [name for name, _ in by_op.most_common()]
    return {
        "devices": n,
        "module": module,
        "module_s": module_ns / per,
        "busy_s": busy_ns / per,
        UNATTRIBUTED: by_phase.get(UNATTRIBUTED, 0) / per,
        "seconds": {p: by_phase.get(p, 0) / per for p in PHASES},
        "share": {
            p: (by_phase.get(p, 0) / busy_ns if busy_ns else 0.0)
            for p in PHASES + (UNATTRIBUTED,)
        },
        "inferred_s": sum(
            ns for name, ns in by_op.items()
            if name in inferred and table.get(name, UNATTRIBUTED) == UNATTRIBUTED
        ) / per,
        "top": rows(ranked[:top]),
        "unattributed_top": rows(
            [name for name in ranked if phase(name) == UNATTRIBUTED][: top // 2]
        ),
    }
