"""Phase scopes inside the round program.

The round program (engine/steps.py `build_round_fn`) is ONE XLA program
per partition round; a profiler trace names what ran in it only as the
compiler does (`%maximum_select_fusion.3`). The phases of `PHASES` are
opened as `jax.named_scope("fedtpu.<phase>")` at the phase boundaries of
the program's source; a scope is HLO metadata (`op_name`), never an
operation, so the programs compute what they computed.

Device time by phase is read outside the program: the benchmark joins a
trace of its round programs with their text (`Trainer.round_program_text`,
chipbench/phase_join.py), and scripts/phase_table.py does the same for
the raw `--profile-dir` windows (docs/OBSERVABILITY.md §Phase scopes and
`--profile-dir`).

Module-level imports stay jax-free (the `report`/`watch` verbs import
`obs/` on hosts without a backend).
"""

from __future__ import annotations

PREFIX = "fedtpu."

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
