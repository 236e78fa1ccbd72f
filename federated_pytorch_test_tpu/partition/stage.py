"""A function of `x` split by what depends on `x`, at trace time.

`assemble` (partition/assemble.py) made the dependence visible: a frozen
leaf is the step-entry tree's own leaf, so everything computed from
frozen leaves alone depends on no probe. Whether that part then left
the solver's loops was the compiler's decision, by a size heuristic and
a round count (PERF.md §6, PR 32). `stage_invariant` states it in the
program: the function is traced once, every equation none of whose
inputs depends on `x` is evaluated where `stage_invariant` is called,
and the function handed back replays the dependent equations only,
reading the invariant values as data. No model is split by hand; the
split is the traced function's own dependence structure.

An equation is ONE equation whatever it holds (`pjit`, `custom_jvp_call`,
`custom_vjp_call`, `scan`, `while`, `remat`, `pallas_call`): dependent
if any input is, never opened. The same equations run on the same
operands, bound in another place.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal, jaxpr_as_fun

# producers that compute nothing: where one makes a border value LARGER
# than what it reads, the dependent side replays it and the smaller
# inputs cross the border instead (a broadcast constant is not kept live
# across the whole step)
_DATA_MOVEMENT = frozenset({
    "broadcast_in_dim", "iota", "reshape", "convert_element_type",
    "transpose", "squeeze", "expand_dims",
})


def _nbytes(v) -> int:
    return v.aval.size * v.aval.dtype.itemsize


def _inflates(eqn) -> bool:
    read = sum(_nbytes(v) for v in eqn.invars if not isinstance(v, Literal))
    return eqn.primitive.name in _DATA_MOVEMENT and read < sum(
        _nbytes(v) for v in eqn.outvars
    )


def split_by_dependence(jaxpr: Jaxpr):
    """`(invariant, dependent, border)` of a jaxpr whose `invars` are
    `x`: the equations that cannot see `x`, those that can (with the
    replayed data movement in front, in the jaxpr's order), and the
    variables the second list and the outputs read from outside it."""
    moves = set(jaxpr.invars)
    producer, invariant, dependent = {}, [], []
    for eqn in jaxpr.eqns:
        # an effect is ordered among the function's evaluations: it
        # stays where it was
        if eqn.effects or any(
            not isinstance(v, Literal) and v in moves for v in eqn.invars
        ):
            moves.update(eqn.outvars)
            dependent.append(eqn)
        else:
            producer.update((v, eqn) for v in eqn.outvars)
            invariant.append(eqn)

    def reads(eqns):
        made = moves.union(*(e.outvars for e in eqns))
        return [
            v for e in eqns for v in e.invars
            if not isinstance(v, Literal) and v not in made
        ]

    replayed, todo = set(), reads(dependent)
    while todo:
        eqn = producer.get(todo.pop())
        if eqn is not None and id(eqn) not in replayed and _inflates(eqn):
            replayed.add(id(eqn))
            todo += reads([eqn])
    dependent = [e for e in invariant if id(e) in replayed] + dependent
    border = list(dict.fromkeys(
        reads(dependent) + [
            v for v in jaxpr.outvars
            if not isinstance(v, Literal) and v not in moves
        ]
    ))
    return invariant, dependent, border


def stage_invariant(fn: Callable, x_like: Any) -> Callable:
    """`fn` — a function of `x` alone; everything else closed over,
    tracers included — with its `x`-invariant part evaluated HERE, once.

    The function returned computes `fn(x)`'s own pytree from `x` and the
    border values; an output that does not depend on `x` comes from the
    invariant part. Both parts run as jaxprs (`jaxpr_as_fun`), so every
    equation keeps its source information and, under the scope of its
    call site, its own name stack (a flax module path in a profile).
    """
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(x_like)
    jaxpr = closed.jaxpr
    invariant, dependent, border = split_by_dependence(jaxpr)
    given = dict(zip(jaxpr.constvars, closed.consts))
    made_here = [v for v in border if v not in given]
    values = jaxpr_as_fun(
        ClosedJaxpr(
            Jaxpr(jaxpr.constvars, (), made_here, invariant, jaxpr.effects,
                  jaxpr.debug_info.with_unknown_names()),
            closed.consts,
        )
    )()
    given.update(zip(made_here, values))
    replay = jaxpr_as_fun(
        ClosedJaxpr(
            Jaxpr(border, jaxpr.invars, jaxpr.outvars, dependent,
                  jaxpr.effects, jaxpr.debug_info),
            [given[v] for v in border],
        )
    )
    out_tree = jax.tree.structure(out_shape)

    def staged(x):
        return jax.tree.unflatten(out_tree, replay(*jax.tree.leaves(x)))

    return staged
