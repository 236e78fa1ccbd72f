"""The inner solver's history passes and the flagship's client step,
compiled at their real size for a TPU v5e that is described and not
attached.

What interpret mode and the CPU backend cannot show: whether the TPU's
compiler takes the Pallas kernels' blocks and in-kernel reshapes, and
what it makes of the `[K, m, R, 128]` histories (optim/history.py) in
the compact direction — the layout must be read as it lies and written
one slab at a time, in place, never relaid or copied whole; and where,
in the compiled client step, the whole-vector copies and the forward
pass below the active layer end up once the objective is assembled from
(frozen tree, active group) and split by dependence on `x`: at the
step's level, not in the solver's loops. Nothing runs here: these are
compiles, a few seconds each (the client step: a minute or two a
group), no times.

The topology is described inside a fixture, never at import: a worker
that only collects this file must not load the TPU's library
(`on-chip-measurement` guide §2). All such tests live in this one file.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from federated_pytorch_test_tpu.optim import LBFGSConfig, lbfgs_init, lbfgs_step
from federated_pytorch_test_tpu.optim.history import lane_rows

M = 10
RESNET18_LARGEST_GROUP = 4_720_640  # group 8 of the benchmark's cell


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile can be written to the persistent cache but not read
    # back without a chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# instructions that hand a buffer on and make none
_PASSES_ON = (
    "parameter", "get-tuple-element", "tuple", "bitcast", "while",
    "conditional", "call",
)


def _computations(text):
    """{computation: [(name, result shape, opcode, line)]} of a compiled
    program's text, and the entry's name. A tuple shape has spaces."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        m = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$", line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
            continue
        head = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)
        if cur is None or not head:
            continue
        rest = line[head.end():]
        if rest.startswith("("):
            depth = 0
            for i, ch in enumerate(rest):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    break
            shape, rest = rest[: i + 1], rest[i + 1:]
        else:
            shape, _, rest = rest.partition(" ")
        op = re.match(r"\s*([\w\-]+)\(", rest)
        if op:
            comps[cur].append((head.group(1), shape, op.group(1), line))
    return comps, entry


def _top_level(text, shape):
    """[(name, op, line)] of the instructions OUTSIDE fused computations
    whose result is `shape` (parameters, tuples and loops hand a buffer
    on and are left out)."""
    return [
        (name, op, line)
        for comp, instructions in _computations(text)[0].items()
        if "fused_computation" not in comp
        for name, result, op, line in instructions
        if result.startswith(shape) and op not in _PASSES_ON
    ]


def _fused_root(text, line):
    """The root instruction's op of the computation a fusion calls."""
    name = re.search(r"calls=%?([\w.\-]+)", line).group(1)
    body = text[text.index(f"%{name} ("):]
    root = re.search(r"^\s*ROOT %?[\w.\-]+ = \S+ ([\w\-]+)\(", body, re.M)
    return root.group(1)


_STEPS = {}


def _compiled_step(sharding, direction, k=6, n=RESNET18_LARGEST_GROUP):
    """The vmapped `lbfgs_step` at K=6, m=10, N=4,720,640, as the
    benchmark's cell runs it in group 8's round, compiled once a
    direction for the described chip."""
    key = (sharding, direction, k, n)
    if key in _STEPS:
        return _STEPS[key]
    cfg = LBFGSConfig(
        max_iter=4, history_size=M, line_search=True, batch_mode=True,
        direction=direction,
    )

    def one(x, a, state):
        return lbfgs_step(lambda xx: jnp.sum(a * (xx - 1.0) ** 2), x, state, cfg)

    def on_chip(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)

    x = on_chip(jax.ShapeDtypeStruct((k, n), jnp.float32))
    state = jax.tree.map(
        on_chip,
        jax.eval_shape(
            jax.vmap(lambda xx: lbfgs_init(xx, cfg)), jnp.zeros((k, n))
        ),
    )
    assert state.s_hist.shape == (k, M, 36880, 128)  # no padding at all
    with jax.default_matmul_precision("highest"):
        _STEPS[key] = (
            jax.jit(jax.vmap(one), donate_argnums=(2,))
            .lower(x, x, state)
            .compile()
        )
    return _STEPS[key]


@pytest.mark.parametrize("direction", ["compact", "two_loop"])
def test_compiled_step_never_relays_a_history(one_chip, direction):
    # the vmapped step at K=6, m=10, N=4,720,640: the only instructions
    # that produce a [6, 10, 36880, 128] array are the two row writes,
    # each a fusion whose root updates a slice of its own operand, in
    # place
    k, n = 6, RESNET18_LARGEST_GROUP
    compiled = _compiled_step(one_chip, direction)
    text = compiled.as_text()
    made = _top_level(text, f"f32[{k},{M},36880,128]")
    assert [op for _, op, _ in made] == ["fusion", "fusion"], [
        (name, op) for name, op, _ in made
    ]
    for _, _, line in made:
        assert _fused_root(text, line) == "dynamic-update-slice", line
    # and nothing holds a masked or relaid copy: the program's scratch is
    # smaller than ONE history (it is the loop's [6, N] vectors)
    one_history = k * M * n * 4
    assert compiled.memory_analysis().temp_size_in_bytes < one_history


def _history_reads(text, shape):
    """{fusion: operands of type `shape`} of the fusions outside fused
    computations that take one, the row writes (whose root updates a
    slice of their operand in place) left out."""
    params = {
        m.group(1): m.group(2)
        for m in re.finditer(r"^%?([\w.\-]+) \((.*)\) -> .*\{\s*$", text, re.M)
    }
    return {
        name: params[callee].count(shape)
        for comp, instructions in _computations(text)[0].items()
        if "fused_computation" not in comp
        for name, _, op, line in instructions
        if op == "fusion"
        for callee in re.findall(r"calls=%?([\w.\-]+)", line)
        if shape in params[callee]
        and _fused_root(text, line) != "dynamic-update-slice"
    }


def test_compact_step_reads_each_history_twice(one_chip):
    # The compact direction carries `S Yᵀ` and `Y Yᵀ` with the ring
    # (optim/compact.py): an iteration reads both histories in pass A
    # (`Sᵀg`, `Yᵀg` and the pushed pair's row of both matrices, one
    # multiply-reduce) and in pass B (the assembly, one fusion). So the
    # fusions that take a [6, 10, 36880, 128] operand, the row writes
    # left out, take it four times in all. With the Gram built from
    # scratch every iteration and `(YᵀY)u` as `Y·(uᵀY)` they took it six
    # times: the Gram S and Y, `Sᵀg` S, `u·Y` Y, `Yᵀg` with `Y·(uY)` Y,
    # `w·S` S (PERF.md §6)
    reads = _history_reads(
        _compiled_step(one_chip, "compact").as_text(), f"f32[6,{M},36880,128]"
    )
    assert sum(reads.values()) == 4, reads
    assert sorted(reads.values()) == [2, 2], reads


@pytest.mark.parametrize("n", [RESNET18_LARGEST_GROUP, 1_000_003])
def test_pallas_direction_compiles_at_real_widths(one_chip, n, monkeypatch):
    # the kernels compiled by the chip's compiler, not interpreted: the
    # (m, 128, 128) blocks over R, the in-kernel tile sums, the SMEM
    # scalars, under the client vmap (batch axis on the grid). An odd N
    # has zero lanes in its last tile and a ragged last grid step
    from federated_pytorch_test_tpu.ops import compact_pallas

    monkeypatch.setattr(compact_pallas, "_interpret", lambda: False)
    k = 3

    def on_chip(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hist = on_chip((k, M, lane_rows(n), 128))
    compiled = (
        jax.jit(jax.vmap(compact_pallas.compact_direction_pallas))
        .lower(on_chip((k, n)), hist, hist, on_chip((k,), jnp.int32), on_chip((k,)))
        .compile()
    )
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    # the kernels read the buffers as they are: nothing history-sized is
    # made on the way in
    assert not _top_level(text, f"f32[{k},{M},{lane_rows(n)},128]")


def test_standalone_compact_direction_writes_no_product(one_chip):
    # `compact_direction` makes `S Yᵀ`, `Y Yᵀ`, `Sᵀg`, `Yᵀg` from scratch
    # in multiply-reduces the compiler fuses: at m = 10, N = 4,720,640 its
    # scratch stays far under one [10, 36880, 128] history (a reduce of
    # 220 products is not fused, and wrote 3.27 GB of them out)
    from federated_pytorch_test_tpu.optim.compact import compact_direction

    n = RESNET18_LARGEST_GROUP

    def on_chip(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hist = on_chip((M, lane_rows(n), 128))
    i32 = on_chip((), jnp.int32)
    with jax.default_matmul_precision("highest"):
        compiled = (
            jax.jit(compact_direction)
            .lower(on_chip((n,)), hist, hist, i32, on_chip(()), i32)
            .compile()
        )
    one_history = M * lane_rows(n) * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < one_history / 10


# ------------------------------------------------ the flagship's client step


def _branches(line):
    """The computations a `conditional` instruction names as branches."""
    return re.findall(
        r"(?:true_computation|false_computation)=%?([\w.\-]+)", line
    ) + [
        c.strip().lstrip("%")
        for group in re.findall(r"branch_computations=\{([^}]*)\}", line)
        for c in group.split(",")
    ]


def _reached(comps, name, seen=None):
    """(computation, instruction) for everything `name` runs, the
    computations its fusions, calls and branches name included; a
    nested loop's body is NOT entered (it is a loop of its own)."""
    seen = set() if seen is None else seen
    if name in seen or name not in comps:
        return
    seen.add(name)
    for ins in comps[name]:
        yield name, ins
        if ins[2] == "while":
            continue
        for callee in re.findall(
            r"(?:calls|to_apply)=%?([\w.\-]+)", ins[3]
        ) + _branches(ins[3]):
            yield from _reached(comps, callee, seen)


def _loops(comps, name, depth=0):
    """[(depth, body)] of the loops under computation `name`, nested."""
    out = []
    for _, ins in _reached(comps, name):
        if ins[2] == "while":
            body = re.search(r"body=%?([\w.\-]+)", ins[3]).group(1)
            out += [(depth, body)] + _loops(comps, body, depth + 1)
    return out


def _convolutions(comps, name):
    return sum(ins[2] == "convolution" for _, ins in _reached(comps, name))


def _branch_convolutions(comps, name):
    """[convolutions in the branches of each `conditional`] that `name`
    runs (a nested loop's body not entered)."""
    return [
        sum(_convolutions(comps, b) for b in _branches(ins[3]))
        for _, ins in _reached(comps, name)
        if ins[2] == "conditional"
    ]


# convolutions of the compiled client step by loop level, (at least at
# the step's own level, at most in the L-BFGS body, at most in the
# Armijo body), a conditional's branches counted with the body that
# runs it: read 25 / 10 / 3 for group 8 and 40 / 55 / 18 for group 2
# with the objective split by dependence on `x`; 25 / 32 / 5 and
# 40 / 58 / 20 before, when the compiler alone decided what left the
# loops. The re-evaluation's conditional holds 7 of group 8's 10 and 37
# of group 2's 55 (its forward and backward pass); the program's
# scratch reads 1,218,930,176 and 1,342,521,856 bytes
_CONVOLUTIONS_BY_LEVEL = {
    8: (21, 12, 4, RESNET18_LARGEST_GROUP),  # layer4.1: the longest prefix
    2: (21, 56, 18, 73_984),  # layer1.1: stem and layer1.0 below it
}


@pytest.mark.parametrize("gid", sorted(_CONVOLUTIONS_BY_LEVEL))
def test_client_step_keeps_whole_vector_and_frozen_forward_out_of_the_loops(
    one_chip, gid
):
    # the vmapped client step of preset admm_resnet as the benchmark's
    # cell runs it (K = 6, batch 32, float32 at `highest`, both of the
    # cell's groups), compiled by the chip's compiler. Every evaluation's
    # tree comes from (frozen tree, active group) (partition/assemble.py)
    # and the objective is split by dependence on `x`
    # (partition/stage.py), so
    # (a) nothing inside a loop makes a whole [6, 11173962] parameter
    # matrix: the one write of a step is the final insert, outside them;
    # (b) the forward pass below the active block is at the step's
    # level: an evaluation inside the loops runs the active block's
    # convolutions and what follows (the backward pass below the block
    # belongs to no evaluation: only `x`'s gradient is asked for)
    from jax.flatten_util import ravel_pytree

    from federated_pytorch_test_tpu.engine import get_preset
    from federated_pytorch_test_tpu.engine.steps import (
        GroupContext,
        _client_train_step,
    )
    from federated_pytorch_test_tpu.models import ResNet18

    k, batch = 6, 32
    step_least, lbfgs_most, armijo_most, group_size = _CONVOLUTIONS_BY_LEVEL[gid]
    cfg = get_preset(
        "admm_resnet", n_clients=k, batch=batch, lbfgs_history=M,
        lbfgs_max_iter=4, lbfgs_direction="compact", client_fold="gemm",
    )
    model = ResNet18()
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)), train=False
        )
    )
    zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    flat, unravel = ravel_pytree(zeros["params"])
    part = ResNet18.partition(zeros["params"])
    n, g = part.total, part.group_size(gid)
    assert (n, g) == (11_173_962, group_size)
    ctx = GroupContext(
        model=model, unravel=unravel, partition=part, gid=gid,
        has_stats=True, lbfgs=cfg.lbfgs_config(), strategy="admm",
        admm=cfg.admm_config(), reg_on_active=False,
        client_fold=cfg.client_fold,
    )

    def on_chip(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def stacked(tree):
        return jax.tree.map(lambda s: on_chip((k,) + s.shape, s.dtype), tree)

    lstate = jax.eval_shape(
        lambda x: lbfgs_init(x, ctx.lbfgs), jnp.zeros((g,))
    )
    args = (
        on_chip((k, n)), stacked(lstate), stacked(shapes["batch_stats"]),
        on_chip((k, batch, 32, 32, 3), jnp.uint8),
        on_chip((k, batch), jnp.int32),
        on_chip((k,)), on_chip((k,)),            # mean, std
        on_chip((k, g)), on_chip((g,)), on_chip((k, 1)),  # y, z, rho
    )
    step = jax.vmap(
        _client_train_step(ctx), in_axes=(0, 0, 0, 0, 0, 0, 0, 0, None, 0)
    )
    with jax.default_matmul_precision("highest"):
        compiled = (
            jax.jit(step, donate_argnums=(0, 1, 2)).lower(*args).compile()
        )
    comps, entry = _computations(compiled.as_text())
    loops = _loops(comps, entry)
    # the L-BFGS loop, and inside it the Armijo loop: the deepest loop
    # that runs the model (the ring's row writes are loops too)
    with_convs = [
        (depth, body) for depth, body in loops if _convolutions(comps, body)
    ]
    assert [d for d, _ in with_convs] == [0, 1], loops
    (_, lbfgs_body), (_, armijo_body) = with_convs

    whole = f"f32[{k},{n}]"
    for _, body in loops:
        made = [
            (c, ins[0], ins[2]) for c, ins in _reached(comps, body)
            if ins[1].startswith(whole) and ins[2] not in _PASSES_ON
        ]
        assert not made, made

    counts = tuple(
        _convolutions(comps, c) for c in (entry, lbfgs_body, armijo_body)
    )
    # (c) the re-evaluation is a real conditional in the L-BFGS body,
    # its predicate the block's: its model pass is a branch, skipped in
    # an iteration in which every client stops
    branches = _branch_convolutions(comps, lbfgs_body)
    print(f"group {gid}: convolutions by level {counts}, in the L-BFGS "
          f"body's conditionals {branches}, scratch "
          f"{compiled.memory_analysis().temp_size_in_bytes} bytes")
    assert any(branches), branches
    assert (
        counts[0] >= step_least
        and 0 < counts[1] <= lbfgs_most
        and 0 < counts[2] <= armijo_most
    ), counts
