"""The inner solver's history passes, compiled at the flagship's real
size for a TPU v5e that is described and not attached.

What interpret mode and the CPU backend cannot show: whether the TPU's
compiler takes the Pallas kernels' blocks and in-kernel reshapes, and
what it makes of the `[K, m, R, 128]` histories (optim/history.py) in
the compact direction — the layout must be read as it lies and written
one slab at a time, in place, never relaid or copied whole. Nothing runs
here: these are compiles, a few seconds each, no times.

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


def _top_level(text, shape):
    """[(name, op, line)] of the instructions OUTSIDE fused computations
    whose result is `shape` (parameters, tuples and loops hand a buffer
    on and are left out)."""
    out, fused = [], False
    for line in text.splitlines():
        if re.match(r"^(ENTRY\s+)?%?[\w.\-]+\s*\(.*\)\s*->.*\{\s*$", line):
            fused = "fused_computation" in line.split("(")[0]
            continue
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\S+) ([\w\-]+)\(", line)
        if fused or not m or not m.group(2).startswith(shape):
            continue
        if m.group(3) not in (
            "parameter", "get-tuple-element", "tuple", "bitcast", "while",
            "conditional", "call",
        ):
            out.append((m.group(1), m.group(3), line))
    return out


def _fused_root(text, line):
    """The root instruction's op of the computation a fusion calls."""
    name = re.search(r"calls=%?([\w.\-]+)", line).group(1)
    body = text[text.index(f"%{name} ("):]
    root = re.search(r"^\s*ROOT %?[\w.\-]+ = \S+ ([\w\-]+)\(", body, re.M)
    return root.group(1)


@pytest.mark.parametrize("direction", ["compact", "two_loop"])
def test_compiled_step_never_relays_a_history(one_chip, direction):
    # the vmapped step at K=6, m=10, N=4,720,640, as the benchmark's cell
    # runs it in group 8's round: the only instructions that produce a
    # [6, 10, 36880, 128] array are the two row writes, each a fusion
    # whose root updates a slice of its own operand, in place
    k, n = 6, RESNET18_LARGEST_GROUP
    cfg = LBFGSConfig(
        max_iter=4, history_size=M, line_search=True, batch_mode=True,
        direction=direction,
    )

    def one(x, a, state):
        return lbfgs_step(lambda xx: jnp.sum(a * (xx - 1.0) ** 2), x, state, cfg)

    def on_chip(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    x = on_chip(jax.ShapeDtypeStruct((k, n), jnp.float32))
    state = jax.tree.map(
        on_chip,
        jax.eval_shape(
            jax.vmap(lambda xx: lbfgs_init(xx, cfg)), jnp.zeros((k, n))
        ),
    )
    assert state.s_hist.shape == (k, M, 36880, 128)  # no padding at all
    with jax.default_matmul_precision("highest"):
        compiled = (
            jax.jit(jax.vmap(one), donate_argnums=(2,))
            .lower(x, x, state)
            .compile()
        )
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
