"""Fused Pallas kernels for the compact-representation L-BFGS direction.

`optim/compact.py` computes -H·g (Byrd–Nocedal compact form) as XLA
fusions over the history buffers: `S Yᵀ`, `Y Yᵀ`, `Sᵀg`, `Yᵀg` in one
multiply-reduce, `w @ S − γ·(u @ Y)` in one elementwise pass, with N up to
~11M (ResNet18) and m = 10. The arithmetic is trivial next to the
bandwidth, so fusing passes is the whole game (the reference's two-loop
recursion, src/lbfgsnew.py:615-637, is far worse: 2m
sequentially-dependent BLAS1 passes). These kernels are the same two
passes written by hand.

The histories are `[m, R, 128]`, every pair laid out in lanes
(optim/history.py), and the kernels read them as they lie: a grid step
takes a `(m, T/128, 128)` block over the `R` axis — m slabs of whole
`(8, 128)` tiles — and works on it with the VPU alone, a slab of `S`
against a slab of `Y`. Nothing is relaid for the MXU, whose `[m, T]`
operand would want the pair index in the sublanes.

Two kernels bound the history traffic at the minimum of two passes:

* `fused_gram_projections` — ONE pass over (S, Y, g) blocks producing all
  four contractions `S Yᵀ` [m,m], `Y Yᵀ` [m,m], `Sᵀg` [m], `Yᵀg` [m]:
  each grid step loads a block of S and Y once, multiplies slab by slab
  and adds the products down to one `(8, 128)` tile per contraction
  entry, accumulated in VMEM-resident outputs; the last reduction, over a
  tile, is left to XLA. Computing `Y Yᵀ` in the same pass makes the
  `(YᵀY)u` term of the compact form an m×m matvec instead of its own pair
  of passes.
* `fused_direction_assembly` — ONE pass producing
  `hg = γ·g + wᵀS − γ·(uᵀY)` block by block from the same S/Y blocks,
  `w` and `u` read as scalars.

History-slot validity (`i < count`) is masked INSIDE the kernels (by
select, next to the tail block's row mask), so the raw history buffers
feed the kernels directly — no masked copies are materialized in HBM
beforehand. The m×m triangular solves between the passes are
`optim.compact.compact_solves`, shared with the pure-JAX backend so the
two cannot drift.

Off-TPU the kernels run in Pallas interpret mode, so the CPU test mesh and
the multi-chip dry run exercise the exact same code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from federated_pytorch_test_tpu.ops import _interpret
from federated_pytorch_test_tpu.optim.compact import compact_solves
from federated_pytorch_test_tpu.optim.history import LANES, from_lanes, to_lanes

# Parameters per grid step: wide enough that grid-step overhead does not
# dominate a block (a pre-round sweep put the knee well above 1024; not
# re-measured), while the two (m, _TILE_N/128, 128) history blocks stay
# at m * 64 KiB each (640 KiB at m = 10, 2.5 MiB double-buffered: the pair
# index pads nothing). `vmap` (the engine maps the direction over each
# device's local client block) prepends the batch axis to the GRID with a
# squeezed block dimension, so VMEM per step does not grow with K_local.
# `R` need not divide: the tail block's rows past it are masked inside
# the Gram kernel (the assembly is elementwise, its tail is dropped).
_TILE_N = 16384
_TILE_R = _TILE_N // LANES


def _vma(*operands) -> frozenset:
    """The mesh axes a kernel's outputs vary over: the union of its
    operands'. `shard_map(check_vma=True)` (the engine maps the direction
    over the client axis) requires it declared on every `out_shape`;
    outside a `shard_map` it is empty."""
    return frozenset().union(*(jax.typeof(x).vma for x in operands))


def _to_tile(x):
    """`[..., T/128, 128]` products added down to `[..., 8, 128]`: adds
    of whole tiles, no work across sublanes or lanes."""
    return jnp.sum(x.reshape(*x.shape[:-2], -1, 8, LANES), axis=-3)


def _gram_kernel(
    cnt_ref, s_ref, y_ref, g_ref, sy_ref, yy_ref, p_ref, q_ref, *, rows: int
):
    """One grid step: accumulate block contributions of S Yᵀ, Y Yᵀ, Sᵀg,
    Yᵀg, each entry as an `(8, 128)` tile of partial sums."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        sy_ref[:] = jnp.zeros_like(sy_ref)
        yy_ref[:] = jnp.zeros_like(yy_ref)
        p_ref[:] = jnp.zeros_like(p_ref)
        q_ref[:] = jnp.zeros_like(q_ref)

    m = s_ref.shape[0]
    # rows >= count are invalid history slots; block rows past `rows` are
    # the tail block's padding (OOB block reads are unspecified, incl.
    # NaNs). Lanes past N are zero in the buffers and in `g`
    slot = jax.lax.broadcasted_iota(jnp.int32, (m, 1, 1), 0) < cnt_ref[0, 0]
    tail = (
        jax.lax.broadcasted_iota(jnp.int32, (_TILE_R, LANES), 0) + i * _TILE_R
        < rows
    )
    s = jnp.where(slot & tail[None], s_ref[:], 0.0)
    y = jnp.where(slot & tail[None], y_ref[:], 0.0)
    g = jnp.where(tail, g_ref[:], 0.0)

    for k in range(m):  # static: a slab of S, or of Y, against all of Y
        sy_ref[k] += _to_tile(s[k][None] * y)
        yy_ref[k] += _to_tile(y[k][None] * y)
    p_ref[:] += _to_tile(s * g[None])
    q_ref[:] += _to_tile(y * g[None])


def fused_gram_projections(s, y, g, count=None):
    """(S Yᵀ, Y Yᵀ, Sᵀg, Yᵀg) in one HBM pass over the history.

    s, y: `[m, R, 128]` buffers (optim/history.py); g: [N]; count:
    valid-slot count (rows `>= count` are ignored; defaults to all m).
    Returns (sy [m,m], yy [m,m], p [m], q [m]), f32.
    """
    m, rows, _ = s.shape
    if count is None:
        count = m
    grid = (pl.cdiv(rows, _TILE_R),)
    hist = pl.BlockSpec((m, _TILE_R, LANES), lambda i: (0, i, 0))
    mm = pl.BlockSpec((m, m, 8, LANES), lambda i: (0, 0, 0, 0))
    m1 = pl.BlockSpec((m, 8, LANES), lambda i: (0, 0, 0))
    count = jnp.asarray(count, jnp.int32).reshape(1, 1)
    vma = _vma(count, s, y, g)
    sy, yy, p, q = pl.pallas_call(
        functools.partial(_gram_kernel, rows=rows),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            hist,
            hist,
            pl.BlockSpec((_TILE_R, LANES), lambda i: (i, 0)),
        ],
        out_specs=[mm, mm, m1, m1],
        out_shape=[
            jax.ShapeDtypeStruct((m, m, 8, LANES), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((m, m, 8, LANES), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((m, 8, LANES), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((m, 8, LANES), jnp.float32, vma=vma),
        ],
        interpret=_interpret(),
    )(count, s, y, to_lanes(g))
    return tuple(jnp.sum(t, axis=(-2, -1)) for t in (sy, yy, p, q))


def _assembly_kernel(cnt_ref, hd_ref, w_ref, u_ref, s_ref, y_ref, g_ref, out_ref):
    """One grid step: hg = γ·g + wᵀS − γ·(uᵀY) for one block of rows.

    w, u are zero at invalid slots already, but invalid S/Y rows may hold
    anything (public-API buffers) — 0·NaN would poison the sum, so slots
    are taken out by select here too. Elementwise over the block: what
    the tail block reads past `R` lands past `R`, and is dropped.
    """
    hd = hd_ref[0, 0]
    acc = hd * g_ref[:]
    for k in range(s_ref.shape[0]):  # static: one slab of S and of Y each
        ok = k < cnt_ref[0, 0]
        acc += w_ref[0, k] * jnp.where(ok, s_ref[k], 0.0)
        acc -= (hd * u_ref[0, k]) * jnp.where(ok, y_ref[k], 0.0)
    out_ref[:] = acc


def fused_direction_assembly(s, y, g, w, u, h_diag, count=None):
    """hg = h_diag * g + w @ S - h_diag * (u @ Y) in one HBM pass.

    s, y: `[m, R, 128]` buffers; g: [N]; w, u: [m]. Returns hg [N].
    """
    m, rows, _ = s.shape
    if count is None:
        count = m
    grid = (pl.cdiv(rows, _TILE_R),)
    smem = functools.partial(
        pl.BlockSpec, index_map=lambda i: (0, 0), memory_space=pltpu.SMEM
    )
    hist = pl.BlockSpec((m, _TILE_R, LANES), lambda i: (0, i, 0))
    vec = pl.BlockSpec((_TILE_R, LANES), lambda i: (i, 0))
    count = jnp.asarray(count, jnp.int32).reshape(1, 1)
    h_diag = jnp.asarray(h_diag, jnp.float32).reshape(1, 1)
    hg = pl.pallas_call(
        _assembly_kernel,
        grid=grid,
        in_specs=[
            smem((1, 1)), smem((1, 1)), smem((1, m)), smem((1, m)),
            hist, hist, vec,
        ],
        out_specs=vec,
        out_shape=jax.ShapeDtypeStruct(
            (rows, LANES), jnp.float32, vma=_vma(count, h_diag, s, y, g, w, u)
        ),
        interpret=_interpret(),
    )(count, h_diag, w[None, :], u[None, :], s, y, to_lanes(g))
    return from_lanes(hg, g.shape[0])


def compact_direction_pallas(g, s_hist, y_hist, count, h_diag, oldest=0):
    """-H·g via the compact representation, history traffic fused to 2 passes.

    Drop-in replacement for `optim.compact.compact_direction` (same
    signature, same result up to reduction order); see that module's
    docstring for the algebra, the ring layout (`oldest`: both kernels
    run over the rows as stored, `compact_solves` alone reorders) and the
    masking of invalid/degenerate slots.
    """
    m = s_hist.shape[0]
    dt = g.dtype
    f32 = jnp.float32
    # f32 casts are free for the engine's f32 trees; row masking happens
    # inside the kernels, so no masked copies of a history hit HBM
    g32 = g.astype(f32)
    s32 = s_hist.astype(f32)
    y32 = y_hist.astype(f32)

    sy, yy, p, q = fused_gram_projections(s32, y32, g32, count)

    valid = jnp.arange(m) < count
    u, w, _ = compact_solves(sy, yy, p, q, valid, h_diag.astype(f32), oldest)

    hg = fused_direction_assembly(s32, y32, g32, w, u, h_diag, count)
    return (-hg).astype(dt)
