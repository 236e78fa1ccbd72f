"""Fused Pallas kernels for the compact-representation L-BFGS direction.

`optim/compact.py` computes -H·g (Byrd–Nocedal compact form) as a chain of
XLA ops whose heavy terms each re-read the `[m, N]` history buffers from
HBM: `S Yᵀ`, `Sᵀg`, `Yᵀg`, `u @ Y`, `w @ S` — several history-sized HBM
passes per direction, with N up to ~11M (ResNet18) and m = 10. The
arithmetic is trivial next to the bandwidth, so fusing passes is the whole
game (the reference's two-loop recursion, src/lbfgsnew.py:615-637, is even
worse: 2m sequentially-dependent BLAS1 passes).

Two kernels bound the history traffic at the minimum of two passes:

* `fused_gram_projections` — ONE pass over (S, Y, g) tiles producing all
  four contractions `S Yᵀ` [m,m], `Y Yᵀ` [m,m], `Sᵀg` [m], `Yᵀg` [m]:
  each grid step loads a `[m, T]` tile of S and Y once and feeds both the
  MXU (tile Grams) and the VPU reductions, accumulating into VMEM-resident
  outputs. Computing `Y Yᵀ` in the same pass makes the `(YᵀY)u` term of
  the compact form an m×m matvec instead of its own pair of [N] passes.
* `fused_direction_assembly` — ONE pass producing
  `hg = γ·g + wᵀS − γ·(uᵀY)` tile by tile from the same S/Y tiles.

History-slot validity (`i < count`) is masked INSIDE the kernels (a
sublane-iota row mask next to the lane tail mask), so the raw history
buffers feed the kernels directly — no masked [m, N] copies are
materialized in HBM beforehand. The m×m triangular solves between the
passes are `optim.compact.compact_solves`, shared with the pure-JAX
backend so the two cannot drift.

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

# Tile width along N: wide enough that grid-step overhead does not
# dominate a [m, T] tile (a pre-round sweep put the knee well above 1024;
# not re-measured), while the two [m, T] history tiles (m pads to 16
# sublanes) stay at 1 MiB each, 4 MiB double-buffered. `vmap` (the
# engine maps the direction over each device's local client block)
# prepends the batch axis to the GRID with a squeezed block dimension, so
# VMEM per step does not grow with K_local.
# The tail tile is masked inside the kernels, so any N works.
_TILE_N = 16384



def _vma(*operands) -> frozenset:
    """The mesh axes a kernel's outputs vary over: the union of its
    operands'. `shard_map(check_vma=True)` (the engine maps the direction
    over the client axis) requires it declared on every `out_shape`;
    outside a `shard_map` it is empty."""
    return frozenset().union(*(jax.typeof(x).vma for x in operands))


def _masks(i, n: int, m: int, count):
    """(row [m,1], col [1,T]) validity masks for one grid step.

    Rows `>= count` are invalid history slots; lanes past `n` are the tail
    tile's padding (OOB block reads are unspecified, incl. NaNs).
    """
    col = jax.lax.broadcasted_iota(jnp.int32, (1, _TILE_N), 1) + i * _TILE_N
    row = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    return row < count, col < n


def _gram_kernel(
    cnt_ref, s_ref, y_ref, g_ref, sy_ref, yy_ref, p_ref, q_ref, *, n: int
):
    """One grid step: accumulate tile contributions of S Yᵀ, Y Yᵀ, Sᵀg, Yᵀg."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        sy_ref[:] = jnp.zeros_like(sy_ref)
        yy_ref[:] = jnp.zeros_like(yy_ref)
        p_ref[:] = jnp.zeros_like(p_ref)
        q_ref[:] = jnp.zeros_like(q_ref)

    row, col = _masks(i, n, s_ref.shape[0], cnt_ref[0, 0])
    mask = row & col
    s = jnp.where(mask, s_ref[:], 0.0)
    y = jnp.where(mask, y_ref[:], 0.0)
    g = jnp.where(col, g_ref[:], 0.0)

    contract = (((1,), (1,)), ((), ()))
    sy_ref[:] += jax.lax.dot_general(
        s, y, contract, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST
    )
    yy_ref[:] += jax.lax.dot_general(
        y, y, contract, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST
    )
    p_ref[:] += jnp.sum(s * g, axis=1, keepdims=True)
    q_ref[:] += jnp.sum(y * g, axis=1, keepdims=True)


def fused_gram_projections(s, y, g, count=None):
    """(S Yᵀ, Y Yᵀ, Sᵀg, Yᵀg) in one HBM pass over the [m, N] history.

    s, y: [m, N]; g: [N]; count: valid-slot count (rows `>= count` are
    ignored; defaults to all m). Returns (sy [m,m], yy [m,m], p [m],
    q [m]), f32.
    """
    m, n = s.shape
    if count is None:
        count = m
    grid = (pl.cdiv(n, _TILE_N),)
    mm = pl.BlockSpec((m, m), lambda i: (0, 0))
    m1 = pl.BlockSpec((m, 1), lambda i: (0, 0))
    count = jnp.asarray(count, jnp.int32).reshape(1, 1)
    vma = _vma(count, s, y, g)
    sy, yy, p, q = pl.pallas_call(
        functools.partial(_gram_kernel, n=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((m, _TILE_N), lambda i: (0, i)),
            pl.BlockSpec((m, _TILE_N), lambda i: (0, i)),
            pl.BlockSpec((1, _TILE_N), lambda i: (0, i)),
        ],
        out_specs=[mm, mm, m1, m1],
        out_shape=[
            jax.ShapeDtypeStruct((m, m), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((m, m), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((m, 1), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((m, 1), jnp.float32, vma=vma),
        ],
        interpret=_interpret(),
    )(count, s, y, g[None, :])
    return sy, yy, p[:, 0], q[:, 0]


def _assembly_kernel(
    cnt_ref, hd_ref, s_ref, y_ref, g_ref, w_ref, u_ref, out_ref, *, n: int
):
    """One grid step: hg_tile = γ·g + wᵀS − γ·(uᵀY) for one N tile.

    w, u are zero at invalid slots already, but invalid S/Y rows may hold
    anything (public-API buffers) — 0·NaN would poison the dot, so rows
    are masked here too.
    """
    i = pl.program_id(0)
    row, col = _masks(i, n, s_ref.shape[0], cnt_ref[0, 0])
    mask = row & col
    s = jnp.where(mask, s_ref[:], 0.0)
    y = jnp.where(mask, y_ref[:], 0.0)
    g = jnp.where(col, g_ref[:], 0.0)
    hd = hd_ref[0, 0]
    contract = (((1,), (0,)), ((), ()))  # [1, m] @ [m, T]
    ws = jax.lax.dot_general(
        w_ref[:].T, s, contract, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST
    )
    uy = jax.lax.dot_general(
        u_ref[:].T, y, contract, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST
    )
    out_ref[:] = hd * g + ws - hd * uy


def fused_direction_assembly(s, y, g, w, u, h_diag, count=None):
    """hg = h_diag * g + w @ S - h_diag * (u @ Y) in one HBM pass."""
    m, n = s.shape
    if count is None:
        count = m
    grid = (pl.cdiv(n, _TILE_N),)
    smem11 = pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)
    count = jnp.asarray(count, jnp.int32).reshape(1, 1)
    h_diag = jnp.asarray(h_diag, jnp.float32).reshape(1, 1)
    hg = pl.pallas_call(
        functools.partial(_assembly_kernel, n=n),
        grid=grid,
        in_specs=[
            smem11,
            smem11,
            pl.BlockSpec((m, _TILE_N), lambda i: (0, i)),
            pl.BlockSpec((m, _TILE_N), lambda i: (0, i)),
            pl.BlockSpec((1, _TILE_N), lambda i: (0, i)),
            pl.BlockSpec((m, 1), lambda i: (0, 0)),
            pl.BlockSpec((m, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, _TILE_N), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct(
            (1, n), jnp.float32, vma=_vma(count, h_diag, s, y, g, w, u)
        ),
        interpret=_interpret(),
    )(
        count,
        h_diag,
        s,
        y,
        g[None, :],
        w[:, None],
        u[:, None],
    )
    return hg[0]


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
    # inside the kernels, so no masked [m, N] copies hit HBM
    g32 = g.astype(f32)
    s32 = s_hist.astype(f32)
    y32 = y_hist.astype(f32)

    sy, yy, p, q = fused_gram_projections(s32, y32, g32, count)

    valid = jnp.arange(m) < count
    u, w, _, _ = compact_solves(
        sy, p, q, valid, h_diag.astype(f32), lambda u: (yy @ u, None),
        oldest,
    )

    hg = fused_direction_assembly(s32, y32, g32, w, u, h_diag, count)
    return (-hg).astype(dt)
