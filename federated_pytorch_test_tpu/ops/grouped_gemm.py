"""Grouped block GEMM: `[G, M, K] x [G, K, N] -> [G, M, N]`.

The widened client fold (`--client-fold gemm`, engine/steps.py) turns the
probe fan's frozen layers into genuinely wide contractions, but the
ACTIVE group's per-client/per-probe weights stay a G-way family of dots
sharing one logical shape — exactly the contraction the layer-group
partition guarantees is legal to batch (all clients share identical
group shapes). XLA lowers it as a batched `dot_general`, which on TPU
refuses to widen M across the group axis for small per-group M: each
group member becomes its own skinny MXU launch. The kernel here sweeps
the M tiles of ALL groups through one `pallas_call` so the MXU pipeline
sees G·M rows back to back — the grouped-GEMM arrangement the ISSUE's
`[K, B·P, in] x [K, in, out]` contraction names.

`grouped_matmul` is the public entry: the default backend is the einsum
(`'gmk,gkn->gmn'` — what `jax.vmap` of a dense layer lowers to anyway,
byte-for-byte engine-safe on every platform and under every transform);
`backend='pallas'` opts into the TPU kernel (interpret mode off-TPU, so
CPU tests exercise the same code path). The kernel keeps K untiled — the
engine's per-group inner dims are at most a few thousand, so a
`[TM, K] + [K, TN]` working set fits VMEM comfortably — and pads M/N
tails through Pallas block padding (K is never masked, so no padding
value can contaminate a valid output row).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from federated_pytorch_test_tpu.ops import _interpret

# MXU-friendly tiles; f32 minimum tile is (8, 128) so both are multiples.
# M tiles sized for the fold's realistic per-group rows (B·P = 128..1024);
# the tail tile is block-padded, any M/N works.
_TILE_M = 256
_TILE_N = 256



def _grouped_kernel(lhs_ref, rhs_ref, out_ref):
    """One grid step: out[g, i·TM:(i+1)·TM, j·TN:(j+1)·TN] = lhs @ rhs.

    K arrives whole, so the contraction never crosses a block boundary
    and M/N tail padding stays confined to discarded output rows/cols —
    no masks needed (a padded lhs row can only produce a padded out row).
    """
    out_ref[:] = jax.lax.dot_general(
        lhs_ref[:],
        rhs_ref[:],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    ).astype(out_ref.dtype)


def grouped_matmul_pallas(lhs: jnp.ndarray, rhs: jnp.ndarray) -> jnp.ndarray:
    """The TPU grouped GEMM: grid sweeps (group, M tile, N tile).

    lhs: [G, M, K]; rhs: [G, K, N] -> [G, M, N] in lhs's dtype, f32
    accumulation. Interpret mode off-TPU.
    """
    g, m, k = lhs.shape
    g2, k2, n = rhs.shape
    if g != g2 or k != k2:
        raise ValueError(
            f"grouped_matmul shapes disagree: lhs {lhs.shape}, rhs {rhs.shape}"
        )
    tm = min(_TILE_M, m)
    tn = min(_TILE_N, n)
    grid = (g, pl.cdiv(m, tm), pl.cdiv(n, tn))
    return pl.pallas_call(
        _grouped_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, tm, k), lambda gi, i, j: (gi, i, 0)),
            pl.BlockSpec((None, k, tn), lambda gi, i, j: (gi, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, tm, tn), lambda gi, i, j: (gi, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, m, n), lhs.dtype),
        interpret=_interpret(),
    )(lhs, rhs)


def grouped_matmul(
    lhs: jnp.ndarray, rhs: jnp.ndarray, backend: str = "einsum"
) -> jnp.ndarray:
    """`[G, M, K] x [G, K, N] -> [G, M, N]`, backend-selectable.

    'einsum' (default) is the engine-safe path — identical lowering to
    the `jax.vmap`-of-dense formulation it replaces, on every platform;
    'pallas' is the explicit TPU opt-in (interpret mode off-TPU). The
    engine itself never routes through 'pallas' implicitly: model-level
    Pallas would change `engine/steps.py _check_vma`'s contract.
    """
    if backend == "einsum":
        return jnp.einsum("gmk,gkn->gmn", lhs, rhs)
    if backend == "pallas":
        return grouped_matmul_pallas(lhs, rhs)
    raise ValueError(
        f"grouped_matmul backend must be 'einsum' or 'pallas', got {backend!r}"
    )
