"""Flash attention as Pallas TPU kernels (forward + flash-2 backward).

`parallel.dense_attention` materializes the `[B, H, S, S]` score matrix —
fine at ViT's 64 tokens, hostile at long context: HBM traffic and memory
grow with S². These kernels compute exact attention blockwise in VMEM
(online softmax, never more than a `[BQ, BK]` tile of scores live), with
the standard flash-2 backward from the saved per-row logsumexp:

    fwd:  for each Q block, stream KV blocks; carry (m, l, o); save
          L = m + log(l) per row.
    bwd:  D = rowsum(dO * O); then
          dV_j = sum_i P_ij^T dO_i,   dP_ij = dO_i V_j^T,
          dS_ij = P_ij (dP_ij - D_i),
          dQ_i = sum_j dS_ij K_j * scale,  dK_j = sum_i dS_ij^T Q_i * scale
          with P recomputed blockwise from (Q, K, L).

Memory: NOTHING is whole-sequence-resident in VMEM. Every kernel runs a
3-D grid `(batch*head, outer block, streamed block)` — the streamed
operand (KV for fwd/dq, Q/dO for dk/dv) enters one `[128, D]` tile per
grid step through its BlockSpec while accumulators live in VMEM scratch,
initialized on the first streamed step and flushed to the revisited
output block on the last. Sequence length is therefore HBM-bound, not
VMEM-bound.

Causal iteration comes in two shapes:

* ALIGNED (the single-device `flash_attention` path, offsets == 0,
  s_q == s_kv): the grid itself is TRIANGULAR — a `(batch*head, npairs)`
  grid over exactly the lower-triangular (Q block, KV block) pairs,
  driven by scalar-prefetched (i, j) lookup tables that the BlockSpec
  index maps read. Skipped tiles do not exist: no grid step, no DMA, no
  compute is spent above the diagonal, so causal runs the ~S²/2 work a
  causal kernel should, not predicated-S².
* OFFSET (`flash_block` under ring attention, device-varying traced
  offsets): the rectangular grid stays (the useful-pair count is not
  static), with `@pl.when` predication plus index-map CLAMPING onto the
  last useful block — a repeated block index makes the tile DMA a no-op,
  so skipped steps still cost neither bandwidth nor MXU compute, only
  grid-step overhead.

Global-position offsets: every kernel takes an int32 `[q_off, k_off]`
scalar-prefetch operand placing this call's Q and K/V blocks on the
GLOBAL sequence axis, so the causal mask compares `k_off + kcol <=
q_off + qrow`. The single-device entry `flash_attention` passes (0, 0);
`flash_block` takes device-varying offsets and additionally returns the
per-row logsumexp — that pair is exactly the partial result
`ring_attention(use_flash=True)` (parallel/ring.py) folds across ring
steps, composing sequence parallelism with the VMEM-blockwise kernel:
the ring streams K/V blocks across devices over ICI while this kernel
streams tiles within the device. A KV block entirely in a causal Q row's
future contributes `lse = -1e30` and a zero output row, which the ring's
online-softmax merge discards exactly. The backward treats the lse
cotangent analytically: d lse/d scores is the softmax itself, so `dlse`
just shifts the flash-2 `delta` term (`delta = rowsum(dO*O) - dlse`) and
the kernels are unchanged.

Layout: kernels take `[S, D]` per (batch, head) — Q/K/V arrive as
`[BH, S, D]`. The public entries keep the framework's `[B, S, H, D]`
convention of `parallel/ring.py`; `flash_attention(q, k, v)` is a
drop-in for `dense_attention` (same signature, exact same math —
tests/test_flash.py). MXU dots are pinned to HIGHEST precision — the
f32 reference comparison exposes the default fast-precision passes at
long S.

Off-TPU the kernels run in Pallas interpret mode, so CPU tests exercise
the exact code path the TPU compiles.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from federated_pytorch_test_tpu.ops import _interpret

_NEG_BIG = -1e30

_LOG2E = 1.4426950408889634  # 1/ln 2: exp(x) == exp2(x * _LOG2E)
_LN2 = 0.6931471805599453

# Default tile heights; S must be a multiple of the resolved tile (the
# LM/ViT sequence lengths are powers of two — raise, don't silently pad,
# so callers see the constraint). 128 is the MXU systolic edge and the
# floor; at D=64 a 128-row tile leaves every grid step overhead-dominated
# (~1 us/step vs ~20 ns of MXU work), so the defaults are larger — see
# benchmarks/flash_bf16_tiles.json for the measured sweep on a v5e.
# `flash_attention` upgrades the default to 1024 for bf16 inputs at
# D <= 64 (measured best; bf16 halves tile VMEM so 1024 compiles).
# Both public entries take block_q/block_k overrides.
_BQ = 512
_BK = 512

_HI = jax.lax.Precision.HIGHEST



# dot_general contracting specs: last-with-last ([M,D]x[N,D] -> [M,N]),
# last-with-first ([M,N]x[N,D] -> [M,D]), first-with-first (transpose-left)
_LL = ((1,), (1,))
_LF = ((1,), (0,))
_FF = ((0,), (0,))


def _dot(a, b, dims, prec=_HI):
    if a.dtype != b.dtype:
        # mixed tiles (bf16 residuals dotted against f32 cotangents):
        # promote both sides — dot_general requires matching dtypes
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=jnp.float32,
        precision=prec,
    )


def _causal_mask(sc, qpos0, kpos0):
    """Mask scores where global k position exceeds global q position.

    `qpos0`/`kpos0` are the global positions of the tile's first row/col
    (offset + block index * tile height); they may be traced scalars.
    """
    qpos = qpos0 + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
    kpos = kpos0 + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
    return jnp.where(kpos <= qpos, sc, _NEG_BIG)


def _p_block(q, k, lse, qpos0, kpos0, causal, scale, prec):
    """Recompute the probability tile P = exp(S*scale - lse) for one
    (Q block, KV block) pair — shared by both backward kernels."""
    sc = _dot(q * scale, k, _LL, prec)  # [BQ, BK]
    if causal:
        sc = _causal_mask(sc, qpos0, kpos0)
        # a fully-masked row has lse == sc == _NEG_BIG and exp(0) would
        # be 1; such rows (possible for non-tile-aligned k_off - q_off,
        # where a KEPT tile still contains maskless rows) have P == 0
        return jnp.where(
            (lse > _NEG_BIG * 0.5)[:, None], jnp.exp(sc - lse[:, None]), 0.0
        )
    return jnp.exp(sc - lse[:, None])


def _run_unless_skipped(causal, keep_pred, compute):
    """Predicate the streamed-step compute on the causal skip (compute
    runs unconditionally when not causal)."""
    if causal:
        pl.when(keep_pred)(compute)
    else:
        compute()


def _online_softmax_update(sc, m, l, o, v, prec, guard_masked_rows: bool):
    """Fold one score tile into the (m, l, o) online-softmax accumulators.

    Used by the rectangular (offset/ring) forward kernel; the triangular
    kernel carries its own exp2-domain copy of this recurrence with the
    round-5 layout changes (fused denominator, slice-written statistics —
    `_fwd_kernel_tri`). A numerical fix here likely applies there too.
    `guard_masked_rows` zeroes
    rows whose running max is still _NEG_BIG — they have seen only masked
    scores (sc - m_new == 0 there, NOT -inf), possible for non-tile-
    aligned offsets in the OFFSET path; the ALIGNED triangular path never
    produces such rows (every causal row's diagonal tile holds its own
    key), so it skips the guard. The threshold assumes real scores
    satisfy |score| << 5e29 — true for any f32 q,k.
    """
    m_new = jnp.maximum(m, jnp.max(sc, axis=1))
    p = jnp.exp(sc - m_new[:, None])
    if guard_masked_rows:
        p = jnp.where((m_new > _NEG_BIG * 0.5)[:, None], p, 0.0)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=1)
    o_new = o * corr[:, None] + _dot(p, v, _LF, prec)
    return m_new, l_new, o_new


def _p_ds_tile(q, k, v, do, lse, delta, qpos0, kpos0, causal, scale, prec):
    """Recompute P and dS = P * (dP - delta) for one tile — the shared
    backward-pass core (flash-2: dP = dO V^T)."""
    p = _p_block(q, k, lse, qpos0, kpos0, causal, scale, prec)
    dp = _dot(do, v, _LL, prec)
    return p, p * (dp - delta[:, None])


# ---------------------------------------------------------------------------
# causal block-skip predicates and DMA-elision index maps, in terms of the
# global offsets. A streamed block is USEFUL iff its tile overlaps the
# lower-triangular region of the (global q, global k) plane:
#   kv block j vs q block i:  k_off + j*BK  <=  q_off + (i+1)*BQ - 1
# Skipped steps clamp their streamed-operand index onto the last/first
# useful block — the repeated block index makes the DMA a no-op, so
# skipped blocks cost neither bandwidth nor compute.
# ---------------------------------------------------------------------------


def _kv_keep(off, i, j, bq, bk):
    return off[1] + j * bk <= off[0] + (i + 1) * bq - 1


def _kv_clamp(off, i, j, nkv, bq, bk):
    # last useful kv block for q block i (may be <0: whole row masked)
    jmax = (off[0] + (i + 1) * bq - 1 - off[1]) // bk
    return jnp.clip(jnp.minimum(j, jmax), 0, nkv - 1)


def _q_keep(off, j, i, bq, bk):
    return off[0] + (i + 1) * bq - 1 >= off[1] + j * bk


def _q_clamp(off, j, i, nq, bq, bk):
    # first useful q block for kv block j (may be >= nq: block unseen)
    imin = (off[1] + j * bk - off[0]) // bq
    return jnp.clip(jnp.maximum(i, imin), 0, nq - 1)


# ---------------------------------------------------------------------------
# Triangular-grid causal kernels (aligned path). The iteration space is the
# npairs = nq(nq+1)/2 lower-triangular tile pairs; two int32 tables map the
# flat pair index p -> (i, j) and are scalar-prefetched so the BlockSpec
# index maps can read them. i is the outer (Q, accumulate) block and runs
# majored, so each output block's visits are consecutive (Pallas's revisit
# rule) and the accumulators init at j == 0 and flush at the diagonal
# j == i. For dk/dv the roles swap: j outer, i streamed from the diagonal
# down, flush at i == nq - 1.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _tri_tables_qmajor(nq: int):
    """(i_of_p, j_of_p): i-major lower-triangular pairs, j = 0..i."""
    import numpy as np

    i = np.repeat(np.arange(nq), np.arange(1, nq + 1))
    j = np.concatenate([np.arange(r + 1) for r in range(nq)])
    return i.astype(np.int32), j.astype(np.int32)


@functools.lru_cache(maxsize=None)
def _tri_tables_kmajor(nq: int):
    """(j_of_p, i_of_p): j-major lower-triangular pairs, i = j..nq-1."""
    import numpy as np

    j = np.repeat(np.arange(nq), np.arange(nq, 0, -1))
    i = np.concatenate([np.arange(r, nq) for r in range(nq)])
    return j.astype(np.int32), i.astype(np.int32)


def _fwd_kernel_tri(itab, jtab, q_ref, k_ref, v_ref, o_ref, lse_ref,
                    acc, m_acc, l_acc, *, bq: int, d: int, cast16: bool,
                    fuse_l: bool, prec):
    """VPU-lean aligned-causal forward (the measured redesign, round 5).

    The round-3 kernel spent ~80% of its step in VPU softmax work, not
    in the D=64 half-filled MXU dots the round-4 ceiling analysis blamed
    (attribution in `benchmarks/flash_attrib_probe.json`). Measured
    changes, largest first:

    * `fuse_l` (bf16 inputs, D not a lane multiple): `v_ref` is V with a
      ones column appended at `d` (then zero-padded to the 128-lane
      multiple): `p @ v` accumulates the softmax denominator l into
      `acc[:, d]` inside the SAME MXU dot that accumulates o — the
      separate [BQ, BK] rowsum pass and the l scratch disappear. Free
      exactly when the single-pass bf16 PV dot pads its output to the
      next 128 lanes anyway; at f32 precisions the wider dot costs real
      passes (measured: +29% on a 'highest' forward), so those take the
      plain path with an l scratch (`l_acc`, ignored otherwise).
    * the running max / denominator write back as [BQ, 1] lane slices
      instead of broadcast [BQ, 128] stores (~20% of the old step time).
    * scores live in base 2 — Q arrives pre-scaled by scale*log2(e), so
      `exp2` replaces `exp` and the flush converts lse back to natural
      log (lse_nat = lse2 * ln2); the public contract is unchanged.

    With `cast16` the probability tile feeds the MXU in bf16 (inputs
    were bf16 and the caller asked for 'default' precision — the same
    rounding class XLA's dense softmax@V takes on that path). A
    diagonal-only causal mask via `lax.cond` was tried and reverted:
    Mosaic's cond costs more than the masked-tile arithmetic it saves
    (measured: +50% on the backward, where it ran per recompute tile).
    """
    p_id = pl.program_id(1)
    i = itab[p_id]
    j = jtab[p_id]

    @pl.when(j == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_acc[:] = jnp.full_like(m_acc, _NEG_BIG)
        if not fuse_l:
            l_acc[:] = jnp.zeros_like(l_acc)

    sc = _dot(q_ref[0], k_ref[0], _LL, prec)  # [BQ, BK], base-2 domain
    sc = _causal_mask(sc, i * bq, j * bq)
    m = m_acc[:, 0]
    m_new = jnp.maximum(m, jnp.max(sc, axis=1))
    p = jnp.exp2(sc - m_new[:, None])
    if cast16:
        p = p.astype(jnp.bfloat16)
    corr = jnp.exp2(m - m_new)
    acc[:] = acc[:] * corr[:, None] + _dot(p, v_ref[0], _LF, prec)
    if not fuse_l:
        l_acc[:, 0:1] = (
            l_acc[:, 0] * corr + jnp.sum(p.astype(jnp.float32), axis=1)
        )[:, None]
    m_acc[:, 0:1] = m_new[:, None]

    @pl.when(j == i)
    def _():
        a = acc[:]
        l = jnp.maximum(a[:, d] if fuse_l else l_acc[:, 0], 1e-30)
        o_ref[0] = a[:, :d] / l[:, None]
        lse_ref[0] = ((m_acc[:, 0] + jnp.log2(l)) * _LN2)[:, None]


def _p_ds_tile_tri(q, k, v, do, lse, delta, i, j, bq, prec, cast16):
    """P and dS for one triangular-grid tile, in the base-2 domain.

    `q` arrives pre-scaled by scale*log2(e) (as in the forward), so the
    raw dot IS the base-2 score and `exp2` recovers the exact softmax
    P = exp2(s2 - lse*log2e) = exp(s_nat - lse); `lse` stays natural-log
    (the public contract) and converts per row. P and dP are domain-free,
    so the returned dS = P*(dP - delta) is the ordinary NATURAL-domain
    flash-2 cotangent dL/ds_nat — only the callers' final constant
    multiplies account for the q pre-scaling (see the flush comments).
    With `cast16`, P and dS feed the MXU in bf16.
    """
    sc = _causal_mask(_dot(q, k, _LL, prec), i * bq, j * bq)
    p = jnp.exp2(sc - (lse * _LOG2E)[:, None])
    dp = _dot(do, v, _LL, prec)
    ds = p * (dp - delta[:, None])
    if cast16:
        p = p.astype(jnp.bfloat16)
        ds = ds.astype(jnp.bfloat16)
    return p, ds


def _bwd_dq_kernel_tri(itab, jtab, q_ref, k_ref, v_ref, do_ref, lse_ref,
                       delta_ref, dq_ref, dq_acc, *, bq: int, scale: float,
                       cast16: bool, prec):
    p_id = pl.program_id(1)
    i = itab[p_id]
    j = jtab[p_id]

    @pl.when(j == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    k = k_ref[0]
    _, ds = _p_ds_tile_tri(q_ref[0], k, v_ref[0], do_ref[0],
                           lse_ref[0][:, 0], delta_ref[0][:, 0], i, j, bq,
                           prec, cast16)
    dq_acc[:] = dq_acc[:] + _dot(ds, k, _LF, prec)

    @pl.when(j == i)
    def _():
        # ds is natural-domain and k is unscaled: dL/dq = scale*(ds @ k),
        # exactly as in the offset-path kernel
        dq_ref[0] = dq_acc[:] * scale


def _bwd_dkv_kernel_tri(jtab, itab, q_ref, k_ref, v_ref, do_ref, lse_ref,
                        delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                        *, nq: int, bq: int, cast16: bool, prec):
    p_id = pl.program_id(1)
    j = jtab[p_id]
    i = itab[p_id]

    @pl.when(i == j)  # first streamed Q block for this KV block
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q = q_ref[0]
    do = do_ref[0]
    p, ds = _p_ds_tile_tri(q, k_ref[0], v_ref[0], do, lse_ref[0][:, 0],
                           delta_ref[0][:, 0], i, j, bq, prec, cast16)
    # under cast16, dO was cast to bf16 at HBM level in _bwd_tri, so
    # this dot is already bf16 x bf16
    dv_acc[:] = dv_acc[:] + _dot(p, do, _FF, prec)
    dk_acc[:] = dk_acc[:] + _dot(ds, q, _FF, prec)

    @pl.when(i == nq - 1)
    def _():
        # the q tile is PRE-SCALED by scale2 = scale*log2e, so the
        # accumulated ds^T @ q_scaled = scale2*(ds^T @ q); the true
        # dL/dk = scale*(ds^T @ q) = (scale/scale2)*acc = ln2 * acc
        dk_ref[0] = dk_acc[:] * _LN2
        dv_ref[0] = dv_acc[:]


def _fwd_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                o_acc, m_acc, l_acc, *, nkv: int, causal: bool, scale: float,
                prec, bq: int, bk: int):
    qi = pl.program_id(1)
    j = pl.program_id(2)  # streamed KV block

    @pl.when(j == 0)
    def _():
        o_acc[:] = jnp.zeros_like(o_acc)
        m_acc[:] = jnp.full_like(m_acc, _NEG_BIG)
        l_acc[:] = jnp.zeros_like(l_acc)

    def compute():
        q = q_ref[0] * scale  # [BQ, D]
        sc = _dot(q, k_ref[0], _LL, prec)  # [BQ, BK]
        if causal:
            sc = _causal_mask(sc, off_ref[0] + qi * bq, off_ref[1] + j * bk)
        # masked-row guard on: non-aligned ring offsets can produce tiles
        # whose kept rows still see no key (see _online_softmax_update)
        m_new, l_new, o_new = _online_softmax_update(
            sc, m_acc[:, 0], l_acc[:, 0], o_acc[:], v_ref[0], prec,
            guard_masked_rows=causal,
        )
        o_acc[:] = o_new
        m_acc[:] = jnp.broadcast_to(m_new[:, None], m_acc.shape)
        l_acc[:] = jnp.broadcast_to(l_new[:, None], l_acc.shape)

    _run_unless_skipped(causal, _kv_keep(off_ref, qi, j, bq, bk), compute)

    @pl.when(j == nkv - 1)
    def _():
        l = l_acc[:, 0]
        m = m_acc[:, 0]
        # rows with no visible key (possible when k_off > q positions in
        # the ring's off-diagonal blocks): emit 0 output and -BIG lse so
        # the caller's online-softmax merge gives them zero weight
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[0] = o_acc[:] / l_safe[:, None]
        lse_ref[0] = jnp.where(l > 0, m + jnp.log(l_safe), _NEG_BIG)[:, None]


def _bwd_dq_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *, nkv: int, causal: bool, scale: float,
                   prec, bq: int, bk: int):
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def compute():
        k = k_ref[0]
        _, ds = _p_ds_tile(q_ref[0], k, v_ref[0], do_ref[0],
                           lse_ref[0][:, 0], delta_ref[0][:, 0],
                           off_ref[0] + qi * bq, off_ref[1] + j * bk,
                           causal, scale, prec)
        dq_acc[:] = dq_acc[:] + _dot(ds, k, _LF, prec)

    _run_unless_skipped(causal, _kv_keep(off_ref, qi, j, bq, bk), compute)

    @pl.when(j == nkv - 1)
    def _():
        dq_ref[0] = dq_acc[:] * scale


def _bwd_dkv_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, nq: int, causal: bool, scale: float, prec,
                    bq: int, bk: int):
    ki = pl.program_id(1)
    i = pl.program_id(2)  # streamed Q block

    @pl.when(i == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def compute():
        q = q_ref[0]
        do = do_ref[0]
        p, ds = _p_ds_tile(q, k_ref[0], v_ref[0], do, lse_ref[0][:, 0],
                           delta_ref[0][:, 0],
                           off_ref[0] + i * bq, off_ref[1] + ki * bk,
                           causal, scale, prec)
        dv_acc[:] = dv_acc[:] + _dot(p, do, _FF, prec)
        dk_acc[:] = dk_acc[:] + _dot(ds, q, _FF, prec)

    _run_unless_skipped(causal, _q_keep(off_ref, ki, i, bq, bk), compute)

    @pl.when(i == nq - 1)
    def _():
        dk_ref[0] = dk_acc[:] * scale
        dv_ref[0] = dv_acc[:]


def _resolve_blocks(s_q: int, s_kv: int, d: int, block_q, block_k):
    """Pick (bq, bk) tile heights: explicit overrides, else the largest
    default that divides the sequence (floor 128, the MXU edge)."""
    if s_q % 128 != 0 or s_kv % 128 != 0:
        raise ValueError(
            f"flash attention needs S divisible by 128; got ({s_q}, {s_kv}) "
            "(use parallel.dense_attention for short/ragged sequences)"
        )
    bq = block_q or min(_BQ, s_q)
    bk = block_k or min(_BK, s_kv)
    if block_q is None:  # only DEFAULTS shrink to fit; overrides must fit
        while s_q % bq != 0 and bq > 128:
            bq //= 2
    if block_k is None:
        while s_kv % bk != 0 and bk > 128:
            bk //= 2
    if s_q % bq != 0 or s_kv % bk != 0 or bq % 128 != 0 or bk % 128 != 0:
        raise ValueError(
            f"tile heights must be multiples of 128 dividing S; got "
            f"({bq}, {bk}) for S=({s_q}, {s_kv})"
        )
    if d > 256:
        raise ValueError(f"head dim {d} too large for a single VMEM tile")
    return bq, bk


def _grid_spec(grid, in_specs, out_specs, scratch_shapes):
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )


def _augmented_v(v3, d: int, da: int):
    """V with a ones column at `d`, zero-padded to `da` lanes (the fused
    softmax-denominator operand — see `_fwd_kernel_tri`)."""
    bh, s, _ = v3.shape
    parts = [v3, jnp.ones((bh, s, 1), v3.dtype)]
    if da > d + 1:
        parts.append(jnp.zeros((bh, s, da - d - 1), v3.dtype))
    return jnp.concatenate(parts, axis=-1)


def _prescale_q(q3, scale: float):
    """Q pre-scaled into the base-2 score domain (one f32 multiply in
    HBM, so bf16 inputs round once rather than per tile)."""
    return (q3.astype(jnp.float32) * (scale * _LOG2E)).astype(q3.dtype)


def _fwd_tri(q3, k3, v3, scale: float, vma, prec, bq: int, cast16: bool):
    """Aligned-causal forward on the triangular pair grid."""
    bh, s_q, d = q3.shape
    nq = s_q // bq
    # fused softmax denominator: only where the wider PV dot is free —
    # the single-pass bf16 probability dot (cast16) with D below the next
    # 128-lane boundary (see the kernel docstring). bf16 inputs at
    # 'highest' precision keep f32 probabilities, so they take the plain
    # l-scratch path like f32 — the fused dot would pay the multi-pass
    # wider-N cost there.
    fuse_l = cast16 and d % 128 != 0
    da = ((d + 1) + 127) // 128 * 128 if fuse_l else d
    itab, jtab = _tri_tables_qmajor(nq)
    qspec = pl.BlockSpec((1, bq, d), lambda b, p, it, jt: (b, it[p], 0))
    kspec = pl.BlockSpec((1, bq, d), lambda b, p, it, jt: (b, jt[p], 0))
    vspec = pl.BlockSpec((1, bq, da), lambda b, p, it, jt: (b, jt[p], 0))
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel_tri, bq=bq, d=d, cast16=cast16,
                          fuse_l=fuse_l, prec=prec),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, itab.shape[0]),
            in_specs=[qspec, kspec, vspec],
            out_specs=[
                qspec,
                pl.BlockSpec((1, bq, 1), lambda b, p, it, jt: (b, it[p], 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, da), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_q, d), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((bh, s_q, 1), jnp.float32, vma=vma),
        ],
        interpret=_interpret(),
    )(jnp.asarray(itab), jnp.asarray(jtab), _prescale_q(q3, scale), k3,
      _augmented_v(v3, d, da) if fuse_l else v3)
    return o, lse


def _fwd(q3, k3, v3, off, causal: bool, scale: float, vma=None, prec=_HI,
         aligned: bool = False, bq: int = _BQ, bk: int = _BK,
         cast16: bool = False):
    bh, s_q, d = q3.shape
    s_kv = k3.shape[1]
    if causal and aligned and s_q == s_kv and bq == bk:
        return _fwd_tri(q3, k3, v3, scale, vma, prec, bq, cast16)
    nq, nkv = s_q // bq, s_kv // bk
    qspec = pl.BlockSpec((1, bq, d), lambda b, i, j, off: (b, i, 0))
    kvdx = (
        (lambda b, i, j, off: (b, _kv_clamp(off, i, j, nkv, bq, bk), 0))
        if causal
        else (lambda b, i, j, off: (b, j, 0))
    )
    kvspec = pl.BlockSpec((1, bk, d), kvdx)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, nkv=nkv, causal=causal, scale=scale,
                          prec=prec, bq=bq, bk=bk),
        grid_spec=_grid_spec(
            (bh, nq, nkv),
            [qspec, kvspec, kvspec],
            [qspec, pl.BlockSpec((1, bq, 1), lambda b, i, j, off: (b, i, 0))],
            [
                pltpu.VMEM((bq, d), jnp.float32),    # o accumulator
                pltpu.VMEM((bq, 128), jnp.float32),  # running max (col 0)
                pltpu.VMEM((bq, 128), jnp.float32),  # running sum-exp (col 0)
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_q, d), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((bh, s_q, 1), jnp.float32, vma=vma),
        ],
        interpret=_interpret(),
    )(off, q3, k3, v3)
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _flash3(q3, k3, v3, off, causal: bool, scale: float, vma=None, prec=_HI,
            aligned: bool = False, bq: int = _BQ, bk: int = _BK,
            cast16: bool = False):
    return _fwd(q3, k3, v3, off, causal, scale, vma, prec, aligned, bq, bk,
                cast16)


def _flash3_fwd(q3, k3, v3, off, causal, scale, vma, prec, aligned, bq, bk,
                cast16):
    o, lse = _fwd(q3, k3, v3, off, causal, scale, vma, prec, aligned, bq, bk,
                  cast16)
    return (o, lse), (q3, k3, v3, off, o, lse)


def _bwd_tri(q3, k3, v3, do, lse, delta, scale: float, vma, prec, bq: int,
             cast16: bool):
    """Aligned-causal backward on the triangular pair grids."""
    bh, s_q, d = q3.shape
    nq = s_q // bq
    q3s = _prescale_q(q3, scale)  # kernels recompute base-2 scores
    if cast16:
        # one HBM-level cast instead of per-tile dtype promotions: with
        # bf16 residuals, a f32 dO tile would force _dot to promote the
        # V/P sides back to f32 inside every recompute tile (measured:
        # the whole bf16 backward advantage disappeared into those casts)
        do = do.astype(jnp.bfloat16)

    itab, jtab = _tri_tables_qmajor(nq)
    qspec = pl.BlockSpec((1, bq, d), lambda b, p, it, jt: (b, it[p], 0))
    q1spec = pl.BlockSpec((1, bq, 1), lambda b, p, it, jt: (b, it[p], 0))
    kvspec = pl.BlockSpec((1, bq, d), lambda b, p, it, jt: (b, jt[p], 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_tri, bq=bq, scale=scale,
                          cast16=cast16, prec=prec),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, itab.shape[0]),
            in_specs=[qspec, kvspec, kvspec, qspec, q1spec, q1spec],
            out_specs=qspec,
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, s_q, d), jnp.float32, vma=vma),
        interpret=_interpret(),
    )(jnp.asarray(itab), jnp.asarray(jtab), q3s, k3, v3, do, lse, delta)

    jtab2, itab2 = _tri_tables_kmajor(nq)
    kspec = pl.BlockSpec((1, bq, d), lambda b, p, jt, it: (b, jt[p], 0))
    qstream = pl.BlockSpec((1, bq, d), lambda b, p, jt, it: (b, it[p], 0))
    q1stream = pl.BlockSpec((1, bq, 1), lambda b, p, jt, it: (b, it[p], 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_tri, nq=nq, bq=bq, cast16=cast16,
                          prec=prec),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, jtab2.shape[0]),
            in_specs=[qstream, kspec, kspec, qstream, q1stream, q1stream],
            out_specs=[kspec, kspec],
            scratch_shapes=[
                pltpu.VMEM((bq, d), jnp.float32),
                pltpu.VMEM((bq, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_q, d), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((bh, s_q, d), jnp.float32, vma=vma),
        ],
        interpret=_interpret(),
    )(jnp.asarray(jtab2), jnp.asarray(itab2), q3s, k3, v3, do, lse, delta)
    return dq, dk, dv


def _flash3_bwd(causal, scale, vma, prec, aligned, bq, bk, cast16, res, cts):
    q3, k3, v3, off, o, lse = res
    do, dlse = cts
    bh, s_q, d = q3.shape
    s_kv = k3.shape[1]
    nq, nkv = s_q // bq, s_kv // bk
    do = do.astype(jnp.float32)
    # d lse/d scores is the softmax P itself, so the lse cotangent enters
    # dS = P (dP - delta) as a shift of delta: delta = rowsum(dO*O) - dlse
    delta = jnp.sum(do * o, axis=-1, keepdims=True) - dlse.astype(jnp.float32)

    if causal and aligned and s_q == s_kv and bq == bk:
        dq, dk, dv = _bwd_tri(q3, k3, v3, do, lse, delta, scale, vma, prec,
                              bq, cast16)
        doff = jax.custom_derivatives.zero_from_primal(off)
        return dq.astype(q3.dtype), dk.astype(k3.dtype), dv.astype(v3.dtype), doff

    # dq: outer = Q blocks, streamed = KV blocks
    qspec = pl.BlockSpec((1, bq, d), lambda b, i, j, off: (b, i, 0))
    q1spec = pl.BlockSpec((1, bq, 1), lambda b, i, j, off: (b, i, 0))
    kvdx = (
        (lambda b, i, j, off: (b, _kv_clamp(off, i, j, nkv, bq, bk), 0))
        if causal
        else (lambda b, i, j, off: (b, j, 0))
    )
    kvspec = pl.BlockSpec((1, bk, d), kvdx)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, nkv=nkv, causal=causal, scale=scale,
                          prec=prec, bq=bq, bk=bk),
        grid_spec=_grid_spec(
            (bh, nq, nkv),
            [qspec, kvspec, kvspec, qspec, q1spec, q1spec],
            qspec,
            [pltpu.VMEM((bq, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, s_q, d), jnp.float32, vma=vma),
        interpret=_interpret(),
    )(off, q3, k3, v3, do, lse, delta)

    # dk/dv: outer = KV blocks, streamed = Q blocks (causal: Q blocks
    # before the KV block see none of it — clamp onto the first useful)
    kspec = pl.BlockSpec((1, bk, d), lambda b, j, i, off: (b, j, 0))
    qdx = (
        (lambda b, j, i, off: (b, _q_clamp(off, j, i, nq, bq, bk), 0))
        if causal
        else (lambda b, j, i, off: (b, i, 0))
    )
    qstream = pl.BlockSpec((1, bq, d), qdx)
    q1stream = pl.BlockSpec((1, bq, 1), qdx)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, nq=nq, causal=causal, scale=scale,
                          prec=prec, bq=bq, bk=bk),
        grid_spec=_grid_spec(
            (bh, nkv, nq),
            [qstream, kspec, kspec, qstream, q1stream, q1stream],
            [kspec, kspec],
            [
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_kv, d), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((bh, s_kv, d), jnp.float32, vma=vma),
        ],
        interpret=_interpret(),
    )(off, q3, k3, v3, do, lse, delta)

    doff = jax.custom_derivatives.zero_from_primal(off)
    return dq.astype(q3.dtype), dk.astype(k3.dtype), dv.astype(v3.dtype), doff


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


def _to3(x, b, h, keep_bf16: bool = False):
    s = x.shape[1]
    dt = (
        jnp.bfloat16
        if keep_bf16 and x.dtype == jnp.bfloat16
        else jnp.float32
    )
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, -1).astype(dt)


_PRECS = {
    "highest": jax.lax.Precision.HIGHEST,
    "default": jax.lax.Precision.DEFAULT,
}


def _prec_of(precision: str):
    try:
        return _PRECS[precision]
    except KeyError:
        raise ValueError(
            f"precision must be one of {sorted(_PRECS)}, got {precision!r}"
        ) from None


def _static_scale(sm_scale, d: int) -> float:
    if isinstance(sm_scale, jax.core.Tracer):
        raise TypeError(
            "sm_scale must be static (it is baked into the kernel); close "
            "over it rather than passing a traced value"
        )
    return float(sm_scale) if sm_scale is not None else 1.0 / (float(d) ** 0.5)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    precision: str = "highest",
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jnp.ndarray:
    """Exact attention, blockwise in VMEM. q,k,v: [B, S, H, D] -> same.

    Drop-in for `parallel.dense_attention` at long S (S must be a
    multiple of 128): no [S, S] score matrix ever exists in HBM, nothing
    whole-sequence-resident ever sits in VMEM, forward or backward.

    `precision` sets the MXU pass count of every tile dot: 'highest'
    (default) runs full-f32 passes; 'default' runs single bf16 passes —
    several times faster on the MXU and the standard choice for
    long-context training, with softmax statistics and accumulators
    still f32. Accuracy: the ~1e-6 agreement with the f32 dense
    reference holds for F32 INPUTS at 'highest' only. BF16 inputs are
    input-rounding-limited at ANY precision setting: q/k/v already
    carry bf16's ~8-bit mantissa, so expect ~2e-2 against an f32
    reference whatever the MXU pass count — raising `precision` on bf16
    inputs buys back only the in-kernel rounding, not the input
    quantization (tests/test_flash.py tolerances).

    `block_q`/`block_k` override the VMEM tile heights (multiples of 128
    dividing S; defaults swept on a v5e — see `_BQ`). Causal uses
    equal tiles (the triangular grid pairs them).
    """
    b, s, h, d = q.shape
    if block_q is None and block_k is None and (
        q.dtype == jnp.bfloat16 and precision == "default" and causal
        and d <= 64 and s % 1024 == 0
    ):
        # measured best tile for the configuration the sweep actually ran
        # (flash_bf16_tiles.json round 5: causal fwd+bwd, bf16 tiles at
        # 'default' precision, reference-scale head dims — 1024 beats 512
        # by ~15% at S=4k and ~33% at S=8k; bf16 halves the tile VMEM
        # that made 1024 uncompilable in round 4). Unmeasured shapes
        # (f32, 'highest' — whose f32 probability tiles carry the VMEM
        # class that fails compile at S=8k f32 — and the non-causal
        # rectangular kernels) keep the 512 default.
        block_q = block_k = 1024
    bq, bk = _resolve_blocks(s, s, d, block_q, block_k)
    if causal:
        bk = bq = min(bq, bk)  # triangular grid pairs equal tiles
    scale = _static_scale(sm_scale, d)
    off = jnp.zeros((2,), jnp.int32)
    # bf16 inputs stay bf16 through the aligned kernels (half the tile
    # DMA; accumulators and softmax statistics are f32 regardless), and
    # at 'default' precision the probability tiles feed the MXU in bf16
    # too — the same rounding class as XLA's dense softmax@V on that
    # path (measured ~10% of the step, benchmarks/flash_attrib_probe.json)
    cast16 = q.dtype == jnp.bfloat16 and precision == "default"
    # offsets are statically zero: causal takes the triangular grid
    o, _ = _flash3(_to3(q, b, h, True), _to3(k, b, h, True),
                   _to3(v, b, h, True),
                   off, causal, scale, None, _prec_of(precision), True,
                   bq, bk, cast16)
    return o.reshape(b, h, s, d).transpose(0, 2, 1, 3).astype(q.dtype)


def flash_block(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_offset,
    k_offset,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    vma=None,
    precision: str = "highest",
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One (Q block, KV block) partial attention with global positions.

    q: [B, Sq, H, D] at global positions `q_offset + [0, Sq)`;
    k, v: [B, Skv, H, D] at `k_offset + [0, Skv)` (offsets may be traced,
    device-varying scalars — e.g. `ring_attention`'s block origins).
    Returns `(o, lse)`, both f32 and both in head-major layout — o
    `[B, H, Sq, D]`, lse `[B, H, Sq]` — which is what an online-softmax
    merge accumulates in (and the kernel's native layout: no transposes
    on the fold path). o is this block's normalized attention output,
    lse its per-row logsumexp — the pair needed to fold partial blocks
    exactly
    (lse = -1e30 and o = 0 for causal rows that see no key in this
    block). Differentiable in q, k, v — including through uses of lse.
    """
    b, s_q, h, d = q.shape
    s_kv = k.shape[1]
    bq, bk = _resolve_blocks(s_q, s_kv, d, block_q, block_k)
    scale = _static_scale(sm_scale, d)
    off = jnp.stack(
        [jnp.asarray(q_offset, jnp.int32), jnp.asarray(k_offset, jnp.int32)]
    )
    o, lse = _flash3(_to3(q, b, h), _to3(k, b, h), _to3(v, b, h),
                     off, causal, scale,
                     frozenset(vma) if vma else None, _prec_of(precision),
                     False, bq, bk)
    # both outputs stay f32 regardless of input dtype: partials feed an
    # online-softmax accumulation (ring.py fold_flash) and rounding them
    # before the merge would waste the f32 carry
    return o.reshape(b, h, s_q, d), lse.reshape(b, h, s_q)
