"""Ring attention: sequence/context parallelism over a mesh axis.

The reference has no sequence dimension anywhere (CNNs on 32x32 images;
SURVEY.md §2.3 records SP/CP as absent), but long-context support is a
first-class capability of this framework, not an afterthought: the
transformer family (models/transformer.py) trains under the same
federated/consensus engine, and when a sequence no longer fits one device
it is sharded over a `seq` mesh axis and attention runs as a RING —
the TPU-native equivalent of Ring Attention with Blockwise Transformers
(Liu et al., 2023):

* each device holds a `[B, S/P, H, D]` shard of Q, K, V;
* P ring steps: attend Q_local against the resident K/V block while
  `lax.ppermute` rotates the K/V blocks one neighbour around the axis —
  compute and ICI transfer overlap, and no device ever materializes the
  full `[S, S]` score matrix or the full K/V;
* softmax is accumulated ONLINE (flash-attention style running max /
  sum-exp / output triple), so the result is exact dense attention, not
  an approximation.

Causality is handled with global position ids derived from each block's
ring origin, so the same code path serves encoder (bidirectional) and
decoder (causal) stacks.

`dense_attention` is the single-device reference implementation used by
the transformer models when the sequence axis is unsharded; the ring path
is numerically identical to it (tests/test_ring.py, 8-device CPU mesh).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

SEQ_AXIS = "seq"

_NEG_BIG = -1e30  # large-negative instead of -inf: keeps exp() at exact 0
# without NaNs from (-inf) - (-inf) in fully-masked blocks


def mark_varying(x, axis_name):
    """Mark `x` as varying over `axis_name`.

    Used for constant-initialized accumulators that a loop will overwrite
    with varying values, and for replicated operands (e.g. the consensus
    vector z) that are closed over by a `lax.while_loop` — JAX's vma
    fixpoint re-applies recorded pvary insertions when loop carries get
    promoted, which errors on an unvarying closed-over constant.
    """
    return lax.pcast(x, (axis_name,), to="varying")


_pvary = mark_varying  # internal alias used below


def dense_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    sm_scale: Optional[float] = None,
) -> jnp.ndarray:
    """Reference single-device attention. q,k,v: [B, S, H, D] -> [B, S, H, D]."""
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / jnp.sqrt(float(d))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        qi = jnp.arange(s_q)[:, None]
        ki = jnp.arange(s_k)[None, :]
        scores = jnp.where(ki <= qi, scores, _NEG_BIG)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = SEQ_AXIS,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    use_flash: bool = False,
    precision: Optional[str] = None,
) -> jnp.ndarray:
    """Exact attention over a sequence sharded on `axis_name`.

    Must be called inside `shard_map`/`pmap` with `axis_name` bound.
    q, k, v: `[B, S_local, H, D]` shards (sequence axis 1); returns the
    `[B, S_local, H, D]` output shard. One `ppermute` per ring step moves
    each K/V block to the next neighbour, so the interconnect carries
    exactly `(P-1)/P` of K and V once — the minimum for exact attention —
    and every step's compute overlaps the next block's transfer.

    `use_flash=True` swaps each ring step's block compute from the dense
    einsum (materializes the local `[S_q, S_kv]` score tile in HBM) to the
    Pallas flash kernel (`ops/flash_attention.flash_block`): the kernel
    streams 128-row tiles through VMEM and returns this block's
    `(output, logsumexp)` partial, which the same online-softmax merge
    folds across ring steps. Two-level streaming — ring over ICI, tiles
    within the device — so LOCAL shard length is no longer score-matrix-
    bound either (requires S_local % 128 == 0).

    `precision` ('highest' | 'default' | None) applies to both folds:
    the flash kernels' MXU pass count (None = their 'highest' default,
    see `ops.flash_attention.flash_attention`) and the dense fold's
    einsum precision (None = ambient default). In Pallas interpret mode
    (CPU tests) the enclosing shard_map needs `check_vma=False`: the
    interpreter cannot propagate varying-mesh-axis metadata through its
    internal slicing (compiled TPU kernels carry it via the out_shape
    `vma` annotation).
    """
    if precision not in (None, "highest", "default"):
        raise ValueError(
            f"precision must be None, 'highest' or 'default', got "
            f"{precision!r}"
        )
    p = lax.psum(1, axis_name)  # ring size (number of sequence shards)
    my = lax.axis_index(axis_name)
    b, s_q, h, d = q.shape
    s_kv = k.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / jnp.sqrt(float(d))

    q_pos = my * s_q + jnp.arange(s_q)  # global positions of local queries
    # the precision knob applies to BOTH folds: kernel MXU passes for
    # flash, einsum precision for dense (None = leave each at its default)
    prec = None if precision is None else (
        jax.lax.Precision.HIGHEST if precision == "highest"
        else jax.lax.Precision.DEFAULT
    )

    def fold_dense(acc, k_blk, v_blk, i):
        """Fold one K/V block (ring step i) into the online softmax."""
        o, m, l = acc
        # the resident block started on device (my - i) mod p
        src = (my - i) % p
        k_pos = src * s_kv + jnp.arange(s_kv)

        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k_blk, precision=prec
        ) * scale
        if causal:
            keep = (k_pos[None, :] <= q_pos[:, None])[None, None]
            scores = jnp.where(keep, scores, _NEG_BIG)

        blk_max = jnp.max(scores, axis=-1)  # [B,H,Sq]
        m_new = jnp.maximum(m, blk_max)
        probs = jnp.exp(scores - m_new[..., None])  # [B,H,Sq,Skv]
        # rows with no visible key in THIS block (blk_max == _NEG_BIG)
        # must contribute zero weight even if the accumulator is still
        # empty (m == -1e30, where exp(scores - m_new) == exp(0) == 1
        # would add phantom weight) — same order-independence guard as
        # fold_flash's beta
        probs = jnp.where((blk_max > _NEG_BIG * 0.5)[..., None], probs, 0.0)
        corr = jnp.exp(m - m_new)  # [B,H,Sq]
        l_new = l * corr + jnp.sum(probs, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", probs, v_blk, precision=prec
        )
        return o_new, m_new, l_new

    def fold_flash(acc, k_blk, v_blk, i):
        """Fold one K/V block's `flash_block` partial (Pallas kernel):

            m' = max(m, lse);  l' = l*e^{m-m'} + e^{lse-m'}
            o' = o*e^{m-m'} + o_blk*e^{lse-m'}      (o_blk normalized)

        The merge is order-independent: a fully-masked partial
        (lse = -1e30) gets its block weight forced to exactly 0, so it
        contributes nothing even if it meets a still-empty accumulator
        (m = -1e30), where exp(lse - m_new) would otherwise be exp(0) = 1.
        """
        from federated_pytorch_test_tpu.ops.flash_attention import flash_block

        o, m, l = acc
        src = (my - i) % p  # ring origin of the resident block
        o_blk, lse = flash_block(
            q, k_blk, v_blk, my * s_q, src * s_kv, causal=causal,
            sm_scale=sm_scale, vma=(axis_name,),
            precision=precision or "highest",
        )  # o_blk [B,H,Sq,D]: already the accumulator layout
        m_new = jnp.maximum(m, lse)
        alpha = jnp.exp(m - m_new)
        # zero (not exp(0)=1) weight for masked partials: lse = _NEG_BIG
        # means "no visible keys in this block", regardless of m_new
        beta = jnp.where(lse > _NEG_BIG * 0.5, jnp.exp(lse - m_new), 0.0)
        o_new = o * alpha[..., None] + o_blk.astype(o.dtype) * beta[..., None]
        return o_new, m_new, l * alpha + beta

    fold = fold_flash if use_flash else fold_dense
    acc_dtype = jnp.float32 if use_flash else q.dtype
    perm = [(j, (j + 1) % p) for j in range(p)]

    def step(i, carry):
        o, m, l, k_blk, v_blk = carry
        # rotate K/V to the next neighbour, then fold the received block —
        # p-1 permutes total, so the interconnect carries exactly (P-1)/P
        # of K and V once
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        o, m, l = fold((o, m, l), k_blk, v_blk, i)
        return o, m, l, k_blk, v_blk

    o0 = jnp.zeros((b, h, s_q, d), acc_dtype)
    m0 = jnp.full((b, h, s_q), _NEG_BIG, acc_dtype)
    l0 = jnp.zeros((b, h, s_q), acc_dtype)
    # constant-initialized carries are 'unvarying' over the mesh axis while
    # the loop writes varying values into them; mark them varying up front
    o0, m0, l0 = (_pvary(x, axis_name) for x in (o0, m0, l0))
    # ring step 0: the device's own resident block, no transfer needed
    acc = fold((o0, m0, l0), k, v, 0)
    o, m, l, _, _ = lax.fori_loop(1, p, step, acc + (k, v))

    # causal rows always see at least their own position, non-causal rows
    # see everything — l == 0 cannot happen; the maximum is pure paranoia
    o = o / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(o, (0, 2, 1, 3)).astype(q.dtype)  # [B, Sq, H, D]


def seq_shard(x: jnp.ndarray, axis_name: str = SEQ_AXIS):
    """Inside shard_map: global [B, S, ...] -> this device's [B, S/P, ...]."""
    p = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    s = x.shape[1]
    if s % p != 0:
        raise ValueError(f"sequence length {s} not divisible by ring size {p}")
    blk = s // p
    return lax.dynamic_slice_in_dim(x, my * blk, blk, axis=1)


def seq_unshard(x_local: jnp.ndarray, axis_name: str = SEQ_AXIS):
    """Inside shard_map: [B, S/P, ...] shard -> replicated [B, S, ...]."""
    gathered = lax.all_gather(x_local, axis_name, axis=1, tiled=True)
    return gathered
