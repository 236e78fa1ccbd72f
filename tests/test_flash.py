"""Flash-attention kernel tests: exactness vs the dense reference.

The kernels run in Pallas interpret mode on the CPU test platform — the
same code path the TPU compiles. Forward AND backward (custom flash-2
VJP) must match `parallel.dense_attention`'s values and gradients.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from federated_pytorch_test_tpu.ops.flash_attention import (
    flash_attention,
    flash_block,
)
from federated_pytorch_test_tpu.parallel import dense_attention

pytestmark = pytest.mark.slow  # heavy tier (jit-compile dominated)


def _qkv(b=2, s=256, h=2, d=32, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_dense(causal):
    q, k, v = _qkv()
    ref = dense_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_dense(causal):
    q, k, v = _qkv(b=1, s=128, h=2, d=16, seed=1)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
            err_msg=f"d{name}",
        )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_multiblock(causal):
    # s=384 with forced 128-row tiles => 3 tiles per axis: exercises
    # cross-block accumulation and BOTH causal skip bounds in the backward
    # kernels. The explicit block_q/block_k matter: the 512 default would
    # resolve to ONE 384-row tile and the multi-tile init/flush paths of
    # the triangular dq/dkv kernels would never run.
    q, k, v = _qkv(b=1, s=384, h=1, d=16, seed=5)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
            ** 2
        )

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4,
            err_msg=f"d{name}",
        )


def test_flash_2048_tokens_match_dense():
    # nothing is whole-sequence-resident in VMEM (S is HBM-bound only);
    # 16x16 streamed-grid blocks, compared in full against dense
    q, k, v = _qkv(b=1, s=2048, h=1, d=16, seed=6)
    ref = dense_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-5, atol=3e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_multiblock_default_tiles(causal):
    # s=1024 with the DEFAULT 512 tiles => 2x2 triangular tile grid:
    # gradient coverage for the production tile shape (the forced-128
    # test above covers 3x3; the s=2048 test is forward-only)
    q, k, v = _qkv(b=1, s=1024, h=1, d=16, seed=11)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4,
            err_msg=f"d{name}",
        )


def test_flash_custom_scale_and_jit():
    q, k, v = _qkv(b=1, s=128, h=1, d=64, seed=2)
    ref = dense_attention(q, k, v, sm_scale=0.07)
    out = jax.jit(lambda *a: flash_attention(*a, sm_scale=0.07))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6)
    # static numpy scalars are fine; only traced values are rejected
    out = flash_attention(q, k, v, sm_scale=np.float32(0.07))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6)
    with pytest.raises(TypeError, match="static"):
        jax.jit(lambda q, k, v, sc: flash_attention(q, k, v, sm_scale=sc))(
            q, k, v, jnp.float32(0.07)
        )


def test_flash_default_precision_mode():
    # precision='default' (single bf16 MXU passes) must stay close to the
    # f32 reference — loose tolerance, it exists to be fast, not exact —
    # and gradients must flow; bogus precision names must be rejected
    q, k, v = _qkv(b=1, s=256, h=2, d=32, seed=10)
    ref = dense_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, precision="default")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-2, atol=2e-2)

    g = jax.grad(
        lambda q: jnp.sum(
            flash_attention(q, k, v, causal=True, precision="default") ** 2
        )
    )(q)
    assert np.isfinite(np.asarray(g)).all()

    with pytest.raises(ValueError, match="precision"):
        flash_attention(q, k, v, precision="fast")


def test_auto_attn_dispatch_matches_measured_crossover():
    # attn_impl='auto' picks dense below the flash crossover the old
    # runtime's sweeps set (S>=1024 at 'default' precision, S>=2048 at
    # 'highest'; not measured on this installation) and flash above
    # it. Bit-equality against
    # the explicit impls proves which core ran (same params, same ops).
    from federated_pytorch_test_tpu.models.transformer import (
        MultiHeadAttention,
    )

    rng = np.random.default_rng(12)

    def outs(s, prec):
        x = jnp.asarray(rng.normal(size=(1, s, 32)), jnp.float32)
        mods = {
            name: MultiHeadAttention(
                32, 2, attn_impl=name, causal=True, attn_precision=prec
            )
            for name in ("auto", "dense", "flash")
        }
        params = mods["dense"].init(jax.random.PRNGKey(0), x)
        return {n: np.asarray(m.apply(params, x)) for n, m in mods.items()}

    o = outs(256, None)  # f32, short: auto must BE dense
    np.testing.assert_array_equal(o["auto"], o["dense"])
    o = outs(2048, "default")  # past the crossover: flash
    np.testing.assert_array_equal(o["auto"], o["flash"])
    assert np.abs(o["flash"] - o["dense"]).max() > 0.0  # distinct cores
    o = outs(1024, "default")  # 'default' crossover moved here (1.55x)
    np.testing.assert_array_equal(o["auto"], o["flash"])
    o = outs(1024, None)  # 'highest' at S=1024: dense still wins (0.72x)
    np.testing.assert_array_equal(o["auto"], o["dense"])


def test_flash_rejects_ragged_seq():
    q, k, v = _qkv(s=100)
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k, v)


def test_flash_in_transformer_lm_matches_dense():
    # the model-family wiring: TransformerLM(attn_impl='flash') == dense
    from federated_pytorch_test_tpu.models import TransformerLM

    rng = np.random.default_rng(4)
    tokens = jnp.asarray(rng.integers(0, 64, size=(2, 128)), jnp.int32)
    dense_lm = TransformerLM(attn_impl="dense", dim=32, num_heads=2, vocab=64)
    flash_lm = TransformerLM(attn_impl="flash", dim=32, num_heads=2, vocab=64)
    params = dense_lm.init(jax.random.PRNGKey(0), tokens)
    ref = dense_lm.apply(params, tokens)
    out = flash_lm.apply(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)

    # and gradients flow through the custom VJP inside the full model
    def loss(p, lm):
        return jnp.sum(lm.apply(p, tokens) ** 2)

    gf = jax.grad(lambda p: loss(p, flash_lm))(params)
    gd = jax.grad(lambda p: loss(p, dense_lm))(params)
    for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4)


def test_flash_block_offsets_and_merge():
    # flash_block with global offsets is the ring's per-step partial:
    # folding the two partials of a split K/V axis with the online-softmax
    # merge must reproduce full causal attention over S=256 exactly
    q, k, v = _qkv(b=1, s=256, h=2, d=16, seed=7)
    ref = dense_attention(q, k, v, causal=True)

    qb = q[:, 128:, :, :]  # rows 128..255
    o_parts, lse_parts = [], []
    for j in (0, 1):
        kb = k[:, 128 * j : 128 * (j + 1), :, :]
        vb = v[:, 128 * j : 128 * (j + 1), :, :]
        o, lse = flash_block(
            qb, kb, vb, jnp.int32(128), jnp.int32(128 * j), causal=True
        )  # o [B,H,Sq,D]: kernel-native accumulator layout
        o_parts.append(o)
        lse_parts.append(lse)
    m = jnp.maximum(lse_parts[0], lse_parts[1])
    w0, w1 = (jnp.exp(l - m) for l in lse_parts)
    merged = (o_parts[0] * w0[..., None] + o_parts[1] * w1[..., None]) / (
        w0 + w1
    )[..., None]
    merged = jnp.transpose(merged, (0, 2, 1, 3))
    np.testing.assert_allclose(
        np.asarray(merged), np.asarray(ref)[:, 128:], rtol=2e-5, atol=2e-6
    )

    # a block entirely in the causal future: zero output, -BIG lse
    o, lse = flash_block(
        q[:, :128], k[:, 128:], v[:, 128:], jnp.int32(0), jnp.int32(128),
        causal=True,
    )
    assert float(jnp.abs(o).max()) == 0.0
    assert float(lse.max()) <= -1e29


def test_flash_block_unaligned_offsets():
    # k_off - q_off not a multiple of the tile height: a KEPT tile then
    # contains rows with no visible key at all. Those rows must emit
    # o = 0 / lse = -BIG (and zero gradients), and the visible rows must
    # stay exact — the regression case for the in-tile all-masked-row
    # guard in the forward and backward kernels.
    q, k, v = _qkv(b=1, s=128, h=1, d=16, seed=9)
    off = 64
    o, lse = flash_block(q, k, v, jnp.int32(0), jnp.int32(off), causal=True)
    # o is [B, H, Sq, D] (head-major, the merge-accumulator layout)
    assert float(jnp.abs(o[:, :, :off]).max()) == 0.0
    assert float(lse[:, :, :off].max()) <= -1e29
    # visible rows r >= off see keys with kpos = off + col <= r
    qn, kn, vn = (np.asarray(x)[0, :, 0, :] for x in (q, k, v))
    for row in (off, 100, 127):
        sc = (qn[row] @ kn[: row - off + 1].T) / np.sqrt(16.0)
        pr = np.exp(sc - sc.max())
        pr /= pr.sum()
        np.testing.assert_allclose(
            np.asarray(o)[0, 0, row, :], pr @ vn[: row - off + 1],
            rtol=3e-5, atol=3e-6, err_msg=f"row {row}",
        )

    # gradients: masked rows contribute nothing, so dq there is 0 and
    # the total grads equal those of a loss over visible rows only
    def loss(q, k, v):
        o, _ = flash_block(q, k, v, jnp.int32(0), jnp.int32(off), causal=True)
        return jnp.sum(o**2)

    dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert float(jnp.abs(dq[:, :off]).max()) == 0.0

    def loss_dense(q, k, v):
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(16.0)
        qi = jnp.arange(128)[:, None]
        ki = off + jnp.arange(128)[None, :]
        sc = jnp.where((ki <= qi)[None, None], sc, -1e30)
        o = jnp.einsum("bhqk,bkhd->bhqd", jax.nn.softmax(sc, axis=-1), v)
        return jnp.sum(o[:, :, off:] ** 2)

    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip((dq, dk, dv), gd, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4,
            err_msg=f"d{name}",
        )


def test_flash_block_lse_gradient():
    # d lse/d scores == softmax: the custom VJP folds the lse cotangent
    # into delta. Check grads of a loss that uses BOTH outputs against
    # autodiff through an explicit dense (o, lse) computation.
    q, k, v = _qkv(b=1, s=128, h=1, d=16, seed=8)

    def loss_flash(q, k, v):
        o, lse = flash_block(q, k, v, jnp.int32(0), jnp.int32(0), causal=True)
        return jnp.sum(o**2) + jnp.sum(jnp.sin(lse))

    def loss_dense(q, k, v):
        scale = 1.0 / np.sqrt(16.0)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        qi = jnp.arange(128)[:, None]
        ki = jnp.arange(128)[None, :]
        sc = jnp.where((ki <= qi)[None, None], sc, -1e30)
        lse = jax.scipy.special.logsumexp(sc, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bhqd", jax.nn.softmax(sc, axis=-1), v)
        return jnp.sum(o**2) + jnp.sum(jnp.sin(lse))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4,
            err_msg=f"d{name}",
        )


def test_flash_long_context_values_stay_exact():
    # 1024 tokens, causal — the regime dense attention exists to avoid;
    # spot-check rows against a numpy softmax computed directly
    q, k, v = _qkv(b=1, s=1024, h=1, d=16, seed=3)
    out = flash_attention(q, k, v, causal=True)
    qn, kn, vn = (np.asarray(x)[0, :, 0, :] for x in (q, k, v))
    for row in (0, 511, 1023):
        sc = (qn[row] @ kn[: row + 1].T) / np.sqrt(16.0)
        p = np.exp(sc - sc.max())
        p /= p.sum()
        np.testing.assert_allclose(
            np.asarray(out)[0, row, 0, :], p @ vn[: row + 1],
            rtol=3e-5, atol=3e-6, err_msg=f"row {row}",
        )


def test_flash_bf16_inputs_match_dense():
    # the round-5 bf16-resident path end to end: bf16 tiles stay bf16
    # through the kernels (keep_bf16), the probability tile feeds the MXU
    # in bf16 at 'default' precision (cast16), the fused softmax
    # denominator rides the augmented-V dot (fuse_l), and s % 1024 == 0
    # picks the measured 1024 default tile. Values and gradients must
    # stay within bf16 rounding class of the f32 dense reference.
    q, k, v = _qkv(b=1, s=1024, h=2, d=16, seed=13)
    q16, k16, v16 = (x.astype(jnp.bfloat16) for x in (q, k, v))
    ref = dense_attention(q, k, v, causal=True)
    out = flash_attention(q16, k16, v16, causal=True, precision="default")
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=0.06, atol=0.03
    )

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, precision="default")
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q16, k16, v16)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        assert a.dtype == jnp.bfloat16, f"d{name} cotangent dtype"
        denom = np.maximum(np.abs(np.asarray(b)), 1.0)
        rel = np.max(np.abs(np.asarray(a, np.float32) - np.asarray(b)) / denom)
        assert rel < 0.08, f"d{name} rel err {rel}"


def test_flash_bf16_highest_precision_keeps_f32_probabilities():
    # bf16 inputs with precision='highest' must NOT take the cast16/fuse_l
    # shortcuts: probabilities stay f32, so values sit much closer to the
    # f32 dense reference than the bf16-rounded default path
    q, k, v = _qkv(b=1, s=256, h=1, d=16, seed=14)
    q16, k16, v16 = (x.astype(jnp.bfloat16) for x in (q, k, v))
    ref = dense_attention(
        q16.astype(jnp.float32), k16.astype(jnp.float32),
        v16.astype(jnp.float32), causal=True,
    )
    out = flash_attention(q16, k16, v16, causal=True, precision="highest")
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=2e-2, atol=8e-3
    )
