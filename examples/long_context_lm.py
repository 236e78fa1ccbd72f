"""Sequence-parallel causal LM: the long-context recipe, end to end.

Shards a context of `SEQ` tokens over every available device as a ring
(`parallel/ring.py`), trains the TransformerLM with the framework's
jitted stochastic L-BFGS on a copy task, and checks the sharded loss
equals the dense one. On a CPU dev box run with a virtual ring:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/long_context_lm.py

On a TPU slice just run it — the ring rides the ICI.

`ATTN_IMPL=ring_flash` swaps each ring step's block compute to the
Pallas flash kernel (two-level streaming; needs SEQ such that every
device's shard is a multiple of 128, e.g. SEQ=1024 on 8 devices):

    ATTN_IMPL=ring_flash SEQ=1024 python examples/long_context_lm.py
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P

from federated_pytorch_test_tpu.models import TransformerLM
from federated_pytorch_test_tpu.optim import LBFGSConfig, lbfgs_init, lbfgs_step
from federated_pytorch_test_tpu.parallel import SEQ_AXIS
from federated_pytorch_test_tpu.partition import flatten_params

SEQ = int(os.environ.get("SEQ", "512"))
STEPS = int(os.environ.get("STEPS", "12"))
VOCAB = 64
ATTN_IMPL = os.environ.get("ATTN_IMPL", "ring")  # 'ring' | 'ring_flash'


def main():
    # 'dense'/'flash' would pass model validation but attend only over
    # each device's local shard inside the seq-axis shard_map — reject
    # them up front instead of failing the parity check obscurely
    assert ATTN_IMPL in ("ring", "ring_flash"), ATTN_IMPL
    devs = jax.devices()
    p = len(devs)
    assert SEQ % p == 0, f"SEQ={SEQ} must be divisible by {p} devices"
    if ATTN_IMPL == "ring_flash":
        assert (SEQ // p) % 128 == 0, (
            f"ring_flash needs 128-multiple shards; SEQ={SEQ} over {p} "
            f"devices gives {SEQ // p}"
        )
    mesh = Mesh(np.asarray(devs), (SEQ_AXIS,))
    print(f"{p}-device sequence ring on {devs[0].platform} ({ATTN_IMPL})")

    # params are attention-impl-agnostic: init the dense twin (ring
    # attention needs the seq axis bound, which only exists in shard_map)
    lm = TransformerLM(attn_impl=ATTN_IMPL, dim=64, num_heads=4, vocab=VOCAB,
                       max_len=SEQ)
    lm_dense = TransformerLM(attn_impl="dense", dim=64, num_heads=4,
                             vocab=VOCAB, max_len=SEQ)
    rng = np.random.default_rng(0)
    seq = jnp.asarray(np.tile(rng.integers(0, VOCAB, size=32), SEQ)[: SEQ + 1],
                      jnp.int32)
    tokens, targets = seq[None, :-1], seq[None, 1:]

    params = lm_dense.init(jax.random.PRNGKey(0), tokens)["params"]
    flat, unravel = flatten_params(params)

    def shard_loss(f, tok_shard, tgt_shard):
        # every device: its token shard, its global positions, ring attn
        my = jax.lax.axis_index(SEQ_AXIS)
        blk = SEQ // p
        pos = (my * blk + jnp.arange(blk))[None, :]
        logits = lm.apply({"params": unravel(f)}, tok_shard, positions=pos)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), tgt_shard
        ).sum()
        return jax.lax.psum(loss, SEQ_AXIS) / SEQ  # global mean

    from federated_pytorch_test_tpu.parallel import shard_map
    sharded = shard_map(
        shard_loss,
        mesh=mesh,
        in_specs=(P(), P(None, SEQ_AXIS), P(None, SEQ_AXIS)),
        out_specs=P(),
        check_vma=False,
    )
    loss_fn = lambda f: sharded(f, tokens, targets)  # noqa: E731

    # the sharded ring loss must equal the dense unsharded loss exactly
    def dense_loss(f):
        logits = lm_dense.apply({"params": unravel(f)}, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), targets
        ).mean()

    ring_l, dense_l = float(loss_fn(flat)), float(dense_loss(flat))
    assert abs(ring_l - dense_l) < 1e-3 * max(1.0, abs(dense_l)), (ring_l, dense_l)
    print(f"ring == dense loss check: {ring_l:.6f} vs {dense_l:.6f}")

    cfg = LBFGSConfig(max_iter=4, history_size=10, line_search=True,
                      batch_mode=True)
    state = lbfgs_init(flat, cfg)
    step = jax.jit(lambda f, s: lbfgs_step(loss_fn, f, s, cfg))

    print(f"loss[0] = {float(loss_fn(flat)):.4f}")
    for i in range(STEPS):
        flat, state, aux = step(flat, state)
    print(f"loss[{STEPS}] = {float(loss_fn(flat)):.4f}  "
          f"(func_evals={int(state.func_evals)})")


if __name__ == "__main__":
    main()
