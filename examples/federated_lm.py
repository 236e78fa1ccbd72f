"""Federated causal-LM training: K transformer clients, one mesh axis.

The capstone composition of the framework's two halves. The reference
trains K CNN clients on disjoint CIFAR shards with partial-parameter
FedAvg (reference src/federated_trio.py); here the SAME recipe — common
init, per-group L-BFGS epochs, masked FedAvg collective, per-client eval
— runs on `TransformerLM` clients over disjoint TOKEN streams:

- each client's corpus is a Markov chain sharing a dominant transition
  (i -> i+1) but with a client-BIASED minor transition (i -> i+2+c), the
  LM analogue of the reference's biased per-client normalization
  (reference src/no_consensus_trio.py:32-50);
- the partition groups are the LM's own (embeddings, each block, head —
  models/transformer.py GROUP_PATHS), so only one group's coordinates
  cross the interconnect per round, exactly the reference's bandwidth
  contract (reference README.md:2);
- every client's stochastic L-BFGS epoch (line-search probes included)
  runs vmapped inside one jitted shard_map over the `clients` mesh axis,
  and the FedAvg z-update is a psum collective (consensus/fedavg.py).

On a CPU dev box:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    JAX_PLATFORMS=cpu python examples/federated_lm.py

On a TPU slice just run it — clients ride the ICI.
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import optax
from federated_pytorch_test_tpu.parallel import shard_map
from jax.sharding import PartitionSpec as P

from federated_pytorch_test_tpu.consensus import FedAvgState, fedavg_round
from federated_pytorch_test_tpu.models import TransformerLM, init_client_params
from federated_pytorch_test_tpu.optim import LBFGSConfig, lbfgs_init, lbfgs_step
from federated_pytorch_test_tpu.parallel import (
    CLIENT_AXIS,
    largest_feasible_mesh,
    shard_clients,
)
from federated_pytorch_test_tpu.partition import flatten_params

K = int(os.environ.get("K", "4"))  # clients
VOCAB = 32
SEQ = int(os.environ.get("SEQ", "32"))
BATCH = 8
N_BATCH = 4  # lockstep minibatches per epoch
NLOOP = int(os.environ.get("NLOOP", "2"))
SEED = 0


def markov_corpus(client: int, n_seq: int, rng: np.random.Generator):
    """Client-biased Markov chains: 85% i->i+1 (shared), 15% i->i+2+c."""
    minor = (2 + client) % VOCAB
    seqs = np.empty((n_seq, SEQ + 1), np.int64)
    for j in range(n_seq):
        tok = rng.integers(0, VOCAB)
        for t in range(SEQ + 1):
            seqs[j, t] = tok
            step = 1 if rng.random() < 0.85 else minor
            tok = (tok + step) % VOCAB
    return seqs


def main():
    mesh = largest_feasible_mesh(K)
    d = mesh.devices.size
    print(f"{K} LM clients on a {d}-device mesh "
          f"({mesh.devices.flat[0].platform}, {K // d} per device)")

    rng = np.random.default_rng(SEED)
    train = np.stack([markov_corpus(c, N_BATCH * BATCH, rng) for c in range(K)])
    test = np.stack([markov_corpus(c, 2 * BATCH, rng) for c in range(K)])
    # [K, n_batch, batch, SEQ+1] lockstep minibatches
    train = train.reshape(K, N_BATCH, BATCH, SEQ + 1)

    lm = TransformerLM(vocab=VOCAB, dim=32, num_heads=4, max_len=SEQ)
    variables = init_client_params(lm, K, seed=SEED)
    params0 = jax.tree.map(lambda x: x[0], variables["params"])
    flat0, unravel = flatten_params(params0)
    part = TransformerLM.partition(params0)
    n = int(flat0.shape[0])
    print(f"{n} params in {part.num_groups} partition groups "
          f"{[part.group_size(g) for g in range(part.num_groups)]}")

    flat = shard_clients(
        jnp.broadcast_to(flat0[None], (K, n)).astype(jnp.float32), mesh
    )
    train_d = shard_clients(jnp.asarray(train, jnp.int32), mesh)
    test_d = shard_clients(jnp.asarray(test, jnp.int32), mesh)

    cfg = LBFGSConfig(max_iter=4, history_size=10, line_search=True,
                      batch_mode=True)

    def ce(full_flat, toks):
        logits = lm.apply({"params": unravel(full_flat)}, toks[:, :-1])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), toks[:, 1:]
        ).mean()

    def make_round(gid):
        """One jitted epoch+consensus round for partition group `gid`."""

        def client_epoch(flat_c, batches):
            seg0 = part.extract(flat_c, gid)

            def one_batch(carry, toks):
                seg, state = carry

                def loss(v):
                    return ce(part.insert(flat_c, gid, v), toks)

                seg, state, _ = lbfgs_step(loss, seg, state, cfg)
                return (seg, state), loss(seg)

            # fresh optimizer per partition round (reference
            # src/federated_trio.py:273-275)
            (seg, _), losses = jax.lax.scan(
                one_batch, (seg0, lbfgs_init(seg0, cfg)), batches
            )
            return part.insert(flat_c, gid, seg), losses[-1]

        def round_fn(flat_loc, batches_loc, z):
            flat_loc, last_loss = jax.vmap(client_epoch)(flat_loc, batches_loc)
            x = jax.vmap(lambda f: part.extract(f, gid))(flat_loc)
            state, metrics = fedavg_round(x, FedAvgState(z=z))
            flat_loc = jax.vmap(
                lambda f: part.insert(f, gid, state.z)
            )(flat_loc)
            return flat_loc, last_loss, metrics["dual_residual"]

        return jax.jit(
            shard_map(
                round_fn,
                mesh=mesh,
                in_specs=(P(CLIENT_AXIS), P(CLIENT_AXIS), P()),
                out_specs=(P(CLIENT_AXIS), P(CLIENT_AXIS), P()),
                check_vma=False,
            )
        )

    def eval_fn(flat_loc, toks_loc):
        def client_acc(flat_c, toks):
            logits = lm.apply({"params": unravel(flat_c)}, toks[:, :-1])
            pred = jnp.argmax(logits, axis=-1)
            return jnp.mean((pred == toks[:, 1:]).astype(jnp.float32))

        return jax.vmap(client_acc)(flat_loc, toks_loc)

    evaluate = jax.jit(
        shard_map(
            eval_fn, mesh=mesh, in_specs=(P(CLIENT_AXIS), P(CLIENT_AXIS)),
            out_specs=P(CLIENT_AXIS), check_vma=False,
        )
    )

    rounds = {g: make_round(g) for g in part.train_order}
    print(f"chance accuracy = {1 / VOCAB:.3f}")
    for nloop in range(NLOOP):
        for gid in part.train_order:
            z0 = jnp.zeros((part.group_size(gid),), jnp.float32)
            flat, last_loss, dual = rounds[gid](flat, train_d, z0)
            accs = evaluate(flat, test_d)
            print(f"nloop {nloop} group {gid}: loss {np.mean(last_loss):.4f} "
                  f"dual {float(dual):.3e} acc {np.asarray(accs).round(3)}")
            # the averaged group is bit-identical across clients
            xg = np.asarray(
                jax.vmap(lambda f: part.extract(f, gid))(flat)
            )
            assert np.abs(xg - xg[:1]).max() == 0.0

    accs = np.asarray(evaluate(flat, test_d))
    print(f"final per-client next-token accuracy: {accs.round(3)}")
    assert accs.mean() > 5.0 / VOCAB, (
        f"federated LM failed to learn: {accs}"
    )


if __name__ == "__main__":
    main()
