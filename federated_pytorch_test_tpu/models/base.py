"""Shared model protocol: partition metadata + common-seed client init."""

from __future__ import annotations

from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from federated_pytorch_test_tpu.partition import Partition, build_partition

PyTree = Any

# Reference init: xavier_uniform on conv/linear weights, bias = 0.01
# (reference src/federated_trio.py:115-118).
kernel_init = nn.initializers.xavier_uniform()
bias_init = nn.initializers.constant(0.01)


class PartitionedModel(nn.Module):
    """A flax module that knows its own layer/block partition.

    Subclasses set three class attrs mirroring the reference's metadata
    methods (reference src/simple_models.py:29-39):

      GROUP_PATHS:      per-group list of path prefixes into the params tree
      LINEAR_GROUP_IDS: groups that receive L1/L2 regularization
      TRAIN_ORDER:      default group visit order per outer loop

    Every model carries a `dtype` compute-dtype field (declared here once):
    params stay f32; convs/matmuls run in `dtype` (the engine's
    `compute_dtype` knob) while norms and the loss stay f32.
    """

    dtype: Any = jnp.float32

    # NOTE: deliberately un-annotated so linen's dataclass transform treats
    # them as plain class attributes, not module fields.
    GROUP_PATHS = ()
    LINEAR_GROUP_IDS = ()
    TRAIN_ORDER = ()
    # Widened-GEMM fold capability per layer kind (docs/PERF.md §Widened
    # GEMM). "free": weights are probe-invariant under the fold (broadcast
    # or per-client vectors) — the probe axis folds straight into the
    # example axis of the dot. "grouped": the layer's weights live in a
    # trainable group, so when that group is active its dot stays a G-way
    # grouped block GEMM (ops/grouped_gemm.py on TPU, batched dot_general
    # elsewhere). Metadata only — consumed by docs/roofline, never by the
    # apply path.
    FOLD_LAYERS = {}

    @classmethod
    def partition(cls, params: PyTree) -> Partition:
        """Build the static `Partition` for a params tree of this model."""
        return build_partition(
            params,
            cls.GROUP_PATHS,
            linear_group_ids=cls.LINEAR_GROUP_IDS,
            train_order=cls.TRAIN_ORDER,
        )

    @classmethod
    def input_shape(cls) -> Tuple[int, int, int]:
        return (32, 32, 3)

    def dummy_input(self) -> jnp.ndarray:
        """A minimal batch for `init`. Image models derive it from
        `input_shape`; token models (TransformerLM) override both."""
        return jnp.zeros((1,) + tuple(self.input_shape()), jnp.float32)


def init_client_params(model: nn.Module, n_clients: int, seed: int = 0) -> PyTree:
    """Initialize K identical clients (common-seed init).

    The reference re-seeds before each client's init so all clients start
    from the same point (reference src/federated_trio.py:229-236). Here we
    init once and broadcast along a leading `clients` axis; the stacked tree
    is what gets sharded over the client mesh axis.

    Returns the full variables dict with every leaf shaped `[K, ...]`
    (including e.g. `batch_stats` collections for BatchNorm models).
    """
    import inspect

    rng = jax.random.PRNGKey(seed)
    dummy = (
        model.dummy_input()
        if hasattr(model, "dummy_input")
        else jnp.zeros((1,) + tuple(model.input_shape()), jnp.float32)
    )
    kwargs = {}
    if "train" in inspect.signature(model.__call__).parameters:
        kwargs["train"] = False
    variables = model.init(rng, dummy, **kwargs)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_clients,) + x.shape), variables
    )
