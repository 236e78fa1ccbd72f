"""Cross-client collectives: the framework's communication backend.

The reference has no communication backend at all — its "master ↔ slave"
exchange is in-process flat-vector arithmetic with comments marking where
the wire protocol would go (reference src/consensus_admm_trio.py:501-513).
Here those exchanges are XLA collectives over the `clients` mesh axis,
riding ICI within a slice and DCN across slices.

All functions are designed to be called inside a `shard_map` whose inputs
carry a LOCAL client block as their leading axis (size K/D per device, see
`mesh.py`): reductions first collapse the local axis, then `psum` across
devices, so the result is identical for any device count D dividing K.

The ADMM z-update `z = Σ_k (y_k + ρ_k x_k) / Σ_k ρ_k` (reference
src/consensus_admm_trio.py:502) and the FedAvg mean (reference
src/federated_trio.py:357) are both `weighted_client_mean` — the API takes
`(value, weight)` pairs from day one (SURVEY.md §2.4).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax
from jax._src.lax.parallel import all_gather_invariant

from federated_pytorch_test_tpu.parallel.mesh import CLIENT_AXIS


def client_sum(x: jnp.ndarray, local_axis: int | None = 0, axis_name: str = CLIENT_AXIS) -> jnp.ndarray:
    """Sum over all K clients: local-block sum + cross-device psum.

    Pass `local_axis=None` when the value is already reduced per device.
    """
    if local_axis is not None:
        x = jnp.sum(x, axis=local_axis)
    return lax.psum(x, axis_name)


def client_count(x_local: jnp.ndarray, axis_name: str = CLIENT_AXIS) -> jnp.ndarray:
    """Total number of clients K, derived from the local block size."""
    return lax.psum(jnp.asarray(x_local.shape[0], jnp.float32), axis_name)


def client_mean(x: jnp.ndarray, local_axis: int = 0, axis_name: str = CLIENT_AXIS) -> jnp.ndarray:
    """Unweighted mean over all K clients — the FedAvg z-update
    `z = (x_1 + ... + x_K)/K` (reference src/federated_trio.py:357).

    Unlike `client_sum` there is no already-reduced form: the local client
    block must still be present so K can be derived from its size.
    """
    total = client_sum(x, local_axis, axis_name)
    k = client_sum(jnp.asarray(float(x.shape[local_axis])), None, axis_name)
    return total / k


def weighted_client_mean(
    value: jnp.ndarray,
    weight: jnp.ndarray,
    local_axis: int | None = 0,
    axis_name: str = CLIENT_AXIS,
) -> jnp.ndarray:
    """`Σ_k w_k v_k / Σ_k w_k` over all clients.

    `weight` must have the same rank as `value` with broadcastable trailing
    axes — pass per-client scalar weights as `[K_loc, 1]` against
    `[K_loc, N]` values. This is the ADMM z-update with `v = y/ρ + x`,
    `w = ρ` (reference src/consensus_admm_trio.py:502).
    """
    num = client_sum(value * weight, local_axis, axis_name)
    den = client_sum(weight, local_axis, axis_name)
    return num / den


def all_clients(x_local: jnp.ndarray, axis_name: str = CLIENT_AXIS) -> jnp.ndarray:
    """Gather every client's value to all devices: `[K, ...]` everywhere.

    Used by diagnostics (the `distance_of_layers` equivalent, reference
    src/federated_trio.py:170-186) and by the Byzantine-robust order
    statistics (consensus/robust.py): a coordinate-wise median/trim needs
    every client's value per coordinate, so robust-agg exchanges
    DELIBERATELY spend a full [K, N] gather on integrity. The mean path
    keeps its psum — the reference's bandwidth-saving contract holds
    exactly when `robust_agg='mean'` (the default).

    The result is identical on every device, and typed so: jax 0.9.0's
    `lax.all_gather` returns a value `shard_map` still treats as varying
    over `axis_name`, which a replicated scan carry (the consensus z) and
    `out_specs=P()` both reject. `all_gather_invariant` is the same XLA
    all-gather with the varying -> invariant type; 0.9.0 ships it without
    a `jax.lax` export.
    """
    return all_gather_invariant(x_local, axis_name, axis=0, tiled=True)
