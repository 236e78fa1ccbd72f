"""Multi-host setup: process initialization and DCN-spanning client meshes.

The reference is strictly single-process (SURVEY.md §2.4 — no
torch.distributed, no sockets). This framework scales the `clients` axis
past one host the JAX way:

* every host runs the SAME program; `initialize_distributed()` wires the
  processes together (coordinator discovery via the standard TPU
  environment, or explicit arguments elsewhere);
* `multihost_client_mesh(K)` builds the client mesh over ALL processes'
  devices, DCN-aware: with `jax.experimental.mesh_utils`'s hybrid layout
  the client axis is ordered so that the clients of one slice are
  ICI-adjacent and the slice boundary (DCN) is crossed as few times as
  possible — consensus `psum`s then reduce within slices first and cross
  DCN once, which is exactly the weighted-mean collective's reduction
  shape (parallel/collectives.py).

Single-process (the dev box, CI's virtual CPU mesh) everything degrades
to the plain `client_mesh` — the same code runs everywhere.
"""

from __future__ import annotations

import os
import time
import warnings

import jax
import numpy as np
from jax.sharding import Mesh

from federated_pytorch_test_tpu.parallel.mesh import (
    CLIENT_AXIS,
    largest_feasible_mesh,
)

def _env_signals_multihost() -> bool:
    """True when the environment describes MORE than this one process.

    A coordinator address always does; `TPU_WORKER_HOSTNAMES` only when
    it lists several workers — single-worker setups carry a one-entry
    list and are NOT multi-host.
    """
    if any(
        v in os.environ
        for v in ("COORDINATOR_ADDRESS", "MEGASCALE_COORDINATOR_ADDRESS")
    ):
        return True
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    return len([h for h in hosts.split(",") if h.strip()]) > 1


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    max_attempts: int = 5,
    backoff_s: float = 2.0,
) -> int:
    """Initialize JAX's multi-process runtime; returns this process' id.

    On TPU pods with standard environment variables, call with no
    arguments on every host, BEFORE any other JAX call (touching the
    backend first makes `jax.distributed.initialize` impossible — even
    `jax.devices()` counts). A no-op (returning 0) when single-process
    (nothing configured and no arguments given).

    On pods the coordinator process routinely comes up seconds after the
    workers (pod schedulers give no start-order guarantee), so the
    connection is retried with exponential backoff — `max_attempts` tries,
    `backoff_s * 2**attempt` seconds between them (capped at 30 s per
    wait). A failed `jax.distributed.initialize` leaves partial global
    state behind (the client object is created before connect()), and a
    second call against that state dies instantly on "should only be
    called once" instead of touching the network — so every failed
    attempt is followed by a best-effort `jax.distributed.shutdown()` to
    make the next connect real. When every attempt fails, the LAST error
    raises loudly: continuing would leave every host training the whole
    job independently, racing on checkpoints — worse than a crash.
    """
    # decide from env/args alone — probing jax.process_count() here would
    # itself initialize the backend and break the multi-process path
    if coordinator_address is None and num_processes is None:
        if not _env_signals_multihost():
            return 0  # single-process run
    if max_attempts < 1:
        raise ValueError(f"max_attempts must be >= 1, got {max_attempts}")
    last: Exception | None = None
    for attempt in range(max_attempts):
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
            )
            return jax.process_index()
        except RuntimeError as e:
            msg = str(e).lower()
            if attempt == 0 and ("already" in msg or "called once" in msg):
                # the runtime was initialized before we were called:
                # benign. Only trustworthy on the FIRST attempt — after
                # our own failed connect the same message just means the
                # broken partial state was not cleared.
                return jax.process_index()
            last = e
            try:  # clear the partial init state so the retry reconnects
                jax.distributed.shutdown()
            except Exception:
                pass
            if attempt + 1 < max_attempts:
                delay = min(backoff_s * (2.0 ** attempt), 30.0)
                warnings.warn(
                    f"jax.distributed.initialize failed (attempt "
                    f"{attempt + 1}/{max_attempts}): {e}; coordinator may "
                    f"not be up yet — retrying in {delay:.1f}s"
                )
                time.sleep(delay)
    raise RuntimeError(
        f"jax.distributed.initialize failed after {max_attempts} attempts; "
        "a configured multi-host run MUST NOT fall back to independent "
        "single-process training (checkpoint races, split-brain consensus) "
        f"— last error: {last}"
    ) from last


def _dcn_islands() -> tuple[int, bool]:
    """(number of DCN islands, islands-are-processes?).

    TPU devices expose `slice_index` — ICI-connected slices are the
    islands, however many processes drive them (multi-host single-slice
    pods are ONE island). Backends without slice topology (CPU workers,
    the CI multi-process harness) have no ICI at all: every process
    boundary is the DCN analogue, so each process is its own island and
    `mesh_utils` groups by process (`process_is_granule`).
    """
    devs = jax.devices()
    slices = {getattr(d, "slice_index", None) for d in devs}
    if None not in slices and len(slices) > 1:
        return len(slices), False  # real multi-slice accelerator topology
    if devs[0].platform == "cpu":
        # no ICI anywhere (the distributed CPU backend reports a uniform
        # slice_index 0, which says nothing): every process boundary is
        # the DCN analogue
        return max(1, jax.process_count()), True
    return 1, False


def multihost_client_mesh(n_clients: int) -> Mesh:
    """A 1-D `clients` mesh over every device of every process, laid out
    DCN-aware when multiple slices are present.

    Single-process: identical to `largest_feasible_mesh` (the largest
    local device count dividing K). Multi-process: all global devices
    participate, so `n_clients` must be a multiple of the global device
    count (each device carries a K/D local client block).
    """
    if jax.process_count() == 1:
        return largest_feasible_mesh(n_clients)

    n_global = len(jax.devices())
    if n_clients % n_global != 0:
        raise ValueError(
            f"multi-process mesh uses all {n_global} global devices; "
            f"n_clients={n_clients} must be a multiple of that"
        )

    from jax.experimental import mesh_utils

    n_slices, by_process = _dcn_islands()
    per_slice = n_global // n_slices
    if n_slices > 1 and n_slices * per_slice == n_global:
        try:
            devices = mesh_utils.create_hybrid_device_mesh(
                mesh_shape=(per_slice,),
                dcn_mesh_shape=(n_slices,),
                process_is_granule=by_process,
            )
            return Mesh(np.asarray(devices).reshape(-1), (CLIENT_AXIS,))
        except (ValueError, AssertionError) as e:
            warnings.warn(
                f"hybrid mesh layout unavailable ({e}); falling back to "
                "default device order"
            )
    return Mesh(np.asarray(jax.devices()), (CLIENT_AXIS,))
