"""`shard_map` — the one import the whole engine rides.

The installed jax (0.9.0) exports `shard_map` at top level with its
replication check behind `check_vma`; every engine/consensus/parallel
module imports it from here, so the name the repo maps through is
stated once.
"""

from jax import shard_map

__all__ = ["shard_map"]
