"""Pallas TPU kernels for the framework's hot ops.

The compute path is JAX/XLA; these kernels exist where fusion beyond what
XLA does automatically pays off on TPU — primarily the L-BFGS compact
direction, whose history-sized matmul chain XLA schedules as ~5 HBM passes
over the `[m, R, 128]` buffers but a fused pair of kernels does in 2
(see `ops/compact_pallas.py`).

All kernels run in interpret mode off-TPU (CPU tests / the virtual
8-device mesh) and compiled on real TPU chips; `_interpret` is the one
place that choice is made (chip_smoke.py asserts it False on the chip
before it trusts a kernel result).
"""

import jax


def _interpret() -> bool:
    """Whether `pallas_call`s run in Pallas interpret mode: everywhere
    but on a TPU backend. Defined before the kernel imports below, which
    read it from this package."""
    return jax.default_backend() != "tpu"


from federated_pytorch_test_tpu.ops.compact_pallas import (
    compact_direction_pallas,
    fused_gram_projections,
)
from federated_pytorch_test_tpu.ops.flash_attention import flash_attention
from federated_pytorch_test_tpu.ops.grouped_gemm import (
    grouped_matmul,
    grouped_matmul_pallas,
)

__all__ = [
    "compact_direction_pallas",
    "flash_attention",
    "fused_gram_projections",
    "grouped_matmul",
    "grouped_matmul_pallas",
]
