"""Cross-cutting utilities: metrics, checkpointing, profiling."""

from federated_pytorch_test_tpu.utils.metrics import Deferred, MetricsRecorder
from federated_pytorch_test_tpu.utils.checkpoint import (
    checkpoint_path,
    load_checkpoint,
    save_checkpoint,
)
from federated_pytorch_test_tpu.utils.hostcpu import (
    compile_cache_dir,
    enable_compile_cache,
    force_host_cpu,
    set_host_device_count,
)

__all__ = [
    "compile_cache_dir",
    "enable_compile_cache",
    "Deferred",
    "MetricsRecorder",
    "checkpoint_path",
    "load_checkpoint",
    "save_checkpoint",
    "force_host_cpu",
    "set_host_device_count",
]
