"""Memory telemetry: host RSS + per-device allocator stats.

ROADMAP item 4's spilled-store work needs a bounded-RSS *gate*, and a
gate needs a measurement: this module is the one place host and device
memory are read, feeding the trainer's per-round `memory` series and
the `watch` console's memory panel (via the status sidecar — see below).

Sources, each gracefully None where absent:

* **host** — `/proc/self/status` `VmRSS` (current) and `VmHWM` (peak)
  on Linux; `resource.getrusage` ru_maxrss as the peak fallback
  elsewhere (there is no portable *current*-RSS source without psutil,
  which this repo does not depend on).
* **device** — `device.memory_stats()` per addressable device:
  `bytes_in_use` / `peak_bytes_in_use` / `bytes_limit` / allocation
  counts where the backend's allocator exposes them (TPU and GPU BFC
  allocators do; the CPU backend typically returns nothing — recorded
  as None, never an error).

Memory numbers are facts about THIS PROCESS — a resumed run's RSS has
nothing to do with the crashed one's — so the trainer records the
`memory` series with `stream=False` (the `recompile_count` rule):
crash+resume twin metric streams stay byte-identical with the telemetry
on. The live surface for `watch` is instead the atomically-rewritten
`<stream>.status.json` sidecar (engine/trainer.py `_write_status`).

`jax` is imported inside the device functions only, so the analysis
verbs (`report`, `watch`) can import this module without initializing
an accelerator backend.
"""

from __future__ import annotations

from typing import List, Optional

# allocator keys worth recording where present (jax device.memory_stats
# vocabulary — backends report a superset or nothing at all)
_DEVICE_KEYS = (
    "bytes_in_use",
    "peak_bytes_in_use",
    "bytes_limit",
    "num_allocs",
    "largest_alloc_size",
)


def _proc_status_kb(key: str) -> Optional[int]:
    """One `VmXXX:  N kB` row of /proc/self/status, or None off-Linux."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith(key + ":"):
                    return int(line.split()[1])
    except (OSError, ValueError, IndexError):
        pass
    return None


def host_rss_bytes() -> Optional[int]:
    """Current resident set size of this process, or None where no
    current-RSS source exists (non-Linux without psutil)."""
    kb = _proc_status_kb("VmRSS")
    return kb * 1024 if kb is not None else None


def host_rss_peak_bytes() -> Optional[int]:
    """Peak resident set size of this process — the bounded-RSS gate's
    number (ROADMAP item 4)."""
    kb = _proc_status_kb("VmHWM")
    if kb is not None:
        return kb * 1024
    try:
        import resource

        # ru_maxrss is kilobytes on Linux (moot — /proc handled it) and
        # bytes on macOS; scale for the only platform that reaches here
        # with kB semantics absent
        peak = int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)
        import sys

        return peak if sys.platform == "darwin" else peak * 1024
    except Exception:
        return None


def device_memory_stats(devices=None) -> List[Optional[dict]]:
    """Per-device allocator stats (`_DEVICE_KEYS` where present), one
    entry per addressable device; None for backends whose allocator
    reports nothing (host CPU) — graceful, never an error."""
    import jax

    if devices is None:
        devices = jax.local_devices()
    out: List[Optional[dict]] = []
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            out.append(None)
        else:
            out.append(
                {k: int(stats[k]) for k in _DEVICE_KEYS if k in stats}
            )
    return out


def memory_record(devices=None) -> dict:
    """The `memory` series value: host RSS (current + peak) and the
    per-device allocator stats — all host-side reads, zero device
    dispatches (the folded round stays `{round: 1, round_init: 1}`
    with the telemetry on)."""
    return {
        "rss_bytes": host_rss_bytes(),
        "peak_rss_bytes": host_rss_peak_bytes(),
        "devices": device_memory_stats(devices),
    }
