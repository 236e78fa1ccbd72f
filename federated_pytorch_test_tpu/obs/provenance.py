"""Provenance stamps: every performance number says WHERE it came from.

Nothing in an artifact's numbers distinguishes a CPU-twin run from a
real chip measurement — a host number can masquerade as a TPU result
the moment the filename stops saying so. One small self-describing
stamp is attached to what the program itself emits:

* the trainer's end-of-run `roofline` record (obs/roofline.py),
* the `<stream>.status.json` live sidecar (`watch` renders a one-line
  `backend/sha/twin` row from it; `report --integrity` lists each run's
  class),
* the chaos verb's repro bundles and soak summary (fault/chaos.py).

The stamp answers: which commit (sha + dirty flag), which backend and
chip (platform, device kind and count), which host (hostname, cpu
count), which jax, whether this is the CPU twin. `provenance_class`
collapses a stamp to one word: `cpu_twin`, the backend (`tpu`, ...), or
`unstamped` for an artifact that carries no stamp.

Import rules: this module is accelerator-free. `provenance_stamp`
PROBES jax only when asked (`probe_jax=True` — callers that already
initialized a backend: the trainer); `host_stamp` never touches jax at
all (the jax version comes from package metadata, no import) — it is
the stamp for host-side facts of runs on the forced-CPU virtual mesh
(the chaos verb pins it), so `backend: cpu` is the honest label.
"""

from __future__ import annotations

import os
import socket
import subprocess
from typing import Optional, Tuple

STAMP_SCHEMA = 1

# the stamp's full key set, in canonical order (consumers slice this,
# never invent keys)
STAMP_KEYS = (
    "schema",
    "git_sha",
    "git_dirty",
    "backend",
    "device_kind",
    "device_count",
    "host",
    "cpu_count",
    "jax_version",
    "cpu_twin",
    "bench_repeats",
)

_CACHED_STAMP: Optional[dict] = None


def git_info(root: Optional[str] = None) -> Tuple[Optional[str], Optional[bool]]:
    """`(short_sha, dirty)` of the working tree, or `(None, None)` when
    git (or the repo) is unavailable — a stamp from an exported tarball
    is still a stamp, just commit-less."""
    if root is None:
        root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=root, capture_output=True, text=True, timeout=10,
        )
        if sha.returncode != 0:
            return None, None
        status = subprocess.run(
            ["git", "status", "--porcelain"],
            cwd=root, capture_output=True, text=True, timeout=10,
        )
        dirty = bool(status.stdout.strip()) if status.returncode == 0 else None
        return sha.stdout.strip() or None, dirty
    except Exception:
        return None, None


def _jax_version() -> Optional[str]:
    """The installed jax version WITHOUT importing jax (package
    metadata only) — safe in backend-free verbs."""
    try:
        from importlib.metadata import version

        return version("jax")
    except Exception:
        return None


def provenance_stamp(
    *,
    repeats: Optional[int] = None,
    probe_jax: bool = True,
    backend: Optional[str] = None,
    device_kind: Optional[str] = None,
    device_count: Optional[int] = None,
) -> dict:
    """Build one provenance stamp.

    `probe_jax=True` (default) reads backend/device facts from an
    ALREADY-IMPORTABLE jax — `jax.default_backend()` initializes the
    backend, so only call it from processes that run device work anyway
    (the trainer). Backend-free callers pass the
    facts explicitly or use `host_stamp`. Any probe failure degrades to
    nulls: a stamp is never the thing that kills a run.
    """
    if probe_jax and backend is None:
        try:
            import jax

            backend = jax.default_backend()
            devs = jax.devices()
            device_kind = devs[0].device_kind
            device_count = len(devs)
        except Exception:
            pass
    sha, dirty = git_info()
    return {
        "schema": STAMP_SCHEMA,
        "git_sha": sha,
        "git_dirty": dirty,
        "backend": backend,
        "device_kind": device_kind,
        "device_count": device_count,
        "host": socket.gethostname(),
        "cpu_count": os.cpu_count(),
        "jax_version": _jax_version(),
        "cpu_twin": (backend == "cpu") if backend is not None else None,
        "bench_repeats": repeats,
    }


def host_stamp() -> dict:
    """A stamp for HOST-side artifacts (the chaos verb's bundles): no
    jax probe, `backend: cpu` asserted — honest because the caller
    runs the forced-CPU virtual mesh (fault/chaos.py pins it)."""
    return provenance_stamp(probe_jax=False, backend="cpu")


def cached_stamp() -> dict:
    """One stamp per process (git subprocesses run once): the trainer
    rewrites the status sidecar every round and must not fork git each
    time."""
    global _CACHED_STAMP
    if _CACHED_STAMP is None:
        _CACHED_STAMP = provenance_stamp()
    return dict(_CACHED_STAMP)


def provenance_class(stamp) -> str:
    """Collapse a stamp to the class a run's numbers are listed under.

    * no stamp (pre-provenance artifacts) -> `unstamped`;
    * `cpu_twin` stamps -> `cpu_twin`;
    * everything else -> the backend string (`tpu`, `gpu`, ...), or
      `unstamped` when the stamp carries no backend at all.
    """
    if not isinstance(stamp, dict):
        return "unstamped"
    if stamp.get("cpu_twin"):
        return "cpu_twin"
    backend = stamp.get("backend")
    if not backend:
        return "unstamped"
    return str(backend)
