"""Process start-up: pin jax to the host CPU, place the compile cache.

Two process-global decisions every launcher (the CLI, the test conftest,
the multichip dry run, the chaos verb, chip_smoke.py, chipbench) makes
before the first program compiles, kept here so each is decided once:

* `force_host_cpu` — run on XLA's host platform with a virtual device
  mesh (tests and CPU drives). The accelerator path needs nothing: jax
  picks the TPU by default where one is attached.
* `enable_compile_cache` — where compiled programs persist. The rule is
  `compile_cache_dir`: `$JAX_COMPILATION_CACHE_DIR` when the environment
  sets it (jax reads that variable itself, so nothing is configured in
  code and the cache can be placed from outside), else the fixed
  `<checkout>/.cache/xla`. The path is part of the cache key's locality:
  a directory that moves between runs never hits.

Must run before any jax backend is instantiated (importing jax is fine;
creating arrays / calling `jax.devices()` is not).
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"
_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def set_host_device_count(n: int) -> None:
    """Ensure `XLA_FLAGS` requests at least `n` virtual host devices.

    Replaces an existing smaller `--xla_force_host_platform_device_count`
    value rather than silently keeping it.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"{_COUNT_FLAG}=(\d+)", flags)
    if m is None:
        flags = (flags + f" {_COUNT_FLAG}={n}").strip()
    elif int(m.group(1)) < n:
        flags = flags.replace(m.group(0), f"{_COUNT_FLAG}={n}")
    else:
        return
    os.environ["XLA_FLAGS"] = flags


def force_host_cpu(min_devices: int | None = None):
    """Pin jax to the cpu platform; return the jax module.

    With `min_devices`, also guarantees that many virtual host devices (or
    raises RuntimeError if a backend was already initialized with fewer).
    """
    if min_devices is not None:
        set_host_device_count(min_devices)

    import jax

    jax.config.update("jax_platforms", "cpu")

    if min_devices is not None and jax.local_device_count() < min_devices:
        raise RuntimeError(
            f"need {min_devices} host devices, have {jax.local_device_count()} "
            f"on platform {jax.default_backend()!r}; a jax backend was "
            f"initialized before force_host_cpu could raise the count"
        )
    return jax


def compile_cache_dir() -> str:
    """The persistent XLA compile-cache directory: the environment's
    `JAX_COMPILATION_CACHE_DIR` when set, else `<checkout>/.cache/xla`.

    One definition for every consumer — `enable_compile_cache` below and
    the fresh-interpreter subprocesses tests spawn (CLI, examples,
    multiprocess workers) — so no second copy of the path can drift and
    cost every compile again.
    """
    env = os.environ.get(_CACHE_ENV)
    if env:
        return env
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".cache", "xla")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on for this process; return its
    directory.

    With `JAX_COMPILATION_CACHE_DIR` set, jax has already read the
    placement from the environment and this configures nothing; unset,
    it points jax at the fixed checkout path. THE one
    `jax_compilation_cache_dir` update site in the repo.
    """
    cache = compile_cache_dir()
    os.makedirs(cache, exist_ok=True)
    if not os.environ.get(_CACHE_ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", cache)
    return cache
