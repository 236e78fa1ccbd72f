"""Counts this process's compile requests through `jax.monitoring`.

Copied from `chip_smoke.CompileLog` (PR 21): every jit compile asks the
persistent cache first (`compile_requests_use_cache`); a `cache_hits`
event means the cache served it, the rest went to the XLA backend.
"""

from __future__ import annotations

import collections


class CompileLog:
    _EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "requests",
        "/jax/compilation_cache/cache_hits": "cache_hits",
    }

    def __init__(self):
        import jax

        self.counts: collections.Counter = collections.Counter()
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **_) -> None:
        key = self._EVENTS.get(name)
        if key is not None:
            self.counts[key] += 1

    def snapshot(self) -> collections.Counter:
        return collections.Counter(self.counts)

    def since(self, before: collections.Counter | None = None) -> dict:
        """`{requests, cache_hits, compiled}` since `before` (or ever)."""
        before = before or collections.Counter()
        now = self.counts
        requests = now["requests"] - before["requests"]
        hits = now["cache_hits"] - before["cache_hits"]
        return {
            "requests": requests,
            "cache_hits": hits,
            "compiled": requests - hits,
        }
