"""Host-side tracing: Chrome trace-event export + dispatch/recompile counts.

`jax.profiler.trace` (config.profile_dir: one window per round of the
run's second loop) captures device timelines but needs TensorBoard
tooling to look at. `TraceRecorder` is the host-side view that needs
none: every round/epoch/consensus/compile region the trainer enters
becomes one span in a Chrome trace-event JSON. The same regions are
ALSO on the profiler's clock: `MetricsRecorder.phase` opens a
`jax.profiler.TraceAnnotation("fedtpu:<phase>")` beside each span, so a
device trace shows what the host was doing in its idle gaps
(docs/OBSERVABILITY.md §Trace export). Evals appear as a SPLIT pair —
`eval_enqueue` (the async program dispatch, inside its round's span) and
`eval_harvest` (the deferred device->host fetch at the round-boundary
flush, after the round span) — or not at all when they are folded into
the fused round program (docs/OBSERVABILITY.md). Drag the file into
https://ui.perfetto.dev (or chrome://tracing) and the whole experiment's
nesting, stalls, and per-phase walls are a timeline. The span context
managers are shared with the `step_time` metric calls
(`MetricsRecorder.phase`), so the trace and the timing series can never
disagree about what was measured.

`DispatchCounter` turns PR 2's headline property — one jitted dispatch
per fused round — into a *recorded series* instead of a one-off test
assertion: every jitted program the trainer builds is wrapped in a
counting proxy (tagged at its `engine/steps.py` build site), per-round
deltas land in a `dispatch_count` series, and the number of distinct
compiled programs (sampled from jax's jit caches) lands in
`recompile_count`. A change that silently de-fuses a round or triggers
per-round recompiles now shows up in the metrics of every run, not vibes.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Dict, List

from federated_pytorch_test_tpu.obs.sinks import jsonable


class TraceRecorder:
    """Records host-side spans as Chrome trace-event JSON.

    Events use the "X" (complete) phase with microsecond timestamps on a
    single host track; Perfetto nests them by time containment, which
    mirrors the trainer's `round > {epoch, consensus, eval}` structure.
    `save()` writes the JSON-object trace format
    (`{"traceEvents": [...]}`) atomically (tmp + rename).
    """

    def __init__(self, label: str = "fedtpu host"):
        self._t0 = time.perf_counter()
        self._pid = os.getpid()
        # per-thread track ids: Chrome-trace complete ("X") events on
        # ONE track must nest by time containment, and the cohort
        # prefetcher's spans (clients/prefetch.py) deliberately OVERLAP
        # the main thread's round spans — on a shared track Perfetto
        # would mis-nest them. The constructing (main) thread keeps the
        # historical track 0; each further thread gets the next small id.
        self._tids: Dict[int, int] = {threading.get_ident(): 0}
        self._tids_lock = threading.Lock()
        self.events: List[dict] = [
            {
                "name": "process_name",
                "ph": "M",
                "pid": self._pid,
                "tid": 0,
                "args": {"name": label},
            }
        ]

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            with self._tids_lock:  # two first-touching threads must not
                # both read len() before either inserts (same track id
                # == the very mis-nesting per-thread tracks prevent)
                tid = self._tids.setdefault(ident, len(self._tids))
        return tid

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """One complete ("X") event covering the with-block, crash-safe:
        the event is recorded even when the block raises (an InjectedCrash
        mid-round still leaves its span in the trace)."""
        t0 = self._now_us()
        try:
            yield
        finally:
            self.events.append(
                {
                    "name": name,
                    "cat": "trainer",
                    "ph": "X",
                    "ts": round(t0, 3),
                    "dur": round(self._now_us() - t0, 3),
                    "pid": self._pid,
                    "tid": self._tid(),
                    "args": args,
                }
            )

    def instant(self, name: str, **args) -> None:
        """A zero-duration marker (faults, crash points)."""
        self.events.append(
            {
                "name": name,
                "cat": "trainer",
                "ph": "i",
                "s": "t",
                "ts": round(self._now_us(), 3),
                "pid": self._pid,
                "tid": self._tid(),
                "args": args,
            }
        )

    def counter(self, name: str, values: Dict[str, int]) -> None:
        """A counter ("C") sample — cumulative dispatch counts per round."""
        self.events.append(
            {
                "name": name,
                "cat": "trainer",
                "ph": "C",
                "ts": round(self._now_us(), 3),
                "pid": self._pid,
                "args": {k: int(v) for k, v in values.items()},
            }
        )

    def to_dict(self) -> dict:
        return {"traceEvents": list(self.events), "displayTimeUnit": "ms"}

    def save(self, path: str) -> str:
        """Atomically write the trace (the checkpoint writer's tmp+rename
        pattern: a crash mid-write must not leave torn JSON)."""
        path = os.path.abspath(path)
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            # span args arrive from arbitrary call sites and may carry
            # numpy scalars — same hook the JSONL sink uses
            json.dump(self.to_dict(), f, default=jsonable)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        return path


class _CountedProgram:
    """Transparent counting proxy around one jitted program.

    Forwards everything (`lower`, `trace`, ...) to the wrapped function so
    AOT-seeding (`Trainer.compile_round`) and benchmarks keep working;
    only `__call__` is intercepted.
    """

    def __init__(self, fn, counter: "DispatchCounter", category: str):
        self._fn = fn
        self._counter = counter
        self._category = category

    def __call__(self, *args, **kwargs):
        c = self._counter.counts
        c[self._category] = c.get(self._category, 0) + 1
        return self._fn(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)


class DispatchCounter:
    """Counts jitted-program dispatches and compiled-program cache growth.

    `wrap(fn, category)` is called by the `engine/steps.py` builders (the
    one place that knows what kind of program it built); the trainer
    snapshots `counts` around each partition round to produce the
    per-round `dispatch_count` deltas, and samples `compiled_programs()`
    — the summed jit-cache sizes of every tracked program — for the
    `recompile_count` series. The cache sizes are read through the jit
    object's `_cache_size()` (private but stable across the pinned jax
    line; absent attributes degrade to not-counted, never to a crash).
    """

    def __init__(self):
        self.counts: Dict[str, int] = {}
        self._programs: List[_CountedProgram] = []

    def wrap(self, fn, category: str):
        if fn is None:
            return None
        p = _CountedProgram(fn, self, category)
        self._programs.append(p)
        return p

    def snapshot(self) -> Dict[str, int]:
        return dict(self.counts)

    def delta_since(self, snap: Dict[str, int]) -> Dict[str, int]:
        d = {
            k: v - snap.get(k, 0)
            for k, v in self.counts.items()
            if v - snap.get(k, 0)
        }
        d["total"] = sum(d.values())
        return d

    def compiled_programs(self) -> int:
        n = 0
        for p in self._programs:
            cache_size = getattr(p._fn, "_cache_size", None)
            if callable(cache_size):
                try:
                    n += int(cache_size())
                except Exception:
                    pass
        return n
