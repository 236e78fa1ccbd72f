"""Training observability: the reference's five metric series, structured.

The reference's observability is `print()` lines with grep-able formats
plus documented shell pipelines to extract series from logs (reference
src/consensus_admm_trio.py:392,517,548-552). The capability contract
(SURVEY.md §5) is five series: per-client per-batch loss, per-round primal
and dual residuals, mean rho, and per-client test accuracy. Here every
observation lands in a structured in-memory store (JSON-serializable) AND
is printed in a format close to the reference's, so the same shell recipes
still work.

The store is extended by the `obs/` layer (docs/OBSERVABILITY.md):

* **sinks** — every `log()` record is forwarded to pluggable sinks
  (`obs/sinks.py JsonlSink` is the crash-safe streaming one); `flush()` /
  `commit_loop()` are the trainer's per-round and per-checkpoint
  durability barriers, and `add_sink(..., replay=...)` seeds the
  in-memory series from a resumed stream so a crash+resume run's series
  is continuous;
* **deferred records** — a record's value may be a `Deferred` (a thunk,
  typically closing over a `jax.Array` whose device->host fetch is the
  expensive part): the record takes its place in the series immediately,
  but the value is materialized lazily — harvested in batch at the
  trainer's round boundaries (`flush`) and ALWAYS before a
  `commit_loop()` marker reaches the sinks, so the crash-safety contract
  (everything before an `nloop_complete` marker is durable and complete)
  holds with async evals exactly as with sync ones. While a deferred
  record is pending, subsequent streamed records queue behind it, so the
  sink stream stays record-for-record in logging order;
* **tracer** — `phase()` is the ONE enter/exit context manager shared by
  the wall-clock `step_time` records and the Chrome-trace span recorder
  (`obs/trace.py`), so the timing series and the exported trace can never
  disagree about what was measured.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple


class Deferred:
    """A lazily-materialized metric value.

    Wraps a zero-arg thunk whose call is postponed until the record is
    harvested (round boundary / commit / serialization). The thunk runs
    at most once; `resolve()` returns the cached value afterwards. The
    intended payload is a device array already ENQUEUED on the
    accelerator — the dispatch happened at log time, only the blocking
    device->host fetch is deferred — so rollback/late mutation of the
    live training state cannot change what a deferred record reports.
    """

    __slots__ = ("_fn", "_value", "_resolved")

    def __init__(self, fn: Callable[[], Any]):
        self._fn = fn
        self._value = None
        self._resolved = False

    def resolve(self) -> Any:
        if not self._resolved:
            self._value = self._fn()
            self._fn = None  # drop the closure (and its device arrays)
            self._resolved = True
        return self._value


@dataclasses.dataclass
class MetricsRecorder:
    """Append-only metric series, keyed by name.

    Each record is a dict with a `step` context (nloop/group/nadmm/...)
    plus the value. `print_fn` mirrors each record to stdout in a
    reference-style grep-able line.
    """

    series: Dict[str, List[dict]] = dataclasses.field(default_factory=dict)
    verbose: bool = True
    # cursor of the FIRST non-finite loss/residual observed, or None while
    # the run is healthy (see _flag_nonfinite). Frozen once set: the first
    # poisoned round is the diagnostic one, everything after is fallout.
    first_nonfinite: Optional[dict] = None
    # streaming sinks (obs/sinks.py protocol: record/flush/commit/close)
    # and the optional trace-span recorder (obs/trace.py TraceRecorder)
    sinks: List[Any] = dataclasses.field(default_factory=list)
    # synchronous observers (obs/health.py HealthEngine protocol:
    # observe(name, rec)): called at LOG time for every STREAMED record —
    # the exact record set (and order) the sinks persist, which is what
    # lets a resumed observer rebuild identical state from a stream
    # replay. Unlike sinks, observers see a deferred record BEFORE its
    # value is materialized (they must ignore Deferred-valued series).
    observers: List[Any] = dataclasses.field(default_factory=list)
    tracer: Optional[Any] = None
    # `annotate(name)` -> context manager that puts a host span of that
    # name on the DEVICE profiler's clock (the Trainer hands over
    # `jax.profiler.TraceAnnotation`; this module stays free of jax)
    annotate: Optional[Callable[[str], Any]] = None
    _t0: float = dataclasses.field(default_factory=time.perf_counter)
    # streamed records not yet forwarded to the sinks: a `Deferred` value
    # holds its slot here until harvested, and every later streamed
    # record queues BEHIND it so the sink stream preserves logging order
    _pending: List[Tuple[str, dict]] = dataclasses.field(default_factory=list)

    def log(self, name: str, value: Any, *, stream: bool = True, **context) -> None:
        """Append one record; `stream=False` keeps it OUT of the sinks —
        for series that are facts about THIS PROCESS rather than the run's
        trajectory (`recompile_count`: a resumed process recompiles
        programs the crashed one had warm, so streaming it would break the
        crash/resume stream-continuity contract).

        `value` may be a `Deferred`: the record enters the series now and
        is materialized + forwarded to the sinks at the next harvest
        (`flush`/`commit_loop`/serialization)."""
        rec = {"t": time.perf_counter() - self._t0, "value": value, **context}
        self.series.setdefault(name, []).append(rec)
        if stream:
            for ob in self.observers:
                ob.observe(name, rec)
            if self._pending or isinstance(value, Deferred):
                self._pending.append((name, rec))
            else:
                for s in self.sinks:
                    s.record(name, rec)

    def _harvest(self) -> None:
        """Materialize every pending deferred value and forward the queued
        records to the sinks, in logging order."""
        pending, self._pending = self._pending, []
        for name, rec in pending:
            if isinstance(rec["value"], Deferred):
                rec["value"] = rec["value"].resolve()
            for s in self.sinks:
                s.record(name, rec)

    def _materialize(self) -> None:
        """Resolve every deferred value in the store IN PLACE (no sink
        forwarding — pending records keep their queue slots and reach the
        sinks, already resolved, at the next harvest)."""
        for recs in self.series.values():
            for rec in recs:
                if isinstance(rec["value"], Deferred):
                    rec["value"] = rec["value"].resolve()

    def discard_pending(self, name: str) -> None:
        """Drop the not-yet-harvested records of one series — from both
        the sink queue and the in-memory store. The trainer's rollback
        path uses this: a poisoned round is discarded wholesale, and its
        enqueued (deferred) evals go with it — they never reach the
        stream, in ANY eval mode (docs/FAULT.md §Rollback mode)."""
        dropped = [rec for n, rec in self._pending if n == name]
        self._pending = [(n, r) for n, r in self._pending if n != name]
        if dropped and name in self.series:
            drop_ids = {id(r) for r in dropped}
            self.series[name] = [
                r for r in self.series[name] if id(r) not in drop_ids
            ]
            if not self.series[name]:
                del self.series[name]

    # ------------------------------------------------------ sinks & tracing

    def add_sink(self, sink, replay=()) -> None:
        """Attach a sink, optionally seeding the store from its replayed
        records (a resumed JSONL stream): replayed records enter `series`
        directly — NOT re-forwarded to the sink, which already holds them
        — and a replayed `nonfinite_flag` restores the poisoned cursor."""
        for name, rec in replay:
            self.series.setdefault(name, []).append(rec)
            if name == "nonfinite_flag" and self.first_nonfinite is None:
                self.first_nonfinite = dict(rec["value"])
        self.sinks.append(sink)

    def flush(self) -> None:
        """Per-round durability: harvest pending deferred records, then
        push buffered sink writes to the OS."""
        self._harvest()
        for s in self.sinks:
            s.flush()

    def commit_loop(self, nloop: int) -> None:
        """Checkpoint-boundary durability: marker + fsync in every sink.
        Pending deferred records are ALWAYS resolved and written first —
        the marker's contract (everything before it is durable and
        complete) must hold for async evals too, or a crash+resume stream
        would diverge from an uninterrupted one. The JSONL resume path
        truncates to these markers (obs/sinks.py)."""
        self._harvest()
        for s in self.sinks:
            s.commit(nloop)

    def close(self) -> None:
        self._harvest()
        for s in self.sinks:
            s.close()

    @contextlib.contextmanager
    def phase(self, phase: str, *, record: bool = True, **context):
        """Time one phase: a tracer span plus (optionally) a `step_time`
        record — the shared enter/exit point of the timing series and the
        Chrome trace (obs/trace.py). `record=False` emits the span only,
        keeping the `step_time` series exactly its pre-obs phase set
        (epoch / consensus / fused_round / straggler_wait).

        With `annotate` set the phase is also a host event named
        `fedtpu:<phase>` in a `jax.profiler` trace, whether or not a
        tracer is attached. The prefix keeps these events apart from the
        trainer's explicit `StepTraceAnnotation("fused_round")`, which
        the benchmark's trace reduction finds by exact name."""
        t0 = time.perf_counter()
        with contextlib.ExitStack() as stack:
            if self.tracer is not None:
                stack.enter_context(self.tracer.span(phase, **context))
            if self.annotate is not None:
                stack.enter_context(self.annotate("fedtpu:" + phase))
            yield
        if record:
            self.step_time(phase, time.perf_counter() - t0, **context)

    def _flag_nonfinite(self, name: str, values, context: dict) -> None:
        """Flag the FIRST NaN/Inf observation with its loop cursor.

        The reference lets a poisoned loss print as `nan` and scroll away
        (its only guards live inside the optimizer, src/lbfgsnew.py:542);
        here the first non-finite loss/residual pins the exact
        (loop, group, round) cursor in `first_nonfinite` and a
        `nonfinite_flag` series record, instead of propagating silently
        through the remaining rounds.
        """
        if self.first_nonfinite is not None:
            return
        if any(not math.isfinite(v) for v in values):
            self.first_nonfinite = {"series": name, **context}
            self.log("nonfinite_flag", {"series": name, **context})
            if self.verbose:
                ctx = " ".join(f"{k}={v}" for k, v in context.items())
                print(f"NONFINITE first non-finite {name} at {ctx}")

    def batch_losses(self, losses, *, nloop, group, nadmm, epoch, minibatch) -> None:
        """Per-client training losses for one lockstep minibatch.

        Reference line: `layer=%d %d minibatch=%d epoch=%d losses %e,%e,%e`
        (src/federated_trio.py:352).
        """
        vals = [float(v) for v in losses]
        ctx = dict(
            nloop=nloop, group=group, nadmm=nadmm, epoch=epoch,
            minibatch=minibatch,
        )
        self._flag_nonfinite("train_loss", vals, ctx)
        self.log("train_loss", vals, **ctx)
        if self.verbose:
            print(
                f"layer={group} {nloop} minibatch={minibatch} epoch={epoch} "
                "losses " + ",".join(f"{v:e}" for v in vals)
            )

    def residuals(
        self, primal, dual, mean_rho, *, nloop, group, nadmm, group_size
    ) -> None:
        """Consensus residuals for one averaging/ADMM round.

        Reference line: `layer=%d(%d,%f) ADMM=%d primal=%e dual=%e`
        (src/consensus_admm_trio.py:517); FedAvg prints only the dual
        (src/federated_trio.py:359).
        """
        ctx = dict(nloop=nloop, group=group, nadmm=nadmm)
        self._flag_nonfinite(
            "residuals",
            [float(v) for v in (dual, primal) if v is not None],
            ctx,
        )
        self.log("dual_residual", float(dual), **ctx)
        if primal is not None:
            self.log("primal_residual", float(primal), **ctx)
        if mean_rho is not None:
            self.log("mean_rho", float(mean_rho), **ctx)
        if self.verbose:
            p = f" primal={float(primal):e}" if primal is not None else ""
            r = f",{float(mean_rho):f}" if mean_rho is not None else ""
            print(
                f"layer={group}({group_size}{r}) ADMM={nadmm}{p} "
                f"dual={float(dual):e}"
            )

    def accuracies(
        self, accs, *, nloop, group, nadmm, epoch=None, minibatch=None
    ) -> None:
        """Per-client top-1 test accuracy (fractions in [0,1]).

        Reference: `verification_error_check` prints per-client percentages
        (src/federated_trio.py:199-223). `epoch`/`minibatch` are set on the
        per-batch cadence (`eval_every_batch`, the reference's
        check_results=True telemetry, src/no_consensus_trio.py:266-267).

        `accs` may be a `Deferred` (the trainer's async eval path): the
        record is logged now and materialized — including the verbose
        per-client print, which then appears at harvest time instead of
        inline — when the round's deferred records are harvested.
        """
        ctx = dict(nloop=nloop, group=group, nadmm=nadmm)
        if epoch is not None:
            ctx["epoch"] = epoch
        if minibatch is not None:
            ctx["minibatch"] = minibatch

        def emit(raw):
            vals = [float(a) for a in raw]
            if self.verbose:
                for k, a in enumerate(vals):
                    print(
                        f"Accuracy of client {k + 1} on the test images: "
                        f"{100.0 * a:.2f} %"
                    )
            return vals

        if isinstance(accs, Deferred):
            self.log(
                "test_accuracy", Deferred(lambda: emit(accs.resolve())), **ctx
            )
        else:
            self.log("test_accuracy", emit(accs), **ctx)

    def step_time(self, phase: str, seconds: float, **context) -> None:
        """Wall-clock duration of one phase (epoch / consensus / eval).

        The tracing series the reference's dead `start_time = time.time()`
        never produced (reference src/no_consensus_trio.py:6,175).
        """
        self.log("step_time", {"phase": phase, "seconds": seconds}, **context)
        if self.verbose:
            ctx = " ".join(f"{k}={v}" for k, v in context.items())
            print(f"step_time phase={phase} {ctx} seconds={seconds:.4f}")

    def participation(self, survivors: int, k: int, **context) -> None:
        """Surviving-client count of one masked consensus round.

        Only recorded when a fault plan is active (engine/trainer.py), so
        no-chaos runs keep their pre-fault metric series byte-identical.
        """
        self.log("participation", {"survivors": survivors, "clients": k}, **context)
        if self.verbose:
            ctx = " ".join(f"{k_}={v}" for k_, v in context.items())
            print(f"participation {survivors}/{k} {ctx}")

    def fault(self, kind: str, clients, **context) -> None:
        """A detected client fault (non-finite loss/params).

        The failure-detection series the reference lacks entirely
        (SURVEY.md §5: NaN guards exist only inside the optimizer).
        """
        ids = [int(c) for c in clients]
        self.log("fault", {"kind": kind, "clients": ids}, **context)
        if self.tracer is not None:
            self.tracer.instant(f"fault:{kind}", clients=ids, **context)
        if self.verbose:
            ctx = " ".join(f"{k}={v}" for k, v in context.items())
            print(f"FAULT kind={kind} clients={ids} {ctx}")

    def update_norms(self, norms, *, nloop, group, nadmm) -> None:
        """Per-client update norms of one consensus exchange (`[K]`).

        The auto-quarantine evidence series (consensus/robust.py
        `update_suspects`): `‖x_k − z‖` for every alive client; a
        non-finite norm (nan-burst-corrupted sender) records as `null` —
        a bare NaN token would make the JSONL stream invalid RFC-8259
        (jq and strict parsers abort mid-stream even though Python's
        json.loads tolerates it). Only recorded when `quarantine_z` is
        configured, so pre-quarantine runs keep their series byte-
        identical. Deliberately NOT fed to the first-nonfinite cursor —
        a corrupt update here is a DETECTED corruption, not a
        training-health event.
        """
        vals = [
            float(v) if math.isfinite(float(v)) else None for v in norms
        ]
        self.log("update_norm", vals, nloop=nloop, group=group, nadmm=nadmm)
        if self.verbose:
            print(
                f"update_norm nloop={nloop} group={group} nadmm={nadmm} "
                + ",".join("nonfinite" if v is None else f"{v:e}" for v in vals)
            )

    def quarantine(self, clients, *, nloop, group, nadmm) -> None:
        """Clients auto-quarantined at one consensus exchange.

        Flagged by their update-norm z-score (or a non-finite update) and
        excluded from the REST OF THE ROUND's exchanges — the suspect
        mask ANDs into the participation mask (docs/FAULT.md). Mirrors
        `fault` (trace instant + grep-able line) but is its own series:
        a quarantine is the DEFENSE acting, not a failure observed.
        """
        ids = [int(c) for c in clients]
        self.log(
            "quarantine", {"clients": ids}, nloop=nloop, group=group,
            nadmm=nadmm,
        )
        if self.tracer is not None:
            self.tracer.instant(
                "fault:quarantine", clients=ids, nloop=nloop, group=group,
                nadmm=nadmm,
            )
        if self.verbose:
            print(
                f"QUARANTINE clients={ids} nloop={nloop} group={group} "
                f"nadmm={nadmm}"
            )

    def client_times(self, pct: dict, *, nloop, group, nadmm) -> None:
        """Simulated client-time tail of one consensus round's local work.

        `pct` carries the per-client time percentiles (`p50`/`p95`/`p99`,
        seconds of SIMULATED compute: steps × step_time × speed —
        fault/plan.py's speed axis), the slowest client (`max`) and the
        round's simulated wall `round` — `min(max, deadline)` when
        deadline rounds are on, since the coordinator closes the round
        at the deadline instead of waiting out the tail. Recorded only
        for heterogeneous or deadline runs, so homogeneous streams stay
        byte-identical (engine/trainer.py `_hetero_enabled`).
        """
        vals = {k: float(v) for k, v in pct.items()}
        self.log("client_time", vals, nloop=nloop, group=group, nadmm=nadmm)
        if self.verbose:
            print(
                f"client_time nloop={nloop} group={group} nadmm={nadmm} "
                + " ".join(f"{k}={v:.3f}" for k, v in vals.items())
            )

    def step_budgets(self, budgets, *, nloop, group, nadmm) -> None:
        """Per-client inner-step budgets of one deadline round (`[K]`).

        What each client could afford before the round deadline
        (fault/injector.py `step_budgets_for_round`); a value below the
        lockstep step count is a deadline miss, zero means the client's
        report never arrived. Only recorded under `--round-deadline`.
        """
        vals = [int(b) for b in budgets]
        self.log("step_budget", vals, nloop=nloop, group=group, nadmm=nadmm)
        if self.verbose:
            print(
                f"step_budget nloop={nloop} group={group} nadmm={nadmm} "
                + ",".join(str(v) for v in vals)
            )

    def deadline_miss(self, clients, *, nloop, group, nadmm) -> None:
        """Clients whose step budget fell short of the full lockstep
        count at one exchange — they contributed a PARTIAL update (or,
        at budget zero, none at all). Mirrors `quarantine` (trace
        instant + grep-able line) but is its own series: a miss is
        graceful degradation, not a failure or a defense.
        """
        ids = [int(c) for c in clients]
        self.log(
            "deadline_miss", {"clients": ids}, nloop=nloop, group=group,
            nadmm=nadmm,
        )
        if self.tracer is not None:
            self.tracer.instant(
                "fault:deadline_miss", clients=ids, nloop=nloop, group=group,
                nadmm=nadmm,
            )
        if self.verbose:
            print(
                f"DEADLINE_MISS clients={ids} nloop={nloop} group={group} "
                f"nadmm={nadmm}"
            )

    def cohort(self, ids, *, nloop) -> None:
        """The virtual-client cohort gathered for one outer loop (`[C]`
        ascending virtual ids — clients/cohort.py).

        Slot `s` of every other per-client series of the loop
        (train_loss columns, update_norm, step_budget, fault/quarantine
        client lists) refers to virtual client `ids[s]`: this record is
        the slot→virtual-id key. Only recorded in cohort mode, so
        legacy-mode streams stay byte-identical (and the identity-
        sampling bitwise bridge compares trajectories, not this series).
        """
        vals = [int(i) for i in ids]
        self.log("cohort", {"clients": vals}, nloop=nloop)
        if self.verbose:
            ids_s = ",".join(str(v) for v in vals)
            print(f"cohort nloop={nloop} clients={ids_s}")

    def group_distance(self, dists, *, nloop, group) -> None:
        """Per-group distance-from-mean diagnostic (`[num_groups]`).

        The series `parallel/diagnostics.py group_distances` feeds when
        the trainer's `--diagnostics-every N` cadence is on — the
        reference defines the equivalent `distance_of_layers` but never
        calls it (reference src/federated_trio.py:170-186).
        """
        vals = [float(v) for v in dists]
        self.log("group_distance", vals, nloop=nloop, group=group)
        if self.verbose:
            print(
                f"group_distance nloop={nloop} group={group} "
                + ",".join(f"{v:e}" for v in vals)
            )

    def latest(self, name: str):
        if not self.series.get(name):
            return None
        rec = self.series[name][-1]
        if isinstance(rec["value"], Deferred):
            rec["value"] = rec["value"].resolve()
        return rec["value"]

    def to_json(self) -> str:
        """The full store as JSON: `{"series": ..., "first_nonfinite": ...}`.

        The envelope carries the poisoned-round cursor alongside the
        series — a bare-series dump would lose exactly the record a
        post-mortem of a `--metrics-out` file needs. Deferred values are
        materialized first (a thunk is not JSON).
        """
        self._materialize()
        return json.dumps(
            {"series": self.series, "first_nonfinite": self.first_nonfinite}
        )

    def save(self, path: str) -> None:
        """Atomic write (tmp + rename, the `utils/checkpoint.py` pattern):
        a crash mid-write replaces the file completely or not at all,
        never with torn JSON."""
        path = os.path.abspath(path)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(self.to_json())
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
