"""Observability: sinks, ledger, traces, health, roofline, registry,
flight recorder, memory telemetry, live console, provenance.

Ten pillars over the structured metric store (`utils/metrics.py`):

* `JsonlSink` — a crash-safe append-only JSONL metric stream with
  per-outer-loop commit markers; `resume='auto'` replays it and truncates
  to the restore point, so a chaos run's metric series is continuous
  across crashes (sinks.py);
* `CommLedger` — exact per-round communicated bytes from the static
  `Partition` spec, dtype, and participation masks: the quantity the
  paper's bandwidth claim is about, finally measured (ledger.py);
* `TraceRecorder` / `DispatchCounter` — host-side span recording exported
  as Chrome trace-event JSON (loadable in Perfetto) plus dispatch- and
  recompile-count series, so fusion regressions show up as metrics
  (trace.py);
* `HealthEngine` / `PercentileSketch` — streaming in-run statistics
  (P²-style online percentile sketches over loss / update norms /
  client-time tails) and a windowed anomaly monitor emitting a `health`
  series + `health:*` trace instants, replay-identical across crash and
  resume (health.py);
* `lbfgs_round_cost` / `roofline_record` / `chip_peaks` — the analytic
  per-round cost model and achieved-utilization accounting behind the
  trainer's `roofline` record (roofline.py);
* `RunRegistry` — the cross-run experiment registry behind the
  `python -m federated_pytorch_test_tpu report` CLI: validated stream
  ingestion, round-aligned comparisons, and the convergence-vs-bytes
  frontier (registry.py);
* `FlightRecorder` — a bounded ring over exactly the records the JSONL
  sink persists, dumped as self-contained `incident-<nloop>-<round>.json`
  bundles when the health engine fires or the process dies mid-run
  (flight.py; `report --incidents` tables them);
* `memory_record` / `host_rss_peak_bytes` — host RSS + per-device
  allocator stats as the process-local `memory` series and the
  bounded-RSS evidence ROADMAP item 4 gates on (memory.py);
* `watch_main` — the `watch` CLI verb: a refreshing terminal dashboard
  tailing metric streams through the registry's validated ingestion
  (console.py);
* `provenance_stamp` / `provenance_class` — the self-describing stamp
  (commit, backend, chip, host) attached to the trainer's status
  sidecar and `roofline` record and to the chaos verb's artifacts, and
  the class (`tpu`, `cpu_twin`, `unstamped`) `report` lists a run
  under (provenance.py).

Beside them, `phases.py` (imported as a module, not re-exported): the
closed list of `fedtpu.<phase>` named scopes inside the round program
and the reduction of a `--profile-dir` window to device seconds by
phase.
"""

from federated_pytorch_test_tpu.obs.console import render, watch_main
from federated_pytorch_test_tpu.obs.flight import (
    MAX_INCIDENTS,
    FlightRecorder,
    incidents_dir,
    list_incidents,
    validate_incident,
)
from federated_pytorch_test_tpu.obs.health import (
    DEADLINE_WARMUP_OBS,
    DeadlineController,
    HealthEngine,
    P2Quantile,
    PercentileSketch,
)
from federated_pytorch_test_tpu.obs.ledger import CommLedger
from federated_pytorch_test_tpu.obs.memory import (
    device_memory_stats,
    host_rss_bytes,
    host_rss_peak_bytes,
    memory_record,
)
from federated_pytorch_test_tpu.obs.provenance import (
    STAMP_KEYS,
    cached_stamp,
    git_info,
    host_stamp,
    provenance_class,
    provenance_stamp,
)
from federated_pytorch_test_tpu.obs.registry import (
    RunRegistry,
    StreamRefused,
    read_stream,
    render_markdown,
    report_main,
)
from federated_pytorch_test_tpu.obs.roofline import (
    CHIP_PEAKS,
    chip_peaks,
    lbfgs_round_cost,
    roofline_record,
)
from federated_pytorch_test_tpu.obs.sinks import JsonlSink
from federated_pytorch_test_tpu.obs.trace import DispatchCounter, TraceRecorder

__all__ = [
    "CHIP_PEAKS",
    "CommLedger",
    "DEADLINE_WARMUP_OBS",
    "DeadlineController",
    "DispatchCounter",
    "FlightRecorder",
    "HealthEngine",
    "JsonlSink",
    "MAX_INCIDENTS",
    "P2Quantile",
    "PercentileSketch",
    "RunRegistry",
    "STAMP_KEYS",
    "StreamRefused",
    "TraceRecorder",
    "cached_stamp",
    "chip_peaks",
    "device_memory_stats",
    "git_info",
    "host_rss_bytes",
    "host_rss_peak_bytes",
    "host_stamp",
    "incidents_dir",
    "lbfgs_round_cost",
    "list_incidents",
    "memory_record",
    "provenance_class",
    "provenance_stamp",
    "read_stream",
    "render",
    "render_markdown",
    "report_main",
    "roofline_record",
    "watch_main",
]
