"""The bench trend database behind the `trend` CLI verb.

The BENCH_r0N trajectory was unqueryable prose: five wrapper files at
the repo root (four removed in PR 21 with the runtime they were taken
on; the torn r03 stays as the ingestion test case), one torn payload
(round 3's ~3KB headline truncated
mid-JSON and recorded as `parsed: null`), and no machine anywhere that
notices a regression — or a CPU number masquerading as a TPU result —
before it lands. This module is obs/ part 4's data layer:

* **tolerant ingestion** of every measurement artifact the repo emits:
  the driver's `{n, cmd, rc, tail, parsed}` BENCH wrappers (a missing
  or torn `parsed` payload is skipped with a named warning, the
  registry's torn-tail rule applied to benchmarks — never a crash),
  bare bench.py headline JSONs, `benchmarks/full_*_tpu.json` schedule
  artifacts, `benchmarks/*scaling*_tpu*.json` sweep artifacts, and the
  CI preflight/tier-walls JSON (scripts/ci.sh);
* an **append-only trend store** (one JSON line per measurement record,
  content-digest deduplicated — re-ingesting the same files adds
  nothing, so the report is byte-identical on re-ingest) keyed by
  `(metric, provenance class)` (obs/provenance.py);
* a **deterministic trajectory report** (JSON + markdown, sorted keys,
  no wall-clock content) with noise-aware per-point deltas — the
  bench headline's `sps_p25/p75` dispersion becomes each point's
  relative noise band;
* the **regression sentinel**: a directional metric that worsens
  beyond its noise band vs the LAST baseline of the SAME provenance
  class is flagged. CPU-twin compares against CPU-twin, TPU against
  TPU, and unstamped (pre-provenance) history only against itself —
  never across;
* **debt closing**: an ingested record whose provenance satisfies a
  DEBT.json entry's owed condition AND carries the owed metric closes
  the entry (obs/debt.py) — the first TPU session burns the queue down
  by just running it.

Like `report`/`watch`/`scrub`, the verb is pure host-side file
analysis: no engine import, no accelerator backend init.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import warnings
from typing import List, Optional, Tuple

from federated_pytorch_test_tpu.obs.provenance import (
    STAMP_KEYS,
    provenance_class,
)

TREND_VERSION = 1
STORE_SCHEMA = 1

# The sentinel's noise-band floor: relative change a directional metric
# may move between consecutive same-class points before it flags, when
# no measured dispersion says otherwise. Deliberately wide — BASELINE.md
# records single flagship draws ranging 160-2600 samples/s on the
# shared chip; the measured sps_p25/p75 band widens (never narrows
# below) this floor.
REL_NOISE_FLOOR = 0.25

# metric names that are facts/knobs, not performance — never sentineled
NEUTRAL_METRICS = {
    "batch",
    "repeats",
    "n",
    "n_clients",
    "nloop",
    "linesearch_probes",
    "effective_gemm_m",
    "round_dispatches",
    "rounds_evaluated",
    "store_resident_chunks",
    "store_evictions",
    "threshold_pcpu",
}

_HIGHER_TOKENS = (
    "speedup",
    "throughput",
    "samples_per_sec",
    "sps",
    "mfu",
    "tflops",
    "pct_peak",
    "accuracy",
    "acc_",
    "efficiency",
    "scaling",
    "vs_baseline",
    "savings",
    "gain",
    "passed",
    "hbm_frac",
    "flat_in_n",
)
_LOWER_TOKENS = (
    "time",
    "wall",
    "overhead",
    "rss",
    "seconds",
    "bytes",
    "evals_per_step",
    "stray_cpu_hogs",
)


class TrendRefused(ValueError):
    """A file `trend` cannot treat as a measurement (named reason)."""


def metric_direction(name: str) -> Optional[str]:
    """`'higher'` / `'lower'` = which way is better, `None` = neutral
    (never sentineled). Namespaced metrics (`full_fedavg_tpu:wall_
    seconds`) are judged by their base name."""
    base = name.rsplit(":", 1)[-1]
    if base in NEUTRAL_METRICS:
        return None
    for tok in _HIGHER_TOKENS:
        if tok in base:
            return "higher"
    for tok in _LOWER_TOKENS:
        if tok in base:
            return "lower"
    return None


def _numeric_items(doc: dict) -> dict:
    return {
        k: v
        for k, v in doc.items()
        if isinstance(v, (int, float)) and not isinstance(v, bool)
    }


def _trim_stamp(prov) -> Optional[dict]:
    if not isinstance(prov, dict):
        return None
    return {k: prov.get(k) for k in STAMP_KEYS}


def _headline_measurement(parsed: dict, source: str) -> dict:
    """One bench.py headline -> a trend record's metrics/spread."""
    metrics = _numeric_items(parsed)
    spread = {}
    name = parsed.get("metric")
    value = metrics.pop("value", None)
    if isinstance(name, str) and value is not None:
        metrics[name] = value
        p25, p75 = metrics.pop("sps_p25", None), metrics.pop("sps_p75", None)
        if p25 is not None and p75 is not None and value:
            # the headline's measured dispersion, as the primary
            # metric's relative noise band
            spread[name] = round(abs(p75 - p25) / abs(value), 4)
    return {
        "source": source,
        "order": parsed.get("n"),
        "metrics": metrics,
        "spread": spread,
        "provenance": _trim_stamp(parsed.get("provenance")),
    }


def extract_measurement(doc, source: str) -> dict:
    """One artifact JSON -> one trend record (no store fields yet).

    Raises `TrendRefused` (with the file and reason named) for torn or
    unrecognized documents — directory ingestion downgrades that to a
    warning, the registry's skip-with-a-named-warning idiom.
    """
    stem = os.path.splitext(os.path.basename(source))[0]
    if not isinstance(doc, dict):
        raise TrendRefused(f"{source}: not a JSON object")

    # the driver's BENCH wrapper: {n, cmd, rc, tail, parsed}
    if "parsed" in doc and "cmd" in doc:
        parsed = doc.get("parsed")
        if not isinstance(parsed, dict):
            raise TrendRefused(
                f"{source}: wrapper parsed payload missing or torn "
                f"(rc={doc.get('rc')}) — skipping, tail not trusted"
            )
        rec = _headline_measurement(parsed, stem)
        if rec.get("order") is None:
            rec["order"] = doc.get("n")
        return rec

    # a bare bench.py headline (or bench_full.json's top level)
    if "metric" in doc and "value" in doc and "unit" in doc:
        return _headline_measurement(doc, stem)

    # benchmarks/full_schedule_tpu.py artifact
    if "experiment" in doc:
        metrics = {}
        for key in (
            "wall_seconds",
            "epoch_step_time_median_s",
            "fused_round_time_median_s",
        ):
            if isinstance(doc.get(key), (int, float)):
                metrics[f"{stem}:{key}"] = doc[key]
        curve = doc.get("acc_mean_per_round")
        if isinstance(curve, list) and curve:
            metrics[f"{stem}:final_acc_mean"] = curve[-1]
        if not metrics:
            raise TrendRefused(f"{source}: schedule artifact has no walls")
        return {
            "source": stem,
            "order": None,
            "metrics": metrics,
            "spread": {},
            "provenance": _trim_stamp(doc.get("provenance")),
        }

    # benchmarks/client_scaling_tpu.py / cohort sweep artifact. Older
    # committed generations spelled the keys per-client
    # (`samples_per_sec_per_client`, `scaling_efficiency_vs_k3`) before
    # the per-device rename — both generations ingest.
    if "workload" in doc and isinstance(doc.get("rows"), list):
        def _column(*names):
            vals = []
            for r in doc["rows"]:
                if not isinstance(r, dict):
                    continue
                for name in names:
                    v = r.get(name)
                    if isinstance(v, (int, float)) and not isinstance(v, bool):
                        vals.append(v)
                        break
            return vals

        sps = _column("samples_per_sec_per_device", "samples_per_sec_per_client")
        eff = _column("scaling_efficiency", "scaling_efficiency_vs_k3")
        flat = _column("flat_in_n")
        metrics = {}
        if sps:
            metrics[f"{stem}:samples_per_sec_per_device_max"] = max(sps)
        if eff:
            metrics[f"{stem}:scaling_efficiency_min"] = min(eff)
        if flat:
            metrics[f"{stem}:flat_in_n_min"] = min(flat)
        if metrics:
            return {
                "source": stem,
                "order": None,
                "metrics": metrics,
                "spread": {},
                "provenance": _trim_stamp(doc.get("provenance")),
            }
        # unknown row schema: fall through to top-level numeric facts

    # other benchmarks/ artifacts (stream overlap, ...): numeric
    # top-level facts, namespaced by stem
    if "workload" in doc:
        metrics = {
            f"{stem}:{k}": v for k, v in sorted(_numeric_items(doc).items())
        }
        if not metrics:
            raise TrendRefused(f"{source}: workload artifact has no numbers")
        return {
            "source": stem,
            "order": None,
            "metrics": metrics,
            "spread": {},
            "provenance": _trim_stamp(doc.get("provenance")),
        }

    # scripts/ci.sh preflight + per-tier walls JSON
    if "tiers" in doc or "stray_cpu_hogs" in doc:
        metrics = {}
        for tier in doc.get("tiers") or []:
            if not isinstance(tier, dict) or "tier" not in tier:
                continue
            label = str(tier["tier"])
            if isinstance(tier.get("wall_s"), (int, float)):
                metrics[f"ci_{label}_wall_s"] = tier["wall_s"]
            if isinstance(tier.get("passed"), (int, float)):
                metrics[f"ci_{label}_passed"] = tier["passed"]
        hogs = doc.get("stray_cpu_hogs")
        if isinstance(hogs, list):
            metrics["ci_stray_cpu_hogs"] = len(hogs)
        if not metrics:
            raise TrendRefused(f"{source}: preflight JSON has no tier walls")
        return {
            "source": stem,
            "order": None,
            "metrics": metrics,
            "spread": {},
            "provenance": _trim_stamp(doc.get("provenance")),
        }

    raise TrendRefused(f"{source}: unrecognized measurement document")


def _record_digest(rec: dict) -> str:
    """Content digest for append-only dedup: a record re-ingested from
    the same bytes is the same record, whatever session ingests it."""
    canon = json.dumps(
        {k: rec.get(k) for k in ("source", "order", "metrics", "spread",
                                 "provenance")},
        sort_keys=True,
    )
    return hashlib.sha1(canon.encode()).hexdigest()[:16]


class BenchDB:
    """The append-only trend store: one JSON line per measurement."""

    def __init__(self, store_path: str):
        self.store_path = store_path
        self.records: List[dict] = []
        self._digests = set()
        self._load()

    def _load(self) -> None:
        try:
            f = open(self.store_path)
        except OSError:
            return
        with f:
            for ln, line in enumerate(f, 1):
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    # the store is append-only and line-buffered: only a
                    # torn final line is legitimate; nothing after the
                    # first unparsable line is trusted (the stream rule)
                    warnings.warn(
                        f"{self.store_path}: torn store line {ln} — "
                        "dropping it and everything after"
                    )
                    break
                self.records.append(rec)
                self._digests.add(rec.get("digest"))

    # -- ingestion ----------------------------------------------------
    def ingest_doc(self, doc, source: str) -> Optional[dict]:
        """Ingest one parsed artifact; returns the appended record or
        None when it deduplicated against the store."""
        rec = extract_measurement(doc, source)
        rec["schema"] = STORE_SCHEMA
        rec["class"] = provenance_class(rec.get("provenance"))
        rec["digest"] = _record_digest(rec)
        if rec["digest"] in self._digests:
            return None
        self.records.append(rec)
        self._digests.add(rec["digest"])
        with open(self.store_path, "a") as f:
            f.write(json.dumps(rec, sort_keys=True) + "\n")
        return rec

    def ingest_path(self, path: str) -> Optional[dict]:
        try:
            with open(path) as f:
                doc = json.load(f)
        except OSError as e:
            raise TrendRefused(f"{path}: unreadable ({e})")
        except ValueError as e:
            raise TrendRefused(f"{path}: not JSON ({e})")
        return self.ingest_doc(doc, path)

    def ingest(self, paths) -> Tuple[int, int]:
        """Files and directories -> `(added, skipped)`. Directories are
        scanned for `BENCH_*.json` wrappers and `benchmarks/*_tpu*.json`
        artifacts; every refusal is a named warning, never a crash —
        one torn wrapper must not cost the rest of the trajectory."""
        files: List[str] = []
        for p in paths:
            if os.path.isdir(p):
                names = sorted(os.listdir(p))
                files += [
                    os.path.join(p, n)
                    for n in names
                    if n.startswith("BENCH_") and n.endswith(".json")
                ]
                bdir = os.path.join(p, "benchmarks")
                if os.path.isdir(bdir):
                    files += [
                        os.path.join(bdir, n)
                        for n in sorted(os.listdir(bdir))
                        if n.endswith(".json") and "_tpu" in n
                    ]
            else:
                files.append(p)
        added = skipped = 0
        for path in files:
            try:
                rec = self.ingest_path(path)
            except TrendRefused as e:
                warnings.warn(str(e))
                skipped += 1
                continue
            if rec is None:
                skipped += 1
            else:
                added += 1
        return added, skipped

    # -- the trajectory report ---------------------------------------
    def report(self) -> dict:
        """The deterministic trajectory document: a pure function of
        the store's record content (sorted keys, no wall-clock, no
        hostnames) — byte-identical however many times the same files
        were re-ingested."""
        classes: dict = {}
        series: dict = {}
        for seq, rec in enumerate(self.records):
            cls = rec.get("class", "unstamped")
            classes[cls] = classes.get(cls, 0) + 1
            noise = rec.get("spread") or {}
            for metric, value in sorted((rec.get("metrics") or {}).items()):
                point = {
                    "seq": seq,
                    "source": rec.get("source"),
                    "value": value,
                }
                if metric in noise:
                    point["noise_rel"] = noise[metric]
                series.setdefault(metric, {}).setdefault(cls, []).append(
                    point
                )

        regressions: List[dict] = []
        checked = 0
        metrics_doc: dict = {}
        for metric in sorted(series):
            direction = metric_direction(metric)
            per_class: dict = {}
            for cls in sorted(series[metric]):
                points = series[metric][cls]
                for prev, cur in zip(points, points[1:]):
                    if prev["value"]:
                        cur["delta_rel"] = round(
                            (cur["value"] - prev["value"]) / abs(prev["value"]),
                            4,
                        )
                    if direction is None:
                        continue
                    checked += 1
                    band = max(
                        REL_NOISE_FLOOR,
                        prev.get("noise_rel", 0.0),
                        cur.get("noise_rel", 0.0),
                    )
                    if not prev["value"]:
                        continue
                    change = (cur["value"] - prev["value"]) / abs(prev["value"])
                    worse = (
                        change < -band
                        if direction == "higher"
                        else change > band
                    )
                    if worse:
                        cur["flagged"] = True
                        regressions.append(
                            {
                                "metric": metric,
                                "class": cls,
                                "source": cur["source"],
                                "value": cur["value"],
                                "baseline_source": prev["source"],
                                "baseline": prev["value"],
                                "change_rel": round(change, 4),
                                "band_rel": round(band, 4),
                                "direction": direction,
                            }
                        )
                per_class[cls] = {
                    "points": points,
                    "last": points[-1]["value"],
                }
            metrics_doc[metric] = {
                "direction": direction,
                "classes": per_class,
            }
        return {
            "trend_version": TREND_VERSION,
            "records": len(self.records),
            "classes": {k: classes[k] for k in sorted(classes)},
            "metrics": metrics_doc,
            "sentinel": {
                "checked_deltas": checked,
                "noise_floor_rel": REL_NOISE_FLOOR,
                "regressions": regressions,
                "pass": not regressions,
            },
        }


def render_trend_markdown(doc: dict) -> str:
    """The trajectory as markdown tables, one per (metric, class)."""
    out = [
        "# Bench trend",
        "",
        f"{doc['records']} measurement record(s); classes: "
        + ", ".join(f"{k}={v}" for k, v in doc["classes"].items()),
        "",
    ]
    sent = doc["sentinel"]
    if sent["pass"]:
        out.append(
            f"**Regression sentinel: PASS** "
            f"({sent['checked_deltas']} delta(s) checked, noise floor "
            f"±{int(sent['noise_floor_rel'] * 100)}%)"
        )
    else:
        out.append(
            f"**Regression sentinel: {len(sent['regressions'])} "
            "REGRESSION(S)**"
        )
        for r in sent["regressions"]:
            out.append(
                f"- `{r['metric']}` [{r['class']}]: {r['baseline']} "
                f"({r['baseline_source']}) -> {r['value']} "
                f"({r['source']}), {r['change_rel']:+.1%} vs a "
                f"±{r['band_rel']:.0%} band"
            )
    out.append("")
    for metric, m in doc["metrics"].items():
        arrow = {"higher": "↑ better", "lower": "↓ better", None: "neutral"}[
            m["direction"]
        ]
        out.append(f"## {metric}  ({arrow})")
        out.append("")
        out.append("| class | source | value | delta | flag |")
        out.append("|---|---|---|---|---|")
        for cls, block in m["classes"].items():
            for p in block["points"]:
                delta = (
                    f"{p['delta_rel']:+.1%}" if "delta_rel" in p else "-"
                )
                flag = "REGRESSION" if p.get("flagged") else ""
                out.append(
                    f"| {cls} | {p['source']} | {p['value']} | {delta} "
                    f"| {flag} |"
                )
        out.append("")
    return "\n".join(out) + "\n"


def trend_main(argv=None) -> int:
    """`python -m federated_pytorch_test_tpu trend [PATHS...]`."""
    ap = argparse.ArgumentParser(
        prog="federated_pytorch_test_tpu trend",
        description="ingest BENCH wrappers / benchmark artifacts into "
        "the append-only trend store and report the per-metric, "
        "per-provenance-class trajectory with the regression sentinel",
    )
    ap.add_argument(
        "paths",
        nargs="*",
        default=["."],
        help="files or directories to ingest (dirs scan BENCH_*.json "
        "and benchmarks/*_tpu*.json); default: the current directory",
    )
    ap.add_argument(
        "--store",
        default="TREND.jsonl",
        help="append-only trend store path (default TREND.jsonl)",
    )
    ap.add_argument(
        "--debt",
        default=None,
        help="DEBT.json to close against newly-ingested provenanced "
        "measurements (default: ./DEBT.json when present; 'none' "
        "disables debt closing)",
    )
    ap.add_argument("--json", dest="json_out", default=None)
    ap.add_argument("--md", dest="md_out", default=None)
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)

    db = BenchDB(args.store)
    before = len(db.records)
    added, skipped = db.ingest(args.paths or ["."])

    debt_path = args.debt
    if debt_path is None and os.path.exists("DEBT.json"):
        debt_path = "DEBT.json"
    closed = []
    if debt_path and debt_path != "none" and os.path.exists(debt_path):
        from federated_pytorch_test_tpu.obs.debt import (
            close_entries,
            load_debt,
            save_debt,
        )

        try:
            doc = load_debt(debt_path)
        except ValueError as e:
            # a broken ledger must not cost the trend report
            warnings.warn(f"debt ledger unreadable, not closing: {e}")
            doc = None
        if doc is not None:
            for rec in db.records[before:]:
                closed += close_entries(doc, rec)
            if closed:
                save_debt(debt_path, doc)

    report = db.report()
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
            f.write("\n")
    md = render_trend_markdown(report)
    if args.md_out:
        with open(args.md_out, "w") as f:
            f.write(md)
    if not args.quiet:
        print(md, end="")
    sent = report["sentinel"]
    print(
        f"trend: {added} ingested, {skipped} skipped/deduped, "
        f"{report['records']} in store ({args.store}); sentinel "
        + ("PASS" if sent["pass"] else f"{len(sent['regressions'])} "
           "REGRESSION(S)")
        + (f"; debt closed: {', '.join(closed)}" if closed else "")
    )
    return 0 if sent["pass"] else 1
