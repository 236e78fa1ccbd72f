#!/usr/bin/env bash
# CI gate, tiered (markers declared in pyproject.toml):
#
#   tier 0  pytest -m smoke        — <2 min on the virtual 8-device CPU
#                                    mesh: kernels, consensus math,
#                                    collectives, fault-plan purity,
#                                    obs units (JSONL sink truncation,
#                                    comm-ledger arithmetic, trace JSON,
#                                    deferred-record queue mechanics)
#   tier 1  pytest -m 'not slow'   — the DEFAULT budgeted gate (the
#                                    driver's verify command): smoke plus
#                                    the middle tier (partition, models,
#                                    trainer-level chaos, fused-round
#                                    bit-identity, crash/resume metric-
#                                    stream continuity, dispatch/trace
#                                    integration — tests/test_obs.py —
#                                    and the eval-tail contracts: the
#                                    folded-round dispatch-budget gate
#                                    (dispatch_count == {round:1,
#                                    round_init:1}), cross-eval-mode
#                                    stream identity, rollback eval
#                                    discard — tests/test_fold_eval.py),
#                                    ~7 min
#   tier 2  pytest -m slow         — full integration (~20+ min): engine
#                                    sweeps, resnet-engine runs,
#                                    streaming-equivalence, Pallas
#                                    interpret kernels, ring, 2- and
#                                    4-process distributed runs, the
#                                    heavy heterogeneity contracts
#                                    (tests/test_hetero.py slow tier:
#                                    admm/BB uniform-budget bitwise,
#                                    ragged + corruption + trimmed +
#                                    quarantine composition, crash/
#                                    resume stream identity with
#                                    deadline records), plus the CLI
#                                    smokes below: byzantine_smoke
#                                    (corruption plan + trimmed combiner
#                                    + quarantine + planned crash,
#                                    recovered end to end with --resume
#                                    auto) and hetero_smoke (speed-
#                                    heterogeneous plan + round deadline
#                                    + trimmed combiner + planned crash,
#                                    recovered via rerun, crashed+resumed
#                                    stream identical to the
#                                    uninterrupted twin's), bf16_smoke
#                                    (bf16 exchange codec + trimmed
#                                    combiner + corruption + quarantine
#                                    + planned crash recovered via
#                                    rerun, halved comm ledger asserted
#                                    on the stream), codec_smoke (the
#                                    codec-zoo frontier probe: identity/
#                                    topk(0.1)+EF+adaptive/q8 sweep over
#                                    one corruption+dropout plan, topk
#                                    crashed + resumed with twin stream
#                                    identity incl. group_schedule
#                                    records, `report` gating the
#                                    <=25%-bytes / within-2-points
#                                    frontier acceptance) and
#                                    cohort_smoke (10k virtual clients,
#                                    C=8 cohorts, dropout+corruption
#                                    keyed by virtual id, trimmed
#                                    combiner, planned crash recovered
#                                    via rerun — store manifest + stream
#                                    + cohort sequence all splice, twin
#                                    stream-identity asserted),
#                                    spill_smoke (the million-client
#                                    shape: N=1M lazy virtual clients,
#                                    --store-resident-chunks pinned to 2
#                                    so evictions/spills fire, planned
#                                    crash recovered via rerun with twin
#                                    stream identity, and the bounded-
#                                    RSS gate — sidecar peak RSS at 1M
#                                    within 1.25x of the 10k run's),
#                                    fleet_smoke (the closed loop at 10k
#                                    virtual clients: churn + speed +
#                                    corruption plan, --round-deadline
#                                    auto, telemetry-weighted cohorts,
#                                    planned crash recovered via rerun
#                                    with twin stream-compare over the
#                                    deadline/availability/cohort_weight
#                                    records) and
#                                    report_smoke (f32-vs-bf16 codec
#                                    sweep through the `report` CLI:
#                                    convergence-vs-bytes frontier with
#                                    exactly-halved bf16 uplink, and the
#                                    crashed+resumed sweep's report
#                                    byte-identical to the twin's) and
#                                    incident_smoke (flight recorder
#                                    through the real CLI: corruption
#                                    plan -> health fires -> incident
#                                    bundle written + schema-validated
#                                    + in-bundle series == stream tail,
#                                    real anomaly-armed jax.profiler
#                                    capture, `report --incidents`
#                                    table, `watch --once` renders) and
#                                    integrity_smoke (storage chaos at
#                                    100k clients: bitrot plan + planned
#                                    crash recovered via rerun with twin
#                                    stream identity, transient-ioerror
#                                    write plan survived via retry,
#                                    scrub detect-then-repair, nonzero
#                                    storage_faults= scoreboard) and
#                                    widened_smoke (the widened client
#                                    GEMM — docs/PERF.md §Widened GEMM:
#                                    --client-fold gemm with a P=4 probe
#                                    fan under dropout+corruption +
#                                    trimmed(1) + topk codec, planned
#                                    crash recovered via rerun with twin
#                                    stream identity and the per-round
#                                    {round: 1} dispatch budget held on
#                                    the stream, then a --client-fold
#                                    vmap rerun whose stream matches the
#                                    gemm twin's bitwise modulo the
#                                    fold-mode tag — the documented
#                                    CPU tolerance)
#                                    and chaos_smoke (the chaos HARNESS
#                                    — fault/chaos.py: a fixed-seed
#                                    soak of composed fuzzer-drawn
#                                    plans must clear the invariant
#                                    oracle clean, then a deliberately
#                                    broken robust combiner
#                                    (CHAOS_PLANT_BUG=combiner) must be
#                                    CAUGHT, SHRUNK to a <=2-axis repro
#                                    bundle, and REPLAYED from the
#                                    bundle via chaos --repro — the
#                                    oracle's own false-negative test)
#
# Every tier starts with a PREFLIGHT stray-process check (see
# preflight() below): the tier-1 wall sits within ~10 s of the driver's
# 870 s timeout, and a leftover benchmark process eating a host core
# has silently inflated it before. Findings are recorded as JSON in
# $CI_PREFLIGHT_JSON (default ci_preflight.json) for the round's CI
# artifact — and every pytest tier run through run_tier() APPENDS its
# suite wall + pass count to the same file, so the tier-1-at-the-edge
# trend (PR 10 note) is data, not anecdote.
#
# Usage:
#   scripts/ci.sh            # tier 1 then tier 2 (both tiers, full CI)
#   CI_TIER=1 scripts/ci.sh  # tier 1 only (the under-budget default gate)
#   CI_TIER=0 scripts/ci.sh  # smoke only (the old fast gate)
#   CI_TIER=2 scripts/ci.sh  # slow tier only
#
# tests/conftest.py forces the CPU platform and 8 virtual devices, so no
# TPU is needed; the persistent compile cache amortizes repeat runs.
set -euo pipefail
cd "$(dirname "$0")/.."

preflight() {
  # Stray-CPU-hog check BEFORE the suite starts: a leftover benchmark
  # process from a crashed session once ate one of the two host cores
  # for hours and silently inflated the tier-1 wall to within seconds
  # of the driver's 870 s timeout (CHANGES.md PR 9 session note). Warn
  # loudly and record the finding as JSON ($CI_PREFLIGHT_JSON, default
  # ci_preflight.json — embed it in the round's CI_r*.json artifact) so
  # a slow suite can be told apart from a contended host after the fact.
  local out="${CI_PREFLIGHT_JSON:-ci_preflight.json}"
  python - "$out" <<'PY' || true
import json, os, subprocess, sys

me, shell = os.getpid(), os.getppid()
hogs, err = [], None
try:
    ps = subprocess.run(
        ["ps", "-eo", "pid,ppid,pcpu,comm"],
        capture_output=True, text=True, timeout=10,
    ).stdout
    for line in ps.splitlines()[1:]:
        parts = line.split(None, 3)
        if len(parts) < 4:
            continue
        try:
            pid, ppid, pcpu = int(parts[0]), int(parts[1]), float(parts[2])
        except ValueError:
            continue
        if pid in (me, shell) or ppid == me:
            continue  # this check and its shell are not strays
        if pcpu > 50.0:
            hogs.append({"pid": pid, "pcpu": pcpu, "comm": parts[3]})
except Exception as e:  # a broken ps must not block CI
    err = f"{type(e).__name__}: {e}"[:200]
doc = {"threshold_pcpu": 50.0, "stray_cpu_hogs": hogs}
if err:
    doc["error"] = err
with open(sys.argv[1], "w") as f:
    json.dump(doc, f, indent=1)
    f.write("\n")
if hogs:
    print(
        "CI PREFLIGHT WARNING: stray process(es) eating >50% of a host "
        "core before the suite starts:", file=sys.stderr,
    )
    for h in hogs:
        print(
            f"  pid={h['pid']} pcpu={h['pcpu']} {h['comm']}",
            file=sys.stderr,
        )
    print(
        "  the tier-1 wall budget sits within ~10 s of the 870 s "
        f"timeout — kill the strays or expect a timeout (recorded in "
        f"{sys.argv[1]})", file=sys.stderr,
    )
PY
}

run_tier() {
  # Run one pytest tier and APPEND {tier, wall_s, passed, rc} to the
  # preflight JSON (ISSUE-14 satellite): the tier-1 wall has sat within
  # tens of seconds of the driver's 870 s timeout since PR 9, and until
  # now the trend lived in CHANGES.md prose. $1: tier label; rest:
  # pytest args.
  local label="$1"; shift
  local log rc t0
  log="$(mktemp)"
  t0=$SECONDS
  set +e
  python -m pytest "$@" 2>&1 | tee "$log"
  rc=${PIPESTATUS[0]}
  set -e
  python - "$label" "$((SECONDS - t0))" "$rc" "$log" \
    "${CI_PREFLIGHT_JSON:-ci_preflight.json}" <<'PY' || true
import json, re, sys

label, wall, rc, log, out = sys.argv[1:6]
passed = 0
for m in re.finditer(r"(\d+) passed", open(log, errors="replace").read()):
    passed = int(m.group(1))
try:
    with open(out) as f:
        doc = json.load(f)
except Exception:
    doc = {}
doc.setdefault("tiers", []).append(
    {"tier": label, "wall_s": int(wall), "passed": passed, "rc": int(rc)}
)
with open(out, "w") as f:
    json.dump(doc, f, indent=1)
    f.write("\n")
print(f"ci: tier {label} wall={wall}s passed={passed} rc={rc} -> {out}")
PY
  rm -f "$log"
  return "$rc"
}

assert_stream_identity() {
  # THE twin-compare normalizer, shared by every smoke that proves
  # crashed+resumed stream identity: records equal modulo wall-clock
  # fields ("t", step_time seconds) and the header tag (the twins'
  # plans legitimately differ by the crash point). $1/$2: the two JSONL
  # streams; $3: extra python asserts evaluated with the normalized
  # record list bound as `recs`.
  python - "$1" "$2" "${3:-}" <<'PY'
import json, sys

def norm(path):
    out = []
    for line in open(path):
        d = json.loads(line)
        d.pop("t", None)
        d.pop("crc", None)
        if d.get("event") == "stream_header":
            d.pop("tag", None)
        if d.get("series") == "step_time":
            d["value"] = {k: v for k, v in d["value"].items() if k != "seconds"}
        out.append(d)
    return out

a, b = norm(sys.argv[1]), norm(sys.argv[2])
assert a == b, f"streams differ: {len(a)} vs {len(b)} records"
if sys.argv[3]:
    exec(sys.argv[3], {"recs": a})
PY
}

byzantine_smoke() {
  # End-to-end Byzantine chaos through the REAL CLI: one client per round
  # sends a 10x-scaled update, trimmed-mean(1) + auto-quarantine defend,
  # and a planned crash at (nloop=1, gid=2, nadmm=0) kills the first run
  # mid-experiment (gid 2 is model net's first train_order group). The
  # recovery procedure is rerunning the IDENTICAL command: --resume auto
  # restores the checkpoint, the metric stream splices, and the run
  # finishes with zero rollback rounds.
  local d; d="$(mktemp -d)"
  local cmd=(python -m federated_pytorch_test_tpu --preset fedavg --quiet
    --synthetic-n-train 240 --synthetic-n-test 60 --batch 40
    --nloop 2 --nadmm 2 --max-groups 1 --eval-batch 30
    --fault-plan "seed=5,corrupt=1:scale:10,crash=1:2:0"
    --robust-agg trimmed --robust-f 1 --quarantine-z 1.0
    --fault-mode rollback --save-model --resume auto
    --checkpoint-dir "$d/ckpt" --metrics-stream "$d/run.jsonl")
  echo "byzantine smoke: expecting the planned crash..."
  if "${cmd[@]}" > "$d/run1.log" 2>&1; then
    echo "byzantine smoke FAILED: the planned crash never fired" >&2
    tail -5 "$d/run1.log" >&2; rm -rf "$d"; return 1
  fi
  echo "byzantine smoke: resuming..."
  "${cmd[@]}" > "$d/run2.log" 2>&1 || {
    echo "byzantine smoke FAILED: resume did not finish" >&2
    tail -20 "$d/run2.log" >&2; rm -rf "$d"; return 1
  }
  # 2 nloops x 1 group x 2 exchanges, one corrupted client each = 4
  grep -q '# faults injected: .*corruptions=4' "$d/run2.log" || {
    echo "byzantine smoke FAILED: missing/incorrect injected-faults line" >&2
    grep '# faults' "$d/run2.log" >&2; rm -rf "$d"; return 1
  }
  if grep -q 'round_rollback' "$d/run.jsonl"; then
    echo "byzantine smoke FAILED: the robust combiner let a round roll back" >&2
    rm -rf "$d"; return 1
  fi
  echo "byzantine smoke OK"
  rm -rf "$d"
}

chaos_smoke() {
  # The chaos HARNESS end to end (fault/chaos.py, ISSUE 20): two legs.
  #
  # Leg 1 — fixed-seed soak: the first handful of fuzzer-drawn composed
  # plans (the three deterministic invariant probes + composed cases)
  # must clear the full invariant oracle with ZERO violations. Every
  # verdict streams to verdicts.jsonl; the chaos_soak.json workload
  # summary is crc-self-verified (it carries a host provenance stamp).
  #
  # Leg 2 — the planted bug: CHAOS_PLANT_BUG=combiner swaps the
  # Byzantine-robust combiner for a naive masked mean that averages
  # NaNs straight in. The harness must CATCH the robust_finite
  # violation (exit 2), SHRINK it to a repro bundle of <= 2 fault axes,
  # REPLAY the bundle to the same violation under the planted bug
  # (chaos --repro, exit 0), and see it NOT reproduce on the honest
  # engine (exit 1) — the oracle's own false-negative test.
  local d t0; d="$(mktemp -d)"; t0=$SECONDS
  echo "chaos smoke: soaking fixed-seed composed plans under the oracle..."
  if ! python -m federated_pytorch_test_tpu chaos \
      --cases 5 --seed 0 --budget-s 900 --out "$d/soak" \
      > "$d/soak.log" 2>&1; then
    echo "chaos smoke FAILED: clean-engine soak found a violation" >&2
    tail -30 "$d/soak.log" >&2; rm -rf "$d"; return 1
  fi
  python - "$d/soak" <<'PY' || { rm -rf "$d"; return 1; }
import json, sys

from federated_pytorch_test_tpu.fault.io import verify_crc

out = sys.argv[1]
doc = json.load(open(f"{out}/chaos_soak.json"))
assert verify_crc(doc), "soak summary failed its own crc"
assert doc["workload"] == "chaos_soak" and doc["violations"] == 0, doc
verdicts = [json.loads(l) for l in open(f"{out}/verdicts.jsonl")]
assert len(verdicts) == doc["cases_cleared"] >= 5
assert all(v["ok"] for v in verdicts)
assert verdicts[0]["provenance"]["backend"] == "cpu"
cov = verdicts[-1]["coverage"]
print(f"chaos smoke: {len(verdicts)} plans clean, axes={sorted(cov['axes'])}")
PY
  echo "chaos smoke: planting a broken combiner..."
  set +e
  CHAOS_PLANT_BUG=combiner python -m federated_pytorch_test_tpu chaos \
    --cases 3 --seed 0 --out "$d/plant" > "$d/plant.log" 2>&1
  local rc=$?
  set -e
  if [ "$rc" -ne 2 ]; then
    echo "chaos smoke FAILED: planted combiner bug not caught (rc=$rc)" >&2
    tail -30 "$d/plant.log" >&2; rm -rf "$d"; return 1
  fi
  local bundle="$d/plant/repro-0000.json"
  python - "$bundle" <<'PY' || { rm -rf "$d"; return 1; }
import json, sys

doc = json.load(open(sys.argv[1]))
axes = doc["case"]["axes"]
assert len(axes) <= 2, f"shrunk repro kept {len(axes)} axes: {axes}"
bad = {v["invariant"] for v in doc["violations"]}
assert "robust_finite" in bad, bad
print(f"chaos smoke: shrunk to axes={axes}, violations={sorted(bad)}")
PY
  echo "chaos smoke: replaying the shrunk bundle..."
  CHAOS_PLANT_BUG=combiner python -m federated_pytorch_test_tpu chaos \
    --repro "$bundle" --out "$d/replay" > "$d/replay.log" 2>&1 || {
    echo "chaos smoke FAILED: bundle did not reproduce under the bug" >&2
    tail -10 "$d/replay.log" >&2; rm -rf "$d"; return 1
  }
  if python -m federated_pytorch_test_tpu chaos \
      --repro "$bundle" --out "$d/replay2" > "$d/replay2.log" 2>&1; then
    echo "chaos smoke FAILED: bundle 'reproduced' on the honest engine" >&2
    tail -10 "$d/replay2.log" >&2; rm -rf "$d"; return 1
  fi
  # feed this smoke's wall to the preflight JSON like run_tier does
  python - chaos_smoke "$((SECONDS - t0))" \
    "${CI_PREFLIGHT_JSON:-ci_preflight.json}" <<'PY' || true
import json, sys

label, wall, out = sys.argv[1:4]
try:
    with open(out) as f:
        doc = json.load(f)
except Exception:
    doc = {}
doc.setdefault("tiers", []).append(
    {"tier": label, "wall_s": int(wall), "passed": 2, "rc": 0}
)
with open(out, "w") as f:
    json.dump(doc, f, indent=1)
    f.write("\n")
PY
  echo "chaos smoke OK"
  rm -rf "$d"
}

hetero_smoke() {
  # End-to-end deadline rounds through the REAL CLI: one 3x slow client
  # per round (speed axis), a round deadline at the nominal full-work
  # time (4 lockstep steps at batch 20: the slow client's budget is 1 —
  # a PARTIAL contribution every exchange), the trimmed combiner riding
  # along, and a planned crash at (nloop=1, gid=2, nadmm=0) killing the
  # first run. Recovery is rerunning the IDENTICAL command; an
  # uninterrupted twin (same plan minus the crash point) then proves
  # crashed+resumed stream identity — client_time/step_budget/
  # deadline_miss records included — modulo wall-clock fields and the
  # header tag the twins legitimately differ in.
  local d; d="$(mktemp -d)"
  local common=(python -m federated_pytorch_test_tpu --preset fedavg --quiet
    --synthetic-n-train 240 --synthetic-n-test 60 --batch 20
    --nloop 2 --nadmm 2 --max-groups 1 --eval-batch 30
    --round-deadline 4 --robust-agg trimmed --robust-f 1
    --fault-mode rollback --save-model --resume auto)
  local cmd=("${common[@]}"
    --fault-plan "seed=6,slow=1:3,crash=1:2:0"
    --checkpoint-dir "$d/ckpt" --metrics-stream "$d/run.jsonl")
  local twin=("${common[@]}"
    --fault-plan "seed=6,slow=1:3"
    --checkpoint-dir "$d/ckpt_twin" --metrics-stream "$d/twin.jsonl")
  echo "hetero smoke: expecting the planned crash..."
  if "${cmd[@]}" > "$d/run1.log" 2>&1; then
    echo "hetero smoke FAILED: the planned crash never fired" >&2
    tail -5 "$d/run1.log" >&2; rm -rf "$d"; return 1
  fi
  echo "hetero smoke: resuming..."
  "${cmd[@]}" > "$d/run2.log" 2>&1 || {
    echo "hetero smoke FAILED: resume did not finish" >&2
    tail -20 "$d/run2.log" >&2; rm -rf "$d"; return 1
  }
  "${twin[@]}" > "$d/twin.log" 2>&1 || {
    echo "hetero smoke FAILED: the uninterrupted twin did not finish" >&2
    tail -20 "$d/twin.log" >&2; rm -rf "$d"; return 1
  }
  # 2 nloops x 1 group x 2 exchanges, the one slow client misses each
  grep -q '# faults injected: .*deadline_misses=4' "$d/run2.log" || {
    echo "hetero smoke FAILED: missing/incorrect deadline scoreboard" >&2
    grep '# faults' "$d/run2.log" >&2; rm -rf "$d"; return 1
  }
  if grep -q 'round_rollback' "$d/run.jsonl"; then
    echo "hetero smoke FAILED: partial updates tripped a rollback" >&2
    rm -rf "$d"; return 1
  fi
  assert_stream_identity "$d/run.jsonl" "$d/twin.jsonl" '
assert any(d.get("series") == "deadline_miss" for d in recs)
assert any(d.get("series") == "client_time" for d in recs)
' || {
    echo "hetero smoke FAILED: crashed+resumed stream differs from twin" >&2
    rm -rf "$d"; return 1
  }
  echo "hetero smoke OK"
  rm -rf "$d"
}

bf16_smoke() {
  # End-to-end bf16 exchange codec through the REAL CLI (exchange/,
  # docs/PERF.md): every consensus exchange ships the group slice as
  # bfloat16 (half the uplink bytes on the ledger), one client per round
  # sends a 10x-scaled update, trimmed-mean(1) + auto-quarantine defend
  # ON THE DECODED f32 VIEWS, and a planned crash at (nloop=1, gid=2,
  # nadmm=0) kills the first run. Recovery is rerunning the IDENTICAL
  # command; an uninterrupted twin (same plan minus the crash) then
  # proves crashed+resumed stream identity under the codec — comm_bytes
  # records included (exactly half the f32 ledger, asserted below) —
  # with zero rollbacks and the quarantine still firing.
  local d; d="$(mktemp -d)"
  local common=(python -m federated_pytorch_test_tpu --preset fedavg --quiet
    --synthetic-n-train 240 --synthetic-n-test 60 --batch 40
    --nloop 2 --nadmm 2 --max-groups 1 --eval-batch 30
    --exchange-dtype bfloat16
    --robust-agg trimmed --robust-f 1 --quarantine-z 1.0
    --fault-mode rollback --save-model --resume auto)
  local cmd=("${common[@]}"
    --fault-plan "seed=5,corrupt=1:scale:10,crash=1:2:0"
    --checkpoint-dir "$d/ckpt" --metrics-stream "$d/run.jsonl")
  local twin=("${common[@]}"
    --fault-plan "seed=5,corrupt=1:scale:10"
    --checkpoint-dir "$d/ckpt_twin" --metrics-stream "$d/twin.jsonl")
  echo "bf16 smoke: expecting the planned crash..."
  if "${cmd[@]}" > "$d/run1.log" 2>&1; then
    echo "bf16 smoke FAILED: the planned crash never fired" >&2
    tail -5 "$d/run1.log" >&2; rm -rf "$d"; return 1
  fi
  echo "bf16 smoke: resuming..."
  "${cmd[@]}" > "$d/run2.log" 2>&1 || {
    echo "bf16 smoke FAILED: resume did not finish" >&2
    tail -20 "$d/run2.log" >&2; rm -rf "$d"; return 1
  }
  "${twin[@]}" > "$d/twin.log" 2>&1 || {
    echo "bf16 smoke FAILED: the uninterrupted twin did not finish" >&2
    tail -20 "$d/twin.log" >&2; rm -rf "$d"; return 1
  }
  if grep -q 'round_rollback' "$d/run.jsonl"; then
    echo "bf16 smoke FAILED: the codec broke the robust combiner (rollback)" >&2
    rm -rf "$d"; return 1
  fi
  assert_stream_identity "$d/run.jsonl" "$d/twin.jsonl" '
comm = [d for d in recs if d.get("series") == "comm_bytes"]
assert comm, "no comm_bytes records"
summ = [d for d in recs if d.get("series") == "comm_summary"][-1]["value"]
assert summ["exchange_dtype"] == "bfloat16", summ
assert summ["wire_bytes_per_value"] == 2, summ
# half the f32 ledger exactly: per-survivor wire bytes are constant
# across exchanges (one group) and 2 bytes/value — i.e. exactly half the
# 4-byte parameter width (the exact hand-check vs masks lives in
# tests/test_exchange.py; here the stream must be self-consistent)
per = {d["value"] // d["survivors"] for d in comm if d["survivors"]}
assert len(per) == 1, per
assert summ["bytes_total"] == sum(d["value"] for d in comm), summ
assert any(d.get("series") == "quarantine" for d in recs), (
    "quarantine never fired under the codec")
' || {
    echo "bf16 smoke FAILED: crashed+resumed stream differs from twin" >&2
    rm -rf "$d"; return 1
  }
  echo "bf16 smoke OK"
  rm -rf "$d"
}

cohort_smoke() {
  # End-to-end cross-device scale through the REAL CLI (clients/,
  # docs/SCALE.md): 10k virtual clients mapped onto 8 data shards, a
  # C=8 cohort per outer loop, a dropout+corruption plan keyed by
  # VIRTUAL client id, the trimmed combiner, and a planned crash at
  # (nloop=1, gid=2, nadmm=0) killing the first run after loop 0's
  # store scatter + dirty-chunk checkpoint. Recovery is rerunning the
  # IDENTICAL command (--resume auto restores the checkpoint AND the
  # store manifest, and the pure cohort sampler re-derives every
  # historical cohort); an uninterrupted twin (same plan minus the
  # crash) then proves crashed+resumed stream identity — cohort
  # membership records included. Small-N fast variants of the same
  # contracts run in tier 1 (tests/test_clients.py).
  local d; d="$(mktemp -d)"
  local common=(python -m federated_pytorch_test_tpu --preset fedavg --quiet
    --synthetic-n-train 320 --synthetic-n-test 60 --batch 20
    --nloop 2 --nadmm 2 --max-groups 1 --eval-batch 30
    --virtual-clients 10000 --cohort 8 --data-shards 8 --cohort-seed 11
    --store-chunk-clients 8
    --robust-agg trimmed --robust-f 1
    --save-model --resume auto)
  local cmd=("${common[@]}"
    --fault-plan "seed=7,dropout=0.2,corrupt=0.05:scale:10,crash=1:2:0"
    --checkpoint-dir "$d/ckpt" --metrics-stream "$d/run.jsonl")
  local twin=("${common[@]}"
    --fault-plan "seed=7,dropout=0.2,corrupt=0.05:scale:10"
    --checkpoint-dir "$d/ckpt_twin" --metrics-stream "$d/twin.jsonl")
  echo "cohort smoke: expecting the planned crash..."
  if "${cmd[@]}" > "$d/run1.log" 2>&1; then
    echo "cohort smoke FAILED: the planned crash never fired" >&2
    tail -5 "$d/run1.log" >&2; rm -rf "$d"; return 1
  fi
  echo "cohort smoke: resuming..."
  "${cmd[@]}" > "$d/run2.log" 2>&1 || {
    echo "cohort smoke FAILED: resume did not finish" >&2
    tail -20 "$d/run2.log" >&2; rm -rf "$d"; return 1
  }
  "${twin[@]}" > "$d/twin.log" 2>&1 || {
    echo "cohort smoke FAILED: the uninterrupted twin did not finish" >&2
    tail -20 "$d/twin.log" >&2; rm -rf "$d"; return 1
  }
  grep -q '# cohort: 8 of 10000 virtual clients' "$d/run2.log" || {
    echo "cohort smoke FAILED: missing/incorrect cohort summary line" >&2
    grep '# cohort' "$d/run2.log" >&2; rm -rf "$d"; return 1
  }
  assert_stream_identity "$d/run.jsonl" "$d/twin.jsonl" '
cohorts = [d for d in recs if d.get("series") == "cohort"]
assert len(cohorts) == 2, cohorts
assert all(len(d["value"]["clients"]) == 8 for d in cohorts)
assert any(d.get("series") == "cohort_participation" for d in recs)
' || {
    echo "cohort smoke FAILED: crashed+resumed stream differs from twin" >&2
    rm -rf "$d"; return 1
  }
  echo "cohort smoke OK"
  rm -rf "$d"
}

spill_smoke() {
  # Million-client fleet on one host through the REAL CLI (clients/,
  # docs/SCALE.md §Spilled store): N=1,000,000 lazy virtual clients, a
  # C=16 cohort per loop, the store's resident set pinned to TWO chunks
  # (--store-resident-chunks 2, 8-client chunks) so every loop's
  # scatter forces clean-chunk evictions and dirty-chunk spills, and a
  # planned crash at (nloop=1, gid=2, nadmm=0) killing the first run
  # while loop 1's prefetched gather is being consumed. Recovery is
  # rerunning the IDENTICAL command; an uninterrupted twin proves
  # crashed+resumed stream identity. The bounded-RSS gate reads peak
  # host RSS off each run's status sidecar: the N=1M twin must land
  # within 1.25x of an otherwise-identical N=10k run (flat in N) and
  # under an absolute ceiling — a store that silently materialized the
  # population would blow both.
  local d; d="$(mktemp -d)"
  local base=(python -m federated_pytorch_test_tpu --preset fedavg --quiet
    --synthetic-n-train 320 --synthetic-n-test 60 --batch 20
    --nloop 2 --nadmm 2 --max-groups 1 --eval-batch 30
    --cohort 16 --data-shards 8 --cohort-seed 11
    --store-chunk-clients 8 --store-resident-chunks 2
    --save-model --resume auto)
  local cmd=("${base[@]}" --virtual-clients 1000000
    --fault-plan "seed=7,dropout=0.2,crash=1:2:0"
    --checkpoint-dir "$d/ckpt" --metrics-stream "$d/run.jsonl")
  local twin=("${base[@]}" --virtual-clients 1000000
    --fault-plan "seed=7,dropout=0.2"
    --checkpoint-dir "$d/ckpt_twin" --metrics-stream "$d/twin.jsonl")
  local small=("${base[@]}" --virtual-clients 10000
    --fault-plan "seed=7,dropout=0.2"
    --checkpoint-dir "$d/ckpt_small" --metrics-stream "$d/small.jsonl")
  echo "spill smoke: expecting the planned crash..."
  if "${cmd[@]}" > "$d/run1.log" 2>&1; then
    echo "spill smoke FAILED: the planned crash never fired" >&2
    tail -5 "$d/run1.log" >&2; rm -rf "$d"; return 1
  fi
  echo "spill smoke: resuming..."
  "${cmd[@]}" > "$d/run2.log" 2>&1 || {
    echo "spill smoke FAILED: resume did not finish" >&2
    tail -20 "$d/run2.log" >&2; rm -rf "$d"; return 1
  }
  "${twin[@]}" > "$d/twin.log" 2>&1 || {
    echo "spill smoke FAILED: the 1M twin did not finish" >&2
    tail -20 "$d/twin.log" >&2; rm -rf "$d"; return 1
  }
  "${small[@]}" > "$d/small.log" 2>&1 || {
    echo "spill smoke FAILED: the 10k baseline did not finish" >&2
    tail -20 "$d/small.log" >&2; rm -rf "$d"; return 1
  }
  grep -q '# cohort: 16 of 1000000 virtual clients' "$d/run2.log" || {
    echo "spill smoke FAILED: missing/incorrect cohort summary line" >&2
    grep '# cohort' "$d/run2.log" >&2; rm -rf "$d"; return 1
  }
  grep -q '# store: .*eviction' "$d/run2.log" || {
    echo "spill smoke FAILED: the residency budget forced no evictions" >&2
    grep '# store' "$d/run2.log" >&2; rm -rf "$d"; return 1
  }
  assert_stream_identity "$d/run.jsonl" "$d/twin.jsonl" '
cohorts = [d for d in recs if d.get("series") == "cohort"]
assert len(cohorts) == 2, cohorts
assert all(len(d["value"]["clients"]) == 16 for d in cohorts)
assert any(d.get("series") == "cohort_participation" for d in recs)
' || {
    echo "spill smoke FAILED: crashed+resumed stream differs from twin" >&2
    rm -rf "$d"; return 1
  }
  if ! python - "$d/twin.jsonl.status.json" "$d/small.jsonl.status.json" <<'PY'
import json, sys
big = json.load(open(sys.argv[1]))
small = json.load(open(sys.argv[2]))
for doc, name in ((big, "1M"), (small, "10k")):
    assert doc.get("completed"), f"{name} sidecar not stamped completed"
peak_big = big["memory"]["peak_rss_bytes"]
peak_small = small["memory"]["peak_rss_bytes"]
assert peak_big and peak_small, (peak_big, peak_small)
ratio = peak_big / peak_small
# flat in N: 100x the population, within 1.25x the peak RSS (the
# store is lazy + spilled; what remains O(N) is int64 metadata and
# the fault plan's per-round [nadmm, N] draws)
assert ratio <= 1.25, f"peak RSS ratio 1M/10k = {ratio:.3f} > 1.25"
# and an absolute sanity ceiling for the whole process (jax + data +
# store): a population-sized store would be ~250 GB of flat rows
assert peak_big < 6 * 2**30, f"peak RSS {peak_big/2**30:.2f} GiB >= 6 GiB"
st = big.get("store") or {}
assert st.get("resident_budget") == 2, st
assert st.get("evictions", 0) > 0, st
print(
    f"spill smoke: peak RSS 1M={peak_big/2**20:.0f} MiB "
    f"10k={peak_small/2**20:.0f} MiB (ratio {ratio:.3f}); "
    f"evictions={st.get('evictions')} spill_bytes={st.get('spill_bytes')}"
)
PY
  then
    echo "spill smoke FAILED: bounded-RSS gate" >&2
    rm -rf "$d"; return 1
  fi
  echo "spill smoke OK"
  rm -rf "$d"
}

fleet_smoke() {
  # End-to-end CLOSED-LOOP fleet control through the REAL CLI (the
  # ROADMAP-item-3 scenario at population scale): 10k virtual clients
  # with availability churn (churn=0.1:2), Bernoulli 4x stragglers, and
  # corrupting liars; `--round-deadline auto` tracks the online
  # client_time sketch, `--cohort-weighting telemetry` steers sampling
  # by the store's accumulated reliability state, trimmed(1) +
  # quarantine (with the 2f release rule) defend, and a planned crash
  # at (nloop=1, gid=2, nadmm=0) kills the first run AFTER loop 0's
  # scatter committed the telemetry + cohort history. Recovery is
  # rerunning the IDENTICAL command (--resume auto restores checkpoint,
  # store, cohort history, and replays the deadline decisions from the
  # stream); an uninterrupted twin (same plan minus the crash) then
  # proves crashed+resumed stream identity — deadline, availability,
  # cohort_weight, and cohort records included.
  local d; d="$(mktemp -d)"
  local common=(python -m federated_pytorch_test_tpu --preset fedavg --quiet
    --synthetic-n-train 320 --synthetic-n-test 60 --batch 20
    --nloop 3 --nadmm 2 --max-groups 1 --eval-batch 30
    --virtual-clients 10000 --cohort 8 --data-shards 8 --cohort-seed 11
    --store-chunk-clients 8 --cohort-weighting telemetry
    --round-deadline auto
    --robust-agg trimmed --robust-f 1 --quarantine-z 1.0
    --save-model --resume auto)
  local plan="seed=7,churn=0.1:2,slow=0.08:4,corrupt=0.05:scale:10"
  local cmd=("${common[@]}"
    --fault-plan "$plan,crash=1:2:0"
    --checkpoint-dir "$d/ckpt" --metrics-stream "$d/run.jsonl")
  local twin=("${common[@]}"
    --fault-plan "$plan"
    --checkpoint-dir "$d/ckpt_twin" --metrics-stream "$d/twin.jsonl")
  echo "fleet smoke: expecting the planned crash..."
  if "${cmd[@]}" > "$d/run1.log" 2>&1; then
    echo "fleet smoke FAILED: the planned crash never fired" >&2
    tail -5 "$d/run1.log" >&2; rm -rf "$d"; return 1
  fi
  echo "fleet smoke: resuming..."
  "${cmd[@]}" > "$d/run2.log" 2>&1 || {
    echo "fleet smoke FAILED: resume did not finish" >&2
    tail -20 "$d/run2.log" >&2; rm -rf "$d"; return 1
  }
  "${twin[@]}" > "$d/twin.log" 2>&1 || {
    echo "fleet smoke FAILED: the uninterrupted twin did not finish" >&2
    tail -20 "$d/twin.log" >&2; rm -rf "$d"; return 1
  }
  # the scoreboard's churn row (population client-loop absences) is
  # pure in the plan, so the resumed run prints a nonzero total
  grep -Eq '# faults injected: .*churned=[1-9]' "$d/run2.log" || {
    echo "fleet smoke FAILED: missing/zero churned scoreboard row" >&2
    grep '# faults' "$d/run2.log" >&2; rm -rf "$d"; return 1
  }
  assert_stream_identity "$d/run.jsonl" "$d/twin.jsonl" '
dl = [d for d in recs if d.get("series") == "deadline"]
assert dl and all(d["value"]["source"] in ("warmup", "sketch") for d in dl)
assert any(d.get("series") == "availability" for d in recs)
assert any(d.get("series") == "cohort_weight" for d in recs)
assert any(d.get("series") == "client_time" for d in recs)
cohorts = [d for d in recs if d.get("series") == "cohort"]
assert len(cohorts) == 3 and all(
    len(d["value"]["clients"]) == 8 for d in cohorts)
' || {
    echo "fleet smoke FAILED: crashed+resumed stream differs from twin" >&2
    rm -rf "$d"; return 1
  }
  echo "fleet smoke OK"
  rm -rf "$d"
}

codec_smoke() {
  # End-to-end codec zoo + adaptive layer-group scheduling through the
  # REAL CLI (exchange/, docs/PERF.md §Codec zoo): a 3-codec sweep —
  # identity/roundrobin baseline, topk(0.1)+error-feedback under the
  # ADAPTIVE scheduler, and q8 — over the identical corruption+dropout
  # plan with the trimmed combiner. The topk run is CRASHED by a
  # planned crash at (nloop=1, gid=2, nadmm=0) and recovered by
  # rerunning the identical command (--resume auto replays the slot
  # decisions and drift signal from the stream); an uninterrupted twin
  # proves crashed+resumed stream identity — group_schedule and
  # group_distance records included. `report` over the sweep then
  # gates the ISSUE-13 frontier acceptance: the sparse point lands
  # within 2 accuracy points of the f32/roundrobin baseline at <= 25%
  # of its cumulative uplink bytes (topk(0.1) prices at 20%: 8 bytes
  # per kept pair on a tenth of the coordinates vs 4 bytes/value
  # dense), with the report byte-identical between the crashed+resumed
  # sweep dir and the twin dir.
  local d; d="$(mktemp -d)"
  mkdir -p "$d/a" "$d/b"
  local plan="seed=5,dropout=0.2,corrupt=1:scale:10"
  local base=(python -m federated_pytorch_test_tpu --preset fedavg --quiet
    --synthetic-n-train 240 --synthetic-n-test 60 --batch 40
    --nloop 2 --nadmm 2 --max-groups 2 --eval-batch 30
    --robust-agg trimmed --robust-f 1
    --fault-mode rollback --save-model --resume auto)
  echo "codec smoke: f32/roundrobin baseline..."
  "${base[@]}" --fault-plan "$plan" \
    --checkpoint-dir "$d/ckpt_f32" --metrics-stream "$d/a/f32.jsonl" \
    > "$d/f32.log" 2>&1 || {
    echo "codec smoke FAILED: f32 baseline did not finish" >&2
    tail -20 "$d/f32.log" >&2; rm -rf "$d"; return 1
  }
  cp "$d/a/f32.jsonl" "$d/b/f32.jsonl"
  local topk=("${base[@]}" --exchange-codec topk --topk-fraction 0.1
    --error-feedback --group-schedule adaptive)
  local crash=("${topk[@]}" --fault-plan "$plan,crash=1:2:0"
    --checkpoint-dir "$d/ckpt_topk" --metrics-stream "$d/a/topk.jsonl")
  echo "codec smoke: expecting the planned topk crash..."
  if "${crash[@]}" > "$d/topk1.log" 2>&1; then
    echo "codec smoke FAILED: the planned crash never fired" >&2
    tail -5 "$d/topk1.log" >&2; rm -rf "$d"; return 1
  fi
  echo "codec smoke: resuming..."
  "${crash[@]}" > "$d/topk2.log" 2>&1 || {
    echo "codec smoke FAILED: resume did not finish" >&2
    tail -20 "$d/topk2.log" >&2; rm -rf "$d"; return 1
  }
  "${topk[@]}" --fault-plan "$plan" \
    --checkpoint-dir "$d/ckpt_topk_twin" --metrics-stream "$d/b/topk.jsonl" \
    > "$d/twin.log" 2>&1 || {
    echo "codec smoke FAILED: the uninterrupted twin did not finish" >&2
    tail -20 "$d/twin.log" >&2; rm -rf "$d"; return 1
  }
  echo "codec smoke: q8 run..."
  "${base[@]}" --exchange-codec quant --quant-bits 8 --fault-plan "$plan" \
    --checkpoint-dir "$d/ckpt_q8" --metrics-stream "$d/a/q8.jsonl" \
    > "$d/q8.log" 2>&1 || {
    echo "codec smoke FAILED: q8 run did not finish" >&2
    tail -20 "$d/q8.log" >&2; rm -rf "$d"; return 1
  }
  cp "$d/a/q8.jsonl" "$d/b/q8.jsonl"
  if grep -q 'round_rollback' "$d/a/topk.jsonl" "$d/a/q8.jsonl"; then
    echo "codec smoke FAILED: a codec broke the robust combiner (rollback)" >&2
    rm -rf "$d"; return 1
  fi
  assert_stream_identity "$d/a/topk.jsonl" "$d/b/topk.jsonl" '
sched = [d for d in recs if d.get("series") == "group_schedule"]
assert sched and all(
    d["value"]["source"] in ("warmup", "drift") for d in sched)
assert any(d.get("series") == "group_distance" for d in recs)
summ = [d for d in recs if d.get("series") == "comm_summary"][-1]["value"]
assert summ["codec"]["label"] == "topk(0.1)", summ
' || {
    echo "codec smoke FAILED: crashed+resumed stream differs from twin" >&2
    rm -rf "$d"; return 1
  }
  python -m federated_pytorch_test_tpu report "$d/a" \
    --json "$d/a.json" --md "$d/a.md" --quiet || {
    echo "codec smoke FAILED: report over the sweep dir errored" >&2
    rm -rf "$d"; return 1
  }
  python -m federated_pytorch_test_tpu report "$d/b" \
    --json "$d/b.json" --md "$d/b.md" --quiet || {
    echo "codec smoke FAILED: report over the twin dir errored" >&2
    rm -rf "$d"; return 1
  }
  cmp -s "$d/a.json" "$d/b.json" && cmp -s "$d/a.md" "$d/b.md" || {
    echo "codec smoke FAILED: crashed+resumed report differs from twin" >&2
    diff "$d/a.json" "$d/b.json" | head -20 >&2; rm -rf "$d"; return 1
  }
  python - "$d/a.json" <<'PY' || { rm -rf "$d"; return 1; }
import json, sys

doc = json.load(open(sys.argv[1]))
runs = doc["runs"]
assert set(runs) == {"f32", "topk", "q8"}, sorted(runs)
f32, topk, q8 = runs["f32"], runs["topk"], runs["q8"]
assert f32["config"]["label"] == "identity/roundrobin", f32["config"]
assert topk["config"]["label"] == "topk(0.1)/adaptive", topk["config"]
assert q8["config"]["label"] == "q8/roundrobin", q8["config"]
# THE frontier acceptance (ISSUE 13): the sparse+scheduled point lands
# within 2 accuracy points of the f32/roundrobin baseline at <= 25% of
# its cumulative uplink bytes (bf16's halving was 50%)
assert topk["total_comm_bytes"] <= 0.25 * f32["total_comm_bytes"], (
    topk["total_comm_bytes"], f32["total_comm_bytes"])
assert topk["final_accuracy"] >= f32["final_accuracy"] - 0.02, (
    topk["final_accuracy"], f32["final_accuracy"])
# q8 prices at ~25.1% (scale header over 1 byte/value) — cheaper than
# bf16's 50% but above the 25% gate; the frontier shows both points
assert q8["total_comm_bytes"] < 0.27 * f32["total_comm_bytes"]
# the cheapest codec is on the frontier, the baseline is dominated or
# the single most-accurate point; every frontier row carries its
# codec+scheduler label
front = {p["run"]: p for p in doc["frontier"]}
assert front["topk"]["pareto"], doc["frontier"]
assert front["topk"]["config"] == "topk(0.1)/adaptive"
print("codec smoke: frontier acceptance OK",
      {k: (v["total_comm_bytes"], v["final_accuracy"])
       for k, v in runs.items()})
PY
  echo "codec smoke OK"
  rm -rf "$d"
}

report_smoke() {
  # End-to-end cross-run registry through the REAL CLI (obs/registry.py,
  # docs/OBSERVABILITY.md): a two-point codec sweep — identical configs
  # except f32 vs bf16 exchange wire format, same corruption plan — whose
  # streams land in one directory, and `report` turns it into the
  # convergence-vs-bytes frontier in one command (the bf16 run's uplink
  # is exactly half the f32 run's for the identical schedule). The bf16
  # run is additionally CRASHED by a planned crash at (nloop=1, gid=2,
  # nadmm=0) and recovered by rerunning the identical command; an
  # uninterrupted twin directory (same f32 stream file, twin bf16 plan
  # minus the crash) then gates the registry's determinism contract:
  # `report` over the crashed+resumed sweep is BYTE-identical (JSON and
  # markdown) to the twin sweep's — no wall-clock or tag content leaks
  # into the report.
  local d; d="$(mktemp -d)"
  mkdir -p "$d/a" "$d/b"
  local base=(python -m federated_pytorch_test_tpu --preset fedavg --quiet
    --synthetic-n-train 240 --synthetic-n-test 60 --batch 40
    --nloop 2 --nadmm 2 --max-groups 1 --eval-batch 30
    --robust-agg trimmed --robust-f 1
    --fault-mode rollback --save-model --resume auto)
  echo "report smoke: f32 baseline run..."
  "${base[@]}" --fault-plan "seed=5,corrupt=1:scale:10" \
    --checkpoint-dir "$d/ckpt_f32" --metrics-stream "$d/a/f32.jsonl" \
    > "$d/f32.log" 2>&1 || {
    echo "report smoke FAILED: f32 run did not finish" >&2
    tail -20 "$d/f32.log" >&2; rm -rf "$d"; return 1
  }
  cp "$d/a/f32.jsonl" "$d/b/f32.jsonl"
  local crash=("${base[@]}" --exchange-dtype bfloat16
    --fault-plan "seed=5,corrupt=1:scale:10,crash=1:2:0"
    --checkpoint-dir "$d/ckpt_bf" --metrics-stream "$d/a/bf16.jsonl")
  echo "report smoke: expecting the planned bf16 crash..."
  if "${crash[@]}" > "$d/bf1.log" 2>&1; then
    echo "report smoke FAILED: the planned crash never fired" >&2
    tail -5 "$d/bf1.log" >&2; rm -rf "$d"; return 1
  fi
  echo "report smoke: resuming..."
  "${crash[@]}" > "$d/bf2.log" 2>&1 || {
    echo "report smoke FAILED: resume did not finish" >&2
    tail -20 "$d/bf2.log" >&2; rm -rf "$d"; return 1
  }
  "${base[@]}" --exchange-dtype bfloat16 \
    --fault-plan "seed=5,corrupt=1:scale:10" \
    --checkpoint-dir "$d/ckpt_bf_twin" --metrics-stream "$d/b/bf16.jsonl" \
    > "$d/twin.log" 2>&1 || {
    echo "report smoke FAILED: the uninterrupted twin did not finish" >&2
    tail -20 "$d/twin.log" >&2; rm -rf "$d"; return 1
  }
  python -m federated_pytorch_test_tpu report "$d/a" \
    --json "$d/a.json" --md "$d/a.md" --quiet || {
    echo "report smoke FAILED: report over the sweep dir errored" >&2
    rm -rf "$d"; return 1
  }
  python -m federated_pytorch_test_tpu report "$d/b" \
    --json "$d/b.json" --md "$d/b.md" --quiet || {
    echo "report smoke FAILED: report over the twin dir errored" >&2
    rm -rf "$d"; return 1
  }
  cmp -s "$d/a.json" "$d/b.json" && cmp -s "$d/a.md" "$d/b.md" || {
    echo "report smoke FAILED: crashed+resumed report differs from twin" >&2
    diff "$d/a.json" "$d/b.json" | head -20 >&2; rm -rf "$d"; return 1
  }
  python - "$d/a.json" <<'PY' || { rm -rf "$d"; return 1; }
import json, sys

doc = json.load(open(sys.argv[1]))
runs = doc["runs"]
assert set(runs) == {"f32", "bf16"}, sorted(runs)
f32, bf16 = runs["f32"], runs["bf16"]
# identical schedule, half the wire width: exactly half the bytes
assert f32["total_comm_bytes"] == 2 * bf16["total_comm_bytes"], (
    f32["total_comm_bytes"], bf16["total_comm_bytes"])
assert bf16["comm"]["exchange_dtype"] == "bfloat16", bf16["comm"]
assert f32["evals"] == bf16["evals"] > 0
# the cheaper codec is on the frontier by construction
front = {p["run"]: p["pareto"] for p in doc["frontier"]}
assert front["bf16"], doc["frontier"]
# the health engine monitored every round of both runs
assert f32["health"]["records"] == bf16["health"]["records"] > 0
print("report smoke: frontier + health checks OK")
PY
  echo "report smoke OK"
  rm -rf "$d"
}

incident_smoke() {
  # Flight-recorder forensics through the REAL CLI (obs/flight.py,
  # ISSUE 14): a nan_burst corruption under the MEAN combiner poisons
  # every round's consensus, rollback mode sacrifices the round, and
  # the health engine fires (nonfinite + rollback) -> the flight
  # recorder dumps one incident bundle (rising edge) beside the stream
  # and the anomaly-armed profiler captures ONE round (budget 1, the
  # real jax.profiler leg — tier-1 stubs it for wall budget). Assert
  # the bundle exists, validates against the schema, its in-bundle
  # series match the stream's last W rounds EXACTLY (the acceptance
  # criterion), `report --incidents` tables it, and `watch --once`
  # renders the directory without error.
  local d; d="$(mktemp -d)"
  python -m federated_pytorch_test_tpu --preset fedavg --quiet \
    --synthetic-n-train 240 --synthetic-n-test 60 --batch 40 \
    --nloop 2 --nadmm 2 --max-groups 1 --eval-batch 30 \
    --fault-plan "seed=5,corrupt=1:nan_burst" --fault-mode rollback \
    --profile-on-anomaly "$d/prof" --profile-budget 1 \
    --metrics-stream "$d/run.jsonl" > "$d/run.log" 2>&1 || {
    echo "incident smoke FAILED: the run did not finish" >&2
    tail -20 "$d/run.log" >&2; rm -rf "$d"; return 1
  }
  python - "$d" <<'PY' || { rm -rf "$d"; return 1; }
import glob, json, os, sys

from federated_pytorch_test_tpu.obs.flight import validate_incident

d = sys.argv[1]
bundles = sorted(
    glob.glob(os.path.join(d, "run.jsonl.incidents", "incident-*.json"))
)
assert len(bundles) == 1, bundles  # chronic anomaly: one rising-edge dump
doc = json.load(open(bundles[0]))
validate_incident(doc)
assert set(doc["anomalies"]) >= {"nonfinite", "rollback"}, doc["anomalies"]
# in-bundle series match the stream's last W rounds EXACTLY: segment
# the stream on dispatch_count (the round's final streamed record)
rounds, cur = [], []
for line in open(os.path.join(d, "run.jsonl")):
    rec = json.loads(line)
    if "series" not in rec:
        continue
    cur.append(rec)
    if rec["series"] == "dispatch_count":
        rounds.append(cur)
        cur = []
held = rounds[: doc["round"] + 1][-doc["window"]:]
assert [b["records"] for b in doc["rounds"]] == held, "bundle != stream tail"
# the real profiler capture landed (round AFTER the first alert)
caps = glob.glob(os.path.join(d, "prof", "round-*", "**", "*"),
                 recursive=True)
assert any(os.path.isfile(p) for p in caps), "no profiler capture files"
print("incident smoke: bundle schema + stream-tail match + capture OK",
      os.path.basename(bundles[0]))
PY
  python -m federated_pytorch_test_tpu report "$d" --incidents \
    --json "$d/report.json" --quiet || {
    echo "incident smoke FAILED: report --incidents errored" >&2
    rm -rf "$d"; return 1
  }
  grep -q '"incidents"' "$d/report.json" || {
    echo "incident smoke FAILED: report JSON has no incidents table" >&2
    rm -rf "$d"; return 1
  }
  python -m federated_pytorch_test_tpu watch "$d" --once > "$d/watch.out" || {
    echo "incident smoke FAILED: watch --once errored" >&2
    tail -20 "$d/watch.out" >&2; rm -rf "$d"; return 1
  }
  grep -q 'incident-0-0.json' "$d/watch.out" || {
    echo "incident smoke FAILED: watch output missing the incident line" >&2
    cat "$d/watch.out" >&2; rm -rf "$d"; return 1
  }
  echo "incident smoke OK"
  rm -rf "$d"
}

integrity_smoke() {
  # Storage-integrity axis through the REAL CLI (fault/io.py,
  # docs/FAULT.md §Storage-integrity axis): a 100k-client spilled run
  # (telemetry weighting, so every loop re-reads the spilled chunks
  # through the verify-on-read path) under an injected bitrot plan
  # with a planned crash at (nloop=1, gid=2, nadmm=0), recovered by
  # rerunning the IDENTICAL command — resume-time verify_all and the
  # bounded retry heal every hit (the disk is intact; only read
  # buffers are corrupted), so the crashed+resumed stream is
  # byte-identical to an uninterrupted twin's. A second leg survives a
  # transient-ioerror plan on the write paths (spills, stream lines,
  # checkpoint staging). Then the offline ladder: bit-flip a chunk
  # file in the twin's store, `scrub` exits nonzero NAMING it,
  # `scrub --repair` resolves it, and a re-scrub is clean. Both run
  # logs must show a nonzero `storage_faults=` scoreboard entry.
  # --no-prefetch pins the shim's per-op draw schedule: background
  # gathers would interleave read ordinals nondeterministically.
  local d; d="$(mktemp -d)"
  local base=(python -m federated_pytorch_test_tpu --preset fedavg --quiet
    --synthetic-n-train 320 --synthetic-n-test 60 --batch 20
    --nloop 2 --nadmm 2 --max-groups 1 --eval-batch 30
    --virtual-clients 100000 --cohort 16 --data-shards 8 --cohort-seed 11
    --cohort-weighting telemetry --no-prefetch
    --store-chunk-clients 8 --store-resident-chunks 2
    --save-model --resume auto)
  local cmd=("${base[@]}" --fault-plan "seed=7,storage=0.1:bitrot,crash=1:2:0"
    --checkpoint-dir "$d/ckpt" --metrics-stream "$d/run.jsonl")
  local twin=("${base[@]}" --fault-plan "seed=7,storage=0.1:bitrot"
    --checkpoint-dir "$d/ckpt_twin" --metrics-stream "$d/twin.jsonl")
  echo "integrity smoke: expecting the planned crash..."
  if "${cmd[@]}" > "$d/run1.log" 2>&1; then
    echo "integrity smoke FAILED: the planned crash never fired" >&2
    tail -5 "$d/run1.log" >&2; rm -rf "$d"; return 1
  fi
  echo "integrity smoke: resuming through the verify gate..."
  "${cmd[@]}" > "$d/run2.log" 2>&1 || {
    echo "integrity smoke FAILED: resume did not finish" >&2
    tail -20 "$d/run2.log" >&2; rm -rf "$d"; return 1
  }
  "${twin[@]}" > "$d/twin.log" 2>&1 || {
    echo "integrity smoke FAILED: the twin did not finish" >&2
    tail -20 "$d/twin.log" >&2; rm -rf "$d"; return 1
  }
  for log in run2 twin; do
    grep -Eq 'storage_faults=[1-9]' "$d/$log.log" || {
      echo "integrity smoke FAILED: $log scoreboard shows no storage faults" >&2
      grep '# faults injected' "$d/$log.log" >&2; rm -rf "$d"; return 1
    }
  done
  grep -q 'checksum verification' "$d/run2.log" || {
    echo "integrity smoke FAILED: no bitrot hit was ever detected" >&2
    rm -rf "$d"; return 1
  }
  assert_stream_identity "$d/run.jsonl" "$d/twin.jsonl" '
assert not any(d.get("series") == "incident" for d in recs)
' || {
    echo "integrity smoke FAILED: crashed+resumed stream differs from twin" >&2
    rm -rf "$d"; return 1
  }
  if ! python - "$d/run.jsonl.status.json" <<'PY'
import json, sys
doc = json.load(open(sys.argv[1]))
assert doc.get("completed"), "sidecar not stamped completed"
dig = doc.get("integrity") or {}
assert dig.get("checksums") and dig.get("verified_reads", 0) > 0, dig
assert dig.get("failures", 0) > 0, dig          # rot was DETECTED...
assert dig.get("retry_heals", 0) > 0, dig       # ...and healed
assert not dig.get("repairs_prior") and not dig.get("repairs_reinit"), dig
assert doc.get("storage_faults", 0) > 0, doc.get("storage_faults")
print(
    f"integrity smoke: verified_reads={dig['verified_reads']} "
    f"failures={dig['failures']} retry_heals={dig['retry_heals']}"
)
PY
  then
    echo "integrity smoke FAILED: integrity sidecar gate" >&2
    rm -rf "$d"; return 1
  fi
  echo "integrity smoke: surviving a transient-ioerror write plan..."
  "${base[@]}" --fault-plan "seed=3,storage=0.05:ioerror" \
    --checkpoint-dir "$d/ckpt_io" --metrics-stream "$d/io.jsonl" \
    > "$d/io.log" 2>&1 || {
    echo "integrity smoke FAILED: ioerror plan run did not finish" >&2
    tail -20 "$d/io.log" >&2; rm -rf "$d"; return 1
  }
  grep -Eq 'storage_faults=[1-9]' "$d/io.log" && grep -q 'retrying' "$d/io.log" || {
    echo "integrity smoke FAILED: ioerror plan injected/retried nothing" >&2
    rm -rf "$d"; return 1
  }
  echo "integrity smoke: scrub detect-then-repair..."
  local chunk
  chunk="$(ls "$d/ckpt_twin/client_store/" | grep '^chunk_' | head -1)"
  python -c "
p = '$d/ckpt_twin/client_store/$chunk'
b = bytearray(open(p, 'rb').read()); b[120] ^= 0xFF
open(p, 'wb').write(bytes(b))"
  if python -m federated_pytorch_test_tpu scrub "$d/ckpt_twin" > "$d/scrub1.out" 2>&1; then
    echo "integrity smoke FAILED: scrub missed the corrupt chunk" >&2
    cat "$d/scrub1.out" >&2; rm -rf "$d"; return 1
  fi
  grep -q "CORRUPT $chunk" "$d/scrub1.out" || {
    echo "integrity smoke FAILED: scrub did not name the chunk" >&2
    cat "$d/scrub1.out" >&2; rm -rf "$d"; return 1
  }
  python -m federated_pytorch_test_tpu scrub "$d/ckpt_twin" --repair \
    > "$d/scrub2.out" 2>&1 || {
    echo "integrity smoke FAILED: scrub --repair left problems" >&2
    cat "$d/scrub2.out" >&2; rm -rf "$d"; return 1
  }
  python -m federated_pytorch_test_tpu scrub "$d/ckpt_twin" > "$d/scrub3.out" 2>&1 || {
    echo "integrity smoke FAILED: store still dirty after repair" >&2
    cat "$d/scrub3.out" >&2; rm -rf "$d"; return 1
  }
  echo "integrity smoke OK"
  rm -rf "$d"
}

widened_smoke() {
  # Widened client GEMM through the REAL CLI (engine/steps.py,
  # ops/grouped_gemm.py, docs/PERF.md §Widened GEMM): a P=4 probe fan
  # under --client-fold gemm — the fold that turns the K-client x
  # P-probe fan into one wide contraction — with a dropout+corruption
  # plan, trimmed(1), and the topk codec riding the exchange, and a
  # planned crash at (nloop=1, gid=2, nadmm=0) killing the first run.
  # Recovery is rerunning the IDENTICAL command; an uninterrupted twin
  # proves crashed+resumed stream identity, with the per-round
  # {round: 1} dispatch budget asserted ON THE STREAM (the fold must
  # not cost a dispatch). Then the escape hatch: a --client-fold vmap
  # rerun of the twin's exact plan, whose stream must match the gemm
  # twin's within the documented tolerance — on the CPU twin that
  # tolerance is BITWISE (docs/PERF.md fallback matrix) modulo the
  # fold-mode tag the step_time/epoch records deliberately carry and
  # the stream-tag header (the knob is a tag member).
  local d; d="$(mktemp -d)"
  local common=(python -m federated_pytorch_test_tpu --preset fedavg --quiet
    --synthetic-n-train 240 --synthetic-n-test 60 --batch 40
    --nloop 2 --nadmm 2 --max-groups 1 --eval-batch 30
    --linesearch-probes 4
    --exchange-codec topk --topk-fraction 0.1
    --robust-agg trimmed --robust-f 1
    --fault-mode rollback --save-model --resume auto)
  local plan="seed=8,dropout=0.3,corrupt=1:gauss:0.5"
  local cmd=("${common[@]}" --client-fold gemm
    --fault-plan "$plan,crash=1:2:0"
    --checkpoint-dir "$d/ckpt" --metrics-stream "$d/run.jsonl")
  local twin=("${common[@]}" --client-fold gemm
    --fault-plan "$plan"
    --checkpoint-dir "$d/ckpt_twin" --metrics-stream "$d/twin.jsonl")
  local vmapped=("${common[@]}" --client-fold vmap
    --fault-plan "$plan"
    --checkpoint-dir "$d/ckpt_vmap" --metrics-stream "$d/vmap.jsonl")
  echo "widened smoke: expecting the planned crash..."
  if "${cmd[@]}" > "$d/run1.log" 2>&1; then
    echo "widened smoke FAILED: the planned crash never fired" >&2
    tail -5 "$d/run1.log" >&2; rm -rf "$d"; return 1
  fi
  echo "widened smoke: resuming..."
  "${cmd[@]}" > "$d/run2.log" 2>&1 || {
    echo "widened smoke FAILED: resume did not finish" >&2
    tail -20 "$d/run2.log" >&2; rm -rf "$d"; return 1
  }
  "${twin[@]}" > "$d/twin.log" 2>&1 || {
    echo "widened smoke FAILED: the uninterrupted twin did not finish" >&2
    tail -20 "$d/twin.log" >&2; rm -rf "$d"; return 1
  }
  assert_stream_identity "$d/run.jsonl" "$d/twin.jsonl" '
dc = [d for d in recs if d.get("series") == "dispatch_count"]
assert dc, "no dispatch_count records"
# the fold must not cost a dispatch: every round is ONE round dispatch
# (plus the first round its init), faults+trimmed+topk live inside it
assert all(d["value"].get("round") == 1 for d in dc), dc
assert not any(d["value"].get("epoch") for d in dc), dc
st = [d for d in recs if d.get("series") == "step_time"]
assert any(d["value"]["phase"] == "fused_round" for d in st), "not fused"
assert all(
    d.get("client_fold") == "gemm"
    for d in st if d["value"]["phase"] == "fused_round"
), "fused_round spans not tagged with the fold mode"
summ = [d for d in recs if d.get("series") == "comm_summary"][-1]["value"]
assert summ["codec"]["label"] == "topk(0.1)", summ
' || {
    echo "widened smoke FAILED: crashed+resumed stream differs from twin" >&2
    rm -rf "$d"; return 1
  }
  echo "widened smoke: vmap escape-hatch rerun..."
  "${vmapped[@]}" > "$d/vmap.log" 2>&1 || {
    echo "widened smoke FAILED: the vmap rerun did not finish" >&2
    tail -20 "$d/vmap.log" >&2; rm -rf "$d"; return 1
  }
  # the cross-fold compare: same normalization as assert_stream_identity
  # PLUS the fold-mode tag (step_time/epoch records carry client_fold by
  # design — it is the ONLY legitimate cross-fold difference on CPU)
  python - "$d/twin.jsonl" "$d/vmap.jsonl" <<'PY' || {
import json, sys

def norm(path):
    out = []
    for line in open(path):
        d = json.loads(line)
        d.pop("t", None)
        d.pop("crc", None)
        d.pop("client_fold", None)
        if d.get("event") == "stream_header":
            d.pop("tag", None)
        if d.get("series") == "step_time":
            d["value"] = {k: v for k, v in d["value"].items() if k != "seconds"}
        out.append(d)
    return out

a, b = norm(sys.argv[1]), norm(sys.argv[2])
assert a == b, f"gemm vs vmap streams differ: {len(a)} vs {len(b)} records"
print(f"widened smoke: gemm == vmap over {len(a)} records (CPU bitwise)")
PY
    echo "widened smoke FAILED: vmap stream differs from gemm beyond the fold tag" >&2
    rm -rf "$d"; return 1
  }
  echo "widened smoke OK"
  rm -rf "$d"
}

tier="${CI_TIER:-all}"
preflight
case "$tier" in
  0) run_tier smoke tests/ -m smoke -q "$@" ;;
  1) run_tier tier1 tests/ -m 'not slow' -q "$@" ;;
  2)
    run_tier slow tests/ -m slow -q "$@"
    byzantine_smoke
    hetero_smoke
    bf16_smoke
    codec_smoke
    cohort_smoke
    spill_smoke
    fleet_smoke
    report_smoke
    incident_smoke
    integrity_smoke
    widened_smoke
    chaos_smoke
    ;;
  all)
    run_tier tier1 tests/ -m 'not slow' -q "$@"
    run_tier slow tests/ -m slow -q "$@"
    byzantine_smoke
    hetero_smoke
    bf16_smoke
    codec_smoke
    cohort_smoke
    spill_smoke
    fleet_smoke
    report_smoke
    incident_smoke
    integrity_smoke
    widened_smoke
    chaos_smoke
    ;;
  *) echo "unknown CI_TIER='$tier' (want 0, 1, 2 or all)" >&2; exit 2 ;;
esac
