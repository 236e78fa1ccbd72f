"""Chaos harness: composed fault-plan fuzzer, invariant oracle, shrinker.

Every robustness guarantee in the repo is proven one axis or one
hand-picked combination at a time (tests/test_robust.py,
tests/test_hetero.py, the tier-2 *_smoke legs). This module is the
repo's first tool that SEARCHES the cross-product instead of pinning
known points — a quarantined straggler under a lossy codec during a
storage fault is exactly the composed condition FedADMM-style system
heterogeneity (arXiv:2204.03529) and partial-participation regimes
(TAMUNA, arXiv:2302.09832) fail in. Four parts:

* `ChaosPlanGenerator` — a seeded, validity-aware fuzzer: case `i` of
  generator seed `S` is a pure function of `(S, i)` and composes random
  fault-plan axes (PLAN_DOMAINS) with a random knob lattice drawn from
  the engine's exported `KNOB_DOMAINS` table, respecting the strict
  config validators BY CONSTRUCTION (n > 2f for trimmed, lossy-codec
  for error feedback, churn-requires-cohort, nan_burst-requires-robust
  — `_COUPLINGS` below). A deterministic coverage rotation forces axis
  `i % 7` and knob group `i % 8` into case `i`, so every axis and every
  lattice knob is exercised within the first dozen cases of any soak.
* the invariant ORACLE (`run_case`) — runs each drawn config through
  the real `Trainer` with its planned mid-run crash, auto-resumes it,
  runs the uninterrupted twin, and checks machine-readable properties
  harvested from the stream / sidecar / store (`INVARIANTS` below).
* the delta-debugging SHRINKER (`shrink`) — greedily removes one
  component at a time (axes → knob groups → crash → rounds → clients)
  while the violation reproduces, to a 1-minimal fixpoint: no single
  remaining component can be dropped without losing the violation. The
  result is dumped as a self-contained repro bundle (plan JSON + full
  config overrides + seeds + any flight-recorder incidents) runnable
  via `chaos --repro FILE`.
* SOAK mode (`chaos --budget-s N --seed S`) — streams one verdict per
  plan as JSONL with provenance stamps and cumulative axis/knob
  coverage, and writes a crc-self-verified `chaos_soak.json` summary
  of the soak (cases cleared, violations, coverage, wall).

The `chaos` verb dispatches ENGINE-IMPORT-FREE from `__main__` (like
`report`/`scrub`): this module imports no engine code at import
time, pins the backend to host CPU itself (`force_host_cpu`, the
conftest contract — a soak must never claim an accelerator), and only
then lazily imports the Trainer inside the oracle.

Planted-bug self-test: `CHAOS_PLANT_BUG=combiner` monkeypatches the
Byzantine-robust combiner with a naive masked mean that averages NaNs
straight in (`_apply_planted_bug`). The CI leg asserts the harness
CATCHES that violation (the `robust_finite` invariant), SHRINKS it to
<= 2 axes, and that `chaos --repro` reproduces it from the bundle —
the oracle's own false-negative test.
"""

from __future__ import annotations

import dataclasses
import errno
import json
import os
import time
import traceback
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from federated_pytorch_test_tpu.fault.io import stamp_crc, verify_crc
from federated_pytorch_test_tpu.fault.plan import CrashPoint, FaultPlan

# --------------------------------------------------------- plan domains
#
# THE machine-readable fault-axis table: the plan-side mirror of
# `engine.KNOB_DOMAINS` (ISSUE 20). One entry per composable FaultPlan
# axis, declaring the fields the axis binds and the ranges the fuzzer
# draws within. Ranges are chosen for the CPU-twin oracle: sleeps stay
# sub-10ms (straggler_delay_s, step_time_s) so a 50-case soak clears in
# minutes, and rates sit where faults actually FIRE in a 2-loop run.
#
# 'crash' binds no scalar fields: its schedule is structural (a
# CrashPoint drawn against the round cursor) and EVERY oracle case
# carries one anyway — the crash+resume+twin comparison is the oracle's
# spine, so 'crash' membership in `axes` only marks shrinkability.
#
# 'storage' deliberately draws from the TRANSIENT modes only: the
# zero-repairs invariant (`storage_clean`) holds for faults the bounded
# retry can out-wait (bitrot/torn/ioerror garble one read/write
# attempt); a persistent `enospc` disk legitimately ends in the repair
# ladder, outside that invariant's domain (docs/FAULT.md).
PLAN_DOMAINS: dict = {
    "dropout": {
        "dropout_p": {"kind": "float", "lo": 0.1, "hi": 0.6},
    },
    "straggler": {
        "straggler_p": {"kind": "float", "lo": 0.2, "hi": 0.8},
        "straggler_delay_s": {"kind": "float", "lo": 0.001, "hi": 0.008},
    },
    "crash": {},
    "corruption": {
        "corrupt_k": {"kind": "int", "lo": 1, "hi": 2},
        "corrupt_mode": {
            "kind": "choice",
            "choices": ["scale", "signflip", "nan_burst", "gauss"],
        },
        "corrupt_strength": {"kind": "float", "lo": 1.5, "hi": 8.0},
    },
    "speed": {
        "slow_k": {"kind": "int", "lo": 1, "hi": 2},
        "slow_factor": {"kind": "float", "lo": 1.5, "hi": 4.0},
        "step_time_s": {"kind": "float", "lo": 0.0005, "hi": 0.002},
    },
    "churn": {
        "churn_p": {"kind": "float", "lo": 0.1, "hi": 0.4},
        "churn_mean_absence": {"kind": "float", "lo": 1.0, "hi": 3.0},
    },
    "storage": {
        "storage_p": {"kind": "float", "lo": 0.05, "hi": 0.25},
        "storage_mode": {
            "kind": "choice", "choices": ["bitrot", "torn", "ioerror"],
        },
        "storage_strength": {"kind": "float", "lo": 1.0, "hi": 2.0},
    },
}

AXES: Tuple[str, ...] = tuple(PLAN_DOMAINS)

# the fields each axis binds (used by the shrinker to reset a removed
# axis back to the FaultPlan dataclass defaults)
AXIS_FIELDS: Dict[str, Tuple[str, ...]] = {
    ax: tuple(spec) for ax, spec in PLAN_DOMAINS.items()
}
AXIS_FIELDS["crash"] = ("crashes",)
AXIS_FIELDS["corruption"] += ("corrupt_p",)
AXIS_FIELDS["speed"] += ("slow_p",)

# the knob-lattice groups the fuzzer composes on top of the plan. Each
# group is a COHERENT set of ExperimentConfig fields (drawn from
# engine.KNOB_DOMAINS ranges) that must be added or removed together —
# a codec's fraction without its codec is invalid, a cohort's shards
# without its population is invalid — which makes the group the
# shrinker's unit of removal.
KNOB_GROUPS: Tuple[str, ...] = (
    "robust", "quarantine", "codec", "schedule",
    "deadline", "cohort", "fold", "probes",
)

# validity couplings the generator enforces by construction and the
# shrinker must preserve (removing the key's requirement would turn a
# searched-for engine bug into a self-inflicted invalid config):
#   churn axis      -> cohort knob group (churn acts on the sampler pool)
#   deadline knobs  -> speed axis (budgets derive from plan step times)
#   nan_burst mode  -> robust knob group present, quarantine absent
#                      (the robust_finite invariant isolates the
#                      combiner's finite-screening; quarantine would
#                      mask a broken combiner by excluding the NaN
#                      sender upstream)
_COUPLINGS = {
    "churn": "cohort",
    "deadline": "speed",
}

# model 'net', non-shuffled, max_groups=1: the single trained group is
# gid 2 (partition train_order[0] — pinned by tests/test_fault_cli.py);
# every generated crash point targets it so the crash deterministically
# fires under the fixed schedule. Adaptive schedules may legitimately
# never visit it — the oracle's crash_fired invariant is scoped to
# fixed schedules for exactly that reason.
_NET_FIRST_GID = 2


def _draw(rng: np.random.Generator, spec: dict):
    """Draw one value from a PLAN_DOMAINS/KNOB_DOMAINS-style field spec."""
    if spec["kind"] == "choice":
        return spec["choices"][int(rng.integers(len(spec["choices"])))]
    if spec["kind"] == "int":
        return int(rng.integers(spec["lo"], spec["hi"] + 1))
    if spec["kind"] == "float":
        return round(float(rng.uniform(spec["lo"], spec["hi"])), 6)
    raise ValueError(f"undrawable spec kind {spec['kind']!r}")


# --------------------------------------------------------------- cases


@dataclasses.dataclass(frozen=True)
class ChaosCase:
    """One drawn composed configuration: a FaultPlan + a knob lattice.

    `knobs` maps knob-group name -> the ExperimentConfig field overrides
    that group contributes; `base` holds the scalar run shape (strategy,
    n_clients, nloop, nadmm). The case is fully serializable
    (`to_doc`/`from_doc` — the repro-bundle format) and its plan
    round-trips through the STRICT FaultPlan JSON loader, so a bundle
    written by one session is rejected loudly, never reinterpreted, by
    a session whose plan schema drifted.
    """

    index: int
    gen_seed: int
    axes: Tuple[str, ...]
    plan: FaultPlan
    knobs: Dict[str, Dict[str, Any]]
    base: Dict[str, Any]
    tags: Tuple[str, ...] = ()

    def config_overrides(self) -> Dict[str, Any]:
        over = dict(self.base)
        for group in sorted(self.knobs):
            over.update(self.knobs[group])
        return over

    def population(self) -> int:
        """The fault-plan population N: virtual clients in cohort mode,
        the fixed client count otherwise."""
        for g in self.knobs.values():
            if "virtual_clients" in g:
                return int(g["virtual_clients"])
        return int(self.base["n_clients"])

    def to_doc(self) -> dict:
        return {
            "index": self.index,
            "gen_seed": self.gen_seed,
            "axes": list(self.axes),
            "plan": json.loads(self.plan.to_json()),
            "knobs": {g: dict(f) for g, f in self.knobs.items()},
            "base": dict(self.base),
            "tags": list(self.tags),
        }

    @classmethod
    def from_doc(cls, doc: dict) -> "ChaosCase":
        plan = FaultPlan.from_json(json.dumps(doc["plan"]))
        return cls(
            index=int(doc["index"]),
            gen_seed=int(doc["gen_seed"]),
            axes=tuple(doc["axes"]),
            plan=plan,
            knobs={g: dict(f) for g, f in doc["knobs"].items()},
            base=dict(doc["base"]),
            tags=tuple(doc.get("tags", ())),
        )


class ChaosPlanGenerator:
    """Seeded validity-aware fuzzer over composed fault configurations.

    `draw(i)` is pure in `(seed, i)` — `np.random.default_rng([seed, i])`
    — so any case from any soak is reconstructible from the two ints in
    its verdict line. Cases 0-2 are the deterministic invariant probes
    (robust_finite, all_dropped, transparent); from case 3 on, the
    coverage rotation forces axis `AXES[i % 7]` and knob group
    `KNOB_GROUPS[i % 8]` while every other axis/group joins with fixed
    probability, and the validity couplings (`_COUPLINGS`) are applied
    after the draw.
    """

    def __init__(self, seed: int = 0):
        self.seed = int(seed)
        # the engine's exported domain table: the SAME source the config
        # validators enforce, so a drawn knob cannot drift out of the
        # accepted range (generator/validator agreement is a lookup).
        # Imported lazily-at-init: engine.config imports no jax, but
        # keeping chaos.py importable standalone mirrors scrub/report.
        from federated_pytorch_test_tpu.engine.config import KNOB_DOMAINS

        self._kd = KNOB_DOMAINS

    # ------------------------------------------------- deterministic probes

    def _probe_robust_finite(self, i: int) -> ChaosCase:
        """Case 0: nan_burst corruption vs a robust combiner, NO
        quarantine — the honest engine keeps every streamed value
        finite (consensus/robust.py screens non-finite survivors); a
        combiner that averages NaNs in violates `robust_finite`. This
        is the planted-bug CI leg's tripwire, first in every soak."""
        plan = FaultPlan(
            seed=101, corrupt_k=1, corrupt_mode="nan_burst",
            crashes=(CrashPoint(1, _NET_FIRST_GID, 0),),
        )
        return ChaosCase(
            index=i, gen_seed=self.seed,
            axes=("corruption", "crash"), plan=plan,
            knobs={"robust": {"robust_agg": "median", "robust_f": 1}},
            base=self._base(n_clients=5),
            tags=("robust_finite",),
        )

    def _probe_all_dropped(self, i: int) -> ChaosCase:
        """Case 1: dropout_p=1.0 — every exchange loses every client.
        The engine must keep the consensus state (z) exactly, ship zero
        uplink bytes, and stay finite end to end."""
        plan = FaultPlan(
            seed=102, dropout_p=1.0,
            crashes=(CrashPoint(1, _NET_FIRST_GID, 0),),
        )
        return ChaosCase(
            index=i, gen_seed=self.seed,
            axes=("dropout", "crash"), plan=plan,
            knobs={}, base=self._base(),
            tags=("all_dropped",),
        )

    def _probe_transparent(self, i: int) -> ChaosCase:
        """Case 2: every drawn axis at its identity point — dropout 0.0,
        slow_factor x1.0, scale-corruption strength x1.0. The plan is
        ACTIVE (masks drawn, speeds assigned, corruption applied) yet
        must be bit-transparent: the twin's final parameters equal a
        plan-free run's exactly."""
        plan = FaultPlan(
            seed=103, dropout_p=0.0,
            corrupt_k=1, corrupt_mode="scale", corrupt_strength=1.0,
            slow_k=1, slow_factor=1.0, step_time_s=0.001,
            crashes=(CrashPoint(1, _NET_FIRST_GID, 0),),
        )
        return ChaosCase(
            index=i, gen_seed=self.seed,
            axes=("dropout", "corruption", "speed", "crash"), plan=plan,
            knobs={}, base=self._base(),
            tags=("transparent",),
        )

    # ------------------------------------------------------------- drawing

    def _base(self, n_clients: int = 3, strategy: str = "fedavg") -> dict:
        return {
            "n_clients": n_clients, "strategy": strategy,
            "nloop": 2, "nadmm": 2,
        }

    def draw(self, i: int) -> ChaosCase:
        if i == 0:
            return self._probe_robust_finite(i)
        if i == 1:
            return self._probe_all_dropped(i)
        if i == 2:
            return self._probe_transparent(i)
        rng = np.random.default_rng([self.seed, i])

        axes = {AXES[i % len(AXES)], "crash"}
        for ax in AXES:
            if rng.random() < 0.35:
                axes.add(ax)
        groups = {KNOB_GROUPS[i % len(KNOB_GROUPS)]}
        for g in KNOB_GROUPS:
            if rng.random() < 0.30:
                groups.add(g)
        # validity couplings (_COUPLINGS): churn acts on the sampler
        # pool, deadline budgets derive from the plan's step times
        if "churn" in axes:
            groups.add(_COUPLINGS["churn"])
        if "deadline" in groups:
            axes.add(_COUPLINGS["deadline"])

        base = self._base(
            n_clients=int(rng.integers(3, 6)),
            strategy="admm" if rng.random() < 0.4 else "fedavg",
        )
        cohort_mode = "cohort" in groups
        # the client axis the combiners see: the cohort in cohort mode
        k_axis = 4 if cohort_mode else base["n_clients"]

        tags: List[str] = []
        plan_fields = self._draw_plan(axes, rng)
        knobs = self._draw_knobs(groups, rng, k_axis, cohort_mode)

        # nan_burst coupling: force a robust defense, forbid quarantine
        if plan_fields.get("corrupt_mode") == "nan_burst":
            if "robust" not in knobs or knobs["robust"]["robust_agg"] == "clip":
                knobs["robust"] = {
                    "robust_agg": "median" if rng.random() < 0.5 else "trimmed",
                    "robust_f": max(1, plan_fields.get("corrupt_k", 1)),
                }
            knobs["robust"]["robust_f"] = max(
                knobs["robust"]["robust_f"], plan_fields.get("corrupt_k", 1)
            )
            knobs.pop("quarantine", None)
            tags.append("robust_finite")
        # trimmed needs k_axis > 2f; corruption needs corrupt_k <= N
        if knobs.get("robust", {}).get("robust_agg") == "trimmed":
            f_max = max(1, (k_axis - 1) // 2)
            knobs["robust"]["robust_f"] = min(
                knobs["robust"]["robust_f"], f_max
            )
            if "corrupt_k" in plan_fields and "robust_finite" in tags:
                plan_fields["corrupt_k"] = min(
                    plan_fields["corrupt_k"], knobs["robust"]["robust_f"]
                )
        if "corrupt_k" in plan_fields:
            plan_fields["corrupt_k"] = min(plan_fields["corrupt_k"], k_axis)
        if "slow_k" in plan_fields:
            plan_fields["slow_k"] = min(plan_fields["slow_k"], k_axis)

        crashes = [CrashPoint(1, _NET_FIRST_GID, 0)]
        if rng.random() < 0.2 and base["nadmm"] > 1:
            crashes.append(CrashPoint(1, _NET_FIRST_GID, base["nadmm"] - 1))
        plan = FaultPlan(
            seed=1000 + i, crashes=tuple(crashes), **plan_fields
        )
        return ChaosCase(
            index=i, gen_seed=self.seed,
            axes=tuple(a for a in AXES if a in axes),
            plan=plan, knobs=knobs, base=base, tags=tuple(tags),
        )

    def _draw_plan(
        self, axes: set, rng: np.random.Generator
    ) -> Dict[str, Any]:
        fields: Dict[str, Any] = {}
        for ax in AXES:
            if ax not in axes or ax == "crash":
                continue
            for name, spec in PLAN_DOMAINS[ax].items():
                fields[name] = _draw(rng, spec)
        if "corruption" in axes:
            # corrupt_p unused by the engine's k-based targeting here;
            # k clients per exchange is the composable contract
            fields["corrupt_p"] = 0.0
        return fields

    def _draw_knobs(
        self,
        groups: set,
        rng: np.random.Generator,
        k_axis: int,
        cohort_mode: bool,
    ) -> Dict[str, Dict[str, Any]]:
        kd = self._kd
        knobs: Dict[str, Dict[str, Any]] = {}
        if "robust" in groups:
            method = ("median", "trimmed", "clip")[int(rng.integers(3))]
            g: Dict[str, Any] = {"robust_agg": method}
            if method == "trimmed":
                g["robust_f"] = int(rng.integers(1, max(2, (k_axis - 1) // 2) + 1))
            else:
                g["robust_f"] = 1
            knobs["robust"] = g
        if "quarantine" in groups:
            knobs["quarantine"] = {
                "quarantine_z": round(float(rng.uniform(2.0, 4.0)), 3)
            }
        if "codec" in groups:
            pick = ("bf16", "topk", "quant")[int(rng.integers(3))]
            if pick == "bf16":
                knobs["codec"] = {"exchange_dtype": "bfloat16"}
            elif pick == "topk":
                knobs["codec"] = {
                    "exchange_codec": "topk",
                    "topk_fraction": round(float(rng.uniform(0.2, 0.6)), 3),
                    "error_feedback": bool(rng.random() < 0.5),
                }
            else:
                knobs["codec"] = {
                    "exchange_codec": "quant",
                    "quant_bits": (8, 4)[int(rng.integers(2))],
                    "error_feedback": bool(rng.random() < 0.5),
                }
        if "schedule" in groups:
            knobs["schedule"] = {
                "group_schedule": "adaptive",
                "group_skip_frac": round(float(rng.uniform(0.0, 0.5)), 3),
                "max_groups": 2,
            }
        if "deadline" in groups:
            if rng.random() < 0.5:
                knobs["deadline"] = {
                    "round_deadline": round(float(rng.uniform(0.05, 0.2)), 4)
                }
            else:
                knobs["deadline"] = {
                    "round_deadline": ("auto", "auto:p75")[int(rng.integers(2))]
                }
        if "cohort" in groups:
            g = {
                "virtual_clients": 8,
                "cohort": 4,
                "cohort_seed": int(rng.integers(0, 10)),
                "cohort_weighting": ("uniform", "samples")[int(rng.integers(2))],
                "data_shards": (1, 2, 4)[int(rng.integers(3))],
                "store_chunk_clients": 2,
                "prefetch": bool(rng.random() < 0.5),
            }
            if rng.random() < 0.5:
                g["store_resident_chunks"] = 2
            knobs["cohort"] = g
        if "fold" in groups:
            knobs["fold"] = {
                "client_fold": _draw(rng, kd["client_fold"])
            }
        if "probes" in groups:
            knobs["probes"] = {
                "linesearch_probes": int(rng.integers(2, kd["linesearch_probes"]["hi"] + 1))
            }
        return knobs


# --------------------------------------------------------------- oracle


def norm_stream_records(path: str) -> List[dict]:
    """THE twin-stream normalizer: parse a JSONL metric stream into
    records equal modulo wall-clock fields — the `t` stamp, per-line
    `crc`, `step_time` seconds — and the header tag (crashed+resumed
    twins' configs legitimately differ by the fired crash point and the
    run-dir paths baked into the tag). Single definition shared by the
    chaos oracle and tests/conftest.py's `norm_stream` fixture (the
    pytest face); scripts/ci.sh `assert_stream_identity` mirrors it for
    shell legs. A wall-clock field added to the stream format is then
    ignored (or surfaced) everywhere at once."""
    out = []
    for line in open(path):
        d = json.loads(line)
        d.pop("t", None)
        d.pop("crc", None)
        if d.get("event") == "stream_header":
            d.pop("tag", None)
        if d.get("series") == "step_time":
            d["value"] = {
                k: v for k, v in d["value"].items() if k != "seconds"
            }
        out.append(d)
    return out


_SOURCE = None


def _source():
    """One shared synthetic dataset per process (the test-suite idiom):
    the trainer shards it per client count, so every case reuses it."""
    global _SOURCE
    if _SOURCE is None:
        from federated_pytorch_test_tpu.data import synthetic_cifar

        _SOURCE = synthetic_cifar(n_train=240, n_test=60)
    return _SOURCE


def _build_cfg(case: ChaosCase, run_dir: str, plan: FaultPlan):
    from federated_pytorch_test_tpu.engine import get_preset

    os.makedirs(run_dir, exist_ok=True)
    plan_path = os.path.join(run_dir, "plan.json")
    with open(plan_path, "w") as f:
        f.write(plan.to_json())
    over = case.config_overrides()
    over.update(
        model="net", batch=40, check_results=False, synthetic_ok=True,
        shuffle_group_order=False,
        fault_plan=plan_path,
        metrics_stream=os.path.join(run_dir, "stream.jsonl"),
        checkpoint_dir=os.path.join(run_dir, "ckpt"),
        save_model=True, resume="auto",
    )
    over.setdefault("max_groups", 1)
    return get_preset("fedavg", **over)


def _final_flat(trainer) -> np.ndarray:
    return np.asarray(trainer._fetch(trainer.flat))


def _run_to_completion(cfg, src, max_crashes: int):
    """Run a config, auto-resuming through every planned crash; returns
    (trainer, crashes_fired)."""
    from federated_pytorch_test_tpu.engine import Trainer
    from federated_pytorch_test_tpu.fault import InjectedCrash

    fired = 0
    for _ in range(max_crashes + 2):
        tr = Trainer(cfg, verbose=False, source=src)
        try:
            tr.run()
            return tr, fired
        except InjectedCrash:
            fired += 1
    raise RuntimeError(
        f"run never completed after {fired} injected crashes "
        f"(planned {max_crashes}) — the resume ladder is stuck"
    )


def _injected_storage_error(exc: BaseException) -> bool:
    """True when `exc` is the storage shim's own loud failure: an OSError
    carrying the shim's "injected" marker (fault/io.py) that survived
    retry_io's bounded attempts. Each retry re-draws at storage_p (fresh
    op ordinal), so under the error modes an op aborts with probability
    storage_p**attempts — a tail that grows with the op population.
    That abort is the engine's DOCUMENTED contract for a persistent
    error-mode storm ("persistent failures stay loud"), not a bug."""
    return (
        isinstance(exc, OSError)
        and exc.errno in (errno.EIO, errno.ENOSPC)
        and "injected" in str(exc)
    )


def _tolerated_abort(case, exc, crashes_fired, t0, workdir, run_dirs):
    """Verdict for a run that aborted on a retry-exhausted injected
    storage error. The abort itself is tolerated (see
    _injected_storage_error), but the oracle still holds the engine to
    crash-consistency on the way down: error-mode faults refuse I/O
    BEFORE bytes move, so an abort may stop the run, never corrupt the
    store — every run dir must still scrub clean."""
    violations: List[dict] = []
    if case.plan.storage_mode not in ("ioerror", "enospc"):
        # bitrot/torn are read-side buffer damage — they can never
        # surface as an injected OSError, so this abort is unexplained
        violations.append({
            "invariant": "run_completes",
            "detail": (
                f"injected storage OSError under mode="
                f"{case.plan.storage_mode!r}, which never raises: {exc}"
            ),
        })
    from federated_pytorch_test_tpu.fault.scrub import scrub_main

    for i, d in enumerate(run_dirs):
        if not os.path.isdir(d):
            continue
        report_path = os.path.join(workdir, f"scrub-abort-{i}.json")
        rc = scrub_main([d, "--json", report_path])
        with open(report_path) as f:
            doc = json.load(f)
        if rc != 0 or not verify_crc(doc) or not doc.get("ok", False):
            violations.append({
                "invariant": "storage_clean",
                "detail": (
                    f"store at {d} does not scrub clean after a tolerated "
                    f"abort (rc={rc}) — error-mode faults must refuse "
                    "before bytes move, leaving the disk pristine"
                ),
            })
    v = _verdict(case, violations, crashes_fired, t0, workdir)
    v["tags"].append("storage_abort_tolerated")
    return v


# names of every oracle invariant, in check order (docs/FAULT.md
# §Chaos harness carries the catalog with the full semantics)
INVARIANTS: Tuple[str, ...] = (
    "run_completes",        # no unplanned exception escapes the Trainer
    "crash_fired",          # the planned crash actually fired (fixed schedule)
    "stream_twin_identity", # resumed stream == uninterrupted twin's, normalized
    "fused_dispatch",       # fused rounds stay {round:1, round_init:1}
    "ledger_conservation",  # comm_bytes records == pure-plan reconstruction
    "scoreboard",           # injected_faults == twin's == pure recomputation
    "all_dropped_keeps_state",  # p=1.0 dropout: zero uplink, finite, z kept
    "robust_finite",        # robust defense keeps every streamed value finite
    "transparent_axes",     # identity-strength axes are bit-transparent
    "storage_clean",        # transient storage chaos: zero repairs, clean scrub
)


def run_case(case: ChaosCase, workdir: str) -> dict:
    """Run one case under the full invariant oracle; returns the verdict
    `{ok, violations: [{invariant, detail}], crashes_fired, wall_s}`."""
    t0 = time.time()
    violations: List[dict] = []

    def fail(inv: str, detail: str) -> None:
        violations.append({"invariant": inv, "detail": detail})

    plan_crash = case.plan
    plan_twin = dataclasses.replace(plan_crash, crashes=())
    dir_b = os.path.join(workdir, "crash")
    dir_a = os.path.join(workdir, "twin")
    cfg_b = _build_cfg(case, dir_b, plan_crash)
    cfg_a = _build_cfg(case, dir_a, plan_twin)
    src = _source()
    adaptive = "schedule" in case.knobs
    cohort = "cohort" in case.knobs

    crashes_fired = 0
    try:
        tr_b, crashes_fired = _run_to_completion(
            cfg_b, src, len(plan_crash.crashes)
        )
        tr_a, _ = _run_to_completion(cfg_a, src, 0)
    except Exception as e:
        if plan_crash.has_storage and _injected_storage_error(e):
            return _tolerated_abort(
                case, e, crashes_fired, t0, workdir, (dir_b, dir_a)
            )
        fail("run_completes", traceback.format_exc(limit=8))
        return _verdict(case, violations, crashes_fired, t0, workdir)

    rec_a, rec_b = tr_a.recorder, tr_b.recorder

    # crash_fired — scoped to fixed schedules: an adaptive scheduler may
    # legitimately never visit the crash point's group
    if plan_crash.crashes and not adaptive and crashes_fired == 0:
        fail(
            "crash_fired",
            f"planned crashes {plan_crash.crashes} never fired under the "
            "fixed schedule",
        )

    # stream_twin_identity
    na = norm_stream_records(cfg_a.metrics_stream)
    nb = norm_stream_records(cfg_b.metrics_stream)
    if na != nb:
        idx = next(
            (i for i, (x, y) in enumerate(zip(na, nb)) if x != y),
            min(len(na), len(nb)),
        )
        fail(
            "stream_twin_identity",
            f"streams diverge at record {idx}: "
            f"twin={na[idx] if idx < len(na) else '<end>'} "
            f"resumed={nb[idx] if idx < len(nb) else '<end>'}",
        )

    # fused_dispatch
    if tr_a._fused_enabled():
        for r in rec_a.series.get("dispatch_count", []):
            if r["value"] != {"round": 1, "round_init": 1, "total": 2}:
                fail(
                    "fused_dispatch",
                    f"fused round dispatched {r['value']} at "
                    f"nloop={r.get('nloop')} group={r.get('group')}",
                )
                break

    # ledger_conservation: internal consistency always; pure-plan
    # reconstruction when survivors are plan-pure (no deadline budgets,
    # no adaptive visits)
    for name, tr, rec in (("twin", tr_a, rec_a), ("resumed", tr_b, rec_b)):
        records = rec.series.get("comm_bytes", [])
        total = sum(int(r["value"]) for r in records)
        summ = rec.latest("comm_summary") or {}
        if total != summ.get("bytes_total"):
            fail(
                "ledger_conservation",
                f"{name}: sum(comm_bytes records)={total} != "
                f"comm_summary bytes_total={summ.get('bytes_total')}",
            )
    if "deadline" not in case.knobs and not adaptive:
        N = case.population()
        expected = []
        for nloop in range(cfg_a.nloop):
            ids = tr_a.sampler.cohort(nloop) if cohort else None
            for gid in tr_a.group_order:
                for a in range(cfg_a.nadmm):
                    mask = plan_twin.participation(N, nloop, gid, a)
                    if ids is not None:
                        mask = mask[ids]
                    surv = int(mask.sum())
                    expected.append(
                        (nloop, gid, a, surv, tr_a._comm.round_bytes(gid, surv))
                    )
        got = [
            (r["nloop"], r["group"], r["nadmm"], r["survivors"], int(r["value"]))
            for r in rec_a.series.get("comm_bytes", [])
        ]
        if got != expected:
            fail(
                "ledger_conservation",
                f"pure-plan reconstruction mismatch: expected {expected[:6]}"
                f"... got {got[:6]}...",
            )

    # scoreboard: resumed == twin (modulo the fired crash schedule and
    # the per-op storage counter), and both match the pure recomputation
    counts_a = dict(rec_a.latest("injected_faults") or {})
    counts_b = dict(rec_b.latest("injected_faults") or {})
    if counts_b.get("crashes", 0) != len(plan_crash.crashes):
        fail(
            "scoreboard",
            f"resumed run reports crashes={counts_b.get('crashes')} but the "
            f"plan schedules {len(plan_crash.crashes)}",
        )
    drop_keys = ("crashes", "storage_faults")
    cmp_a = {k: v for k, v in counts_a.items() if k not in drop_keys}
    cmp_b = {k: v for k, v in counts_b.items() if k not in drop_keys}
    if cmp_a != cmp_b:
        fail(
            "scoreboard",
            f"resumed scoreboard {cmp_b} != twin scoreboard {cmp_a}",
        )
    if "deadline" not in case.knobs and not adaptive:
        from federated_pytorch_test_tpu.fault import FaultInjector

        inj = FaultInjector(plan_twin, case.population())
        pure = inj.injected_summary(
            cfg_a.nloop, tr_a.group_order, cfg_a.nadmm,
            exchanges=cfg_a.strategy != "none",
            cohort=tr_a.sampler.cohort if cohort else None,
        )
        for k in ("drops", "stragglers", "corruptions", "churned"):
            if k in pure and counts_a.get(k, 0) != pure[k]:
                fail(
                    "scoreboard",
                    f"twin {k}={counts_a.get(k)} != pure-plan {k}={pure[k]}",
                )

    # tag probes
    if "all_dropped" in case.tags:
        survs = [
            r["value"]["survivors"]
            for r in rec_a.series.get("participation", [])
        ]
        summ = rec_a.latest("comm_summary") or {}
        if survs and set(survs) != {0}:
            fail(
                "all_dropped_keeps_state",
                f"p=1.0 dropout left survivors {sorted(set(survs))}",
            )
        if summ.get("bytes_total"):
            fail(
                "all_dropped_keeps_state",
                f"all-dropped run shipped {summ['bytes_total']} uplink bytes",
            )
        if rec_a.first_nonfinite is not None:
            fail(
                "all_dropped_keeps_state",
                f"non-finite under full dropout: {rec_a.first_nonfinite}",
            )

    if "robust_finite" in case.tags:
        for name, rec in (("twin", rec_a), ("resumed", rec_b)):
            if rec.first_nonfinite is not None:
                fail(
                    "robust_finite",
                    f"{name}: first non-finite at {rec.first_nonfinite} — the "
                    "robust combiner let a corrupted update through",
                )
            if rec.series.get("fault"):
                fail(
                    "robust_finite",
                    f"{name}: fault records "
                    f"{[r['value'] for r in rec.series['fault']]} under a "
                    "robust defense sized for the corruption",
                )
        if not np.all(np.isfinite(_final_flat(tr_a))):
            fail("robust_finite", "twin's final parameters are non-finite")

    if "transparent" in case.tags:
        dir_c = os.path.join(workdir, "bare")
        try:
            from federated_pytorch_test_tpu.engine import get_preset

            over = case.config_overrides()
            over.update(
                model="net", batch=40, check_results=False,
                synthetic_ok=True, shuffle_group_order=False,
                metrics_stream=os.path.join(dir_c, "stream.jsonl"),
                checkpoint_dir=os.path.join(dir_c, "ckpt"),
                save_model=True, resume="auto",
            )
            over.setdefault("max_groups", 1)
            os.makedirs(dir_c, exist_ok=True)
            tr_c, _ = _run_to_completion(
                get_preset("fedavg", **over), src, 0
            )
            if not np.array_equal(_final_flat(tr_a), _final_flat(tr_c)):
                fail(
                    "transparent_axes",
                    "identity-strength plan (dropout 0.0, x1.0 scale "
                    "corruption, x1.0 slowdown) changed the final "
                    "parameters vs the plan-free run",
                )
        except Exception:
            fail("transparent_axes", traceback.format_exc(limit=8))

    # cohort data path: the twin's sidecar must show the client store
    # actually moved rows (clients/store.py traffic()) — a cohort run
    # whose gathers never fired is exchanging stale state silently
    if cohort:
        side = cfg_a.metrics_stream + ".status.json"
        try:
            with open(side) as f:
                traffic = (json.load(f).get("store") or {}).get("traffic")
        except (OSError, ValueError) as e:
            traffic = None
            fail("ledger_conservation", f"twin: unreadable sidecar {side}: {e}")
        if traffic is not None:
            bad = {
                k: v for k, v in traffic.items()
                if not isinstance(v, int) or v < 0
            }
            if bad or traffic.get("gather_rows", 0) < case.knobs["cohort"]["cohort"]:
                fail(
                    "ledger_conservation",
                    f"twin: store traffic {traffic} — cohort mode must "
                    "gather at least one full cohort of rows",
                )

    # storage_clean: transient storage chaos heals via bounded retry —
    # never the repair ladder — and the run dir scrubs clean afterwards
    if "storage" in case.axes:
        for name, cfg, rec, tr in (
            ("twin", cfg_a, rec_a, tr_a), ("resumed", cfg_b, rec_b, tr_b),
        ):
            side = cfg.metrics_stream + ".status.json"
            try:
                with open(side) as f:
                    doc = json.load(f)
            except (OSError, ValueError) as e:
                fail("storage_clean", f"{name}: unreadable sidecar {side}: {e}")
                continue
            integ = doc.get("integrity") or {}
            repairs = int(integ.get("repairs_prior", 0)) + int(
                integ.get("repairs_reinit", 0)
            )
            if repairs:
                fail(
                    "storage_clean",
                    f"{name}: {repairs} repair(s) under transient storage "
                    f"faults (integrity={integ}) — bounded retry should "
                    "have healed every read",
                )
        from federated_pytorch_test_tpu.fault.scrub import scrub_main

        report_path = os.path.join(workdir, "scrub.json")
        rc = scrub_main([dir_b, "--json", report_path])
        with open(report_path) as f:
            doc = json.load(f)
        if not verify_crc(doc):
            fail("storage_clean", "scrub --json report failed its own crc")
        if rc not in (0,) or not doc.get("ok", False):
            fail(
                "storage_clean",
                f"post-run scrub of {dir_b} found problems: "
                f"{[r.get('problems') for r in doc.get('roots', [])]}",
            )

    return _verdict(case, violations, crashes_fired, t0, workdir)


def _verdict(case, violations, crashes_fired, t0, workdir) -> dict:
    return {
        "case": case.index,
        "seed": [case.gen_seed, case.index],
        "tags": list(case.tags),
        "axes": list(case.axes),
        "knobs": sorted(case.knobs),
        "ok": not violations,
        "violations": violations,
        "crashes_fired": crashes_fired,
        "wall_s": round(time.time() - t0, 3),
        "workdir": workdir,
    }


# -------------------------------------------------------------- shrinker


def _plan_defaults() -> Dict[str, Any]:
    return {
        f.name: f.default
        for f in dataclasses.fields(FaultPlan)
        if f.default is not dataclasses.MISSING
    }


def _drop_axis(case: ChaosCase, axis: str) -> Optional[ChaosCase]:
    """Remove one fault axis (reset its plan fields to defaults),
    preserving the validity couplings — returns None where removal
    would manufacture an invalid or semantically different case."""
    if axis not in case.axes:
        return None
    # nan_burst's defense is load-bearing for the robust_finite probe:
    # the corruption axis may be removed (taking the tag's trigger with
    # it), but never the other way around (see _drop_knob)
    defaults = _plan_defaults()
    repl = {f: defaults[f] for f in AXIS_FIELDS[axis]}
    if axis == "crash":
        repl = {"crashes": ()}
    plan = dataclasses.replace(case.plan, **repl)
    knobs = {g: dict(f) for g, f in case.knobs.items()}
    tags = tuple(
        t for t in case.tags
        if not (t == "robust_finite" and axis == "corruption")
    )
    if axis == "speed":
        knobs.pop("deadline", None)  # budgets derive from plan step times
    return dataclasses.replace(
        case, axes=tuple(a for a in case.axes if a != axis),
        plan=plan, knobs=knobs, tags=tags,
    )


def _drop_knob(case: ChaosCase, group: str) -> Optional[ChaosCase]:
    if group not in case.knobs:
        return None
    if group == "cohort" and "churn" in case.axes:
        return None  # churn requires the sampler pool — coupled removal only
    if group == "robust" and case.plan.corrupt_mode == "nan_burst" and (
        "corruption" in case.axes
    ):
        return None  # an undefended nan_burst fails honest engines too
    knobs = {g: dict(f) for g, f in case.knobs.items() if g != group}
    return dataclasses.replace(case, knobs=knobs)


def components(case: ChaosCase) -> List[Tuple[str, ChaosCase]]:
    """Every single-component reduction of `case`, in shrink order
    (axes -> knob groups -> crash schedule -> rounds -> clients)."""
    out: List[Tuple[str, ChaosCase]] = []
    for ax in case.axes:
        if ax == "crash":
            continue
        r = _drop_axis(case, ax)
        if r is not None:
            out.append((f"axis:{ax}", r))
    for g in sorted(case.knobs):
        r = _drop_knob(case, g)
        if r is not None:
            out.append((f"knob:{g}", r))
    if case.plan.crashes:
        r = _drop_axis(case, "crash")
        if r is not None:
            out.append(("crash:none", r))
    if case.base.get("nloop", 1) > 1:
        base = dict(case.base, nloop=1)
        plan = dataclasses.replace(
            case.plan,
            crashes=tuple(c for c in case.plan.crashes if c.nloop < 1),
        )
        out.append(
            ("rounds:1", dataclasses.replace(case, base=base, plan=plan))
        )
    if case.base.get("n_clients", 3) > 3 and "cohort" not in case.knobs:
        base = dict(case.base, n_clients=3)
        knobs = {g: dict(f) for g, f in case.knobs.items()}
        if "robust" in knobs:
            knobs["robust"]["robust_f"] = min(
                knobs["robust"].get("robust_f", 1), 1
            )
        repl = {}
        if case.plan.corrupt_k:
            repl["corrupt_k"] = min(case.plan.corrupt_k, 1)
        if case.plan.slow_k:
            repl["slow_k"] = min(case.plan.slow_k, 1)
        plan = dataclasses.replace(case.plan, **repl) if repl else case.plan
        out.append(
            (
                "clients:3",
                dataclasses.replace(case, base=base, knobs=knobs, plan=plan),
            )
        )
    return out


def shrink(
    case: ChaosCase,
    test_fn: Callable[[ChaosCase], bool],
    log: Optional[Callable[[str], None]] = None,
) -> ChaosCase:
    """Greedy delta-debugging: repeatedly drop the first single
    component whose removal keeps `test_fn` (\"still violates\") true,
    until no removal does. The fixpoint is 1-MINIMAL: every remaining
    component is individually necessary for the violation (removing any
    one makes it vanish) — not necessarily globally minimum, which
    would need an exponential search the repro loop doesn't."""
    cur = case
    changed = True
    while changed:
        changed = False
        for name, reduced in components(cur):
            if test_fn(reduced):
                if log:
                    log(f"shrink: dropped {name} — still violates")
                cur = reduced
                changed = True
                break
            if log:
                log(f"shrink: {name} is load-bearing")
    return cur


# ---------------------------------------------------------- repro bundle


def _collect_incidents(workdir: str, limit: int = 3) -> List[dict]:
    """Embed any flight-recorder incident bundles the failing runs
    dumped (`<stream>.incidents/incident-*.json`) — the post-mortem
    rides the repro file instead of a path that may not survive CI."""
    found: List[dict] = []
    for root, _dirs, files in os.walk(workdir):
        if not root.endswith(".incidents"):
            continue
        for fname in sorted(files):
            if len(found) >= limit:
                return found
            try:
                with open(os.path.join(root, fname)) as f:
                    found.append({"file": fname, "incident": json.load(f)})
            except (OSError, ValueError):
                found.append({"file": fname, "incident": None})
    return found


def write_repro_bundle(
    path: str, case: ChaosCase, verdict: dict, workdir: str
) -> dict:
    from federated_pytorch_test_tpu.obs.provenance import host_stamp

    doc = {
        "chaos_repro": 1,
        "case": case.to_doc(),
        "violations": verdict["violations"],
        "crashes_fired": verdict.get("crashes_fired", 0),
        "incidents": _collect_incidents(workdir),
        "provenance": host_stamp(),
    }
    text = stamp_crc(doc)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(text + "\n")
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return json.loads(text)


def load_repro_bundle(path: str) -> Tuple[ChaosCase, dict]:
    with open(path) as f:
        doc = json.load(f)
    if doc.get("chaos_repro") != 1:
        raise ValueError(f"{path} is not a chaos repro bundle")
    if not verify_crc(doc):
        raise ValueError(
            f"{path}: crc mismatch — the bundle was edited or torn; "
            "re-dump it from a soak rather than hand-fixing"
        )
    return ChaosCase.from_doc(doc["case"]), doc


# ------------------------------------------------------------ planted bug


def _apply_planted_bug(name: str) -> None:
    """Deliberately break the engine (CHAOS_PLANT_BUG=<name>) so CI can
    assert the oracle catches, shrinks, and reproduces a real violation.

    'combiner': replace the Byzantine-robust combiner with a naive
    masked mean that averages non-finite updates straight in — the
    exact failure `consensus/robust.py` exists to prevent, caught by
    the `robust_finite` invariant on soak case 0."""
    if name != "combiner":
        raise SystemExit(f"unknown CHAOS_PLANT_BUG {name!r} (have: combiner)")
    import jax.numpy as jnp

    from federated_pytorch_test_tpu.consensus import admm, fedavg
    from federated_pytorch_test_tpu.parallel import client_sum

    def broken_combine(v_local, mask, method, *, trim_f=0, prev=None,
                       axis_name=None):
        m = mask.astype(v_local.dtype)
        survivors = client_sum(m)
        safe = jnp.where(survivors > 0, survivors, 1.0)
        combined = client_sum(v_local * m[:, None]) / safe
        return combined, jnp.ones(combined.shape, bool)

    fedavg.robust_combine = broken_combine
    admm.robust_combine = broken_combine


# ------------------------------------------------------------------ CLI


def _setup_backend() -> None:
    """The conftest contract, verb-side: pin jax to an 8-device host-CPU
    mesh BEFORE any engine import, with the persistent compile cache on
    (a 50-case soak re-jits the same tiny shapes constantly)."""
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    from federated_pytorch_test_tpu.utils import (
        enable_compile_cache,
        force_host_cpu,
    )

    jax = force_host_cpu(min_devices=8)
    jax.config.update("jax_enable_x64", False)
    enable_compile_cache()


def _soak(args) -> int:
    from federated_pytorch_test_tpu.obs.provenance import host_stamp

    os.makedirs(args.out, exist_ok=True)
    stamp = host_stamp()
    gen = ChaosPlanGenerator(seed=args.seed)
    verdict_path = os.path.join(args.out, "verdicts.jsonl")
    t0 = time.time()
    axes_seen: Dict[str, int] = {}
    knobs_seen: Dict[str, int] = {}
    cleared = 0
    i = args.start_index
    with open(verdict_path, "a") as vf:
        while True:
            if args.cases is not None and cleared >= args.cases:
                break
            if args.budget_s is not None and time.time() - t0 > args.budget_s:
                print(f"# chaos: wall budget {args.budget_s}s exhausted")
                break
            case = gen.draw(i)
            workdir = os.path.join(args.out, f"case-{i:04d}")
            verdict = run_case(case, workdir)
            for ax in case.axes:
                axes_seen[ax] = axes_seen.get(ax, 0) + 1
            for g in case.knobs:
                knobs_seen[g] = knobs_seen.get(g, 0) + 1
            line = {
                **verdict,
                "coverage": {"axes": dict(axes_seen), "knobs": dict(knobs_seen)},
                "provenance": stamp,
            }
            vf.write(json.dumps(line, sort_keys=True) + "\n")
            vf.flush()
            status = "ok" if verdict["ok"] else "VIOLATION"
            print(
                f"# case {i}: {status} axes={','.join(case.axes)} "
                f"knobs={','.join(sorted(case.knobs)) or '-'} "
                f"tags={','.join(case.tags) or '-'} "
                f"wall={verdict['wall_s']}s"
            )
            if not verdict["ok"]:
                for v in verdict["violations"]:
                    print(f"#   {v['invariant']}: {v['detail'][:300]}")
                bundle = _shrink_and_dump(case, verdict, args)
                _write_summary(
                    args, stamp, cleared, 1, axes_seen, knobs_seen, t0
                )
                print(f"# chaos: violation shrunk -> {bundle}")
                return 2
            cleared += 1
            i += 1
    _write_summary(args, stamp, cleared, 0, axes_seen, knobs_seen, t0)
    print(
        f"# chaos: {cleared} case(s) clean, "
        f"{len(axes_seen)}/{len(AXES)} axes and "
        f"{len(knobs_seen)}/{len(KNOB_GROUPS)} knob groups covered, "
        f"{round(time.time() - t0, 1)}s"
    )
    return 0


def _shrink_and_dump(case: ChaosCase, verdict: dict, args) -> str:
    """Minimize the violating case and write the self-contained bundle."""
    bad = {v["invariant"] for v in verdict["violations"]}
    shrink_root = os.path.join(args.out, f"shrink-{case.index:04d}")
    os.makedirs(shrink_root, exist_ok=True)
    counter = {"n": 0}

    def still_violates(candidate: ChaosCase) -> bool:
        counter["n"] += 1
        wd = os.path.join(shrink_root, f"try-{counter['n']:03d}")
        v = run_case(candidate, wd)
        return bool(bad & {x["invariant"] for x in v["violations"]})

    shrunk = shrink(case, still_violates, log=lambda m: print(f"# {m}"))
    wd = os.path.join(shrink_root, "final")
    final_verdict = run_case(shrunk, wd)
    bundle_path = os.path.join(args.out, f"repro-{case.index:04d}.json")
    write_repro_bundle(bundle_path, shrunk, final_verdict, wd)
    print(
        f"# shrunk case {case.index}: axes "
        f"{list(case.axes)} -> {list(shrunk.axes)}, knobs "
        f"{sorted(case.knobs)} -> {sorted(shrunk.knobs)} "
        f"({counter['n']} oracle runs)"
    )
    return bundle_path


def _write_summary(args, stamp, cleared, violations, axes_seen, knobs_seen, t0):
    """The soak's summary artifact, `chaos_soak.json`: what was
    cleared, what was violated, how much of the axis and knob lattice
    the drawn cases covered, stamped with where it ran."""
    doc = {
        "workload": "chaos_soak",
        "seed": args.seed,
        "cases_cleared": cleared,
        "violations": violations,
        "axes_covered": len(axes_seen),
        "knob_groups_covered": len(knobs_seen),
        "wall_s": round(time.time() - t0, 3),
        "provenance": stamp,
    }
    path = os.path.join(args.out, "chaos_soak.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(stamp_crc(doc) + "\n")
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def _repro(args) -> int:
    case, doc = load_repro_bundle(args.repro)
    wanted = {v["invariant"] for v in doc.get("violations", [])}
    workdir = os.path.join(args.out, "repro")
    verdict = run_case(case, workdir)
    got = {v["invariant"] for v in verdict["violations"]}
    print(
        f"# repro {args.repro}: recorded {sorted(wanted)}, observed "
        f"{sorted(got)}"
    )
    if wanted & got:
        print("# repro: violation REPRODUCES")
        return 0
    print("# repro: violation did NOT reproduce")
    return 1


def chaos_main(argv: Optional[Sequence[str]] = None) -> int:
    """`chaos` verb entry point (engine-import-free dispatch).

    Usage:
      chaos [--budget-s S] [--cases N] [--seed S] [--out DIR]
      chaos --repro FILE [--out DIR]

    Soak mode fuzzes composed fault configurations under the invariant
    oracle until the case target or the wall budget is hit; any
    violation is shrunk to a 1-minimal repro bundle and exits 2. Repro
    mode replays a bundle and exits 0 iff the recorded violation
    reproduces. `CHAOS_PLANT_BUG=combiner` deliberately breaks the
    robust combiner first (the CI self-test).
    """
    import argparse

    parser = argparse.ArgumentParser(
        prog="federated_pytorch_test_tpu chaos",
        description="composed fault-plan fuzzer + invariant oracle + shrinker",
    )
    parser.add_argument(
        "--budget-s", type=float, default=None,
        help="wall budget for the soak (seconds)",
    )
    parser.add_argument(
        "--cases", type=int, default=None,
        help="stop after this many CLEAN cases (default: budget-bound; "
        "50 with no budget either)",
    )
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--start-index", type=int, default=0,
        help="first generator case index (resume a soak's sequence)",
    )
    parser.add_argument(
        "--out", default="chaos_runs",
        help="verdicts, run dirs, bundles and the soak summary land here",
    )
    parser.add_argument(
        "--repro", default=None,
        help="replay a repro bundle instead of soaking",
    )
    args = parser.parse_args(argv)
    if args.repro is None and args.budget_s is None and args.cases is None:
        args.cases = 50
    _setup_backend()
    plant = os.environ.get("CHAOS_PLANT_BUG")
    if plant:
        print(f"# chaos: PLANTED BUG active: {plant}")
        _apply_planted_bug(plant)
    if args.repro is not None:
        return _repro(args)
    return _soak(args)


if __name__ == "__main__":  # pragma: no cover
    import sys

    sys.exit(chaos_main())
