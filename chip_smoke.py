"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py          # on a machine that holds a TPU

One process (a chip belongs to one process at a time; nothing here
spawns a child that needs it) drives the trainer's main path through
the entry point a user calls — `federated_pytorch_test_tpu.__main__.main`
— and then the Pallas kernels, checks every stage from the run's own
artifacts, and prints as its LAST stdout line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Any failed check or raised stage ends the script non-zero with no such
line; so does a backend other than `tpu` (named in the message), before
any training. Stages, in order:

1. `main_path` — the `fedavg_resnet` preset exactly as shipped (ResNet18
   at its standard widths, B=32, f32, fused rounds, folded eval,
   client_fold=gemm, lbfgs_direction=compact), shortened only in
   schedule and data: seeded synthetic CIFAR, one outer loop, two
   consensus exchanges, the first two partition groups of the shuffled
   order (a small block and the largest). K = 3 clients, or one per
   chip when the host holds four or more.
2. `robust` — the `admm` preset (Net, BB rho) with the trimmed combiner,
   quarantine and one scale-corrupted client per exchange: the
   all-gather combiner and the ADMM collectives on the device.
3. `kernels` — every `pallas_call` in ops/ compiled (interpret mode
   asserted off) and compared with its `jax.numpy` reference at engine
   shapes.
4. `pallas_round` — one `fedavg_resnet` round with
   `lbfgs_direction=pallas` through the same CLI.

Small artifacts (metrics JSON, metric stream, status sidecar) land in
`chiprun_out/chip_smoke/`; checkpoints go to a temporary directory and
are removed.
"""

from __future__ import annotations

import collections
import importlib.metadata
import json
import math
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

RESNET18_PARAMS = 11_173_962
RESNET18_LARGEST_GROUP = 4_720_640


def require(cond, msg: str) -> None:
    """A smoke check: raises (never `assert`, which -O strips)."""
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


# ------------------------------------------------------------ the device


def device_report() -> dict:
    """Refuse any backend but `tpu`; return the device as jax reports it."""
    import jax
    import jaxlib

    from federated_pytorch_test_tpu.obs import chip_peaks

    backend = jax.default_backend()
    dev = jax.devices()[0]
    if backend != "tpu":
        raise SystemExit(
            f"chip_smoke.py needs backend 'tpu'; jax found "
            f"{backend!r} ({dev.device_kind} x{jax.device_count()})"
        )
    # raises for a TPU kind obs/roofline.py CHIP_PEAKS does not list
    chip_peaks(dev.device_kind)
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None  # a label in the report, not a gate
    report = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": jax.device_count(),
    }
    print(
        f"[smoke] device platform={report['platform']} "
        f"kind={report['kind']} count={report['count']} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={libtpu}",
        flush=True,
    )
    return report


def cache_entries(cache_dir: str) -> int:
    """Compiled programs in the persistent cache (jax writes one
    `*-cache` file per entry, plus access-time sidecars)."""
    if not os.path.isdir(cache_dir):
        return 0
    return sum(1 for f in os.listdir(cache_dir) if f.endswith("-cache"))


class CompileLog:
    """Counts this process's compile requests through `jax.monitoring`,
    and how many of them the persistent cache served; the rest went to
    the XLA backend."""

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


def run_stage(name: str, fn, log: CompileLog, cache_dir: str) -> dict:
    """Run one stage — it raises on failure, nothing here catches —
    and print its wall and compile counts."""
    before, t0 = log.snapshot(), time.perf_counter()
    result = fn() or {}
    wall = time.perf_counter() - t0
    after = log.snapshot()
    requests = after["requests"] - before["requests"]
    hits = after["cache_hits"] - before["cache_hits"]
    stats = {
        "wall_s": round(wall, 1),
        "programs_compiled": requests - hits,
        "cache_hits": hits,
        "cache_entries": cache_entries(cache_dir),
    }
    print(
        f"[smoke] stage {name} OK "
        + " ".join(f"{k}={v}" for k, v in {**stats, **result}.items()),
        flush=True,
    )
    return {"stage": name, **stats, **result}


# ------------------------------------------------- main path through the CLI


def smoke_clients() -> int:
    """One client per chip on a host with four or more, else the
    reference trio (folded onto however few devices there are)."""
    import jax

    n = jax.device_count()
    return n if n >= 4 else 3


def run_cli(argv: list) -> None:
    from federated_pytorch_test_tpu.__main__ import main

    print(f"[smoke] main({' '.join(argv)})", flush=True)
    rc = main(argv)
    require(rc == 0, f"main() returned {rc}")


def _series(out_dir: str, name: str) -> dict:
    with open(os.path.join(out_dir, f"{name}.metrics.json")) as f:
        return json.load(f)["series"]


def _cli_leg(out_dir: str, name: str, preset: str, argv: list) -> dict:
    """One `main()` run writing `<name>.metrics.json` + `<name>.jsonl`
    (+ status sidecar) under `out_dir`; returns the recorded series."""
    os.makedirs(out_dir, exist_ok=True)
    run_cli([
        "--preset", preset, "--quiet",
        "--metrics-out", os.path.join(out_dir, f"{name}.metrics.json"),
        "--metrics-stream", os.path.join(out_dir, f"{name}.jsonl"),
        *argv,
    ])
    return _series(out_dir, name)


def check_round_artifacts(
    out_dir: str, name: str, *, rounds: int, nadmm: int, n_clients: int,
    expect_backend: str,
) -> dict:
    """The per-run assertions, from the artifacts `main()` wrote."""
    from federated_pytorch_test_tpu.parallel import (
        largest_feasible_mesh,
        mesh_size,
    )

    s = _series(out_dir, name)

    losses = [r["value"] for r in s["train_loss"]]
    require(losses, "no train_loss recorded")
    require(
        all(math.isfinite(v) for row in losses for v in row),
        f"{name}: non-finite train_loss",
    )
    first = sum(losses[0]) / len(losses[0])
    last = sum(losses[-1]) / len(losses[-1])
    require(
        last < first,
        f"{name}: train_loss did not fall (first {first:.4g}, "
        f"last {last:.4g})",
    )

    accs = s.get("test_accuracy", [])
    require(
        len(accs) == rounds * nadmm,
        f"{name}: {len(accs)} test_accuracy records, expected "
        f"{rounds * nadmm}",
    )
    require(
        all(len(r["value"]) == n_clients for r in accs),
        f"{name}: test_accuracy is not per-client [{n_clients}]",
    )

    # none of Trainer._fused_enabled's per-epoch fallbacks engaged
    phases = {r["value"]["phase"] for r in s["step_time"]}
    require(
        phases == {"fused_round"},
        f"{name}: step_time phases {sorted(phases)}, expected fused_round",
    )
    dispatches = [r["value"] for r in s["dispatch_count"]]
    require(len(dispatches) == rounds, f"{name}: {len(dispatches)} rounds ran")
    require(
        all(d == {"round": 1, "round_init": 1, "total": 2} for d in dispatches),
        f"{name}: dispatches per round {dispatches}",
    )

    with open(os.path.join(out_dir, f"{name}.jsonl.status.json")) as f:
        status = json.load(f)
    prov = status["provenance"]
    require(
        prov["backend"] == expect_backend and status.get("completed"),
        f"{name}: status sidecar says backend={prov['backend']!r} "
        f"completed={status.get('completed')}",
    )

    # every device of the client mesh holds live buffers. The CPU
    # allocator reports no statistics, so this one check is chip-only.
    mesh = mesh_size(largest_feasible_mesh(n_clients))
    mem = [r["value"]["devices"] for r in s["memory"]]
    require(len(mem) == rounds, f"{name}: {len(mem)} memory records")
    if expect_backend == "tpu":
        for devs in mem:
            in_use = [(d or {}).get("bytes_in_use", 0) for d in devs[:mesh]]
            require(
                len(in_use) == mesh and all(b > 0 for b in in_use),
                f"{name}: bytes_in_use per mesh device {in_use}",
            )

    comm = s["comm_summary"][-1]["value"]
    n_params = comm["bytes_full_exchange"] // (
        comm["dtype_bytes"] * comm["n_clients"] * comm["rounds"]
    )
    return {
        "n_params": n_params,
        "mesh": mesh,
        "loss_first": round(first, 4),
        "loss_last": round(last, 4),
        "acc_last": round(sum(accs[-1]["value"]) / n_clients, 4),
        "trainer_programs": sum(r["value"] for r in s["recompile_count"]),
    }


def stage_main_path(
    out_dir: str = OUT_DIR, *, model: str = "resnet18",
    n_clients: int | None = None, batch: int = 32, steps: int = 4,
    expect_backend: str = "tpu",
) -> dict:
    """FedAvg through `main()`: the `fedavg_resnet` preset as shipped
    (the CPU test swaps in `model="net"`), two groups, two exchanges."""
    import numpy as np

    from federated_pytorch_test_tpu.utils import load_checkpoint

    k = n_clients or smoke_clients()
    groups, nadmm = 2, 2
    ckpt = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        _cli_leg(out_dir, "main_path", "fedavg_resnet", [
            "--model", model, "--n-clients", str(k),
            "--batch", str(batch), "--eval-batch", str(batch),
            "--synthetic-n-train", str(k * batch * steps),
            "--synthetic-n-test", str(3 * batch),
            "--nloop", "1", "--nadmm", str(nadmm),
            "--max-groups", str(groups),
            "--save-model", "--checkpoint-dir", ckpt,
        ])
        out = check_round_artifacts(
            out_dir, "main_path", rounds=groups, nadmm=nadmm, n_clients=k,
            expect_backend=expect_backend,
        )
        # after a FedAvg exchange the active group is bit-identical
        # across clients; untrained groups still hold the common-seed
        # init — so the checkpointed [K, N] vector is, whole
        flat = np.asarray(load_checkpoint(ckpt)["flat"])
        require(
            flat.shape == (k, out["n_params"]),
            f"checkpoint flat {flat.shape}, expected {(k, out['n_params'])}",
        )
        require(
            float(np.abs(flat - flat[:1]).max()) == 0.0,
            "client parameters differ after the FedAvg exchanges",
        )
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    if model == "resnet18":
        require(
            out["n_params"] == RESNET18_PARAMS,
            f"ResNet18 ran at {out['n_params']} parameters, not full width",
        )
    return out


def stage_robust(
    out_dir: str = OUT_DIR, *, n_clients: int | None = None,
    expect_backend: str = "tpu",
) -> dict:
    """ADMM (Net, BB rho) + trimmed combiner + quarantine under one
    scale-corrupted client per exchange, through `main()`."""
    import numpy as np

    from federated_pytorch_test_tpu.fault import FaultPlan

    k = n_clients or smoke_clients()
    plan, nadmm = "seed=5,corrupt=1:scale:10", 2
    s = _cli_leg(out_dir, "robust", "admm", [
        "--n-clients", str(k), "--batch", "40", "--eval-batch", "30",
        "--synthetic-n-train", str(k * 80), "--synthetic-n-test", "60",
        "--nloop", "1", "--nadmm", str(nadmm), "--max-groups", "1",
        "--robust-agg", "trimmed", "--robust-f", "1",
        "--quarantine-z", "1.0", "--fault-plan", plan,
    ])
    out = check_round_artifacts(
        out_dir, "robust", rounds=1, nadmm=nadmm, n_clients=k,
        expect_backend=expect_backend,
    )
    # the plan is pure in (seed, cursor): recompute who was corrupted at
    # each exchange and require the quarantine record to name them
    parsed = FaultPlan.parse(plan)
    flagged = {
        (r["nloop"], r["group"], r["nadmm"]): set(r["value"]["clients"])
        for r in s.get("quarantine", [])
    }
    gid = s["dispatch_count"][0]["group"]
    for a in range(nadmm):
        victims = set(
            int(i) for i in np.nonzero(parsed.corruption(k, 0, gid, a)[0])[0]
        )
        require(victims, f"plan corrupted nobody at exchange {a}")
        require(
            victims <= flagged.get((0, gid, a), set()),
            f"exchange {a}: corrupted {sorted(victims)}, quarantine "
            f"flagged {sorted(flagged.get((0, gid, a), ()))}",
        )
    out["quarantined"] = sum(len(v) for v in flagged.values())
    return out


def stage_pallas_round(
    out_dir: str = OUT_DIR, *, n_clients: int | None = None,
    expect_backend: str = "tpu",
) -> dict:
    """One `fedavg_resnet` round with the Pallas L-BFGS direction."""
    k = n_clients or smoke_clients()
    _cli_leg(out_dir, "pallas_round", "fedavg_resnet", [
        "--n-clients", str(k), "--batch", "32", "--eval-batch", "32",
        "--synthetic-n-train", str(k * 32 * 4), "--synthetic-n-test", "96",
        "--nloop", "1", "--nadmm", "1", "--max-groups", "1",
        "--lbfgs-direction", "pallas",
    ])
    return check_round_artifacts(
        out_dir, "pallas_round", rounds=1, nadmm=1, n_clients=k,
        expect_backend=expect_backend,
    )


# ------------------------------------------------------------- the kernels


def _close(got, ref, rtol: float, atol: float, what: str) -> float:
    """`|got - ref| <= atol * max|ref| + rtol * |ref|` elementwise.

    The CPU tests these tolerances come from compare against references
    of magnitude ~1, where an absolute `atol` and this scale-relative
    one coincide; at engine shapes the outputs are larger (a K=512
    contraction of unit normals reaches ~100) and an f32 result carries
    rounding in proportion to its scale, so `atol` is taken relative to
    the reference's largest magnitude."""
    import numpy as np

    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    require(np.isfinite(got).all(), f"{what}: non-finite kernel output")
    err = np.abs(got - ref)
    scale = float(np.abs(ref).max())
    ok = bool((err <= atol * scale + rtol * np.abs(ref)).all())
    require(
        ok,
        f"{what}: max abs err {err.max():.3e} (ref max {scale:.3e}) "
        f"outside rtol={rtol} atol={atol}*max",
    )
    return float(err.max())


def check_flash(seq: int = 2048, heads: int = 4, dim: int = 64) -> dict:
    """`flash_attention` fwd+bwd vs `dense_attention` — f32 at the 512
    tiles, bf16 at its tile defaults (causal upgrades to 1024), causal
    and not — and `flash_block` with traced offsets. Tolerances are
    tests/test_flash.py's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from federated_pytorch_test_tpu.ops.flash_attention import (
        flash_attention,
        flash_block,
    )
    from federated_pytorch_test_tpu.parallel import dense_attention

    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.normal(size=(1, seq, heads, dim)), jnp.float32)
        for _ in range(3)
    )

    def grads(attn, args, **kw):
        return jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(
                attn(q, k, v, **kw).astype(jnp.float32) ** 2
            ),
            argnums=(0, 1, 2),
        ))(*args)

    errs = {}
    # the f32 dense reference must itself be pinned to full-f32 passes:
    # at default precision XLA lowers f32 einsums to single bf16 MXU
    # passes and the difference would measure the reference
    with jax.default_matmul_precision("highest"):
        for causal in (False, True):
            tag = "causal" if causal else "full"
            ref = jax.jit(
                lambda q, k, v: dense_attention(q, k, v, causal=causal)
            )(q, k, v)
            gref = grads(dense_attention, (q, k, v), causal=causal)

            out = jax.jit(
                lambda q, k, v: flash_attention(q, k, v, causal=causal)
            )(q, k, v)
            errs[f"f32_{tag}_fwd"] = _close(
                out, ref, 3e-5, 3e-6, f"flash f32 {tag} fwd"
            )
            errs[f"f32_{tag}_bwd"] = max(
                _close(g, gr, 1e-3, 1e-4, f"flash f32 {tag} d{n}")
                for g, gr, n in zip(
                    grads(flash_attention, (q, k, v), causal=causal),
                    gref, "qkv",
                )
            )

            # bf16: the reference is the same f32 dense attention on the
            # bf16-ROUNDED inputs, so the comparison holds the kernel's
            # own rounding (bf16 probability tiles at 'default'
            # precision) and not the input quantization — at this
            # shape the latter alone spends the whole gradient band
            h = tuple(x.astype(jnp.bfloat16) for x in (q, k, v))
            hr = tuple(x.astype(jnp.float32) for x in h)
            ref = jax.jit(
                lambda q, k, v: dense_attention(q, k, v, causal=causal)
            )(*hr)
            gref = grads(dense_attention, hr, causal=causal)
            out = jax.jit(lambda q, k, v: flash_attention(
                q, k, v, causal=causal, precision="default"
            ))(*h)
            require(out.dtype == jnp.bfloat16, "flash bf16 output dtype")
            errs[f"bf16_{tag}_fwd"] = _close(
                out, ref, 0.06, 0.03, f"flash bf16 {tag} fwd"
            )
            worst = 0.0
            for g, gr, n in zip(
                grads(flash_attention, h, causal=causal, precision="default"),
                gref, "qkv",
            ):
                require(g.dtype == jnp.bfloat16, f"flash bf16 d{n} dtype")
                gr = np.asarray(gr)
                rel = float((
                    np.abs(np.asarray(g, np.float32) - gr)
                    / np.maximum(np.abs(gr), 1.0)
                ).max())
                require(
                    rel < 0.08, f"flash bf16 {tag} d{n} rel err {rel:.3e}"
                )
                worst = max(worst, rel)
            errs[f"bf16_{tag}_bwd_rel"] = worst

        # flash_block under jit with TRACED offsets: fold two K/V halves
        # for the second half of the rows == full causal attention
        half = seq // 2
        ref = jax.jit(lambda q, k, v: dense_attention(q, k, v, causal=True))(
            q, k, v
        )

        @jax.jit
        def merged(q, k, v, q_off, k_offs):
            parts = [
                flash_block(
                    q[:, half:], k[:, half * j: half * (j + 1)],
                    v[:, half * j: half * (j + 1)], q_off, k_offs[j],
                    causal=True,
                )
                for j in (0, 1)
            ]
            m = jnp.maximum(parts[0][1], parts[1][1])
            w0, w1 = (jnp.exp(lse - m) for _, lse in parts)
            out = (
                parts[0][0] * w0[..., None] + parts[1][0] * w1[..., None]
            ) / (w0 + w1)[..., None]
            return jnp.transpose(out, (0, 2, 1, 3))

        out = merged(q, k, v, jnp.int32(half), jnp.asarray([0, half], jnp.int32))
        # 2e-5 absolute is the bound the on-chip check this stage
        # replaces (removed in PR 21) asserted: the fold
        # goes through the kernel's in-VMEM log and an exp of the lse,
        # and on the chip that path is ~6x less exact than the
        # triangular kernel (1.1e-5 vs 1.8e-6 against a float64
        # reference, PR 21) — outside tests/test_flash.py's CPU band
        err = float(jnp.abs(out - ref[:, half:]).max())
        require(
            math.isfinite(err) and err < 2e-5,
            f"flash_block offset merge: max abs err {err:.3e} >= 2e-5",
        )
        errs["block_merge"] = err
        # a K/V block wholly in the rows' future: exact zeros, -BIG lse
        o, lse = jax.jit(lambda q, k, v, qo, ko: flash_block(
            q[:, :half], k[:, half:], v[:, half:], qo, ko, causal=True
        ))(q, k, v, jnp.int32(0), jnp.int32(half))
        require(
            float(jnp.abs(o).max()) == 0.0 and float(lse.max()) <= -1e29,
            "flash_block future block is not (0, -BIG)",
        )
    return {k: f"{v:.1e}" for k, v in errs.items()}


def check_compact(
    sizes=(RESNET18_LARGEST_GROUP, 1_000_003), m: int = 10, k: int = 3
) -> dict:
    """`compact_direction_pallas` vs `optim.compact.compact_direction`
    at m=10: the flagship's largest group and an odd N (masked tail
    grid step, zero lanes in the last tile), plain and under `jax.vmap`
    over K clients — the form the engine uses — on `[m, R, 128]`
    histories (optim/history.py). Relative tolerance is
    tests/test_ops.py's. Also times one vmapped call of each backend at
    each size, warm (`ms_*`: host clock around `block_until_ready`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from federated_pytorch_test_tpu.ops import compact_direction_pallas
    from federated_pytorch_test_tpu.optim.compact import compact_direction
    from federated_pytorch_test_tpu.optim.history import history_of

    def history(key, n):
        # y ~ B s with B SPD: a well-conditioned compact form
        ks, kd, kn, kg = jax.random.split(key, 4)
        s = 0.1 * jax.random.normal(ks, (m, n), jnp.float32)
        d = jax.random.uniform(kd, (n,), jnp.float32, 0.5, 2.0)
        y = s * d + 0.01 * jax.random.normal(kn, (m, n), jnp.float32)
        return (
            history_of(s), history_of(y),
            jax.random.normal(kg, (n,), jnp.float32),
        )

    def ms(fn, *args, reps: int = 5) -> str:
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        return f"{(time.perf_counter() - t0) / reps * 1e3:.2f}"

    errs = {}
    for n in sizes:
        keys = jax.random.split(jax.random.PRNGKey(n % 1000), k)
        s, y, g = jax.vmap(lambda kk: history(kk, n))(keys)
        counts = jnp.asarray([m, 4, 0][:k] + [m] * max(0, k - 3), jnp.int32)
        hd = jnp.asarray([0.7, 1.0, 2.0][:k] + [1.0] * max(0, k - 3), jnp.float32)
        compact = jax.jit(jax.vmap(compact_direction))
        pallas = jax.jit(jax.vmap(compact_direction_pallas))
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(compact(g, s, y, counts, hd))
            errs[f"ms_compact_{n}"] = ms(compact, g, s, y, counts, hd)
        pal = np.asarray(pallas(g, s, y, counts, hd))
        errs[f"ms_pallas_{n}"] = ms(pallas, g, s, y, counts, hd)
        one = np.asarray(jax.jit(compact_direction_pallas)(
            g[0], s[0], y[0], counts[0], hd[0]
        ))
        for tag, got, want in (
            ("vmap", pal, ref), ("plain", one, ref[0]),
        ):
            require(np.isfinite(got).all(), f"compact {tag} N={n}: non-finite")
            scale = float(np.abs(want).max()) + 1e-30
            err = float(np.abs(got - want).max()) / scale
            require(
                err <= 1e-5,
                f"compact direction {tag} N={n}: rel err {err:.3e} > 1e-5",
            )
            errs[f"{tag}_{n}"] = f"{err:.1e}"
    return errs


def check_grouped() -> dict:
    """`grouped_matmul(backend='pallas')` vs the einsum backend at the
    widened fold's shapes: the square block, an M off the 8-sublane
    grid, and the N=10 classifier head. Tolerance is
    tests/test_widened.py's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from federated_pytorch_test_tpu.ops import grouped_matmul

    rng = np.random.default_rng(1)
    errs = {}
    for g, m, k, n in ((3, 128, 512, 512), (3, 100, 512, 512), (3, 128, 512, 10)):
        lhs = jnp.asarray(rng.normal(size=(g, m, k)), jnp.float32)
        rhs = jnp.asarray(rng.normal(size=(g, k, n)), jnp.float32)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(grouped_matmul)(lhs, rhs)
        out = jax.jit(lambda a, b: grouped_matmul(a, b, backend="pallas"))(
            lhs, rhs
        )
        errs[f"{m}x{k}x{n}"] = "%.1e" % _close(
            out, ref, 1e-6, 1e-5, f"grouped_matmul [{g},{m},{k}]x[{g},{k},{n}]"
        )
    return errs


def stage_kernels() -> dict:
    from federated_pytorch_test_tpu.ops import _interpret

    require(
        not _interpret(),
        "Pallas kernels would run in interpret mode on this backend",
    )
    out = {}
    for name, check in (
        ("flash", check_flash), ("compact", check_compact),
        ("grouped", check_grouped),
    ):
        t0 = time.perf_counter()
        errs = check()
        print(
            f"[smoke] kernel {name} compiled and matched in "
            f"{time.perf_counter() - t0:.1f}s: {errs}",
            flush=True,
        )
        out[name] = "ok"
    return out


# -------------------------------------------------------------------- main


def main() -> int:
    device = device_report()  # exits here on any backend but tpu

    from federated_pytorch_test_tpu.data.native import get_lib
    from federated_pytorch_test_tpu.utils import enable_compile_cache

    cache_dir = enable_compile_cache()
    print(
        f"[smoke] compile cache {cache_dir} "
        f"entries_before={cache_entries(cache_dir)}; data loader: "
        f"{'native' if get_lib() is not None else 'numpy'}; "
        f"clients={smoke_clients()}",
        flush=True,
    )
    log = CompileLog()
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    t0 = time.perf_counter()
    stages = [
        run_stage(name, fn, log, cache_dir)
        for name, fn in (
            ("main_path", stage_main_path),
            ("robust", stage_robust),
            ("kernels", stage_kernels),
            ("pallas_round", stage_pallas_round),
        )
    ]
    summary = {
        "device": device,
        "wall_s": round(time.perf_counter() - t0, 1),
        "compile_cache": cache_dir,
        "cache_entries_after": cache_entries(cache_dir),
        "stages": stages,
    }
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(
        f"[smoke] all stages OK in {summary['wall_s']}s; "
        f"cache entries_after={summary['cache_entries_after']}",
        flush=True,
    )
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
