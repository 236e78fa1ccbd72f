"""Attribute flagship epoch time to its components, by measurement.

BASELINE.md argues the flagship (3-client ResNet18 FedAvg) plateaus at
~4.5k samples/s because the inner solver's sequential dependency chain —
line-search probes, direction algebra, curvature guards between every
forward — cannot be hidden by batch size. Round-3 VERDICT weak #5:
that attribution was a hypothesis. This benchmark MEASURES it.

Method: with the same scalar-fetch timing barrier bench.py uses, time
separately, best-of-3, at batch 512 and 2048 (f32, group = the shuffled
order's first block):

  epoch_step   one step of the jitted sharded epoch program (the real
               thing: L-BFGS step + metrics, S steps scanned, / S)
  grad_eval    one vmapped value_and_grad of the SAME group loss at the
               same batch (what each inner iteration pays for its
               closure gradient)
  probe_eval   one vmapped forward-only loss (what each line-search
               probe pays)
  machinery    one full lbfgs_step on a dummy quadratic loss of the same
               group dimension (direction algebra, curvature updates,
               line-search control flow — everything BUT the model)

and read the solver's own counter (aux.func_evals) for how many
closure-equivalent evaluations one step actually performs. The modeled
step time is then

  modeled = n_grad * grad_eval + n_probe * probe_eval + machinery

with n_grad = max_iter re-evals and n_probe = func_evals - n_grad, and
`unattributed = epoch_step - modeled` is dispatch/scan overhead the
components cannot see. Writes epoch_attribution.json.

Run: python benchmarks/epoch_attribution.py
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _best_of(fn, n=3):
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def measure(batch: int, steps: int) -> dict:
    import jax
    import jax.numpy as jnp

    from federated_pytorch_test_tpu.data import synthetic_cifar
    from federated_pytorch_test_tpu.engine import Trainer, get_preset
    from federated_pytorch_test_tpu.engine.steps import _data_loss
    from federated_pytorch_test_tpu.optim import (
        LBFGSConfig,
        lbfgs_init,
        lbfgs_step,
    )

    k = 3
    src = synthetic_cifar(n_train=k * batch * max(steps, 4), n_test=64)
    cfg = get_preset(
        "fedavg_resnet",
        n_clients=k,
        batch=batch,
        check_results=False,
        max_scan_steps=None,
    )
    tr = Trainer(cfg, verbose=False, source=src)
    gid = tr.group_order[0]
    ctx = tr._ctx(gid)
    epoch_fn, _, init_fn = tr._fns(gid)
    lstate, y, z, rho, extra = init_fn(tr.flat)
    idx = tr._epoch_indices(0, gid, 0, 0)[:steps]
    # the epoch program donates (flat, lstate, stats); keep copies for
    # the component measurements below, which run after the epoch timing
    flat_snap = jnp.array(tr.flat)
    stats_snap = jax.tree.map(jnp.array, tr.stats)

    # ---- the real epoch program (S steps scanned), per-step time ----
    # epoch_fn donates (flat, lstate, stats): thread them through calls
    carry = {"flat": tr.flat, "lstate": lstate, "stats": tr.stats}

    def run_epoch():
        flat2, lstate2, stats2, _losses = epoch_fn(
            carry["flat"], carry["lstate"], carry["stats"],
            tr.shard_imgs, tr.shard_labels, idx, tr.mean, tr.std, y, z, rho,
        )
        carry.update(flat=flat2, lstate=lstate2, stats=stats2)
        float(jnp.sum(flat2[:, 0]))  # scalar fetch = completion barrier

    run_epoch()  # compile + warmup
    t_epoch_step = _best_of(run_epoch) / steps
    # the solver's own counter: closure-equivalent evals per step,
    # cumulative over 1 warmup + 3 timed epochs
    fe = np.asarray(
        jax.tree.leaves(carry["lstate"].func_evals)[0]
    ).reshape(-1)
    evals_per_step = float(fe.mean()) / (4 * steps)

    # ---- one vmapped grad eval / probe eval of the same group loss ----
    imgs0 = tr.shard_imgs[:, : batch]
    labs0 = tr.shard_labels[:, : batch]
    flat_c, stats_c = flat_snap, stats_snap

    def group_loss(x_k, flat_k, stats_k, img_k, lab_k, mean_k, std_k):
        from federated_pytorch_test_tpu.data import normalize

        full = ctx.partition.insert(flat_k, gid, x_k)
        loss, _ = _data_loss(
            ctx, full, stats_k, normalize(img_k, mean_k, std_k), lab_k
        )
        return loss

    x0 = jax.vmap(lambda f: ctx.partition.extract(f, gid))(flat_c)

    # each component is measured as ONE jitted program of R dependent
    # repeats (the tiny carry update forces sequential execution), then
    # divided by R — the per-call dispatch+fetch cost otherwise rides
    # on every single component call and the standalone numbers
    # overstate the epoch's true per-eval cost
    R = 8
    from jax import lax

    def vg_chain(x, flat_k, stats_k, img_k, lab_k, mean_k, std_k):
        def body(c, _):
            l, g = jax.value_and_grad(group_loss)(
                c, flat_k, stats_k, img_k, lab_k, mean_k, std_k
            )
            return c + 1e-12 * g, l

        xf, ls = lax.scan(body, x, None, length=R)
        return xf, ls

    def fwd_chain(x, flat_k, stats_k, img_k, lab_k, mean_k, std_k):
        def body(c, _):
            l = group_loss(c, flat_k, stats_k, img_k, lab_k, mean_k, std_k)
            return c * (1.0 + 1e-12 * l), l

        xf, ls = lax.scan(body, x, None, length=R)
        return xf, ls

    vg = jax.jit(jax.vmap(vg_chain))
    fwd = jax.jit(jax.vmap(fwd_chain))

    def run_vg():
        xf, l = vg(x0, flat_c, stats_c, imgs0, labs0, tr.mean, tr.std)
        float(jnp.sum(xf[:, 0]))

    def run_fwd():
        xf, l = fwd(x0, flat_c, stats_c, imgs0, labs0, tr.mean, tr.std)
        float(jnp.sum(xf[:, 0]))

    run_vg()
    t_grad = _best_of(run_vg) / R
    run_fwd()
    t_fwd = _best_of(run_fwd) / R

    # ---- solver machinery on a dummy quadratic of the group size ----
    n = int(x0.shape[1])
    lcfg = LBFGSConfig(
        max_iter=cfg.lbfgs_max_iter,
        history_size=cfg.lbfgs_history,
        line_search=True,
        batch_mode=True,
        direction=cfg.lbfgs_direction,
    )

    def quad(v):
        return 0.5 * jnp.sum(v * v)

    def machinery_chain(xs, ss):
        def one(x, s):
            x_init = x

            def body(carry, _):
                xx, sst = carry
                x2, s2, _ = lbfgs_step(quad, xx, sst, lcfg)
                # re-inflate: on the plain quadratic the solver converges
                # in one repeat and later repeats would early-exit on a
                # ~zero gradient, understating the algebra cost; the
                # displacement keeps the gradient O(|x_init|) every
                # repeat while the carried state keeps real curvature
                # history flowing through the direction computation
                return (x2 + x_init, s2), None

            (xf, _), _ = lax.scan(body, (x, s), None, length=R)
            return xf

        return jax.vmap(one)(xs, ss)

    ms = jax.jit(machinery_chain)
    st0 = jax.vmap(lambda x: lbfgs_init(x, lcfg))(x0)
    xs = ms(x0, st0)
    float(jnp.sum(xs[:, 0]))

    def run_mach():
        a = ms(x0, st0)
        float(jnp.sum(a[:, 0]))

    t_mach = _best_of(run_mach) / R

    n_grad = float(cfg.lbfgs_max_iter)
    n_probe = max(evals_per_step - n_grad, 0.0)
    modeled = n_grad * t_grad + n_probe * t_fwd + t_mach
    return {
        "batch": batch,
        "steps_timed": steps,
        "group_id": int(gid),
        "group_dim": n,
        "epoch_step_ms": round(1e3 * t_epoch_step, 2),
        "grad_eval_ms": round(1e3 * t_grad, 2),
        "probe_eval_ms": round(1e3 * t_fwd, 2),
        "machinery_ms": round(1e3 * t_mach, 2),
        "evals_per_step": round(evals_per_step, 2),
        "n_grad": n_grad,
        "n_probe": round(n_probe, 2),
        "modeled_step_ms": round(1e3 * modeled, 2),
        "unattributed_ms": round(1e3 * (t_epoch_step - modeled), 2),
        "modeled_fraction": round(modeled / t_epoch_step, 3),
    }


def main() -> None:
    import jax

    assert jax.default_backend() == "tpu", jax.default_backend()
    rows = [measure(512, 4), measure(2048, 2)]
    out = {
        "workload": "fedavg_resnet flagship epoch, f32, 3 clients, "
        "first shuffled group",
        "method": "component timings as 8-repeat dependent scans with "
        "scalar-fetch barriers, best-of-3 / 8 (amortizes per-call "
        "dispatch cost exactly as the scanned epoch does); evals from "
        "the solver's own func_evals counter",
        "rows": rows,
    }
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "epoch_attribution.json"
    )
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
