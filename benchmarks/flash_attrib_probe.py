"""One-off attribution probe for the D=64 flash ceiling (round 5).

Answers two questions on the real chip before committing to a packed-head
kernel design:

1. Does the MXU pad sub-128 contraction/output dims temporally? Timed
   bf16 matmul chains [M,K]x[K,N] at K in {64, 128, 256} and N in
   {64, 128} — if time(K=64) ~= time(K=128), the D=64 score dot wastes
   half the array, as BASELINE.md's constant-width sweep implied.

2. Where does the flash fwd tile step actually spend its time? Three
   kernels on the SAME grid / BlockSpecs / tile shapes (S=4096, D=64,
   causal triangular grid, tile 512):
     full    — the real forward (matmuls + online softmax)
     mmonly  — matmuls only (o += (q kT) v, no max/exp/sum)
     dmaonly — tile copy only (no MXU, no VPU beyond a vector add)
   full - mmonly ~= VPU softmax cost; mmonly - dmaonly ~= MXU cost;
   dmaonly ~= DMA + grid overhead. Writes flash_attrib_probe.json.
"""

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import importlib

# ops/__init__ re-exports the flash_attention FUNCTION under the same
# name, shadowing the submodule attribute `import ... as` would resolve
fa = importlib.import_module("federated_pytorch_test_tpu.ops.flash_attention")

S = 4096
D = 64
B, H = 2, 8
BQ = 512
# every measurement loops enough inner steps inside ONE jitted call that
# the per-call dispatch floor is a small share of the total, and
# subtracts a measured floor estimate
INNER_TILE = 256
INNER_MM = 16384
REPS = 6


def floor_estimate():
    from tpu_timing import dispatch_floor  # single copy of the protocol

    return dispatch_floor()


def best_of(fn, inner, floor, *args):
    float(fn(*args))  # compile + warm
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        float(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return max(best - floor, 0.0) / inner


def matmul_chain(m, k, n, floor):
    """Per-step time of a dependent bf16 [m,k]x[k,n] matmul chain."""
    a = jnp.asarray(np.random.default_rng(0).normal(size=(m, k)), jnp.bfloat16)
    b = jnp.asarray(np.random.default_rng(1).normal(size=(k, n)), jnp.bfloat16)

    @jax.jit
    def step(a, b):
        def body(i, acc):
            x = jax.lax.dot_general(
                a * (1 + i.astype(jnp.bfloat16) * jnp.bfloat16(1e-3)), b,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return acc + jnp.sum(x * x)

        return jax.lax.fori_loop(0, INNER_MM, body, jnp.float32(0))

    return best_of(step, INNER_MM, floor, a, b)


# ---------------------------------------------------------------- tile probes


def _probe_kernel(itab, jtab, q_ref, k_ref, v_ref, o_ref, o_acc, m_acc,
                  l_acc, *, mode: str, bq: int):
    p_id = pl.program_id(1)
    i = itab[p_id]
    j = jtab[p_id]

    @pl.when(j == 0)
    def _():
        o_acc[:] = jnp.zeros_like(o_acc)
        m_acc[:] = jnp.full_like(m_acc, fa._NEG_BIG)
        l_acc[:] = jnp.zeros_like(l_acc)

    if mode == "dmaonly":
        o_acc[:] = o_acc[:] + q_ref[0] + k_ref[0] + v_ref[0]
    elif mode == "mmonly":
        sc = fa._dot(q_ref[0], k_ref[0], fa._LL, None)
        o_acc[:] = o_acc[:] + fa._dot(sc, v_ref[0], fa._LF, None)
    elif mode in ("full", "diagmask", "exp2", "slicewrite", "combo",
                  "combo_bf16"):
        sc = fa._dot(q_ref[0], k_ref[0], fa._LL, None)
        if mode in ("diagmask", "combo", "combo_bf16"):
            # off-diagonal tiles (j < i) are entirely sub-diagonal: the
            # causal mask is the identity there — only the j == i tile
            # needs the iota/compare/where pass
            sc = jax.lax.cond(
                j == i,
                lambda s: fa._causal_mask(s, i * bq, j * bq),
                lambda s: s,
                sc,
            )
        else:
            sc = fa._causal_mask(sc, i * bq, j * bq)
        m, l, o = m_acc[:, 0], l_acc[:, 0], o_acc[:]
        m_new = jnp.maximum(m, jnp.max(sc, axis=1))
        if mode in ("exp2", "combo", "combo_bf16"):
            # scores pre-scaled by log2(e) would fold the base change into
            # the q scale; the probe approximates the cost with exp2 direct
            p = jnp.exp2(sc - m_new[:, None])
        else:
            p = jnp.exp(sc - m_new[:, None])
        if mode == "combo_bf16":
            p16 = p.astype(jnp.bfloat16)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=1)
            o_new = o * corr[:, None] + fa._dot(p16, v_ref[0], fa._LF, None)
        else:
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=1)
            o_new = o * corr[:, None] + fa._dot(p, v_ref[0], fa._LF, None)
        o_acc[:] = o_new
        if mode in ("slicewrite", "combo", "combo_bf16"):
            m_acc[:, 0:1] = m_new[:, None]
            l_acc[:, 0:1] = l_new[:, None]
        else:
            m_acc[:] = jnp.broadcast_to(m_new[:, None], m_acc.shape)
            l_acc[:] = jnp.broadcast_to(l_new[:, None], l_acc.shape)
    elif mode == "bf16p":
        sc = fa._causal_mask(
            fa._dot(q_ref[0], k_ref[0], fa._LL, None), i * bq, j * bq
        )
        m, l, o = m_acc[:, 0], l_acc[:, 0], o_acc[:]
        m_new = jnp.maximum(m, jnp.max(sc, axis=1))
        p = jnp.exp(sc - m_new[:, None]).astype(jnp.bfloat16)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p.astype(jnp.float32), axis=1)
        o_new = o * corr[:, None] + fa._dot(p, v_ref[0], fa._LF, None)
        o_acc[:] = o_new
        m_acc[:] = jnp.broadcast_to(m_new[:, None], m_acc.shape)
        l_acc[:] = jnp.broadcast_to(l_new[:, None], l_acc.shape)
    else:
        raise ValueError(mode)

    @pl.when(j == i)
    def _():
        o_ref[0] = o_acc[:]


def tile_probe(mode: str, floor: float):
    bh = B * H
    nq = S // BQ
    itab, jtab = fa._tri_tables_qmajor(nq)
    spec = pl.BlockSpec((1, BQ, D), lambda b, p, it, jt: (b, it[p], 0))
    kvspec = pl.BlockSpec((1, BQ, D), lambda b, p, it, jt: (b, jt[p], 0))
    call = pl.pallas_call(
        functools.partial(_probe_kernel, mode=mode, bq=BQ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, itab.shape[0]),
            in_specs=[spec, kvspec, kvspec],
            out_specs=spec,
            scratch_shapes=[
                pltpu.VMEM((BQ, D), jnp.float32),
                pltpu.VMEM((BQ, 128), jnp.float32),
                pltpu.VMEM((BQ, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, S, D), jnp.float32),
    )
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(bh, S, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(bh, S, D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(bh, S, D)), jnp.bfloat16)
    it, jt = jnp.asarray(itab), jnp.asarray(jtab)

    @jax.jit
    def step(q, k, v):
        def body(i, acc):
            qi = q * (1 + i.astype(jnp.bfloat16) * jnp.bfloat16(1e-3))
            o = call(it, jt, qi, k, v)
            return acc + jnp.sum(o * o)

        return jax.lax.fori_loop(0, INNER_TILE, body, jnp.float32(0))

    return best_of(step, INNER_TILE, floor, q, k, v)


def main():
    floor = floor_estimate()
    print(f"[floor] {floor*1e3:.1f} ms per call", flush=True)
    out = {"device": jax.devices()[0].device_kind, "S": S, "D": D,
           "tile": BQ, "inner_tile": INNER_TILE, "inner_mm": INNER_MM,
           "dispatch_floor_s": round(floor, 4)}
    mm = {}
    for m, k, n in [(4096, 64, 4096), (4096, 128, 4096), (4096, 256, 4096),
                    (4096, 512, 64), (4096, 512, 128)]:
        t = matmul_chain(m, k, n, floor)
        useful = 2 * m * k * n
        mm[f"{m}x{k}x{n}"] = {
            "step_s": round(t, 8),
            "useful_tflops": round(useful / t / 1e12, 2),
        }
        print(f"[mm] {m}x{k}x{n}: {t*1e6:.0f} us  "
              f"{useful / t / 1e12:.1f} TF/s useful", flush=True)
    out["matmul_chains"] = mm

    tiles = {}
    for mode in ("dmaonly", "mmonly", "full", "diagmask", "exp2",
                 "slicewrite", "bf16p", "combo", "combo_bf16"):
        t = tile_probe(mode, floor)
        tiles[mode] = round(t, 6)
        print(f"[tile] {mode}: {t*1e3:.3f} ms/step", flush=True)
    out["tile_modes_fwd_s"] = tiles
    out["attribution"] = {
        "dma_plus_grid_s": tiles["dmaonly"],
        "mxu_s": round(tiles["mmonly"] - tiles["dmaonly"], 6),
        "vpu_softmax_s": round(tiles["full"] - tiles["mmonly"], 6),
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "flash_attrib_probe.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out["attribution"]))


if __name__ == "__main__":
    main()
