"""bf16 tile sweep for the flash kernels at long sequence lengths.

Round-3 VERDICT item 7: flash sustains ~8% of bf16 peak at S=2k — tune
bf16 tile shapes at S=4k/8k and report the kernel-only roofline per
shape, or document the measured ceiling.

This probe times the causal fwd+bwd step of `ops.flash_attention` at
S=4096 and S=8192 across square VMEM tile sizes (causal pairs equal
tiles, so rectangular shapes collapse to the min — only squares are
distinct), in BOTH input regimes:

  f32-in   f32 q/k/v, 'default' precision (single bf16 MXU passes —
           what the engine's compute_dtype=float32 path gets)
  bf16-in  bf16 q/k/v end-to-end (half the HBM traffic on every tile
           load; softmax statistics and accumulators stay f32 inside
           the kernel) — the long-context training configuration.

Per row: achieved TFLOP/s against the analytical 7*B*H*S^2*D fwd+bwd
count (same math both regimes, so rows are comparable) and % of the
chip's bf16 peak — the kernel-only roofline. Timing uses the shared
harness (tpu_timing.py: inner-loop amortization, distinct inputs,
scalar-fetch barrier, best-of-N). Writes flash_bf16_tiles.json
with the per-shape winner and updates nothing automatically — if a
non-default tile wins decisively, change `_BQ`/`_BK` in
ops/flash_attention.py and record it here.

Run: python benchmarks/flash_bf16_tiles.py
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from bench import _peaks
from federated_pytorch_test_tpu.ops.flash_attention import flash_attention
from tpu_timing import dispatch_floor, make_fwd_bwd_step, timed

B, H, D = 2, 8, 64
LENGTHS = (4096, 8192)
SQUARE_TILES = (128, 256, 512, 1024)

# protocol v2 (round 5): inner-step counts sized so one jitted call runs
# ~1 s of kernel work and the measured per-call dispatch floor is
# subtracted. Rounds 3-4 ran inner=16 WITHOUT floor subtraction, so a
# ~5 ms kernel measured as ~11 ms — those rows understate the kernel by
# up to ~2x and are not comparable with v2 rows.
PROTOCOL = "v2: floor-subtracted, ~1s of work per call (round 5)"


def attn_flops(s: int) -> float:
    return 7.0 * B * H * float(s) * s * D  # causal fwd+bwd (long_context_tpu)


def main():
    assert jax.default_backend() == "tpu", jax.default_backend()
    rng = np.random.RandomState(0)
    reps = 3
    peak_tflops, _ = _peaks(jax.devices()[0].device_kind)
    w = jnp.ones((1, 128, 1, 64), jnp.float32)
    float(flash_attention(w, w, w, causal=True).sum())

    floor = dispatch_floor()
    out = {
        "workload": f"causal flash fwd+bwd, B={B} H={H} D={D}; "
        "kernel-only roofline vs bf16 peak",
        "device": str(jax.devices()[0].device_kind),
        "peak_tflops_bf16": peak_tflops,
        "protocol": PROTOCOL,
        "dispatch_floor_s": round(floor, 4),
        "rows": [],
    }
    for s in LENGTHS:
        # ~1 s of kernel work per call, assuming ~40 TF/s (measured
        # round-5 kernel class) — overshooting just lengthens the run
        flops = attn_flops(s)
        inner = max(16, int(40e12 * 1.0 / flops))
        row = {"seq_len": s, "inner_steps": inner, "regimes": {}}
        for regime, dtype in (("f32_in", jnp.float32), ("bf16_in", jnp.bfloat16)):
            qs, ks, vs = (
                [jnp.asarray(rng.randn(B, s, H, D), dtype)
                 for _ in range(reps + 1)]
                for _ in range(3)
            )
            float(sum(x[0, 0, 0, 0].astype(jnp.float32) for x in qs + ks + vs))
            tiles = {}
            best_tile, best_t = None, float("inf")
            for bt in SQUARE_TILES:
                if bt > s:
                    continue

                def attn(q, k, v, causal=True, _bt=bt):
                    return flash_attention(
                        q, k, v, causal=causal, precision="default",
                        block_q=_bt, block_k=_bt,
                    )

                try:
                    t = timed(
                        make_fwd_bwd_step(attn, "default", inner),
                        qs, ks, vs, reps, inner, floor_s=floor,
                    )
                except Exception as e:  # a tile too big for VMEM etc.
                    tiles[str(bt)] = {"error": f"{type(e).__name__}: {e}"[:120]}
                    continue
                tf = flops / t / 1e12
                tiles[str(bt)] = {
                    "step_s": round(t, 5),
                    "achieved_tflops": round(tf, 2),
                    "pct_peak": round(100.0 * tf / peak_tflops, 1),
                }
                if t < best_t:
                    best_tile, best_t = bt, t
            row["regimes"][regime] = {
                "tiles": tiles,
                "best_tile": best_tile,
                "best_achieved_tflops": round(flops / best_t / 1e12, 2),
                "best_pct_peak": round(100.0 * flops / best_t / 1e12 / peak_tflops, 1),
            }
            print(json.dumps({"seq_len": s, "regime": regime,
                              "best": row["regimes"][regime]["best_tile"],
                              "pct_peak": row["regimes"][regime]["best_pct_peak"]}),
                  flush=True)
        out["rows"].append(row)

    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "flash_bf16_tiles.json"
    )
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
